"""The serving cells' check after the window, at the rehearsal size on the
CPU. (1) The control: the reference itself, with every weight matrix
rounded to float8 and back, put in the program's place, comes out NOT
correct where the program comes out correct. (2) A run driven end to end
(all of `run.py` but its look for a chip) with the timed path broken
underneath, a token altered where the decode dispatch hands it over, comes
out with `correct` false. The limits the chip's cells run under are set
from chip readings (PERF.md section 4); the rehearsal's limit is the
configuration's `rehearsal.check`. Outside tier-1: `pytest benchmark/tests`."""

import json
import os
import sys
import time
import types

import numpy as np
import pytest

from harness import cells
from harness import spans as spans_lib
from harness import traffic as traffic_lib
from harness.compiles import CompileCounter

# The rehearsal's 2 layers of 64 over 257 tokens are too small for float8
# weights to move a first place often (the control read 0.0 on one seed in
# six), so the control is held at 4 layers of 128 over 4,099 tokens: over
# six seeds x 348 served tokens the program's widest gap read 0 to 0.0028
# and the control's 0.035 to 0.099 (CPU, PR 27); the rehearsal's limit,
# 0.01, lies 3.6 times over the one and 3.5 times under the other.
CONTROL_SIZE = {"model": {"n_layer": 4, "n_embd": 128, "vocab_size": 4099}}

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")


@pytest.fixture(scope="module")
def control():
    return cells.load_module(os.path.join(
        BENCH, "tools", "serve_check_control.py"), "serve_check_control")


@pytest.fixture(scope="module")
def built():
    """One tiny engine, the checked warm-up behind it."""
    cell = cells.Cell("serve-gpt2xl-chat")
    cell.use_rehearsal_size()
    cell.config = cells._merge(cell.config, CONTROL_SIZE)
    ctx = types.SimpleNamespace(
        cell=cell, seed=2147483659, say=lambda **row: None,
        compiles=CompileCounter(), trace=False, trace_seconds=0.0,
        rehearsal=cell.config)
    driver = cell.driver()
    srv, _, _, _, correct, compared, params = driver.build(ctx)
    assert correct and compared[0][1] < compared[0][2]
    return cell, driver, srv, params


@pytest.mark.parametrize("seed", [11, 12, 15])
def test_control_is_not_correct_where_the_program_is(built, control, seed):
    """Forty requests of the rehearsal mix served to their end (no clock in
    it: the same tokens every time), every served token compared."""
    from deepspeed_tpu.inference.serving import ServeRequest
    cell, driver, srv, params = built
    hp = cell.config["model"]
    limit = float(cell.config["check"]["served_gap_limit"])
    rng = np.random.default_rng([seed, 1])
    reqs = [ServeRequest(rid=f"s{seed}-{i}", max_new_tokens=a,
                         prompt=traffic_lib.prompt_tokens(
                             p, int(hp["vocab_size"]), rng))
            for i, (p, a) in enumerate(traffic_lib.request_lengths(
                cell.traffic, 40, rng))]
    for r in reqs:
        srv.submit(r, now=time.perf_counter())
    guard = 0
    while srv.busy:
        srv.step(time.perf_counter())
        guard += 1
        assert guard < 20_000
    assert all(r.state == "done" for r in reqs)
    reference = cell.reference()
    sound = driver.served_token_gaps(reqs, params, hp, reference)
    chosen = control.control_chooser(
        reference, control.rounded_to_fp8(params), int(hp["n_head"]))
    low = driver.served_token_gaps(reqs, params, hp, reference,
                                   chosen=chosen)
    assert sum(len(g) for g in sound.values()) == sum(
        len(r.out) for r in reqs) > 300
    s_max = max(float(g.max()) for g in sound.values())
    c_max = max(float(g.max()) for g in low.values())
    assert s_max <= limit < c_max, (s_max, limit, c_max)
    # a sample has the longest request in it, whatever the seed draws
    pick = driver.sample_finished(reqs, seed)
    longest = max(len(r.prompt) + len(r.out) for r in reqs)
    assert len(pick) == driver.SAMPLE_REQUESTS == len({r.rid for r in pick})
    assert len(pick[0].prompt) + len(pick[0].out) == longest
    assert [r.rid for r in driver.sample_finished(reqs, seed)] == \
        [r.rid for r in pick]


def test_a_broken_timed_path_is_not_correct(monkeypatch, capsys):
    """`run.py` as the driver starts it, at the rehearsal size, with every
    decode dispatch after the checked warm-up handing over a token one
    above the one it sampled: the warm-up's comparison passes, the sample
    of the window's served tokens does not."""
    whole = spans_lib.instrument_serving
    seen = {"decodes": 0, "altered": 0}

    def broken(srv, log, on_dispatch=None):
        whole(srv, log, on_dispatch)
        inner = srv._device_call

        def call(site, fn, *args, now=None):
            out = inner(site, fn, *args, now=now)
            if site == "serving.decode":
                seen["decodes"] += 1
                if seen["decodes"] > 30:
                    toks = np.asarray(out[1])
                    out = (out[0], 1 + toks % 200) + tuple(out[2:])
                    seen["altered"] += 1
            return out
        srv._device_call = call

    monkeypatch.setattr(spans_lib, "instrument_serving", broken)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                       os.path.join(ROOT, ".jax_cache_t"))
    monkeypatch.setattr(sys, "argv", [
        "run.py", "--workload", "serve-gpt2xl-chat", "--seed", "2147483777",
        "--seconds", "3", "--trace", "0", "--rehearse"])
    run_py = cells.load_module(os.path.join(BENCH, "run.py"), "bench_run_py")
    with pytest.raises(SystemExit) as exit_:
        run_py.main()
    assert exit_.value.code == 3
    assert seen["altered"] > 50
    err = capsys.readouterr().err
    line = next(ln for ln in err.splitlines()
                if ln.startswith("REHEARSAL on cpu"))
    out = json.loads(line.split("): ", 1)[1])
    assert out["correct"] is False and out["failed"] == 0
    compared = {ln.split()[1]: ln for ln in err.splitlines()
                if ln.startswith("compared: ")}
    gap = float(compared["served_gap_max"].split(" = ")[1].split()[0])
    limit = float(compared["served_gap_max"].split("limit ")[1].rstrip(")"))
    assert gap > 10 * limit
    # the part of the check that ran before the break passed
    assert float(compared["warmup_max_abs_logit_error"].split(" = ")[1]
                 .split()[0]) < 0.25
    assert err.rstrip().splitlines()[-2] == "correct: False"
