"""The SmallThinker cell's checks at the rehearsal size on the CPU. (1) The
warm-up comparison, with its treatment of routing near-ties (the reference
forced to the program's selection, every disagreement held to a near-tie),
excuses no wrong router or attention: each control comes out NOT correct
where the program comes out correct. (2) A run driven end to end with the
decode path broken underneath comes out with `correct` false. Outside
tier-1: `pytest benchmark/tests`."""

import json
import os
import sys
import types

import numpy as np
import pytest

from harness import cells
from harness import spans as spans_lib
from harness.compiles import CompileCounter

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
CELL = "serve-smallthinker-mixed-context-backlog"


@pytest.fixture(scope="module")
def built():
    cell = cells.Cell(CELL)
    cell.use_rehearsal_size()
    ctx = types.SimpleNamespace(
        cell=cell, seed=2147483659, say=lambda **row: None,
        compiles=CompileCounter(), trace=False, trace_seconds=0.0,
        rehearsal=cell.config)
    driver = cell.driver()
    b = driver.build(ctx)
    assert b["correct"], b["compared"]
    return cell, driver, b


def test_warmup_is_correct_and_every_decision_was_compared(built):
    cell, driver, b = built
    check, cap = b["checked"]
    ok, d = driver.check_warmup(check, cap, b["params"], b["cfg"],
                                cell.reference(), cell.config["check"])
    assert ok and d["route_decisions_disputed"] == 0
    tokens = sum(len(r.prompt) + len(r.out) - 1 for r in check)
    assert d["route_decisions_compared"] == tokens * b["cfg"].n_sparse_layers
    assert d["positions_compared"] == sum(len(r.out) for r in check)


@pytest.mark.parametrize("variant", [
    "router_after_attention", "router_normed", "silu",
    "softmax_all_unnormalised", "rotary_on_full", "no_rotary",
    "window_off_by_one", "qk_norm"])
def test_each_control_is_not_correct(built, variant):
    cell, driver, b = built
    check, cap = b["checked"]
    ok, d = driver.check_warmup(check, cap, b["params"], b["cfg"],
                                cell.reference(), cell.config["check"],
                                variant=(variant,))
    assert not ok, d
    if variant in ("router_after_attention", "router_normed"):
        # the forced selection hides WHAT the router read from the logits;
        # the comparison of the selections does not
        assert d["route_worst_margin"] > 10 * d["route_tie_eps"]
    else:
        assert d["max_abs_logit_error"] > 10 * d["tolerance"]


def test_float8_control_is_not_correct(built):
    cell, driver, b = built
    check, cap = b["checked"]
    ok, d = driver.check_warmup(check, cap, b["params"], b["cfg"],
                                cell.reference(), cell.config["check"],
                                fp8=True)
    assert not ok and d["max_abs_logit_error"] > 10 * d["tolerance"]


def test_a_broken_timed_path_is_not_correct(monkeypatch, capsys):
    """`run.py` at the rehearsal size with every decode dispatch after the
    checked warm-up handing over a token other than the one it sampled."""
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
        "run.py", "--workload", CELL, "--seed", "2147483777",
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
    share = float(compared["served_off_share"].split(" = ")[1].split()[0])
    limit = float(compared["served_off_share"].split("limit ")[1].rstrip(")"))
    assert share > 10 * limit
    assert float(compared["warmup_max_abs_logit_error"].split(" = ")[1]
                 .split()[0]) < 0.001


def test_rooflines_count_the_keys_a_query_sees():
    from harness import rooflines_window as R
    brute = lambda s, n, w: sum(min(t + 1, w) if w else t + 1
                                for t in range(s, s + n))
    for start, n, w in ((0, 512, 4096), (3584, 512, 4096), (3900, 300, 4096),
                        (8192, 512, 4096), (0, 7, None), (1000, 512, None)):
        assert R._seen_keys(start, n, w) == brute(start, n, w)
    flops, nbytes = R.prefill_attention(8192, 512, 28, 4, 128, 4096)
    assert flops == 4 * 3584 * 512 * 4096
    assert nbytes == 2 * (2 * (4095 + 512) * 512 + 2 * 512 * 3584)
    flops, _ = R.prefill_attention(0, 512, 28, 4, 128)
    assert flops == 4 * 3584 * 512 * 513 / 2


def test_readers_return_nothing_without_their_sources():
    from harness import readers_window as W
    for read in (W.attn_window_prefill_share, W.attn_window_prefill_roofline,
                 W.attn_full_prefill_roofline, W.window_ring_fill_share,
                 W.moe_act_zero_share):
        assert read({"kind": "serve"}) is None
    run = {"window": (0.0, 10.0), "window_attn": {"ring": [
        (1.0, 3, 50, 400), (2.0, 4, 120, 400), (3.0, 4, 100, 400),
        (11.0, 4, 399, 400)]}}
    assert W.window_ring_fill_share(run) == 30.0
    assert W.scope_order({"kind": "serve"}) is None

    class Trace:
        tables = {"p": {"a": {"scope": "while/body/moe_router/dot"},
                        "b": {"scope": "while/body/attn_qkv/dot"},
                        "c": {"scope": "while/body/moe_experts/gmm"},
                        "d": {"scope": "while/body/attn_out"}}}
        ops = [[(("p", o), float(t), t + 0.5, 0.5)
                for t, o in enumerate("abdcabbc")]]

        def entry(self, program, op):
            return self.tables[program].get(op)

    assert W.scope_order({"program_trace": Trace()}) == {
        "attn_qkv>moe_experts": 2, "moe_experts>moe_router": 1,
        "moe_router>attn_qkv": 2}
    run = {"moe_counters": {"decode": {"act_zero": 30, "act_total": 80}}}
    assert W.moe_act_zero_share(run) == 37.5
