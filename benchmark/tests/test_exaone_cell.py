"""The K-EXAONE cell's checks at the rehearsal size on the CPU. (1) The
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
CELL = "serve-kexaone-reason-backlog"


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
    "softmax_router", "no_scale", "unnormalised", "bias_in_weights",
    "no_bias", "wrong_held", "rotary_on_full", "no_qk_norm"])
def test_each_control_is_not_correct(built, variant):
    cell, driver, b = built
    check, cap = b["checked"]
    ok, d = driver.check_warmup(check, cap, b["params"], b["cfg"],
                                cell.reference(), cell.config["check"],
                                variant=(variant,))
    assert not ok, d
    if variant == "no_bias":
        # the forced selection hides a dropped bias from the logits; the
        # comparison of the selections does not
        assert d["route_worst_margin"] > 10 * d["route_tie_eps"]
    if variant in ("no_scale", "wrong_held", "rotary_on_full"):
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


def test_rooflines_count_pairs_and_touched_experts():
    from harness import rooflines_moe
    flops, nbytes = rooflines_moe.held_experts_decode(
        48, 15, d_model=6144, d_ff=2048)
    assert flops == 2 * 48 * 3 * 6144 * 2048
    assert nbytes > 15 * 3 * 6144 * 2048 * 2
    assert nbytes < 15.1 * 3 * 6144 * 2048 * 2
    b = rooflines_moe.paged_decode_mixed_bytes(
        100, 48 * 128, block_size=16, kv_heads=8, head_dim=128,
        full_layers=2, window_layers=6)
    assert b == 4096 * (100 * 16 * 2 + 48 * 128 * 6)
