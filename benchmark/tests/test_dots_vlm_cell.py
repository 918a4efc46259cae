"""The dots.vlm1 cell's checks at the rehearsal size on the CPU. (1) The
warm-up comparison, with its treatment of routing near-ties at the level
where they arise, excuses no wrong router or attention: each control comes
out NOT correct where the program comes out correct. (2) A run driven end
to end with the decode path broken underneath comes out with `correct`
false. (3) The new readers on a synthetic run: what they count, and None
where there is nothing to read. Outside tier-1: `pytest benchmark/tests`."""

import dataclasses
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
CELL = "serve-dotsvlm1-longdoc-backlog"


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


def _warmup(built, cfg=None, **kw):
    cell, driver, b = built
    check, cap = b["checked"]
    return driver.check_warmup(check, cap, b["params"], cfg or b["cfg"],
                               cell.reference(), cell.config["check"],
                               pad=0, **kw)


def test_warmup_is_correct_and_every_decision_was_compared(built):
    _, _, b = built
    ok, d = _warmup(built)
    assert ok and d["route_decisions_disputed_by_group"] == 0 \
        and d["route_decisions_disputed_by_expert"] == 0
    check, _ = b["checked"]
    tokens = sum(len(r.prompt) + len(r.out) - 1 for r in check)
    assert d["route_decisions_compared"] == tokens * b["cfg"].n_sparse_layers
    assert d["positions_compared"] == sum(len(r.out) for r in check)


@pytest.mark.parametrize("variant", [
    "no_group_limit", "no_bias", "no_scale", "unnormalised", "wrong_held",
    "no_yarn", "no_mscale", "rotate_half", "no_q_norm", "no_kv_norm",
    "fp8_up"])
def test_each_control_is_not_correct(built, variant):
    if variant == "no_group_limit":
        # check 2 holds the program's selection to the RULE on the
        # reference's scores, so the control is a rule without the limit
        # (a model that has none, served by a program that applies one)
        ok, d = _warmup(built, cfg=dataclasses.replace(
            built[2]["cfg"], n_group=1, topk_group=1))
    else:
        ok, d = _warmup(built, variant=(variant,))
    assert not ok, d
    if variant in ("no_bias", "no_group_limit"):
        # the forced selection hides a wrong router from the logits; the
        # comparison of the selections does not
        assert d["route_worst_margin"] > 10 * d["route_tie_eps"]
    if variant in ("no_scale", "wrong_held", "no_mscale", "rotate_half"):
        assert d["max_abs_logit_error"] > 10 * d["tolerance"]


def test_float8_control_is_not_correct(built):
    ok, d = _warmup(built, fp8=True)
    assert not ok and d["max_abs_logit_error"] > 10 * d["tolerance"]


def test_a_dispute_is_judged_at_the_level_where_it_arose(built):
    _, driver, b = built
    cfg = b["cfg"]                  # 16 experts in 4 groups, 2 kept, k = 3
    biased = np.full((16,), 0.1, np.float32)
    biased[[7, 10, 15, 11, 9, 6]] = [0.9, 0.85, 0.8, 0.75, 0.74, 0.7]
    group = np.asarray([0.30, 0.90, 0.80, 0.79], np.float32)
    theirs = np.asarray([7, 10, 11])        # the best of groups 1 and 2
    # the program chose the best of groups 1 and 3, and the reference
    # left group 3 out by 0.01 of its sum: a near-tie of the GROUPS
    m, level = driver._dispute_margin(
        cfg, np.asarray([6, 7, 15]), theirs, biased, group)
    assert level == "group" and abs(m - 0.01) < 1e-6
    # both inside the reference's groups: the experts' scores
    m, level = driver._dispute_margin(
        cfg, np.asarray([7, 9, 10]), theirs, biased, group)
    assert level == "expert" and abs(m - 0.01) < 1e-6
    # an expert far below one passed over, a group far from the cut, and
    # more groups than the rule keeps are near-ties at no level
    m, _ = driver._dispute_margin(
        cfg, np.asarray([4, 7, 10]), theirs, biased, group)
    assert abs(m - 0.65) < 1e-6
    m, _ = driver._dispute_margin(
        cfg, np.asarray([2, 3, 7]), theirs, biased, group)
    assert abs(m - 0.6) < 1e-6      # group 2 leads by 0.5, expert 6 by 0.6
    m, _ = driver._dispute_margin(
        cfg, np.asarray([3, 7, 10]), theirs, biased, group)
    assert m == driver.NO_TIE


def test_the_after_window_sample_keeps_its_bounds(built):
    _, driver, _ = built
    reqs = [types.SimpleNamespace(rid=i, prompt=[0] * n, out=[0] * 4)
            for i, n in enumerate((20, 300, 60, 90, 40, 120, 70))]
    got = driver.sample_served(reqs, seed=5, longest_max=100, others_max=80)
    totals = [len(r.prompt) + len(r.out) for r in got]
    assert totals[0] == 94 and len(got) == 3
    assert all(t <= 84 for t in totals[1:]) and len({r.rid for r in got}) == 3
    assert driver.sample_served([], 5, 100, 80) == []


def test_the_selection_bias_is_at_rest_under_the_balancing_rule(built):
    """A random bias leaves hot and cold experts; the balanced one gives
    every expert its share on the calibration tokens, from the seed alone."""
    import jax.numpy as jnp
    from harness import weights_dots_vlm as W
    cell, driver, b = built
    cfg = b["cfg"]
    raw = W.dots_vlm_params(7, cfg, jnp.float32, std=0.2)
    hp = driver.reference_hp(cfg)
    one, report = W.balance_router_bias(raw, cfg, 7, cell.reference(), hp,
                                        tokens=256)
    two, _ = W.balance_router_bias(raw, cfg, 7, cell.reference(), hp,
                                   tokens=256)
    assert len(report) == cfg.n_sparse_layers
    for before, after in report:
        assert before > 1.3 and after < 1.15, report
    bias = one["block"]["moe"]["router"]["bias"]
    np.testing.assert_array_equal(np.asarray(bias), np.asarray(
        two["block"]["moe"]["router"]["bias"]))
    assert bias.shape == raw["block"]["moe"]["router"]["bias"].shape
    assert float(jnp.abs(bias - raw["block"]["moe"]["router"]["bias"])
                 .max()) > 0.05
    # nothing else of the tree is touched
    assert one["block"]["q_a"]["kernel"] is raw["block"]["q_a"]["kernel"]


def test_the_host_probe_reads_what_the_machine_exposes(built):
    _, driver, b = built
    probe = driver.host_probe()
    assert isinstance(probe, dict) and "loadavg" in probe
    json.dumps(probe)
    # no dispatch of the warm-up counts as a stall (it compiles)
    assert b["counts"]["stalls"] == [] and "host_before" in b["counts"]


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


def test_rooflines_count_the_unpadded_row_and_the_occupied_history():
    from harness import rooflines_mla
    flops, nbytes = rooflines_mla.mla_decode(1000, heads=128, latent=512,
                                             d_r=64)
    assert flops == 1000 * 278528 and nbytes == 1000 * 1152
    # 242 FLOP a byte: both sides of the v5e's ridge (240) bind
    assert abs(flops / nbytes - 241.8) < 0.1
    flops, nbytes, expand = rooflines_mla.mla_prefill(
        512, 4096, heads=128, latent=512, d_n=128, d_r=64, d_v=128)
    assert expand == 2 * (4096 + 512) * 512 * 128 * 256
    pairs = 512 * 4096 + 512 * 513 / 2
    assert flops == expand + 2 * pairs * 128 * 320
    assert nbytes == (4096 + 512) * 1152
    # no history: the chunk alone
    f0, _, e0 = rooflines_mla.mla_prefill(512, 0, 128, 512, 128, 64, 128)
    assert e0 == 2 * 512 * 512 * 128 * 256 and f0 < flops / 5


class _Trace:
    busy_s = 2.0

    def kernel_seconds(self, name):
        return 0.012 if name == "mla_decode" else 0.0

    def kernel_calls(self, name):
        return 12 if name == "mla_decode" else 0


def _run(**over):
    from harness import peaks, rooflines
    log = spans_lib.SpanLog()
    log.spans += [("decode_dispatch", 1.0, 1.1, (16, 300, 150_000)),
                  ("decode_dispatch", 1.2, 1.3, (16, 300, 150_000)),
                  ("prefill_dispatch", 1.4, 1.5, (512, 4096))]
    run = {"kind": "serve", "trace": _Trace(), "trace_host_window": (0.9, 2.0),
           "log": log, "rooflines": rooflines, "say": lambda **row: None,
           "peaks": peaks.peaks_for("TPU v5 lite"), "program_trace": None,
           "mla": {"heads": 128, "d_n": 128, "d_r": 64, "d_v": 128,
                   "latent": 512, "row_lanes": 640, "layers": 6,
                   "block_size": 512, "itemsize": 2}}
    run.update(over)
    return run


def test_decode_roofline_reader_on_a_synthetic_trace():
    from harness import readers_mla
    got = readers_mla.mla_decode_roofline(_run())
    # 150,000 rows a call: 41.8 GFLOP = 212 us, 172.8 MB = 211 us; twelve
    # calls against 12 ms of kernel time
    assert 21.0 < got < 21.4
    assert got <= 100.0


def test_readers_return_none_where_there_is_nothing_to_read():
    from harness import readers_mla
    for reader in (readers_mla.mla_decode_roofline,
                   readers_mla.mla_prefill_roofline,
                   readers_mla.mla_expand_share):
        assert reader(_run(trace=None)) is None
        assert reader({"kind": "serve", "log": spans_lib.SpanLog()}) is None
    # a program without the kernel or the scopes (the parent's)
    bare = _run()
    bare["trace"].kernel_seconds = lambda name: 0.0
    assert readers_mla.mla_decode_roofline(bare) is None
    assert readers_mla.mla_prefill_roofline(_run()) is None
    assert readers_mla.mla_expand_share(_run()) is None
    cell = cells.Cell(CELL)
    for name in ("mla_time_share", "mla_decode_roofline",
                 "mla_prefill_roofline", "mla_expand_share"):
        assert cell.layer_reader(name).read(_run(trace=None)) is None
        assert name in [m["name"] for m in cell.per_layer]
