"""The ZAYA1 cell at the rehearsal size on the CPU. (1) The warm-up
comparison excuses no dropped term: each control comes out NOT correct where
the program comes out correct. (2) The cell's line is well formed in both
trace modes, every listed metric has a reader, and the three parts of the
check run. (3) The new readers on a synthetic run: what they count, and None
where there is nothing to read. (4) The traffic file has only keys the
generator reads; the new entries of BENCHMARK.json were appended and nothing
accepted changed. Outside tier-1: `pytest benchmark/tests`."""

import json
import os
import subprocess
import types

import numpy as np
import pytest

from harness import cells
from harness import spans as spans_lib
from harness.compiles import CompileCounter
from test_rehearsal import rehearsed, run_cell

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
CELL = "serve-zaya1-reason-long-backlog"
CONFIG = "zaya1-8b-serve-pp2"
NEW_METRICS = ("cca_time_share", "cca_mix_share", "paged_decode_cca_roofline",
               "moe_router_share", "moe_skip_share")
APPENDED_TO = ("sched_host_share_tput", "step_prefill_share_tput",
               "decode_occupancy_tput", "kv_blocks_peak_share_tput",
               "prefill_chunk_ms_tput", "kv_relayout_share_tput",
               "dispatch_enqueue_ms_tput", "dispatch_idle_ms_tput",
               "moe_time_share", "moe_experts_roofline",
               "moe_load_max_over_mean")


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


def _warmup(built, **kw):
    cell, driver, b = built
    check, cap = b["checked"]
    return driver.check_warmup(check, cap, b["params"], b["cfg"],
                               cell.reference(), cell.config["check"],
                               pad=0, **kw)


def test_warmup_is_correct_and_every_decision_was_compared(built):
    _, _, b = built
    ok, d = _warmup(built)
    assert ok and d["route_decisions_disputed"] == 0
    check, _ = b["checked"]
    tokens = sum(len(r.prompt) + len(r.out) - 1 for r in check)
    assert d["route_decisions_compared"] == tokens * b["cfg"].n_layers
    assert d["positions_compared"] == sum(len(r.out) for r in check)
    # the longer request crossed chunk borders: its tail was resumed
    assert len(check[0].prompt) > 2 * b["srv"].prefill_chunk


@pytest.mark.parametrize("variant", [
    "no_conv0", "no_conv1", "no_qk_mean", "no_value_shift", "no_temp",
    "full_rotary", "no_res_bias", "no_router_state", "no_skip", "no_bias",
    "no_gate", "fp8_conv"])
def test_each_control_is_not_correct(built, variant):
    ok, d = _warmup(built, variant=(variant,))
    assert not ok, d
    if variant in ("no_bias", "no_router_state"):
        # the forced selection hides a wrong router from the logits; the
        # comparison of the choices does not
        assert d["route_worst_margin"] > 10 * d["route_tie_eps"]
    if variant in ("no_value_shift", "no_conv1", "no_gate"):
        assert d["max_abs_logit_error"] > 10 * d["tolerance"]


def test_float8_control_is_not_correct(built):
    ok, d = _warmup(built, fp8=True)
    assert not ok and d["max_abs_logit_error"] > 10 * d["tolerance"]


def test_the_selection_bias_is_at_rest_under_the_balancing_rule(built):
    """A random bias leaves hot and cold experts; the balanced one levels
    the experts and gives the skip its stated share on the calibration
    tokens, from the seed alone."""
    import jax.numpy as jnp
    from harness import weights_zaya as W
    cell, driver, b = built
    cfg = b["cfg"]
    raw = W.zaya_params(7, cfg, jnp.float32, std=0.2)
    hp = driver.reference_hp(cfg)
    one, report = W.balance_router_bias(raw, cfg, 7, cell.reference(), hp,
                                        tokens=256, skip_share=0.125)
    two, _ = W.balance_router_bias(raw, cfg, 7, cell.reference(), hp,
                                   tokens=256, skip_share=0.125)
    assert len(report) == cfg.n_layers
    for before, after, skip in report:
        # (at this size a layer's tokens cluster, and a cluster moves whole)
        assert before > 1.3 and after < 1.2 and abs(skip - 0.125) < 0.1, \
            report
    assert abs(report[0][2] - 0.125) < 0.02
    bias = one["block"]["moe"]["router"]["bias"]
    np.testing.assert_array_equal(np.asarray(bias), np.asarray(
        two["block"]["moe"]["router"]["bias"]))
    assert bias.shape == (cfg.n_layers, cfg.num_experts + 1)
    # nothing else of the tree is touched
    assert one["block"]["qkv"]["kernel"] is raw["block"]["qkv"]["kernel"]
    assert one["wte"] is raw["wte"]
    # given sequences (the driver's: the model's own continuations), each
    # is attended on its own and only the counted positions are levelled
    seqs = np.random.default_rng(3).integers(1, cfg.vocab_size, (3, 96))
    counted = np.arange(96)[None] >= 32 + np.zeros((3, 1), int)
    own, report = W.balance_router_bias(one, cfg, 7, cell.reference(), hp,
                                        skip_share=0.125, sequences=seqs,
                                        counted=counted)
    assert len(report) == cfg.n_layers and report[0][1] < 1.2
    alone, _ = W.balance_router_bias(one, cfg, 7, cell.reference(), hp,
                                     skip_share=0.125, sequences=seqs[:1],
                                     counted=counted[:1])
    assert not np.array_equal(
        np.asarray(own["block"]["moe"]["router"]["bias"]),
        np.asarray(alone["block"]["moe"]["router"]["bias"]))


def test_the_cells_line_is_well_formed_in_both_trace_modes():
    """`run.py --rehearse` end to end: the three parts of the check run,
    the end-to-end line has the cell's two metrics, the traced line only
    metrics that BENCHMARK.json lists for the cell."""
    proc = run_cell(ROOT, CELL, "--trace", "0", "--rehearse")
    assert proc.returncode == 3, proc.stderr[-2000:]
    out = rehearsed(proc)
    assert out["correct"] is True and out["failed"] == 0
    assert sorted(out["metrics"]) == ["serve_tok_s", "setup_s"]
    compared = [ln.split()[1] for ln in proc.stderr.splitlines()
                if ln.startswith("compared: ")]
    assert compared == [
        "warmup_max_abs_logit_error", "warmup_route_worst_disagreement",
        "compiles_inside_window", "served_tokens_compared",
        "served_off_share", "served_gap_max_not_held"]
    proc = run_cell(ROOT, CELL, "--trace", "1", "--rehearse")
    assert proc.returncode == 3, proc.stderr[-2000:]
    out = rehearsed(proc)
    assert out["correct"] is True
    listed = {m["name"] for m in cells.Cell(CELL).per_layer}
    assert set(out["metrics"]) <= listed
    # what needs no device trace reads on the CPU too
    assert {"moe_skip_share", "moe_load_max_over_mean",
            "decode_occupancy_tput"} <= set(out["metrics"])
    assert 0.0 <= out["metrics"]["moe_skip_share"]["value"] <= 100.0


def test_every_listed_metric_has_a_reader_and_new_entries_were_appended():
    man = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cell = cells.Cell(CELL)
    names = [m["name"] for m in cell.per_layer]
    assert set(names) == set(NEW_METRICS) | set(APPENDED_TO)
    for name in names:
        assert cell.layer_reader(name) is not None, name
    # appended: the new entries come last in every list they joined
    assert man["configs"][-1]["name"] == CONFIG
    assert man["configs"][-1]["reduced"] == ["num_hidden_layers",
                                             "max_position_embeddings"]
    assert man["workloads"][-1] == dict(
        man["workloads"][-1], name=CELL, config=CONFIG,
        traffic="reason-long-backlog", chips=1)
    assert [m["name"] for m in man["per_layer"][-5:]] == list(NEW_METRICS)
    for m in man["per_layer"][-5:]:
        assert m["workloads"] == [CELL] and m["moves"] == "serve_tok_s"
    for m in man["per_layer"] + man["end_to_end"]:
        if CELL in m.get("workloads", ()) and m["name"] not in NEW_METRICS:
            assert m["workloads"][-1] == CELL, m["name"]
    tput = next(m for m in man["end_to_end"] if m["name"] == "serve_tok_s")
    assert tput["workloads"][-1] == CELL and tput["bound"] == 0.04


def test_nothing_accepted_changed():
    """Against the parent commit, where git has it: no accepted file under
    benchmark/ was edited, and every entry BENCHMARK.json had is still
    there, in its place."""
    def git(*args):
        return subprocess.run(("git", "-C", ROOT) + args, text=True,
                              capture_output=True)
    # PR 56 (a `benchmark` PR) edited accepted files, as only its kind
    # may: what stands since then is what no later PR may edit
    base = git("log", "--format=%H", "-n", "1", "--grep", "^PR 56:")
    if base.returncode or not base.stdout.strip():
        pytest.skip("no git history to compare with")
    parent = base.stdout.strip()
    changed = git("diff", "--name-status", parent, "--", "benchmark")
    edited = [ln for ln in changed.stdout.splitlines()
              if not ln.startswith("A")]
    assert edited == [], edited
    old = json.loads(git("show", parent + ":BENCHMARK.json").stdout)
    new = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    # what the parent had stands at the head of every list, unchanged but
    # for cells appended to a metric's `workloads`
    for key in ("command", "paths", "run_seconds"):
        assert new[key] == old[key]
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        for was, now in zip(old[key], new[key]):
            cut = dict(now)
            if "workloads" in was:
                cut["workloads"] = now["workloads"][:len(was["workloads"])]
            assert cut == was, (key, was["name"])
        assert len(new[key]) >= len(old[key])


def test_the_traffic_file_has_only_keys_the_generator_reads():
    mix = json.load(open(os.path.join(BENCH, "traffic",
                                      "reason-long-backlog.json")))
    accepted = json.load(open(os.path.join(BENCH, "traffic",
                                           "longdoc-backlog.json")))
    assert set(mix) <= set(accepted), set(mix) - set(accepted)
    assert mix["kind"] == "requests" and mix["loop"] == "closed"
    assert mix["outstanding"] == "num_slots" and mix["backlog"] == 1024
    assert mix["ramp_requests"] == 20 and mix["schedule_seed"] == 23
    assert mix["prompt"] == {"dist": "lognormal", "median": 512,
                             "sigma": 0.8, "min": 64, "max": 2048}
    assert mix["answer"] == {"dist": "lognormal", "median": 2048,
                             "sigma": 0.5, "min": 512, "max": 4096}
    conf = cells.Cell(CELL).config
    assert mix["max_total"] == conf["serving"]["max_total"] == 6144
    # every slot can reach max_total: slots bind, not blocks
    sv = conf["serving"]
    assert sv["num_blocks"] * sv["block_size"] \
        == sv["num_slots"] * sv["max_total"]


def test_the_configuration_keeps_every_published_width():
    conf = cells.Cell(CELL).config
    for key, value in (
            ("hidden_size", 2048), ("num_attention_heads", 8),
            ("num_key_value_heads", 2), ("head_dim", 128),
            ("moe_intermediate_size", 2048), ("num_experts", 16),
            ("num_experts_per_tok", 1), ("router_hidden_size", 256),
            ("vocab_size", 262272), ("cca_time0", 2), ("cca_time1", 2),
            ("partial_rotary_factor", 0.5), ("tie_word_embeddings", True)):
        assert conf[key] == value, key
    assert sorted(conf["reduced"]) == ["max_position_embeddings",
                                       "num_hidden_layers"]
    assert conf["num_hidden_layers"] == 20
    assert conf["deployment_share"]["experts_held"] == conf["num_experts"]
    assert conf["parameters_held_here"] == 4688810364
    assert conf["assumed"] and conf["deployment"]


def test_rooflines_count_the_occupied_rows_at_two_kv_heads():
    from harness import rooflines_cca
    flops, nbytes = rooflines_cca.paged_decode_gqa(
        96000, heads=8, kv_heads=2, head_dim=128)
    assert flops == 96000 * 4096 and nbytes == 96000 * 1024
    # 4 FLOP a byte: far under the v5e's ridge (240), the bytes bind
    assert flops / nbytes == 4.0


class _Trace:
    busy_s = 2.0

    def kernel_seconds(self, name):
        return 0.008 if name == "paged_decode" else 0.0

    def kernel_calls(self, name):
        return 40 if name == "paged_decode" else 0


def _run(**over):
    from harness import peaks, rooflines
    log = spans_lib.SpanLog()
    log.spans += [("decode_dispatch", 1.0, 1.1, (40, 400, 96_000)),
                  ("decode_dispatch", 1.2, 1.3, (40, 400, 96_000)),
                  ("prefill_dispatch", 1.4, 1.5, (512, 1024))]
    run = {"kind": "serve", "trace": _Trace(), "trace_host_window": (0.9, 2.0),
           "log": log, "rooflines": rooflines, "say": lambda **row: None,
           "peaks": peaks.peaks_for("TPU v5 lite"), "program_trace": None,
           "cca": {"heads": 8, "kv_heads": 2, "head_dim": 128, "layers": 20,
                   "itemsize": 2},
           "moe_counters": {"decode": {"pairs_total": 800,
                                       "pairs_skipped": 48,
                                       "pairs_held": 752}}}
    run.update(over)
    return run


def test_readers_on_a_synthetic_run():
    from harness import readers_cca
    got = readers_cca.paged_decode_cca_roofline(_run())
    # 96,000 rows a call: 98.3 MB = 120 us against 200 us a call
    assert 59.9 < got < 60.1
    assert readers_cca.moe_skip_share(_run()) == 6.0


def test_readers_return_none_where_there_is_nothing_to_read():
    from harness import readers_cca
    for reader in (readers_cca.paged_decode_cca_roofline,
                   lambda run: readers_cca.scope_share(run, "attn_cca")):
        assert reader(_run(trace=None)) is None
        assert reader({"kind": "serve", "log": spans_lib.SpanLog()}) is None
    # a program without the counter or the scopes (the parent's)
    assert readers_cca.moe_skip_share(_run(moe_counters={})) is None
    assert readers_cca.moe_skip_share(_run(moe_counters={"decode": {
        "pairs_total": 8, "pairs_held": 1}})) is None
    assert readers_cca.scope_share(_run(), "cca_mix") is None
    bare = _run()
    bare["trace"].kernel_seconds = lambda name: 0.0
    assert readers_cca.paged_decode_cca_roofline(bare) is None
    cell = cells.Cell(CELL)
    for name in NEW_METRICS:
        assert cell.layer_reader(name).read(_run(
            trace=None, moe_counters={})) is None
