"""The LongCat-Flash cell at the rehearsal size on the CPU. (1) The warm-up
comparison excuses no dropped term: each control comes out NOT correct where
the program comes out correct. (2) The cell's line is well formed in both
trace modes and every listed metric has a reader. (3) The new readers on a
synthetic run: what they count, and None where there is nothing to read.
(4) The selection bias comes to rest with the zero-compute experts at their
share. (5) The traffic file has only keys the generator reads; the entries
of BENCHMARK.json are found BY NAME (PERF.md 7(ap): a test that pins the
end of a list fails on the next PR that appends); the configuration keeps
every number of the catalog row. Outside tier-1: `pytest benchmark/tests`."""

import json
import os
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
CELL = "serve-longcat-flash-agent-backlog"
CONFIG = "longcat-flash-chat-serve-ep32"
NEW_METRICS = ("moe_zero_share", "moe_real_max_over_mean",
               "scmoe_dense_share", "moe_zero_time_share")
APPENDED_TO = ("sched_host_share_tput", "step_prefill_share_tput",
               "decode_occupancy_tput", "kv_blocks_peak_share_tput",
               "prefill_chunk_ms_tput", "kv_relayout_share_tput",
               "dispatch_enqueue_ms_tput", "dispatch_idle_ms_tput",
               "moe_time_share", "moe_experts_roofline",
               "moe_load_max_over_mean", "mla_time_share",
               "mla_decode_roofline", "mla_prefill_roofline",
               "mla_expand_share", "host_gap_ms_tput", "gap_runtime_ms_tput",
               "gap_sched_ms_tput", "gap_caller_ms_tput")


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
    assert d["route_decisions_compared"] == tokens * b["cfg"].n_sparse_layers
    assert d["positions_compared"] == sum(len(r.out) for r in check)
    # the long request crosses chunk borders: both sublayers' rows are
    # read back from the pool
    assert len(check[0].prompt) > 2 * b["srv"].prefill_chunk
    # the bias rests with a third of the pairs on zero-compute experts
    assert 0.2 < d["route_pairs_on_zero_experts_share"] < 0.45


@pytest.mark.parametrize("kw", [
    {"fp8": True}, {"variant": ("fp8_up",)}, {"variant": ("no_zero_term",)},
    {"variant": ("no_kv_scale",)}, {"variant": ("no_q_scale",)},
    {"variant": ("scale_k_r",)}, {"variant": ("sigmoid",)},
    {"variant": ("renormalised",)}, {"variant": ("no_bias",)},
    {"variant": ("no_scale",)}, {"variant": ("wrong_held",)},
    {"variant": ("no_shortcut",)}, {"variant": ("rotate_half",)}],
    ids=lambda kw: "fp8" if "fp8" in kw else kw["variant"][0])
def test_each_control_is_not_correct(built, kw):
    ok, d = _warmup(built, **kw)
    assert not ok, d
    if kw.get("variant") == ("no_bias",):
        # the forced selection hides a wrong router from the logits; the
        # comparison of the selections does not
        assert d["route_worst_margin"] > 10 * d["route_tie_eps"]


def test_a_dispute_is_a_lead_in_the_biased_probabilities(built):
    _, driver, _ = built
    biased = np.asarray([0.30, 0.20, 0.19, 0.05], np.float32)
    assert driver.dispute_margin(np.asarray([0, 1]), biased) == 0.0
    assert abs(driver.dispute_margin(np.asarray([0, 2]), biased) - 0.01) \
        < 1e-7
    assert abs(driver.dispute_margin(np.asarray([0, 3]), biased) - 0.15) \
        < 1e-7


def test_the_selection_bias_rests_with_the_zero_experts_at_their_share(
        built):
    """A zero bias leaves hot and cold outputs and the zero-compute
    experts wherever the seed put them; the balanced one levels the real
    experts and gives the identity experts ``Z / (E + Z)`` of the pairs,
    from the seed alone."""
    import jax.numpy as jnp
    from harness import weights_longcat_flash as W
    cell, driver, b = built
    cfg = b["cfg"]
    raw = W.longcat_flash_params(7, cfg, jnp.float32, std=0.2)
    hp = driver.reference_hp(cfg)
    one, report = W.balance_router_bias(raw, cfg, 7, cell.reference(), hp,
                                        tokens=256)
    two, _ = W.balance_router_bias(raw, cfg, 7, cell.reference(), hp,
                                   tokens=256)
    assert len(report) == cfg.n_sparse_layers
    share = cfg.n_zero_experts / (cfg.num_experts + cfg.n_zero_experts)
    for before, after, _, zero_after in report:
        assert before > 1.3 and after < 1.15, report
        assert abs(zero_after - share) < 0.02, report
    bias = one["block"]["moe"]["router"]["bias"]
    np.testing.assert_array_equal(np.asarray(bias), np.asarray(
        two["block"]["moe"]["router"]["bias"]))
    assert bias.shape == (cfg.n_layers,
                          cfg.num_experts + cfg.n_zero_experts)
    # nothing else of the tree is touched
    assert one["block"]["a"]["q_a"]["kernel"] \
        is raw["block"]["a"]["q_a"]["kernel"]


def test_the_cells_line_is_well_formed_in_both_trace_modes():
    proc = run_cell(ROOT, CELL, "--trace", "0", "--rehearse")
    assert proc.returncode == 3, proc.stderr[-2000:]
    out = rehearsed(proc)
    assert out["correct"] is True and out["failed"] == 0
    assert sorted(out["metrics"]) == ["serve_tok_s", "setup_s"]
    proc = run_cell(ROOT, CELL, "--trace", "1", "--rehearse")
    assert proc.returncode == 3, proc.stderr[-2000:]
    out = rehearsed(proc)
    assert out["correct"] is True
    man = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    listed = {m["name"] for m in man["per_layer"]
              if CELL in m.get("workloads", ())}
    assert set(out["metrics"]) <= listed
    assert {"moe_zero_share", "moe_real_max_over_mean",
            "moe_load_max_over_mean", "decode_occupancy_tput"} \
        <= set(out["metrics"])
    assert 20.0 < out["metrics"]["moe_zero_share"]["value"] < 45.0
    assert out["metrics"]["moe_real_max_over_mean"]["value"] >= 1.0
    # "engine ready" says how many latent rows a token holds
    assert "latent rows a token: 6" in proc.stderr + proc.stdout


def test_every_listed_metric_has_a_reader_and_the_entries_are_there():
    man = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cell = cells.Cell(CELL)
    names = [m["name"] for m in cell.per_layer]
    assert set(names) == set(NEW_METRICS) | set(APPENDED_TO)
    for name in names:
        assert cell.layer_reader(name) is not None, name
    by_name = {m["name"]: m for m in man["per_layer"]}
    for name in NEW_METRICS:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "serve_tok_s"
    config = next(c for c in man["configs"] if c["name"] == CONFIG)
    assert config["reduced"] == ["num_layers", "n_routed_experts",
                                 "vocab_size", "max_position_embeddings"]
    work = next(w for w in man["workloads"] if w["name"] == CELL)
    assert work == dict(work, config=CONFIG, traffic="agent-backlog",
                        chips=1)
    tput = next(m for m in man["end_to_end"] if m["name"] == "serve_tok_s")
    assert CELL in tput["workloads"] and tput["bound"] == 0.04


def test_the_traffic_file_has_only_keys_the_generator_reads():
    mix = json.load(open(os.path.join(BENCH, "traffic",
                                      "agent-backlog.json")))
    accepted = json.load(open(os.path.join(BENCH, "traffic",
                                           "rollout-backlog.json")))
    assert set(mix) <= set(accepted), set(mix) - set(accepted)
    assert mix["kind"] == "requests" and mix["loop"] == "closed"
    assert mix["outstanding"] == "num_slots" and mix["backlog"] == 2048
    assert mix["prompt"] == {"dist": "lognormal", "median": 1024,
                             "sigma": 0.8, "min": 128, "max": 4096}
    assert mix["answer"] == {"dist": "lognormal", "median": 1024,
                             "sigma": 0.5, "min": 256, "max": 3072}
    assert mix["shared_prefix_tokens"] == 0 and mix["sampling"] == "greedy"
    conf = cells.Cell(CELL).config
    sv = conf["serving"]
    assert mix["max_total"] == sv["max_total"] == 6144
    # the pool is SMALLER than slots x table: blocks are held as the mix
    # needs them, and the run fails if the scheduler ever preempts
    assert sv["num_blocks"] * sv["block_size"] \
        < sv["num_slots"] * sv["max_total"]
    assert sv["table_entries"] * sv["block_size"] == sv["max_total"]
    assert sv["kv_bytes_per_token"] == sv["latent_rows_per_token"] \
        * sv["latent_row_lanes_stored"] * 2 == 10240


def test_the_configuration_keeps_every_number_of_the_catalog_row():
    conf = cells.Cell(CELL).config
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    row = next(r for r in map(json.loads, open(catalog))
               if r["name"] == "LongCat-Flash-Chat")
    assert conf["source"] == row["source_url"]
    differs = sorted(k for k, v in row["config"].items() if conf[k] != v)
    assert differs == sorted(conf["reduced"]) == [
        "max_position_embeddings", "n_routed_experts", "num_layers",
        "vocab_size"]
    assert conf["published"] == {k: row["config"][k] for k in differs}
    for key, cut in conf["reduced"].items():
        assert cut["published"] == row["config"][key]
        assert cut["here"] == conf[key]
    assert conf["zero_expert_num"] == 256            # none is cut
    share = conf["deployment_share"]
    assert share["experts_held"] == conf["n_routed_experts"] == 16
    assert share["chips_per_layer"] * share["experts_held"] == 512
    assert conf["parameters_held_here"] == 5172749312
    assert conf["parameters_published"] == 560664980480
    assert conf["assumed"] and conf["deployment"]
    for key in ("order inside the double layer", "router",
                "low-rank scales", "rotary", "selection bias"):
        assert conf["assumed"][key]


class _Trace:
    busy_s = 2.0


def _run(**over):
    log = spans_lib.SpanLog()
    run = {"kind": "serve", "trace": _Trace(), "log": log,
           "say": lambda **row: None, "program_trace": None,
           "moe": {"held": 16, "k": 12, "zero_experts": 256},
           "moe_counters": {"decode": {
               "layer_calls": 100, "pairs_total": 100 * 64 * 12,
               "pairs_held": 1600, "pairs_zero": 100 * 64 * 4,
               "real_pairs_max_token": 100 * 11}}}
    run.update(over)
    return run


def test_readers_on_a_synthetic_run():
    from harness import readers_scmoe
    assert abs(readers_scmoe.moe_zero_share(_run()) - 100 / 3) < 1e-9
    # the busiest token chose 11 real experts where the mean is 8
    assert abs(readers_scmoe.moe_real_max_over_mean(_run()) - 11 / 8) < 1e-9


def test_readers_return_none_where_there_is_nothing_to_read():
    from harness import readers_scmoe
    # a program without the counters (the parent's), or an end-to-end run
    for run in (_run(moe_counters=None), _run(moe_counters={}),
                _run(moe_counters={"decode": {"pairs_total": 10,
                                              "layer_calls": 1}}),
                {"kind": "serve", "log": spans_lib.SpanLog()}):
        assert readers_scmoe.moe_zero_share(run) is None
        assert readers_scmoe.moe_real_max_over_mean(run) is None
    # no device trace, or no provenance to find a scope in
    for run in (_run(trace=None), _run(),
                {"kind": "serve", "log": spans_lib.SpanLog()}):
        assert readers_scmoe.scmoe_dense_share(run) is None
        assert readers_scmoe.moe_zero_time_share(run) is None
    cell = cells.Cell(CELL)
    for name in NEW_METRICS:
        assert cell.layer_reader(name).read(
            _run(trace=None, moe_counters=None)) is None
