"""The Kimi-Linear cell at the rehearsal size on the CPU. (1) The warm-up
comparison excuses no dropped term: each control comes out NOT correct where
the program comes out correct. (2) The cell's line is well formed in both
trace modes and every listed metric has a reader. (3) The new readers on a
synthetic run: what they count, and None where there is nothing to read.
(4) The traffic file has only keys the generator reads; the new entries of
BENCHMARK.json were appended and nothing accepted changed; the
configuration keeps every number of the catalog row. Outside tier-1:
`pytest benchmark/tests`."""

import json
import os
import subprocess
import types

import pytest

from harness import cells
from harness import spans as spans_lib
from harness.compiles import CompileCounter
from test_rehearsal import rehearsed, run_cell

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
CELL = "serve-kimilinear-rollout-backlog"
CONFIG = "kimi-linear-48b-a3b-serve-ep16"
NEW_METRICS = ("kda_time_share", "kda_mix_share", "kda_step_roofline",
               "kda_chunk_roofline", "recurrent_state_share")
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
    # the long request crosses chunk borders: its state is carried
    assert len(check[0].prompt) > 2 * b["srv"].prefill_chunk


@pytest.mark.parametrize("kw", [
    {"fp8": True}, {"variant": ("fp8_kda",)}, {"variant": ("state_bf16",)},
    {"variant": ("no_decay",)}, {"variant": ("no_conv",)},
    {"variant": ("rotated",)}, {"variant": ("wrong_held",)}])
def test_each_control_is_not_correct(built, kw):
    ok, d = _warmup(built, **kw)
    assert not ok, d


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
    assert {"recurrent_state_share", "moe_load_max_over_mean",
            "decode_occupancy_tput"} <= set(out["metrics"])
    assert 0.0 < out["metrics"]["recurrent_state_share"]["value"] < 100.0


def test_every_listed_metric_has_a_reader_and_new_entries_were_appended():
    man = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cell = cells.Cell(CELL)
    names = [m["name"] for m in cell.per_layer]
    assert set(names) == set(NEW_METRICS) | set(APPENDED_TO)
    for name in names:
        assert cell.layer_reader(name) is not None, name
    assert man["configs"][-1]["name"] == CONFIG
    assert man["configs"][-1]["reduced"] == ["num_experts", "vocab_size",
                                             "model_max_length"]
    assert man["workloads"][-1] == dict(
        man["workloads"][-1], name=CELL, config=CONFIG,
        traffic="rollout-backlog", chips=1)
    assert [m["name"] for m in man["per_layer"][-5:]] == list(NEW_METRICS)
    for m in man["per_layer"][-5:]:
        assert m["workloads"] == [CELL] and m["moves"] == "serve_tok_s"
    for m in man["per_layer"] + man["end_to_end"]:
        if CELL in m.get("workloads", ()) and m["name"] not in NEW_METRICS:
            assert m["workloads"][-1] == CELL, m["name"]
    tput = next(m for m in man["end_to_end"] if m["name"] == "serve_tok_s")
    assert tput["workloads"][-1] == CELL and tput["bound"] == 0.04
    assert len(man["workloads"]) == 8
    assert sum(w["chips"] == 4 for w in man["workloads"]) == 1


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
                                      "rollout-backlog.json")))
    accepted = json.load(open(os.path.join(BENCH, "traffic",
                                           "reason-long-backlog.json")))
    assert set(mix) <= set(accepted), set(mix) - set(accepted)
    assert mix["kind"] == "requests" and mix["loop"] == "closed"
    assert mix["outstanding"] == "num_slots" and mix["backlog"] == 1024
    assert mix["ramp_requests"] == 20 and mix["schedule_seed"] == 23
    assert mix["prompt"] == {"dist": "lognormal", "median": 768,
                             "sigma": 0.8, "min": 128, "max": 2048}
    assert mix["answer"] == {"dist": "lognormal", "median": 3072,
                             "sigma": 0.5, "min": 1024, "max": 6144}
    conf = cells.Cell(CELL).config
    assert mix["max_total"] == conf["serving"]["max_total"] == 8192
    # every slot can reach max_total: slots bind, not blocks
    sv = conf["serving"]
    assert sv["num_blocks"] * sv["block_size"] \
        == sv["num_slots"] * sv["max_total"]


def test_the_configuration_keeps_every_number_of_the_catalog_row():
    conf = cells.Cell(CELL).config
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    row = next(r for r in map(json.loads, open(catalog))
               if r["name"] == "Kimi-Linear-48B-A3B-Instruct")
    assert conf["source"] == row["source_url"]
    differs = sorted(k for k, v in row["config"].items() if conf[k] != v)
    assert differs == sorted(conf["reduced"]) == [
        "model_max_length", "num_experts", "vocab_size"]
    assert conf["published"] == {k: row["config"][k] for k in differs}
    assert conf["num_hidden_layers"] == 27            # no depth cut
    assert conf["deployment_share"]["experts_held"] == conf["num_experts"]
    assert conf["parameters_held_here"] == 4296057728
    assert conf["assumed"] and conf["deployment"]


def test_rooflines_count_the_recurrence():
    from harness import rooflines_kda
    flops, nbytes = rooflines_kda.kda_step(40, heads=32, head_dim=128)
    assert flops == 40 * 32 * 6 * 128 * 128
    # each slot's state read and written, and its rows: 4.2 MB a slot
    assert nbytes == 40 * (2 * 32 * 128 * 128 * 4 + 32 * 641 * 4)
    assert flops / nbytes < 1.0      # far under the ridge: the bytes bind
    cf, cb = rooflines_kda.kda_chunk(512, heads=32, head_dim=128)
    assert cf == 512 * 32 * 6 * 128 * 128
    assert cb == 2 * 32 * 128 * 128 * 4 + 512 * 32 * 641 * 4


class _Trace:
    busy_s = 2.0

    def kernel_seconds(self, name):
        return 0.016 if name == "kda_step" else 0.0

    def kernel_calls(self, name):
        return 40 if name == "kda_step" else 0


def _run(**over):
    from harness import peaks, rooflines
    log = spans_lib.SpanLog()
    log.spans += [("decode_dispatch", 1.0, 1.1, (40, 400, 96_000)),
                  ("decode_dispatch", 1.2, 1.3, (40, 400, 96_000)),
                  ("prefill_dispatch", 1.4, 1.5, (512, 1024))]
    run = {"kind": "serve", "trace": _Trace(), "trace_host_window": (0.9, 2.0),
           "host_window": (0.0, 3.0), "kv_used": [(1.0, 100), (2.0, 300)],
           "log": log, "rooflines": rooflines, "say": lambda **row: None,
           "peaks": peaks.peaks_for("TPU v5 lite"), "program_trace": None,
           "kda": {"heads": 32, "head_dim": 128, "layers": 20,
                   "state_itemsize": 4, "recurrent_state_bytes": 1_677_721_600,
                   "conv_tail_bytes": 58_982_400,
                   "latent_bytes_per_block": 512 * 8960}}
    run.update(over)
    return run


def test_readers_on_a_synthetic_run():
    from harness import readers_kda
    got = readers_kda.kda_step_roofline(_run())
    # 40 slots a call: 171 MB = 208.8 us against 400 us a call
    assert 52.0 < got < 52.4
    share = readers_kda.recurrent_state_share(_run())
    assert abs(share - 100 * 1677721600 / (1677721600 + 300 * 512 * 8960)) \
        < 1e-9


def test_readers_return_none_where_there_is_nothing_to_read():
    from harness import readers_kda
    for reader in (readers_kda.kda_step_roofline,
                   readers_kda.kda_chunk_roofline,
                   lambda run: readers_kda.scope_share(run, "attn_kda")):
        assert reader(_run(trace=None)) is None
        assert reader({"kind": "serve", "log": spans_lib.SpanLog()}) is None
    # a program without the state or the scopes (the parent's)
    assert readers_kda.recurrent_state_share(_run(kda=None)) is None
    assert readers_kda.recurrent_state_share(
        {"kind": "serve", "log": spans_lib.SpanLog()}) is None
    assert readers_kda.scope_share(_run(), "kda_mix") is None
    assert readers_kda.kda_chunk_roofline(_run()) is None
    bare = _run()
    bare["trace"].kernel_seconds = lambda name: 0.0
    assert readers_kda.kda_step_roofline(bare) is None
    cell = cells.Cell(CELL)
    for name in NEW_METRICS:
        assert cell.layer_reader(name).read(_run(trace=None, kda=None)) \
            is None
