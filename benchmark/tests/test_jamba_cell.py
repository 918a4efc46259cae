"""The Jamba cell at the rehearsal size on the CPU. (1) The warm-up
comparison excuses no dropped term: each control comes out NOT correct where
the program comes out correct. (2) The cell's line is well formed in both
trace modes and every listed metric has a reader (a traced line on the CPU
holds those that read the program's spans and counters; the readers of the
device trace say nothing there, and nothing raises). (3) The new readers on
a synthetic run: what they count, and None where there is nothing to read.
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
CELL = "serve-jamba2-3b-reason-context-backlog"
CONFIG = "ai21-jamba2-3b-serve"
NEW_METRICS = ("ssm_time_share", "ssm_mix_share", "ssm_step_roofline",
               "ssm_scan_roofline", "ssm_state_share",
               "paged_decode_mqa_roofline")
APPENDED_TO = ("sched_host_share_tput", "step_prefill_share_tput",
               "decode_occupancy_tput", "kv_blocks_peak_share_tput",
               "prefill_chunk_ms_tput", "kv_relayout_share_tput",
               "dispatch_enqueue_ms_tput", "dispatch_idle_ms_tput",
               "host_gap_ms_tput", "gap_runtime_ms_tput",
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


def test_warmup_is_correct_and_every_emitted_token_was_compared(built):
    _, _, b = built
    ok, d = _warmup(built)
    assert ok and d["every_token_has_logits_and_is_their_argmax"]
    check, _ = b["checked"]
    assert d["positions_compared"] == sum(len(r.out) for r in check)
    # the long request crosses chunk borders: its state is carried
    assert len(check[0].prompt) > 2 * b["srv"].prefill_chunk


@pytest.mark.parametrize("kw", [
    {"fp8": True}, {"variant": ("fp8_ssm",)}, {"variant": ("state_bf16",)},
    {"variant": ("no_dt_norm",)}, {"variant": ("no_skip",)},
    {"variant": ("no_softplus",)}, {"variant": ("rotated",)}])
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
    # every listed metric is in the line or is said to be absent: off the
    # chip there is no device trace, so its readers are silent
    absent = listed - set(out["metrics"])
    device = {m["name"] for m in man["per_layer"]
              if m["source"] == "device_trace"}
    assert absent <= device | {"dispatch_idle_ms_tput", "gap_runtime_ms_tput",
                               "gap_sched_ms_tput", "gap_caller_ms_tput"}, \
        absent
    assert {"ssm_state_share", "decode_occupancy_tput",
            "sched_host_share_tput"} <= set(out["metrics"])
    assert 0.0 < out["metrics"]["ssm_state_share"]["value"] < 100.0


def test_every_listed_metric_has_a_reader_and_new_entries_were_appended():
    man = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cell = cells.Cell(CELL)
    names = [m["name"] for m in cell.per_layer]
    assert set(names) == set(NEW_METRICS) | set(APPENDED_TO)
    for name in names:
        assert cell.layer_reader(name) is not None, name
    assert man["configs"][-1]["name"] == CONFIG
    assert man["configs"][-1]["reduced"] == ["max_position_embeddings"]
    assert man["workloads"][-1] == dict(
        man["workloads"][-1], name=CELL, config=CONFIG,
        traffic="reason-context-backlog", chips=1)
    assert [m["name"] for m in man["per_layer"][-6:]] == list(NEW_METRICS)
    for m in man["per_layer"][-6:]:
        assert m["workloads"] == [CELL] and m["moves"] == "serve_tok_s"
    for m in man["per_layer"] + man["end_to_end"]:
        if CELL in m.get("workloads", ()) and m["name"] not in NEW_METRICS:
            assert m["workloads"][-1] == CELL, m["name"]
    tput = next(m for m in man["end_to_end"] if m["name"] == "serve_tok_s")
    assert tput["workloads"][-1] == CELL and tput["bound"] == 0.04
    assert len(man["workloads"]) == 9
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
                                      "reason-context-backlog.json")))
    accepted = json.load(open(os.path.join(BENCH, "traffic",
                                           "rollout-backlog.json")))
    # `why_shapes` is a note beside `who` and the other two `why_*`
    assert set(mix) - {"why_shapes"} <= set(accepted), \
        set(mix) - set(accepted)
    assert mix["kind"] == "requests" and mix["loop"] == "closed"
    assert mix["outstanding"] == "num_slots" and mix["backlog"] == 4096
    assert mix["ramp_requests"] == 96 and mix["schedule_seed"] == 23
    assert mix["prompt"] == {"dist": "lognormal", "median": 2048,
                             "sigma": 0.8, "min": 256, "max": 8192}
    assert mix["answer"] == {"dist": "lognormal", "median": 1536,
                             "sigma": 0.5, "min": 512, "max": 4096}
    conf = cells.Cell(CELL).config
    sv = conf["serving"]
    assert mix["max_total"] == sv["max_total"] == 12288
    # 2.1M tokens of blocks for 192 slots: blocks do not bind
    assert sv["num_slots"] == 192 and sv["num_blocks"] * sv["block_size"] \
        == 4096 * 512


def test_the_configuration_keeps_every_number_of_the_catalog_row():
    conf = cells.Cell(CELL).config
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    row = next(r for r in map(json.loads, open(catalog))
               if r["name"] == "AI21-Jamba2-3B")
    assert conf["source"] == row["source_url"]
    differs = sorted(k for k, v in row["config"].items() if conf[k] != v)
    assert differs == sorted(conf["reduced"]) == ["max_position_embeddings"]
    assert conf["published"] == {k: row["config"][k] for k in differs}
    assert conf["num_hidden_layers"] == 28            # no depth cut
    assert conf["vocab_size"] == 65536                # nor of the vocabulary
    assert conf["parameters_held_here"] == 3029337472
    assert conf["assumed"]["order of the layer types"] and conf["deployment"]


def test_rooflines_count_the_recurrence():
    from harness import rooflines_ssm
    ops, nbytes = rooflines_ssm.ssm_step(185, d_inner=5120, d_state=16)
    assert ops == 185 * 7 * 5120 * 16
    # each slot's state read and written, and its rows: 0.72 MB a slot
    assert nbytes == 185 * (2 * 5120 * 16 * 4 + (3 * 5120 + 32) * 4)
    cf, cb = rooflines_ssm.ssm_scan(512, d_inner=5120, d_state=16)
    assert cf == 512 * 7 * 5120 * 16
    assert cb == 2 * 5120 * 16 * 4 + 512 * (3 * 5120 + 32) * 4


class _Trace:
    busy_s = 2.0

    def kernel_seconds(self, name):
        return {"ssm_step": 0.0104, "ssm_scan": 0.026,
                "paged_decode": 0.002}.get(name, 0.0)

    def kernel_calls(self, name):
        return {"ssm_step": 52, "ssm_scan": 26, "paged_decode": 4}.get(
            name, 0)


def _run(**over):
    from harness import peaks, rooflines
    log = spans_lib.SpanLog()
    log.spans += [("decode_dispatch", 1.0, 1.1, (185, 900, 400_000)),
                  ("decode_dispatch", 1.2, 1.3, (185, 900, 400_000)),
                  ("prefill_dispatch", 1.4, 1.5, (512, 1024))]
    run = {"kind": "serve", "trace": _Trace(), "trace_host_window": (0.9, 2.0),
           "host_window": (0.0, 3.0), "kv_used": [(1.0, 100), (2.0, 1800)],
           "log": log, "rooflines": rooflines, "say": lambda **row: None,
           "peaks": peaks.peaks_for("TPU v5 lite"), "program_trace": None,
           "ssm": {"d_inner": 5120, "d_state": 16, "layers": 26,
                   "state_itemsize": 4, "heads": 20, "kv_heads": 1,
                   "head_dim": 128, "attention_layers": 2, "itemsize": 2,
                   "recurrent_state_bytes": 1_635_778_560,
                   "conv_tail_bytes": 153_354_240,
                   "kv_bytes_per_block": 512 * 1024}}
    run.update(over)
    return run


def test_readers_on_a_synthetic_run():
    from harness import readers_ssm
    # 185 slots a call: 132.6 MB = 161.9 us against 200 us a call
    got = readers_ssm.ssm_step_roofline(_run())
    assert 80.5 < got < 81.5
    # one chunk of 512 tokens in 26 layers: 32.1 MB a layer = 39.2 us
    # against 1,000 us a call
    got = readers_ssm.ssm_scan_roofline(_run())
    assert 3.8 < got < 4.0
    # 400,000 rows a call: 204.8 MB = 250 us against 500 us a call
    got = readers_ssm.paged_decode_mqa_roofline(_run())
    assert 49.5 < got < 50.5
    share = readers_ssm.ssm_state_share(_run())
    assert abs(share - 100 * 1635778560 / (1635778560 + 1800 * 512 * 1024)) \
        < 1e-9


def test_readers_return_none_where_there_is_nothing_to_read():
    from harness import readers_ssm
    for reader in (readers_ssm.ssm_step_roofline,
                   readers_ssm.ssm_scan_roofline,
                   readers_ssm.paged_decode_mqa_roofline,
                   lambda run: readers_ssm.scope_share(run, "attn_ssm")):
        assert reader(_run(trace=None)) is None
        assert reader({"kind": "serve", "log": spans_lib.SpanLog()}) is None
    # a program without the state or the scopes (the parent's)
    assert readers_ssm.ssm_state_share(_run(ssm=None)) is None
    assert readers_ssm.ssm_state_share(
        {"kind": "serve", "log": spans_lib.SpanLog()}) is None
    assert readers_ssm.scope_share(_run(), "ssm_mix") is None
    bare = _run()
    bare["trace"].kernel_seconds = lambda name: 0.0
    assert readers_ssm.ssm_step_roofline(bare) is None
    assert readers_ssm.ssm_scan_roofline(bare) is None
    cell = cells.Cell(CELL)
    for name in NEW_METRICS:
        assert cell.layer_reader(name).read(_run(trace=None, ssm=None)) \
            is None
