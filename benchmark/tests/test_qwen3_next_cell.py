"""The Qwen3-Next cell at the rehearsal size on the CPU. (1) The warm-up
comparison excuses no dropped term: each control comes out NOT correct where
the program comes out correct. (2) The cell's line is well formed in both
trace modes and every listed metric has a reader. (3) The new readers on a
synthetic run: what they count, and None where there is nothing to read.
(4) The traffic file has only keys the generator reads and the parameters
the issue gives; the new entries of BENCHMARK.json were appended and
nothing accepted changed; the configuration keeps every number of the
catalog row. Outside tier-1: `pytest benchmark/tests`."""

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
CELL = "serve-qwen3next-context-qa-backlog"
CONFIG = "qwen3-next-80b-a3b-serve-ep16pp2"
NEW_METRICS = ("gdn_time_share", "gdn_mix_share", "gdn_chunk_roofline",
               "gdn_step_roofline", "attn_gated_prefill_roofline",
               "paged_decode_gqa256_roofline")
APPENDED_TO = ("sched_host_share_tput", "step_prefill_share_tput",
               "decode_occupancy_tput", "kv_blocks_peak_share_tput",
               "prefill_chunk_ms_tput", "kv_relayout_share_tput",
               "dispatch_enqueue_ms_tput", "dispatch_idle_ms_tput",
               "moe_time_share", "moe_experts_roofline",
               "moe_load_max_over_mean", "moe_router_share",
               "host_gap_ms_tput", "gap_runtime_ms_tput",
               "gap_sched_ms_tput", "gap_caller_ms_tput", "ssm_state_share",
               "telemetry_self_ms_tput")


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
    # 2 key heads feed 4 value heads; 8 experts, 3 a token, 4 held
    cfg = b["cfg"]
    assert (cfg.linear_key_heads, cfg.linear_value_heads) == (2, 4)
    assert (cfg.num_experts, cfg.moe_k, cfg.held) == (8, 3, (0, 4))
    assert cfg.n_recurrent_layers == 6 and cfg.n_full_layers == 2


@pytest.mark.parametrize("kw", [
    {"fp8": True}, {"variant": ("state_bf16",)},
    {"variant": ("no_attn_gate",)}, {"variant": ("wrong_held",)},
    {"variant": ("shared_gate_off",)}, {"variant": ("key_heads_tiled",)}])
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
    # what a CPU run can read: the host's and the program's own counts (the
    # device-trace metrics need a device plane)
    assert {"ssm_state_share", "moe_load_max_over_mean",
            "decode_occupancy_tput", "step_prefill_share_tput"} \
        <= set(out["metrics"])
    assert 0.0 < out["metrics"]["ssm_state_share"]["value"] < 100.0


def test_every_listed_metric_has_a_reader_and_new_entries_were_appended():
    man = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cell = cells.Cell(CELL)
    names = [m["name"] for m in cell.per_layer]
    assert set(names) == set(NEW_METRICS) | set(APPENDED_TO)
    for name in names:
        assert cell.layer_reader(name) is not None, name
    at = [c["name"] for c in man["configs"]].index(CONFIG)
    assert at == 10 and man["configs"][at]["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size",
        "max_position_embeddings"]
    w = [w["name"] for w in man["workloads"]].index(CELL)
    assert w == 11 and man["workloads"][w] == dict(
        man["workloads"][w], name=CELL, config=CONFIG,
        traffic="context-qa-backlog", chips=1)
    first = [m["name"] for m in man["per_layer"]].index(NEW_METRICS[0])
    assert [m["name"] for m in man["per_layer"][first:first + 6]] \
        == list(NEW_METRICS)
    for m in man["per_layer"][first:first + 6]:
        assert m["workloads"][0] == CELL and m["moves"] == "serve_tok_s"
    for m in man["per_layer"] + man["end_to_end"]:
        if CELL in m.get("workloads", ()) and m["name"] not in NEW_METRICS:
            assert m["workloads"].index(CELL) >= 1, m["name"]
    tput = next(m for m in man["end_to_end"] if m["name"] == "serve_tok_s")
    assert CELL in tput["workloads"] and tput["bound"] == 0.04
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


def test_the_traffic_file_is_the_issues_and_has_only_keys_the_generator_reads():
    mix = json.load(open(os.path.join(BENCH, "traffic",
                                      "context-qa-backlog.json")))
    accepted = json.load(open(os.path.join(BENCH, "traffic",
                                           "agent-backlog.json")))
    assert set(mix) <= set(accepted), set(mix) - set(accepted)
    conf = cells.Cell(CELL).config
    sv = conf["serving"]
    assert mix["kind"] == "requests" and mix["loop"] == "closed"
    assert mix["outstanding"] == "num_slots" and mix["backlog"] == 1024
    assert mix["ramp_requests"] >= sv["num_slots"]
    assert mix["schedule_seed"] == 31 and mix["sampling"] == "greedy"
    assert mix["shared_prefix_tokens"] == 0
    assert mix["prompt"] == {"dist": "lognormal", "median": 4096,
                             "sigma": 0.8, "min": 512, "max": 23552}
    assert mix["answer"] == {"dist": "lognormal", "median": 384,
                             "sigma": 0.5, "min": 64, "max": 1024}
    assert mix["max_total"] == sv["max_total"] == 24576
    assert sv["num_slots"] % 8 == 0 and sv["num_slots"] <= 48
    assert sv["prefill_chunk"] in (512, 1024)


def test_the_configuration_keeps_every_number_of_the_catalog_row():
    conf = cells.Cell(CELL).config
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    row = next(r for r in map(json.loads, open(catalog))
               if r["name"] == "Qwen3-Next-80B-A3B-Instruct")
    assert conf["source"] == row["source_url"]
    differs = sorted(k for k, v in row["config"].items() if conf[k] != v)
    assert differs == sorted(conf["reduced"]) == [
        "max_position_embeddings", "num_experts", "num_hidden_layers",
        "vocab_size"]
    assert conf["published"] == {k: row["config"][k] for k in differs}
    # the widths, uncut
    assert (conf["hidden_size"], conf["head_dim"]) == (2048, 256)
    assert (conf["linear_num_key_heads"], conf["linear_num_value_heads"],
            conf["linear_key_head_dim"]) == (16, 32, 128)
    assert (conf["num_attention_heads"], conf["num_key_value_heads"]) \
        == (16, 2)
    assert (conf["moe_intermediate_size"], conf["num_experts_per_tok"],
            conf["shared_expert_intermediate_size"]) == (512, 10, 512)
    assert conf["deployment_share"]["experts_held"] == conf["num_experts"]
    assert conf["parameters_published"] == 79674391296
    assert conf["parameters_held_here"] == 3365036416
    assert conf["assumed"] and conf["deployment"] and conf["left_out"]
    import jax.numpy as jnp
    from deepspeed_tpu.models import qwen3_next
    cfg = cells.Cell(CELL).driver().model_config(conf, jnp.bfloat16)
    assert qwen3_next.num_params(cfg) == conf["parameters_held_here"]
    assert cfg.rotary_dim == 64 and cfg.rope_theta == 1e7


def test_rooflines_count_the_recurrence():
    from harness import rooflines_gdn
    flops, nbytes = rooflines_gdn.gdn_step(40, heads=32, head_dim=128)
    assert flops == 40 * 32 * 6 * 128 * 128
    # each slot's state read and written, and its rows: 4.2 MB a slot
    assert nbytes == 40 * (2 * 32 * 128 * 128 * 4 + 32 * 514 * 4)
    assert flops / nbytes < 1.0      # far under the ridge: the bytes bind
    cf, cb = rooflines_gdn.gdn_chunk(512, heads=32, head_dim=128)
    assert cf == 512 * 32 * 6 * 128 * 128
    assert cb == 2 * 32 * 128 * 128 * 4 + 512 * 32 * 514 * 4


class _Trace:
    busy_s = 2.0

    def kernel_seconds(self, name):
        return {"kda_step": 0.016, "paged_decode": 0.012}.get(name, 0.0)

    def kernel_calls(self, name):
        return {"kda_step": 36, "paged_decode": 12}.get(name, 0)


def _run(**over):
    from harness import peaks, rooflines
    log = spans_lib.SpanLog()
    log.spans += [("decode_dispatch", 1.0, 1.1, (40, 400, 220_000)),
                  ("decode_dispatch", 1.2, 1.3, (40, 400, 220_000)),
                  ("prefill_dispatch", 1.4, 1.5, (512, 4096))]
    run = {"kind": "serve", "trace": _Trace(), "trace_host_window": (0.9, 2.0),
           "host_window": (0.0, 3.0), "kv_used": [(1.0, 100), (2.0, 300)],
           "log": log, "rooflines": rooflines, "say": lambda **row: None,
           "peaks": peaks.peaks_for("TPU v5 lite"), "program_trace": None,
           "gdn": {"heads": 32, "key_heads": 16, "head_dim": 128,
                   "layers": 18, "state_itemsize": 4, "attn_heads": 16,
                   "kv_heads": 2, "attn_head_dim": 256,
                   "attention_layers": 6, "itemsize": 2,
                   "recurrent_state_bytes": 1_509_949_440,
                   "conv_tail_bytes": 35_389_440},
           "ssm": {"recurrent_state_bytes": 1_509_949_440,
                   "kv_bytes_per_block": 512 * 12288}}
    run.update(over)
    return run


def test_readers_on_a_synthetic_run():
    from harness import readers_gdn, readers_ssm
    got = readers_gdn.gdn_step_roofline(_run())
    # 40 slots a call: 170.4 MB = 208.1 us against 444 us a call
    assert 46.6 < got < 47.0
    got = readers_gdn.paged_decode_gqa256_roofline(_run())
    # 220,000 rows of 2 x 2 x 256 bf16: 450.6 MB = 550 us against 1 ms
    assert 54.8 < got < 55.2
    share = readers_ssm.ssm_state_share(_run())
    assert abs(share - 100 * 1509949440 / (1509949440
                                           + 300 * 512 * 12288)) < 1e-9


def test_readers_return_none_where_there_is_nothing_to_read():
    from harness import readers_gdn
    for reader in (readers_gdn.gdn_step_roofline,
                   readers_gdn.gdn_chunk_roofline,
                   readers_gdn.attn_gated_prefill_roofline,
                   readers_gdn.paged_decode_gqa256_roofline,
                   lambda run: readers_gdn.scope_share(run, "attn_gdn")):
        assert reader(_run(trace=None)) is None
        assert reader({"kind": "serve", "log": spans_lib.SpanLog()}) is None
    # a program without the scopes (the parent's)
    assert readers_gdn.scope_share(_run(), "gdn_mix") is None
    assert readers_gdn.gdn_chunk_roofline(_run()) is None
    assert readers_gdn.attn_gated_prefill_roofline(_run()) is None
    bare = _run()
    bare["trace"].kernel_seconds = lambda name: 0.0
    assert readers_gdn.gdn_step_roofline(bare) is None
    assert readers_gdn.paged_decode_gqa256_roofline(bare) is None
    cell = cells.Cell(CELL)
    for name in NEW_METRICS:
        assert cell.layer_reader(name).read(_run(trace=None, gdn=None)) \
            is None
