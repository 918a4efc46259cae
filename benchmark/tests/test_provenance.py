"""The provenance readers (harness/provenance.py) on a small serving trace
recorded on the chip with telemetry on (`tests/record_serving_trace.py`,
TPU v5e: a 2-layer model through `ServingEngine`, three requests), and on
the two older recorded traces. Outside tier-1: `pytest benchmark/tests`."""

import json
import os

import pytest

from harness import provenance as pv
from harness import tracereduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
SERVE = os.path.join(DATA, "small_serve.xplane.pb")
FACTS = json.load(open(os.path.join(DATA, "small_serve.json")))
PROGRAM_TABLE = json.load(open(os.path.join(
    DATA, "small_serve.provenance.json")))["provenance"]


@pytest.fixture(scope="module")
def base():
    return tr.load(SERVE, ("step",), "bench_traced_window")


@pytest.fixture(scope="module")
def pt(base):
    return pv.load(SERVE, base.t0, base.t1)


def program(pt, stem):
    return next(p for p in pt.tables if pv.short_program(p) == stem)


def seconds(pt, prog, op):
    return sum(x for (p, o), _, _, x in pt.ops[0] if p == prog and o == op)


def test_every_program_that_ran_has_its_module(pt):
    assert {pv.short_program(p) for p in pt.tables} >= {
        "jit_serve_prefill_slot", "jit_serve_decode_slots"}
    ran = {p for (p, _), _, _, _ in pt.ops[0]}
    assert "no_program" not in ran and ran <= set(pt.tables)


@pytest.mark.parametrize("op", ["conditional", "add_rsqrt_fusion.5",
                                "copy-done.1"])
def test_same_named_instructions_split_by_program(pt, base, op):
    """The prefill and the decode program both have an instruction of this
    name; summed by name alone (tracereduce.top_ops) they are one row."""
    d = seconds(pt, program(pt, "jit_serve_decode_slots"), op)
    f = seconds(pt, program(pt, "jit_serve_prefill_slot"), op)
    merged = sum(x for n, _, _, x in base.devices[0].events if n == op)
    assert d > 0 and f > 0 and d != f
    assert d + f == pytest.approx(merged, rel=1e-9)


def test_top_ops_carry_program_scope_and_source_line(pt, base):
    rows = pt.top_ops(10)
    assert rows[0][0] == ("paged_decode.9@jit_serve_decode_slots/while/body/"
                          "closed_call/paged_attn/paged_decode/pallas_call "
                          "deepspeed_tpu/ops/attention/paged.py:352")
    assert rows[0][1] == pytest.approx(1.57429e-4, rel=1e-4)
    assert rows[1][0].startswith(
        "fusion.228@jit_serve_prefill_slot/while/body/closed_call/kv_write/")
    assert " deepspeed_tpu/inference/engine.py:" in rows[1][0]
    for label, _ in rows:
        op, rest = label.split("@", 1)
        assert rest.startswith("jit_serve_") and ".py:" in rest, label
    # the same seconds as the reduction that does not know the program
    assert sum(x for dev in pt.ops for _, _, _, x in dev) == pytest.approx(
        sum(x for _, _, _, x in base.devices[0].events), rel=1e-9)


def test_trace_table_agrees_with_the_programs_own(pt):
    """The program's table (cost_registry.to_json, parsed by
    telemetry/costs.py from compiled.as_text()) and the benchmark's (parsed
    from the trace's HLO protos) are two parsers over what was loaded."""
    for pid, stem in (("decode_slots", "jit_serve_decode_slots"),
                      ("prefill_slot", "jit_serve_prefill_slot")):
        theirs = PROGRAM_TABLE[pid]
        assert theirs["module"] == stem
        mine = pt.tables[program(pt, stem)]
        for name, e in theirs["instructions"].items():
            assert mine[name]["scope"] == e["scope"], name
            # (an async pair prints as async-start in one text form and
            # as slice-start in the other)
            if "-start" not in e["opcode"] and "-done" not in e["opcode"]:
                assert mine[name]["opcode"] == e["opcode"]
            assert mine[name]["source"] == e["source"]
            assert mine[name].get("inferred") == e.get("inferred")


def test_kv_relayout_share_and_pool_shape_rule(pt, base):
    said = []
    run = {"kind": "serve", "trace": base, "program_trace": pt,
           "say": lambda **row: said.append(row),
           **{k: FACTS[k] for k in ("pool_blocks", "block_size", "kv_heads",
                                    "head_dim")}}
    # the tiny model's relayout copies are of the two layers' stacked pools
    # (bf16[2,49,16,4,64]), outside the layer loop, with no scope of their
    # own: found by shape (54.699 us of 624.772 busy: 8.7550%). Four
    # reshapes run on the device inside the cache's scopes (reshape.681 /
    # .682 in kv_write, .683 and .420 in paged_attn: 7.498 us, 1.2001%)
    # and count as the copies do
    assert pv.kv_relayout_share(run) == pytest.approx(9.9552, rel=1e-4)
    by = said[0]["seconds_by_program_scope"]
    assert set(by) == {
        "jit_serve_prefill_slot/no_scope", "jit_serve_decode_slots/no_scope",
        "jit_serve_prefill_slot/while/body/closed_call/kv_write/"
        "squeeze;attn_qkv",
        "jit_serve_prefill_slot/while/body/closed_call/paged_attn/"
        "ckgd,skd->ckgs",
        "jit_serve_decode_slots/while/body/closed_call/paged_attn/"
        "reshape;attn_qkv"}
    in_scopes = sum(v for k, v in by.items() if "no_scope" not in k)
    assert in_scopes == pytest.approx(7.498e-6, rel=1e-3)
    assert in_scopes == pytest.approx(sum(
        seconds(pt, program(pt, "jit_serve_" + stem), op) for stem, op in (
            ("prefill_slot", "reshape.681"), ("prefill_slot", "reshape.682"),
            ("prefill_slot", "reshape.683"),
            ("decode_slots", "reshape.420"))), rel=1e-9)
    # a reshape outside the cache's scopes is the model's own
    assert seconds(pt, program(pt, "jit_serve_decode_slots"),
                   "reshape.421") > 0
    assert pv.remat_time_share(run) is None          # not a train run
    copy = {"opcode": "copy", "scope": "while/body/kv_gather",
            "shape": "bf16[8,8]{1,0}"}
    assert pv.is_kv_relayout(copy, (49, 16, 4, 64))          # by scope
    assert pv.is_kv_relayout(dict(copy, scope="", shape="bf16[1,49,16,4,64]"
                                  "{1,4,3,2,0}"), (49, 16, 4, 64))
    assert not pv.is_kv_relayout(dict(copy, scope="mlp"), (49, 16, 4, 64))
    assert not pv.is_kv_relayout(dict(copy, opcode="fusion"),
                                 (49, 16, 4, 64))
    # the unfold of a prefill chunk's gathered blocks (PR 25's reshape.714)
    assert pv.is_kv_relayout(dict(copy, opcode="reshape",
                                  shape="bf16[1024,25,64]{2,1,0}"),
                             (49, 16, 4, 64))
    assert not pv.is_kv_relayout(dict(copy, opcode="reshape", scope="mlp"),
                                 (49, 16, 4, 64))


def test_idle_gaps_go_to_the_programs_innermost_span(pt, base):
    sums, single = pt.idle_gaps(base.host_spans)
    assert sum(sums.values()) == pytest.approx(base.window_s - base.busy_s,
                                               rel=1e-9)
    # a tiny model's device is idle while the host prepares the launch
    assert max(sums, key=sums.get) == "serve.dispatch.enqueue"
    assert sums["serve.dispatch.enqueue"] == pytest.approx(0.0278284,
                                                           rel=1e-4)
    assert sums["serve.dispatch.wait"] == pytest.approx(0.0065455, rel=1e-4)
    assert sums.get("no_span", 0.0) < 0.003 * sum(sums.values())
    assert set(sums) <= {"no_span", "serve.admit", "serve.prefill",
                         "serve.decode", "serve.dispatch.enqueue",
                         "serve.dispatch.wait", "serve.pull", "serve.emit",
                         "serve.bookkeep", "serve.step", "serve.expire",
                         "serve.spill", "serve.dispatch"}


def test_program_spans_on_the_profilers_clock_match_the_ring(pt):
    """The same spans twice: in the trace on the profiler's clock, and in
    the program's ring on perf_counter (the recorder's facts)."""
    names = [n for n, _, _ in pt.spans]
    assert names.count("serve.step") == FACTS["steps"]
    assert names.count("serve.prefill") == FACTS["prefill_dispatches"]
    assert names.count("serve.dispatch") == FACTS["dispatches"] == \
        names.count("serve.dispatch.enqueue") == \
        names.count("serve.dispatch.wait")
    summary = pv.program_span_summary(pt)
    assert summary["dispatches_seen_whole"] == FACTS["dispatches"]
    assert summary["dispatch_enqueue_ms_median"] == pytest.approx(
        FACTS["ring_enqueue_ms_median"], rel=0.01)
    assert summary["dispatch_idle_ms"] == pytest.approx(3.5791, rel=1e-3)
    # every child lies inside a span of its parent's name
    for child, parent in (("serve.dispatch.enqueue", "serve.dispatch"),
                          ("serve.dispatch.wait", "serve.dispatch"),
                          ("serve.dispatch", "serve.step")):
        for n, s, e in pt.spans:
            if n == child:
                assert any(p == parent and ps <= s and e <= pe
                           for p, ps, pe in pt.spans), (child, s)


@pytest.mark.parametrize("name,chips", [("small_1chip", 1),
                                        ("small_4chip", 4)])
def test_older_traces_without_program_spans(name, chips):
    """A trace of a program that has no spans and no scopes of ours: the
    operations still get their program and source line, the idle gaps fall
    to the benchmark's outside spans, and the serving summary is empty."""
    path = os.path.join(DATA, name + ".xplane.pb")
    b = tr.load(path, ("step", "decode_dispatch"), "bench_traced_window")
    t = pv.load(path, b.t0, b.t1)
    assert len(t.ops) == chips and t.spans == []
    label, _ = t.top_ops(1)[0]
    assert "@jit_step/while/body" in label
    assert label.endswith("benchmark/tests/record_trace.py:34")
    sums, _ = t.idle_gaps(b.host_spans)
    assert set(sums) <= {"decode_dispatch", "step", "no_span"}
    assert pv.program_span_summary(t) == {}
    run = {"kind": "train", "trace": b, "program_trace": t,
           "say": lambda **row: None}
    assert pv.remat_time_share(run) == 0.0


def test_parse_hlo_fusion_root_and_inferred_copy():
    text = '''HloModule jit_f, is_scheduled=true

FileNames
1 "/w/deepspeed_tpu/inference/engine.py"

FunctionNames
1 "f"

FileLocations
1 {file_name_id=1 function_name_id=1 line=77 end_line=77 column=1 end_column=9}

StackFrames
1 {file_location_id=1 parent_frame_id=1}

%fused (p0: bf16[8]) -> bf16[8] {
  %p0 = bf16[8]{0} parameter(0)
  ROOT %neg = bf16[8]{0} negate(%p0), metadata={op_name="jit(f)/while/body/kv_gather/neg" stack_frame_id=1}
}

ENTRY %main (a: bf16[8]) -> bf16[8] {
  %a = bf16[8]{0} parameter(0)
  %copy.3 = bf16[8]{0} copy(%a)
  %k = bf16[8]{0} custom-call(%copy.3), custom_call_target="x", frontend_attributes={kernel_metadata={}}, metadata={op_name="jit(f)/paged_attn/k/pallas_call" stack_frame_id=1}
  ROOT %fusion.1 = bf16[8]{0} fusion(%k), kind=kLoop, calls=%fused
}
'''
    t = pv.parse_hlo(text)
    assert t["fusion.1"]["scope"] == "while/body/kv_gather"
    assert t["fusion.1"]["source"] == "/w/deepspeed_tpu/inference/engine.py:77"
    assert t["k"]["scope"] == "paged_attn/k" and t["k"]["op"] == "pallas_call"
    assert t["copy.3"]["inferred"] and t["copy.3"]["scope"] == "paged_attn/k"
    assert "neg" not in t
    assert pv.dims_of("bf16[1,1089,16,25,64]{1,4,3,2,0:T(8,128)(2,1)}") == (
        1, 1089, 16, 25, 64)
