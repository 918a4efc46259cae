"""The gap reader (harness/gaps.py): on hand-made traces the pieces of a gap
partition it to the nanosecond and a gap's time moves between classes only
as far as a boundary moves (where `idle_gaps` renames the whole gap); on
the recorded serving trace (a program from before the `sid` and
`after_empty` marks) it runs and says None for what the recording lacks;
in a CPU rehearsal with telemetry on the ring's `host_gap_ms` is reported
and the three trace metrics are left out. Outside tier-1:
`pytest benchmark/tests`."""

import os
import shutil

import pytest

from harness import gaps
from harness import provenance as pv
from harness import tracereduce as tr
from test_rehearsal import MANIFEST, ROOT, rehearsed, run_cell

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
SERVE = os.path.join(DATA, "small_serve.xplane.pb")
S = gaps.Span


def one_step(t, pull=300, emit=200, enqueue=500, launch=100, wake=50,
             before=120, program=5_000, site="serving.decode", **marks):
    """A decode step that starts at ``t`` ns: (spans, program, end)."""
    d0 = t + 25 + before                  # self 5, expire 10, admit 5, self 5
    e1 = d0 + 5 + enqueue                 # dispatch self 5, then enqueue
    p0 = e1 + launch
    p1 = p0 + program
    w1 = p1 + wake
    pull0 = w1 + 5 + 40                   # dispatch self, decode:after
    emit0 = pull0 + pull + 7
    dec1 = emit0 + emit + 3
    end = dec1 + 20 + 30 + 5              # spill, bookkeep, step self
    spans = [
        S("serve.step", t, end), S("serve.expire", t + 5, t + 15),
        S("serve.admit", t + 15, t + 20),
        S("serve.decode", t + 25, dec1, {"sid": t}),
        S("serve.dispatch", d0, w1 + 5, dict(marks, site=site)),
        S("serve.dispatch.enqueue", d0 + 5, e1),
        S("serve.dispatch.wait", e1, w1),
        S("serve.pull", pull0, pull0 + pull),
        S("serve.emit", emit0, emit0 + emit),
        S("serve.spill", dec1, dec1 + 20),
        S("serve.bookkeep", dec1 + 20, dec1 + 50)]
    return spans, (p0, p1), end


def two_steps(caller=400, first=None, **second):
    s1, prog1, end1 = one_step(1_000, **(first or {}))
    s2, prog2, end2 = one_step(end1 + caller, **second)
    return s1 + s2, [prog1, prog2], end2


def test_pieces_partition_a_gap_to_the_nanosecond():
    spans, programs, end = two_steps()
    (g,) = gaps.split(programs, spans, 0, end + 1)
    assert g["b"] - g["a"] == programs[1][0] - programs[0][1]
    assert sum(g["pieces"].values()) == g["b"] - g["a"]
    assert sum(g["classes"].values()) == g["b"] - g["a"]
    assert g["pieces"] == {
        "wake": 50, "launch": 100, "serve.dispatch.enqueue": 500,
        "serve.dispatch": 10, "serve.pull": 300, "serve.emit": 200,
        "serve.decode:after": 40 + 7 + 3, "serve.decode:before": 120,
        "serve.spill": 20, "serve.bookkeep": 30, "serve.expire": 10,
        "serve.admit": 5, "serve.step": 5 + 5 + 5, "caller": 400}
    assert g["classes"] == {"runtime": 150, "enqueue": 510, "caller": 400,
                            "sched": g["b"] - g["a"] - 150 - 510 - 400}
    assert g["opened"].stats["site"] == g["closed"].stats["site"]
    assert g["opened"] is not g["closed"]


def test_a_program_that_starts_inside_the_enqueue_has_no_launch():
    spans, programs, end = two_steps(launch=-200)
    (g,) = gaps.split(programs, spans, 0, end + 1)
    assert "launch" not in g["pieces"]
    assert g["pieces"]["serve.dispatch.enqueue"] == 300
    assert sum(g["pieces"].values()) == g["b"] - g["a"]
    assert g["closed"].stats["site"] == "serving.decode"


@pytest.mark.parametrize("move", [1, 60, 260])
def test_moving_a_boundary_moves_no_class_by_more_than_the_move(move):
    """The defect of `idle_gaps`, pinned: the pull grows by ``move`` and
    the enqueue shrinks by as much, so the gap's length and its middle
    stay. The wake-up is chosen so that the middle sits on the pull's end:
    a move of one nanosecond renames the whole gap from `serve.decode` to
    `serve.pull` there, and moves one nanosecond of the partition."""
    def gap(shift):
        spans, programs, end = two_steps(
            first={"wake": 1_070, "pull": 300 + shift}, enqueue=500 - shift)
        (g,) = gaps.split(programs, spans, 0, end + 1)
        named = [(sp.name, sp.s * 1e-9, sp.e * 1e-9) for sp in spans]
        mid = 0.5 * (g["a"] + g["b"]) * 1e-9
        return g, pv.innermost(named, mid)

    (g0, name0), (g1, name1) = gap(0), gap(move)
    assert g0["pieces"]["wake"] == 1_070
    assert g0["b"] - g0["a"] == g1["b"] - g1["a"]
    for cls in gaps.CLASSES:
        assert abs(g1["classes"][cls] - g0["classes"][cls]) <= move
    assert g1["classes"]["sched"] - g0["classes"]["sched"] == move
    assert g0["classes"]["enqueue"] - g1["classes"]["enqueue"] == move
    assert g1["pieces"]["serve.pull"] - g0["pieces"]["serve.pull"] == move
    assert (name0, name1) == ("serve.decode", "serve.pull")


def test_gaps_after_an_empty_engine_are_left_out_and_counted():
    s1, prog1, end1 = one_step(1_000, after_empty=1)
    s2, prog2, end2 = one_step(end1 + 400, after_empty=0)
    s3, prog3, end3 = one_step(end2 + 9_000_000, after_empty=1)
    s4, prog4, end4 = one_step(end3 + 400, after_empty=0)
    all_gaps = gaps.split([prog1, prog2, prog3, prog4], s1 + s2 + s3 + s4,
                          0, end4 + 1)
    out = gaps.summarize(all_gaps, 0)
    assert len(all_gaps) == 3
    assert out["gaps"] == 2 and out["left_out_after_empty"] == 1
    assert out["left_out_s"] > 9e-3 > out["counted_s"]
    assert out["gap_ms"]["mean"] < 0.01
    assert out["class_sum_ms"] == pytest.approx(out["gap_ms"]["mean"],
                                                abs=1e-12)
    assert out["by_pair"] == {"decode_after_decode": dict(
        gaps=2, gap_ms=out["gap_ms"]["mean"], **out["class_ms"])}
    # the slowest third of two slots: live 2
    by_live = gaps.summarize(all_gaps, 0, {1_000: 2}.get, 2)["by_live_third"]
    assert by_live == [{"third": 3, "gaps": 1, "live_mean": 2.0,
                        "gap_ms": pytest.approx(out["gap_ms"]["mean"]),
                        "sched_ms": pytest.approx(out["class_ms"]["sched"]),
                        "emit_ms": pytest.approx(200e-6)}]


def test_the_pair_of_sites_is_the_programs_own_word():
    """`prev` on the closing dispatch's annotation names the site before,
    whatever span the clock finds around the opening program's end: here
    the first program "ends" 400 ns late, inside `serve.pull`, where no
    dispatch is."""
    s1, (p0, p1), end1 = one_step(1_000, site="serving.prefill", prev="")
    s2, prog2, end2 = one_step(end1 + 400, prev="serving.prefill")
    s3, prog3, end3 = one_step(end2 + 400, prev="serving.decode")
    spans = s1 + s2 + s3
    late = (p0, p1 + 400, "jit_serve_prefill_slot(1)")
    named = [late, prog2 + ("jit_serve_decode_slots(2)",),
             prog3 + ("jit_serve_decode_slots(2)",)]
    cut = gaps.split(named, spans, 0, end3 + 1)
    assert cut[0]["opened"] is None and cut[0]["before"] == late[2]
    out = gaps.summarize(cut, 0)
    assert set(out["by_pair"]) == {"decode_after_prefill",
                                   "decode_after_decode"}
    assert out["prev_disagrees"] == 0
    assert out["longest"][0]["pair"] in out["by_pair"]
    # without the names the clock's word is all there is
    bare = gaps.summarize(gaps.split([p[:2] for p in named], spans, 0,
                                     end3 + 1), 0)
    assert set(bare["by_pair"]) == {"decode_after_other",
                                    "decode_after_decode"}
    # a program that no dispatch wraps opens a gap of its own: `prev` is
    # the dispatch before THAT, and is not asked
    seed = (prog2[1] + 100, prog2[1] + 150, "jit__threefry_seed")
    other = gaps.summarize(gaps.split(named + [seed], spans, 0, end3 + 1), 0)
    assert set(other["by_pair"]) == {"decode_after_prefill",
                                     "other_after_decode",
                                     "decode_after_other"}
    # a `prev` that the aligned clock contradicts is counted: the offset
    # put a program into the wrong dispatch
    s3[4].stats["prev"] = "serving.prefill"
    wrong = gaps.summarize(gaps.split(named, spans, 0, end3 + 1), 0)
    assert wrong["prev_disagrees"] == 1
    assert wrong["by_pair"]["decode_after_prefill"]["gaps"] == 2


class _Ring:
    """`RequestTracer.spans` over hand-made records: (t0, ..., counts at 5,
    t1 at 6, span id at 7)."""

    def __init__(self, rows):
        self.rows = rows

    def spans(self, name):
        return [(t0, 0, name, 0, 0, counts, t1, i)
                for i, (t0, t1, counts) in enumerate(self.rows)
                if name == gaps.DISPATCH]


def test_the_rings_gap_by_pair_and_its_callers_part():
    d = dict(site="serving.decode", prev="serving.decode", after_empty=0)
    rows = [(0.0, 0.1, dict(d, prev="", after_empty=1)),     # the first ever
            # its gap began before the window did: the profiler's start
            (0.6, 0.7, dict(d, gap_us=150_000, caller_us=90_000)),
            (1.0, 1.1, dict(d, gap_us=2_000, caller_us=100)),
            (2.0, 2.1, dict(d, gap_us=3_000, caller_us=300)),
            (3.0, 3.1, dict(d, gap_us=900_000, caller_us=0, after_empty=1)),
            (4.0, 4.1, dict(d, site="serving.prefill", gap_us=1_000,
                            caller_us=40)),
            (5.0, 5.1, dict(d, gap_us=7_000, caller_us=0))]  # past the window
    run = {"kind": "serve", "tracer": _Ring(rows), "host_window": (0.5, 4.5)}
    kept = gaps.ring_rows(run, run["host_window"])
    assert [c["gap_us"] for c in kept] == [2_000, 3_000, 1_000]
    assert gaps.host_gap_ms(run) == 2.0
    assert gaps.ring_by_pair(kept) == {
        "decode_after_decode": {
            "n": 2, "gap_p50_ms": 2.5, "gap_mean_ms": 2.5,
            "caller_mean_ms": pytest.approx(0.2)},
        "prefill_after_decode": {
            "n": 1, "gap_p50_ms": 1.0, "gap_mean_ms": 1.0,
            "caller_mean_ms": 0.04}}
    # a program from before `prev`
    assert gaps.ring_by_pair([{"gap_us": 5, "site": "serving.decode"}]) \
        is None


# ---- the two clocks ---------------------------------------------------------------

def skewed(skew, steps=4, handed_after=30, seen_after=80):
    """``steps`` decode steps whose programs the "profiler" stamped ``skew``
    ns late, with the runtime's two host events inside each dispatch: the
    program handed over ``handed_after`` ns into the wait, its completion
    seen ``seen_after`` ns after its end. Returns (spans, programs as the
    trace has them, runtime events, the true programs, the end)."""
    spans, true, runtime, t = [], [], [], 1_000
    for i in range(steps):
        site = "serving.prefill" if i == 1 else "serving.decode"
        sp, (p0, p1), t = one_step(t, site=site, launch=100 + 10 * i,
                                   wake=150 - 10 * i, program=5_000 + 500 * i)
        wait = next(x for x in sp if x.name == gaps.WAIT)
        runtime += [(wait.s + handed_after - 20, wait.s + handed_after,
                     "DoEnqueueProgram"),
                    (p1 + seen_after, p1 + seen_after + 60,
                     "tpu::System::Execute=>Done")]
        spans += sp
        true.append((p0, p1))
        t += 400
    kind = {"serving.prefill": "jit_serve_prefill_slot(1)",
            "serving.decode": "jit_serve_decode_slots(2)"}
    names = [kind["serving.prefill" if i == 1 else "serving.decode"]
             for i in range(steps)]
    seen = [(p0 + skew, p1 + skew, n) for (p0, p1), n in zip(true, names)]
    return spans, seen, runtime, true, t


@pytest.mark.parametrize("skew", [-1_500_000, -700, 0, 2_400_000])
def test_align_takes_the_profilers_skew_out(skew):
    """Whatever the profiler's offset, the shifted programs lie inside the
    window physics allows, and the gaps' pieces are those of the true
    programs to within half that window."""
    spans, seen, runtime, true, end = skewed(skew)
    shift, clock = gaps.align(seen, spans, runtime)
    assert clock["aligned"] and clock["bounds_from"] == "runtime events"
    assert clock["paired"] == 4 and clock["offset"] == 0
    lo, hi = clock["window_us"]
    # the launch can be no shorter than 100 - 30, the wake-up than 80
    assert (hi - lo) * 1e3 == pytest.approx((100 - 30) + 80)
    assert abs(shift - skew) <= 75
    want = gaps.split(true, spans, 0, end + 1)
    got = gaps.split([(s - shift, e - shift) for s, e, _ in seen], spans,
                     0, end + 1)
    assert len(want) == len(got) == 3
    for w, g in zip(want, got):
        assert g["b"] - g["a"] == w["b"] - w["a"]
        for cls in gaps.CLASSES:
            assert abs(g["classes"][cls] - w["classes"][cls]) <= 2 * 75
        # nothing between one wait and the next enqueue moves at all
        assert g["classes"]["sched"] == w["classes"]["sched"]
        assert g["classes"]["caller"] == w["classes"]["caller"]


def test_align_without_the_runtimes_events_has_only_the_spans():
    spans, seen, _, _, _ = skewed(-900)
    shift, clock = gaps.align(seen, spans)
    assert clock["aligned"] and clock["bounds_from"] == "spans"
    lo, hi = clock["window_us"]
    # the window is the enqueue span and the wake-up: 500 + 100 + 120 ns wide
    assert (hi - lo) * 1e3 > 700
    assert lo * 1e3 <= -900 <= hi * 1e3


def test_align_says_so_when_nothing_fits():
    spans, seen, runtime, _, _ = skewed(0)
    # the device says every program took longer than the host waited for it
    longer = [(s, e + 1_000_000, n) for s, e, n in seen]
    assert gaps.align(longer, spans, runtime) == (
        0, {"dispatches": 4, "programs": 4, "bounds_from": "runtime events",
            "aligned": False})
    assert gaps.align([(s, e, "") for s, e, _ in seen], spans, runtime)[1][
        "aligned"] is False


def test_a_window_cuts_the_programs_it_does_not_hold_whole():
    spans, programs, end = two_steps()
    assert gaps.split(programs, spans, programs[0][0] + 1, end) == []
    assert len(gaps.split(programs, spans, programs[0][0], end)) == 1


# ---- the recorded trace: a program from before `sid` and `after_empty` -------

class _Cell:
    name = "recorded"

    def __init__(self, root):
        self.root = root


def _recorded(root, tracer=None):
    there = root / ".bench_out" / "trace" / _Cell.name / "plugins" / \
        "profile" / "x"
    there.mkdir(parents=True)
    shutil.copy(SERVE, there / "small.xplane.pb")
    said = []
    return {"kind": "serve", "cell": _Cell(str(root)), "tracer": tracer,
            "trace": tr.load(SERVE, ("step",), "bench_traced_window"),
            "num_slots": 3, "say": lambda **row: said.append(row),
            "said": said}


@pytest.fixture(scope="module")
def recorded_run(tmp_path_factory):
    return _recorded(tmp_path_factory.mktemp("gaps"))


def test_recorded_trace_is_cut_and_says_none_for_what_it_lacks(recorded_run):
    run = recorded_run
    out = gaps.of_run(run)
    assert gaps.of_run(run) is out and len(run["said"]) == 1
    assert run["said"][0]["info"] == "dispatch_gaps"
    assert out["programs_from"] == pv.MODULES_LINE and out["gaps"] > 20
    # the profiler stamped this session's device events 1.5 ms early: every
    # decode program "ran" before the runtime handed it to the device
    clock = out["clock"]
    assert clock["aligned"] and clock["bounds_from"] == "runtime events"
    assert clock["paired"] == clock["dispatches"] == 11
    assert clock["shift_us"] == pytest.approx(-1510.5, abs=1.0)
    lo, hi = clock["window_us"]
    assert lo < clock["shift_us"] < hi and hi - lo < 250
    # so a program starts after its enqueue returned and ends before the
    # host wakes: both pieces exist, and the enqueue span is the gap's whole
    assert out["launch_ms"] > 0.03 and out["wake_ms"] > 0.03
    steady = out["by_pair"]["decode_after_decode"]
    assert steady["runtime"] == pytest.approx(0.54, abs=0.13)
    assert steady["enqueue"] > 1.8
    # the classes partition the mean gap
    assert out["class_sum_ms"] == pytest.approx(out["gap_ms"]["mean"],
                                                abs=1e-9)
    assert set(out["class_ms"]) == set(gaps.CLASSES)
    for cls in gaps.CLASSES:
        assert gaps.class_ms(run, cls) == out["class_ms"][cls] >= 0
    # the gaps are the device's idle time but for the window's two ends
    assert out["counted_s"] <= out["idle_s"]
    assert out["counted_s"] == pytest.approx(out["idle_s"], rel=0.1)
    # and the rest is named: idle inside a program, idle at the two ends
    assert out["inside_programs_idle_s"] >= 0 and out["edge_idle_s"] >= 0
    assert out["counted_s"] + out["inside_programs_idle_s"] \
        + out["edge_idle_s"] == pytest.approx(out["idle_s"], abs=2e-5)
    assert all("serve" not in name for name in out["other_programs"])
    assert {"decode_after_decode", "prefill_after_prefill",
            "decode_after_prefill"} <= set(out["by_pair"])
    assert len(out["longest"]) == 3
    assert out["longest"][0]["ms"] >= out["longest"][1]["ms"]
    # what that older program did not record
    assert out["left_out_after_empty"] is None
    assert out["by_live_third"] is None
    assert out["ring_tail_gap_ms"] is None
    assert out["prev_disagrees"] is None and out["ring_by_pair"] is None
    assert out["caller_check_ms"] is None
    assert gaps.host_gap_ms(run) is None


def test_a_trace_whose_clocks_do_not_align_gives_no_class(tmp_path,
                                                          monkeypatch):
    """Unaligned stamps are the profiler's skew, a millisecond and more:
    the line is printed and says so, the three trace metrics are left out
    (the ring's own account needs no alignment)."""
    monkeypatch.setattr(gaps, "MAX_SKEW_NS", -1)     # no offset is allowed
    run = _recorded(tmp_path)
    out = gaps.of_run(run)
    assert out["clock"] == {"dispatches": 11, "programs": 11,
                            "bounds_from": "runtime events",
                            "aligned": False}
    assert run["said"][0]["info"] == "dispatch_gaps" and out["gaps"] > 20
    assert out["class_sum_ms"] == pytest.approx(out["gap_ms"]["mean"])
    for cls in gaps.CLASSES:
        assert gaps.class_ms(run, cls) is None


def test_the_callers_part_by_two_accounts(tmp_path):
    """`caller_us` of the ring records of the traced tail beside the
    trace's `caller` class: one quantity, one account from each clock."""
    d = dict(site="serving.decode", prev="serving.decode", after_empty=0)
    ring = _Ring([(1.0, 1.1, dict(d, gap_us=2_000, caller_us=100)),
                  (2.0, 2.1, dict(d, gap_us=3_000, caller_us=300))])
    run = _recorded(tmp_path, tracer=ring)
    run["trace_host_window"] = run["host_window"] = (0.0, 3.0)
    out = gaps.of_run(run)
    check = out["caller_check_ms"]
    assert check["ring"] == pytest.approx(0.2)
    assert check["trace"] == out["class_ms"]["caller"]
    assert check["ring_less_trace"] == pytest.approx(
        0.2 - out["class_ms"]["caller"])
    assert out["ring_tail_gap_ms"]["n"] == 2
    assert out["ring_by_pair"]["decode_after_decode"]["n"] == 2


def test_no_trace_no_numbers():
    run = {"kind": "serve", "trace": None, "tracer": None,
           "host_window": (0.0, 1.0)}
    assert gaps.of_run(run) is None
    assert gaps.class_ms(run, gaps.SCHED) is None
    assert gaps.host_gap_ms(run) is None
    assert gaps.host_gap_ms({"kind": "train"}) is None


# ---- the CPU rehearsal, telemetry on --------------------------------------------

NEW = [m for m in MANIFEST["per_layer"] if m["name"].startswith(
    ("host_gap_ms", "gap_runtime_ms", "gap_sched_ms", "gap_caller_ms"))]


def test_the_eight_entries_and_their_readers():
    # found by name, not by place: a later PR appends after them
    assert [m["name"] for m in NEW] == [
        f"{stem}{suffix}" for stem in ("host_gap_ms", "gap_runtime_ms",
                                       "gap_sched_ms", "gap_caller_ms")
        for suffix in ("", "_tput")]
    for m in NEW:
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "layer_metrics", m["name"] + ".py"))
        moved = next(e for e in MANIFEST["end_to_end"]
                     if e["name"] == m["moves"])
        assert set(m["workloads"]) <= set(moved["workloads"])
        assert m["better"] == "lower" and m["unit"] == "ms"
        assert (m["source"] == "program_span") == m["name"].startswith(
            "host_gap_ms")


def test_rehearsal_reports_the_rings_gap_and_leaves_the_traces_out(
        monkeypatch):
    monkeypatch.setenv("DS_TELEMETRY", "on")
    proc = run_cell(ROOT, "serve-gpt2xl-chat", "--trace", "1", "--rehearse")
    assert proc.returncode == 3, proc.stderr[-2000:]
    out = rehearsed(proc)
    assert out["correct"] is True
    assert out["metrics"]["host_gap_ms"]["value"] > 0
    assert out["metrics"]["host_gap_ms"]["unit"] == "ms"
    assert not {"gap_runtime_ms", "gap_sched_ms",
                "gap_caller_ms"} & set(out["metrics"])
    assert '"dispatch_gaps"' not in proc.stdout
