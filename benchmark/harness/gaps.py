"""The time between two device programs of a traced serving run, cut up by
what the host was doing: the arithmetic behind ``gap_runtime_ms``,
``gap_sched_ms``, ``gap_caller_ms`` (traced tail, one clock) and
``host_gap_ms`` (the program's own account, from its ring), and the
``dispatch_gaps`` line.

On device 0 the programs in order (the ``XLA Modules`` line; without one,
the merged busy intervals); for each pair of neighbours seen whole inside
the traced window the gap [end of one, start of the next] is cut at every
boundary of a ``serve.*`` span of the host plane (the program's own
``TraceAnnotation``s, telemetry on). Each piece belongs to the innermost
span that covers it; a piece of the self time of a span that holds a
``serve.dispatch`` is named ``<span>:before`` or ``<span>:after`` by the
side of that dispatch it lies on; a piece that no program span covers is
``caller``. The pieces of a gap sum to the gap exactly (whole nanoseconds):
a partition, where ``provenance.ProgramTrace.idle_gaps`` gives a whole gap
to the span that covers its middle.

| class | pieces |
|---|---|
| ``runtime`` | inside ``serve.dispatch.wait``: ``launch`` (the jitted call returned, the program has not started) and ``wake`` (the program ended, the host has not returned) |
| ``enqueue`` | inside ``serve.dispatch.enqueue``, and ``serve.dispatch``'s self |
| ``sched`` | every other ``serve.*`` piece |
| ``caller`` | no program span: whoever calls ``step`` |

**The two clocks.** The profiler puts the device's events and the host's
on one axis, but its alignment of the two is off by a different amount in
every session, up to milliseconds (a program of the recorded trace "runs"
2.4 ms after the host was told it had finished; of two runs of one cell one
names every gap ``serve.dispatch.wait`` and the other ``serve.pull``). So
the device's events are shifted first (``align``) by the one offset that
physics allows: a program starts after it was enqueued and ends before its
completion was seen. The host-side bounds of each dispatch's program come
from the runtime's own events inside the ``serve.dispatch`` span
(``DoEnqueueProgram``; ``tpu::System::Execute=>Done``) and, without them,
from the spans (the enqueue's start, the wait's end: a much wider window).
The offset is the middle of the window that every dispatch allows, and the
line says the window: that much of ``launch`` against ``wake`` is not known.
``sched`` and ``caller`` lie between one dispatch's wait and the next one's
enqueue, host clock both: no offset inside the window moves them. Where no
offset fits (``aligned`` false: the runtime's events renamed, a trace cut
short) the line is still printed, with its ``clock`` saying so, and the
three trace metrics are left out: stamps off by a millisecond move a
millisecond between ``runtime`` and the classes beside it.

**What the program says itself.** A ``serve.dispatch`` annotation carries
``prev``, the site of the dispatch before it: the pair of sites of a gap
(``by_pair``) comes from the closing dispatch's ``prev``, which no clock
put there, and ``prev_disagrees`` counts the gaps whose opening program the
aligned clock finds inside a dispatch of another site (0, or the offset is
wrong). The ring record has ``gap_us`` and ``caller_us``: the line sets the
ring's ``caller`` over the tail beside the trace's (``caller_check_ms``:
two accounts of one quantity, one from each clock) and gives the ring's gap
by pair of sites over the whole untraced window (``ring_by_pair``).

A gap whose closing dispatch (the ``serve.dispatch`` that covers the next
program's start) carries ``after_empty=1`` is a pause, not a cost: left
out and counted. A trace of a program from before those marks (no ``sid``,
no ``after_empty``) is cut all the same and reports None for what it
lacks."""

import bisect
from collections import defaultdict

from harness import provenance
from harness import tracereduce as tr
from harness.stats import median, pct

RUNTIME, ENQUEUE, SCHED, CALLER = CLASSES = ("runtime", "enqueue", "sched",
                                             "caller")
DISPATCH = "serve.dispatch"
WAIT, ENQ = DISPATCH + ".wait", DISPATCH + ".enqueue"
SPAN_PREFIX = "serve."
# the runtime's own host events that bound a program from outside: it was
# handed to the device; its completion was seen (libtpu's names)
ENQUEUED = ("DoEnqueueProgram",)
DONE = ("tpu::System::Execute=>Done",)
SERVING_PROGRAM = "jit_serve_"
ALIGN_SLACK_NS = 50_000     # drift and rounding a window may be short by
MAX_SKEW_NS = 5_000_000     # the profiler is off by milliseconds, not steps


class Span:
    """One host span: whole nanoseconds [s, e), the annotation's counts."""

    __slots__ = ("name", "s", "e", "stats", "parent", "dispatches", "kids")

    def __init__(self, name, s, e, stats=None):
        self.name, self.s, self.e = name, int(s), int(e)
        self.stats = stats or {}
        self.parent, self.dispatches, self.kids = None, [], {}

    def up(self, name):
        """This span or the nearest one around it called ``name``."""
        sp = self
        while sp is not None and sp.name != name:
            sp = sp.parent
        return sp


def timeline(spans):
    """[(start, end, innermost span or None)], ascending and disjoint, from
    the first span's start to the last one's end; links every span to its
    parent and every ``serve.dispatch`` to the span it runs in. Spans of
    one thread nest; one that outlasts its parent (a rounding) is cut."""
    segs, stack, at = [], [], None
    for sp in spans:    # linked anew: align and split each ask
        sp.parent, sp.dispatches, sp.kids = None, [], {}

    def close(upto):
        nonlocal at
        if at is not None and upto > at:
            segs.append((at, upto, stack[-1] if stack else None))
        at = upto if at is None else max(at, upto)

    for sp in sorted(spans, key=lambda x: (x.s, -x.e)):
        while stack and stack[-1].e <= sp.s:
            close(stack[-1].e)
            stack.pop()
        close(sp.s)
        if stack:
            sp.e = min(sp.e, stack[-1].e)
            sp.parent = stack[-1]
            sp.parent.kids[sp.name] = sp
            if sp.name == DISPATCH:
                sp.parent.dispatches.append(sp)
        stack.append(sp)
    while stack:
        close(stack[-1].e)
        stack.pop()
    return segs


def piece_of(sp, u, v, a):
    """(piece name, class) of [u, v) inside its innermost span ``sp``, in
    a gap that opened at ``a``."""
    if sp is None:
        return CALLER, CALLER
    if sp.name == WAIT:
        # the wait that was already blocked when the program ended
        return ("wake" if sp.s <= a else "launch"), RUNTIME
    if sp.name in (ENQ, DISPATCH):
        return sp.name, ENQUEUE
    if sp.dispatches:
        side = "before" if v <= sp.dispatches[-1].s else "after"
        return f"{sp.name}:{side}", SCHED
    return sp.name, SCHED


def _kind(name):
    return "prefill" if "prefill" in str(name) else "decode"


def align(programs, spans, runtime=()):
    """(shift, info): ``shift`` ns to take from every device stamp so that
    each dispatch's program lies between the host-side bounds of its start
    and its end. ``programs``: [(start, end, name)]; ``runtime``: [(start,
    end, name)] of the runtime's ``ENQUEUED`` / ``DONE`` host events. The
    k-th serving program belongs to the (k + offset)-th dispatch that went
    through, for the offset (the trace may cut either list at its ends)
    that pairs prefill programs with prefill dispatches and leaves a
    window: the most pairs, then the smallest shift. (0, why) where none
    does."""
    timeline(spans)
    mine = sorted((s, e) + (_kind(n),) for s, e, n in programs
                  if SERVING_PROGRAM in str(n))
    marks = sorted(runtime)
    starts = [m[0] for m in marks]
    bounds, from_runtime = [], 0
    for d in sorted((sp for sp in spans if sp.name == DISPATCH
                     and WAIT in sp.kids and ENQ in sp.kids),
                    key=lambda sp: sp.s):
        lower, upper = d.kids[ENQ].s, d.kids[WAIT].e
        inside = marks[bisect.bisect_left(starts, d.s):
                       bisect.bisect_right(starts, d.e)]
        # the dispatch's own program is the last one handed over inside it
        handed = max((e for _, e, n in inside if n in ENQUEUED and e <= d.e),
                     default=None)
        seen = max((s for s, _, n in inside if n in DONE
                    and handed is not None and s >= handed), default=None)
        if seen is not None:
            lower, upper = max(lower, handed), min(upper, seen)
            from_runtime += 1
        bounds.append((lower, upper, _kind(d.stats.get("site"))))
    info = {"dispatches": len(bounds), "programs": len(mine),
            "bounds_from": "runtime events" if bounds
            and from_runtime == len(bounds) else "spans"}
    best = None
    for k in range(-3, 4):
        pairs = [(p, bounds[j + k]) for j, p in enumerate(mine)
                 if 0 <= j + k < len(bounds)]
        if len(pairs) < max(1, min(len(mine), len(bounds)) - 3) \
                or any(p[2] != b[2] for p, b in pairs):
            continue
        lo = max(p[1] - b[1] for p, b in pairs)     # ends before it was seen
        hi = min(p[0] - b[0] for p, b in pairs)     # starts after handed over
        if lo - hi > ALIGN_SLACK_NS:
            continue
        mid = (lo + hi) // 2
        # the most pairs, then the smallest shift: a wrong offset shifts
        # by a whole step
        rank = (-len(pairs), abs(mid))
        if abs(mid) <= MAX_SKEW_NS and (best is None or rank < best[0]):
            best = (rank, mid, lo, hi, k, len(pairs))
    if best is None:
        return 0, dict(info, aligned=False)
    _, mid, lo, hi, k, n = best
    return mid, dict(info, aligned=True, shift_us=mid * 1e-3,
                     window_us=[lo * 1e-3, hi * 1e-3], offset=k, paired=n)


def split(programs, spans, t0, t1):
    """``programs``: [(start, end)] or [(start, end, name)] of one device,
    whole nanoseconds. Returns one dict per gap between two neighbours that
    lie wholly inside [t0, t1]: ``a``, ``b``, ``pieces`` {name: ns},
    ``classes`` {class: ns}, ``opened`` / ``closed`` (the ``serve.dispatch``
    spans around the program before and the program after, or None),
    ``before`` (the name of the program before, None where not given)."""
    segs = timeline(spans)
    starts = [s for s, _, _ in segs]

    def at(t):
        """The innermost span at instant ``t``."""
        i = bisect.bisect_right(starts, t) - 1
        return segs[i][2] if i >= 0 and t < segs[i][1] else None

    whole = sorted(((int(p[0]), int(p[1]), p[2] if len(p) > 2 else None)
                    for p in programs if p[0] >= t0 and p[1] <= t1),
                   key=lambda p: p[:2])
    out = []
    for (_, a, before), (b, _, _) in zip(whole, whole[1:]):
        if b <= a:
            continue
        pieces, classes = defaultdict(int), defaultdict(int)
        t = a
        i = max(0, bisect.bisect_right(starts, a) - 1)
        while t < b:
            while i < len(segs) and segs[i][1] <= t:
                i += 1
            if i < len(segs) and segs[i][0] <= t:
                upto, sp = min(b, segs[i][1]), segs[i][2]
            else:       # before the first span, after the last
                upto, sp = (min(b, segs[i][0]) if i < len(segs) else b), None
            name, cls = piece_of(sp, t, upto, a)
            pieces[name] += upto - t
            classes[cls] += upto - t
            t = upto
        inner_a, inner_b = at(a), at(b)
        out.append({"a": a, "b": b, "before": before,
                    "pieces": dict(pieces), "classes": dict(classes),
                    "opened": inner_a.up(DISPATCH) if inner_a else None,
                    "closed": inner_b.up(DISPATCH) if inner_b else None})
    return out


# ---- the trace ----------------------------------------------------------------

def load(path):
    """(programs of device 0 [(start, end, name)], the line they came from,
    serve.* spans of the host plane, the runtime's ``ENQUEUED`` / ``DONE``
    host events [(start, end, name)]), whole nanoseconds."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    programs, source, spans, runtime = [], None, [], []
    device = min((p.name for p in pd.planes
                  if p.name.startswith(tr.DEVICE_PLANE_PREFIX)), default=None)
    for plane in pd.planes:
        if plane.name == device:
            lines = {ln.name: ln for ln in plane.lines}
            if provenance.MODULES_LINE in lines:
                source = provenance.MODULES_LINE
                programs = [(round(ev.start_ns),
                             round(ev.start_ns + ev.duration_ns), ev.name)
                            for ev in lines[source].events]
            elif tr.OPS_LINE in lines:
                source = "busy intervals"
                programs = [(round(s), round(e), "") for s, e in tr.merge(
                    [(ev.start_ns, ev.start_ns + ev.duration_ns)
                     for ev in lines[tr.OPS_LINE].events])]
        elif plane.name == tr.HOST_PLANE:
            for ln in plane.lines:
                for ev in ln.events:
                    name = provenance.span_name(ev.name)
                    if name.startswith(SPAN_PREFIX):
                        spans.append(Span(
                            name, round(ev.start_ns),
                            round(ev.start_ns + ev.duration_ns),
                            dict(ev.stats)))
                    elif name in ENQUEUED or name in DONE:
                        runtime.append((round(ev.start_ns), round(
                            ev.start_ns + ev.duration_ns), name))
    return sorted(programs), source, spans, runtime


# ---- the reduction --------------------------------------------------------------

def _short(site):
    return str(site).rsplit(".", 1)[-1] if site else "other"


def _site(dispatch):
    return _short(dispatch.stats.get("site") if dispatch is not None
                  else None)


def _prev(g):
    """The site of the dispatch before the one that closes gap ``g``, by
    the program's own word (``prev`` on the annotation), where the program
    before the gap is one a dispatch wraps; None from an older program and
    after a program that no dispatch wraps."""
    prev = g["closed"].stats.get("prev") if g["closed"] is not None else None
    before = g.get("before")
    wrapped = SERVING_PROGRAM in str(before) if before \
        else g["opened"] is not None
    return _short(prev) if prev and wrapped else None


def _pair(g):
    return f"{_site(g['closed'])}_after_{_prev(g) or _site(g['opened'])}"


def _ms(ns, n):
    return ns * 1e-6 / n if n else None


def _by_class(gaps):
    n = len(gaps)
    return {c: _ms(sum(g["classes"].get(c, 0) for g in gaps), n)
            for c in CLASSES}


def summarize(gaps, t0, live_of=None, num_slots=None):
    """The ``dispatch_gaps`` line's numbers from ``split``'s gaps.
    ``live_of(span id) -> live slots`` of a ``serve.decode`` ring record
    (None where the ring has none)."""
    marked = any("after_empty" in g["closed"].stats for g in gaps
                 if g["closed"] is not None)
    paused, kept = [], []
    for g in gaps:
        pause = g["closed"] is not None \
            and g["closed"].stats.get("after_empty") == 1
        (paused if pause else kept).append(g)
    n = len(kept)
    out = {"gaps": n,
           "left_out_after_empty": len(paused) if marked else None,
           "left_out_s": sum(g["b"] - g["a"] for g in paused) * 1e-9,
           "counted_s": sum(g["b"] - g["a"] for g in kept) * 1e-9}
    if not n:
        return out
    sizes = [(g["b"] - g["a"]) * 1e-6 for g in kept]
    out["gap_ms"] = {"mean": sum(sizes) / n, "p50": pct(sizes, 50),
                     "p90": pct(sizes, 90)}
    out["class_ms"] = _by_class(kept)
    out["class_sum_ms"] = sum(out["class_ms"].values())
    # the host's part of a gap as the program's own account sees it
    host = [(g["b"] - g["a"] - g["classes"].get(RUNTIME, 0)) * 1e-6
            for g in kept]
    out["host_part_ms"] = {"mean": sum(host) / n, "p50": pct(host, 50)}
    names = sorted({k for g in kept for k in g["pieces"]})
    piece = {k: _ms(sum(g["pieces"].get(k, 0) for g in kept), n)
             for k in names}
    out["launch_ms"], out["wake_ms"] = piece.get("launch", 0.0), \
        piece.get("wake", 0.0)
    out["piece_ms"] = dict(sorted(piece.items(), key=lambda kv: -kv[1]))
    pairs = defaultdict(list)
    for g in kept:
        pairs[_pair(g)].append(g)
    said = [g for g in kept if _prev(g)]
    out["prev_disagrees"] = sum(
        g["opened"] is not None and _site(g["opened"]) != _prev(g)
        for g in said) if said else None
    out["by_pair"] = {
        k: dict(gaps=len(v), gap_ms=_ms(sum(g["b"] - g["a"] for g in v),
                                        len(v)), **_by_class(v))
        for k, v in sorted(pairs.items())}
    out["by_live_third"] = _by_live(kept, live_of, num_slots)
    out["longest"] = [
        {"ms": (g["b"] - g["a"]) * 1e-6, "at_s": (g["a"] - t0) * 1e-9,
         "pair": _pair(g),
         "pieces_ms": {k: v * 1e-6 for k, v in sorted(
             g["pieces"].items(), key=lambda kv: -kv[1])}}
        for g in sorted(kept, key=lambda g: g["a"] - g["b"])[:3]]
    return out


def _by_live(kept, live_of, num_slots):
    """Gaps opened by a decode dispatch, by the live slots of its step in
    thirds of the slots: the emit loop's slope per slot. The join is the
    span id: ``serve.decode``'s annotation names its ring record, which
    has ``live`` (set at the span's exit, so not on the annotation)."""
    if live_of is None or not num_slots:
        return None
    thirds = defaultdict(list)
    for g in kept:
        step = g["opened"].up("serve.decode") if g["opened"] else None
        live = live_of(step.stats.get("sid")) if step is not None else None
        if live is not None:
            thirds[min(2, max(0, 3 * live - 1) // num_slots)].append(
                (live, g))
    if not thirds:
        return None
    return [{"third": i + 1, "gaps": len(rows),
             "live_mean": sum(x for x, _ in rows) / len(rows),
             "gap_ms": _ms(sum(g["b"] - g["a"] for _, g in rows), len(rows)),
             "sched_ms": _ms(sum(g["classes"].get(SCHED, 0)
                                 for _, g in rows), len(rows)),
             "emit_ms": _ms(sum(g["pieces"].get("serve.emit", 0)
                                for _, g in rows), len(rows))}
            for i, rows in sorted(thirds.items())]


# ---- what the readers ask ---------------------------------------------------------

def ring_rows(run, window):
    """The counts of the program's ``serve.dispatch`` ring records that
    carry ``gap_us`` and lie inside ``window`` (perf_counter) with the gap
    before them (it ends inside the record, so it began no earlier than
    ``gap_us`` before the record did: the window's first dispatch, whose
    gap holds whatever came before the window, the profiler's start for
    one, is left out); those after an empty engine left out. [] without
    telemetry or from a program that keeps no such count."""
    tracer = run.get("tracer")
    if tracer is None or window is None:
        return []
    t0, t1 = window
    return [r[5] for r in tracer.spans(DISPATCH)
            if r[6] <= t1 and r[5] and "gap_us" in r[5]
            and r[0] - r[5]["gap_us"] * 1e-6 >= t0
            and not r[5].get("after_empty")]


def _mean(xs):
    return sum(xs) / len(xs) if xs else None


def ring_by_pair(rows):
    """The ring's gap by the pair of sites (``site`` after ``prev``), with
    the part of it that lay outside every ``serve.step``: {pair: n, p50 and
    mean of ``gap_us``, mean of ``caller_us``, ms}. None where the records
    name no ``prev``."""
    pairs = defaultdict(list)
    for c in rows:
        if c.get("prev"):
            pairs[f"{_short(c.get('site'))}_after_{_short(c['prev'])}"] \
                .append(c)
    return {k: {"n": len(v),
                "gap_p50_ms": median([c["gap_us"] * 1e-3 for c in v]),
                "gap_mean_ms": _mean([c["gap_us"] * 1e-3 for c in v]),
                "caller_mean_ms": _mean([c["caller_us"] * 1e-3 for c in v
                                         if "caller_us" in c])}
            for k, v in sorted(pairs.items())} or None


def host_gap_ms(run):
    """Median of the program's own account of a gap (from the return of
    one dispatch's wait to the return of the next one's enqueue) over the
    untraced part of the window: thousands of dispatches, not a 6 s tail."""
    if run.get("kind") != "serve":
        return None
    return median([c["gap_us"] * 1e-3
                   for c in ring_rows(run, run.get("host_window"))])


def of_run(run):
    """The traced tail's ``dispatch_gaps`` summary (made once; says the
    line), or None without a device trace or the program's spans."""
    if "dispatch_gaps" in run:
        return run["dispatch_gaps"]
    run["dispatch_gaps"] = None
    base, path = run.get("trace"), provenance.trace_path(run)
    if base is None or path is None or run.get("kind") != "serve":
        return None
    programs, source, spans, runtime = load(path)
    if not programs or not spans:
        return None
    t0, t1 = round(base.t0 * 1e9), round(base.t1 * 1e9)
    shift, clock = align(programs, spans, runtime)
    named = [(s - shift, e - shift, n) for s, e, n in programs]
    programs = [(s, e) for s, e, _ in named]
    live = {}
    if run.get("tracer") is not None:
        live = {r[7]: r[5]["live"] for r in run["tracer"].spans(
            "serve.decode") if r[5] and "live" in r[5]}
    out = summarize(split(named, spans, t0, t1), t0,
                    live.get if live else None, run.get("num_slots"))
    out["programs_from"], out["clock"] = source, clock
    # the programs that no serve.dispatch wraps (a block copy, the
    # sampler's mask): the "other" of a pair of sites
    others = defaultdict(int)
    for s, e, name in named:
        if t0 <= s and e <= t1 and SERVING_PROGRAM not in str(name):
            others[provenance.short_program(str(name))] += 1
    out["other_programs"] = dict(others)
    # beside the sum: the device's idle seconds of the tail per gap, what
    # of them lies in no gap (inside a program, between two of its
    # operations; before the first whole program and after the last), and
    # the ring's own account over the same tail (perf_counter)
    idle_s = base.window_s - base.busy_s
    out["idle_s"] = idle_s
    out.update(_idle_outside_gaps(programs, base.devices[0], shift, t0, t1))
    if out["gaps"]:
        out["idle_less_left_out_per_gap_ms"] = \
            1e3 * (idle_s - out["left_out_s"]) / out["gaps"]
    rows = ring_rows(run, run.get("trace_host_window"))
    tail = [c["gap_us"] * 1e-3 for c in rows]
    out["ring_tail_gap_ms"] = {"n": len(tail), "p50": median(tail),
                               "mean": _mean(tail)} if tail else None
    # one quantity by two accounts: what lay outside every serve.step, by
    # the program's stamps (perf_counter) and by the cut of the trace
    called = [c["caller_us"] * 1e-3 for c in rows if "caller_us" in c]
    out["caller_check_ms"] = {
        "ring": _mean(called), "trace": out["class_ms"][CALLER],
        "ring_less_trace": _mean(called) - out["class_ms"][CALLER]} \
        if called and out["gaps"] else None
    out["ring_by_pair"] = ring_by_pair(
        ring_rows(run, run.get("host_window")))
    run["dispatch_gaps"] = out
    run["say"](info="dispatch_gaps", **out)
    return out


def _idle_outside_gaps(programs, device, shift, t0, t1):
    """The idle seconds of [t0, t1] that ``split`` gives to no gap: inside
    the programs seen whole (between two of a program's operations) and at
    the window's two edges (around a program the window cuts)."""
    whole = [(s, e) for s, e in programs if s >= t0 and e <= t1]
    if not whole:
        return {}
    first, last = min(s for s, _ in whole), max(e for _, e in whole)
    busy = [(round(s * 1e9) - shift, round(e * 1e9) - shift)
            for s, e in device.busy_intervals()]
    inside = sum(max(0, min(e, last) - max(s, first)) for s, e in busy)
    at_edges = sum(max(0, min(e, first) - max(s, t0))
                   + max(0, min(e, t1) - max(s, last)) for s, e in busy)
    return {"inside_programs_idle_s":
            (sum(e - s for s, e in whole) - inside) * 1e-9,
            "edge_idle_s": (first - t0 + t1 - last - at_edges) * 1e-9}


def class_ms(run, cls):
    """Seconds of class ``cls`` over the gaps counted, ms a gap. None where
    the two clocks could not be aligned: the cut of such a trace is the
    profiler's skew (the line still says what it read, under ``clock``)."""
    out = of_run(run)
    if not out or not out.get("gaps") or not out["clock"]["aligned"]:
        return None
    return out["class_ms"][cls]
