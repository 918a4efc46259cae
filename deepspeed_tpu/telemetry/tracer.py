"""Request-lifecycle tracer — a host-side ring buffer of scheduler
events, exportable as a Chrome-trace / Perfetto JSON timeline.

Every request transition the scheduler makes lands here as one record:
``enqueue`` → ``admit`` (tagged with the prefix match and any COW) →
``prefill_chunk``* → ``prefill_done`` → ``first_token`` → ``evict`` /
re-``admit`` → ``finish`` (state done/timeout/shed), plus scheduler-
lane records (``watchdog``, ``degraded``) and ``fault`` records
streamed in from
:class:`deepspeed_tpu.utils.faults.FaultInjector` listeners — so a
seeded chaos run replays as a single ordered timeline
(docs/OBSERVABILITY.md has the schema, docs/ROBUSTNESS.md the chaos
cross-reference).

The buffer is a preallocated ring of fixed capacity: recording is one
tuple build + indexed store (no growth, no I/O, no device work), old
records are overwritten once the ring wraps (``dropped`` counts them),
and nothing is serialized until :meth:`export` — so the tracer can sit
inside the scheduler hot loop without breaking the DS001 sync-free
contract or the zero-recompile CompileWatch pin.

Export builds per-request lifecycle SPANS from the point records: a
``queued`` span per enqueue→admit interval, ``prefill`` per
admit→prefill_done, ``decode`` per prefill_done→(finish|evict); an
evicted request simply opens a new queued span, so a preempted
lifecycle shows up as repeated queued/prefill/decode triples on one
timeline row. Faults ride along as instant events on the scheduler row
(tid 0).

The same ring is the program's one SPAN recorder: ``span(name, ...)`` is
a context manager whose record is the point-event tuple plus an end
time, a span id and the id of the span that was open when it started
(``serve.step`` > ``serve.prefill`` > ``serve.dispatch`` > ...; names in
docs/OBSERVABILITY.md). Entering a span also enters a
``jax.profiler.TraceAnnotation`` of the same name, so whenever a
profiler trace is running the span sits in the ``.xplane.pb`` on the
profiler's clock beside the device events, and in the ring on
``perf_counter`` otherwise. The annotation carries the span id (``sid``)
beside the counts given at construction, so a reader of the trace finds
an event's ring record, and with it the counts ``set()`` at exit. A span's self time is its duration minus its
children's (:func:`span_self_times`); the Chrome export draws spans as
nested slices on the scheduler lane.

What a span costs the host it measures is stamped too: ``__enter__`` reads
the clock on its first line as well as on its last (``t0``), ``__exit__``
on its last as well as on its first (``t1``), and the two differences (the
ring slot, the annotation's enter and exit: ``o0`` and ``o1`` on the span)
add up in ``RequestTracer.span_self``, which the serving scheduler takes
at every dispatch (``self_us`` / ``self_parts`` on ``serve.dispatch``,
inference/serving.py ``_account_gap``).
"""

import json
import time
from typing import Any, Callable, Dict, List, Optional

import jax

# record layout: (ts, etype, rid, step, slot, data-dict-or-None); a span
# record carries three more fields: (..., end_ts, span_id, parent_id)
_TS, _ETYPE, _RID, _STEP, _SLOT, _DATA, _END, _SID, _PARENT = range(9)

# the order of ``self_parts`` on a ``serve.dispatch`` ring record: what the
# telemetry plane stamped of its own host time since the dispatch before
# (inference/serving.py ``_account_gap``); ``self_us`` is their sum less
# the last, which ran under a running program
SELF_PARTS = ("spans", "accountant", "histograms", "counts", "gauges",
              "hidden")

# lifecycle phases, in the order a healthy request traverses them
SPAN_QUEUED = "queued"
SPAN_PREFILL = "prefill"
SPAN_DECODE = "decode"


def is_span(rec: tuple) -> bool:
    return len(rec) > _END


def span_self_times(records: List[tuple]) -> Dict[int, float]:
    """span id -> self seconds (duration minus the durations of the spans
    that name it as parent) for the span records in ``records``. A child
    whose parent has left the ring takes nothing from anyone."""
    selfs = {r[_SID]: r[_END] - r[_TS] for r in records if is_span(r)}
    for r in records:
        if is_span(r) and r[_PARENT] in selfs:
            selfs[r[_PARENT]] -= r[_END] - r[_TS]
    return selfs


class _Span:
    """One open span: reserves its ring slot on entry (records stay in
    start order), fills it on exit. ``set(**counts)`` adds counts known
    only at the end (tokens emitted, bytes pulled)."""

    __slots__ = ("_tr", "_ann", "_idx", "name", "rid", "step", "slot",
                 "counts", "sid", "parent", "t0", "t1", "o0", "o1")

    def __init__(self, tracer, name, rid, step, slot, counts):
        self._tr, self.name, self.rid = tracer, name, rid
        self.step, self.slot, self.counts = step, slot, counts
        self.t0 = self.t1 = self.o0 = self.o1 = 0.0

    def set(self, **counts) -> None:
        self.counts.update(counts)

    @property
    def dur(self) -> float:
        return self.t1 - self.t0

    def __enter__(self):
        tr = self._tr
        c0 = tr._clock()
        tr._last_sid += 1
        self.sid = tr._last_sid
        self.parent = tr._open[-1] if tr._open else 0
        tr._open.append(self.sid)
        self._idx = tr._n
        tr._buf[tr._n % tr.capacity] = None
        tr._n += 1
        self._ann = jax.profiler.TraceAnnotation(self.name, sid=self.sid,
                                                 **self.counts)
        self._ann.__enter__()
        self.t0 = tr._clock()
        self.o0 = self.t0 - c0
        tr.span_self += self.o0
        return self

    def __exit__(self, *exc):
        tr = self._tr
        self.t1 = tr._clock()
        self._ann.__exit__(*exc)
        if tr._open and tr._open[-1] == self.sid:
            tr._open.pop()
        # unless the ring wrapped over the slot (or was reset) meanwhile
        if tr._n - tr.capacity <= self._idx < tr._n:
            tr._buf[self._idx % tr.capacity] = (
                self.t0, self.name, self.rid, self.step, self.slot,
                self.counts or None, self.t1, self.sid, self.parent)
        self.o1 = tr._clock() - self.t1
        tr.span_self += self.o1
        return False


class _NoopSpan:
    """The off-mode span: one shared object, no clock, no annotation."""

    __slots__ = ()
    dur = t0 = t1 = 0.0

    def set(self, **counts) -> None:
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


NOOP_SPAN = _NoopSpan()


class RequestTracer:
    """Ring-buffered recorder of point events (``event``) and spans
    (``span``), the hot-path entry points; everything else is
    export-time."""

    enabled = True

    def __init__(self, capacity: int = 65536,
                 clock: Callable[[], float] = time.perf_counter):
        self.capacity = int(capacity)
        if self.capacity <= 0:
            raise ValueError("tracer capacity must be positive")
        self._clock = clock
        self._buf: List[Optional[tuple]] = [None] * self.capacity
        self._n = 0          # total records ever written
        self._last_sid = 0   # span ids start at 1; parent 0 = no parent
        self._open: List[int] = []   # ids of the spans open now
        # seconds inside _Span.__enter__ before t0 and inside __exit__
        # after t1 since the scheduler last took them (_account_gap)
        self.span_self = 0.0

    # -- recording (hot path) ------------------------------------------
    def event(self, etype: str, rid: Any = None, step: int = -1,
              slot: int = -1, **data) -> None:
        self._buf[self._n % self.capacity] = (
            self._clock(), etype, rid, step, slot, data or None)
        self._n += 1

    def span(self, name: str, rid: Any = None, step: int = -1,
             slot: int = -1, **counts) -> _Span:
        """Context manager: one span record in the ring and one
        ``TraceAnnotation`` of the same name (``counts``: integers or
        short strings known at the boundary)."""
        return _Span(self, name, rid, step, slot, counts)

    # -- inspection ----------------------------------------------------
    @property
    def dropped(self) -> int:
        return max(0, self._n - self.capacity)

    def records(self) -> List[tuple]:
        """Surviving records, oldest first (a span still open holds its
        slot empty and is left out)."""
        if self._n <= self.capacity:
            recs = self._buf[:self._n]
        else:
            head = self._n % self.capacity
            recs = self._buf[head:] + self._buf[:head]
        return [r for r in recs if r is not None]

    def spans(self, name: Optional[str] = None) -> List[tuple]:
        """Closed span records, in start order."""
        return [r for r in self.records() if is_span(r)
                and (name is None or r[_ETYPE] == name)]

    def events_of(self, rid: Any) -> List[tuple]:
        return [r for r in self.records() if r[_RID] == rid]

    def reset(self) -> None:
        self._buf = [None] * self.capacity
        self._n = 0
        self._open = []
        self.span_self = 0.0

    # -- export --------------------------------------------------------
    def to_chrome_trace(self) -> Dict:
        """Chrome-trace/Perfetto JSON object. pid 1 is the serving
        process; tid 0 the scheduler lane (spans, faults, watchdog);
        tids 1.. one lane per request in first-seen order. Request
        lifecycles and spans become ``ph: "X"`` complete events (a
        span's args carry its counts, parent and self time); faults and
        terminal states become ``ph: "i"`` instants."""
        recs = self.records()
        events: List[Dict] = [
            {"ph": "M", "pid": 1, "name": "process_name",
             "args": {"name": "deepspeed_tpu.serving"}},
            {"ph": "M", "pid": 1, "tid": 0, "name": "thread_name",
             "args": {"name": "scheduler"}},
        ]
        if not recs:
            return {"traceEvents": events, "displayTimeUnit": "ms",
                    "dropped_events": 0}
        t0 = recs[0][_TS]

        def us(ts: float) -> float:
            return round((ts - t0) * 1e6, 3)

        tids: Dict[Any, int] = {}

        def tid_of(rid: Any) -> int:
            t = tids.get(rid)
            if t is None:
                t = tids[rid] = len(tids) + 1
                events.append({"ph": "M", "pid": 1, "tid": t,
                               "name": "thread_name",
                               "args": {"name": f"req {rid}"}})
            return t

        # open[rid] = (span_name, start_ts, start_args)
        open_span: Dict[Any, tuple] = {}

        def close(rid: Any, ts: float, extra: Optional[Dict] = None) -> None:
            sp = open_span.pop(rid, None)
            if sp is None:
                return
            name, start, args = sp
            a = {"rid": str(rid)}
            a.update(args or {})
            a.update(extra or {})
            events.append({"ph": "X", "pid": 1, "tid": tid_of(rid),
                           "cat": "request", "name": name,
                           "ts": us(start), "dur": us(ts) - us(start),
                           "args": a})

        selfs = span_self_times(recs)
        for rec in recs:
            ts, etype, rid, step, slot, data = rec[:_END]
            data = data or {}
            if is_span(rec):
                a = {"step": step, "span_id": rec[_SID],
                     "parent_id": rec[_PARENT]}
                if rid is not None:
                    a["rid"] = str(rid)
                if slot >= 0:
                    a["slot"] = slot
                a.update(data)
                if "self_us" in data:
                    # a dispatch's count of the plane's own host time;
                    # ``self_us`` stays the span's self time here
                    a["plane_self_us"] = data["self_us"]
                a["self_us"] = round(selfs[rec[_SID]] * 1e6, 3)
                events.append({"ph": "X", "pid": 1, "tid": 0,
                               "cat": "span", "name": etype,
                               "ts": us(ts),
                               "dur": round((rec[_END] - ts) * 1e6, 3),
                               "args": a})
            elif etype == "enqueue":
                close(rid, ts)           # defensive: rid reuse
                open_span[rid] = (SPAN_QUEUED, ts, {})
            elif etype == "admit":
                close(rid, ts)
                open_span[rid] = (SPAN_PREFILL, ts, {
                    "slot": slot,
                    "prefix_hit": bool(data.get("matched", 0)),
                    "matched_tokens": data.get("matched", 0)})
            elif etype == "prefill_done":
                close(rid, ts)
                open_span[rid] = (SPAN_DECODE, ts, {"slot": slot})
            elif etype == "evict":
                close(rid, ts, {"evicted": True})
                open_span[rid] = (SPAN_QUEUED, ts, {"requeued": True})
                events.append({"ph": "i", "pid": 1, "tid": tid_of(rid),
                               "cat": "request", "name": "evict",
                               "ts": us(ts), "s": "t",
                               "args": {"rid": str(rid), "slot": slot,
                                        "step": step}})
            elif etype == "finish":
                state = data.get("state", "done")
                # a request shed/timed out straight from the queue (or a
                # prefill-final-chunk finish) closes whatever span is open
                if rid not in open_span:
                    open_span[rid] = (SPAN_QUEUED, ts, {})
                close(rid, ts, {"state": state})
                events.append({"ph": "i", "pid": 1, "tid": tid_of(rid),
                               "cat": "request", "name": f"finish:{state}",
                               "ts": us(ts), "s": "t",
                               "args": {"rid": str(rid), "step": step,
                                        "generated":
                                            data.get("generated", 0)}})
            elif etype == "fault":
                events.append({"ph": "i", "pid": 1, "tid": 0,
                               "cat": "fault",
                               "name": f"fault:{data.get('site')}:"
                                       f"{data.get('kind')}",
                               "ts": us(ts), "s": "g",
                               "args": {"site": data.get("site"),
                                        "kind": data.get("kind"),
                                        "visit": data.get("visit"),
                                        "step": step}})
            else:
                # first_token, prefill_chunk, cow, cache_evict_block,
                # watchdog, degraded, ... — instant on the owning lane
                tid = tid_of(rid) if rid is not None else 0
                a = {"step": step}
                if rid is not None:
                    a["rid"] = str(rid)
                a.update(data)
                events.append({"ph": "i", "pid": 1, "tid": tid,
                               "cat": "scheduler", "name": etype,
                               "ts": us(ts), "s": "t", "args": a})
        # whatever is still open at export time renders as in-flight
        last = max(r[_END] if is_span(r) else r[_TS] for r in recs)
        for rid in list(open_span):
            close(rid, last, {"in_flight": True})
        return {"traceEvents": events, "displayTimeUnit": "ms",
                "dropped_events": self.dropped}

    def export(self, path: str) -> str:
        """Write the Chrome-trace JSON to ``path`` (load it in Perfetto
        / chrome://tracing, or ``tools/trace_analyze.py serve <path>``)."""
        with open(path, "w") as f:
            json.dump(self.to_chrome_trace(), f)
        return path


class NoopTracer:
    """DS_TELEMETRY=off twin: every entry point is a constant-time
    no-op, so the scheduler's call sites need no branching."""

    enabled = False
    capacity = 0
    dropped = 0

    def event(self, etype, rid=None, step=-1, slot=-1, **data) -> None:
        pass

    def span(self, name, rid=None, step=-1, slot=-1, **counts) -> _NoopSpan:
        return NOOP_SPAN

    def events_of(self, rid) -> List[tuple]:
        return []

    def records(self) -> List[tuple]:
        return []

    def spans(self, name=None) -> List[tuple]:
        return []

    def reset(self) -> None:
        pass

    def to_chrome_trace(self) -> Dict:
        return {"traceEvents": [], "displayTimeUnit": "ms",
                "dropped_events": 0}

    def export(self, path: str) -> str:
        with open(path, "w") as f:
            json.dump(self.to_chrome_trace(), f)
        return path
