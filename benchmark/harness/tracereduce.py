"""From a profiler trace (``.xplane.pb``) to numbers: device busy time,
kernel time by name, exposed collective time, the top device operations and
the idle gaps by the host span that covered them.

A device plane's "XLA Ops" line holds one event per executed HLO operation;
containers (a ``while`` around a scanned layer stack) hold their body's
events nested inside them, so every sum here is over SELF time: an event's
duration minus the part its children cover. Host spans are the benchmark's
own ``jax.profiler.TraceAnnotation`` events on the host plane, which the
profiler puts on the same clock as the device events."""

import bisect
from collections import defaultdict

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
COLLECTIVE_PREFIXES = ("all-gather", "all-reduce", "reduce-scatter",
                       "all-to-all", "collective-permute",
                       "collective-broadcast")


def op_name(event_name: str) -> str:
    """The operation's own name. The profiler names a device event either
    by that (``copy.46``) or by the whole HLO instruction
    (``%copy.46 = bf16[...] copy(...)``)."""
    name = event_name.split(" = ", 1)[0].strip()
    return name[1:] if name.startswith("%") else name


def base_name(op: str) -> str:
    """``copy.46`` -> ``copy``; ``paged_decode.9`` -> ``paged_decode``."""
    op = op_name(op)
    head, _, tail = op.rpartition(".")
    return head if head and tail.isdigit() else op


def is_collective(op: str) -> bool:
    return op.startswith(COLLECTIVE_PREFIXES)


def merge(intervals):
    """Sorted, disjoint [start, end) from any list of (start, end)."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def union_seconds(intervals) -> float:
    return sum(e - s for s, e in merge(intervals))


def self_times(events):
    """[(name, start, end, self_seconds)] for events [(name, start, end)]
    of ONE line, where an event nested inside another takes its time away
    from the enclosing one."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][1], -events[i][2]))
    selfs = [events[i][2] - events[i][1] for i in range(len(events))]
    stack = []
    for i in order:
        _, s, e = events[i]
        while stack and events[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            parent = stack[-1]
            selfs[parent] -= max(0.0, min(e, events[parent][2]) - s)
        stack.append(i)
    return [(events[i][0], events[i][1], events[i][2], max(0.0, selfs[i]))
            for i in order]


class DeviceTrace:
    """The operations of one device inside [t0, t1)."""

    def __init__(self, name, events, t0, t1):
        self.name = name
        clipped = [(n, max(s, t0), min(e, t1)) for n, s, e in events
                   if e > t0 and s < t1]
        self.events = self_times(clipped)
        self.t0, self.t1 = t0, t1

    @property
    def busy_s(self) -> float:
        return union_seconds([(s, e) for _, s, e, _ in self.events])

    def busy_intervals(self):
        return merge([(s, e) for _, s, e, _ in self.events])

    def kernel_events(self, kernel: str):
        return [ev for ev in self.events if base_name(ev[0]) == kernel]

    def kernel_seconds(self, kernel: str) -> float:
        return sum(e - s for _, s, e, _ in self.kernel_events(kernel))

    def by_name(self):
        tot = defaultdict(float)
        for n, _, _, self_s in self.events:
            tot[n] += self_s
        return tot

    def collective_self_seconds(self) -> float:
        """Time in which the innermost running operation is a collective:
        the core runs one operation at a time, so nothing computes then."""
        return sum(x for n, _, _, x in self.events if is_collective(n))


class Trace:
    def __init__(self, devices, host_spans, t0, t1):
        self.devices = devices          # [DeviceTrace]
        self.host_spans = host_spans    # [(name, start, end)]
        self.t0, self.t1 = t0, t1

    @property
    def window_s(self) -> float:
        return self.t1 - self.t0

    @property
    def busy_s(self) -> float:
        """Mean over the devices used of the seconds an operation ran."""
        return sum(d.busy_s for d in self.devices) / len(self.devices)

    def kernel_seconds(self, kernel: str) -> float:
        return sum(d.kernel_seconds(kernel) for d in self.devices) \
            / len(self.devices)

    def kernel_calls(self, kernel: str) -> float:
        return sum(len(d.kernel_events(kernel)) for d in self.devices) \
            / len(self.devices)

    def collective_exposed_s(self) -> float:
        return sum(d.collective_self_seconds() for d in self.devices) \
            / len(self.devices)

    def top_ops(self, n=10):
        tot = defaultdict(float)
        for d in self.devices:
            for name, x in d.by_name().items():
                tot[name] += x / len(self.devices)
        return [[k, v] for k, v in sorted(tot.items(),
                                          key=lambda kv: -kv[1])[:n]]

    def idle_gaps(self, n=10):
        """Idle gaps of the first device by the innermost host span that
        covers each gap's middle: the per-span sums first, then the
        longest single gaps, ``n`` entries in all."""
        dev = self.devices[0]
        busy = dev.busy_intervals()
        edges = [self.t0] + [x for iv in busy for x in iv] + [self.t1]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        spans = sorted(self.host_spans, key=lambda sp: sp[1])
        starts = [sp[1] for sp in spans]
        sums = defaultdict(float)
        single = []
        for s, e in gaps:
            mid = 0.5 * (s + e)
            j = bisect.bisect_right(starts, mid)
            name = "no_span"
            best = None
            for sp in spans[max(0, j - 8):j]:
                if sp[1] <= mid < sp[2] and (best is None
                                             or sp[2] - sp[1] < best):
                    name, best = sp[0], sp[2] - sp[1]
            sums[name] += e - s
            single.append((name, e - s))
        head = [["sum:" + k, v] for k, v in sorted(sums.items(),
                                                   key=lambda kv: -kv[1])]
        head = head[:n // 2]
        tail = [[k, v] for k, v in sorted(single, key=lambda kv: -kv[1])]
        return head + tail[:n - len(head)]


def load(path: str, span_names=(), window_span: str = None) -> Trace:
    """Read ``path`` (.xplane.pb). ``span_names``: the host annotations to
    keep. The window is the extent of the annotation ``window_span`` when
    given (the benchmark wraps its traced window in one), else the extent
    of the device events."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    raw_devices, host_spans, window = [], [], None
    keep = set(span_names) | ({window_span} if window_span else set())
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            lines = [ln for ln in plane.lines if ln.name == OPS_LINE]
            events = [(op_name(ev.name), ev.start_ns * 1e-9,
                       (ev.start_ns + ev.duration_ns) * 1e-9)
                      for ln in lines for ev in ln.events]
            if events:
                raw_devices.append((plane.name, events))
        elif plane.name == HOST_PLANE and keep:
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name in keep:
                        span = (ev.name, ev.start_ns * 1e-9,
                                (ev.start_ns + ev.duration_ns) * 1e-9)
                        if ev.name == window_span:
                            window = span
                        else:
                            host_spans.append(span)
    if not raw_devices:
        return None
    if window is not None:
        t0, t1 = window[1], window[2]
    else:
        t0 = min(s for _, evs in raw_devices for _, s, _ in evs)
        t1 = max(e for _, evs in raw_devices for _, _, e in evs)
    devices = [DeviceTrace(name, evs, t0, t1)
               for name, evs in sorted(raw_devices)]
    host_spans = [sp for sp in host_spans if sp[2] > t0 and sp[1] < t1]
    return Trace(devices, host_spans, t0, t1)


def inventory(path: str):
    """[(plane, line, events)] of a trace: look at one by hand first."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    return [(pl.name, ln.name, len(list(ln.events)))
            for pl in pd.planes for ln in pl.lines]
