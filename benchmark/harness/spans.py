"""The benchmark's own spans, recorded from outside the program: a host
clock pair per span, kept in memory, plus a ``jax.profiler.TraceAnnotation``
of the same name so a traced run carries the span on the profiler's clock.
``instrument_serving`` wraps a ``ServingEngine``'s admission and device
calls on the INSTANCE (no edit to the program): every dispatch already
passes through ``_device_call(site, ...)``, which blocks on the device."""

import time
from contextlib import contextmanager

import jax

SERVING_SPANS = ("step", "admit", "prefill_dispatch", "decode_dispatch")
TRAIN_SPANS = ("train_step",)
WINDOW_SPAN = "bench_traced_window"
SITE_SPAN = {"serving.prefill": "prefill_dispatch",
             "serving.decode": "decode_dispatch"}


class SpanLog:
    def __init__(self):
        self.spans = []      # (name, t0, t1, value)
        self.clock = time.perf_counter

    @contextmanager
    def span(self, name, value=None):
        with jax.profiler.TraceAnnotation(name):
            t0 = self.clock()
            try:
                yield
            finally:
                self.spans.append((name, t0, self.clock(), value))

    def named(self, name, t0=None, t1=None):
        return [s for s in self.spans if s[0] == name
                and (t0 is None or s[1] >= t0) and (t1 is None or s[2] <= t1)]

    def total(self, name, t0=None, t1=None) -> float:
        return sum(s[2] - s[1] for s in self.named(name, t0, t1))


def instrument_serving(srv, log: SpanLog, on_dispatch=None):
    """Wrap ``srv._admit`` and ``srv._device_call`` in spans.
    ``on_dispatch(span_name, t0, t1, args, out)`` sees every dispatch after
    it returned (token counting, logits for the correctness check)."""
    admit, device_call = srv._admit, srv._device_call

    def timed_admit(now=0.0):
        with log.span("admit"):
            admit(now)

    def timed_device_call(site, fn, *args, now=None):
        name = SITE_SPAN.get(site, site)
        with jax.profiler.TraceAnnotation(name):
            t0 = log.clock()
            out = device_call(site, fn, *args, now=now)
            t1 = log.clock()
        value = on_dispatch(name, t0, t1, args, out) if on_dispatch else None
        log.spans.append((name, t0, t1, value))
        return out

    srv._admit = timed_admit
    srv._device_call = timed_device_call
