"""Serving telemetry — request-lifecycle tracing and spans, and a
metrics registry with Prometheus/Perfetto exporters.

The reproduction's analog of the reference's engine-owned monitoring
(deepspeed/monitor/* + the flops profiler), at serving granularity: an
iteration-level scheduler is exactly the system where aggregate
counters hide what matters (per-request queue wait, TTFT, TPOT,
eviction/COW/retry timelines), so this package gives the
:class:`~deepspeed_tpu.inference.serving.ServingEngine` a first-class
observability plane — see docs/OBSERVABILITY.md for the metric catalog,
trace schema and overhead notes.

Two pieces, one facade:

- :class:`~deepspeed_tpu.telemetry.metrics.MetricsRegistry` — counters,
  gauges, fixed-bucket histograms; exports Prometheus text exposition
  and Monitor-compatible scalar tuples;
- :class:`~deepspeed_tpu.telemetry.tracer.RequestTracer` — ring-
  buffered host-side lifecycle events and spans (the program's one span
  recorder; a span is also a profiler annotation); exports
  Chrome-trace/Perfetto JSON (``tools/trace_analyze.py serve <file>``
  reads it).

Enablement mirrors the prefix-cache knob: explicit ``telemetry=`` on
``ServingEngine`` wins, else ``DS_TELEMETRY=on|off`` (default OFF — the
off path swaps in constant-time no-op twins, so the hot loop pays one
attribute access per call site and the compile/parity contracts are
byte-identical either way).
"""

import time
from typing import Optional

from deepspeed_tpu.utils.env import resolve_flag
from deepspeed_tpu.telemetry.metrics import (Counter, DEFAULT_BUCKETS,
                                             Gauge, Histogram,
                                             MetricsRegistry,
                                             RATE_BUCKETS, TEMP_BUCKETS,
                                             merge_registries)
from deepspeed_tpu.telemetry.tracer import (NOOP_SPAN, NoopTracer,
                                            RequestTracer, span_self_times)
from deepspeed_tpu.telemetry.costs import (CostAccountant,
                                           NOOP_COSTS,
                                           NoopCostAccountant,
                                           ProgramCostRegistry,
                                           device_peak_flops,
                                           model_flops_per_token)
from deepspeed_tpu.telemetry.flight import (FlightRecorder, NOOP_FLIGHT,
                                            NoopFlightRecorder,
                                            load_artifact)

__all__ = ["Telemetry", "NoopTelemetry", "NOOP", "resolve_telemetry",
           "MetricsRegistry", "Counter", "Gauge", "Histogram",
           "RequestTracer", "NoopTracer", "NOOP_SPAN", "span_self_times",
           "DEFAULT_BUCKETS", "RATE_BUCKETS",
           "TEMP_BUCKETS", "merge_registries",
           "CostAccountant", "NoopCostAccountant", "NOOP_COSTS",
           "ProgramCostRegistry", "device_peak_flops",
           "model_flops_per_token",
           "FlightRecorder", "NoopFlightRecorder", "NOOP_FLIGHT",
           "load_artifact"]


def resolve_telemetry(flag: Optional[bool] = None) -> bool:
    """Explicit flag wins; else the ``DS_TELEMETRY`` env knob; default
    off (the no-op plane is the bit-reference)."""
    return resolve_flag("DS_TELEMETRY", flag)


class Telemetry:
    """Live bundle: one registry + one tracer, shared by everything a
    single :class:`ServingEngine` emits. Pass an instance to several
    engines to aggregate, or one per engine to keep timelines separate.
    ``sample_every`` is the cadence (in scheduler steps) of the gauges
    that cost a host reduction or a device pull."""

    enabled = True

    def __init__(self, registry: Optional[MetricsRegistry] = None,
                 trace_capacity: int = 65536, sample_every: int = 16,
                 clock=time.perf_counter):
        self.registry = registry if registry is not None else MetricsRegistry()
        self.tracer = RequestTracer(capacity=trace_capacity, clock=clock)
        self.sample_every = max(1, int(sample_every))

    # convenience exporters -------------------------------------------
    def to_prometheus(self) -> str:
        return self.registry.to_prometheus()

    def export_trace(self, path: str) -> str:
        return self.tracer.export(path)

    def to_scalars(self, step: int):
        return self.registry.to_scalars(step)


class NoopTelemetry:
    """Off-mode bundle: no registry (the engine keeps a private one for
    the stats view), no recording."""

    enabled = False
    registry = None

    def __init__(self):
        self.tracer = NoopTracer()

    def to_prometheus(self) -> str:
        return ""

    def export_trace(self, path: str) -> str:
        return self.tracer.export(path)

    def to_scalars(self, step: int):
        return []


NOOP = NoopTelemetry()
