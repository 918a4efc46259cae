"""Median of the program's own serve.dispatch.enqueue span (arguments, transfers, launch), telemetry on (inference/serving.py _device_call)."""

from harness import readers


def read(run):
    return readers.program_span_median_ms(run, "serve.dispatch.enqueue")
