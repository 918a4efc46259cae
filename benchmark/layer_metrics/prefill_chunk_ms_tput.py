"""Median prefill_dispatch span; it blocks on the device (inference/engine.py)."""

from harness import readers


def read(run):
    return readers.median_span_ms(run, "prefill_dispatch")
