"""prefill_dispatch spans over step wall time, docs cell (inference/serving.py)."""

from harness import readers


def read(run):
    return readers.span_share(run, "prefill_dispatch")
