"""Scheduler step self time over step wall time, docs cell (inference/serving.py)."""

from harness import readers


def read(run):
    return readers.step_self_share(run)
