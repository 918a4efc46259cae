"""Scheduler step self time over step wall time, chat cell (inference/serving.py)."""

from harness import readers


def read(run):
    return readers.step_self_share(run)
