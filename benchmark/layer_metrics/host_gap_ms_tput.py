"""Median of the program's own account of the host time before a dispatch (`gap_us` on its serve.dispatch ring records: from the return of the previous dispatch's wait to the return of this one's enqueue), untraced part of the window, gaps after an empty engine left out (inference/serving.py _account_gap)."""

from harness import gaps


def read(run):
    return gaps.host_gap_ms(run)
