"""Median of the host time before a dispatch that the program's telemetry plane spent on its own account (`self_us` on its serve.dispatch ring records, beside `gap_us`: span enter/exit, cost charges, histogram observes, span counts, sampled gauges), untraced part of the window: how far host_gap_ms, gap_sched_ms, sched_host_share and idle_share of the same line overstate the step the end-to-end metric was timed on (harness/readers_selfcost.py)."""

from harness import readers_selfcost


def read(run):
    return readers_selfcost.telemetry_self_ms(run)
