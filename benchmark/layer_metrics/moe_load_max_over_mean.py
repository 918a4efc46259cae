"""Decode dispatches: the busiest held expert's pairs over the mean per held expert, from the counters kept on the device."""

from harness import readers_moe


def read(run):
    return readers_moe.moe_load_max_over_mean(run)
