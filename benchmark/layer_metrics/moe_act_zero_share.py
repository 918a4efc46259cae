"""Decode dispatches: the share of the held pairs' gate activations that the ReLU left exactly 0, from the counters kept on the device."""

from harness import readers_window


def read(run):
    return readers_window.moe_act_zero_share(run)
