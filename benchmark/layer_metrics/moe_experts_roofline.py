"""The held experts' grouped product in decode dispatches: touched experts' weight bytes and the pairs' FLOPs against the chip's peaks, over the device time under moe_experts."""

from harness import readers_moe


def read(run):
    return readers_moe.moe_experts_roofline(run)
