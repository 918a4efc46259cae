"""The kda_step kernel's share of its roofline: the larger of the recurrence's FLOPs (6 x 128 x 128 a head a token) over 197 TFLOP/s and the rewritten slots' state (read and written) and rows over 819 GB/s, over kernel time."""

from harness import readers_kda


def read(run):
    return readers_kda.kda_step_roofline(run)
