"""The ssm_step kernel's share of its roofline by the bytes: the decoding slots' state (16 x 5,120 float32 a layer, read and written) and their x, delta, y, B, C rows over 819 GB/s, over kernel time."""

from harness import readers_ssm


def read(run):
    return readers_ssm.ssm_step_roofline(run)
