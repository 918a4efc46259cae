"""The mla_decode kernel's share of its roofline: the larger of its FLOPs over 197 TFLOP/s and its latent rows' bytes over 819 GB/s, over kernel time."""

from harness import readers_mla


def read(run):
    return readers_mla.mla_decode_roofline(run)
