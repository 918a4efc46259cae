"""The paged_decode kernel's share of its roofline at 1 KV head under 20 query heads: the larger of its FLOPs over 197 TFLOP/s and the occupied K and V rows' bytes over 819 GB/s, over kernel time."""

from harness import readers_ssm


def read(run):
    return readers_ssm.paged_decode_mqa_roofline(run)
