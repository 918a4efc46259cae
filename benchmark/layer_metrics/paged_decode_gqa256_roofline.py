"""The paged_decode kernel's share of its roofline at 2 KV heads of 256 under 8 query rows each: the larger of its FLOPs over 197 TFLOP/s and the occupied K and V rows' bytes over 819 GB/s, over kernel time."""

from harness import readers_gdn


def read(run):
    return readers_gdn.paged_decode_gqa256_roofline(run)
