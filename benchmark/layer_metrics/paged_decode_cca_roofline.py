"""The paged_decode kernel's share of its roofline at 2 KV heads under 8 query heads: the larger of its FLOPs over 197 TFLOP/s and the occupied K and V rows' bytes over 819 GB/s, over kernel time."""

from harness import readers_cca


def read(run):
    return readers_cca.paged_decode_cca_roofline(run)
