"""paged_decode's share of its memory roofline: occupied blocks' bytes over 819 GB/s over kernel time (ops/attention/paged.py)."""

from harness import readers


def read(run):
    return readers.paged_decode_roofline(run)
