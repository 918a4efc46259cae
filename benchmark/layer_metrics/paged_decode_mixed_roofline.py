"""paged_decode's share of its memory roofline over two kinds of KV state: the full layers' occupied blocks and the window layers' min(length, window) tokens, over 819 GB/s, over kernel time."""

from harness import readers_moe


def read(run):
    return readers_moe.paged_decode_mixed_roofline(run)
