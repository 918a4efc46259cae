"""Peak KV blocks held over blocks in the pool (inference/paged_cache.py)."""

from harness import readers


def read(run):
    return readers.kv_blocks_peak_share(run)
