"""Device time of the copies that re-lay-out the paged KV pool (a `copy` or a `reshape` that runs on the device, in the kv_write, kv_gather or paged_attn scope, or one whose result is one layer's whole pool) over busy time."""

from harness import provenance


def read(run):
    return provenance.kv_relayout_share(run)
