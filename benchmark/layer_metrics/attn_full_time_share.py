"""Device time of paged attention in full-attention layers (scope attn_full, inference/hybrid.py) over busy time."""

from harness import readers_moe


def read(run):
    return readers_moe.scope_time_share(run, ("attn_full",))
