"""Device time of paged attention in sliding-window layers (scope attn_window, inference/hybrid.py) over busy time."""

from harness import readers_moe


def read(run):
    return readers_moe.scope_time_share(run, ("attn_window",))
