"""Device time of the window layers' attention in the prefill program (scope attn_window under serve_prefill_slot, inference/hybrid.py) over busy time."""

from harness import readers_window


def read(run):
    return readers_window.attn_window_prefill_share(run)
