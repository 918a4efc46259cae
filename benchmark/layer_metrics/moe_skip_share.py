"""Decode dispatches: routed tokens whose top-1 choice was the skip output, of all routed tokens, from the counter pairs_skipped kept on the device."""

from harness import readers_cca


def read(run):
    return readers_cca.moe_skip_share(run)
