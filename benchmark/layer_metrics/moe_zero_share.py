"""Decode dispatches: pairs on zero-compute (identity) experts of all routed pairs, from the counter pairs_zero kept on the device; a reading of the selection bias (33 at rest), not a target."""

from harness import readers_scmoe


def read(run):
    return readers_scmoe.moe_zero_share(run)
