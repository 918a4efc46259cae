"""Device time of the mixing that makes a token's q, k and v (scope cca_mix: the two convolutions over time, the q-k means, the value shift, the per-head norm, temperature and rotary) over busy time."""

from harness import readers_cca


def read(run):
    return readers_cca.scope_share(run, "cca_mix")
