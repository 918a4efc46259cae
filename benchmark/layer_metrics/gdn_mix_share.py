"""Device time of what makes a Gated DeltaNet layer's q, k, v, decay and gates from its projections (scope gdn_mix: the depthwise convolution, SiLU, the two L2 norms, softplus, sigmoid, the key heads' repeat) over busy time."""

from harness import readers_gdn


def read(run):
    return readers_gdn.scope_share(run, "gdn_mix")
