"""Device time of what makes a linear-attention layer's q, k, v, decay and gates from its projections (scope kda_mix: the depthwise convolution, SiLU, the L2 norms, softplus and sigmoids) over busy time."""

from harness import readers_kda


def read(run):
    return readers_kda.scope_share(run, "kda_mix")
