"""Device time of the state-space mixers' small work (scope ssm_mix: the convolution, W_x, the three inner norms, W_dt, softplus, the skip and the gate; neither the two projections nor the recurrence) over busy time."""

from harness import readers_ssm


def read(run):
    return readers_ssm.scope_share(run, "ssm_mix")
