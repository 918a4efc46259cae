"""Device time of the Mamba-1 state-space mixers (scope attn_ssm, inference/ssm.py: the two projections, the convolution, the inner norms and the step, the recurrence in prefill and in decode, the gate) over busy time."""

from harness import readers_ssm


def read(run):
    return readers_ssm.scope_share(run, "attn_ssm")
