"""Device time of Gated DeltaNet linear attention (scope attn_gdn, inference/linear.py: projections, convolution and gates, the scalar-decay chunk form in prefill and the step kernel in decode, the gated norm and projection) over busy time."""

from harness import readers_gdn


def read(run):
    return readers_gdn.scope_share(run, "attn_gdn")
