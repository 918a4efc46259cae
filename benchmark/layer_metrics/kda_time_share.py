"""Device time of gated delta-rule linear attention (scope attn_kda, inference/linear.py: projections, convolution and gates, the chunkwise rule in prefill and the recurrent step in decode, the output norm and projection) over busy time."""

from harness import readers_kda


def read(run):
    return readers_kda.scope_share(run, "attn_kda")
