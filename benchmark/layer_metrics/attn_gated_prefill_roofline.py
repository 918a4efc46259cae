"""The gated full-attention layers' causal work (4 x 16 x 256 FLOPs a query-key pair, a query at t sees t + 1 keys) over the chip's peaks, over device time under the scope attn_gated in the prefill program."""

from harness import readers_gdn


def read(run):
    return readers_gdn.attn_gated_prefill_roofline(run)
