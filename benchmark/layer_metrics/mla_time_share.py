"""Device time of latent attention (scope attn_mla, inference/latent.py: absorbed decode and expanded prefill, their up-projections included) over busy time."""

from harness import readers_moe


def read(run):
    return readers_moe.scope_time_share(run, ("attn_mla",))
