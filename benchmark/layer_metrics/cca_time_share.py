"""Device time of the attention inside the compressed latent (scope attn_cca, inference/cca.py: the paged_decode calls of decode and the chunk-and-history attention of prefill) over busy time."""

from harness import readers_cca


def read(run):
    return readers_cca.scope_share(run, "attn_cca")
