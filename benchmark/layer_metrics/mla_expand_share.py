"""Re-expansion of cached latent rows to per-head keys and values (scope mla_expand) as a share of the prefill program's device time."""

from harness import readers_mla


def read(run):
    return readers_mla.mla_expand_share(run)
