"""The recurrent state's bytes over those and the occupied latent rows' bytes at the window's peak (inference/paged_cache.py: what the slots cost before they hold a token)."""

from harness import readers_kda


def read(run):
    return readers_kda.recurrent_state_share(run)
