"""The recurrent state's bytes over those and the occupied K and V rows' bytes at the window's peak (inference/paged_cache.py: what the slots cost before they hold a token)."""

from harness import readers_ssm


def read(run):
    return readers_ssm.ssm_state_share(run)
