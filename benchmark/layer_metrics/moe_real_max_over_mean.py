"""Decode dispatches: the most real experts any one token of a layer call chose (counter real_pairs_max_token, mean over calls) over the mean real experts a token."""

from harness import readers_scmoe


def read(run):
    return readers_scmoe.moe_real_max_over_mean(run)
