"""The two dense FFNs of a shortcut-connected double layer (scope mlp under scmoe_a / scmoe_b) as a share of device busy time."""

from harness import readers_scmoe


def read(run):
    return readers_scmoe.scmoe_dense_share(run)
