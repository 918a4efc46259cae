"""Collective time with nothing computing on that device, over the traced window (parallel/mesh.py, sharding.py)."""

from harness import readers


def read(run):
    return readers.collective_exposed_share(run)
