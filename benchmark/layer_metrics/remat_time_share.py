"""Device time of operations that recompute (jax.checkpoint's rematted_computation in the backward pass, or an instruction XLA rematerialised itself) over busy time."""

from harness import provenance


def read(run):
    return provenance.remat_time_share(run)
