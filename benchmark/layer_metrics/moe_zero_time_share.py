"""The zero-compute experts' term (scope moe_zero) as a share of device busy time: what "zero-compute" costs on the device."""

from harness import readers_scmoe


def read(run):
    return readers_scmoe.moe_zero_time_share(run)
