"""The step kernel's share of its roofline in decode: the rewritten slots' state (read and written) and their rows over 819 GB/s (or the recurrence's FLOPs over 197 TFLOP/s, whichever binds), over kernel time; one kernel, kda_step, serves both delta rules."""

from harness import readers_gdn


def read(run):
    return readers_gdn.gdn_step_roofline(run)
