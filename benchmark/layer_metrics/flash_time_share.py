"""Device time of flash_fwd, flash_bwd_dq and flash_bwd_dkv over busy time (ops/attention/flash.py)."""

from harness import readers


def read(run):
    return readers.kernel_time_share(run, ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")) if run.get("kind") == "train" else None
