"""Peak bytes in use over the device's limit, fullest device (model and remat policy, models/gpt.py)."""

from harness import readers


def read(run):
    return readers.train_peak_hbm_share(run)
