"""Order statistics used by the drivers and the per-layer readers."""

import numpy as np


def pct(values, q: float):
    """The q-th percentile (linear interpolation), or None when empty."""
    if len(values) == 0:
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))


def median(values):
    return pct(values, 50.0)
