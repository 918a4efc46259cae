"""Order statistics used by the drivers and the per-layer readers."""

import numpy as np


def pct(values, q: float):
    """The q-th percentile (linear interpolation), or None when empty."""
    if len(values) == 0:
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))


def median(values):
    return pct(values, 50.0)


def request_mean_gaps_ms(requests, t0: float, t1: float):
    """``requests`` is [(finished_at, [(earlier, later), ...])], the stamps
    of each request's own consecutive tokens. For every request that
    finished inside [t0, t1]: the mean of its gaps that lie wholly inside,
    in ms (a request with no such gap gives nothing). What a reader of one
    answer feels as its speed: time per output token."""
    out = []
    for done, gaps in requests:
        if done is None or not t0 <= done <= t1:
            continue
        own = [b - a for a, b in gaps if a >= t0 and b <= t1]
        if own:
            out.append(1e3 * sum(own) / len(own))
    return out


def binned(events, t0: float, t1: float, width: float, mean=False):
    """``events`` is [(t, n)]. Per bin of ``width`` seconds of [t0, t1):
    the sum of n over the bin's seconds (a rate), or with ``mean`` the mean
    of n (None for an empty bin). The last bin may be shorter."""
    nbins = max(1, int(np.ceil((t1 - t0) / width - 1e-9)))
    sums, cnts = [0.0] * nbins, [0] * nbins
    for t, n in events:
        if t0 <= t < t1:
            i = min(int((t - t0) / width), nbins - 1)
            sums[i] += n
            cnts[i] += 1
    if mean:
        return [s / c if c else None for s, c in zip(sums, cnts)]
    return [s / min(width, t1 - t0 - i * width) for i, s in enumerate(sums)]

