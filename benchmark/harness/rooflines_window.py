"""Operations and bytes of a prefill chunk's attention in a layer with a
window and in a full layer, computed from where the chunk starts (the
companion of ``rooflines.py`` for ``serve_smallthinker`` cells). The LEAST
work the mathematics asks for, whatever the program computes: a query at
position ``t`` needs the keys it can see, ``min(t + 1, window)`` of them in
a window layer and ``t + 1`` in a full layer, two products of ``2 H Dh``
FLOPs a key, and each K and V row the chunk can see read once."""


def _seen_keys(start: int, n: int, window=None) -> float:
    """Sum over the chunk's queries ``t = start .. start + n - 1`` of the
    keys each sees: ``t + 1``, or ``min(t + 1, window)``."""
    def upto(t):                        # sum_{u=1}^{t} min(u, window)
        if window is None or t <= window:
            return t * (t + 1) / 2.0
        return window * (window + 1) / 2.0 + (t - window) * float(window)
    return upto(start + n) - upto(start)


def prefill_attention(start: int, n: int, heads: int, kv_heads: int,
                      head_dim: int, window=None, itemsize: int = 2):
    """(flops, bytes) of ONE layer's attention over a chunk of ``n`` tokens
    at ``start``: scores and values, ``4 H Dh`` FLOPs a (query, seen key);
    bytes: K and V of the keys the chunk can see (history inside the
    window's reach plus the chunk), q read and the output written."""
    flops = 4.0 * heads * head_dim * _seen_keys(start, n, window)
    reach = start if window is None else min(start, window - 1)
    nbytes = itemsize * (2.0 * (reach + n) * kv_heads * head_dim
                         + 2.0 * n * heads * head_dim)
    return flops, nbytes
