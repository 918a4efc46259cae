"""Operations and bytes of latent (MLA) attention, computed from shapes and
from what a dispatch carried (the companion of ``rooflines.py`` for
``serve_dots_vlm`` cells). Counted as the ALGORITHM needs them, on the
unpadded row (the latent and the shared rotated key): a pool that stores
padding, a tile that overhangs a slot's length and a masked half of a
causal tile are the program's costs, and lower its share."""


def mla_decode(rows: float, heads: int, latent: int, d_r: int,
               itemsize: int = 2):
    """(flops, bytes) of ONE layer's absorbed decode attention over
    ``rows`` cached tokens in all (the live slots' lengths, each with the
    token it has just written): per row and head a score over ``latent +
    d_r`` values and a value of ``latent``; each row read once."""
    return (2.0 * rows * heads * (2 * latent + d_r),
            rows * (latent + d_r) * itemsize)


def mla_prefill(n: float, history: float, heads: int, latent: int, d_n: int,
                d_r: int, d_v: int, itemsize: int = 2):
    """(flops, bytes) of ONE layer's expanded prefill attention for a chunk
    of ``n`` tokens over ``history`` cached ones: the re-expansion of the
    history's and the chunk's rows through the up-projection, scores and
    values of every (query, visible key) pair (the chunk's own half
    causal), and the history's rows read once. Returns also the
    re-expansion's FLOPs alone."""
    expand = 2.0 * (history + n) * latent * heads * (d_n + d_v)
    pairs = n * history + n * (n + 1) / 2.0
    attend = 2.0 * pairs * heads * (d_n + d_r + d_v)
    nbytes = (history + n) * (latent + d_r) * itemsize
    return expand + attend, nbytes, expand
