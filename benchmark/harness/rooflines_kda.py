"""Operations and bytes of gated delta-rule linear attention (KDA), computed
from shapes and from what a dispatch carried (the companion of
``rooflines.py`` for ``serve_kimi_linear`` cells). Counted as the RECURRENCE
needs them, whatever chunking implements it: per token and head ``S'^T k``,
the rank-1 update and ``S^T q`` over a state of ``head_dim x head_dim``.
What a chunkwise form adds (pairwise decays, the triangular solve, products
at the highest precision) is the program's cost, and lowers its share."""


def kda_step(slots: float, heads: int, head_dim: int, state_itemsize: int = 4,
             row_itemsize: int = 4):
    """(flops, bytes) of ONE layer's recurrent step for ``slots`` decoding
    slots: 6 x head_dim^2 FLOPs a head; each slot's state read and written
    once, and its q, k, v, g (``head_dim`` each), b and o rows."""
    state = heads * head_dim * head_dim
    rows = heads * (5 * head_dim + 1)
    return (6.0 * slots * state,
            slots * (2.0 * state * state_itemsize + rows * row_itemsize))


def kda_chunk(tokens: float, heads: int, head_dim: int,
              state_itemsize: int = 4, row_itemsize: int = 4):
    """(flops, bytes) of ONE layer's rule over a prompt chunk of ``tokens``
    tokens of one slot: the same FLOPs a token as :func:`kda_step`; the
    slot's state read and written ONCE for the chunk, and every token's q,
    k, v, g, b and o rows."""
    state = heads * head_dim * head_dim
    rows = heads * (5 * head_dim + 1)
    return (6.0 * tokens * state,
            2.0 * state * state_itemsize + tokens * rows * row_itemsize)
