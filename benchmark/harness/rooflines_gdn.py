"""Operations and bytes of Gated DeltaNet linear attention (the delta rule
with ONE decay a head a token), computed from shapes and from what a
dispatch carried (the companion of ``rooflines.py`` for ``serve_qwen3_next``
cells). Counted as the RECURRENCE needs them, whatever implements it: per
token and value head ``S'^T k``, the rank-1 update and ``S^T q`` over a
state of ``head_dim x head_dim``. What a chunkwise form adds (the pair
products, the triangular solve, products at the highest precision) and what
a step kernel's packed rows add is the program's cost, and lowers its
share."""


def _rows(heads: int, head_dim: int) -> int:
    """Values a token brings to and takes from the rule: a value head's q,
    k, v and o rows and its two scalars, the decay and the write strength."""
    return heads * (4 * head_dim + 2)


def gdn_step(slots: float, heads: int, head_dim: int, state_itemsize: int = 4,
             row_itemsize: int = 4):
    """(flops, bytes) of ONE layer's recurrent step for ``slots`` decoding
    slots: 6 x head_dim^2 FLOPs a value head; each slot's state read and
    written once, and its rows."""
    state = heads * head_dim * head_dim
    return (6.0 * slots * state,
            slots * (2.0 * state * state_itemsize
                     + _rows(heads, head_dim) * row_itemsize))


def gdn_chunk(tokens: float, heads: int, head_dim: int,
              state_itemsize: int = 4, row_itemsize: int = 4):
    """(flops, bytes) of ONE layer's rule over a prompt chunk of ``tokens``
    valid tokens of one slot: the same FLOPs a token as :func:`gdn_step`;
    the slot's state read and written ONCE for the chunk, and every token's
    rows."""
    state = heads * head_dim * head_dim
    return (6.0 * tokens * state,
            2.0 * state * state_itemsize
            + tokens * _rows(heads, head_dim) * row_itemsize)
