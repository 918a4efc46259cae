"""Operations and bytes of a Mamba-1 selective state-space recurrence,
computed from shapes and from what a dispatch carried (the companion of
``rooflines.py`` for ``serve_jamba`` cells). Counted as the RECURRENCE needs
them, whatever implements it: per token, ``d_inner x d_state`` state values
each decayed (one ``exp``, one product), written to (two products, one sum)
and read (one product, one sum). None of it is a matrix product: the
chip's published peak (197 TFLOP/s) is the matrix unit's and says nothing
about this work, whose ceiling is the vector unit's, which has no
published peak. The shares below are therefore against the BYTES alone."""

ELEMENT_OPS = 7     # exp, delta A, decay, delta x, B (delta x), +, C h


def ssm_step(slots: float, d_inner: int, d_state: int,
             state_itemsize: int = 4, row_itemsize: int = 4):
    """(element operations, bytes) of ONE layer's recurrent step for
    ``slots`` decoding slots: each slot's state read and written once, and
    its x, delta and y rows (``d_inner`` each) and B, C (``d_state``)."""
    state = d_inner * d_state
    rows = 3 * d_inner + 2 * d_state
    return (ELEMENT_OPS * slots * state,
            slots * (2.0 * state * state_itemsize + rows * row_itemsize))


def ssm_scan(tokens: float, d_inner: int, d_state: int,
             state_itemsize: int = 4, row_itemsize: int = 4):
    """(element operations, bytes) of ONE layer's recurrence over a prompt
    chunk of ``tokens`` tokens of one slot: the same operations a token as
    :func:`ssm_step`; the slot's state read and written ONCE for the chunk,
    and every token's x, delta, y, B and C rows."""
    state = d_inner * d_state
    rows = 3 * d_inner + 2 * d_state
    return (ELEMENT_OPS * tokens * state,
            2.0 * state * state_itemsize + tokens * rows * row_itemsize)

