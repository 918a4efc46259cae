"""Operations and bytes of grouped-query decode attention over the K and V
pools of a ``serve_zaya`` cell (the companion of ``rooflines.py``). Counted
as the ALGORITHM needs them, on the occupied rows: a tile that overhangs a
slot's length and a table entry that names trash are the program's costs,
and lower its share."""


def paged_decode_gqa(rows: float, heads: int, kv_heads: int, head_dim: int,
                     itemsize: int = 2):
    """(flops, bytes) of ONE layer's decode attention over ``rows`` cached
    tokens in all (the live slots' lengths, each with the token it has just
    written): per row and query head a score and a value product over
    ``head_dim``; each row's K and V of ``kv_heads x head_dim`` read once."""
    return (4.0 * rows * heads * head_dim,
            2.0 * rows * kv_heads * head_dim * itemsize)
