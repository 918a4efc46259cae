"""Operations and bytes of the expert-share layer and of paged decode
attention over two kinds of KV state, computed from shapes and counters
(the companion of ``rooflines.py`` for ``serve_exaone_moe`` cells)."""


def held_experts_decode(pairs: float, experts_touched: float, d_model: int,
                        d_ff: int, itemsize: int = 2):
    """(flops, bytes) of ONE sparse layer's grouped product in one decode
    dispatch: three matrices of ``d_model x d_ff`` per expert. FLOPs are
    those of the (token, expert) pairs on held experts, not of every expert
    on every token. Bytes: the weights of the held experts that were
    TOUCHED, read once, plus the rows moved (each pair's input row read
    for two products, its hidden row written and read, its output row
    written)."""
    per_expert = 3.0 * d_model * d_ff
    flops = 2.0 * pairs * per_expert
    rows = pairs * (2.0 * d_model + 2.0 * d_ff + d_model) * itemsize
    return flops, experts_touched * per_expert * itemsize + rows


def paged_decode_mixed_bytes(full_blocks: float, window_tokens: float,
                             block_size: int, kv_heads: int, head_dim: int,
                             full_layers: int, window_layers: int,
                             itemsize: int = 2):
    """Bytes one decode step has to read from the two kinds of KV state: K
    and V of every occupied block in each FULL layer, and of
    ``min(length + 1, window)`` tokens per slot in each WINDOW layer."""
    row = 2.0 * kv_heads * head_dim * itemsize
    return row * (full_blocks * block_size * full_layers
                  + window_tokens * window_layers)
