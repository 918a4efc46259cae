"""Shapes and problems shared by the paged-attention kernel tests
(tests/test_paged_attention.py: the kernel against its reference;
tests/test_paged_attention_plan.py: its work list)."""

import functools

import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.attention.paged import blocks_per_step

# the serving cells' head shapes (heads, kv heads, head size, table
# entries, window, block) with small pools, and a table whose length is
# prime
CELL_SHAPES = [
    pytest.param(25, 25, 64, 64, None, 16, id="gpt2-xl-table64"),
    pytest.param(64, 8, 128, 256, None, 16, id="kexaone-full-table256"),
    pytest.param(64, 8, 128, 9, 128, 16, id="kexaone-ring9-window128"),
    pytest.param(25, 25, 64, 13, None, 16, id="gpt2-xl-prime-table13"),
]
# large blocks, where the tile follows the row's bytes (float32 pools here:
# rows of 1,024 and of 256 bytes): 4 blocks of 128 and of 512 a step; a
# window ring and a table that do not divide by 4, a band that starts in
# the middle of a tile
BYTE_SHAPES = [
    pytest.param(8, 2, 128, 33, 4096, 128, id="block128-ring33-window4096"),
    pytest.param(8, 2, 128, 20, 700, 128, id="block128-table20-window700"),
    pytest.param(4, 1, 64, 6, None, 512, id="block512-table6-mqa"),
]
CELL_SHAPES += BYTE_SHAPES


def row_bytes(Hkv, Dh):
    return Hkv * Dh * 4                 # float32 pools


def edge_lengths(nb, bs, window, P):
    """Slot lengths at every edge of a block, a tile (``P`` blocks) and
    the table, and one whose band starts in the middle of a tile. A ring
    table's lengths are relative to its first block, so they stay within
    the ring and (the band being the caller's whole table) past nothing
    the window has dropped."""
    edges = [0, bs - 1, bs, P * bs - 1, P * bs, P * bs + 1, nb * bs - 1]
    if window is not None:
        edges += [window - 1, window, window + bs // 2,
                  window + (P // 2) * bs + bs // 2]
    return sorted({min(n, nb * bs - 1) for n in edges})


@functools.lru_cache(maxsize=None)
def _cell_problem(H, Hkv, Dh, nb, window, seed, bs):
    """A slot per edge length plus one whose unused table entries name
    the trash block 0 (as the paged cache leaves them); the other slots'
    entries past their length name blocks of their own, poisoned."""
    rng = np.random.default_rng(seed)
    lengths = edge_lengths(
        nb, bs, window, blocks_per_step(nb, bs, row_bytes(Hkv, Dh)))
    lengths.append(lengths[len(lengths) // 2])       # the trash-table slot
    B = len(lengths)
    N = B * nb + 1
    q = jnp.asarray(rng.normal(size=(B, Hkv, H // Hkv, Dh)), jnp.float32)
    kp = rng.normal(size=(N, bs, Hkv * Dh)).astype(np.float32)
    vp = rng.normal(size=(N, bs, Hkv * Dh)).astype(np.float32)
    tables = rng.permutation(np.arange(1, N)).reshape(B, nb).astype(np.int32)
    tables[-1, lengths[-1] // bs + 1:] = 0
    return q, kp, vp, tables, np.asarray(lengths, np.int32)


def cell_problem(H, Hkv, Dh, nb, window, seed=0, bs=16):
    """``(q, k pool, v pool, tables, lengths)`` of :func:`_cell_problem`,
    drawn once a shape a process (the largest pools are 128 MiB each and
    take seconds to draw); the numpy arrays are copies, for the tests
    poison blocks and rewrite tables and lengths in place."""
    q, *arrays = _cell_problem(H, Hkv, Dh, nb, window, seed, bs)
    return (q, *(a.copy() for a in arrays))


# ZAYA1's attention (8 query / 2 KV heads of 128, a table of 6 blocks)
# beside the older cells'; its block is 1,024 on the chip, 128 here; and
# the tiles by the bytes
MASKED_SHAPES = CELL_SHAPES[:3] + [
    pytest.param(8, 2, 128, 6, None, 128, id="zaya1-table6")] + BYTE_SHAPES
