"""A prefill chunk's write into the pool, block by block, against the row
scatter it replaced (``pool.at[blk, pos % bs].set(rows)``): the two leave
the same pool, bit for bit, outside the trash block."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.paged_cache import (chunk_blocks, write_chunk,
                                                 write_window)


def _nb(C, bs):
    """Table entries a slot: a row that holds two chunks and more."""
    return max(8, 2 * C // bs + 2)


def row_scatter(pool, table_row, start, n_valid, rows, base):
    """The statement the four prefill programs held until PR 48."""
    C, bs, nb = rows.shape[0], pool.shape[1], table_row.shape[0]
    positions = start + jnp.arange(C)
    blk = table_row[jnp.clip(positions // bs, 0, nb - 1)]
    blk = jnp.where(jnp.arange(C) < n_valid, blk, 0) + base
    return pool.at[blk, positions % bs].set(rows)


# one compilation a shape: ``start``, ``n_valid`` and ``base`` are traced
NEW, OLD = jax.jit(write_chunk), jax.jit(row_scatter)


def _case(C, bs, lanes, dtype, base, seed=0):
    rng = np.random.default_rng(seed)
    NB = _nb(C, bs)
    n_blocks = NB + 3                       # block 0 of a layer is trash
    pool = jnp.asarray(rng.standard_normal((base + n_blocks, bs, lanes)),
                       dtype)
    table_row = jnp.asarray(1 + rng.permutation(n_blocks - 1)[:NB], jnp.int32)
    rows = jnp.asarray(rng.standard_normal((C, lanes)), dtype)
    return pool, table_row, rows


def _starts(C, bs):
    """Aligned, mid-block, and the chunk that ends with the table."""
    last = _nb(C, bs) * bs - C
    return {"first": 0, "aligned": bs, "mid": bs + bs // 2 + 1,
            "odd": 1, "last": last, "last-mid": last - 1}


# (C, bs, lanes): the cells' chunks and blocks, and a latent cell's row of
# 640 lanes, whose block of 512 is written in windows of 128
SHAPES = [(64, 16, 128), (512, 512, 128), (512, 1024, 128), (256, 16, 128),
          (16, 4, 128), (512, 512, 640)]
IDS = [f"{c}in{b}x{l}" for c, b, l in SHAPES]


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("base", [0, 5], ids=["base0", "base5"])
@pytest.mark.parametrize("which", ["first", "aligned", "mid", "odd", "last",
                                   "last-mid"])
@pytest.mark.parametrize("C,bs,lanes", SHAPES, ids=IDS)
def test_write_chunk_leaves_the_pool_the_row_scatter_leaves(C, bs, lanes,
                                                            which, base,
                                                            dtype):
    pool, table_row, rows = _case(C, bs, lanes, dtype, base)
    start = _starts(C, bs)[which]
    for n_valid in (0, 1, C - 1, C):
        got = np.asarray(NEW(pool, table_row, start, n_valid, rows, base)
                         .astype(jnp.float32))
        want = np.asarray(OLD(pool, table_row, start, n_valid, rows, base)
                          .astype(jnp.float32))
        # the trash block of this layer holds whatever came last
        np.testing.assert_array_equal(np.delete(got, base, axis=0),
                                      np.delete(want, base, axis=0),
                                      err_msg=f"n_valid={n_valid}")
        # and the rows are there: the scatter is not the only witness
        at = np.arange(start, start + n_valid)
        np.testing.assert_array_equal(
            got[np.asarray(table_row)[at // bs] + base, at % bs],
            np.asarray(rows[:n_valid].astype(jnp.float32)))
        assert chunk_blocks(start, n_valid, bs, _nb(C, bs))[1] \
            == len(np.unique(at // bs))


@pytest.mark.parametrize("shape,windows,g", zip(
    SHAPES, [5, 2, 2, 17, 5, 5], [16, 512, 1024, 16, 4, 128]), ids=IDS)
def test_window_count_is_static_and_small(shape, windows, g):
    """The scatter the program holds has a few update windows of a whole
    block (of 128 rows of a latent cell's block), whatever ``start`` is:
    read off the traced program."""
    C, bs, lanes = shape
    pool, table_row, rows = _case(C, bs, lanes, jnp.bfloat16, 0)
    assert write_window(bs, lanes * 2) == g
    text = jax.jit(write_chunk).lower(pool, table_row, jnp.int32(3),
                                      jnp.int32(C), rows, 0).as_text()
    assert sum("stablehlo.scatter" in ln for ln in text.splitlines()) == 1
    assert f"tensor<{windows}x{g}x{lanes}xbf16>" in text


@pytest.mark.parametrize("bs,row_bytes,g", [
    (16, 3200, 16),             # GPT-2 XL: the block, 50 KiB
    (16, 2048, 16),             # K-EXAONE's full layers
    (512, 1280, 128),           # a latent row of 640 lanes: 160 KiB a window
    (1024, 512, 512),           # ZAYA1: 256 KiB
    (512, 256, 512),            # Jamba2: the block, 128 KiB
    (64, 16384, 16),            # never a part of the device's tile of 16
    (16, 65536, 16), (4, 512, 4)])
def test_write_window_fits_the_budget_in_whole_tiles(bs, row_bytes, g):
    assert write_window(bs, row_bytes) == g and bs % g == 0


@pytest.mark.parametrize("start,n,bs,nb,want", [
    (0, 64, 16, 64, (0, 4)),          # docs: an aligned chunk of 64 in 16
    (8, 64, 16, 64, (0, 5)),          # a copy-on-write block: one more
    (0, 512, 512, 48, (0, 1)),        # the 512 / 512 cells
    (512, 300, 512, 48, (1, 1)),
    (100, 512, 1024, 12, (0, 1)),
    (1000, 512, 1024, 12, (0, 2)),
    (48, 0, 16, 64, (3, 0)),          # no real row: nothing live
    (40, 0, 16, 64, (2, 0)),
    (1008, 64, 16, 64, (63, 1)),      # never past the table's end
    (7, 1, 4, 8, (1, 1)),
])
def test_chunk_blocks_for_ints_and_tracers(start, n, bs, nb, want):
    first, count = chunk_blocks(start, n, bs, nb)
    assert (first, count) == want and isinstance(count, int)
    traced = jax.jit(chunk_blocks, static_argnums=(2, 3))(start, n, bs, nb)
    assert tuple(int(t) for t in traced) == want
