"""The grouped matrix product of the expert layer (moe/expert_share.py):
rows of ``lhs`` ``[m, k]`` sorted into groups, each group times its own
``[k, n]`` matrix of a stack ``rhs`` ``[G, k, n]``.

The kernel is megablox's (``jax.experimental.pallas.ops.tpu.megablox.gmm``
as shipped with jax 0.9.0): its body, its grid ``(n tiles, visited row
tiles, k tiles)``, its block shapes and its masks are kept as they are, so
the numbers are megablox's bit for bit (tests/test_grouped_matmul.py). What
differs is who makes the group metadata and where the groups lie in the
stack:

- megablox makes the metadata INSIDE every product, over every group of
  ``rhs``. A layer loop hands the product every sparse layer's experts
  ``[layers * count, k, n]`` (a layer's slice for a custom call is a copy
  of it: PERF.md, PR 28), so each of a layer's three products asked for
  the same metadata over ``layers * count`` groups, of which ``count``
  hold rows: 151 us a layer at 768 groups and 5,120 rows, beside kernels
  of 95 (PERF.md, PR 57). Here the metadata is an OPERAND: the layer makes
  it once, over its own ``count`` groups (:func:`group_metadata`), and
  hands the same arrays to all three products;
- ``group_base`` is where the layer's groups begin in the stack: the
  weight block's index map ADDS it to the tile's group, so the kernel reads
  the whole stack in place. (megablox's ``group_offset`` is for a SHARDED
  ``rhs`` and subtracts.)

Left out of megablox's ``gmm`` because nothing here uses them:
``transpose_rhs``, ``existing_out``, the tiling look-up, the VJP."""

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


class GroupMetadata(NamedTuple):
    """What the kernel's index maps read, as megablox's
    ``make_group_metadata(..., visit_empty_groups=False)`` gives it for the
    same groups: ``offsets`` ``[G + 1]`` (the row where each group begins;
    the last is the rows in all groups), ``group_ids`` and ``m_tile_ids``
    ``[m // tm + G - 1]`` (the group and the row tile of each visited tile,
    in grid order) and ``num_tiles`` (how many of them the grid visits), all
    int32."""
    offsets: jnp.ndarray
    group_ids: jnp.ndarray
    m_tile_ids: jnp.ndarray
    num_tiles: jnp.ndarray


def _repeat_ids(counts, length: int):
    """``jnp.repeat(arange(len(counts)), counts, total_repeat_length=
    length)``, the tail filled with the last id: entry ``i`` is how many of
    the counts' running sums lie at or below ``i``. One compare and one sum
    over ``[length, len(counts)]`` where ``repeat`` is a search."""
    ends = jnp.cumsum(counts)
    at = jnp.arange(length, dtype=jnp.int32)
    ids = jnp.sum(ends[None, :] <= at[:, None], axis=1, dtype=jnp.int32)
    return jnp.minimum(ids, counts.shape[0] - 1)


def group_metadata(group_sizes, m: int, tm: int) -> GroupMetadata:
    """The metadata of a product of ``m`` rows in row tiles of ``tm``
    (``tm`` divides ``m``) whose first ``sum(group_sizes)`` rows lie in the
    groups, in order. An empty group is not visited; a row tile that two
    groups share is visited once for each, consecutively; rows behind the
    last group belong to no visited tile or are masked out of it.

    The arithmetic is megablox's: a group's tiles run from the tile of its
    first row to the tile of its last, and a row tile is visited once by the
    group of its first row and once more by every group that begins inside
    it. The arrays are small (``G`` and ``m // tm + G - 1`` entries), so
    both id lists are made by compare-and-sum, not by a search."""
    G = group_sizes.shape[0]
    tiles_m = m // tm
    assert tiles_m * tm == m, (m, tm)
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    filled = group_sizes > 0
    group_tiles = jnp.where(filled, (ends + tm - 1) // tm - starts // tm, 0)
    length = tiles_m + G - 1
    # one visit for the tile's owner, one more for each group that begins
    # inside the tile, past its first row
    inside = jnp.logical_and(filled, starts % tm != 0)
    tile = jnp.arange(tiles_m, dtype=jnp.int32)
    visits = 1 + jnp.sum(jnp.logical_and(
        inside[None, :], (starts // tm)[None, :] == tile[:, None]),
        axis=1, dtype=jnp.int32)
    return GroupMetadata(offsets, _repeat_ids(group_tiles, length),
                         _repeat_ids(visits, length),
                         jnp.sum(group_tiles, dtype=jnp.int32))


def gmm(lhs, rhs, metadata: GroupMetadata, group_base,
        tiling: Tuple[int, int, int], interpret: bool = False):
    """``lhs[offsets[g]:offsets[g + 1]] @ rhs[group_base + g]`` for each
    group ``g`` of ``metadata`` (:func:`group_metadata` of ``lhs``'s rows
    and ``tiling``'s row tile). lhs ``[m, k]``, rhs ``[G_all, k, n]`` with
    the groups at ``group_base ... group_base + G - 1`` (int32 scalar,
    traced or not), both in one dtype. Returns ``[m, n]`` in that dtype,
    accumulated in float32; rows of no group come back undefined."""
    (m, k), n = lhs.shape, rhs.shape[2]
    tm, tk, tn = tiling
    assert m % tm == 0 and rhs.shape[1] == k, (lhs.shape, rhs.shape, tiling)
    assert lhs.dtype == rhs.dtype, (lhs.dtype, rhs.dtype)
    tiles_k, k_rem = -(-k // tk), k % tk
    tiles_n = -(-n // tn)
    out_dtype = lhs.dtype
    base = jnp.asarray(group_base, jnp.int32).reshape(1)

    def kernel(offsets, group_ids, m_tile_ids, base, lhs, rhs, out, acc):
        grid_id, k_i = pl.program_id(1), pl.program_id(2)

        @pl.when(k_i == 0)
        def _zero_acc():
            acc[...] = jnp.zeros_like(acc)

        def mask_k_rem(x, dim):
            if k_rem == 0:
                return x
            iota = lax.broadcasted_iota(jnp.int32, x.shape, dim)
            return jnp.where(iota < k_rem, x.astype(jnp.float32),
                             0).astype(x.dtype)

        def _accum(is_last_k_tile):
            a, b = lhs[...], rhs[...]
            if is_last_k_tile:
                a, b = mask_k_rem(a, 1), mask_k_rem(b, 0)
            acc[...] += lax.dot_general(
                a, b, preferred_element_type=jnp.float32,
                dimension_numbers=(((1,), (0,)), ((), ())))
            if is_last_k_tile:
                # the rows of this tile that belong to this visit's group
                g = group_ids[grid_id]
                row = lax.broadcasted_iota(jnp.int32, (tm, tn), 0) \
                    + m_tile_ids[grid_id] * tm
                mask = jnp.logical_and(row >= offsets[g],
                                       row < offsets[g + 1])
                out[...] = lax.select(
                    mask, acc[...], out[...].astype(jnp.float32)
                ).astype(out_dtype)

        lax.cond(k_i == tiles_k - 1, functools.partial(_accum, True),
                 functools.partial(_accum, False))

    def lhs_index(n_i, grid_id, k_i, offsets, group_ids, m_tile_ids, base):
        return m_tile_ids[grid_id], k_i

    def rhs_index(n_i, grid_id, k_i, offsets, group_ids, m_tile_ids, base):
        return group_ids[grid_id] + base[0], k_i, n_i

    def out_index(n_i, grid_id, k_i, offsets, group_ids, m_tile_ids, base):
        return m_tile_ids[grid_id], n_i

    visited = metadata.group_ids.size
    cost = pl.CostEstimate(
        flops=2 * m * k * n, transcendentals=0,
        bytes_accessed=lhs.size * lhs.itemsize * tiles_n
        + k * n * rhs.itemsize * visited + m * n * out_dtype.itemsize)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            in_specs=[pl.BlockSpec((tm, tk), lhs_index),
                      pl.BlockSpec((None, tk, tn), rhs_index)],
            out_specs=pl.BlockSpec((tm, tn), out_index),
            grid=(tiles_n, metadata.num_tiles, tiles_k),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        cost_estimate=cost, name="gmm",
        # only when asked: a test's patched pallas_call keeps its own
        **({"interpret": True} if interpret else {}),
    )(metadata.offsets, metadata.group_ids, metadata.m_tile_ids, base,
      lhs, rhs)
