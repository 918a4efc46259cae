"""Latent (MLA) attention kernels: the absorbed decode through the block
table over a pool of latent rows (:func:`mla_decode_attention`), and one
flash step of the expanded prefill (:func:`mla_prefill_step`, at the end).

A token's cache row is ``[c_kv | k_r | 0...]``: the normalised latent, the
one rotated key all heads share, zero padding to whole lane tiles
(models/dots_vlm.py ``latent_lanes``). With the up-projection's key half
folded into the query (``q = [q_n W^K^T | q_r | 0...]``, one row a head)
every head's score against a cached token is ONE dot product with that
token's row, and its value is the row's first ``kv_lora_rank`` lanes: all
``H`` heads are rows of one product against a tile the step fetched ONCE,
and nothing is ever expanded to per-head keys and values. At 128 heads the
kernel does 2 x 128 x (640 + 512) FLOPs for a row of 1,280 bytes: the first
kernel here that the matrix unit and HBM bound alike.

The walk is ops/attention/paged.py's (its module docstring): the grid is
the work list of :func:`~deepspeed_tpu.ops.attention.paged.decode_plan`, a
step attends ``P`` consecutive table entries of one slot, each a BlockSpec
of one pool block whose index map reads the table, fp32 running max, sum
and accumulator in VMEM across a slot's tiles. What differs is the body:
one shared row for all heads, the value a lane slice of the key tile."""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.attention.paged import (
    LANES, NEG_INF, DecodePlan, _band, blocks_per_step, decode_plan,
    zero_idle_rows)


def _mla_decode_kernel(tables_ref, held_ref, slots_ref, tiles_ref,
                       lengths_ref, q_ref, *rest, bs: int, P: int, nb: int,
                       vw: int, scale: float):
    """One grid step: tile ``tiles_ref[w]`` of slot ``slots_ref[w]``.
    q_ref ``[1, H, row]``; then P refs of ONE pool block ``[1, bs, row]``
    each, table-indirected by their index maps; o_ref ``[1, H, vw]``."""
    row_refs, (o_ref, m_scr, l_scr, acc_scr) = rest[:P], rest[P:]
    b = slots_ref[pl.program_id(0)]
    j = tiles_ref[pl.program_id(0)]
    T = P * bs
    pos = lengths_ref[b]
    lo, hi = _band(pos, bs, nb, None, 1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    blocks = [r[0] for r in row_refs]                      # P x [bs, row]
    if P == 1:
        k = blocks[0]
        v = k[:, :vw]
    else:
        k = jnp.concatenate(blocks, axis=0)
        # a ref whose entry the slot does not attend holds whatever block
        # it held before (maybe another slot's): as keys the position mask
        # rules its columns out; as values they are read as zeros, so a
        # probability of 0 times another request's NaN is not a NaN here
        v = jnp.concatenate([
            jnp.where(jnp.logical_and(j * P + i >= lo, j * P + i <= hi),
                      blk[:, :vw], 0) for i, blk in enumerate(blocks)],
            axis=0)

    H = q_ref.shape[1]
    s = jax.lax.dot_general(q_ref[0], k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    cols = jax.lax.broadcasted_iota(jnp.int32, (H, T), 1) + j * T
    s = jnp.where(jnp.logical_and(cols <= pos, cols < nb * bs), s, NEG_INF)
    m_prev = m_scr[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_new = alpha * l_scr[:, :1] + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)                # [H, vw]
    m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
    l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(j == hi // P)
    def _finish():
        l = l_scr[:, :1]
        o_ref[0] = (acc_scr[...] / jnp.where(l == 0.0, 1.0, l)
                    ).astype(o_ref.dtype)


def mla_decode_attention(q: jnp.ndarray, pool: jnp.ndarray,
                         tables: jnp.ndarray, lengths: jnp.ndarray, *,
                         value_width: int, scale: float,
                         interpret: bool = False,
                         plan: Optional[DecodePlan] = None) -> jnp.ndarray:
    """One new token per serving slot over the latent pool, THROUGH the
    block table. q ``[B, H, row]``: each head's absorbed query in the
    row's lanes (zeros in the padding); pool ``[N', block, row]`` (the
    token's own row already written at position ``lengths[b]``); tables
    ``[B, NB]`` into dimension 0; lengths ``[B]``. Returns ``[B, H,
    value_width]``: per head, the attention-weighted mean of the rows'
    first ``value_width`` lanes (the latents), to be up-projected by the
    caller. ``plan``: :func:`decode_plan` of these lengths and this
    table. Call it under ``jax.jit`` (paged.py)."""
    B, H, row = q.shape
    N, bs, prow = pool.shape
    assert prow == row and value_width <= row, \
        (q.shape, pool.shape, value_width)
    tables = jnp.asarray(tables, jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32)
    nb = tables.shape[1]
    # the token rule's tile (paged.py's byte rule is paged_decode's: a step
    # here is saturated at a block of 512, PERF.md 6, PR 46)
    P = blocks_per_step(nb, bs)
    nt = -(-nb // P)
    if plan is None:
        plan = decode_plan(lengths, nb, bs)
    assert plan.cut == (nb, bs, None, 1, P) \
        and plan.held.shape == (P, B * nt), (plan.cut, plan.held.shape)

    def qmap(w, tables_ref, held_ref, slots_ref, tiles_ref, lengths_ref):
        return (slots_ref[w], 0, 0)

    def rowmap(i):
        def imap(w, tables_ref, held_ref, slots_ref, tiles_ref,
                 lengths_ref):
            return (tables_ref[held_ref[i, w]], 0, 0)
        return imap

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(plan.steps,),
        in_specs=[pl.BlockSpec((1, H, row), qmap)]
        + [pl.BlockSpec((1, bs, row), rowmap(i)) for i in range(P)],
        out_specs=pl.BlockSpec((1, H, value_width), qmap),
        scratch_shapes=[pltpu.VMEM((H, LANES), jnp.float32),    # max
                        pltpu.VMEM((H, LANES), jnp.float32),    # sum
                        pltpu.VMEM((H, value_width), jnp.float32)])
    kernel = functools.partial(_mla_decode_kernel, bs=bs, P=P, nb=nb,
                               vw=value_width, scale=float(scale))
    out = pl.pallas_call(
        kernel, name="mla_decode", grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, value_width), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        **({"interpret": True} if interpret else {}),
    )(tables.reshape(-1), plan.held, plan.slot, plan.tile, lengths, q,
      *([pool] * P))
    return zero_idle_rows(out, plan)


def mla_decode_reference(q, pool, tables, lengths, *, value_width: int,
                         scale: float):
    """The plain latent decode :func:`mla_decode_attention` is held to,
    and the portable path: the slot's whole virtual cache gathered,
    ``[B, NB*block, row]``, then masked by position."""
    B, H, row = q.shape
    rows = pool[tables].reshape(B, -1, row)
    s = jnp.einsum("bhr,bsr->bhs", q, rows).astype(jnp.float32) * scale
    idx = jax.lax.broadcasted_iota(jnp.int32, (1, 1, rows.shape[1]), 2)
    s = jnp.where(idx <= lengths[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bhs,bsv->bhv", p, rows[..., :value_width])


def _mla_prefill_kernel(qpos_ref, kpos_ref, qn_ref, qr_ref, kn_ref, kr_ref,
                        v_ref, m_ref, l_ref, acc_ref, mo_ref, lo_ref, o_ref,
                        *, scale: float):
    """One grid step: head ``h``'s ``C`` queries against key block ``j`` of
    the call, everything with the QUERIES IN THE LANES. qn_ref ``[1, d_n,
    C]``, qr_ref ``[1, d_r, C]``; kn_ref ``[1, T, d_n]``, kr_ref ``[T,
    d_r]`` (the ONE shared key), v_ref ``[1, d_v, T]``; qpos_ref ``[1,
    C]``, kpos_ref ``[T, 1]``. The carry's blocks are the head's max and sum
    ``[1, 1, C]`` and accumulator ``[1, d_v, C]``, held in the output
    blocks across the head's key blocks. The scores ``[T, C]`` live here
    only; their max and sum over the keys run down the sublanes and meet
    the carry's rows as they are, so no step moves a vector between rows
    and columns."""
    @pl.when(pl.program_id(1) == 0)
    def _init():
        mo_ref[...] = m_ref[...]
        lo_ref[...] = l_ref[...]
        o_ref[...] = acc_ref[...]

    nn = (((1,), (0,)), ((), ()))
    s = (jax.lax.dot_general(kn_ref[0], qn_ref[0], nn,
                             preferred_element_type=jnp.float32)
         + jax.lax.dot_general(kr_ref[...], qr_ref[0], nn,
                               preferred_element_type=jnp.float32)) * scale
    s = jnp.where(kpos_ref[...] <= qpos_ref[...], s, NEG_INF)    # [T, C]
    m_prev = mo_ref[0]                                           # [1, C]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    lo_ref[0] = alpha * lo_ref[0] + jnp.sum(p, axis=0, keepdims=True)
    v = v_ref[0]                                                 # [d_v, T]
    o_ref[0] = o_ref[0] * alpha + jax.lax.dot_general(
        v, p.astype(v.dtype), nn, preferred_element_type=jnp.float32)
    mo_ref[0] = m_new


# float32 scores and probabilities and their cast, bytes a (key, query)
# pair, and what of VMEM a step's may fill
_STEP_PAIR_BYTES = 12
_STEP_VMEM = 24 << 20


def mla_prefill_step(carry, q_n, q_r, k_n, k_r, v, kpos, qpos, scale, *,
                     blocks: Optional[int] = None, interpret: bool = False):
    """One flash step of the EXPANDED prefill for all heads of a chunk:
    inference/latent.py ``_attend_tile``'s arithmetic with the queries in
    the minor dimension. Queries ``q_n`` ``[H, d_n, C]`` / ``q_r`` ``[H,
    d_r, C]`` at ``qpos`` ``[C]`` against a tile's per-head keys ``k_n``
    ``[H, S, d_n]`` and values ``v`` ``[H, d_v, S]`` and the ONE key
    ``k_r`` ``[S, d_r]`` all heads share, at ``kpos`` ``[S]`` (a key a
    query may not see: ``kpos > qpos``); ``carry`` = float32 (max ``[H, 1,
    C]``, sum ``[H, 1, C]``, accumulator ``[H, d_v, C]``), returned
    advanced. The score is ``k_n q_n + k_r q_r`` in float32; probabilities
    are cast to ``v.dtype`` for the value product, which accumulates in
    float32: nothing narrower anywhere. The grid is (heads, ``blocks`` key
    blocks of ``S / blocks``): a head's carry stays in VMEM over its key
    blocks and the score tile never leaves it. A grid step costs about a
    microsecond whatever it attends, so ``blocks`` is by default the
    fewest whose score tile fits ``_STEP_VMEM`` (ONE up to 2,048 keys
    under 512 queries). The carry is updated in place: call it under
    ``jax.jit`` with the carry donated or dead."""
    m, l, acc = carry
    H, dn, C = q_n.shape
    S, dr = k_r.shape
    dv = v.shape[1]
    if blocks is None:
        blocks = next(b for b in range(1, S + 1) if S % b == 0
                      and _STEP_PAIR_BYTES * (S // b) * C <= _STEP_VMEM)
    assert S % blocks == 0, (S, blocks)
    T = S // blocks
    assert k_n.shape == (H, S, dn) and v.shape == (H, dv, S) \
        and q_r.shape == (H, dr, C) and acc.shape == (H, dv, C) \
        and m.shape == l.shape == (H, 1, C), \
        (q_n.shape, q_r.shape, k_n.shape, k_r.shape, v.shape, acc.shape)

    def head(h, j):
        return (h, 0, 0)

    row = pl.BlockSpec((1, 1, C), head)
    # the step's score tile beside the double-buffered operands: more than
    # Mosaic's default scope at 1,024 keys and up
    vmem = _STEP_PAIR_BYTES * T * C + 8 * (
        T * (dn + dr + dv) + C * (dn + dr + 4 * dv))
    kernel = functools.partial(_mla_prefill_kernel, scale=float(scale))
    return tuple(pl.pallas_call(
        kernel, name="mla_prefill", grid=(H, blocks),
        in_specs=[pl.BlockSpec((1, C), lambda h, j: (0, 0)),
                  pl.BlockSpec((T, 1), lambda h, j: (j, 0)),
                  pl.BlockSpec((1, dn, C), head),
                  pl.BlockSpec((1, dr, C), head),
                  pl.BlockSpec((1, T, dn), lambda h, j: (h, j, 0)),
                  pl.BlockSpec((T, dr), lambda h, j: (j, 0)),
                  pl.BlockSpec((1, dv, T), lambda h, j: (h, 0, j)),
                  row, row, pl.BlockSpec((1, dv, C), head)],
        out_specs=[row, row, pl.BlockSpec((1, dv, C), head)],
        out_shape=[jax.ShapeDtypeStruct(a.shape, jnp.float32) for a in carry],
        input_output_aliases={7: 0, 8: 1, 9: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=max(32 << 20, 2 * vmem)),
        **({"interpret": True} if interpret else {}),
    )(jnp.asarray(qpos, jnp.int32)[None, :],
      jnp.asarray(kpos, jnp.int32)[:, None], q_n, q_r, k_n, k_r, v, m, l,
      acc))
