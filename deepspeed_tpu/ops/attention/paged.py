"""Paged-attention decode kernel: Pallas TPU flash-decode through the
block table, a tile of 128 to 256 tokens a grid step or, where a block is
large, of the fewest blocks whose K and V make a megabyte.

The pool this kernel reads is the one the paged cache stores and the
layer loop carries: ``[N', block, Hkv*Dh]``, a token's kv heads side by
side in ONE row of ``Hkv*Dh`` lanes (inference/paged_cache.py): row-major
on the device as it is here, so the entry parameter, the loop's state and
this kernel's operand are one layout and nothing copies the pool. ``N'``
is whatever the caller stacked: the serving programs hand over all
layers' pools as ``[L*N, ...]`` and address layer ``l`` by
``tables + l*N``.

The walk (vLLM's PagedAttention, Kwon et al. 2023, with FlashAttention-2's
online softmax, Dao 2023):

- one grid step attends a TILE of ``P`` consecutive table entries of
  one slot, ``T = P * block`` key positions; :func:`blocks_per_step`
  works ``P`` out from the table's, the block's and the pool row's sizes
  alone (8 blocks of 16; the window ring's 9 entries are one tile; 4
  blocks of 128 rows of 1 KiB, or of 512 rows of 256 bytes: a window
  ring of 33 such blocks is 9 steps a slot, not 33). A step's fetch
  does not hide what the step costs besides (0.8 us at one block of
  256 KiB whose bytes take 0.32; 1.6 at four, whose bytes take 1.28:
  PERF.md 6, PR 53), so a step that fetches little spends the call on
  steps: the tile follows the BYTES, ``STEP_BYTES`` of K and V a step,
  and the block size stays the allocator's to choose;
- the grid is a WORK LIST, not (slots x tiles): :func:`decode_plan`
  works out from the lengths, in XLA, each step's slot and tile (a
  slot's tiles from the one that holds its band's first block to the
  one that holds position ``lengths[b]``, slot after slot), and the
  grid's bound is their number, a dynamic one. A caller with many layers at the same lengths works
  the plan out once and passes it to every call. A caller that knows
  which slots decode says so (``active=``): the others (no request, or
  one still in prefill, whose length is its progress) have NO step, and
  the call gives their rows back as zeros;
- the pools stay where they are and are handed over ``P`` times each,
  every view a BlockSpec of ONE block whose index map reads the block
  table (scalar-prefetched, with the plan): Pallas pipelines the ``2P``
  fetches of the next step under this one, and the kernel lays the
  blocks side by side as the ``[T, Hkv*Dh]`` tile. A view whose entry
  the slot does not attend (past its length, below its band, past the
  table) names the block it named before, and an index that does not
  change is not fetched again: per-token HBM traffic is O(actual
  length), not O(S_max), and no dense gathered copy exists. What such
  a view holds may be another slot's block, so the kernel reads it as
  zeros: no slot's data, finite or not, reaches another slot's row. (Copying
  the blocks by hand from a pool in ``pl.ANY`` would cost fewer index
  maps a step, but Mosaic refuses a slice of an HBM ref whose minor
  dimension does not divide by 128, and GPT-2's row is 1,600 lanes);
- the tile that holds the last (or the band's first) position is masked
  by position exactly like the gather path, which also covers the
  blocks a tile did not fetch and a last tile that overhangs the table
  (``NB`` need not divide by ``P``: 33 ring blocks in tiles of 4);
- all heads of a tile at once, with the tile's keys on the lanes:
  scores are ``[rows, T]``, so the softmax update fills whole
  registers. The kv heads go in chunks whose lanes end on a lane tile's
  edge (:func:`_head_chunk`): one head of 128 lanes (GQA packs the
  ``group`` query heads that share it into the product's rows), or the
  whole row where heads of 64 do not pair up (GPT-2's 25). A chunk of
  several heads is ONE product against a block-diagonal left side (the
  kernel's docstring), so the keys pass through the matrix unit once
  and no head is a one-row product of its own;
- fp32 running max / sum / accumulator live in VMEM scratch across a
  slot's tiles; probabilities are cast to the pool's dtype before
  ``P.V``; int8 pools are dequantised in registers by the tile's
  ``[P, Hkv]`` scales.

The gather path (`gather_pool_blocks` below: the whole virtual cache
``[B, NB*block, Hkv, Dh]`` materialised, then masked) remains the
reference implementation, numerically interchangeable (docs/PARITY.md),
and the non-TPU default. Interpret mode is the tests' business: they
pass ``interpret=True`` or patch ``pl.pallas_call`` (tests/conftest.py
``pallas_interpret``); nothing on the serving path selects it, so the
kernel asked for off a TPU is an error, not an interpreted run.
"""

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128
TILE_TOKENS = 128     # the fewest key positions a full tile holds
# what a paged_decode grid step fetches, K and V together, before it takes
# no further table entry, and the most entries (pool views a side) the byte
# rule takes: blocks_per_step; the census behind the number: PERF.md 6, PR 53
STEP_BYTES = 1 << 20
STEP_VIEWS = 8


def resolve_decode_impl(impl: Optional[str] = None) -> str:
    """Resolve the paged-decode implementation switch.

    Explicit argument wins, else the ``DS_PAGED_DECODE_IMPL`` env var,
    else the platform default: ``"pallas"`` on TPU, ``"gather"``
    elsewhere (the gather path is the reference implementation and the
    portable fallback). Shared by InferenceEngine and ServingEngine so
    env overrides work uniformly."""
    if impl is None:
        from deepspeed_tpu.utils.env import resolve_flag
        impl = resolve_flag("DS_PAGED_DECODE_IMPL")
    if impl is None:
        from deepspeed_tpu.utils import on_tpu
        impl = "pallas" if on_tpu() else "gather"
    if impl not in ("pallas", "gather"):
        # ValueError, not assert: validates user input (env var / config)
        # and must survive python -O
        raise ValueError(f"unknown paged decode impl {impl!r}: "
                         f"expected 'pallas' or 'gather'")
    return impl


def pool_row_bytes(pool) -> int:
    """Bytes of one row of a pool (its last dimension): what a key
    position costs a fetch of K, and as much again of V."""
    return pool.shape[-1] * jnp.dtype(pool.dtype).itemsize


def blocks_per_step(nb: int, bs: int, row_bytes: Optional[int] = None) -> int:
    """Table entries one grid step attends (``P``), from the static
    shapes alone.

    The token rule (``row_bytes`` None: ``mla_decode``'s tile, and the
    base of a prefill chunk's read lengths, engine.attended_tiles): the
    fewest blocks that make ``TILE_TOKENS`` tokens, or the whole table
    where that is no more than two such tiles (the window ring's 9 x 16
    = 144 tokens are one step).

    The byte rule (``row_bytes``: :func:`pool_row_bytes` of the pool;
    ``paged_decode``'s and ``paged_verify``'s tile): a step's fixed cost
    hides behind its fetch only if the fetch is long enough, so a step
    takes the fewest entries whose K and V blocks reach ``STEP_BYTES``;
    never fewer than the token rule's tile; never more views than
    ``STEP_VIEWS`` unless the token rule already takes more (a view is a
    copy of its own, and blocks of 16 tokens are paced by their copies'
    number, not their bytes); never more than the table. An int8 pool
    fetches half as much a position and takes twice the entries."""
    if nb * bs <= 2 * TILE_TOKENS:
        return nb
    P = -(-TILE_TOKENS // bs)
    if row_bytes is not None:
        P = max(P, min(-(-STEP_BYTES // (2 * bs * row_bytes)), STEP_VIEWS))
    return min(nb, P)


def tiles_run(length, nb: int, bs: int, window: Optional[int] = None,
              q_len: int = 1, row_bytes: Optional[int] = None):
    """Grid steps of one slot that fetch and compute (the kernel's own
    arithmetic, on the host, for the ``kv_steps`` counter): the tiles
    between the one that holds the band's first block and the one that
    holds position ``length + q_len - 1``. ``length`` is one slot's or an
    array of every slot's; ``row_bytes`` as :func:`blocks_per_step` takes
    it (the kernel's pool's; None: the token rule's tile)."""
    P = blocks_per_step(nb, bs, row_bytes)
    hi = np.minimum((length + q_len - 1) // bs, nb - 1)
    lo = 0 if window is None \
        else np.clip((length - window + 1) // bs, 0, nb - 1)
    return hi // P - lo // P + 1


def _band(pos, bs: int, nb: int, window: Optional[int], q_len: int):
    """First and last table entry a slot at position ``pos`` attends
    (traced scalars: the kernel's and its index maps'). A verify chunk's
    last query sits at ``pos + q_len - 1``; the band starts where the
    FIRST query's does (later queries' bands begin higher and are
    enforced per element)."""
    hi = jnp.minimum((pos + (q_len - 1)) // bs, nb - 1)
    lo = 0 if window is None \
        else jnp.clip((pos - window + 1) // bs, 0, nb - 1)
    return lo, hi


def _head_chunk(n_kv: int, Dh: int) -> int:
    """KV heads one matrix product covers: the fewest whose lanes end on
    a lane tile's edge (one head of 128; two of 64 where the heads pair
    up), else the whole row (GPT-2's 25 heads of 64 in 1,600 lanes)."""
    for hc in range(1, n_kv):
        if n_kv % hc == 0 and (hc * Dh) % LANES == 0:
            return hc
    return n_kv


def _paged_decode_kernel(tables_ref, held_ref, slots_ref, tiles_ref,
                         lengths_ref, q_ref, *rest, bs: int, P: int, nb: int,
                         hc: int, hp: int, Dh: int, R: int, q_len: int,
                         scale: float, window: Optional[int], quant: bool):
    """One grid step of flash-decode: tile ``tiles_ref[w]`` of slot
    ``slots_ref[w]``, a tile being ``P`` consecutive table entries,
    ``T = P * bs`` key positions. The grid is the work list of
    :func:`decode_plan`: a slot's tiles from the one that holds its
    band's first block to the one that holds its position, slot after
    slot, and no step that would find nothing to attend.

    q_ref: [1, C, R, cw]: the KV heads in C chunks of ``hc`` heads
    (``cw = hc * Dh`` lanes, a chunk's lanes as the pool's row has
    them), R = group * q_len query rows a KV head, row r = (group
    member, chunk offset). Then P refs of K and P of V, each ONE pool
    block [1, bs, row], already table-indirected by its index map
    (``tables_ref[held_ref[i, w]]``): entry ``j*P + i`` of the slot's
    table where the slot attends it, else whatever block the ref held
    before (maybe another slot's): its columns the position mask rules
    out of the scores, and its V rows are read as zeros, so that a
    probability of 0 times another request's NaN is not a NaN here.
    Scratch: running
    max / sum / fp32 accumulator, persistent across a slot's tiles.

    A chunk's scores are ONE product with the tile's keys on the lanes:
    ``[R * hp, cw] x [T, cw]^T``. With ``hc == 1`` the left side is the
    chunk's queries. With more heads in a chunk it is block-diagonal
    (row (r, h) holds query (h, r) in head h's lanes and zeros
    elsewhere; ``hp`` is ``hc`` rounded up to a sublane tile), so every
    head's scores come out of the same pass of the keys through the
    matrix unit; ``P.V`` mirrors it, and the accumulator's off-diagonal
    blocks are dropped once, when the slot finishes.

    ``quant``: the pools are int8 and ks_ref / vs_ref ([1, P, 1, Hkv]
    fp32, the tile's per-block per-head scales) dequantise the tile in
    registers: HBM traffic stays the int8 payload."""
    k_refs, v_refs, rest = rest[:P], rest[P:2 * P], rest[2 * P:]
    if quant:
        ks_ref, vs_ref, *rest = rest
    o_ref, m_scr, l_scr, acc_scr, *qbd_scr = rest   # [qbd_scr] if hc > 1
    b = slots_ref[pl.program_id(0)]
    j = tiles_ref[pl.program_id(0)]
    C, cw = q_ref.shape[1], q_ref.shape[3]
    n_kv = C * hc
    T = P * bs
    pos = lengths_ref[b]
    lo, hi = _band(pos, bs, nb, window, q_len)
    lo_t, hi_t = lo // P, hi // P

    def seg_mask(dtype):
        # [hp, cw]: 1 where the lane belongs to the row's head
        head = jax.lax.broadcasted_iota(jnp.int32, (hp, cw), 1) // Dh
        return (head == jax.lax.broadcasted_iota(
            jnp.int32, (hp, cw), 0)).astype(dtype)

    @pl.when(j == lo_t)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)
        if hc > 1:
            seg = seg_mask(q_ref.dtype)
            for c in range(C):
                for r in range(R):
                    qbd_scr[0][c, r * hp:(r + 1) * hp, :] = \
                        seg * q_ref[0, c, r:r + 1, :]

    def tile(refs, s_ref, attended_only=False):
        blocks = [r[0] for r in refs]      # P x [bs, row]
        if quant:
            # block i's heads' scales, each over its head's lanes
            lane_head = jax.lax.broadcasted_iota(
                jnp.int32, (1, n_kv * Dh), 1) // Dh
            for i in range(P):
                s = s_ref[0, i]            # [1, Hkv]
                lane_scale = jnp.zeros((1, n_kv * Dh), jnp.float32)
                for h in range(n_kv):
                    lane_scale = jnp.where(lane_head == h,
                                           s[:, h:h + 1], lane_scale)
                blocks[i] = blocks[i].astype(jnp.float32) * lane_scale
        if attended_only:
            # a ref whose entry the slot does not attend holds whatever
            # block it held before: one scalar predicate a ref
            for i in range(P):
                e = j * P + i
                blocks[i] = jnp.where(jnp.logical_and(e >= lo, e <= hi),
                                      blocks[i], 0)
        return jnp.concatenate(blocks, axis=0) if P > 1 else blocks[0]

    # K's strangers need no zeroing: a key is a column of the scores,
    # and the position mask replaces the column
    k = tile(k_refs, ks_ref if quant else None)           # [T, row]
    v = tile(v_refs, vs_ref if quant else None, attended_only=True)

    Rr = R * hp                            # rows of a chunk's product
    # positions of the tile's columns in the slot's virtual cache;
    # masked by position exactly like the gather path (idx <= pos +
    # chunk offset, window band below it), which also rules out the
    # entries outside the band, whose refs hold other blocks, and
    # what lies past the table
    cols = jax.lax.broadcasted_iota(jnp.int32, (Rr, T), 1) + j * T
    qpos = pos
    if q_len > 1:
        # row (r, h) of a chunk: r = (group member, chunk offset);
        # each chunk query is causal at its own position
        qpos = pos + (jax.lax.broadcasted_iota(
            jnp.int32, (Rr, T), 0) // hp) % q_len
    valid = jnp.logical_and(cols <= qpos, cols < nb * bs)
    if window is not None:
        valid = jnp.logical_and(valid, cols > qpos - window)

    for c in range(C):                     # static unroll: C is small
        lanes = slice(c * cw, (c + 1) * cw)
        qc = qbd_scr[0][c] if hc > 1 else q_ref[0, c]
        if quant:
            qc = qc.astype(jnp.float32)
        kc, vc = k[:, lanes], v[:, lanes]
        s = jax.lax.dot_general(
            qc, kc, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # [Rr, T]
        s = jnp.where(valid, s, NEG_INF)

        m_prev = m_scr[c, :, :1]                          # [Rr, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_scr[c, :, :1] + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[c] = acc_scr[c] * alpha + jax.lax.dot_general(
            p.astype(vc.dtype), vc, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)           # [Rr, cw]
        m_scr[c] = jnp.broadcast_to(m_new, m_scr.shape[1:])
        l_scr[c] = jnp.broadcast_to(l_new, l_scr.shape[1:])

    @pl.when(j == hi_t)
    def _finish():
        for c in range(C):
            l = l_scr[c, :, :1]
            out = acc_scr[c] / jnp.where(l == 0.0, 1.0, l)    # [Rr, cw]
            if hc == 1:
                o_ref[0, c] = out.astype(o_ref.dtype)
                continue
            # a row keeps its own head's lanes; the rows of one r then
            # sum to that r's output row over all the chunk's heads
            seg = seg_mask(jnp.float32)
            for r in range(R):
                o_ref[0, c, r:r + 1, :] = jnp.sum(
                    out[r * hp:(r + 1) * hp] * seg, axis=0,
                    keepdims=True).astype(o_ref.dtype)


class DecodePlan(NamedTuple):
    """The grid of the kernel's calls for one set of lengths
    (:func:`decode_plan`): ``steps`` [] grid steps; ``slot`` / ``tile``
    [B*nt] each step's slot and tile, the first ``steps`` filled;
    ``held`` [P, B*nt] where in the flattened ``[B*NB]`` block table the
    block stands that ref i of a step names; ``cut`` the static
    (table entries, block size, window, q_len, ``P``) it was worked out
    for, which the call it is handed to checks against its own (``P``
    follows the kernel and its pool: :func:`blocks_per_step`); ``live`` [B]
    bool the slots the list was cut from (None: all of them), by which
    the call zeroes the rows no step writes (:func:`zero_idle_rows`)."""
    steps: jnp.ndarray
    slot: jnp.ndarray
    tile: jnp.ndarray
    held: jnp.ndarray
    cut: tuple
    live: Optional[jnp.ndarray]


def decode_plan(lengths, num_entries: int, block_size: int, *,
                row_bytes: Optional[int] = None,
                window: Optional[int] = None, q_len: int = 1,
                active=None) -> DecodePlan:
    """Work the grid of a call out from the lengths, in XLA. It depends
    on nothing else, so a program that attends many layers at the same
    lengths works it out ONCE, outside its layer loop, and hands it to
    every call (``plan=``): a call given none works out its own.
    ``row_bytes``: :func:`pool_row_bytes` of the pool a ``paged_decode`` /
    ``paged_verify`` call reads, whose tile follows it
    (:func:`blocks_per_step`); None for ``mla_decode``, whose tile is the
    token rule's. A plan of another tile than its call's is refused there.

    A slot's steps are the tiles from the one that holds its band's
    first block to the one that holds its position: at least one, and
    none that attends nothing. Where a step's slot attends table entry
    ``tile*P + i`` ref i names that entry's block. Elsewhere (past the
    slot's length, below its band, past the table) it names the block it
    named at the last step before that did attend its entry: an index
    that does not change is not fetched again, so only attended blocks
    are ever read, each once (the kernel reads a ref that waits as
    zeros). Before ref i's first attended entry it names that one
    (fetched early, once).

    ``active`` ([B] bool): the slots that decode. A slot that does not
    has no step at all (``searchsorted`` passes over a slot whose end is
    its predecessor's, first and last slot included), so neither the
    trash block of a slot with no request nor the real blocks of one
    still in prefill are fetched; with no slot active the grid is empty.
    The kernel then never writes such a slot's output row: the call
    does, with zeros. None is the plan of every slot."""
    nb, bs = num_entries, block_size
    lengths = jnp.asarray(lengths, jnp.int32)
    B = lengths.shape[0]
    P = blocks_per_step(nb, bs, row_bytes)
    nt = -(-nb // P)
    W = B * nt
    lo, hi = _band(lengths, bs, nb, window, q_len)
    lo = jnp.broadcast_to(lo, hi.shape).astype(jnp.int32)
    steps = hi // P - lo // P + 1
    if active is not None:
        active = jnp.asarray(active, bool)
        steps = jnp.where(active, steps, 0)
    ends = jnp.cumsum(steps)
    w = jnp.arange(W, dtype=jnp.int32)
    slot = jnp.minimum(jnp.searchsorted(
        ends, w, side="right", method="compare_all"), B - 1)
    slot = slot.astype(jnp.int32)
    tile = jnp.minimum((lo // P)[slot] + w - (ends - steps)[slot], nt - 1)
    entry = tile[None, :] * P + jnp.arange(P, dtype=jnp.int32)[:, None]
    attended = (entry >= lo[slot]) & (entry <= hi[slot]) & (w < ends[-1])
    last = jax.lax.cummax(jnp.where(attended, w, -1), axis=1)
    first = jnp.min(jnp.where(attended, w, W - 1), axis=1, keepdims=True)
    src = jnp.where(last >= 0, last, first)            # [P, W]: a step
    held = jnp.minimum(jnp.take_along_axis(entry, src, axis=1), nb - 1)
    return DecodePlan(ends[-1], slot, tile, slot[src] * nb + held,
                      (nb, bs, window, q_len, P), active)


def zero_idle_rows(out, plan: DecodePlan):
    """``out`` [B, ...] of a call over ``plan``'s grid with the rows of
    the slots the plan left out as zeros: no step visited them, so they
    hold whatever the buffer held."""
    if plan.live is None:
        return out
    return jnp.where(plan.live.reshape((-1,) + (1,) * (out.ndim - 1)),
                     out, 0)


def paged_decode_attention(q: jnp.ndarray, k_pool: jnp.ndarray,
                           v_pool: jnp.ndarray, tables: jnp.ndarray,
                           lengths: jnp.ndarray, *, scale: float,
                           window: Optional[int] = None,
                           interpret: bool = False,
                           k_scale=None, v_scale=None,
                           plan: Optional[DecodePlan] = None) -> jnp.ndarray:
    """Flash-decode one new token per serving slot THROUGH the block
    table — no dense cache materialization.

    q: [B, Hkv, group, Dh] post-rotary queries (grouped per shared kv
    head); k_pool / v_pool: [N, block, Hkv*Dh] pools, heads folded into
    the rows as the paged cache stores them (the new token's K/V must
    already be scattered in at position ``lengths[b]``); tables:
    [B, NB] int32 block tables into dimension 0 (the serving programs
    pass all layers' pools stacked and ``tables + l*N``; unused entries
    name a trash block); lengths: [B] int32 per-slot cache positions (slot b
    attends positions <= lengths[b], banded by ``window`` when set).
    ``k_scale``/``v_scale`` ([N, Hkv] fp32): int8 pools, dequantized
    in-register after each tile's fetches (DS_KV_QUANT=int8). ``plan``:
    :func:`decode_plan` of these lengths, this table's width, the
    pool's block size and this window, from a caller that works it out
    once for all its layers.

    Returns [B, Hkv, group, Dh] in q's dtype. ``interpret=True`` is
    for tests; the default compiles through Mosaic and fails off a TPU.
    Call it under ``jax.jit``: the kernel takes ``P`` views of each pool,
    which a jitted program hands over as one buffer and an eager call as
    ``P`` arguments of the pool's size."""
    return _paged_attention_call(
        q, k_pool, v_pool, tables, lengths, q_len=1, scale=scale,
        window=window, interpret=interpret, k_scale=k_scale,
        v_scale=v_scale, plan=plan)


def paged_verify_attention(q: jnp.ndarray, k_pool: jnp.ndarray,
                           v_pool: jnp.ndarray, tables: jnp.ndarray,
                           lengths: jnp.ndarray, *, scale: float,
                           window: Optional[int] = None,
                           interpret: bool = False,
                           k_scale=None, v_scale=None,
                           plan: Optional[DecodePlan] = None) -> jnp.ndarray:
    """Flash-verify a G-token speculative chunk per slot THROUGH the
    block table — the ``q_len > 1`` generalization of
    :func:`paged_decode_attention` for draft/verify serving.

    q: [B, G, Hkv, group, Dh] post-rotary chunk queries; the chunk's
    K/V must already be scattered into the pools at positions
    ``lengths[b] .. lengths[b] + G - 1`` (writes-before-attention, so
    within-chunk causality is just the position mask: chunk query i of
    slot b attends cache positions <= lengths[b] + i). Same grid and
    fetches as decode — the chunk only adds rows to the products of each
    tile, which is exactly why verify is nearly free on TPU (``plan``:
    :func:`decode_plan` with ``q_len=G``). Returns
    [B, G, Hkv, group, Dh] in q's dtype."""
    B, G, n_kv, group, Dh = q.shape
    # rows of a kv head ordered (group member, chunk offset)
    q_rows = q.transpose(0, 2, 3, 1, 4).reshape(B, n_kv, group * G, Dh)
    out = _paged_attention_call(
        q_rows, k_pool, v_pool, tables, lengths, q_len=G, scale=scale,
        window=window, interpret=interpret, k_scale=k_scale,
        v_scale=v_scale, plan=plan)
    return out.reshape(B, n_kv, group, G, Dh).transpose(0, 3, 1, 2, 4)


def _paged_attention_call(q_rows, k_pool, v_pool, tables, lengths, *,
                          q_len: int, scale: float, window: Optional[int],
                          interpret: bool, k_scale=None, v_scale=None,
                          plan: Optional[DecodePlan] = None) -> jnp.ndarray:
    """Shared pallas_call plumbing for decode (q_len=1) and verify
    (q_len=G). q_rows: [B, Hkv, R, Dh], R = group * q_len rows a kv
    head; pools [N', block, Hkv*Dh]. ``k_scale``/``v_scale`` ([N', Hkv]
    fp32) switch the int8 dequantize-in-kernel mode on (pools must then
    be int8). Returns [B, Hkv, R, Dh]."""
    B, n_kv, R, Dh = q_rows.shape
    N, bs, row = k_pool.shape
    assert row == n_kv * Dh and R % q_len == 0, \
        (q_rows.shape, k_pool.shape, q_len)
    assert v_pool.shape == k_pool.shape, (v_pool.shape, k_pool.shape)
    quant = k_scale is not None
    tables = jnp.asarray(tables, jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32)
    nb = tables.shape[1]
    P = blocks_per_step(nb, bs, pool_row_bytes(k_pool))
    nt = -(-nb // P)
    hc = _head_chunk(n_kv, Dh)
    C, cw = n_kv // hc, hc * Dh
    # rows a head takes in a chunk's product: a sublane tile's worth
    # where the chunk's heads share it
    hp = 1 if hc == 1 else -(-hc // 8) * 8
    Rr = R * hp

    def chunked(x, inverse=False):
        # [B, Hkv, R, Dh] <-> [B, C, R, hc*Dh]: a chunk's heads side by
        # side on the lanes, as a pool row has them
        if inverse:
            return x.reshape(B, C, R, hc, Dh).transpose(0, 1, 3, 2, 4) \
                .reshape(B, n_kv, R, Dh)
        return x.reshape(B, C, hc, R, Dh).transpose(0, 1, 3, 2, 4) \
            .reshape(B, C, R, cw)

    def qmap(w, tables_ref, held_ref, slots_ref, tiles_ref, lengths_ref):
        return (slots_ref[w], 0, 0, 0)

    def kvmap(i):
        # the table is read HERE, on the scalar core, a step ahead of the
        # step: gathering the steps' blocks in XLA cost every layer half
        # of what its kernel call did
        def imap(w, tables_ref, held_ref, slots_ref, tiles_ref,
                 lengths_ref):
            return (tables_ref[held_ref[i, w]], 0, 0)
        return imap

    kv_specs = [pl.BlockSpec((1, bs, row), kvmap(i)) for i in range(P)]
    in_specs = [pl.BlockSpec((1, C, R, cw), qmap)] + kv_specs * 2
    operands = [chunked(q_rows)] + [k_pool] * P + [v_pool] * P
    if quant:
        # the scales of each slot's blocks, gathered through the tables
        # out here (B*NB*Hkv floats): the kernel then needs no view of
        # the scale pool in a layout of its own. They ride as
        # [B, nt*P, 1, Hkv], a tile's [P, 1, Hkv] a step, because a
        # block's last two dimensions must divide by (8, 128) or equal
        # the array's
        def smap(w, tables_ref, held_ref, slots_ref, tiles_ref,
                 lengths_ref):
            return (slots_ref[w], tiles_ref[w], 0, 0)

        def tile_scales(s):
            return jnp.pad(s[tables], ((0, 0), (0, nt * P - nb), (0, 0)),
                           constant_values=1.0)[:, :, None, :]
        in_specs += [pl.BlockSpec((1, P, 1, n_kv), smap)] * 2
        operands += [tile_scales(k_scale), tile_scales(v_scale)]

    scratch = [pltpu.VMEM((C, Rr, LANES), jnp.float32),      # running max
               pltpu.VMEM((C, Rr, LANES), jnp.float32),      # running sum
               pltpu.VMEM((C, Rr, cw), jnp.float32)]         # accumulator
    if hc > 1:
        scratch.append(pltpu.VMEM((C, Rr, cw), q_rows.dtype))
    if plan is None:
        plan = decode_plan(lengths, nb, bs, row_bytes=pool_row_bytes(k_pool),
                           window=window, q_len=q_len)
    # a plan of another table, window, chunk or tile would run the wrong
    # tiles without a word
    assert plan.cut == (nb, bs, window, q_len, P) \
        and plan.held.shape == (P, B * nt), \
        (plan.cut, plan.held.shape, (nb, bs, window, q_len, P), (P, B * nt))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(plan.steps,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, C, R, cw), qmap),
        scratch_shapes=scratch,
    )
    kernel = functools.partial(
        _paged_decode_kernel, bs=bs, P=P, nb=nb, hc=hc, hp=hp, Dh=Dh, R=R,
        q_len=q_len, scale=float(scale), window=window, quant=quant)
    out = pl.pallas_call(
        kernel,
        name="paged_decode" if q_len == 1 else "paged_verify",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, C, R, cw), q_rows.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        # passed only when a test asks: the conftest fixture's patched
        # pallas_call keeps its own interpret=True
        **({"interpret": True} if interpret else {}),
    )(tables.reshape(-1), plan.held, plan.slot, plan.tile, lengths,
      *operands)
    return chunked(zero_idle_rows(out, plan), inverse=True)


def gather_pool_blocks(pool, tables, n_kv: int, scale_pool=None,
                       dtype=None):
    """Gather pool blocks ``[N', block, Hkv*Dh]`` through block tables
    ``[B, NB]`` into the virtual contiguous cache
    ``[B, NB*block, Hkv, Dh]``: cache position s of row b lives at
    ``pool[tables[b, s // block], s % block]`` — the PagedAttention
    indirection as one XLA gather. The heads are unfolded in what was
    gathered, never in the pool. With ``scale_pool`` ([N', Hkv] fp32)
    the pool is int8 and the gathered blocks are dequantized to
    ``dtype`` through the ops/quantizer KV helpers. The engine's gather
    path and the references below share it."""
    g = pool[tables]
    B, nb, bs = g.shape[:3]
    g = g.reshape(B, nb, bs, n_kv, g.shape[3] // n_kv)
    if scale_pool is not None:
        from deepspeed_tpu.ops import quantizer
        g = quantizer.kv_dequantize_blocks(g, scale_pool[tables],
                                           dtype=dtype)
    return g.reshape(B, nb * bs, n_kv, g.shape[4])


def paged_decode_reference(q, k_pool, v_pool, tables, lengths, *, scale,
                           window=None, k_scale=None, v_scale=None):
    """Dense gather reference of :func:`paged_decode_attention` for the
    parity tests — the same math as the engine's gather path
    (inference/engine.py _block_decode_paged), minus the model around
    it. With ``k_scale``/``v_scale`` the pools are int8 and the gather
    dequantizes through the ops/quantizer KV helpers."""
    B, n_kv, group, Dh = q.shape
    bs = k_pool.shape[1]
    nb = tables.shape[1]
    kc = gather_pool_blocks(k_pool, tables, n_kv, k_scale, q.dtype)
    vc = gather_pool_blocks(v_pool, tables, n_kv, v_scale, q.dtype)
    s = jnp.einsum("bkgd,bskd->bkgs", q, kc).astype(jnp.float32) * scale
    idx = jax.lax.broadcasted_iota(jnp.int32, (1, 1, 1, nb * bs), 3)
    pos = lengths[:, None, None, None]
    s = jnp.where(idx <= pos, s, NEG_INF)
    if window is not None:
        s = jnp.where(idx > pos - window, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bkgs,bskd->bkgd", p, vc)


def paged_verify_reference(q, k_pool, v_pool, tables, lengths, *, scale,
                           window=None, k_scale=None, v_scale=None):
    """Dense gather reference of :func:`paged_verify_attention` — the
    same math as the engine's gather-path verify block
    (inference/engine.py _block_verify_paged), minus the model.
    q: [B, G, Hkv, group, Dh]."""
    B, G, n_kv, group, Dh = q.shape
    bs = k_pool.shape[1]
    nb = tables.shape[1]
    kc = gather_pool_blocks(k_pool, tables, n_kv, k_scale, q.dtype)
    vc = gather_pool_blocks(v_pool, tables, n_kv, v_scale, q.dtype)
    s = jnp.einsum("bqkgd,bskd->bkgqs", q, kc).astype(jnp.float32) * scale
    idx = jax.lax.broadcasted_iota(jnp.int32, (1, 1, 1, 1, nb * bs), 4)
    qpos = lengths[:, None, None, None, None] + jax.lax.broadcasted_iota(
        jnp.int32, (1, 1, 1, G, 1), 3)
    s = jnp.where(idx <= qpos, s, NEG_INF)
    if window is not None:
        s = jnp.where(idx > qpos - window, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bkgqs,bskd->bqkgd", p, vc)
