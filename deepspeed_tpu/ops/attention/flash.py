"""Flash attention — Pallas TPU kernel with custom VJP.

Capability equivalent of the reference's fused attention path inside the
transformer training kernel (ref: csrc/transformer/softmax_kernels.cu +
strided-batch GEMM attention, csrc/includes/strided_batch_gemm.h) and the
long-sequence story of block-sparse attention (SURVEY §2.5/§5): an O(S)
memory attention that never materializes the [S, S] score matrix.

Algorithm: FlashAttention-2 style online softmax.
Forward: grid (B, H, Q-blocks, KV-blocks), KV innermost ("arbitrary"
dimension) with running max / sum / accumulator in VMEM scratch that
persists across the sequential KV iterations.
Backward: recompute-based FA2 — one kernel accumulating (dk, dv) over Q
blocks, one accumulating dq over KV blocks, using the saved logsumexp and
the precomputed per-row delta = rowsum(dO * O). A backward grid step whose
block straddles an edge of the attention band (the causal diagonal, a
window's lower edge) walks the block as sub-tiles and computes only those
that meet the band (_block_walks, _tile_walk; tile_census counts them).

All matmuls hit the MXU in the input dtype with fp32 accumulation
(preferred_element_type); softmax statistics in fp32.
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128


def _ceil_to(x, m):
    return (x + m - 1) // m * m


def fit_block(pref: int, seq_len: int) -> Optional[int]:
    """Largest block <= pref (>=128) that divides seq_len, or None —
    keeps the kernel on when the preferred size doesn't tile the
    sequence (1024-blocks at S=1536 run as 512)."""
    b = min(pref, seq_len)
    while b >= 128 and seq_len % b != 0:
        b //= 2
    return b if b >= 128 and seq_len % b == 0 else None


def refuse_partial_manual(mesh, axis: str, who: str) -> None:
    """Sequence-parallel attention maps only ``axis`` by hand and leaves
    the other mesh axes to XLA, and a Mosaic kernel lowers only where
    every mesh axis is manual ("Mosaic kernels cannot be automatically
    partitioned", jax 0.9). Say so at trace time on a TPU, before the
    lowering does (ROADMAP: full-manual ring/Ulysses)."""
    from deepspeed_tpu.utils import on_tpu
    if tuple(mesh.axis_names) != (axis,) and on_tpu():
        raise NotImplementedError(
            f"{who} with the flash kernel needs a mesh whose only axis is "
            f"{axis!r}; this mesh has {tuple(mesh.axis_names)}, and a Mosaic "
            f"kernel cannot be lowered under a partly automatic mesh. Pass "
            f"use_flash=False (GPTConfig.use_flash_attention=False) or a "
            f"one-axis mesh.")


def _causal_kv_index_map(block_q, block_kv, num_kv, window=None, q_off=0):
    """Block index map for KV-blocked inputs when the grid is
    (b, h, q-block, kv-block) and causal skipping applies: skipped
    above-diagonal steps re-map to the last valid KV block, so the index
    equals the previous step's and Mosaic elides the DMA (the compute is
    already skipped by pl.when). Clamped into range for Skv != S callers.

    With a sliding ``window``, blocks fully BELOW the band (ki too small)
    clamp up to the first in-band block — their fetches elide the same
    way, making windowed attention O(S*W) in HBM reads as well.

    ``q_off`` is a STATIC global q-position offset: ring attention calls
    the kernel with q rows that globally sit ``q_off`` tokens after the
    held K/V block's first key (the ring-step distance is static once
    the ring loop is unrolled), so all causal/window geometry shifts by
    it."""

    def kvmap(b, h, qi, ki):
        limit = jnp.minimum((qi * block_q + block_q - 1 + q_off) // block_kv,
                            num_kv - 1)
        ki = jnp.minimum(ki, limit)
        if window is not None:
            lo = jnp.clip((qi * block_q + q_off - window + 1) // block_kv,
                          0, num_kv - 1)
            ki = jnp.maximum(ki, lo)
        return (b, h, ki, 0)

    return kvmap


def _band_run(qi, ki, block_q, block_kv, causal, window, q_off=0):
    """Whether tile (qi, ki) of a [block_q, block_kv] tiling intersects
    the attention band. One predicate at two scales: the grid asks it of
    its blocks (traced indices), and a block that straddles an edge of
    the band asks it of its sub-tiles (Python ints in, a Python bool
    out: see _tile_walk)."""
    run = True
    if causal:
        run = qi * block_q + block_q - 1 + q_off >= ki * block_kv
    if window is not None:
        # lowest q row of the block must still reach the block's last col
        run = run & (
            ki * block_kv + block_kv - 1 >= qi * block_q + q_off - window + 1)
    return run


def _band_full(qi, ki, block_q, block_kv, causal, window, q_off=0):
    """Whether tile (qi, ki) lies wholly inside the band: no causal or
    window mask changes a score of it. Static geometry (Python ints)."""
    full = True
    if causal:
        full = qi * block_q + q_off >= ki * block_kv + block_kv - 1
    if window is not None:
        # highest q row of the tile still sees the tile's first col
        full = full and qi * block_q + block_q - 1 + q_off - ki * block_kv \
            < window
    return full


# A grid step of the two backward kernels whose block straddles an edge
# of the band walks the block as square sub-tiles of this side and
# computes only those that meet the band: GPT-2 XL's one 1,024 x 1,024
# block a head is 10 of 16 (tools/kernel_census.py "flash train": the
# readings that chose 256 are in PERF.md 6, PR 58). The forward kernel
# keeps the single product: its online softmax pays the row statistics
# (lane reductions, [rows, 1] columns) once a sub-tile, and every form of
# the walk measured there was slower than the whole square.
SUB_TILE = 256
# Each distinct position of the band's edge inside a block is one more
# copy of the walk in the kernel's text: equal blocks have one (plus up
# to two for a window's lower edge); beyond this many the blocks keep
# the single product.
MAX_WALKS = 4


def _sub_tile(block_q, block_kv):
    """Side of the sub-tiles a [block_q, block_kv] block is walked as,
    or None where the block holds no second one."""
    t = SUB_TILE
    if block_q % t or block_kv % t or block_q * block_kv < 2 * t * t:
        return None
    return t


def _block_walks(S, Skv, block_q, block_kv, causal, window, q_off=0):
    """Which grid blocks are walked as sub-tiles, from the call's static
    geometry: ``(offsets, plain)``. A block's place against the band is
    a function of ``d`` alone, its first q position less its first key
    (``qi * block_q + q_off - ki * block_kv``); ``offsets`` are the d at
    which a block that runs straddles an edge of the band (the causal
    diagonal, or a window's lower edge), and ``plain`` says whether any
    other block runs at all (a block wholly inside the band, or every
    block of a call that has no sub-tile: the single product)."""
    if not causal or _sub_tile(block_q, block_kv) is None:
        return (), True
    offsets, plain = set(), False
    for qi in range(S // block_q):
        for ki in range(Skv // block_kv):
            if not _band_run(qi, ki, block_q, block_kv, causal, window,
                             q_off):
                continue
            if _band_full(qi, ki, block_q, block_kv, causal, window, q_off):
                plain = True
            else:
                offsets.add(qi * block_q + q_off - ki * block_kv)
    if len(offsets) > MAX_WALKS:
        return (), True
    return tuple(sorted(offsets)), plain


def _tile_walk(block_q, block_kv, causal, window, d, kv_major=False):
    """The sub-tiles of a block at offset ``d`` (see _block_walks) that
    meet the band, as ``((start, size, ((start, size, masked), ...)),
    ...)``: q sub-tiles outside and the kv sub-tiles each meets inside,
    or with ``kv_major`` the other way round (the dk/dv kernel). The
    predicates are the grid's own, asked of sub-tile (i, j) with ``d``
    for ``q_off``; ``masked`` is False for a sub-tile wholly inside the
    band, which needs no iota, compare or select. Neighbours of one
    kind come as one longer tile (at d = 0: the strip below the
    diagonal, then the sub-tile on it)."""
    t = _sub_tile(block_q, block_kv)
    nq, nk = block_q // t, block_kv // t
    walk = []
    for a in range(nk if kv_major else nq):
        inner = []
        for b in range(nq if kv_major else nk):
            i, j = (b, a) if kv_major else (a, b)
            if _band_run(i, j, t, t, causal, window, d):
                masked = not _band_full(i, j, t, t, causal, window, d)
                if inner and inner[-1][2] == masked \
                        and inner[-1][0] + inner[-1][1] == b * t:
                    # a run of sub-tiles of one kind is one product
                    inner[-1] = (inner[-1][0], inner[-1][1] + t, masked)
                else:
                    inner.append((b * t, t, masked))
        if inner:
            walk.append((a * t, t, tuple(inner)))
    return tuple(walk)


def tile_census(S, Skv, block_q, block_kv, causal=True, window=None,
                q_off=0):
    """``(computed, in the grid)``: how many tiles of the [S, Skv] score
    matrix the backward kernels compute for this static geometry,
    counted in the sub-tiles of _sub_tile (in whole blocks where a block
    holds no second one). GPT-2 XL's training call, one 1,024 x 1,024
    block a head, reads (10, 16); a non-causal call reads all of them.
    The forward kernel computes every block that meets the band whole."""
    block_q, block_kv = min(block_q, S), min(block_kv, Skv)
    t = _sub_tile(block_q, block_kv)
    per_block = 1 if t is None else (block_q // t) * (block_kv // t)
    offsets, _ = _block_walks(S, Skv, block_q, block_kv, causal, window,
                              q_off)
    walked = {d: sum(size // t for _, _, inner in
                     _tile_walk(block_q, block_kv, causal, window, d)
                     for _, size, _ in inner) for d in offsets}
    computed = sum(
        walked.get(qi * block_q + q_off - ki * block_kv, per_block)
        for qi in range(S // block_q) for ki in range(Skv // block_kv)
        if _band_run(qi, ki, block_q, block_kv, causal, window, q_off))
    return computed, (S // block_q) * (Skv // block_kv) * per_block


def _window_mask(s, rows, cols, window):
    """cols within (rows - window, rows]: Mistral-style local attention."""
    return jnp.where(rows - cols < window, s, NEG_INF)


def _at(ref, start, size):
    """``size`` rows of the block a ref holds, from ``start`` (entries,
    of a [1, 1, n] metadata block)."""
    return ref[0, 0, start:start + size]


def _scores(q, k, scale, row0, col0, masked, window, mask, qseg, kseg,
            kv_major=False):
    """Scaled scores of one tile in float32, with every mask that
    applies: [rows of q, rows of k], or with ``kv_major`` their transpose
    [rows of k, rows of q] straight from the product (the dk/dv kernel:
    p^T and ds^T are then what its two accumulating products take, and
    the row statistics broadcast as the lane-dense rows they arrive as).
    ``row0`` / ``col0`` are the global positions of the tile's first q
    row and first key; ``masked`` is whether the causal (and window)
    mask can change a score of this tile."""
    a, b = (k, q) if kv_major else (q, k)
    s = jax.lax.dot_general(
        a, b, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale
    q_axis = 1 if kv_major else 0

    def along(x, axis):              # a vector laid along one axis of s
        return x[None, :] if axis else x[:, None]
    if masked:
        rows = jax.lax.broadcasted_iota(jnp.int32, s.shape, q_axis) + row0
        cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1 - q_axis) \
            + col0
        s = jnp.where(rows >= cols, s, NEG_INF)
        if window is not None:
            s = _window_mask(s, rows, cols, window)
    if mask is not None:
        s = jnp.where(along(mask, 1 - q_axis) > 0, s, NEG_INF)
    if qseg is not None:
        s = jnp.where(along(qseg, q_axis) == along(kseg, 1 - q_axis),
                      s, NEG_INF)
    return s


def _for_step_tiles(body, qi, ki, *, block_q, block_kv, causal, window,
                    q_off, walks, kv_major=False):
    """Run ``body(tiles)`` for grid step (qi, ki) if its block meets the
    band: with the block's one tile (the single product), or, where the
    block straddles an edge of the band at one of the static offsets of
    ``walks`` (_block_walks), with its sub-tiles that meet the band."""
    offsets, plain = walks
    run = _band_run(qi, ki, block_q, block_kv, causal, window, q_off)
    d = qi * block_q + q_off - ki * block_kv
    for w in offsets:
        pl.when(d == w)(functools.partial(
            body, _tile_walk(block_q, block_kv, causal, window, w,
                             kv_major)))
        run = run & (d != w)
    if plain:
        outer, inner = (block_kv, block_q) if kv_major \
            else (block_q, block_kv)
        pl.when(run)(functools.partial(
            body, ((0, outer, ((0, inner, causal),)),)))


# ---------------------------------------------------------------------------
# forward kernel
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, *rest,
                causal: bool, has_mask: bool, has_segs: bool, scale: float,
                block_q: int, block_kv: int, num_kv: int, window=None,
                q_off: int = 0):
    rest = list(rest)
    mask_ref = rest.pop(0) if has_mask else None
    qseg_ref = rest.pop(0) if has_segs else None
    kseg_ref = rest.pop(0) if has_segs else None
    o_ref, lse_ref, m_scratch, l_scratch, acc_scratch = rest
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scratch[:] = jnp.full_like(m_scratch, NEG_INF)
        l_scratch[:] = jnp.zeros_like(l_scratch)
        acc_scratch[:] = jnp.zeros_like(acc_scratch)

    run = _band_run(qi, ki, block_q, block_kv, causal, window, q_off)

    @pl.when(run)
    def _body():
        q = q_ref[0, 0]                  # [block_q, d]
        v = v_ref[0, 0]                  # [block_kv, d]
        s = _scores(q, k_ref[0, 0], scale, qi * block_q + q_off,
                    ki * block_kv, causal, window,
                    mask_ref[0, 0] if has_mask else None,
                    qseg_ref[0, 0] if has_segs else None,
                    kseg_ref[0, 0] if has_segs else None)   # [bq, bkv]

        m_prev = m_scratch[:, :1]                        # [bq, 1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)       # [bq, 1]
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                           # [bq, bkv] f32
        alpha = jnp.exp(m_prev - m_new)                  # [bq, 1]
        l_new = alpha * l_scratch[:, :1] + jnp.sum(p, axis=-1, keepdims=True)

        acc_scratch[:] = acc_scratch[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

        m_scratch[:] = jnp.broadcast_to(m_new, m_scratch.shape)
        l_scratch[:] = jnp.broadcast_to(l_new, l_scratch.shape)

    @pl.when(ki == num_kv - 1)
    def _finish():
        l = l_scratch[:]                 # [bq, LANES], every lane the same
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_scratch[:] / l_safe[:, :1]).astype(o_ref.dtype)
        # the row statistic leaves lane-dense, one [1, bq] row (_stat_spec)
        lse_ref[0, 0] = jnp.transpose(m_scratch[:] + jnp.log(l_safe))[:1]


def _mask_spec(block_kv, kvmap):
    """Block spec for the optional key-validity mask, following the
    (possibly clamped) kv block index map. The [B, Skv] metadata is fed
    to the kernel as [B, 1, Skv]: Mosaic requires the LAST TWO dims of a
    block to be (8, 128)-tile-divisible or equal to the array dims, and
    a (1, block) slice of [B, Skv] violates that whenever B > 1 (caught
    by the on-chip smoke; interpret mode does not check tiling)."""
    def mmap(b, h, qi, ki):
        _, _, kblk, _ = kvmap(b, h, qi, ki)
        return (b, 0, kblk)

    return pl.BlockSpec((1, 1, block_kv), mmap)


def _qseg_spec(block_q, qmap):
    """Block spec for the q-side segment ids ([B, S] fed as [B, 1, S] —
    see _mask_spec), following qmap."""
    def smap(*ids):
        _, _, qblk, _ = qmap(*ids)
        return (ids[0], 0, qblk)

    return pl.BlockSpec((1, 1, block_q), smap)


def _stat_spec(block_q, qmap):
    """Block spec of a per-row statistic (lse, delta), following qmap.
    The [B, H, S] statistic is fed as [B, H, 1, S], one lane-dense row of
    block_q a block: the dq kernel makes a column of it (_stat_col), the
    dk/dv kernel's transposed tiles take it as the row it is. As
    [B, H, S, 8] columns it was 8 lanes of a 128-lane tile a row: the
    copies of those blocks, not the arithmetic, were what the backward
    kernels waited for (tools/kernel_census.py "flash stats floor";
    PERF.md 6, PR 58)."""
    def smap(*ids):
        b, h, qblk, _ = qmap(*ids)
        return (b, h, 0, qblk)

    return pl.BlockSpec((1, 1, 1, block_q), smap)


def _stat_col(ref, start, size):
    """``size`` rows of a per-row statistic from ``start``, as the
    [size, 1] column that broadcasts against a tile's scores."""
    return ref[0, 0, 0, start:start + size][:, None]


def _group_head(map_fn, group: int):
    """Wrap a (b, h, i, j) block index map so the head index addresses a
    GROUPED kv array (GQA: kv head = q head // group)."""
    if group == 1:
        return map_fn

    def wrapped(b, h, i, j):
        bb, _, blk, z = map_fn(b, h, i, j)
        return (bb, h // group, blk, z)

    return wrapped


def _flash_fwd(q, k, v, mask, qsegs, ksegs, causal, scale, block_q, block_kv,
               window=None, q_off=0):
    # arrays are [B, H, S, D] inside the op (wrapper transposes)
    B, H, S, D = q.shape
    Skv = k.shape[2]
    group = H // k.shape[1]          # GQA: q heads per kv head
    block_q = min(block_q, S)
    block_kv = min(block_kv, Skv)
    assert S % block_q == 0 and Skv % block_kv == 0, (S, Skv, block_q, block_kv)
    num_q = S // block_q
    num_kv = Skv // block_kv

    def qmap(b, h, qi, ki):
        return (b, h, qi, 0)

    if causal:
        kvmap = _causal_kv_index_map(block_q, block_kv, num_kv, window, q_off)
    else:
        def kvmap(b, h, qi, ki):
            return (b, h, ki, 0)
    kvmap_h = _group_head(kvmap, group)

    grid = (B, H, num_q, num_kv)
    has_mask = mask is not None
    has_segs = qsegs is not None
    assert (qsegs is None) == (ksegs is None)
    kernel = functools.partial(
        _fwd_kernel, causal=causal, has_mask=has_mask, has_segs=has_segs,
        scale=scale, block_q=block_q, block_kv=block_kv, num_kv=num_kv,
        window=window, q_off=q_off)

    in_specs = [
        pl.BlockSpec((1, 1, block_q, D), qmap),
        pl.BlockSpec((1, 1, block_kv, D), kvmap_h),
        pl.BlockSpec((1, 1, block_kv, D), kvmap_h),
    ]
    operands = [q, k, v]
    if has_mask:
        in_specs.append(_mask_spec(block_kv, kvmap))
        operands.append(mask[:, None])
    if has_segs:
        in_specs.append(_qseg_spec(block_q, qmap))
        in_specs.append(_mask_spec(block_kv, kvmap))   # kv-side segments
        operands.extend([qsegs[:, None], ksegs[:, None]])

    out_shape = [
        jax.ShapeDtypeStruct((B, H, S, D), q.dtype),
        jax.ShapeDtypeStruct((B, H, 1, S), jnp.float32),
    ]
    o, lse = pl.pallas_call(
        kernel,
        name="flash_fwd",
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, block_q, D), qmap),
            _stat_spec(block_q, qmap),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")),
    )(*operands)
    return o, lse[:, :, 0]


# ---------------------------------------------------------------------------
# backward kernels
# ---------------------------------------------------------------------------

def _grad_tile(s, dp, lse, delta, scale):
    """(p, ds) of one tile from its masked scores ``s`` and ``dp`` (do @
    v^T, or both transposed): the recomputed probabilities and the
    scores' gradient, in float32. ``lse`` / ``delta`` broadcast against
    the tile: [rows, 1] columns, or [1, rows] for a transposed tile."""
    p = jnp.exp(s - lse)
    return p, p * (dp - delta) * scale


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    *rest, causal: bool, has_mask: bool, has_segs: bool,
                    scale: float, block_q: int, block_kv: int, num_q: int,
                    window=None, q_off: int = 0, walks=((), True)):
    rest = list(rest)
    mask_ref = rest.pop(0) if has_mask else None
    qseg_ref = rest.pop(0) if has_segs else None
    kseg_ref = rest.pop(0) if has_segs else None
    dk_ref, dv_ref, dk_scratch, dv_scratch = rest
    ki = pl.program_id(2)
    qi = pl.program_id(3)

    @pl.when(qi == 0)
    def _init():
        dk_scratch[:] = jnp.zeros_like(dk_scratch)
        dv_scratch[:] = jnp.zeros_like(dv_scratch)

    def _body(tiles):
        # a tile is computed TRANSPOSED, [rows of k, rows of q]: p^T and
        # ds^T are what the two accumulating products take
        for c0, cn, q_tiles in tiles:
            k = _at(k_ref, c0, cn)                       # [cn, d]
            v = _at(v_ref, c0, cn)
            mask = _at(mask_ref, c0, cn) if has_mask else None
            kseg = _at(kseg_ref, c0, cn) if has_segs else None
            dk = dv = 0.0
            for r0, rn, masked in q_tiles:
                q = _at(q_ref, r0, rn)                   # [rn, d]
                do = _at(do_ref, r0, rn)
                st = _scores(q, k, scale, qi * block_q + q_off + r0,
                             ki * block_kv + c0, masked, window, mask,
                             _at(qseg_ref, r0, rn) if has_segs else None,
                             kseg, kv_major=True)        # [cn, rn]
                # dp^T = v @ do^T
                dpt = jax.lax.dot_general(v, do, (((1,), (1,)), ((), ())),
                                          preferred_element_type=jnp.float32)
                pt, dst = _grad_tile(
                    st, dpt, lse_ref[0, 0, :, r0:r0 + rn],      # [1, rn]
                    delta_ref[0, 0, :, r0:r0 + rn], scale)
                # dv += p^T @ do ; dk += ds^T @ q
                dv += jax.lax.dot_general(
                    pt.astype(do.dtype), do, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                dk += jax.lax.dot_general(
                    dst.astype(q.dtype), q, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
            dk_scratch[c0:c0 + cn] += dk
            dv_scratch[c0:c0 + cn] += dv

    _for_step_tiles(_body, qi, ki, block_q=block_q, block_kv=block_kv,
                    causal=causal, window=window, q_off=q_off, walks=walks,
                    kv_major=True)

    @pl.when(qi == num_q - 1)
    def _finish():
        dk_ref[0, 0] = dk_scratch[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scratch[:].astype(dv_ref.dtype)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   *rest, causal: bool, has_mask: bool, has_segs: bool,
                   scale: float, block_q: int, block_kv: int, num_kv: int,
                   window=None, q_off: int = 0, walks=((), True)):
    rest = list(rest)
    mask_ref = rest.pop(0) if has_mask else None
    qseg_ref = rest.pop(0) if has_segs else None
    kseg_ref = rest.pop(0) if has_segs else None
    dq_ref, dq_scratch = rest
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        dq_scratch[:] = jnp.zeros_like(dq_scratch)

    def _body(tiles):
        for r0, rn, kv_tiles in tiles:
            q = _at(q_ref, r0, rn)
            do = _at(do_ref, r0, rn)
            lse = _stat_col(lse_ref, r0, rn)            # [rn, 1]
            delta = _stat_col(delta_ref, r0, rn)
            qseg = _at(qseg_ref, r0, rn) if has_segs else None
            dq = 0.0
            for c0, cn, masked in kv_tiles:
                k = _at(k_ref, c0, cn)
                v = _at(v_ref, c0, cn)
                s = _scores(
                    q, k, scale, qi * block_q + q_off + r0,
                    ki * block_kv + c0, masked, window,
                    _at(mask_ref, c0, cn) if has_mask else None,
                    qseg, _at(kseg_ref, c0, cn) if has_segs else None)
                # dp = do @ v^T
                dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                         preferred_element_type=jnp.float32)
                _, ds = _grad_tile(s, dp, lse, delta, scale)
                dq += jax.lax.dot_general(
                    ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
            dq_scratch[r0:r0 + rn] += dq

    _for_step_tiles(_body, qi, ki, block_q=block_q, block_kv=block_kv,
                    causal=causal, window=window, q_off=q_off, walks=walks)

    @pl.when(ki == num_kv - 1)
    def _finish():
        dq_ref[0, 0] = dq_scratch[:].astype(dq_ref.dtype)


def _flash_bwd(causal, scale, block_q, block_kv, window, res, g, q_off=0,
               delta=None, out_fp32=False):
    """out_fp32: emit fp32 grads (ring accumulates per-step contributions
    across hops — rounding each to the input dtype first would compound
    quantization noise; the custom-vjp path keeps input-dtype cotangents
    as jax requires). res's ``o`` may be None when ``delta`` is given."""
    q, k, v, mask, qsegs, ksegs, o, lse = res
    do = g
    B, H, S, D = q.shape
    Skv = k.shape[2]
    group = H // k.shape[1]          # GQA: q heads per kv head
    block_q = min(block_q, S)
    block_kv = min(block_kv, Skv)
    assert S % block_q == 0 and Skv % block_kv == 0, \
        (S, Skv, block_q, block_kv)
    num_q = S // block_q
    num_kv = Skv // block_kv
    has_mask = mask is not None
    has_segs = qsegs is not None
    assert (qsegs is None) == (ksegs is None)
    walks = _block_walks(S, Skv, block_q, block_kv, causal, window, q_off)

    if delta is None:
        delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                        axis=-1)                              # [B,H,S]
    lse_b, delta_b = lse[:, :, None], delta[:, :, None]   # [B, H, 1, S]

    def qmap(b, h, i, j):
        return (b, h, i, 0)

    if causal:
        kvmap_q_outer = _causal_kv_index_map(block_q, block_kv, num_kv,
                                             window, q_off)
    else:
        def kvmap_q_outer(b, h, i, j):
            return (b, h, j, 0)

    # ---- dq ----
    kvmap_q_outer_h = _group_head(kvmap_q_outer, group)
    in_specs = [
        pl.BlockSpec((1, 1, block_q, D), qmap),
        pl.BlockSpec((1, 1, block_kv, D), kvmap_q_outer_h),
        pl.BlockSpec((1, 1, block_kv, D), kvmap_q_outer_h),
        pl.BlockSpec((1, 1, block_q, D), qmap),
        _stat_spec(block_q, qmap),
        _stat_spec(block_q, qmap),
    ]
    operands = [q, k, v, do, lse_b, delta_b]
    if has_mask:
        in_specs.append(_mask_spec(block_kv, kvmap_q_outer))
        operands.append(mask[:, None])
    if has_segs:
        in_specs.append(_qseg_spec(block_q, qmap))
        in_specs.append(_mask_spec(block_kv, kvmap_q_outer))
        operands.extend([qsegs[:, None], ksegs[:, None]])
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, causal=causal, has_mask=has_mask,
                          has_segs=has_segs,
                          scale=scale, block_q=block_q, block_kv=block_kv,
                          num_kv=num_kv, window=window, q_off=q_off,
                          walks=walks),
        name="flash_bwd_dq",
        grid=(B, H, num_q, num_kv),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, block_q, D), qmap),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        out_shape=jax.ShapeDtypeStruct(
            (B, H, S, D), jnp.float32 if out_fp32 else q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")),
    )(*operands)

    # ---- dk, dv ---- (kv outer, q inner)
    def kvmap(b, h, ki, qi):
        return (b, h, ki, 0)

    if causal:
        # early q blocks are above the diagonal for this kv block: clamp
        # to the first valid q block so the skipped steps' fetches elide
        # (min'd into range for Skv > S callers, where no q block may be
        # valid for the last kv blocks). With a sliding window the LAST
        # valid q block is bounded too — late steps clamp down the same
        # way.
        def qmap_kv_outer(b, h, ki, qi):
            first = jnp.clip((ki * block_kv - q_off) // block_q,
                             0, num_q - 1)
            qi = jnp.maximum(qi, first)
            if window is not None:
                last = jnp.clip(
                    (ki * block_kv + block_kv - 1 + window - 1 - q_off)
                    // block_q,
                    0, num_q - 1)
                qi = jnp.minimum(qi, last)
            return (b, h, qi, 0)
    else:
        def qmap_kv_outer(b, h, ki, qi):
            return (b, h, qi, 0)

    kvmap_in_h = _group_head(kvmap, group)
    in_specs = [
        pl.BlockSpec((1, 1, block_q, D), qmap_kv_outer),
        pl.BlockSpec((1, 1, block_kv, D), kvmap_in_h),
        pl.BlockSpec((1, 1, block_kv, D), kvmap_in_h),
        pl.BlockSpec((1, 1, block_q, D), qmap_kv_outer),
        _stat_spec(block_q, qmap_kv_outer),
        _stat_spec(block_q, qmap_kv_outer),
    ]
    operands = [q, k, v, do, lse_b, delta_b]
    if has_mask:
        # kv blocks are on the OUTER grid dim here; _mask_spec follows
        # this call's kvmap, which resolves to (b, ki)
        in_specs.append(_mask_spec(block_kv, kvmap))
        operands.append(mask[:, None])
    if has_segs:
        in_specs.append(_qseg_spec(block_q, qmap_kv_outer))
        in_specs.append(_mask_spec(block_kv, kvmap))
        operands.extend([qsegs[:, None], ksegs[:, None]])
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, causal=causal, has_mask=has_mask,
                          has_segs=has_segs,
                          scale=scale, block_q=block_q, block_kv=block_kv,
                          num_q=num_q, window=window, q_off=q_off,
                          walks=walks),
        name="flash_bwd_dkv",
        grid=(B, H, num_kv, num_q),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, block_kv, D), kvmap),
            pl.BlockSpec((1, 1, block_kv, D), kvmap),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_kv, D), jnp.float32),
            pltpu.VMEM((block_kv, D), jnp.float32),
        ],
        out_shape=[
            # GQA partials stay fp32 so the cross-head reduction below
            # accumulates at full precision (cast once after the sum)
            jax.ShapeDtypeStruct(
                (B, H, Skv, D),
                jnp.float32 if (group > 1 or out_fp32) else k.dtype),
            jax.ShapeDtypeStruct(
                (B, H, Skv, D),
                jnp.float32 if (group > 1 or out_fp32) else v.dtype),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")),
    )(*operands)

    if group > 1:
        # per-q-head partials -> per-kv-head grads (GQA): accumulation
        # across q heads can't happen inside the kernel (h is a parallel
        # grid dim), so reduce the group outside
        Hkv = H // group
        kd = jnp.float32 if out_fp32 else k.dtype
        vd = jnp.float32 if out_fp32 else v.dtype
        dk = dk.reshape(B, Hkv, group, Skv, D).sum(2).astype(kd)
        dv = dv.reshape(B, Hkv, group, Skv, D).sum(2).astype(vd)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public op
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9, 10, 11, 12))
def _flash(q, k, v, mask, qsegs, ksegs, causal, scale, block_q, block_kv,
           window=None, bwd_block_q=None, bwd_block_kv=None):
    o, _ = _flash_fwd(q, k, v, mask, qsegs, ksegs, causal, scale, block_q,
                      block_kv, window)
    return o


def _flash_vjp_fwd(q, k, v, mask, qsegs, ksegs, causal, scale, block_q,
                   block_kv, window=None, bwd_block_q=None,
                   bwd_block_kv=None):
    o, lse = _flash_fwd(q, k, v, mask, qsegs, ksegs, causal, scale, block_q,
                        block_kv, window)
    # named so a selective remat policy can keep the residuals — without
    # these, jax.checkpoint re-runs the whole forward kernel in the backward
    # pass just to regenerate o/lse. The o residual is stored with (H, D)
    # merged into one 128-aligned trailing axis: saving it in the kernel's
    # [B, H, S, D] layout would tile D=64 up to 128 lanes — 2x the HBM for
    # every checkpointed layer.
    B, H, S, D = o.shape
    o_res = o.transpose(0, 2, 1, 3).reshape(B, S, H * D)
    o_res = checkpoint_name(o_res, "flash_out")
    lse = checkpoint_name(lse, "flash_lse")
    return o, (q, k, v, mask, qsegs, ksegs, o_res, lse)


def _flash_vjp_bwd(causal, scale, block_q, block_kv, window, bwd_block_q,
                   bwd_block_kv, res, g):
    q, k, v, mask, qsegs, ksegs, o_res, lse = res
    B, H, S, D = q.shape
    o = o_res.reshape(B, S, H, D).transpose(0, 2, 1, 3)
    # the dq/dkv kernels have different reuse patterns than the forward
    # (both stream the FULL opposite operand per block) — let callers tune
    # their tiles independently of the fwd blocks
    dq, dk, dv = _flash_bwd(causal, scale, bwd_block_q or block_q,
                            bwd_block_kv or block_kv, window,
                            (q, k, v, mask, qsegs, ksegs, o, lse), g)
    return dq, dk, dv, None, None, None


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    causal: bool = True, scale: Optional[float] = None,
                    block_q: int = 512, block_kv: int = 512,
                    kv_mask: Optional[jnp.ndarray] = None,
                    segment_ids: Optional[jnp.ndarray] = None,
                    window: Optional[int] = None,
                    bwd_block_q: Optional[int] = None,
                    bwd_block_kv: Optional[int] = None) -> jnp.ndarray:
    """Flash attention over [B, S, H, D] tensors.

    Head dims that are sublane-aligned (multiple of 8) run unpadded: Mosaic
    masks the lane remainder, so QK^T streams only D real contraction lanes
    through the MXU and HBM moves only real bytes. Padding D=64 up to 128
    (the previous behavior) doubled both the attention matmul cycles and the
    q/k/v/o HBM traffic. Odd head dims still pad to the next sublane
    multiple. Fallback is the caller's job (models gate via _flash_eligible).

    kv_mask: optional [B, Skv] key-validity mask (1 = attend, 0 = padding)
    — the encoder attention-mask path. Padded QUERY rows produce
    normalized-over-valid-keys outputs like the dense path; rows with NO
    valid key degenerate to a uniform average of v (identical to the
    dense softmax-over-NEG_INF behavior) — garbage-by-contract, and
    their gradients are zero as long as the loss masks them, which every
    masked loss here does.

    segment_ids: optional [B, S] int ids for PACKED sequences (requires
    S == Skv): token i attends token j only when segment_ids match (and
    causality holds) — block-diagonal attention, so several short
    documents share one row with zero cross-contamination.

    Grouped-query attention: k/v may carry FEWER heads than q
    (``H % Hkv == 0``); each group of ``H // Hkv`` query heads shares one
    kv head, shrinking the KV cache by the group factor.

    window: optional sliding-window size (requires causal): token i
    attends tokens (i-window, i] only — O(S*window) compute AND HBM
    reads (out-of-band blocks' fetches are elided via index-map clamps).
    """
    B, S, H, D = q.shape
    Hkv = k.shape[2]
    assert H % Hkv == 0, f"q heads {H} not a multiple of kv heads {Hkv}"
    assert v.shape[2] == Hkv, \
        f"k has {Hkv} heads but v has {v.shape[2]} — kv head counts must match"
    if scale is None:
        scale = 1.0 / np.sqrt(D)
    if segment_ids is not None:
        assert k.shape[1] == S, "segment_ids requires self-attention (Skv == S)"
    if window is not None:
        assert causal, "sliding window attention requires causal=True"
        assert window >= 1
    q, k, v, D, Dp = _pad_heads(q, k, v)
    # kernel-internal layout is [B, H, S, D]
    q = q.transpose(0, 2, 1, 3)
    k = k.transpose(0, 2, 1, 3)
    v = v.transpose(0, 2, 1, 3)
    if kv_mask is not None:
        kv_mask = kv_mask.astype(jnp.float32)
    if segment_ids is not None:
        segment_ids = segment_ids.astype(jnp.int32)
    out = _flash(q, k, v, kv_mask, segment_ids, segment_ids, causal, scale,
                 block_q, block_kv, window, bwd_block_q, bwd_block_kv)
    out = out.transpose(0, 2, 1, 3)
    if Dp != D:
        out = out[..., :D]
    return out


# ---------------------------------------------------------------------------
# block-level entry points (ring attention building blocks)
# ---------------------------------------------------------------------------

def flash_block_fwd_t(q, k, v, kv_mask=None, q_segs=None, kv_segs=None, *,
                      causal=True, scale, block_q=512, block_kv=512,
                      window=None, q_off=0):
    """Kernel-layout ([B, H, S, D], D sublane-aligned) variant of
    :func:`flash_block_fwd` — no per-call pad/transpose, so a ring loop
    can hoist the layout change out of its steps. Returns (o [B,H,S,D],
    lse [B,H,S]). Not differentiable (ring owns the VJP)."""
    return _flash_fwd(q, k, v, kv_mask, q_segs, kv_segs, causal, scale,
                      block_q, block_kv, window, q_off)


def flash_block_bwd_t(q, k, v, do, lse, kv_mask=None, q_segs=None,
                      kv_segs=None, *, causal=True, scale, block_q=512,
                      block_kv=512, window=None, q_off=0, delta, o=None):
    """Kernel-layout backward companion of :func:`flash_block_fwd_t`;
    ``delta`` (= rowsum(do*o), [B,H,S]) is precomputed ONCE per ring
    backward, so ``o`` is not needed (pass it only if delta were ever
    recomputed here). Returns fp32 (dq, dk, dv) in [B,H,S,D] — the ring
    sums per-step contributions across hops and must not round each to
    the input dtype first."""
    return _flash_bwd(causal, scale, block_q, block_kv, window,
                      (q, k, v, kv_mask, q_segs, kv_segs, o, lse),
                      do, q_off, delta, out_fp32=True)


def _pad_heads(q, k, v):
    D = q.shape[-1]
    Dp = D if D % 8 == 0 else _ceil_to(D, 8)
    if Dp != D:
        pad = [(0, 0), (0, 0), (0, 0), (0, Dp - D)]
        q, k, v = jnp.pad(q, pad), jnp.pad(k, pad), jnp.pad(v, pad)
    return q, k, v, D, Dp


def flash_block_fwd(q, k, v, kv_mask=None, q_segs=None, kv_segs=None, *,
                    causal=True, scale=None, block_q=512, block_kv=512,
                    window=None, q_off=0):
    """One flash forward over [B, S, H, D] tensors, returning BOTH the
    normalized output and the per-row logsumexp: ``(o [B,S,H,D],
    lse [B,H,S])``.

    NOT differentiable — ring attention (ops/attention/ring.py) calls
    this per held K/V block inside its own custom VJP and combines the
    per-block (o, lse) pairs with an online softmax across ring steps.
    ``q_off`` is the static global position of q row 0 relative to key 0
    of this block (the ring-step distance x S_local); q-side and kv-side
    segment ids are separate because the kv metadata rotates with its
    block."""
    if scale is None:
        scale = 1.0 / np.sqrt(q.shape[-1])
    q, k, v, D, Dp = _pad_heads(q, k, v)
    q = q.transpose(0, 2, 1, 3)
    k = k.transpose(0, 2, 1, 3)
    v = v.transpose(0, 2, 1, 3)
    if kv_mask is not None:
        kv_mask = kv_mask.astype(jnp.float32)
    if q_segs is not None:
        q_segs = q_segs.astype(jnp.int32)
        kv_segs = kv_segs.astype(jnp.int32)
    o, lse = _flash_fwd(q, k, v, kv_mask, q_segs, kv_segs, causal, scale,
                        block_q, block_kv, window, q_off)
    o = o.transpose(0, 2, 1, 3)
    if Dp != D:
        o = o[..., :D]
    return o, lse


def flash_block_bwd(q, k, v, do, o, lse, kv_mask=None, q_segs=None,
                    kv_segs=None, *, causal=True, scale=None, block_q=512,
                    block_kv=512, window=None, q_off=0):
    """Backward companion of :func:`flash_block_fwd`: given the global
    ``lse`` (combined across ring steps) and the global output ``o``,
    returns this block's additive contribution ``(dq, dk, dv)`` in
    [B, S, H, D] layout. Per-block contributions with a shared lse/delta
    sum to the exact softmax gradient (FA2 recompute form)."""
    if scale is None:
        scale = 1.0 / np.sqrt(q.shape[-1])
    q, k, v, D, Dp = _pad_heads(q, k, v)
    # pad do/o the same way (zero lanes contribute nothing to delta)
    if Dp != D:
        pad = [(0, 0), (0, 0), (0, 0), (0, Dp - D)]
        do = jnp.pad(do, pad)
        o = jnp.pad(o, pad)
    q = q.transpose(0, 2, 1, 3)
    k = k.transpose(0, 2, 1, 3)
    v = v.transpose(0, 2, 1, 3)
    do = do.transpose(0, 2, 1, 3)
    o = o.transpose(0, 2, 1, 3)
    if kv_mask is not None:
        kv_mask = kv_mask.astype(jnp.float32)
    if q_segs is not None:
        q_segs = q_segs.astype(jnp.int32)
        kv_segs = kv_segs.astype(jnp.int32)
    dq, dk, dv = _flash_bwd(causal, scale, block_q, block_kv, window,
                            (q, k, v, kv_mask, q_segs, kv_segs, o, lse),
                            do, q_off)
    dq = dq.transpose(0, 2, 1, 3)
    dk = dk.transpose(0, 2, 1, 3)
    dv = dv.transpose(0, 2, 1, 3)
    if Dp != D:
        dq, dk, dv = dq[..., :D], dk[..., :D], dv[..., :D]
    return dq, dk, dv


def mha_reference(q, k, v, causal=True, scale=None, kv_mask=None,
                  segment_ids=None, window=None):
    """Pure-jnp reference for parity tests (analog of the python BERT
    baselines in ref tests/unit/test_cuda_forward.py)."""
    B, S, H, D = q.shape
    if k.shape[2] != H:              # GQA: repeat kv heads per group
        k = jnp.repeat(k, H // k.shape[2], axis=2)
        v = jnp.repeat(v, H // v.shape[2], axis=2)
    if scale is None:
        scale = 1.0 / np.sqrt(D)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        mask = jnp.tril(jnp.ones((S, k.shape[1]), bool))
        if window is not None:
            mask = mask & ~jnp.tril(jnp.ones((S, k.shape[1]), bool),
                                    -window)
        logits = jnp.where(mask[None, None], logits, NEG_INF)
    if kv_mask is not None:
        logits = jnp.where(kv_mask[:, None, None, :] > 0, logits, NEG_INF)
    if segment_ids is not None:
        same = segment_ids[:, :, None] == segment_ids[:, None, :]
        logits = jnp.where(same[:, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)
