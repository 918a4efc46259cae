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
the precomputed per-row delta = rowsum(dO * O).

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


def _norm_window(window):
    """Decode the static ``window`` argument into
    ``(mask_window, band_window)``.

    ``window`` is either an int — the BANDED implementation: DMA-eliding
    index-map clamps + band-aware grid skipping + in-body mask — or the
    tagged tuple ``("masked", int)`` — the second implementation, which
    expresses the sliding window purely as an in-body mask over the
    plain causal geometry. It was written when an earlier toolchain's
    Mosaic hung on the banded form; both compile under libtpu 0.0.34
    (tools/kernel_census.py; ROADMAP D6 keeps one). Cost: O(S^2) HBM
    reads/compute like plain causal instead of O(S*W) — correctness is
    identical because fully out-of-band blocks wash out of the online
    softmax exactly like fully-masked kv_mask blocks (see
    flash_attention docstring)."""
    if window is None:
        return None, None
    if isinstance(window, tuple):
        impl, w = window
        assert impl == "masked", f"unknown window impl {impl!r}"
        return int(w), None
    return int(window), int(window)


def resolve_window_impl(window, window_impl=None):
    """Tag ``window`` for the masked implementation when requested
    (explicit arg wins, else DS_FLASH_WINDOW_IMPL, default banded).
    Shared by every window entry point (flash_attention, ring,
    ulysses)."""
    if window is None or isinstance(window, tuple):
        return window
    from deepspeed_tpu.utils.env import resolve_flag
    impl = window_impl or resolve_flag("DS_FLASH_WINDOW_IMPL")
    if impl not in ("banded", "masked"):
        # ValueError, not assert: this validates user input (env var /
        # config) and must survive python -O
        raise ValueError(f"unknown window impl {impl!r}: "
                         f"expected 'banded' or 'masked'")
    return ("masked", int(window)) if impl == "masked" else int(window)
LANES = 128
STATS = 8   # lane width for per-row softmax stats (lse/delta) — sublane-aligned


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

    window = _norm_window(window)[1]     # banded geometry only

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
    """Whether grid step (qi, ki) intersects the attention band."""
    window = _norm_window(window)[1]     # banded geometry only
    run = True
    if causal:
        run = qi * block_q + block_q - 1 + q_off >= ki * block_kv
    if window is not None:
        # lowest q row of the block must still reach the block's last col
        run = jnp.logical_and(
            run,
            ki * block_kv + block_kv - 1 >= qi * block_q + q_off - window + 1)
    return run


def _window_mask(s, rows, cols, window):
    """cols within (rows - window, rows]: Mistral-style local attention."""
    return jnp.where(rows - cols < window, s, NEG_INF)


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
        k = k_ref[0, 0]                  # [block_kv, d]
        v = v_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # [bq, bkv]

        if causal:
            rows = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) \
                + qi * block_q + q_off
            cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + ki * block_kv
            s = jnp.where(rows >= cols, s, NEG_INF)
            if window is not None:
                s = _window_mask(s, rows, cols, _norm_window(window)[0])
        if has_mask:
            s = jnp.where(mask_ref[0, 0][None, :] > 0, s, NEG_INF)
        if has_segs:
            s = jnp.where(qseg_ref[0, 0][:, None] == kseg_ref[0, 0][None, :],
                          s, NEG_INF)

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
        l = l_scratch[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_scratch[:] / l_safe).astype(o_ref.dtype)
        lse = m_scratch[:, :1] + jnp.log(l_safe)
        lse_ref[0, 0] = jnp.broadcast_to(lse, lse_ref.shape[2:]).astype(jnp.float32)


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
        jax.ShapeDtypeStruct((B, H, S, STATS), jnp.float32),
    ]
    o, lse = pl.pallas_call(
        kernel,
        name="flash_fwd",
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, block_q, D), qmap),
            pl.BlockSpec((1, 1, block_q, STATS), qmap),
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
    return o, lse[..., 0]


# ---------------------------------------------------------------------------
# backward kernels
# ---------------------------------------------------------------------------

def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    *rest, causal: bool, has_mask: bool, has_segs: bool,
                    scale: float, block_q: int, block_kv: int, num_q: int,
                    window=None, q_off: int = 0):
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

    run = _band_run(qi, ki, block_q, block_kv, causal, window, q_off)

    @pl.when(run)
    def _body():
        q = q_ref[0, 0]                # [bq, d]
        k = k_ref[0, 0]                # [bkv, d]
        v = v_ref[0, 0]
        do = do_ref[0, 0]              # [bq, d]
        lse = lse_ref[0, 0][:, :1]     # [bq, 1]
        delta = delta_ref[0, 0][:, :1]  # [bq, 1]

        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            rows = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) \
                + qi * block_q + q_off
            cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + ki * block_kv
            s = jnp.where(rows >= cols, s, NEG_INF)
            if window is not None:
                s = _window_mask(s, rows, cols, _norm_window(window)[0])
        if has_mask:
            s = jnp.where(mask_ref[0, 0][None, :] > 0, s, NEG_INF)
        if has_segs:
            s = jnp.where(qseg_ref[0, 0][:, None] == kseg_ref[0, 0][None, :],
                          s, NEG_INF)
        p = jnp.exp(s - lse)                               # [bq, bkv]

        # dv += p^T @ do
        dv_scratch[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        # dp = do @ v^T ; ds = p * (dp - delta) * scale
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale                      # [bq, bkv]
        # dk += ds^T @ q
        dk_scratch[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(qi == num_q - 1)
    def _finish():
        dk_ref[0, 0] = dk_scratch[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scratch[:].astype(dv_ref.dtype)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   *rest, causal: bool, has_mask: bool, has_segs: bool,
                   scale: float, block_q: int, block_kv: int, num_kv: int,
                   window=None, q_off: int = 0):
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

    run = _band_run(qi, ki, block_q, block_kv, causal, window, q_off)

    @pl.when(run)
    def _body():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0][:, :1]
        delta = delta_ref[0, 0][:, :1]

        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            rows = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) \
                + qi * block_q + q_off
            cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + ki * block_kv
            s = jnp.where(rows >= cols, s, NEG_INF)
            if window is not None:
                s = _window_mask(s, rows, cols, _norm_window(window)[0])
        if has_mask:
            s = jnp.where(mask_ref[0, 0][None, :] > 0, s, NEG_INF)
        if has_segs:
            s = jnp.where(qseg_ref[0, 0][:, None] == kseg_ref[0, 0][None, :],
                          s, NEG_INF)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dq_scratch[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

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

    if delta is None:
        delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                        axis=-1)                              # [B,H,S]
    lse_b = jnp.broadcast_to(lse[..., None], (B, H, S, STATS))
    delta_b = jnp.broadcast_to(delta[..., None], (B, H, S, STATS))

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
        pl.BlockSpec((1, 1, block_q, STATS), qmap),
        pl.BlockSpec((1, 1, block_q, STATS), qmap),
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
                          num_kv=num_kv, window=window, q_off=q_off),
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
        band_w = _norm_window(window)[1]   # banded geometry only

        def qmap_kv_outer(b, h, ki, qi):
            first = jnp.clip((ki * block_kv - q_off) // block_q,
                             0, num_q - 1)
            qi = jnp.maximum(qi, first)
            if band_w is not None:
                last = jnp.clip(
                    (ki * block_kv + block_kv - 1 + band_w - 1 - q_off)
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
        pl.BlockSpec((1, 1, block_q, STATS), qmap_kv_outer),
        pl.BlockSpec((1, 1, block_q, STATS), qmap_kv_outer),
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
                          num_q=num_q, window=window, q_off=q_off),
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
                    bwd_block_kv: Optional[int] = None,
                    window_impl: Optional[str] = None) -> jnp.ndarray:
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

    window_impl: "banded" (default; also via DS_FLASH_WINDOW_IMPL) keeps
    the O(S*W) index-map clamps; "masked" expresses the window purely as
    an in-body mask over plain causal geometry — O(S^2) reads (see
    _norm_window).
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
        assert isinstance(window, tuple) or window >= 1
        window = resolve_window_impl(window, window_impl)
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
