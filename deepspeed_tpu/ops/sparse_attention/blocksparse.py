"""Block-sparse attention for TPU.

Capability equivalent of the reference's Triton block-sparse kernels
(ref: deepspeed/ops/sparse_attention/matmul.py:214 _sparse_matmul /
softmax.py:146 + csrc/sparse_attention/utils.cpp:14 segment_blocks).

TPU-first design: instead of SDD/DSD/DDS matmuls over a CSR-ish layout,
the host compiles the [H, nb, nb] block layout into a gather LUT — for
every (head, query-block-row) the list of active key blocks, padded to
the max row population. Compute is then:

- a Pallas kernel (splash-attention style): grid (B, H, q-block, lut-slot)
  with the LUT scalar-prefetched so the BlockSpec index_map fetches
  exactly the active K/V blocks from HBM; online softmax in VMEM scratch.
  Work is O(S * max_nnz_row * block) — the full sparse speedup.
- a pure-jnp gather path with identical semantics used for grads (the
  Pallas backward recomputes through it) and as the mask-supporting /
  non-TPU fallback. Also O(active blocks), and differentiable.

Both paths never materialize the [S, S] score matrix.
"""

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128


def make_lut(layout: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Compile a [H, nb, nb] 0/1 layout into (lut, valid).

    lut   : int32 [H, nb, L] — active key-block index per slot (0-padded)
    valid : bool  [H, nb, L] — slot validity

    L = max active blocks in any (head, row). This is the TPU analog of
    the reference's segment_blocks LUT (csrc/sparse_attention/utils.cpp:14).
    """
    layout = np.asarray(layout)
    H, nb, _ = layout.shape
    counts = layout.sum(-1)
    L = max(1, int(counts.max()))
    lut = np.zeros((H, nb, L), dtype=np.int32)
    valid = np.zeros((H, nb, L), dtype=bool)
    for h in range(H):
        for r in range(nb):
            cols = np.nonzero(layout[h, r])[0]
            lut[h, r, :len(cols)] = cols
            valid[h, r, :len(cols)] = True
    return lut, valid


# ---------------------------------------------------------------------------
# pure-jnp gather path (differentiable; supports masks)
# ---------------------------------------------------------------------------

def _gather_blocks(xb: jnp.ndarray, lut: jnp.ndarray) -> jnp.ndarray:
    """xb [B, H, nb, bk, D], lut [H, nq, L] -> [B, H, nq, L, bk, D]."""
    return jax.vmap(lambda xh, luth: xh[:, luth],
                    in_axes=(1, 0), out_axes=1)(xb, lut)


def blocksparse_attention_jnp(q, k, v, lut, valid, block: int,
                              causal: bool = False,
                              scale: Optional[float] = None,
                              key_padding_mask=None,
                              key_padding_mask_mode: str = "add",
                              attn_mask=None,
                              attn_mask_mode: str = "mul",
                              rpe=None):
    """Gather-based block-sparse attention over [B, S, H, D] tensors."""
    B, S, H, D = q.shape
    if scale is None:
        scale = 1.0 / np.sqrt(D)
    nb = S // block
    L = lut.shape[-1]
    qb = q.transpose(0, 2, 1, 3).reshape(B, H, nb, block, D)
    kb = k.transpose(0, 2, 1, 3).reshape(B, H, nb, block, D)
    vb = v.transpose(0, 2, 1, 3).reshape(B, H, nb, block, D)
    lut = jnp.asarray(lut)
    valid = jnp.asarray(valid)

    kg = _gather_blocks(kb, lut)                    # [B,H,nb,L,bk,D]
    vg = _gather_blocks(vb, lut)

    s = jnp.einsum("bhqid,bhqlkd->bhqilk", qb, kg,
                   preferred_element_type=jnp.float32) * scale
    # global row/col token ids for masking
    row_ids = (jnp.arange(nb)[:, None] * block +
               jnp.arange(block)[None, :])          # [nb, bq]
    col_ids = lut[..., None] * block + jnp.arange(block)  # [H,nb,L,bk]

    keep = jnp.broadcast_to(valid[None, :, :, None, :, None],
                            s.shape)
    if causal:
        cm = (row_ids[None, :, :, None, None] >=
              col_ids[:, :, None, :, :])            # [H,nb,bq,L,bk]
        keep = keep & cm[None]
    if attn_mask is not None:
        am = jnp.asarray(attn_mask)
        amg = am[row_ids[None, :, :, None, None],
                 col_ids[:, :, None, :, :]]         # [H,nb,bq,L,bk]
        if attn_mask_mode == "mul":
            keep = keep & (amg[None] != 0)
        else:
            s = s + amg[None].astype(jnp.float32)
    if rpe is not None:
        # relative-position bias [S, S], always additive
        rp = jnp.asarray(rpe)
        rpg = rp[row_ids[None, :, :, None, None],
                 col_ids[:, :, None, :, :]]
        s = s + rpg[None].astype(jnp.float32)
    if key_padding_mask is not None:
        kp = jnp.asarray(key_padding_mask)          # [B, S]
        kpg = kp[:, col_ids]                        # [B,H,nb,L,bk]
        if key_padding_mask_mode == "mul":
            keep = keep & (kpg[:, :, :, None] != 0)
        else:
            s = s + kpg[:, :, :, None].astype(jnp.float32)

    s = jnp.where(keep, s, NEG_INF)
    sf = s.reshape(B, H, nb, block, L * block)
    keepf = keep.reshape(sf.shape)
    m = jnp.max(sf, axis=-1, keepdims=True)
    # rows with no active key produce all-NEG_INF: emit zeros
    p = jnp.exp(sf - jax.lax.stop_gradient(m)) * keepf
    denom = jnp.sum(p, axis=-1, keepdims=True)
    p = p / jnp.where(denom == 0.0, 1.0, denom)
    p = p.reshape(B, H, nb, block, L, block).astype(q.dtype)
    out = jnp.einsum("bhqilk,bhqlkd->bhqid", p, vg)
    return out.reshape(B, H, S, D).transpose(0, 2, 1, 3)


# ---------------------------------------------------------------------------
# Pallas forward kernel (LUT scalar-prefetched)
# ---------------------------------------------------------------------------

def _bs_fwd_kernel(lut_ref, nnz_ref, q_ref, k_ref, v_ref, o_ref,
                   m_scratch, l_scratch, acc_scratch,
                   *, causal: bool, scale: float, block: int, num_l: int):
    h = pl.program_id(1)
    qi = pl.program_id(2)
    li = pl.program_id(3)

    @pl.when(li == 0)
    def _init():
        m_scratch[:] = jnp.full_like(m_scratch, NEG_INF)
        l_scratch[:] = jnp.zeros_like(l_scratch)
        acc_scratch[:] = jnp.zeros_like(acc_scratch)

    ki = lut_ref[h, qi, li]
    active = li < nnz_ref[h, qi]

    @pl.when(active)
    def _body():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal:
            rows = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) + qi * block
            cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + ki * block
            s = jnp.where(rows >= cols, s, NEG_INF)
        m_prev = m_scratch[:, :1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        # fully-masked rows: s - m_new would be 0 everywhere; zero them so
        # the kernel matches the jnp path's "no active key -> zeros" output
        p = jnp.where(s <= NEG_INF / 2, 0.0, jnp.exp(s - m_new))
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_scratch[:, :1] + jnp.sum(p, axis=-1, keepdims=True)
        acc_scratch[:] = acc_scratch[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scratch[:] = jnp.broadcast_to(m_new, m_scratch.shape)
        l_scratch[:] = jnp.broadcast_to(l_new, l_scratch.shape)

    @pl.when(li == num_l - 1)
    def _finish():
        l = l_scratch[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_scratch[:] / l_safe).astype(o_ref.dtype)


def _bs_pallas_fwd(q, k, v, lut, nnz, block, causal, scale):
    """q/k/v [B, H, S, D] (kernel layout); lut [H, nb, L], nnz [H, nb]."""
    B, H, S, D = q.shape
    nb = S // block
    L = lut.shape[-1]

    def qmap(b, h, qi, li, lut_ref, nnz_ref):
        return (b, h, qi, 0)

    def kvmap(b, h, qi, li, lut_ref, nnz_ref):
        return (b, h, lut_ref[h, qi, li], 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, H, nb, L),
        in_specs=[
            pl.BlockSpec((1, 1, block, D), qmap),
            pl.BlockSpec((1, 1, block, D), kvmap),
            pl.BlockSpec((1, 1, block, D), kvmap),
        ],
        out_specs=pl.BlockSpec((1, 1, block, D), qmap),
        scratch_shapes=[
            pltpu.VMEM((block, LANES), jnp.float32),
            pltpu.VMEM((block, LANES), jnp.float32),
            pltpu.VMEM((block, D), jnp.float32),
        ],
    )
    kernel = functools.partial(_bs_fwd_kernel, causal=causal, scale=scale,
                               block=block, num_l=L)
    return pl.pallas_call(
        kernel,
        name="blocksparse_fwd",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, S, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
    )(jnp.asarray(lut), jnp.asarray(nnz), q, k, v)


def _ceil_to(x, m):
    return (x + m - 1) // m * m


# one custom_vjp function per (layout, block, causal, scale, D) — cached so
# repeated eager calls reuse the same traced/compiled function object
_KERNEL_CACHE = {}


def _get_kernel_fn(lut, valid, block, causal, scale, D):
    key = (lut.tobytes(), lut.shape, block, causal, float(scale), D)
    if key in _KERNEL_CACHE:
        return _KERNEL_CACHE[key]
    nnz = np.asarray(valid).sum(-1).astype(np.int32)
    Dp = _ceil_to(D, LANES)

    @jax.custom_vjp
    def f(q, k, v):
        qt = q.transpose(0, 2, 1, 3)
        kt = k.transpose(0, 2, 1, 3)
        vt = v.transpose(0, 2, 1, 3)
        if Dp != D:
            pad = [(0, 0), (0, 0), (0, 0), (0, Dp - D)]
            qt, kt, vt = jnp.pad(qt, pad), jnp.pad(kt, pad), jnp.pad(vt, pad)
        o = _bs_pallas_fwd(qt, kt, vt, lut, nnz, block, causal, scale)
        return o[..., :D].transpose(0, 2, 1, 3)

    def fwd(q, k, v):
        return f(q, k, v), (q, k, v)

    def bwd(res, g):
        q, k, v = res
        _, vjp = jax.vjp(
            lambda a, b, c: blocksparse_attention_jnp(
                a, b, c, lut, valid, block, causal=causal, scale=scale),
            q, k, v)
        return vjp(g)

    f.defvjp(fwd, bwd)
    _KERNEL_CACHE[key] = f
    return f


def blocksparse_attention_kernel(q, k, v, lut, valid, block: int,
                                 causal: bool = False,
                                 scale: Optional[float] = None):
    """Pallas block-sparse attention over [B, S, H, D]; grads recompute
    through the jnp gather path (same math, exact VJP)."""
    B, S, H, D = q.shape
    if scale is None:
        scale = 1.0 / np.sqrt(D)
    lut = np.asarray(lut, dtype=np.int32)
    valid = np.asarray(valid)
    return _get_kernel_fn(lut, valid, block, causal, scale, D)(q, k, v)


# ---------------------------------------------------------------------------
# public entry
# ---------------------------------------------------------------------------

def blocksparse_attention(q, k, v, layout, causal: bool = False,
                          scale: Optional[float] = None,
                          key_padding_mask=None,
                          key_padding_mask_mode: str = "add",
                          attn_mask=None, attn_mask_mode: str = "mul",
                          rpe=None,
                          use_kernel: Optional[bool] = None,
                          lut_valid: Optional[Tuple] = None):
    """Block-sparse attention over [B, S, H, D] with a [H, nb, nb] layout.

    The Pallas kernel path is used on TPU when no element-wise masks are
    given; otherwise the jnp gather path (same complexity) runs.
    ``lut_valid`` lets callers pass a pre-compiled ``make_lut`` result.
    """
    B, S, H, D = q.shape
    layout = np.asarray(layout)
    nb = layout.shape[1]
    if S % nb != 0:
        raise ValueError(f"seq len {S} not divisible by layout blocks {nb}")
    block = S // nb
    lut, valid = lut_valid if lut_valid is not None else make_lut(layout)
    if use_kernel is None:
        from deepspeed_tpu.utils import on_tpu
        use_kernel = (key_padding_mask is None and attn_mask is None
                      and rpe is None and block % 8 == 0 and on_tpu())
    if use_kernel:
        return blocksparse_attention_kernel(q, k, v, lut, valid, block,
                                            causal=causal, scale=scale)
    return blocksparse_attention_jnp(
        q, k, v, lut, valid, block, causal=causal, scale=scale,
        key_padding_mask=key_padding_mask,
        key_padding_mask_mode=key_padding_mask_mode,
        attn_mask=attn_mask, attn_mask_mode=attn_mask_mode, rpe=rpe)


def blocksparse_reference(q, k, v, layout, causal: bool = False,
                          scale: Optional[float] = None,
                          key_padding_mask=None,
                          key_padding_mask_mode: str = "add",
                          attn_mask=None, attn_mask_mode: str = "mul",
                          rpe=None):
    """Dense O(S^2) reference with the layout expanded to an element mask
    (parity oracle, analog of ref tests/unit/test_sparse_attention.py)."""
    B, S, H, D = q.shape
    if scale is None:
        scale = 1.0 / np.sqrt(D)
    nb = layout.shape[1]
    block = S // nb
    mask = np.kron(np.asarray(layout), np.ones((block, block)))  # [H,S,S]
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    keep = jnp.asarray(mask != 0)[None]
    if causal:
        keep = keep & jnp.tril(jnp.ones((S, S), bool))[None, None]
    if attn_mask is not None:
        am = jnp.asarray(attn_mask)
        if attn_mask_mode == "mul":
            keep = keep & (am != 0)[None, None]
        else:
            logits = logits + am[None, None].astype(jnp.float32)
    if key_padding_mask is not None:
        kp = jnp.asarray(key_padding_mask)
        if key_padding_mask_mode == "mul":
            keep = keep & (kp != 0)[:, None, None, :]
        else:
            logits = logits + kp[:, None, None, :].astype(jnp.float32)
    if rpe is not None:
        logits = logits + jnp.asarray(rpe)[None, None].astype(jnp.float32)
    logits = jnp.where(keep, logits, NEG_INF)
    m = jnp.max(logits, axis=-1, keepdims=True)
    p = jnp.exp(logits - m)
    denom = jnp.sum(p, axis=-1, keepdims=True)
    p = (p / jnp.where(denom == 0.0, 1.0, denom)).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)
