"""Paged-attention decode kernel — Pallas TPU flash-decode through the
block table.

The pool this kernel reads is the one the paged cache stores and the
layer loop carries: ``[N', block, Hkv*Dh]``, a token's kv heads side by
side in ONE row of ``Hkv*Dh`` lanes (inference/paged_cache.py). With the
heads a dimension of their own (``[..., Hkv, Dh]``) the device tiles the
last two dimensions, pads 25 heads to 32 sublanes and 64 lanes to 128,
and the compiler stores the pool in a compact layout of its own with
the block index minor, which neither the Mosaic call nor the scatter
can use: every dispatch then re-laid every layer's pool out and back.
One row of ``Hkv*Dh`` lanes is row-major on the device as it is here,
so the entry parameter, the loop's state and this kernel's operand are
one layout and nothing copies the pool. ``N'`` is whatever the caller
stacked: the serving programs hand over all layers' pools as
``[L*N, ...]`` and address layer ``l`` by ``tables + l*N``.

The serving engine's gather path (`gather_pool_blocks` below)
materializes the WHOLE virtual cache ``[B, NB*block, Hkv, Dh]`` out of
the block pool every layer, every decoded token, then masks everything
past ``lengths``: per token that is O(S_max) HBM reads plus an
equal-size HBM write of the transient gathered copy, x2 (K, V) xL
layers — decode is gather-bound and the paged cache's memory win is
undone by a dense copy that exists only to feed two einsums.

This kernel attends THROUGH the block table instead (vLLM's
PagedAttention, Kwon et al. 2023, with FlashAttention-2's online
softmax, Dao 2023):

- block tables and per-slot lengths ride in as scalar-prefetch operands
  (``pltpu.PrefetchScalarGridSpec``), so the K/V BlockSpec index_map
  dereferences ``tables[b, j]`` BEFORE the grid step runs and each step
  DMAs exactly one pool block ``[block, Hkv*Dh]`` from HBM — no dense
  gather copy ever exists;
- grid ``(B, NB)`` with the KV (block) dimension innermost; fp32
  running max / sum / accumulator live in VMEM scratch across the
  sequential block iterations (the FA2 online softmax);
- ``pl.when`` skips blocks entirely past ``lengths[b]`` — and, with a
  sliding ``window``, blocks entirely below the band start — while the
  index_map CLAMPS skipped steps to the nearest in-band block so their
  index equals a neighbor step's and Mosaic elides the DMA (the same
  causal-clamp trick as ops/attention/flash.py): per-token HBM traffic
  is O(actual length), not O(S_max);
- GQA: the kv-head loop is unrolled IN the kernel body (Hkv is static
  and small), packing the ``group = H // Hkv`` query heads that share a
  kv head into one MXU matmul per head. Folding the head loop into the
  body — rather than a (B, Hkv, NB) grid — means one pool block fetch
  serves ALL kv heads: head ``h`` is the lane slice
  ``[h*Dh, (h+1)*Dh)`` of the block's rows (a per-head grid would
  re-DMA each block Hkv times);
- the final partial block is masked by position exactly like the gather
  path, so the two implementations are numerically interchangeable (the
  gather path stays the bit-reference, see docs/PARITY.md).

The gather path remains the reference implementation and the non-TPU
default. Interpret mode is the tests' business: they pass
``interpret=True`` or patch ``pl.pallas_call`` (tests/conftest.py
``pallas_interpret``); nothing on the serving path selects it, so the
kernel asked for off a TPU is an error, not an interpreted run.
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128


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


def paged_hbm_bytes_per_token(cfg, num_slots: int, mean_len: float,
                              max_len: int, dtype=jnp.bfloat16,
                              impl: str = "pallas",
                              block_size: Optional[int] = None,
                              scale_bytes_per_block: int = 0) -> int:
    """Analytic HBM bytes the attention cache path moves per decoded
    token (all layers, K+V) — the PERF.md comparison unit.

    gather: reads the whole ``[B, NB*block, ...]`` virtual cache out of
    the pool AND writes the transient gathered copy, then the einsums
    read the copy again — 3 passes over ``num_slots * max_len`` tokens.
    pallas: reads only the occupied blocks of each live slot, once.

    ``dtype`` must be the ACTUAL pool dtype (int8 under DS_KV_QUANT,
    bf16/f32 otherwise — the bench passes ``cache.pool_dtype``);
    ``scale_bytes_per_block`` + ``block_size`` fold the quantized pools'
    per-block fp32 scale overhead into the per-token cost."""
    per_tok = 2.0 * cfg.n_layers * cfg.kv_heads * cfg.head_dim \
        * jnp.dtype(dtype).itemsize
    if scale_bytes_per_block and block_size:
        # the scale pools are read alongside every block DMA
        per_tok += scale_bytes_per_block / float(block_size)
    if impl == "gather":
        return int(3 * num_slots * int(max_len) * per_tok)
    return int(int(num_slots * mean_len) * per_tok)


def _kv_index_map(bs: int, nb: int, window: Optional[int], q_len: int = 1,
                  per_slot: bool = False):
    """Block index map for the K/V pools when the grid is (b, j) and the
    pools are scalar-prefetch-addressed: step (b, j) fetches pool block
    ``tables[b, clamp(j)]``. Steps past the slot's last occupied block
    clamp DOWN to it, steps below the sliding-window band clamp UP to
    the band's first block — either way the skipped step's index equals
    a run step's (or its neighbor's), so Mosaic elides the DMA exactly
    like the causal clamp in ops/attention/flash.py. With a verify
    chunk (``q_len > 1``) the last query sits at ``lengths + q_len - 1``,
    so the high clamp covers that block too.

    The default addresses the K/V pools ``[N', block, Hkv*Dh]``;
    ``per_slot=True`` addresses the int8 mode's scales, already gathered
    through the tables into ``[B, NB, 1, Hkv]``, at ``[b, clamp(j)]``:
    the SAME clamp, so each grid step's scales ride the same prefetch
    discipline as its block."""
    def imap(b, j, tables_ref, lengths_ref):
        pos = lengths_ref[b]
        hi = jnp.minimum((pos + (q_len - 1)) // bs, nb - 1)
        jj = jnp.minimum(j, hi)
        if window is not None:
            lo = jnp.clip((pos - window + 1) // bs, 0, nb - 1)
            jj = jnp.maximum(jj, lo)
        if per_slot:
            return (b, jj, 0, 0)
        return (tables_ref[b, jj], 0, 0)

    return imap


def _paged_decode_kernel(tables_ref, lengths_ref, q_ref, k_ref, v_ref,
                         *rest, bs: int, n_kv: int, group: int, q_len: int,
                         scale: float, window: Optional[int], nb: int,
                         quant: bool = False):
    """One (slot, pool-block) grid step of flash-decode.

    q_ref: [1, H*q_len, Dh] (H = n_kv * group; rows ordered (kv head,
    group member, chunk offset) so each kv head's queries are one
    contiguous MXU matmul); k_ref / v_ref: [1, bs, Hkv*Dh] — ONE pool
    block, already table-indirected by the index_map, kv head h in
    lanes [h*Dh, (h+1)*Dh); scratch: running
    max / sum / fp32 accumulator per query row, persistent across the j
    (block) iterations of slot b. q_len == 1 is plain decode; q_len > 1
    is the speculative verify chunk — query row with chunk offset g is
    causal at position ``lengths[b] + g`` (within-chunk causality falls
    out of the same position mask, since the chunk's K/V are already
    scattered into the pool).

    ``quant=True``: k_ref/v_ref hold int8 and two extra refs
    ks_ref/vs_ref ([1, 1, 1, Hkv] fp32, this block's per-head scales)
    precede the output — each head's slice is dequantized IN-REGISTER
    right after the block's DMA (the ops/int8_matmul.py idiom), so HBM
    traffic stays the int8 payload + one scale vector per block. Head
    h's scale is the (1, 1) lane slice ``[:, h:h+1]``, broadcast over
    the slice: Mosaic has no scalar load from VMEM for a per-head
    ``ks_ref[..., h]``."""
    if quant:
        ks_ref, vs_ref, o_ref, m_scratch, l_scratch, acc_scratch = rest
    else:
        o_ref, m_scratch, l_scratch, acc_scratch = rest
    b = pl.program_id(0)
    j = pl.program_id(1)
    pos = lengths_ref[b]
    # last block any query in the chunk may touch
    hi = jnp.minimum((pos + (q_len - 1)) // bs, nb - 1)

    @pl.when(j == 0)
    def _init():
        m_scratch[:] = jnp.full_like(m_scratch, NEG_INF)
        l_scratch[:] = jnp.zeros_like(l_scratch)
        acc_scratch[:] = jnp.zeros_like(acc_scratch)

    run = j <= hi
    if window is not None:
        # band start of the FIRST query; later queries' bands begin
        # higher and are enforced per element below
        lo = jnp.clip((pos - window + 1) // bs, 0, nb - 1)
        run = jnp.logical_and(run, j >= lo)

    R = group * q_len                         # query rows per kv head

    @pl.when(run)
    def _body():
        q = q_ref[0]                          # [H*q_len, Dh]
        k = k_ref[0]                          # [bs, Hkv*Dh]
        v = v_ref[0]
        Dh = q.shape[-1]
        if quant:
            q = q.astype(jnp.float32)
            ks = ks_ref[0, 0]                 # [1, Hkv]
            vs = vs_ref[0, 0]
        # positions of this block's slots in the slot's virtual cache;
        # the final partial block masks by position exactly like the
        # gather path (idx <= pos + chunk offset, window band below it)
        cols = jax.lax.broadcasted_iota(jnp.int32, (R, bs), 1) + j * bs
        qpos = pos
        if q_len > 1:
            # row r of a kv-head slice is (group member r // q_len,
            # chunk offset r % q_len): each chunk query is causal at
            # its own position
            qpos = pos + jax.lax.broadcasted_iota(
                jnp.int32, (R, bs), 0) % q_len
        valid = cols <= qpos
        if window is not None:
            valid = jnp.logical_and(valid, cols > qpos - window)

        for h in range(n_kv):                 # static unroll: Hkv is small
            rows = slice(h * R, (h + 1) * R)
            qh = q[rows, :]                   # [R, Dh] — one MXU matmul
            kh = k[:, h * Dh:(h + 1) * Dh]    # [bs, Dh]     covers the whole
            vh = v[:, h * Dh:(h + 1) * Dh]    # GQA group of this kv head
            if quant:
                # in-register dequantize: int8 slice x its head's scale
                kh = kh.astype(jnp.float32) * ks[:, h:h + 1]
                vh = vh.astype(jnp.float32) * vs[:, h:h + 1]
            s = jax.lax.dot_general(
                qh, kh, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale   # [R, bs]
            s = jnp.where(valid, s, NEG_INF)

            m_prev = m_scratch[rows, :1]                     # [R, 1]
            m_cur = jnp.max(s, axis=-1, keepdims=True)
            m_new = jnp.maximum(m_prev, m_cur)
            p = jnp.exp(s - m_new)                           # [R, bs]
            alpha = jnp.exp(m_prev - m_new)
            l_new = alpha * l_scratch[rows, :1] \
                + jnp.sum(p, axis=-1, keepdims=True)
            acc_scratch[rows, :] = acc_scratch[rows, :] * alpha \
                + jax.lax.dot_general(
                    p.astype(vh.dtype), vh, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
            m_scratch[rows, :] = jnp.broadcast_to(
                m_new, (R, m_scratch.shape[1]))
            l_scratch[rows, :] = jnp.broadcast_to(
                l_new, (R, l_scratch.shape[1]))

    @pl.when(j == hi)
    def _finish():
        l = l_scratch[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scratch[:] / l_safe).astype(o_ref.dtype)


def paged_decode_attention(q: jnp.ndarray, k_pool: jnp.ndarray,
                           v_pool: jnp.ndarray, tables: jnp.ndarray,
                           lengths: jnp.ndarray, *, scale: float,
                           window: Optional[int] = None,
                           interpret: bool = False,
                           k_scale=None, v_scale=None) -> jnp.ndarray:
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
    in-register after each block DMA (DS_KV_QUANT=int8).

    Returns [B, Hkv, group, Dh] in q's dtype. ``interpret=True`` is
    for tests; the default compiles through Mosaic and fails off a TPU."""
    B, n_kv, group, Dh = q.shape
    return _paged_attention_call(
        q.reshape(B, n_kv * group, Dh), k_pool, v_pool, tables, lengths,
        n_kv=n_kv, group=group, q_len=1, scale=scale, window=window,
        interpret=interpret, k_scale=k_scale,
        v_scale=v_scale).reshape(B, n_kv, group, Dh)


def paged_verify_attention(q: jnp.ndarray, k_pool: jnp.ndarray,
                           v_pool: jnp.ndarray, tables: jnp.ndarray,
                           lengths: jnp.ndarray, *, scale: float,
                           window: Optional[int] = None,
                           interpret: bool = False,
                           k_scale=None, v_scale=None) -> jnp.ndarray:
    """Flash-verify a G-token speculative chunk per slot THROUGH the
    block table — the ``q_len > 1`` generalization of
    :func:`paged_decode_attention` for draft/verify serving.

    q: [B, G, Hkv, group, Dh] post-rotary chunk queries; the chunk's
    K/V must already be scattered into the pools at positions
    ``lengths[b] .. lengths[b] + G - 1`` (writes-before-attention, so
    within-chunk causality is just the position mask: chunk query i of
    slot b attends cache positions <= lengths[b] + i). Same grid and
    per-block DMA economics as decode — the chunk only widens the MXU
    matmul per fetched block, which is exactly why verify is nearly
    free on TPU. Returns [B, G, Hkv, group, Dh] in q's dtype."""
    B, G, n_kv, group, Dh = q.shape
    # head-major row packing (kv head, group member, chunk offset):
    # each kv head's group*G query rows stay one contiguous matmul
    q_rows = q.transpose(0, 2, 3, 1, 4).reshape(B, n_kv * group * G, Dh)
    out = _paged_attention_call(
        q_rows, k_pool, v_pool, tables, lengths, n_kv=n_kv, group=group,
        q_len=G, scale=scale, window=window, interpret=interpret,
        k_scale=k_scale, v_scale=v_scale)
    return out.reshape(B, n_kv, group, G, Dh).transpose(0, 3, 1, 2, 4)


def _paged_attention_call(q_rows, k_pool, v_pool, tables, lengths, *,
                          n_kv: int, group: int, q_len: int, scale: float,
                          window: Optional[int], interpret: bool,
                          k_scale=None, v_scale=None) -> jnp.ndarray:
    """Shared pallas_call plumbing for decode (q_len=1) and verify
    (q_len=G). q_rows: [B, n_kv*group*q_len, Dh], head-major rows;
    pools [N', block, Hkv*Dh]. ``k_scale``/``v_scale`` ([N', Hkv] fp32)
    switch the int8 dequantize-in-kernel mode on (pools must then be
    int8)."""
    B, rows, Dh = q_rows.shape
    N, bs, row = k_pool.shape
    assert (row, rows) == (n_kv * Dh, n_kv * group * q_len), \
        (q_rows.shape, k_pool.shape, (n_kv, group, q_len))
    assert v_pool.shape == k_pool.shape, (v_pool.shape, k_pool.shape)
    quant = k_scale is not None
    tables = jnp.asarray(tables, jnp.int32)
    nb = tables.shape[1]

    kvmap = _kv_index_map(bs, nb, window, q_len)

    def qmap(b, j, tables_ref, lengths_ref):
        return (b, 0, 0)

    in_specs = [
        pl.BlockSpec((1, rows, Dh), qmap),
        pl.BlockSpec((1, bs, row), kvmap),
        pl.BlockSpec((1, bs, row), kvmap),
    ]
    operands = [q_rows, k_pool, v_pool]
    if quant:
        # the scales of each slot's blocks, gathered through the tables
        # out here (B*NB*Hkv floats): the kernel then needs no view of
        # the scale pool in a layout of its own. They ride as
        # [B, NB, 1, Hkv] because a block's last two dimensions must
        # divide by (8, 128) or equal the array's
        smap = _kv_index_map(bs, nb, window, q_len, per_slot=True)
        in_specs += [pl.BlockSpec((1, 1, 1, n_kv), smap),
                     pl.BlockSpec((1, 1, 1, n_kv), smap)]
        operands += [k_scale[tables][:, :, None, :],
                     v_scale[tables][:, :, None, :]]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, nb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, rows, Dh), qmap),
        scratch_shapes=[
            pltpu.VMEM((rows, LANES), jnp.float32),
            pltpu.VMEM((rows, LANES), jnp.float32),
            pltpu.VMEM((rows, Dh), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _paged_decode_kernel, bs=bs, n_kv=n_kv, group=group, q_len=q_len,
        scale=float(scale), window=window, nb=nb, quant=quant)
    return pl.pallas_call(
        kernel,
        name="paged_decode" if q_len == 1 else "paged_verify",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, rows, Dh), q_rows.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        # passed only when a test asks: the conftest fixture's patched
        # pallas_call keeps its own interpret=True
        **({"interpret": True} if interpret else {}),
    )(tables, jnp.asarray(lengths, jnp.int32), *operands)


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
