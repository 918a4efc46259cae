"""Ring attention — sequence/context parallelism over ICI, flash-grade.

The reference version has NO sequence parallelism (SURVEY §2.2: absent at
v0.6.4; its long-sequence story is block-sparse attention). This module is
the modern TPU-native equivalent capability called for by BASELINE.md's
north star: exact attention over sequences sharded across chips.

Design (Ring Attention / blockwise attention):
- the sequence dim of Q, K, V is sharded over the 'sequence' mesh axis;
- each device computes attention of its local Q block against the K/V
  block it currently holds; per-block results carry their logsumexp and
  are combined across ring steps with an online softmax — exactly the
  flash-attention recurrence lifted one level up;
- K/V blocks rotate around the ring via `lax.ppermute` each step, so after
  n_seq steps every Q block has seen every K/V block; the rotation
  overlaps with compute via XLA's latency-hiding scheduler;
- the LOCAL block computation is the Pallas flash kernel
  (ops/attention/flash.py `flash_block_fwd_t/bwd_t`, kernel layout held
  across the whole loop so q/do/o are padded+transposed once, not per
  step) on TPU, and a chunked online-softmax in plain jnp elsewhere —
  peak local memory is O(S_loc · block), never the O(S_loc²) dense score
  matrix;
- the ring loop is UNROLLED (the ring size is static), so each step's
  mask geometry is static too: step 0 is ordinary causal attention,
  step i ≥ 1 sees a K/V block exactly i·S_loc tokens behind its queries
  — causality is automatic there, and a sliding window becomes a band
  at a static offset the kernel's index maps can elide DMAs for.
  Steps whose band is statically empty are dropped entirely, so causal
  sliding-window ring attention does ceil((w+S_loc-1)/S_loc) hops, not
  n_seq;
- a module-level `jax.custom_vjp` replays the rotation schedule in the
  backward pass (dk/dv accumulators travel WITH their K/V block and are
  delivered home over whichever direction is fewer hops), so reverse-mode
  never materializes per-step dense residuals from scan transposition;
- causal masking uses global token positions, so the result is exactly
  standard causal attention; per-token metadata (packed segment ids /
  key-validity) ROTATES with its K/V block, so packing and padding masks
  are exact under the ring.

Contract for degenerate rows: a row with NO valid visible key anywhere
returns exact 0 (the dense single-chip path returns a uniform average of
v instead — both are garbage-by-contract; any masked loss zeroes their
gradient).

Zigzag layout (``layout="zigzag"``): causal ring attention on a
contiguous layout is imbalanced — device d's queries can see d+1 of the
n K/V blocks, so the last device does ~2x the work of the average and
sets the wall clock. The zigzag layout splits the sequence into 2n
chunks and gives device d chunks (d, 2n-1-d) — every device then holds
exactly one "early" and one "late" chunk and does the SAME work at
every ring step:
- step 0 (self): the local shard [lo, hi] is globally monotone and its
  chunk boundaries align, so a plain LOCAL causal mask is exactly the
  global causal mask restricted to this block;
- step i>0 against the block from device src=(idx-i) mod n: if
  src < idx both local chunks see src's LOW chunk fully (its high chunk
  is entirely in their future); if src > idx the local HIGH chunk sees
  both of src's chunks fully (the low chunk sees neither). Either way
  the step computes exactly half the full-block work, mask-free.
Tokens must be pre-permuted with :func:`zigzag_perm` (and positions /
targets / segment metadata with them) — the model's per-token compute
is permutation-invariant, so only the data layout changes.
Sliding windows are not supported under zigzag (the band geometry is no
longer a static per-step offset); use the contiguous layout there.
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from deepspeed_tpu.ops.attention.flash import (
    NEG_INF, _pad_heads, flash_block_bwd_t, flash_block_fwd_t,
    refuse_partial_manual)


def _largest_divisor(n: int, cap: int) -> int:
    """Largest divisor of ``n`` that is <= cap (cap >= 1)."""
    for c in range(min(cap, n), 0, -1):
        if n % c == 0:
            return c
    return 1


def zigzag_perm(S: int, n: int) -> np.ndarray:
    """Token permutation for the zigzag ring layout: split the sequence
    into 2n chunks; device d's shard is [chunk d, chunk 2n-1-d]. Apply to
    tokens/targets/positions/segment metadata on the HOST (``x[:, p]``)
    before sharding the sequence dim contiguously over the ring axis."""
    assert S % (2 * n) == 0, (S, n)
    C = S // (2 * n)
    out = np.empty(S, np.int64)
    for d in range(n):
        base = d * 2 * C
        out[base:base + C] = np.arange(d * C, (d + 1) * C)
        out[base + C:base + 2 * C] = np.arange((2 * n - 1 - d) * C,
                                               (2 * n - d) * C)
    return out


def zigzag_unperm(S: int, n: int) -> np.ndarray:
    """Inverse of :func:`zigzag_perm` (restore global order)."""
    return np.argsort(zigzag_perm(S, n))


def _seq_slice(x, a, b, axis):
    """x[..., a:b, ...] along ``axis`` (static bounds)."""
    return None if x is None else jax.lax.slice_in_dim(x, a, b, axis=axis)


def _num_steps(n: int, S_loc: int, causal: bool, window) -> int:
    """Ring hops that can ever intersect the attention band. For causal
    sliding-window attention, block i's closest key is i*S_loc - (S_loc-1)
    tokens behind the query — once that is >= window the step is dead for
    EVERY device and the rotation chain stops early."""
    if causal and window is not None:
        return min(n, -(-(window + S_loc - 1) // S_loc))
    return n


def _step_cfg(i: int, S_loc: int, causal: bool, window):
    """Static mask geometry of ring step i: (causal, q_off, window) for
    the local block call. Step 0 is self-attention; step i >= 1 sees keys
    exactly i*S_loc tokens behind every query, so causality is automatic
    (mask-free) unless a sliding window cuts a band through the block."""
    if not causal:
        return False, 0, None
    if i == 0:
        return True, 0, window
    off = i * S_loc
    if window is None or off + S_loc - 1 < window:
        return False, 0, None       # fully in band: no masking at all
    return True, off, window


# ---------------------------------------------------------------------------
# local block compute (jnp fallback: chunked online softmax)
# ---------------------------------------------------------------------------

def _mask_scores(s, rows, cols, blk_causal, window, qsegs, ksegs, kvm):
    """Apply causal/window/segment/validity masks to [B, H, Sq, c]."""
    if blk_causal:
        m = rows[None, None, :, None] >= cols[None, None, None, :]
        if window is not None:
            m = jnp.logical_and(
                m, rows[None, None, :, None] - cols[None, None, None, :]
                < window)
        s = jnp.where(m, s, NEG_INF)
    if qsegs is not None:
        same = qsegs[:, None, :, None] == ksegs[:, None, None, :]
        s = jnp.where(same, s, NEG_INF)
    if kvm is not None:
        s = jnp.where(kvm[:, None, None, :] > 0, s, NEG_INF)
    return s


def _chunk_scores(qf, k, v, qsegs, ksegs, kvm, j, c, *, rows, group,
                  blk_causal, window, scale):
    """Shared fwd/bwd chunk prologue: slice chunk j of the held K/V block
    (+ its rotated metadata), repeat GQA groups, compute masked scores.
    The q-position offset is already baked into ``rows`` by the caller.
    Returns (s [B,H,Sq,c] fp32, kj, vj [B,c,H,D])."""
    kj = jax.lax.dynamic_slice_in_dim(k, j * c, c, axis=1)
    vj = jax.lax.dynamic_slice_in_dim(v, j * c, c, axis=1)
    if group > 1:
        kj = jnp.repeat(kj, group, axis=2)
        vj = jnp.repeat(vj, group, axis=2)
    ksj = (None if ksegs is None else
           jax.lax.dynamic_slice_in_dim(ksegs, j * c, c, axis=1))
    kvj = (None if kvm is None else
           jax.lax.dynamic_slice_in_dim(kvm, j * c, c, axis=1))
    s = jnp.einsum("bqhd,bkhd->bhqk", qf, kj.astype(jnp.float32)) * scale
    cols = j * c + jnp.arange(c, dtype=jnp.int32)
    s = _mask_scores(s, rows, cols, blk_causal, window, qsegs, ksj, kvj)
    return s, kj, vj


def _jnp_block_fwd(q, k, v, qsegs, ksegs, kvm, *, blk_causal, window,
                   q_off, scale, chunk):
    """Chunked online-softmax attention of local q [B,S,H,D] against one
    K/V block. Peak memory O(B·H·S·chunk) instead of the dense
    O(B·H·S·S_kv). Returns (o [B,H,S,D] in q.dtype, lse [B,H,S] fp32)."""
    B, S, H, D = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    group = H // Hkv
    c = _largest_divisor(Skv, chunk)
    nc = Skv // c
    qf = q.astype(jnp.float32)
    rows = q_off + jnp.arange(S, dtype=jnp.int32)
    prolog = functools.partial(
        _chunk_scores, qf, k, v, qsegs, ksegs, kvm, c=c, rows=rows,
        group=group, blk_causal=blk_causal, window=window, scale=scale)

    def step(carry, j):
        m, l, acc = carry
        s, _, vj = prolog(j=j)
        m_cur = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m, m_cur)
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p, vj.astype(jnp.float32))
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, H, S), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, H, S), jnp.float32)
    acc0 = jnp.zeros((B, H, S, D), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(step, (m0, l0, acc0),
                                  jnp.arange(nc, dtype=jnp.int32))
    l_safe = jnp.where(l == 0.0, 1.0, l)
    o = (acc / l_safe[..., None]).astype(q.dtype)          # [B,H,S,D]
    lse = m + jnp.log(l_safe)
    return o, lse


def _jnp_block_bwd(q, k, v, do, lse, delta, qsegs, ksegs, kvm, *,
                   blk_causal, window, q_off, scale, chunk):
    """This block's additive (dq, dk, dv) contribution given the GLOBAL
    lse [B,H,S] and delta [B,H,S] (= rowsum(do*o)). Chunked like the
    forward. Returns fp32 (dq [B,H,S,D], dk/dv [B,Hkv,Skv,D])."""
    B, S, H, D = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    group = H // Hkv
    c = _largest_divisor(Skv, chunk)
    nc = Skv // c
    qf = q.astype(jnp.float32)
    dof = do.astype(jnp.float32)
    rows = q_off + jnp.arange(S, dtype=jnp.int32)
    prolog = functools.partial(
        _chunk_scores, qf, k, v, qsegs, ksegs, kvm, c=c, rows=rows,
        group=group, blk_causal=blk_causal, window=window, scale=scale)

    def step(dq_acc, j):
        s, kj, vj = prolog(j=j)
        p = jnp.exp(s - lse[..., None])                    # [B,H,S,c]
        dv_j = jnp.einsum("bhqk,bqhd->bhkd", p, dof)       # [B,H,c,D]
        dp = jnp.einsum("bqhd,bkhd->bhqk", dof, vj.astype(jnp.float32))
        ds = p * (dp - delta[..., None]) * scale
        dq_acc = dq_acc + jnp.einsum("bhqk,bkhd->bhqd", ds,
                                     kj.astype(jnp.float32))
        dk_j = jnp.einsum("bhqk,bqhd->bhkd", ds, qf)       # [B,H,c,D]
        if group > 1:
            dk_j = dk_j.reshape(B, Hkv, group, c, D).sum(2)
            dv_j = dv_j.reshape(B, Hkv, group, c, D).sum(2)
        return dq_acc, (dk_j, dv_j)

    dq0 = jnp.zeros((B, H, S, D), jnp.float32)
    dq, (dks, dvs) = jax.lax.scan(step, dq0,
                                  jnp.arange(nc, dtype=jnp.int32))
    dk = jnp.moveaxis(dks, 0, 2).reshape(B, Hkv, Skv, D)
    dv = jnp.moveaxis(dvs, 0, 2).reshape(B, Hkv, Skv, D)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# ring core (inside shard_map) with custom VJP
# ---------------------------------------------------------------------------

def _rotate(xs, axis, perm):
    return [None if x is None else jax.lax.ppermute(x, axis, perm)
            for x in xs]


def _ring_fwd_inner(q, k, v, segs, kvm, axis, causal, scale, window,
                    use_flash, block_q, block_kv, chunk, layout):
    n = jax.lax.axis_size(axis)
    idx = jax.lax.axis_index(axis)
    B, S_loc, H, D = q.shape
    zig = layout == "zigzag"
    steps = n if zig else _num_steps(n, S_loc, causal, window)
    C = S_loc // 2                           # zigzag half-block
    perm = [(j, (j + 1) % n) for j in range(n)]

    if use_flash:
        # kernel layout once for the whole loop: [B, H, S, Dp] with the
        # head dim sublane-padded — the K/V carry rotates transposed too
        qp, kp, vp, D0, Dp = _pad_heads(q, k, v)
        q_use = qp.transpose(0, 2, 1, 3)
        k_cur = kp.transpose(0, 2, 1, 3)
        v_cur = vp.transpose(0, 2, 1, 3)
        seq_ax = 2                           # seq axis of q/k/v operands
    else:
        q_use, k_cur, v_cur, D0, Dp = q, k, v, D, D
        seq_ax = 1
    segs_cur, kvm_cur = segs, kvm

    def fwd_block(q_c, k_c, v_c, qsg, sg, km, bc, off, w):
        """One local attention block in the current operand layout.
        Returns (o [B,H,Sq,Dp], lse [B,H,Sq])."""
        if use_flash:
            return flash_block_fwd_t(
                q_c, k_c, v_c, kv_mask=km, q_segs=qsg, kv_segs=sg,
                causal=bc, scale=scale, block_q=block_q,
                block_kv=block_kv, window=w, q_off=off)
        return _jnp_block_fwd(q_c, k_c, v_c, qsg, sg, km,
                              blk_causal=bc, window=w, q_off=off,
                              scale=scale, chunk=chunk)

    m = jnp.full((B, H, S_loc), NEG_INF, jnp.float32)
    l = jnp.zeros((B, H, S_loc), jnp.float32)
    acc = jnp.zeros((B, H, S_loc, Dp), jnp.float32)

    for i in range(steps):
        if zig and i > 0:
            # balanced zigzag step: src's block is either entirely
            # visible to-the-low-chunk-level (src < idx: its low chunk
            # is past for BOTH local chunks, its high chunk future for
            # both) or visible only to the local high chunk (src > idx:
            # both its chunks are past for the high chunk, future for
            # the low). Both branches are mask-free half-block work.
            def br_lo(k_c=k_cur, v_c=v_cur, sg=segs_cur, km=kvm_cur):
                o, lse = fwd_block(
                    q_use, _seq_slice(k_c, 0, C, seq_ax),
                    _seq_slice(v_c, 0, C, seq_ax), segs,
                    _seq_slice(sg, 0, C, 1), _seq_slice(km, 0, C, 1),
                    False, 0, None)
                return o, lse

            def br_hi(k_c=k_cur, v_c=v_cur, sg=segs_cur, km=kvm_cur):
                o_hi, lse_hi = fwd_block(
                    _seq_slice(q_use, C, S_loc, 2 if use_flash else 1),
                    k_c, v_c, _seq_slice(segs, C, S_loc, 1), sg, km,
                    False, 0, None)
                pad_o = jnp.zeros((B, H, C, Dp), o_hi.dtype)
                pad_l = jnp.full((B, H, C), NEG_INF, jnp.float32)
                return (jnp.concatenate([pad_o, o_hi], axis=2),
                        jnp.concatenate([pad_l, lse_hi], axis=2))

            src = jax.lax.rem(idx - i + n, n)
            o_i, lse_i = jax.lax.cond(src < idx, br_lo, br_hi)
            o_i = o_i.astype(q.dtype)
        else:
            blk_causal, q_off, blk_window = (
                (causal, 0, window) if zig
                else _step_cfg(i, S_loc, causal, window))

            def compute(k_c=k_cur, v_c=v_cur, sg=segs_cur, km=kvm_cur,
                        bc=blk_causal, off=q_off, w=blk_window):
                return fwd_block(q_use, k_c, v_c, segs, sg, km, bc, off,
                                 w)

            if causal and i > 0:
                # contiguous layout: devices "above" this step's source
                # never see it (the block is entirely in their future) —
                # skip the compute, not just the result. No collectives
                # inside, so a device-varying branch is fine under
                # shard_map.
                o_i, lse_i = jax.lax.cond(
                    idx >= i, compute,
                    lambda: (jnp.zeros((B, H, S_loc, Dp), q.dtype),
                             jnp.full((B, H, S_loc), NEG_INF,
                                      jnp.float32)))
            else:
                o_i, lse_i = compute()

        m_new = jnp.maximum(m, lse_i)
        alpha = jnp.exp(m - m_new)
        # a block where a row has NO valid key reports lse == NEG_INF and
        # a garbage o (uniform over its local keys, the dense-softmax
        # degenerate form) — gate its mass to zero so rows with no valid
        # visible key anywhere come out as exact 0 (see module contract)
        coef = jnp.where(lse_i > NEG_INF / 2, jnp.exp(lse_i - m_new), 0.0)
        l = l * alpha + coef
        acc = acc * alpha[..., None] + coef[..., None] * \
            o_i.astype(jnp.float32)
        m = m_new

        if i < steps - 1:
            k_cur, v_cur, segs_cur, kvm_cur = _rotate(
                [k_cur, v_cur, segs_cur, kvm_cur], axis, perm)

    l_safe = jnp.where(l == 0.0, 1.0, l)
    out = (acc / l_safe[..., None]).transpose(0, 2, 1, 3)[..., :D0]
    lse = m + jnp.log(l_safe)
    return out.astype(q.dtype), lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10,
                                                    11, 12, 13))
def _ring_core(q, k, v, segs, kvm, axis, causal, scale, window, use_flash,
               block_q, block_kv, chunk, layout):
    out, _ = _ring_fwd_inner(q, k, v, segs, kvm, axis, causal, scale,
                             window, use_flash, block_q, block_kv, chunk,
                             layout)
    return out


def _ring_core_fwd(q, k, v, segs, kvm, axis, causal, scale, window,
                   use_flash, block_q, block_kv, chunk, layout):
    out, lse = _ring_fwd_inner(q, k, v, segs, kvm, axis, causal, scale,
                               window, use_flash, block_q, block_kv,
                               chunk, layout)
    return out, (q, k, v, segs, kvm, out, lse)


def _ring_core_bwd(axis, causal, scale, window, use_flash, block_q,
                   block_kv, chunk, layout, res, g):
    q, k, v, segs, kvm, o, lse = res
    do = g
    n = jax.lax.axis_size(axis)
    idx = jax.lax.axis_index(axis)
    B, S_loc, H, D = q.shape
    Hkv = k.shape[2]
    zig = layout == "zigzag"
    steps = n if zig else _num_steps(n, S_loc, causal, window)
    C = S_loc // 2
    perm = [(j, (j + 1) % n) for j in range(n)]

    # global per-row delta = rowsum(do * o) — shared by every block's
    # recompute (FA2 backward identity); computed ONCE, like the layout
    # change below (both are step-invariant)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1).transpose(0, 2, 1)            # [B, H, S_loc]

    if use_flash:
        qp, kp, vp, D0, Dp = _pad_heads(q, k, v)
        dop = _pad_heads(do, do, do)[0]
        q_use = qp.transpose(0, 2, 1, 3)
        k_cur = kp.transpose(0, 2, 1, 3)
        v_cur = vp.transpose(0, 2, 1, 3)
        do_use = dop.transpose(0, 2, 1, 3)
        seq_ax = 2
    else:
        q_use, k_cur, v_cur, do_use = q, k, v, do
        D0, Dp = D, D
        seq_ax = 1
    segs_cur, kvm_cur = segs, kvm

    def bwd_block(q_c, do_c, lse_c, delta_c, k_c, v_c, qsg, sg, km, bc,
                  off, w):
        """One local backward block in the current operand layout.
        Returns fp32 (dq [B,H,Sq,Dp], dk/dv [B,Hkv,Skv,Dp])."""
        if use_flash:
            dq_i, dk_i, dv_i = flash_block_bwd_t(
                q_c, k_c, v_c, do_c, lse_c, kv_mask=km, q_segs=qsg,
                kv_segs=sg, causal=bc, scale=scale, block_q=block_q,
                block_kv=block_kv, window=w, q_off=off, delta=delta_c)
        else:
            dq_i, dk_i, dv_i = _jnp_block_bwd(
                q_c, k_c, v_c, do_c, lse_c, delta_c, qsg, sg, km,
                blk_causal=bc, window=w, q_off=off, scale=scale,
                chunk=chunk)
        return (dq_i.astype(jnp.float32), dk_i.astype(jnp.float32),
                dv_i.astype(jnp.float32))

    dq = jnp.zeros((B, H, S_loc, Dp), jnp.float32)
    dk_acc = jnp.zeros((B, Hkv, S_loc, Dp), jnp.float32)
    dv_acc = jnp.zeros((B, Hkv, S_loc, Dp), jnp.float32)

    for i in range(steps):
        if zig and i > 0:
            # mirror of the forward's balanced branches (see
            # _ring_fwd_inner): src < idx -> all q rows vs src's low
            # chunk (grads land in the accumulator's low half);
            # src > idx -> local high q rows vs src's full block.
            def br_lo(k_c=k_cur, v_c=v_cur, sg=segs_cur, km=kvm_cur):
                dq_i, dk_lo, dv_lo = bwd_block(
                    q_use, do_use, lse, delta,
                    _seq_slice(k_c, 0, C, seq_ax),
                    _seq_slice(v_c, 0, C, seq_ax), segs,
                    _seq_slice(sg, 0, C, 1), _seq_slice(km, 0, C, 1),
                    False, 0, None)
                pad = jnp.zeros((B, Hkv, C, Dp), jnp.float32)
                return (dq_i, jnp.concatenate([dk_lo, pad], axis=2),
                        jnp.concatenate([dv_lo, pad], axis=2))

            def br_hi(k_c=k_cur, v_c=v_cur, sg=segs_cur, km=kvm_cur):
                dq_hi, dk_i, dv_i = bwd_block(
                    _seq_slice(q_use, C, S_loc, seq_ax),
                    _seq_slice(do_use, C, S_loc, seq_ax),
                    _seq_slice(lse, C, S_loc, 2),
                    _seq_slice(delta, C, S_loc, 2),
                    k_c, v_c, _seq_slice(segs, C, S_loc, 1), sg, km,
                    False, 0, None)
                pad = jnp.zeros((B, H, C, Dp), jnp.float32)
                return (jnp.concatenate([pad, dq_hi], axis=2), dk_i,
                        dv_i)

            src = jax.lax.rem(idx - i + n, n)
            dq_i, dk_i, dv_i = jax.lax.cond(src < idx, br_lo, br_hi)
        else:
            blk_causal, q_off, blk_window = (
                (causal, 0, window) if zig
                else _step_cfg(i, S_loc, causal, window))

            def compute(k_c=k_cur, v_c=v_cur, sg=segs_cur, km=kvm_cur,
                        bc=blk_causal, off=q_off, w=blk_window):
                return bwd_block(q_use, do_use, lse, delta, k_c, v_c,
                                 segs, sg, km, bc, off, w)

            if causal and i > 0:
                dq_i, dk_i, dv_i = jax.lax.cond(
                    idx >= i, compute,
                    lambda: (jnp.zeros((B, H, S_loc, Dp), jnp.float32),
                             jnp.zeros((B, Hkv, S_loc, Dp), jnp.float32),
                             jnp.zeros((B, Hkv, S_loc, Dp),
                                       jnp.float32)))
            else:
                dq_i, dk_i, dv_i = compute()

        dq = dq + dq_i
        dk_acc = dk_acc + dk_i
        dv_acc = dv_acc + dv_i

        if i < steps - 1:
            k_cur, v_cur, segs_cur, kvm_cur, dk_acc, dv_acc = _rotate(
                [k_cur, v_cur, segs_cur, kvm_cur, dk_acc, dv_acc],
                axis, perm)

    # deliver each K/V block's grad accumulator back to its origin: block
    # b sits at device (b + steps - 1) % n now — go forward the rest of
    # the way around, or retrace backwards, whichever is fewer hops
    fwd_hops = (n - steps + 1) % n
    bwd_hops = steps - 1
    if fwd_hops <= bwd_hops:
        for _ in range(fwd_hops):
            dk_acc, dv_acc = _rotate([dk_acc, dv_acc], axis, perm)
    else:
        inv = [(j, (j - 1) % n) for j in range(n)]
        for _ in range(bwd_hops):
            dk_acc, dv_acc = _rotate([dk_acc, dv_acc], axis, inv)

    dq_out = dq.transpose(0, 2, 1, 3)[..., :D0].astype(q.dtype)
    dk_out = dk_acc.transpose(0, 2, 1, 3)[..., :D0].astype(k.dtype)
    dv_out = dv_acc.transpose(0, 2, 1, 3)[..., :D0].astype(v.dtype)
    return dq_out, dk_out, dv_out, None, None


_ring_core.defvjp(_ring_core_fwd, _ring_core_bwd)


# ---------------------------------------------------------------------------
# public op
# ---------------------------------------------------------------------------

def ring_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                   mesh: Mesh, *, causal: bool = True,
                   scale: Optional[float] = None,
                   axis: str = "sequence",
                   segment_ids: Optional[jnp.ndarray] = None,
                   kv_mask: Optional[jnp.ndarray] = None,
                   window: Optional[int] = None,
                   use_flash: Optional[bool] = None,
                   block_q: int = 512, block_kv: int = 512,
                   chunk: int = 1024,
                   layout: str = "contiguous") -> jnp.ndarray:
    """Exact (causal) attention with the sequence dim sharded over ``axis``.

    q,k,v: [B, S, H, D] global arrays whose S dim is (or will be) sharded
    over the 'sequence' mesh axis. Batch/head dims stay auto-sharded.
    k/v may carry fewer heads (GQA) — the SMALL grouped k/v rotate around
    the ring (the ICI-traffic win scales with the group factor).

    segment_ids/kv_mask: [B, S] packed-sequence ids / key-validity —
    sharded like the tokens; each shard's slice rotates around the ring
    with its K/V block, so packing/padding masks are exact. window:
    sliding-window causal attention — ring steps whose band is
    statically empty are dropped, so the rotation does
    ceil((window + S_loc - 1)/S_loc) hops instead of n_seq.

    The local block runs the Pallas flash kernel on TPU (``use_flash``
    defaults to auto-detect; ``block_q``/``block_kv`` are clamped to
    divisors of the local shard) and a chunked online-softmax in plain
    jnp elsewhere (``chunk`` keys at a time) — peak local memory is
    O(S_loc · block), not O(S_loc²). Backward runs through a ring-level
    custom VJP that replays the rotation (no dense per-step residuals).

    layout: "contiguous" (default) shards the sequence in order;
    "zigzag" expects tokens pre-permuted with :func:`zigzag_perm` and
    balances the causal triangle so every device does equal work at
    every ring step (~2x faster at large ring sizes; see module
    docstring). Causal-only, no sliding window.
    """
    if scale is None:
        scale = 1.0 / np.sqrt(q.shape[-1])
    if window is not None:
        assert causal, "sliding window requires causal attention"
    if layout not in ("contiguous", "zigzag"):
        raise ValueError(f"unknown ring layout {layout!r}")
    if layout == "zigzag":
        if not causal or window is not None:
            raise ValueError(
                "zigzag layout balances the CAUSAL triangle; use the "
                "contiguous layout for non-causal or windowed attention")
    H, Hkv = q.shape[2], k.shape[2]
    assert H % Hkv == 0, f"q heads {H} not a multiple of kv heads {Hkv}"
    assert v.shape[2] == Hkv, \
        f"k has {Hkv} heads but v has {v.shape[2]} — kv head counts must match"
    n_seq = mesh.shape[axis]
    S = q.shape[1]
    assert S % n_seq == 0, (S, n_seq)
    S_loc = S // n_seq
    if layout == "zigzag":
        assert S_loc % 2 == 0, \
            f"zigzag needs an even local shard, got S_loc={S_loc}"
    if use_flash is None:
        from deepspeed_tpu.utils import on_tpu
        use_flash = on_tpu() and S_loc >= 128
    if use_flash:
        refuse_partial_manual(mesh, axis, "ring_attention")
    # zigzag steps run on half blocks — tiles must divide C as well
    blk_unit = S_loc // 2 if layout == "zigzag" else S_loc
    block_q = _largest_divisor(blk_unit, min(block_q, blk_unit))
    block_kv = _largest_divisor(blk_unit, min(block_kv, blk_unit))
    if segment_ids is not None:
        segment_ids = segment_ids.astype(jnp.int32)
    if kv_mask is not None:
        kv_mask = kv_mask.astype(jnp.float32)

    def inner(q, k, v, segs, kvm):
        return _ring_core(q, k, v, segs, kvm, axis, causal, scale, window,
                          use_flash, block_q, block_kv, chunk, layout)

    spec = P(None, axis, None, None)
    tok_spec = P(None, axis)
    args = [q, k, v, segment_ids, kv_mask]
    in_specs = [spec, spec, spec,
                None if segment_ids is None else tok_spec,
                None if kv_mask is None else tok_spec]
    mapped = jax.shard_map(
        inner, mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=spec,
        axis_names={axis},
        check_vma=False)
    # partial-manual shard_map mis-canonicalizes out_specs when traced
    # eagerly in this jax version; under jit it is correct — force it.
    return jax.jit(mapped)(*args)
