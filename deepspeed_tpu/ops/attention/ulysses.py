"""Ulysses-style all-to-all sequence parallelism.

The reference version has NO sequence parallelism (SURVEY §2.2 — absent at
v0.6.4; DeepSpeed-Ulysses is the lineage's later answer). This is the
TPU-native equivalent: where ring attention (ops/attention/ring.py)
rotates K/V blocks around the ICI ring, Ulysses re-shards with two
all-to-alls so every device runs a FULL-sequence attention over a slice
of the heads:

- activations arrive sharded on the sequence dim: [B, S/sp, H, D];
- all-to-all #1 swaps the shard dim: seq -> heads, giving every device
  the whole sequence for H/sp heads;
- local attention (the Pallas flash kernel when eligible — full sequence
  locally means the fused kernel applies unchanged);
- all-to-all #2 swaps back: heads -> seq.

Trade-off vs ring: 2 all-to-alls of activation size per attention call
(O(B·S·d/sp) bytes each, constant in sp) instead of sp ppermute hops of
K/V; attention compute is perfectly balanced even for causal masks
(ring's lower-triangle causes stage imbalance), and the unmodified
single-device kernel runs inside. Requires the sp degree to divide the
head count — for GQA, BOTH head counts (the local kernel keeps the
global q/kv group ratio).
"""

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P


def _ulysses_local(q, k, v, segs, mask, *, axis: str, causal: bool,
                   scale: float, use_flash: bool, block_q: int,
                   block_kv: int, window: Optional[int],
                   bwd_block_q: Optional[int], bwd_block_kv: Optional[int]):
    """Inside shard_map: q local [B, S_loc, H, D]; k/v may carry Hkv < H
    heads (GQA) -> out [B, S_loc, H, D]. segs/mask: [B, S_loc] or None."""
    sp = jax.lax.axis_size(axis)
    B, S_loc, H, D = q.shape
    Hkv = k.shape[2]
    assert H % sp == 0, f"n_heads {H} not divisible by sp degree {sp}"
    assert Hkv % sp == 0, \
        f"kv heads {Hkv} not divisible by sp degree {sp} (GQA + Ulysses " \
        "needs both head counts divisible)"

    # seq-sharded -> head-sharded: [B, S_loc, H, D] -> [B, S, H/sp, D]
    def seq2head(x):
        return jax.lax.all_to_all(x, axis, split_axis=2, concat_axis=1,
                                  tiled=True)

    def head2seq(x):
        return jax.lax.all_to_all(x, axis, split_axis=1, concat_axis=2,
                                  tiled=True)

    qh, kh, vh = seq2head(q), seq2head(k), seq2head(v)
    # per-token metadata (packed segment ids, kv validity) must cover the
    # FULL sequence the local kernel now sees — an all-gather of [B, S]
    # ints is noise next to the qkv all-to-alls (reference capability
    # analog: block-sparse long-seq, ref ops/sparse_attention/matmul.py)
    full_segs = (None if segs is None else
                 jax.lax.all_gather(segs, axis, axis=1, tiled=True))
    full_mask = (None if mask is None else
                 jax.lax.all_gather(mask, axis, axis=1, tiled=True))

    if use_flash:
        from deepspeed_tpu.ops.attention.flash import flash_attention
        out = flash_attention(qh, kh, vh, causal=causal, scale=scale,
                              block_q=block_q, block_kv=block_kv,
                              segment_ids=full_segs, kv_mask=full_mask,
                              window=window,
                              bwd_block_q=bwd_block_q,
                              bwd_block_kv=bwd_block_kv)
    else:
        from deepspeed_tpu.ops.attention.flash import mha_reference
        out = mha_reference(qh, kh, vh, causal=causal, scale=scale,
                            segment_ids=full_segs, kv_mask=full_mask,
                            window=window)

    return head2seq(out)


def ulysses_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                      mesh: Mesh, *, causal: bool = True,
                      scale: Optional[float] = None,
                      axis: str = "sequence",
                      use_flash: bool = False,
                      block_q: int = 512,
                      block_kv: int = 512,
                      segment_ids: Optional[jnp.ndarray] = None,
                      kv_mask: Optional[jnp.ndarray] = None,
                      window: Optional[int] = None,
                      bwd_block_q: Optional[int] = None,
                      bwd_block_kv: Optional[int] = None) -> jnp.ndarray:
    """Exact (causal) attention with the sequence dim sharded over ``axis``
    via head<->sequence all-to-alls. q,k,v: [B, S, H, D] global arrays.

    Packed sequences (segment_ids), key-validity masks (kv_mask) and
    sliding windows compose with the sequence sharding: heads stay whole
    per rank, so after the seq->head all-to-all the local flash kernel
    sees full rows and applies the masks exactly as in the unsharded
    case. (Ring SP composes with the same features by a different route
    — per-token metadata rotates with its K/V block; see
    ops/attention/ring.py. Trade-off: Ulysses is perfectly
    load-balanced under causal masks and needs sp | heads; the ring has
    no head-divisibility constraint, rotates only the small grouped K/V
    under GQA, and stops early under sliding windows.)
    """
    if scale is None:
        scale = 1.0 / np.sqrt(q.shape[-1])
    if use_flash:
        from deepspeed_tpu.ops.attention.flash import refuse_partial_manual
        refuse_partial_manual(mesh, axis, "ulysses_attention")
    inner = partial(_ulysses_local, axis=axis, causal=causal, scale=scale,
                    use_flash=use_flash, block_q=block_q, block_kv=block_kv,
                    window=window, bwd_block_q=bwd_block_q,
                    bwd_block_kv=bwd_block_kv)
    spec = P(None, axis, None, None)
    tok_spec = P(None, axis)
    args = [q, k, v]
    in_specs = [spec, spec, spec]
    for extra in (segment_ids, kv_mask):
        args.append(extra)
        in_specs.append(None if extra is None else tok_spec)
    mapped = jax.shard_map(
        inner, mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=spec,
        axis_names={axis},
        check_vma=False)
    # same eager-canonicalization workaround as ring_attention
    return jax.jit(mapped)(*args)
