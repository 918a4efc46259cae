"""Communication-compressed collectives (1-bit, error-feedback).

Capability analog of the reference's compressed allreduce backends
(ref: deepspeed/runtime/comm/nccl.py:52 NcclBackend.compressed_allreduce,
runtime/comm/mpi.py MpiBackend, cupy bit packing in
runtime/compression/cupy.py). Intended for DCN links between TPU slices —
over ICI plain XLA collectives win (SURVEY §2.3).

Algorithm (error-feedback signSGD compression, as in 1-bit Adam):
  1. corrected = x + error                (error feedback)
  2. scale = ||corrected||_1 / n          (per-tensor magnitude)
  3. compressed = sign(corrected) * scale
  4. new_error = corrected - compressed   (kept locally)
  5. allreduce(compressed) — executed as all_gather of PACKED sign bits
     (uint8, 8 signs/byte = 32x volume reduction vs fp32) + scalar scales,
     then a local unpack-and-average. A second error-feedback stage on the
     server-side average (ref nccl.py's two-stage scheme) is folded into
     the worker error because TPU all_gather is symmetric.
"""

from functools import partial
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

PyTree = Any


def _pack_signs(x: jnp.ndarray) -> jnp.ndarray:
    """flat float [n] -> uint8 [ceil(n/8)] of sign bits (1 = non-negative)."""
    n = x.shape[0]
    pad = (-n) % 8
    bits = (x >= 0).astype(jnp.uint8)
    bits = jnp.pad(bits, (0, pad))
    bits = bits.reshape(-1, 8)
    weights = (2 ** jnp.arange(8, dtype=jnp.uint8))
    return jnp.sum(bits * weights, axis=1).astype(jnp.uint8)


def _unpack_signs(packed: jnp.ndarray, n: int) -> jnp.ndarray:
    """uint8 [m] -> float [n] of +-1."""
    weights = (2 ** jnp.arange(8, dtype=jnp.uint8))
    bits = (packed[:, None] & weights[None, :]) > 0
    signs = jnp.where(bits, 1.0, -1.0).astype(jnp.float32)
    return signs.reshape(-1)[:n]


def compress(x: jnp.ndarray, error: jnp.ndarray
             ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """-> (packed_bits uint8, scale f32 scalar, new_error)."""
    corrected = x.astype(jnp.float32) + error
    flat = corrected.reshape(-1)
    n = flat.shape[0]
    scale = jnp.sum(jnp.abs(flat)) / n
    packed = _pack_signs(flat)
    compressed = _unpack_signs(packed, n).reshape(x.shape) * scale
    new_error = corrected - compressed
    return packed, scale, new_error


def decompress(packed: jnp.ndarray, scale: jnp.ndarray, n: int,
               shape) -> jnp.ndarray:
    return (_unpack_signs(packed, n) * scale).reshape(shape)


def compressed_allreduce_local(x: jnp.ndarray, error: jnp.ndarray,
                               axis: str) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Inside shard_map over ``axis``: error-feedback 1-bit mean-allreduce.

    Returns (averaged tensor, new local error). The wire payload is the
    packed uint8 sign array + one f32 scale per rank.
    """
    packed, scale, new_error = compress(x, error)
    n = int(np.prod(x.shape))
    # all_gather the compressed payloads (tiled=False -> leading rank dim)
    all_packed = jax.lax.all_gather(packed, axis)          # [R, m] uint8
    all_scales = jax.lax.all_gather(scale, axis)           # [R]
    R = all_packed.shape[0]

    def one(i, acc):
        contrib = decompress(all_packed[i], all_scales[i], n, x.shape)
        return acc + contrib

    total = jax.lax.fori_loop(0, R, one, jnp.zeros(x.shape, jnp.float32))
    return total / R, new_error


def compressed_allreduce(tree: PyTree, error_tree: PyTree, mesh: Mesh,
                         axis: str = "data") -> Tuple[PyTree, PyTree]:
    """Standalone compressed mean-allreduce of a replicated pytree: each
    rank contributes its local values; result is identical on all ranks.
    (For testing / host-level use; the training path calls
    compressed_allreduce_local inside its shard_map.)"""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    err_leaves = jax.tree_util.tree_leaves(error_tree)

    def inner(*flat):
        k = len(flat) // 2
        outs, errs = [], []
        for x, e in zip(flat[:k], flat[k:]):
            o, ne = compressed_allreduce_local(x, e, axis)
            outs.append(o)
            errs.append(ne)
        return tuple(outs) + tuple(errs)

    specs = tuple(P() for _ in range(2 * len(leaves)))
    fn = jax.jit(jax.shard_map(
        inner, mesh=mesh, in_specs=specs, out_specs=specs,
        axis_names={axis}, check_vma=False))
    out = fn(*leaves, *err_leaves)
    k = len(leaves)
    return (jax.tree_util.tree_unflatten(treedef, out[:k]),
            jax.tree_util.tree_unflatten(treedef, out[k:]))


def compression_ratio(shape, dtype=jnp.float32) -> float:
    """Wire bytes full-precision / wire bytes compressed."""
    n = int(np.prod(shape))
    full = n * jnp.dtype(dtype).itemsize
    packed = (n + 7) // 8 + 4
    return full / packed
