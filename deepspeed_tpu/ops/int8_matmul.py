"""Fused int8 weight-dequant matmul (Pallas).

The reference ships dedicated int8 GEMM + dequant inference kernels
(ref: csrc/transformer/inference/csrc/pt_binding.cpp:866 qkv_gemm/
mlp_gemm int8 variants, csrc/transformer/inference/csrc/dequantize.cu).
Here weight-only int8 serving normally leans on XLA to fuse
``q.astype(bf16) * scale`` into the consuming matmul
(models/gpt.py _kernel_of) — bandwidth-bound and usually fused. This
kernel is the guaranteed-fused form: the int8
weight is the ONLY weight HBM traffic (1 byte/param), dequantized in
VMEM tiles on the way into the MXU, fp32 accumulation over K tiles,
per-output-channel scale applied once at the end.

Enable in serving with DS_INT8_FUSED=1 (inference/engine.py wires it
through gpt._dense); no cell of the benchmark measures fused against
XLA-dequant yet (ROADMAP.md Queue 2), so the flag stays off.
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _dq_matmul_kernel(x_ref, q_ref, s_ref, o_ref, acc, *, num_k: int):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)

    x = x_ref[...]                                   # [bm, bk] compute dtype
    w = q_ref[...].astype(x.dtype)                   # [bk, bn] int8 -> bf16
    acc[:] += jax.lax.dot(x, w, preferred_element_type=jnp.float32)

    @pl.when(ki == num_k - 1)
    def _done():
        o_ref[:] = (acc[:] * s_ref[...].astype(jnp.float32)) \
            .astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_m", "block_n",
                                             "block_k", "interpret"))
def int8_matmul(x: jnp.ndarray, q: jnp.ndarray, scale: jnp.ndarray,
                block_m: int = 256, block_n: int = 512,
                block_k: int = 512, interpret: bool = False) -> jnp.ndarray:
    """``x [M, K] @ dequant(q [K, N], scale [1, N]) -> [M, N]`` with the
    weight read from HBM as int8. M is padded up to a tile internally;
    K and N must divide by their blocks (model dims are 128-multiples).
    """
    M, K = x.shape
    Kq, N = q.shape
    assert K == Kq, (x.shape, q.shape)
    scale = scale.reshape(1, N)
    block_m = min(block_m, max(8, M))
    block_k = min(block_k, K)
    block_n = min(block_n, N)
    assert K % block_k == 0 and N % block_n == 0, (K, N, block_k, block_n)
    Mp = -(-M // block_m) * block_m
    if Mp != M:
        x = jnp.pad(x, ((0, Mp - M), (0, 0)))
    grid = (Mp // block_m, N // block_n, K // block_k)
    out = pl.pallas_call(
        functools.partial(_dq_matmul_kernel, num_k=grid[2]),
        name="int8_matmul",
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda mi, ni, ki: (mi, ki)),
            pl.BlockSpec((block_k, block_n), lambda mi, ni, ki: (ki, ni)),
            pl.BlockSpec((1, block_n), lambda mi, ni, ki: (0, ni)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n),
                               lambda mi, ni, ki: (mi, ni)),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        out_shape=jax.ShapeDtypeStruct((Mp, N), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, q, scale)
    return out[:M] if Mp != M else out


def int8_matmul_reference(x: jnp.ndarray, q: jnp.ndarray,
                          scale: jnp.ndarray) -> jnp.ndarray:
    """The XLA-fusion path this kernel replaces (gpt._kernel_of)."""
    return x @ (q.astype(x.dtype) * scale.astype(x.dtype))


def fit_blocks(K: int, N: int, want_k: int = 512, want_n: int = 512,
               align: int = 128):
    """Largest lane-aligned tile sizes dividing (K, N), capped at the
    requested sizes — or None when a dim is not even ``align``-divisible
    (e.g. a raw-vocab lm_head), in which case callers fall back to the
    XLA dequant path instead of crashing mid-trace (model dims like
    llama-7b's d_ff=11008 are 128-multiples but NOT 512-multiples)."""
    def fit(dim, want):
        if dim % align:
            return None
        units = dim // align
        for u in range(min(want // align, units), 0, -1):
            if units % u == 0:
                return u * align
        return None

    bk, bn = fit(K, want_k), fit(N, want_n)
    return None if bk is None or bn is None else (bk, bn)
