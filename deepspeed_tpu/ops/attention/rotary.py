"""Rotary position embeddings (GPT-J / GPT-NeoX convention).

Capability analog of the reference's rotary inference kernel
(ref: csrc/transformer/inference/csrc/apply_rotary_pos_emb.cu, driven from
ops/transformer/inference/transformer_inference.py). TPU-native: a few
fused elementwise ops — XLA folds them into the surrounding attention
matmuls, so no custom kernel is warranted (bandwidth-bound, zero reuse).

GPT-J uses the interleaved ("rotate every two") layout on the first
``rotary_dim`` channels of each head; remaining channels pass through.
"""

from typing import Optional, Tuple

import jax.numpy as jnp


def _rotate_every_two(x: jnp.ndarray) -> jnp.ndarray:
    x1 = x[..., ::2]
    x2 = x[..., 1::2]
    return jnp.stack((-x2, x1), axis=-1).reshape(x.shape)


def rotary_sin_cos(positions: jnp.ndarray, rotary_dim: int,
                   base: float = 10000.0) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """positions [S] or [B, S] -> (sin, cos), each
    ``positions.shape + (rotary_dim,)`` (interleaved pairs)."""
    inv_freq = 1.0 / (base ** (jnp.arange(0, rotary_dim, 2,
                                          dtype=jnp.float32) / rotary_dim))
    ang = positions.astype(jnp.float32)[..., None] * inv_freq
    sin = jnp.repeat(jnp.sin(ang), 2, axis=-1)
    cos = jnp.repeat(jnp.cos(ang), 2, axis=-1)
    return sin, cos


def apply_rotary(q: jnp.ndarray, k: jnp.ndarray,
                 positions: jnp.ndarray,
                 rotary_dim: Optional[int] = None,
                 base: float = 10000.0) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Rotate q, k ([B, S, H, D]) by position; positions is [S] absolute,
    or [B, S] for per-row positions (left-padded / packed batches)."""
    D = q.shape[-1]
    rd = D if rotary_dim is None else rotary_dim
    sin, cos = rotary_sin_cos(positions, rd, base)
    if positions.ndim == 1:            # [S, rd] -> [1, S, 1, rd]
        sin, cos = sin[None], cos[None]
    sin = sin[:, :, None, :].astype(q.dtype)
    cos = cos[:, :, None, :].astype(q.dtype)

    def rot(t):
        t_rot = t[..., :rd] * cos + _rotate_every_two(t[..., :rd]) * sin
        if rd == D:
            return t_rot
        return jnp.concatenate([t_rot, t[..., rd:]], axis=-1)

    return rot(q), rot(k)


def apply_rotary_half(x: jnp.ndarray, positions: jnp.ndarray,
                      base: float = 10000.0) -> jnp.ndarray:
    """The rotate-half (GPT-NeoX / llama) convention over ALL channels of
    each head: channel i pairs with channel i + D/2. x ``[..., T, H, D]``
    with ``positions`` shaped like x's leading dims through T. Computed
    in float32 and returned in x's dtype."""
    D = x.shape[-1]
    inv_freq = 1.0 / (base ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = positions.astype(jnp.float32)[..., None, None] * inv_freq
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], axis=-1)
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], axis=-1)
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :D // 2], xf[..., D // 2:]
    return (xf * cos + jnp.concatenate([-x2, x1], axis=-1) * sin
            ).astype(x.dtype)
