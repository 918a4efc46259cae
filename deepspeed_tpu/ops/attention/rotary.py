"""Rotary position embeddings (GPT-J / GPT-NeoX convention).

Capability analog of the reference's rotary inference kernel
(ref: csrc/transformer/inference/csrc/apply_rotary_pos_emb.cu, driven from
ops/transformer/inference/transformer_inference.py). TPU-native: a few
fused elementwise ops — XLA folds them into the surrounding attention
matmuls, so no custom kernel is warranted (bandwidth-bound, zero reuse).

GPT-J uses the interleaved ("rotate every two") layout on the first
``rotary_dim`` channels of each head; remaining channels pass through.
"""

import math
from typing import Optional, Tuple

import jax.numpy as jnp
import numpy as np


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


def apply_rotary_half_partial(x: jnp.ndarray, positions: jnp.ndarray,
                              rotary_dim: int,
                              base: float = 10000.0) -> jnp.ndarray:
    """Rotate-half on the FIRST ``rotary_dim`` channels of each head
    (channel i pairs with channel i + rotary_dim/2; ``partial_rotary_factor``
    = rotary_dim / D), the rest passing through. x ``[..., T, H, D]``."""
    if rotary_dim == x.shape[-1]:
        return apply_rotary_half(x, positions, base)
    return jnp.concatenate(
        [apply_rotary_half(x[..., :rotary_dim], positions, base),
         x[..., rotary_dim:]], axis=-1)


def yarn_inv_freq(rotary_dim: int, base: float, factor: float,
                  original_max: int, beta_fast: float = 32.0,
                  beta_slow: float = 1.0) -> np.ndarray:
    """YaRN's per-pair frequencies (Peng et al. 2023, as DeepSeek-V3's
    published modelling code computes them): pair ``i`` of ``rotary_dim / 2``
    keeps its frequency ``f_i = base^(-2i/dim)`` where it turns more than
    ``beta_fast`` times within the original context, is divided by
    ``factor`` where it turns fewer than ``beta_slow`` times, and is
    ramped linearly in between. float64 on the host: the table is a
    constant of the program."""
    half = rotary_dim // 2
    f = base ** (-np.arange(half, dtype=np.float64) * 2.0 / rotary_dim)

    def turns_at(n_rot):
        return rotary_dim * math.log(original_max / (n_rot * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(turns_at(beta_fast)), 0)
    high = min(math.ceil(turns_at(beta_slow)), rotary_dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(half) - low) / (high - low), 0.0, 1.0)
    return (f / factor) * ramp + f * (1.0 - ramp)


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """YaRN's attention temperature ``0.1 * mscale * ln(factor) + 1``."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def apply_rotary_freqs(x: jnp.ndarray, positions: jnp.ndarray,
                       inv_freq) -> jnp.ndarray:
    """Rotate the interleaved pairs ``(x_2i, x_2i+1)`` of x's last
    dimension by ``positions * inv_freq[i]`` (a table of given
    frequencies: :func:`yarn_inv_freq`). ``positions`` is shaped like x's
    leading dimensions, or like those before a heads axis (it is then
    broadcast over the heads). float32 inside, x's dtype out."""
    ang = positions.astype(jnp.float32)[..., None] \
        * jnp.asarray(inv_freq, jnp.float32)
    if ang.ndim < x.ndim:
        ang = ang[..., None, :]
    sin = jnp.repeat(jnp.sin(ang), 2, axis=-1)
    cos = jnp.repeat(jnp.cos(ang), 2, axis=-1)
    xf = x.astype(jnp.float32)
    return (xf * cos + _rotate_every_two(xf) * sin).astype(x.dtype)
