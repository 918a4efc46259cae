"""Fused BERT-style transformer encoder layer.

Capability match for the reference's transformer training kernel
(ref: csrc/transformer/ds_transformer_cuda.cpp + the python module
deepspeed/ops/transformer/transformer.py:460 DeepSpeedTransformerLayer,
config :22 DeepSpeedTransformerConfig). The reference hand-fuses QKV
GEMM, softmax, dropout, layernorm and GELU into CUDA kernels; on TPU
the layer is written as straight jax — XLA fuses the elementwise chain
into the GEMMs — with the attention core dispatched to the Pallas flash
kernel when no padding mask is present (the kernel computes full
attention; masked batches take the jnp softmax path, whose masking
fuses too).

Supports both residual placements the reference ships parity models for
(post-LN `tests/unit/modeling.py`, pre-LN `modelingpreln.py`) via
``pre_layer_norm``.
"""

from dataclasses import dataclass
from typing import Dict, Optional

import jax
import jax.numpy as jnp


@dataclass
class DeepSpeedTransformerConfig:
    """(ref: ops/transformer/transformer.py:22) the knobs that affect
    math; kernel-scheduling knobs of the CUDA version (stochastic_mode,
    attn_dropout_checkpoint, ...) dissolve under XLA."""
    batch_size: int = -1          # unused: shapes are traced (API parity)
    hidden_size: int = 256
    intermediate_size: int = -1   # defaults to 4*hidden
    heads: int = 4
    attn_dropout_ratio: float = 0.1
    hidden_dropout_ratio: float = 0.1
    num_hidden_layers: int = -1
    layer_norm_eps: float = 1e-12
    pre_layer_norm: bool = True
    fp16: bool = False            # API parity; dtype follows inputs

    def __post_init__(self):
        if self.intermediate_size <= 0:
            self.intermediate_size = 4 * self.hidden_size
        assert self.hidden_size % self.heads == 0

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.heads


def init_layer_params(rng: jax.Array, cfg: DeepSpeedTransformerConfig,
                      dtype=jnp.float32) -> Dict:
    h, ff = cfg.hidden_size, cfg.intermediate_size
    k = jax.random.split(rng, 4)
    s = 0.02
    return {
        "qkv": {"kernel": jax.random.normal(k[0], (h, 3 * h), dtype) * s,
                "bias": jnp.zeros((3 * h,), dtype)},
        "attn_out": {"kernel": jax.random.normal(k[1], (h, h), dtype) * s,
                     "bias": jnp.zeros((h,), dtype)},
        "mlp_in": {"kernel": jax.random.normal(k[2], (h, ff), dtype) * s,
                   "bias": jnp.zeros((ff,), dtype)},
        "mlp_out": {"kernel": jax.random.normal(k[3], (ff, h), dtype) * s,
                    "bias": jnp.zeros((h,), dtype)},
        "ln1": {"scale": jnp.ones((h,), dtype), "bias": jnp.zeros((h,), dtype)},
        "ln2": {"scale": jnp.ones((h,), dtype), "bias": jnp.zeros((h,), dtype)},
    }


def _layernorm(x, scale, bias, eps):
    m = jnp.mean(x, axis=-1, keepdims=True)
    v = jnp.var(x, axis=-1, keepdims=True)
    return ((x - m) * jax.lax.rsqrt(v + eps)) * scale + bias


def _dropout(x, rate, rng):
    keep = jax.random.bernoulli(rng, 1.0 - rate, x.shape)
    return jnp.where(keep, x / (1.0 - rate), 0.0).astype(x.dtype)


def flash_block(seq_len: int, head_dim: int) -> Optional[int]:
    """Block size at which the encoder's attention runs the Pallas flash
    kernel, None where it runs the jnp softmax: off a TPU, and for
    shapes the kernel does not tile. One gate for ``_attention_core``
    and for the remat policy in models/bert.py."""
    from deepspeed_tpu.ops.attention.flash import fit_block
    from deepspeed_tpu.utils import on_tpu
    if head_dim % 8 or not on_tpu():
        return None
    return fit_block(512, seq_len)


def _attention_core(q, k, v, attn_mask, cfg, dropout_rng, deterministic,
                    allow_flash=True):
    """[B,S,H,D] attention; flash kernel on a TPU when deterministic and
    the shape tiles, masked jnp softmax otherwise. A kernel that fails
    to compile fails the step."""
    B, S, H, D = q.shape
    block = flash_block(S, D) if allow_flash and (
        deterministic or cfg.attn_dropout_ratio == 0.0) else None
    if block is not None:
        from deepspeed_tpu.ops.attention.flash import flash_attention
        return flash_attention(q, k, v, causal=False, kv_mask=attn_mask,
                               block_q=block, block_kv=block)
    scale = 1.0 / jnp.sqrt(jnp.float32(D))
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if attn_mask is not None:
        # attn_mask [B, S]: 1 = attend, 0 = padding
        bias = jnp.where(attn_mask[:, None, None, :] > 0, 0.0, -1e9)
        logits = logits + bias
    probs = jax.nn.softmax(logits, axis=-1)
    if not deterministic and cfg.attn_dropout_ratio > 0:
        probs = _dropout(probs, cfg.attn_dropout_ratio, dropout_rng)
    return jnp.einsum("bhqk,bkhd->bqhd", probs.astype(q.dtype), v)


def layer_forward(params: Dict, x: jnp.ndarray,
                  cfg: DeepSpeedTransformerConfig,
                  attn_mask: Optional[jnp.ndarray] = None,
                  rng: Optional[jax.Array] = None,
                  deterministic: bool = True,
                  allow_flash: bool = True) -> jnp.ndarray:
    """One encoder block. x: [B, S, H]; attn_mask: [B, S] (1=token).

    Pre-LN:  x + Attn(LN(x));  x + MLP(LN(x))
    Post-LN: LN(x + Attn(x));  LN(x + MLP(x))
    (ref: ops/transformer/transformer.py forward, pre_layer_norm branch)
    """
    B, S, h = x.shape
    H, D = cfg.heads, cfg.head_dim
    if rng is not None:
        r_attn, r_probs, r_mlp = jax.random.split(rng, 3)
    else:
        r_attn = r_probs = r_mlp = None
        deterministic = True

    def attn_block(inp):
        from jax.ad_checkpoint import checkpoint_name
        qkv = inp @ params["qkv"]["kernel"].astype(inp.dtype) + \
            params["qkv"]["bias"].astype(inp.dtype)
        qkv = checkpoint_name(qkv, "qkv")
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q = q.reshape(B, S, H, D)
        k = k.reshape(B, S, H, D)
        v = v.reshape(B, S, H, D)
        ctx = _attention_core(q, k, v, attn_mask, cfg, r_probs,
                              deterministic,
                              allow_flash=allow_flash).reshape(B, S, h)
        ctx = checkpoint_name(ctx, "attn")
        out = ctx @ params["attn_out"]["kernel"].astype(inp.dtype) + \
            params["attn_out"]["bias"].astype(inp.dtype)
        if not deterministic and cfg.hidden_dropout_ratio > 0:
            out = _dropout(out, cfg.hidden_dropout_ratio, r_attn)
        return out

    def mlp_block(inp):
        from jax.ad_checkpoint import checkpoint_name
        mid = inp @ params["mlp_in"]["kernel"].astype(inp.dtype) + \
            params["mlp_in"]["bias"].astype(inp.dtype)
        mid = checkpoint_name(mid, "mlp_pre")
        mid = jax.nn.gelu(mid, approximate=True)
        out = mid @ params["mlp_out"]["kernel"].astype(inp.dtype) + \
            params["mlp_out"]["bias"].astype(inp.dtype)
        if not deterministic and cfg.hidden_dropout_ratio > 0:
            out = _dropout(out, cfg.hidden_dropout_ratio, r_mlp)
        return out

    eps = cfg.layer_norm_eps
    dt = x.dtype
    ln1_s = params["ln1"]["scale"].astype(dt)
    ln1_b = params["ln1"]["bias"].astype(dt)
    ln2_s = params["ln2"]["scale"].astype(dt)
    ln2_b = params["ln2"]["bias"].astype(dt)
    if cfg.pre_layer_norm:
        x = x + attn_block(_layernorm(x, ln1_s, ln1_b, eps))
        x = x + mlp_block(_layernorm(x, ln2_s, ln2_b, eps))
    else:
        x = _layernorm(x + attn_block(x), ln1_s, ln1_b, eps)
        x = _layernorm(x + mlp_block(x), ln2_s, ln2_b, eps)
    return x.astype(dt)


def layer_forward_reference(params, x, cfg, attn_mask=None):
    """Naive fp32 reference of the same math, for kernel-parity tests
    (analog of tests/unit/modeling.py vs the fused CUDA layer). Forces
    the jnp softmax path so it stays an independent oracle for the
    flash kernel."""
    p32 = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
    return layer_forward(p32, x.astype(jnp.float32), cfg,
                         attn_mask=attn_mask, deterministic=True,
                         allow_flash=False)
