"""Small seeded Qwen3-Next-style models for the CPU tests: two periods of
three Gated DeltaNet layers and one gated full-attention layer, 2 key heads
feeding 4 value heads of 8, 4 query heads on 2 K/V heads of 16 with rotary
on the first 4 channels, 8 softmax-routed experts of which 3 a token and a
gated shared one, inside a stream of 32; the plain reference of the
benchmark (benchmark/reference/qwen3_next.py) beside the program."""

import importlib.util
import os

import jax
import jax.numpy as jnp

from deepspeed_tpu.models import qwen3_next
from exaone_moe_util import serve_logits  # noqa: F401  (the same drive)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def reference():
    spec = importlib.util.spec_from_file_location(
        "qwen3_next_reference",
        os.path.join(ROOT, "benchmark", "reference", "qwen3_next.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def tiny_config(held=None, max_seq_len=96, **over):
    kw = dict(
        vocab_size=96, n_layers=8, n_heads=4, n_kv_heads=2, head_size=16,
        rotary_dim=4, d_model=32, max_seq_len=max_seq_len, dtype=jnp.float32,
        linear_key_heads=2, linear_value_heads=4, linear_head_dim=8,
        num_experts=8, moe_k=3, moe_d_ff=24, experts_held=held,
        use_flash_attention=False)
    kw.update(over)
    return qwen3_next.Qwen3NextConfig(**kw)


def tiny_params(cfg, seed=0):
    # a larger std than the family's 0.02: at width 32 it keeps every term
    # of the equations visible in the logits; the offset norms' scales far
    # enough from zero that the offset shows
    return qwen3_next.init_params(jax.random.PRNGKey(seed), cfg, std=0.2,
                                  norm_std=0.3)


def hp_of(cfg):
    return {"kinds": tuple(int(k) for k in cfg.attn_kinds),
            "key_heads": cfg.linear_key_heads,
            "value_heads": cfg.linear_value_heads,
            "lin_dim": cfg.linear_head_dim, "taps": cfg.conv_kernel,
            "l2_eps": cfg.l2_eps, "n_heads": cfg.n_heads,
            "n_kv_heads": cfg.kv_heads, "head_dim": cfg.head_dim,
            "rotary_dim": cfg.rotary_dim, "rope_theta": cfg.rope_theta,
            "num_experts": cfg.num_experts, "top_k": cfg.moe_k,
            "held": tuple(cfg.held), "eps": cfg.norm_eps}
