"""Small seeded LongCat-Flash-style models for the CPU tests: three double
layers, 16 experts and 8 zero-compute experts behind them (softmax over 24,
4 choices a token, no renormalisation), a latent of 16 beside a rotated key
of 4, both low ranks rescaled; the plain reference of the benchmark
(benchmark/reference/longcat_flash.py) beside the program."""

import importlib.util
import os

import jax
import jax.numpy as jnp

from deepspeed_tpu.models import longcat_flash
from exaone_moe_util import serve_logits  # noqa: F401  (the same drive)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def reference():
    spec = importlib.util.spec_from_file_location(
        "longcat_flash_reference",
        os.path.join(ROOT, "benchmark", "reference", "longcat_flash.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def tiny_config(held=(4, 8), max_seq_len=96, **over):
    kw = dict(
        vocab_size=96, n_layers=3, n_heads=4, d_model=32, d_ff=48,
        max_seq_len=max_seq_len, dtype=jnp.float32, q_lora_rank=24,
        kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4,
        v_head_dim=8, rope_theta=1e4,
        q_lora_scale=longcat_flash.lora_scale(32, 24),
        kv_lora_scale=longcat_flash.lora_scale(32, 16),
        num_experts=16, n_zero_experts=8, moe_k=4, moe_d_ff=24,
        routed_scaling=6.0, experts_held=held, use_flash_attention=False)
    kw.update(over)
    return longcat_flash.LongcatFlashConfig(**kw)


def tiny_params(cfg, seed=0):
    # a larger std than the family's 0.02: at width 32 it keeps every
    # term of the equations visible in the logits; the bias at the scale
    # of a softmax's probabilities over 24 outputs
    return longcat_flash.init_params(jax.random.PRNGKey(seed), cfg, std=0.2,
                                     bias_std=0.01)


def hp_of(cfg):
    return {"n_heads": cfg.n_heads, "d_n": cfg.qk_nope_head_dim,
            "d_r": cfg.qk_rope_head_dim, "d_v": cfg.v_head_dim,
            "n_layers": cfg.n_layers, "num_experts": cfg.num_experts,
            "zero_experts": cfg.n_zero_experts, "top_k": cfg.moe_k,
            "held": tuple(cfg.held), "routed_scale": cfg.routed_scaling,
            "q_scale": cfg.q_lora_scale, "kv_scale": cfg.kv_lora_scale,
            "eps": cfg.norm_eps, "rope_theta": cfg.rope_theta}
