"""Small seeded SmallThinker-style models for the CPU tests: two periods of
GLLL (the full layer first), no dense layer, 8 ReLU-gated experts top 3
routed on the layer's input, window 8; the plain reference of the benchmark
(benchmark/reference/smallthinker.py) beside the program."""

import importlib.util
import os

import jax
import jax.numpy as jnp

from deepspeed_tpu.models import smallthinker

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def reference():
    spec = importlib.util.spec_from_file_location(
        "smallthinker_reference",
        os.path.join(ROOT, "benchmark", "reference", "smallthinker.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def tiny_config(window=8, max_seq_len=96, **over):
    kw = dict(
        vocab_size=96, n_layers=8, n_heads=4, n_kv_heads=2, d_model=32,
        head_size=16, max_seq_len=max_seq_len, dtype=jnp.float32,
        attn_window=window,
        layer_kinds=smallthinker.layer_kinds([0, 1, 1, 1] * 2,
                                             [0, 1, 1, 1] * 2, 8),
        num_experts=8, moe_k=3, moe_d_ff=24, use_flash_attention=False)
    kw.update(over)
    return smallthinker.SmallThinkerConfig(**kw)


def tiny_params(cfg, seed=0):
    # a larger std than the family's 0.02: at width 32 it keeps every
    # term of the equations visible in the logits
    return smallthinker.init_params(jax.random.PRNGKey(seed), cfg, std=0.2)


def hp_of(cfg):
    return {"n_heads": cfg.n_heads, "n_kv_heads": cfg.kv_heads,
            "head_dim": cfg.head_dim, "window": cfg.attn_window,
            "kinds": tuple(cfg.layer_kinds), "num_experts": cfg.num_experts,
            "top_k": cfg.moe_k, "eps": cfg.norm_eps,
            "rope_theta": cfg.rope_theta}
