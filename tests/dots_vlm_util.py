"""Small seeded dots.vlm1-style models for the CPU tests: one dense layer
and three sparse ones, 16 experts in 4 groups (2 groups a token), a latent
of 16 beside a rotated key of 4, YaRN over an original context of 16; the
plain reference of the benchmark (benchmark/reference/dots_vlm.py) beside
the program."""

import importlib.util
import os

import jax
import jax.numpy as jnp

from deepspeed_tpu.models import dots_vlm
from exaone_moe_util import serve_logits  # noqa: F401  (the same drive)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def reference():
    spec = importlib.util.spec_from_file_location(
        "dots_vlm_reference",
        os.path.join(ROOT, "benchmark", "reference", "dots_vlm.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def tiny_config(held=(4, 8), max_seq_len=96, **over):
    kw = dict(
        vocab_size=96, n_layers=4, n_heads=4, d_model=32, d_ff=48,
        max_seq_len=max_seq_len, dtype=jnp.float32, q_lora_rank=24,
        kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4,
        v_head_dim=8, rope_factor=40.0, rope_original_max=16,
        n_dense_layers=1, num_experts=16, moe_k=3, moe_d_ff=24,
        n_shared_experts=1, routed_scaling=2.5, n_group=4, topk_group=2,
        experts_held=held, use_flash_attention=False)
    kw.update(over)
    return dots_vlm.DotsVLMConfig(**kw)


def tiny_params(cfg, seed=0):
    # a larger std than the family's 0.02: at width 32 it keeps every
    # term of the equations visible in the logits
    return dots_vlm.init_params(jax.random.PRNGKey(seed), cfg, std=0.2,
                                bias_std=0.05)


def hp_of(cfg):
    return {"n_heads": cfg.n_heads, "d_n": cfg.qk_nope_head_dim,
            "d_r": cfg.qk_rope_head_dim, "d_v": cfg.v_head_dim,
            "n_layers": cfg.n_layers, "n_dense": cfg.n_dense_layers,
            "num_experts": cfg.num_experts, "top_k": cfg.moe_k,
            "n_group": cfg.n_group, "topk_group": cfg.topk_group,
            "held": tuple(cfg.held), "routed_scale": cfg.routed_scaling,
            "eps": cfg.norm_eps, "rope_theta": cfg.rope_theta,
            "rope_factor": cfg.rope_factor,
            "rope_original_max": cfg.rope_original_max,
            "beta_fast": cfg.rope_beta_fast, "beta_slow": cfg.rope_beta_slow,
            "mscale": cfg.rope_mscale,
            "mscale_all_dim": cfg.rope_mscale_all_dim}
