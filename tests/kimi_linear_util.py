"""Small seeded Kimi-Linear-style models for the CPU tests: seven layers
whose kinds follow the published pattern's irregular end (linear, linear,
latent, linear, linear, linear, latent ... ``KDA, KDA, MLA``), 4 heads of 8
in both kinds inside a stream of 32, 8 experts of which 3 a token and one
shared; the plain reference of the benchmark
(benchmark/reference/kimi_linear.py) beside the program."""

import importlib.util
import os

import jax
import jax.numpy as jnp

from deepspeed_tpu.models import kimi_linear
from exaone_moe_util import serve_logits  # noqa: F401  (the same drive)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def reference():
    spec = importlib.util.spec_from_file_location(
        "kimi_linear_reference",
        os.path.join(ROOT, "benchmark", "reference", "kimi_linear.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def tiny_config(held=None, max_seq_len=96, **over):
    kw = dict(
        vocab_size=96, n_layers=7, n_heads=4, d_model=32, d_ff=48,
        max_seq_len=max_seq_len, dtype=jnp.float32,
        kda_layers=(1, 2, 4, 5, 6), full_attn_layers=(3, 7),
        linear_heads=4, linear_head_dim=8, kv_lora_rank=16,
        qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
        num_experts=8, moe_k=3, moe_d_ff=24, experts_held=held,
        use_flash_attention=False)
    kw.update(over)
    return kimi_linear.KimiLinearConfig(**kw)


def tiny_params(cfg, seed=0):
    # a larger std than the family's 0.02: at width 32 it keeps every
    # term of the equations visible in the logits
    return kimi_linear.init_params(jax.random.PRNGKey(seed), cfg, std=0.2,
                                   bias_std=0.05)


def hp_of(cfg):
    return {"kinds": tuple(int(k) for k in cfg.attn_kinds),
            "n_dense": cfg.n_dense_layers, "lin_heads": cfg.linear_heads,
            "lin_dim": cfg.linear_head_dim, "taps": cfg.conv_kernel,
            "l2_eps": cfg.l2_eps, "n_heads": cfg.n_heads,
            "d_n": cfg.qk_nope_head_dim, "d_r": cfg.qk_rope_head_dim,
            "d_v": cfg.v_head_dim, "num_experts": cfg.num_experts,
            "top_k": cfg.moe_k, "held": tuple(cfg.held),
            "routed_scale": cfg.routed_scaling, "eps": cfg.norm_eps}
