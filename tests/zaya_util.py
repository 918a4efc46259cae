"""Small seeded ZAYA1-style models for the CPU tests: four layers, 4 query
heads on 2 KV heads of 8 inside a stream of 32, rotary on half of a head,
8 experts and the skip, a router state of 12; the plain reference of the
benchmark (benchmark/reference/zaya.py) beside the program."""

import importlib.util
import os

import jax
import jax.numpy as jnp

from deepspeed_tpu.models import zaya
from exaone_moe_util import serve_logits  # noqa: F401  (the same drive)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def reference():
    spec = importlib.util.spec_from_file_location(
        "zaya_reference",
        os.path.join(ROOT, "benchmark", "reference", "zaya.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def tiny_config(held=None, max_seq_len=96, **over):
    kw = dict(
        vocab_size=96, n_layers=4, n_heads=4, n_kv_heads=2, head_size=8,
        d_model=32, max_seq_len=max_seq_len, dtype=jnp.float32,
        num_experts=8, moe_d_ff=24, router_hidden=12, experts_held=held,
        use_flash_attention=False)
    kw.update(over)
    return zaya.ZayaConfig(**kw)


def tiny_params(cfg, seed=0):
    # a larger std than the family's 0.02: at width 32 it keeps every
    # term of the equations visible in the logits
    return zaya.init_params(jax.random.PRNGKey(seed), cfg, std=0.2,
                            bias_std=0.05)


def hp_of(cfg):
    return {"n_heads": cfg.n_heads, "kv_heads": cfg.kv_heads,
            "head_dim": cfg.head_dim, "rotary_dim": cfg.rotary_channels,
            "rope_theta": cfg.rope_theta, "n_layers": cfg.n_layers,
            "num_experts": cfg.num_experts, "held": tuple(cfg.held),
            "eps": cfg.norm_eps}
