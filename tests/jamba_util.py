"""Small seeded Jamba-style models for the CPU tests: eight layers whose
kinds follow the family's period rule (period 4, offset 2: runs of 2 and 3
state-space layers, each ended by an attention layer, and one behind the
last), 4 query heads of 8 on ONE K/V head inside a stream of 32, 64 inner
channels with a state of 8, the published 4 taps; the plain reference of
the benchmark (benchmark/reference/jamba.py) beside the program."""

import importlib.util
import os

import jax
import jax.numpy as jnp

from deepspeed_tpu.models import jamba
from exaone_moe_util import serve_logits  # noqa: F401  (the same drive)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def reference():
    spec = importlib.util.spec_from_file_location(
        "jamba_reference",
        os.path.join(ROOT, "benchmark", "reference", "jamba.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def tiny_config(max_seq_len=96, **over):
    kw = dict(
        vocab_size=96, n_layers=8, n_heads=4, n_kv_heads=1, d_model=32,
        d_ff=48, max_seq_len=max_seq_len, dtype=jnp.float32,
        attn_layer_period=4, attn_layer_offset=2, mamba_d_state=8,
        mamba_dt_rank=6, use_flash_attention=False)
    kw.update(over)
    return jamba.JambaConfig(**kw)


def tiny_params(cfg, seed=0):
    # a larger std than the family's 0.02: at width 32 it keeps every
    # term of the equations visible in the logits
    return jamba.init_params(jax.random.PRNGKey(seed), cfg, std=0.2)


def hp_of(cfg):
    return {"kinds": tuple(int(k) for k in cfg.attn_kinds),
            "n_heads": cfg.n_heads, "n_kv_heads": cfg.kv_heads,
            "d_state": cfg.mamba_d_state, "dt_rank": cfg.mamba_dt_rank,
            "eps": cfg.norm_eps}
