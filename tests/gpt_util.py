"""A small GPT-2-style model for tests/test_program_digests.py: the plain
K and V pools' two serving programs (GPT-2 XL's, at a tiny size)."""

import jax
import jax.numpy as jnp

from deepspeed_tpu.models import gpt


def tiny_config():
    return gpt.GPTConfig(vocab_size=96, n_layers=2, n_heads=4, d_model=32,
                         max_seq_len=64, dtype=jnp.float32,
                         use_flash_attention=False)


def tiny_params(cfg, seed=0):
    return gpt.init_params(jax.random.PRNGKey(seed), cfg)
