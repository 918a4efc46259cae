"""The traced text (jaxpr) of a config's two paged serving programs at a
small size: what tests/test_program_digests.py holds the configurations
that share code with a new one to."""

import hashlib

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference import dialect
from deepspeed_tpu.inference.engine import InferenceEngine


def serving_programs_text(cfg, params, **kw):
    """{"prefill_slot": jaxpr text, "decode_slots": jaxpr text} of
    :func:`serving_programs`."""
    return {k: str(v) for k, v in serving_programs(cfg, params, **kw).items()}


def serving_programs(cfg, params, impl="gather", B=2, C=16, bs=4, NB=8):
    """{"prefill_slot": jaxpr, "decode_slots": jaxpr} of the engine's two
    paged programs for ``cfg`` over abstract arguments (``params``: the
    tree's shapes, as ``jax.eval_shape`` gives them)."""
    eng = InferenceEngine.__new__(InferenceEngine)
    eng.cfg, eng.max_seq_len, eng.dtype = cfg, cfg.max_seq_len, cfg.dtype
    eng.decode_impl = impl
    d = dialect.of(cfg)
    N = B * NB + 1
    k, v = jax.eval_shape(lambda: d.new_state(cfg, N, bs, B, cfg.dtype))
    row = NB + d.ring_blocks(cfg, bs)
    V = cfg.vocab_size
    i32, f32, u32 = jnp.int32, jnp.float32, jnp.uint32

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype)

    lane = (S((), i32), S((), f32), S((), i32), S((), f32), S((), f32))
    lanes = tuple(S((B,), a.dtype) for a in lane)
    prefill = jax.make_jaxpr(
        lambda *a: eng._prefill_slot_fn(*a[:-1], None, None, a[-1]))(
        params, k, v, S((row,), i32), S((C,), i32), S((), i32), S((), i32),
        S((2,), u32), *lane, S((V,), jnp.bool_), S((), i32))
    decode = jax.make_jaxpr(
        lambda *a: eng._decode_slots_fn(*a[:7], impl, *a[7:]))(
        params, k, v, S((B, row), i32), S((B,), i32), S((B,), i32),
        S((B,), jnp.bool_), S((B, 2), u32), *lanes, S((B, V), jnp.bool_))
    return {"prefill_slot": prefill, "decode_slots": decode}


def digest(text: str) -> str:
    return hashlib.sha256(text.encode()).hexdigest()[:16]
