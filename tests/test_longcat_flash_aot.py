"""The longcat_flash dialect's two serving programs compiled ahead of time
for a TPU v5e from this CPU host (tests/test_pool_layout_aot.py's kind): 8
latent rows a token in ONE pool that neither program copies. The dialect
itself: tests/test_longcat_flash.py."""

import jax
import jax.numpy as jnp
import pytest

from test_pool_layout_aot import v5e  # noqa: F401  (the described chip)


@pytest.mark.parametrize("program", ["prefill_slot", "decode_slots"])
def test_eight_rows_a_token_in_one_pool_that_is_never_copied(v5e, program):
    """tests/test_pool_layout_aot.py's reading for the double layer: the
    serving program compiled ahead of time for a v5e, with the Mosaic
    kernels, at the published head sizes (the tiling is theirs) and 4
    double layers, few slots and experts. ONE pool of 8 rows a token (two
    attention sublayers a layer, each at its own offset), no ``copy`` of a
    pool-shaped value, the pool updated in place, no pool-sized
    temporary."""
    from deepspeed_tpu.inference import latent
    from deepspeed_tpu.inference.engine import InferenceEngine, _named
    from deepspeed_tpu.models import longcat_flash
    from deepspeed_tpu.telemetry.costs import (parse_provenance,
                                               pool_copy_bytes,
                                               scatter_windows)
    cfg = longcat_flash.LongcatFlashConfig(
        vocab_size=512, n_layers=4, n_heads=4, d_model=256, d_ff=512,
        max_seq_len=512, dtype=jnp.bfloat16, q_lora_rank=256,
        q_lora_scale=1.0, kv_lora_scale=2.0, num_experts=8,
        n_zero_experts=4, moe_k=2, moe_d_ff=128, experts_held=(0, 4),
        use_flash_attention=False, remat=False)
    B, C, bs = 8, 128, 128
    NB = cfg.max_seq_len // bs
    N = B * NB + 1
    assert cfg.n_full_layers == 8
    assert latent.kv_bytes_per_token(cfg) == 8 * 640 * 2

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    params = jax.tree_util.tree_map(
        lambda a: S(a.shape, jnp.bfloat16), jax.eval_shape(
            lambda: longcat_flash.init_params(jax.random.PRNGKey(0), cfg)))
    rows, _ = jax.eval_shape(
        lambda: latent.new_state(cfg, N, bs, B, jnp.bfloat16))
    assert rows.rows.shape == (8, N, bs, 640)
    state = latent.LatentState(S(rows.rows.shape, jnp.bfloat16))
    eng = InferenceEngine.__new__(InferenceEngine)
    eng.cfg, eng.max_seq_len, eng.dtype = cfg, cfg.max_seq_len, jnp.bfloat16
    eng.decode_impl = "pallas"
    i32, f32, u32, V = jnp.int32, jnp.float32, jnp.uint32, cfg.vocab_size
    if program == "prefill_slot":
        fn = jax.jit(_named(eng._prefill_slot_fn, "serve_prefill_slot"),
                     donate_argnums=(1,))
        args = (params, state, None, S((NB,), i32), S((C,), i32), S((), i32),
                S((), i32), S((2,), u32), S((), i32), S((), f32), S((), i32),
                S((), f32), S((), f32), S((V,), jnp.bool_))
    else:
        fn = jax.jit(_named(eng._decode_slots_fn, "serve_decode_slots"),
                     donate_argnums=(1,), static_argnums=(7,))
        args = (params, state, None, S((B, NB), i32), S((B,), i32),
                S((B,), i32), S((B,), jnp.bool_), "pallas", S((B, 2), u32),
                S((B,), i32), S((B,), f32), S((B,), i32), S((B,), f32),
                S((B,), f32), S((B, V), jnp.bool_))
    exe = fn.trace(*args).lower(lowering_platforms=("tpu",)).compile()
    text = exe.as_text()
    assert pool_copy_bytes(parse_provenance(text), (N, 8 * N)) == 0
    pool = 8 * N * bs * 640 * 2
    assert exe.memory_analysis().alias_size_in_bytes >= pool
    assert exe.memory_analysis().temp_size_in_bytes < pool // 4
    assert ("mla_prefill" if program == "prefill_slot"
            else "mla_decode") in text and "gmm" in text
    if program == "prefill_slot":   # a chunk's rows: whole blocks, not rows
        assert 0 < max(scatter_windows(text, "kv_write")) \
            <= (C + bs - 2) // bs + 1 == 2
