"""SmallThinker's two serving programs at its cell's own sizes, compiled
ahead of time for a TPU v5e from this CPU host (the hybrid dialect at a
window of 4,096), read as tests/test_pool_layout_aot.py reads GPT-2
XL's."""

import json
import math
import pathlib
import re

import jax
import jax.numpy as jnp
import pytest

from deepspeed_tpu.inference.engine import InferenceEngine, _named
from deepspeed_tpu.telemetry.costs import parse_provenance, pool_copy_bytes

from test_pool_layout_aot import v5e  # noqa: F401 (a fixture)


SMALLTHINKER = json.loads((
    pathlib.Path(__file__).parent.parent / "benchmark" / "configs"
    / "smallthinker-21b-a3b-serve-pp4.json").read_text())


@pytest.mark.parametrize("program", ["prefill_slot", "decode_slots"])
def test_smallthinker_cell_programs_fit_a_v5e(v5e, program):
    """SmallThinker's two serving programs (the hybrid dialect at a window
    of 4,096: a ring of 33 blocks of 128 a slot a window layer) compiled
    for a v5e at the cell's own sizes, 12 layers and 24 slots of 16,384:
    no copy of either pool, the pools updated in place, no float32 score
    tensor over a full layer's whole row for more than one KV head
    (``[512, 28, 16384]`` would be 940 MB), and at least 1.0 GiB of the
    chip's 15.75 left at the program's peak."""
    from deepspeed_tpu.inference import hybrid
    from deepspeed_tpu.models import exaone_moe, smallthinker
    c, sv = SMALLTHINKER, SMALLTHINKER["serving"]
    L = c["num_hidden_layers"]
    cfg = smallthinker.SmallThinkerConfig(
        vocab_size=c["vocab_size"], n_layers=L,
        n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], d_model=c["hidden_size"],
        head_size=c["head_dim"], max_seq_len=sv["max_total"],
        dtype=jnp.bfloat16, attn_window=c["sliding_window_size"],
        layer_kinds=smallthinker.layer_kinds(
            c["sliding_window_layout"], c["rope_layout"], L),
        num_experts=c["moe_num_primary_experts"],
        moe_k=c["moe_num_active_primary_experts"],
        moe_d_ff=c["moe_ffn_hidden_size"], use_flash_attention=False,
        remat=False)
    B, C, bs = sv["num_slots"], sv["prefill_chunk"], sv["block_size"]
    NB = cfg.max_seq_len // bs
    N = sv["num_blocks"] + 1
    RB = exaone_moe.window_blocks(cfg, bs)
    assert (N, RB, cfg.n_full_layers) == (B * NB + 1, 33, 3)

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    params = jax.tree_util.tree_map(
        lambda a: S(a.shape, jnp.bfloat16), jax.eval_shape(
            lambda: smallthinker.init_params(jax.random.PRNGKey(0), cfg)))
    row = cfg.kv_heads * cfg.head_dim
    state = hybrid.PagedState(
        S((cfg.n_full_layers, N, bs, row), jnp.bfloat16),
        S((cfg.n_window_layers, 1 + B * RB, bs, row), jnp.bfloat16))
    eng = InferenceEngine.__new__(InferenceEngine)
    eng.cfg, eng.max_seq_len, eng.dtype = cfg, cfg.max_seq_len, jnp.bfloat16
    eng.decode_impl = "pallas"
    i32, f32, u32, V = jnp.int32, jnp.float32, jnp.uint32, cfg.vocab_size
    if program == "prefill_slot":
        fn = jax.jit(_named(eng._prefill_slot_fn, "serve_prefill_slot"),
                     donate_argnums=(1, 2))
        args = (params, state, state, S((NB + RB,), i32), S((C,), i32),
                S((), i32), S((), i32), S((2,), u32), S((), i32), S((), f32),
                S((), i32), S((), f32), S((), f32), S((V,), jnp.bool_))
    else:
        fn = jax.jit(_named(eng._decode_slots_fn, "serve_decode_slots"),
                     donate_argnums=(1, 2), static_argnums=(7,))
        args = (params, state, state, S((B, NB + RB), i32), S((B,), i32),
                S((B,), i32), S((B,), jnp.bool_), "pallas", S((B, 2), u32),
                S((B,), i32), S((B,), f32), S((B,), i32), S((B,), f32),
                S((B,), f32), S((B, V), jnp.bool_))
    exe = fn.trace(*args).lower(lowering_platforms=("tpu",)).compile()
    text = exe.as_text()
    table = parse_provenance(text)
    assert pool_copy_bytes(table, (N, cfg.n_full_layers * N)) == 0
    wn = 1 + B * RB
    assert pool_copy_bytes(table, (wn, cfg.n_window_layers * wn)) == 0
    m = exe.memory_analysis()
    state_bytes = 2 * sum(a.size * 2 for a in state[:2])
    assert m.alias_size_in_bytes >= state_bytes
    peak = m.argument_size_in_bytes + m.output_size_in_bytes \
        - m.alias_size_in_bytes + m.temp_size_in_bytes
    assert 15.75 * (1 << 30) - peak >= 1.0 * (1 << 30), peak / (1 << 30)
    group = cfg.n_heads // cfg.kv_heads
    for dims in set(re.findall(r"f32\[([0-9,]+)\]", text)):
        shape = [int(d) for d in dims.split(",")]
        if NB * bs in shape:
            assert math.prod(shape) <= C * group * NB * bs, shape
    if program == "decode_slots":
        assert "paged_decode" in text
