"""The serving programs of the dialects that keep a per-slot state beside
the paged pools (Kimi-Linear's recurrent state and tails, Jamba's
state-space state, Qwen3-Next's at its cell's sizes), compiled ahead of
time for a TPU v5e from this CPU host and read as
tests/test_pool_layout_aot.py reads GPT-2 XL's: no copy of the state or
of a pool is compiled in, and a sparse layer makes its group metadata
once."""

import functools
import json
import pathlib
import re

import jax
import jax.numpy as jnp
import pytest

from deepspeed_tpu.inference.engine import InferenceEngine, _named
from deepspeed_tpu.telemetry.costs import (parse_provenance, pool_copy_bytes,
                                           scatter_windows)

from test_pool_layout_aot import v5e  # noqa: F401 (a fixture)


@functools.lru_cache(maxsize=None)
def _kimi_linear_compiled(v5e, program):
    """The linear-attention dialect's serving program compiled for a v5e
    with the Mosaic kernels, at the published head sizes (the tiling is
    theirs) and few layers, slots and experts, runs of 1 and 3 linear
    layers and one behind the last latent layer: (executable, its text,
    the state's buffers, (N, Lm, Lk, B, C, bs), the config)."""
    from deepspeed_tpu.inference import linear
    from deepspeed_tpu.models import kimi_linear
    cfg = kimi_linear.KimiLinearConfig(
        vocab_size=512, n_layers=8, n_heads=4, d_model=256, d_ff=512,
        max_seq_len=512, dtype=jnp.bfloat16, kda_layers=(1, 2, 4, 5, 6, 8),
        full_attn_layers=(3, 7), linear_heads=16, num_experts=8, moe_k=2,
        moe_d_ff=128, experts_held=(0, 4), use_flash_attention=False,
        remat=False)
    B, C, bs = 8, 128, 128
    NB = cfg.max_seq_len // bs
    N = B * NB + 1

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    params = jax.tree_util.tree_map(
        lambda a: S(a.shape, jnp.bfloat16), jax.eval_shape(
            lambda: kimi_linear.init_params(jax.random.PRNGKey(0), cfg)))
    Lm, Lk, H, Dh = 2, 6, 16, 128
    state = linear.LinearState(
        S((Lm, N, bs, cfg.latent_lanes), jnp.bfloat16),
        S((Lk, B, H, Dh, Dh), jnp.float32),
        S((Lk, B, 3 * cfg.kda_channels), jnp.bfloat16))
    eng = InferenceEngine.__new__(InferenceEngine)
    eng.cfg, eng.max_seq_len, eng.dtype = cfg, cfg.max_seq_len, jnp.bfloat16
    eng.decode_impl = "pallas"
    i32, f32, u32, V = jnp.int32, jnp.float32, jnp.uint32, cfg.vocab_size
    if program == "prefill_slot":
        fn = jax.jit(_named(eng._prefill_slot_fn, "serve_prefill_slot"),
                     donate_argnums=(1, 2))
        args = (params, state, None, S((NB,), i32), S((C,), i32), S((), i32),
                S((), i32), S((2,), u32), S((), i32), S((), f32), S((), i32),
                S((), f32), S((), f32), S((V,), jnp.bool_), None, None,
                S((), i32))
    else:
        fn = jax.jit(_named(eng._decode_slots_fn, "serve_decode_slots"),
                     donate_argnums=(1, 2), static_argnums=(7,))
        args = (params, state, None, S((B, NB), i32), S((B,), i32),
                S((B,), i32), S((B,), jnp.bool_), "pallas", S((B, 2), u32),
                S((B,), i32), S((B,), f32), S((B,), i32), S((B,), f32),
                S((B,), f32), S((B, V), jnp.bool_))
    exe = fn.trace(*args).lower(lowering_platforms=("tpu",)).compile()
    return exe, exe.as_text(), state, (N, Lm, Lk, B, C, bs), cfg


@pytest.mark.parametrize("program", ["prefill_slot", "decode_slots"])
def test_no_copy_of_the_state_or_the_pool_is_compiled_in(v5e, program):
    """The same reading for the linear-attention dialect's three buffers
    (inference/linear.py): the serving program compiled ahead of time for a v5e, with the
    Mosaic kernels, at the published head sizes (the tiling is theirs) and
    few layers, slots and experts, runs of 1 and 3 linear layers and one
    behind the last latent layer. No ``copy`` holds a value shaped like the
    latent pool, the recurrent state or the convolution tails, and all
    three are updated in place (a ``lax.cond`` on the layer's kind copied
    the whole state in the latent branch; a fused shifted read of the tail
    copied the tails in and out: PERF.md, PR 40)."""
    exe, text, state, (N, Lm, Lk, B, C, bs), _ = _kimi_linear_compiled(
        v5e, program)
    table = parse_provenance(text)
    # the pool's blocks (one layer's, all layers'), the state's and the
    # tails' leading dimension (all layers' slots: 48)
    assert pool_copy_bytes(table, (N, Lm * N)) == 0
    assert pool_copy_bytes(table, (Lk * B,)) == 0
    buffers = sum(a.size * a.dtype.itemsize for a in state[:3])
    assert exe.memory_analysis().alias_size_in_bytes >= buffers
    assert exe.memory_analysis().temp_size_in_bytes < buffers // 4
    if program == "decode_slots":
        assert "kda_step" in text and "mla_decode" in text
    else:       # the chunk's rows go into the pool as whole blocks
        assert 0 < max(scatter_windows(text, "kv_write")) \
            <= (C + bs - 2) // bs + 1 == 2


@pytest.mark.parametrize("program", ["prefill_slot", "decode_slots"])
def test_state_space_state_is_stored_unpadded_and_never_copied(v5e, program):
    """The same reading for the state-space rule's four buffers
    (inference/ssm.py through linear.py's loop): the serving program
    compiled ahead of time for a v5e, with the Mosaic kernels, at the
    published widths of the mixer (5,120 channels, a state of 16: the
    tiling is theirs) and few layers and slots, runs of 1 and 2 state-space
    layers and one behind the last attention layer. The state is stored
    ``[16, 5120]`` a slot a layer in tiles of (8, 128), which those sizes
    fill: it costs the bytes it holds (as published, ``[5120, 16]``, the 16
    would pad to 128 lanes: 8 times as much). No ``copy`` holds a value
    shaped like a pool, the state or the tails, and all four are updated in
    place."""
    from deepspeed_tpu.inference import linear
    from deepspeed_tpu.models import jamba
    cfg = jamba.JambaConfig(
        vocab_size=512, n_layers=6, n_heads=20, n_kv_heads=1, d_model=2560,
        d_ff=512, max_seq_len=12288, dtype=jnp.bfloat16,
        attn_layer_period=3, attn_layer_offset=1, use_flash_attention=False,
        remat=False)
    # the cell's own chunk, block and row of 24 blocks (8 read lengths)
    B, C, bs = 8, 512, 512
    NB = cfg.max_seq_len // bs
    N = B * NB + 1

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    params = jax.tree_util.tree_map(
        lambda a: S(a.shape, jnp.bfloat16), jax.eval_shape(
            lambda: jamba.init_params(jax.random.PRNGKey(0), cfg)))
    La, Ls = 2, 4
    assert cfg.recurrent_state_shape == (16, 5120)
    pool = S((La, N, bs, 128), jnp.bfloat16)
    state = linear.LinearState(
        pool, S((Ls, B, 16, 5120), jnp.float32),
        S((Ls, B, 3 * 5120), jnp.bfloat16))
    eng = InferenceEngine.__new__(InferenceEngine)
    eng.cfg, eng.max_seq_len, eng.dtype = cfg, cfg.max_seq_len, jnp.bfloat16
    eng.decode_impl = "pallas"
    i32, f32, u32, V = jnp.int32, jnp.float32, jnp.uint32, cfg.vocab_size
    if program == "prefill_slot":
        fn = jax.jit(_named(eng._prefill_slot_fn, "serve_prefill_slot"),
                     donate_argnums=(1, 2))
        args = (params, state, pool, S((NB,), i32), S((C,), i32), S((), i32),
                S((), i32), S((2,), u32), S((), i32), S((), f32), S((), i32),
                S((), f32), S((), f32), S((V,), jnp.bool_), None, None,
                S((), i32))
    else:
        fn = jax.jit(_named(eng._decode_slots_fn, "serve_decode_slots"),
                     donate_argnums=(1, 2), static_argnums=(7,))
        args = (params, state, pool, S((B, NB), i32), S((B,), i32),
                S((B,), i32), S((B,), jnp.bool_), "pallas", S((B, 2), u32),
                S((B,), i32), S((B,), f32), S((B,), i32), S((B,), f32),
                S((B,), f32), S((B, V), jnp.bool_))
    exe = fn.trace(*args).lower(lowering_platforms=("tpu",)).compile()
    text = exe.as_text()
    # the state as the program takes it: minor dimensions (16, 5120) in
    # tiles of (8, 128), which they fill (no padding)
    layouts = set(re.findall(r"f32\[4,8,16,5120\]\{([^}]*)\}", text))
    assert layouts and all(l.startswith("3,2,1,0:T(8,128)")
                           for l in layouts), layouts
    table = parse_provenance(text)
    assert pool_copy_bytes(table, (N, La * N)) == 0
    assert pool_copy_bytes(table, (Ls * B,)) == 0
    buffers = 2 * pool.size * 2 + state.state.size * 4 + state.tail.size * 2
    mem = exe.memory_analysis()
    assert mem.alias_size_in_bytes >= buffers
    # the arguments are the weights and the buffers at the bytes they hold
    weights = sum(a.size * 2 for a in jax.tree_util.tree_leaves(params))
    assert mem.argument_size_in_bytes < 1.02 * (weights + buffers) + (1 << 20)
    if program == "decode_slots":
        assert mem.temp_size_in_bytes < buffers // 4
        assert "ssm_step" in text and "paged_decode" in text
    else:
        # the dense read's scores at the longest of its 8 lengths, 20 heads
        # x 512 queries x 12,288 keys (503 MB in float32), are the largest
        # temporary: nothing of a buffer's size beside them
        assert mem.temp_size_in_bytes < 20 * C * cfg.max_seq_len * 4
        assert "ssm_scan" in text
        # the chunk's K and V go into their pools as whole blocks
        assert 0 < max(scatter_windows(text, "kv_write")) \
            <= (C + bs - 2) // bs + 1 == 2


QWEN3_NEXT = json.loads((
    pathlib.Path(__file__).parent.parent / "benchmark" / "configs"
    / "qwen3-next-80b-a3b-serve-ep16pp2.json").read_text())


@functools.lru_cache(maxsize=None)
def _qwen3_next_compiled(v5e, program):
    """Qwen3-Next's serving program compiled for a v5e at the cell's own
    sizes, 24 layers and the configuration's slots, chunk and pool:
    (executable, its text, the pool, the state, (N, La, Lg, B), config)."""
    from deepspeed_tpu.inference import linear
    from deepspeed_tpu.models import qwen3_next
    c, sv = QWEN3_NEXT, QWEN3_NEXT["serving"]
    cfg = qwen3_next.Qwen3NextConfig(
        vocab_size=c["vocab_size"], n_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], max_seq_len=sv["max_total"],
        dtype=jnp.bfloat16, experts_held=(0, c["num_experts"]),
        num_experts=c["published"]["num_experts"],
        use_flash_attention=False, remat=False)
    assert qwen3_next.num_params(cfg) == c["parameters_held_here"]
    B, C, bs = sv["num_slots"], sv["prefill_chunk"], sv["block_size"]
    NB = cfg.max_seq_len // bs
    N = sv["num_blocks"] + 1
    La, Lg = cfg.n_full_layers, cfg.n_recurrent_layers
    assert (La, Lg, cfg.kv_heads * cfg.head_dim) == (6, 18, 512)

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    params = jax.tree_util.tree_map(
        lambda a: S(a.shape, jnp.bfloat16), jax.eval_shape(
            lambda: qwen3_next.init_params(jax.random.PRNGKey(0), cfg)))
    pool = S((La, N, bs, 512), jnp.bfloat16)
    state = linear.LinearState(
        pool, S((Lg, B, 32, 128, 128), jnp.float32),
        S((Lg, B, 3 * 8192), jnp.bfloat16))
    eng = InferenceEngine.__new__(InferenceEngine)
    eng.cfg, eng.max_seq_len, eng.dtype = cfg, cfg.max_seq_len, jnp.bfloat16
    eng.decode_impl = "pallas"
    i32, f32, u32, V = jnp.int32, jnp.float32, jnp.uint32, cfg.vocab_size
    if program == "prefill_slot":
        fn = jax.jit(_named(eng._prefill_slot_fn, "serve_prefill_slot"),
                     donate_argnums=(1, 2))
        args = (params, state, pool, S((NB,), i32), S((C,), i32), S((), i32),
                S((), i32), S((2,), u32), S((), i32), S((), f32), S((), i32),
                S((), f32), S((), f32), S((V,), jnp.bool_), None, None,
                S((), i32))
    else:
        fn = jax.jit(_named(eng._decode_slots_fn, "serve_decode_slots"),
                     donate_argnums=(1, 2), static_argnums=(7,))
        args = (params, state, pool, S((B, NB), i32), S((B,), i32),
                S((B,), i32), S((B,), jnp.bool_), "pallas", S((B, 2), u32),
                S((B,), i32), S((B,), f32), S((B,), i32), S((B,), f32),
                S((B,), f32), S((B, V), jnp.bool_))
    exe = fn.trace(*args).lower(lowering_platforms=("tpu",)).compile()
    return exe, exe.as_text(), pool, state, (N, La, Lg, B), cfg


@pytest.mark.parametrize("program", ["prefill_slot", "decode_slots"])
def test_qwen3_next_cell_programs_fit_a_v5e(v5e, program):
    """Qwen3-Next's two serving programs (the linear dialect's delta rule
    with one decay a head beside the engine's gated K/V attention) compiled
    for a v5e at the cell's own sizes, 24 layers and the configuration's
    slots, chunk and pool: no copy of a pool, of the recurrent state or of
    the tails, all four updated in place; the chunk form ONE Mosaic kernel
    under ``gdn_chunk`` (it lowers for a v5e at 32 value heads on 16 key
    heads of 128 and a chunk of 512), so neither a per-channel pair decay
    (``[32, 64, 64, 128]``: KDA's chunk form) nor the XLA form's ``[..,
    64, 64]`` pairs and 8 MiB ``[8, 32, 64, 128]`` float32 temporaries are
    in the program; and at least 1.0 GiB of the chip's 15.75 left at the
    program's peak."""
    exe, text, pool, state, (N, La, Lg, B), _ = _qwen3_next_compiled(
        v5e, program)
    table = parse_provenance(text)
    assert pool_copy_bytes(table, (N, La * N)) == 0
    assert pool_copy_bytes(table, (Lg * B,)) == 0
    buffers = 2 * pool.size * 2 + state.state.size * 4 + state.tail.size * 2
    m = exe.memory_analysis()
    assert m.alias_size_in_bytes >= buffers
    peak = m.argument_size_in_bytes + m.output_size_in_bytes \
        - m.alias_size_in_bytes + m.temp_size_in_bytes
    assert 15.75 * (1 << 30) - peak >= 1.0 * (1 << 30), peak / (1 << 30)
    if program == "decode_slots":
        assert "kda_step" in text and "paged_decode" in text
    else:
        # the kernel keeps a sub-chunk's pairs, its solve and the walk over
        # the sub-chunks in VMEM: what the XLA form stacked in HBM is gone
        assert re.search(r'custom_call_target="tpu_custom_call"[^\n]*'
                         r"gdn_chunk/gdn_chunk/pallas_call", text)
        assert not re.search(r"f32\[(\d+,)*64,64(,128)?\]", text)
        assert not re.search(r"f32\[8,32,64,(128|256)\]", text)


_HLO_LINE = re.compile(r"\s*(?:ROOT )?%([\w.\-]+) = (\S+) ([\w\-]+)\(")


@pytest.mark.parametrize("program", ["prefill_slot", "decode_slots"])
@pytest.mark.parametrize("config", ["qwen3_next", "kimi_linear"])
def test_a_sparse_layer_makes_its_group_metadata_once(v5e, config, program):
    """The expert layer in the compiled v5e programs (Qwen3-Next's at the
    cell's sizes: 24 sparse layers of 32 held experts; the linear dialect's
    small ones: 7 of 4): a layer body's three grouped products take the
    SAME metadata operands (the visited tiles' count, the group offsets,
    each tile's group and row tile, the layer's base into the stack), made
    over the layer's own ``count`` groups; nothing under ``moe_experts``
    searches or is as long as the stack's ``layers * count`` groups
    (megablox's metadata, made inside each product over the whole stack,
    was: PERF.md, PR 57); and the stack reaches the kernel as the loop's
    own value, never copied or sliced."""
    if config == "qwen3_next":
        _, text, _, _, _, cfg = _qwen3_next_compiled(v5e, program)
        tokens = QWEN3_NEXT["serving"]["prefill_chunk" if program
                                       == "prefill_slot" else "num_slots"]
    else:
        _, text, _, (_, _, _, B, C, _), cfg = _kimi_linear_compiled(
            v5e, program)
        tokens = C if program == "prefill_slot" else B
    count = cfg.held[1]
    n_sparse = cfg.n_layers - cfg.n_dense_layers
    d, f = cfg.d_model, cfg.moe_d_ff
    shape_of, opcode_of, calls = {}, {}, []
    for line in text.splitlines():
        m = _HLO_LINE.match(line)
        if m is None:
            continue
        shape_of[m.group(1)], opcode_of[m.group(1)] = m.group(2), m.group(3)
        if m.group(3) == "custom-call" and "tpu_custom_call" in line \
                and "moe_experts/gmm" in line:
            calls.append(re.findall(r"%([\w.\-]+)", line[:line.index(
                "custom_call_target")].split("custom-call(", 1)[1]))
    # a layer body: the layers behind a dense one and the runs between
    # attention kinds compile to loops of their own
    bodies = {tuple(c[:5]) for c in calls}
    assert calls and len(calls) == 3 * len(bodies), (len(calls), bodies)
    tiles = -(-tokens * cfg.moe_k // 128)
    for _, offsets, group_ids, m_tile_ids, _ in bodies:
        assert shape_of[offsets].startswith(f"s32[{count + 1}]")
        assert shape_of[group_ids].startswith(f"s32[{tiles + count - 1}]")
        assert shape_of[m_tile_ids].startswith(f"s32[{tiles + count - 1}]")
    scoped = [ln for ln in text.splitlines() if "moe_experts" in ln]
    assert not any("searchsorted" in ln for ln in scoped)
    assert not any(re.search(rf"\[{n_sparse * count}\]", ln) for ln in scoped)
    # the experts: the stack [layers * count, a, b] or a layer's [count, a,
    # b] is nowhere the result of a copy, a slice or a fusion
    stack = re.compile(rf"bf16\[({n_sparse * count}|{count}),"
                       rf"({d},{f}|{f},{d})\]")
    moved = {n: opcode_of[n] for n, s in shape_of.items() if stack.match(s)
             and opcode_of[n] in ("copy", "fusion", "slice", "dynamic-slice",
                                  "dynamic-update-slice")}
    assert not moved, moved
    weights = {c[-1] for c in calls}
    assert all(stack.match(shape_of[w]) for w in weights), weights
