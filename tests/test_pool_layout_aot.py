"""One HBM layout for the paged KV pool, read off the compiled programs.

The two serving programs of the benchmark's cells are compiled ahead of
time for a TPU v5e from this CPU host (libtpu compiles for a described
topology without a chip), at the GPT-2 XL cell's own sizes with abstract
arguments, and the executable is asked what the mechanism is: does any
``copy`` hold a pool-shaped value (``pool_copy_bytes``), does the layer
loop slice a layer's pool out or write it back, and how large are the
program's temporaries. Before the pools were folded to ``Hkv*Dh`` rows
and carried through the layer loop, ``serve_decode_slots`` read 5.9 GB of
pool copies and 6.24 GB of temporaries here, and the chip spent 68-74% of
its serving time in them (PERF.md, PR 25). The same reading for the
weights (``param_copy_bytes``): until the engine stored its looked-up
tables with whole rows of 128 lanes, both programs copied the 161 MB token
embedding and the 3.3 MB positional table on every dispatch (PERF.md,
PR 39).
"""

import functools
import json
import math
import pathlib
import re

import jax
import jax.numpy as jnp
import pytest

from deepspeed_tpu.inference.engine import (InferenceEngine, _named,
                                            whole_lane_tables)
from deepspeed_tpu.inference.paged_cache import write_chunk
from deepspeed_tpu.models import gpt
from deepspeed_tpu.ops.attention.paged import blocks_per_step
from deepspeed_tpu.telemetry.costs import (ProgramCostRegistry,
                                           param_copy_bytes,
                                           parse_provenance,
                                           pool_copy_bytes, probe_compiled,
                                           scatter_windows, shape_dims)

CELL = json.loads((pathlib.Path(__file__).parent.parent / "benchmark"
                   / "configs" / "gpt2-xl-serve.json").read_text())

# what is left is activations and the sampler's work on the logits (a
# [17, 50257] float32 array is 3.4 MB): the decode program needs 16.2 MB,
# the prefill program 1.0 MB. Nothing of a table's size (161 MB) or the
# pool's (one layer's K pool is 56 MB, the stacked 5.4 GB)
TEMP_LIMIT = 32 << 20


@pytest.fixture(scope="module")
def v5e():
    """One described (not attached) v5e chip to compile for."""
    with pytest.MonkeyPatch.context() as mp:
        # libtpu reads these when the topology is described
        mp.setenv("TPU_ACCELERATOR_TYPE", "v5litepod-4")
        mp.setenv("TPU_WORKER_HOSTNAMES", "localhost")
        mp.setenv("TPU_SKIP_MDS_QUERY", "1")
        try:
            from jax.experimental import topologies
            dev = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2").devices[0]
        except Exception as e:  # no libtpu, or one that cannot describe it
            pytest.skip(f"no v5e topology to compile for: {e}")
    return jax.sharding.SingleDeviceSharding(dev)


def _cell_programs(sharding):
    """(name, jitted program, abstract arguments, (N, L*N)) for the plain
    prefill and decode programs at the cell's sizes. The engine is a
    skeleton: the programs read its configuration and take the weights
    as an argument, so nothing of GPT-2 XL's size is ever allocated."""
    m, sv = CELL["model"], CELL["serving"]
    cfg = gpt.GPTConfig(vocab_size=m["vocab_size"], n_layers=m["n_layer"],
                        n_heads=m["n_head"], d_model=m["n_embd"],
                        max_seq_len=m["n_positions"],
                        use_flash_attention=False, remat=False,
                        dtype=jnp.bfloat16)

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    # the weights as the engine stores them: bf16, the tables' lanes whole
    params = jax.tree_util.tree_map(
        lambda a: S(a.shape, jnp.bfloat16
                    if jnp.issubdtype(a.dtype, jnp.floating) else a.dtype),
        jax.eval_shape(lambda: whole_lane_tables(
            gpt.init_params(jax.random.PRNGKey(0), cfg))[0]))
    assert params["wte"]["embedding"].shape == (m["vocab_size"], 1664)
    eng = InferenceEngine.__new__(InferenceEngine)
    eng.cfg, eng.max_seq_len, eng.dtype = cfg, cfg.max_seq_len, jnp.bfloat16

    L, N, bs = cfg.n_layers, sv["num_blocks"] + 1, sv["block_size"]
    B, C, V = sv["num_slots"], sv["prefill_chunk"], cfg.vocab_size
    NB = cfg.max_seq_len // bs
    pool = S((L, N, bs, cfg.kv_heads * cfg.head_dim), jnp.bfloat16)
    i32, f32, u32 = jnp.int32, jnp.float32, jnp.uint32
    prefill = jax.jit(_named(eng._prefill_slot_fn, "serve_prefill_slot"),
                      donate_argnums=(1, 2))
    decode = jax.jit(_named(eng._decode_slots_fn, "serve_decode_slots"),
                     donate_argnums=(1, 2), static_argnums=(7,))
    return (L, N), [
        ("prefill_slot", prefill,
         (params, pool, pool, S((NB,), i32), S((C,), i32), S((), i32),
          S((), i32), S((2,), u32), S((), i32), S((), f32), S((), i32),
          S((), f32), S((), f32), S((V,), jnp.bool_))),
        ("decode_slots", decode,
         (params, pool, pool, S((B, NB), i32), S((B,), i32), S((B,), i32),
          S((B,), jnp.bool_), "pallas", S((B, 2), u32), S((B,), i32),
          S((B,), f32), S((B,), i32), S((B,), f32), S((B,), f32),
          S((B, V), jnp.bool_)))]


@pytest.fixture(scope="module")
def compiled_cell(v5e):
    (L, N), programs = _cell_programs(v5e)
    out = {}
    for name, fn, args in programs:
        exe = fn.trace(*args).lower(lowering_platforms=("tpu",)).compile()
        out[name] = (exe, parse_provenance(exe.as_text()))
    return (L, N), out


@pytest.mark.parametrize("program", ["prefill_slot", "decode_slots"])
def test_no_copy_of_the_pool_is_compiled_in(compiled_cell, program):
    (L, N), exes = compiled_cell
    _, table = exes[program]
    assert pool_copy_bytes(table, (N, L * N)) == 0
    # nor is a layer's pool sliced out of the stack or written back
    pooled = {n: e for n, e in table.items()
              if {N, L * N} & set(shape_dims(e["shape"])[1])}
    assert pooled, "no pool-shaped instruction: the shapes moved"
    moved = [n for n, e in pooled.items()
             if e["opcode"] in ("copy", "dynamic-slice",
                                "dynamic-update-slice")
             or "dynamic-slice" in n or "dynamic-update-slice" in n]
    assert not moved, moved
    # parameter ([L, N, ...]), loop state and the kernel's operand
    # ([L*N, ...]) share ONE layout: row-major, one tiling
    tilings = set()
    for e in pooled.values():
        if not e["shape"].startswith("bf16["):
            continue
        order, tiling = e["shape"].split("{", 1)[1].split("}")[0] \
            .split("S(")[0].split(":")
        order = [int(d) for d in order.split(",")]
        assert order == sorted(order, reverse=True), e["shape"]
        tilings.add(tiling)
    assert len(tilings) == 1, tilings


@pytest.mark.parametrize("program", ["prefill_slot", "decode_slots"])
def test_no_weight_is_copied(compiled_cell, program):
    """Every weight is read in the layout it is stored in: the token
    embedding by the row gather and by the tied head alike."""
    _, table = compiled_cell[1][program]
    assert param_copy_bytes(table) == 0
    # the reading is not blind: the parameters are there to be named
    assert any(e.get("param", "").startswith("params['wte']")
               for e in table.values()), "no user of the embedding found"


@pytest.mark.parametrize("program", ["prefill_slot", "decode_slots"])
def test_no_pool_sized_temporary(compiled_cell, program):
    exe, _ = compiled_cell[1][program]
    mem = probe_compiled(exe)
    assert mem["peak_bytes"] < TEMP_LIMIT, mem
    # the donated pools are updated in place: the outputs alias them
    sv = CELL["serving"]            # pool_bytes_logical: K and V, no trash
    pool_bytes = sv["pool_bytes_logical"] // sv["num_blocks"] \
        * (sv["num_blocks"] + 1)
    assert exe.memory_analysis().alias_size_in_bytes >= pool_bytes


def test_decode_program_attends_through_the_mosaic_kernel(compiled_cell):
    """ONE ``paged_decode`` call in the layer loop's body (the roofline
    readers multiply a call's bytes by the calls they count under that
    name), with the pool's one layout and no pool-sized temporary beside
    it, and the registry says how its grid is cut."""
    (L, N), exes = compiled_cell
    exe, table = exes["decode_slots"]
    calls = [n for n, e in table.items()
             if n.startswith("paged_decode") and e["opcode"] == "custom-call"]
    assert len(calls) == 1, calls
    assert pool_copy_bytes(table, (N, L * N)) == 0
    assert probe_compiled(exe)["peak_bytes"] < TEMP_LIMIT
    sv = CELL["serving"]
    B, bs = sv["num_slots"], sv["block_size"]
    NB = CELL["model"]["n_positions"] // bs
    P = blocks_per_step(NB, bs, 2 * CELL["model"]["n_embd"])
    assert 128 <= P * bs <= 256      # blocks of 16: the views cap the bytes
    reg = ProgramCostRegistry()
    reg.add_provenance("decode_slots", exe.as_text(), pool_blocks=(N, L * N),
                       paged_grid=(P, B * -(-NB // P)))
    entry = reg.to_json()["programs"]["decode_slots"]
    assert entry["pool_copy_bytes"] == 0
    assert entry["paged_blocks_per_step"] == P
    assert entry["paged_grid_steps"] == B * NB // P == 17 * 64 // P
    # the prefill program attends a gathered chunk: no kernel, no grid
    reg.add_provenance("prefill_slot", exes["prefill_slot"][0].as_text(),
                       pool_blocks=(N, L * N))
    assert "paged_grid_steps" not in reg.entries["prefill_slot"]


def test_prefill_program_gathers_the_occupied_part_of_a_row(compiled_cell):
    """Read off the compiled prefill program: every gather of pool rows
    lies in a branch of the ``lax.switch`` on the chunk's position, the
    branch taken at ``start`` 0 yields ONE tile of positions (128 of the
    row's 1,024), the lengths grow by a tile a branch, and only the last
    yields the whole row (PERF.md, PR 41: until then every chunk gathered
    and unfolded all 1,024, whatever was occupied)."""
    from deepspeed_tpu.inference.engine import attended_tiles
    _, table = compiled_cell[1]["prefill_slot"]
    m, sv = CELL["model"], CELL["serving"]
    bs, NB = sv["block_size"], m["n_positions"] // sv["block_size"]
    lo, hi, P = attended_tiles(0, sv["prefill_chunk"], bs, NB)
    assert (lo, hi, P * bs) == (0, 1, 128)
    lanes = m["n_embd"]                      # Hkv * Dh: a pool row
    gathered = {}
    for name, e in table.items():
        if "kv_gather" not in e.get("scope", "") \
                or not e["shape"].startswith("bf16["):
            continue
        branch = [part for part in e["scope"].split("/")
                  if part.startswith("branch_")]
        assert len(branch) == 1, (name, e["scope"])
        size = math.prod(shape_dims(e["shape"])[1])
        assert size % lanes == 0, (name, e["shape"])
        index = int(branch[0].split("_")[1])
        gathered[index] = max(gathered.get(index, 0), size // lanes)
    tiles = NB // P
    assert gathered == {n: (n + 1) * P * bs for n in range(tiles)}, gathered
    assert gathered[0] * 8 == gathered[tiles - 1] == NB * bs


def test_prefill_chunk_is_written_as_the_whole_blocks_it_touches(
        compiled_cell):
    """Read off the compiled programs: the prefill chunk's write into a pool
    is ONE scatter of at most 5 update windows, the whole blocks a run of 64
    rows can touch in blocks of 16 (``paged_cache.write_chunk``; until PR 48
    one window a row: 64, moved one after the other, 4.9% of the docs cell's
    device time a pool). The decode step's write is one row a slot."""
    sv = CELL["serving"]
    C, bs, B = sv["prefill_chunk"], sv["block_size"], sv["num_slots"]
    nblk = (C + bs - 2) // bs + 1
    assert nblk == 5
    exes = compiled_cell[1]
    windows = scatter_windows(exes["prefill_slot"][0].as_text(), "kv_write")
    assert len(windows) == 2 and max(windows) <= nblk, windows    # K and V
    # the reading is not blind
    assert scatter_windows(exes["decode_slots"][0].as_text(),
                           "kv_write") == [B, B]


@pytest.mark.parametrize("name,N,bs,lanes,NB,windows", [
    ("dotsvlm1", 6 * 769, 512, 640, 48, 5),
    ("longcat", 8 * 513, 512, 640, 12, 5),
    ("zaya1", 20 * 241, 1024, 256, 6, 2),
    ("jamba2", 2 * 4097, 512, 128, 24, 2)])
def test_a_large_block_is_written_in_windows_the_compiler_does_not_split(
        v5e, name, N, bs, lanes, NB, windows):
    """``write_chunk`` alone at the large-block cells' pools, a chunk of
    512: a gather whose slice passes 512 KiB (a latent block of 512 rows is
    640 KiB) is split by lanes, and each part first slices the POOL out
    (1.7 GiB of temporaries in the dots.vlm1 cell's prefill program, which
    ``pool_copy_bytes`` does not see: a ``slice``, not a ``copy``). In
    windows of at most ``WRITE_WINDOW_BYTES`` nothing pool-sized is made."""
    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    i32 = jnp.int32
    exe = jax.jit(write_chunk, donate_argnums=(0,)).trace(
        S((N, bs, lanes), jnp.bfloat16), S((NB,), i32), S((), i32),
        S((), i32), S((512, lanes), jnp.bfloat16), S((), i32)).lower(
        lowering_platforms=("tpu",)).compile()
    mem = exe.memory_analysis()
    assert mem.temp_size_in_bytes < 4 * 512 * lanes * 2, mem
    assert mem.alias_size_in_bytes == N * bs * lanes * 2
    assert scatter_windows(exe.as_text(), "") == [windows]


def test_scatter_windows_reads_fused_scatters_by_scope():
    text = """HloModule jit_serve_prefill_slot

%fused_computation.1 (p0: bf16[99,16,128], p1: s32[5], p2: bf16[5,16,128]) -> bf16[99,16,128] {
  %p0 = bf16[99,16,128]{2,1,0} parameter(0)
  %p1 = s32[5]{0} parameter(1)
  %p2 = bf16[5,16,128]{2,1,0:T(8,128)(2,1)} parameter(2)
  ROOT %scatter.1 = bf16[99,16,128]{2,1,0} scatter(%p0, %p1, %p2), update_window_dims={1,2}, inserted_window_dims={0}, scatter_dims_to_operand_dims={0}, index_vector_dim=1, to_apply=%region_0.1, metadata={op_name="jit(serve_prefill_slot)/while/body/kv_write/scatter"}
}

ENTRY %main.1 (a: bf16[99,16,128], b: s32[64,2], c: bf16[64,128]) -> bf16[99,16,128] {
  %a = bf16[99,16,128]{2,1,0} parameter(0)
  %b = s32[64,2]{1,0} parameter(1)
  %c = bf16[64,128]{1,0} parameter(2)
  ROOT %scatter.2 = bf16[99,16,128]{2,1,0} scatter(%a, %b, %c), update_window_dims={1}, inserted_window_dims={0,1}, scatter_dims_to_operand_dims={0,1}, index_vector_dim=1, to_apply=%region_0.1, metadata={op_name="jit(serve_prefill_slot)/sample/scatter"}
}
"""
    assert scatter_windows(text, "kv_write") == [5]
    assert scatter_windows(text, "sample") == [64]
    assert scatter_windows(text, "") == [5, 64]
    assert scatter_windows(text, "kv_gather") == []


def test_pool_copy_bytes_counts_pool_shaped_copies_only():
    table = {
        "copy.40": {"opcode": "copy",
                    "shape": "bf16[1,1089,16,25,64]{4,3,2,1,0:T(8,128)(2,1)}"},
        "copy.67": {"opcode": "copy",
                    "shape": "bf16[48,1089,16,25,64]{1,4,3,2,0:T(8,128)(2,1)}"},
        "copy.26": {"opcode": "copy", "shape": "f32[52272,25]{0,1:T(8,128)}"},
        "copy.14": {"opcode": "copy", "shape": "bf16[50257,1600]{1,0}"},
        "fusion.191": {"opcode": "fusion",
                       "shape": "bf16[52272,16,1600]{2,1,0}"},
        "tuple.1": {"opcode": "tuple", "shape": "(s32[], bf16[1089,16])"},
    }
    one = 1089 * 16 * 25 * 64 * 2
    assert pool_copy_bytes(table, (1089, 52272)) \
        == one + 48 * one + 52272 * 25 * 4
    assert pool_copy_bytes(table, ()) == 0
    assert pool_copy_bytes({}, (1089, 52272)) == 0


WTE, WPE = 50257 * 1600 * 2, 1024 * 1600 * 2


@pytest.mark.parametrize("entry,counted", [
    # the operand is an entry parameter under ``params``
    ({"opcode": "copy", "shape": "bf16[50257,1600]{1,0:T(8,128)(2,1)}",
      "param": "params['wte']['embedding']", "op": ""}, WTE),
    # the parameter reached the copy through a prefetch: its name rode along
    ({"opcode": "copy", "shape": "bf16[1024,1600]{1,0:T(8,128)(2,1)S(1)}",
      "op": "params[\\'wpe\\'][\\'embedding\\']"}, WPE),
    # another argument of the program (a pool, an operand) is not a weight
    ({"opcode": "copy", "shape": "bf16[48,1089,16,1600]{3,2,1,0}",
      "param": "k_pool", "op": ""}, 0),
    ({"opcode": "copy", "shape": "s32[]{:T(128)}", "param": "start",
      "op": ""}, 0),
    # a copy of an activation
    ({"opcode": "copy", "shape": "bf16[17,1,1,1600]{3,0,1,2}",
      "op": "transpose"}, 0),
    # a weight that is read, not copied
    ({"opcode": "fusion", "shape": "bf16[17,1,1664]{2,1,0}",
      "param": "params['wte']['embedding']", "op": "gather"}, 0),
], ids=["parameter", "named-after-parameter", "pool", "operand", "activation",
        "read"])
def test_param_copy_bytes_counts_copies_of_weights_only(entry, counted):
    assert param_copy_bytes({"x.1": entry}) == counted
    both = {"x.1": entry, "copy.9": {
        "opcode": "copy", "shape": "pred[8,2]{1,0}", "op": "",
        "param": "params['block']['mask']"}}
    assert param_copy_bytes(both) == counted + 16


def test_parse_provenance_names_the_parameter_an_instruction_reads():
    text = """HloModule jit_serve_decode_slots

ENTRY %main.1 (p0: bf16[64,192], p1: s32[4]) -> bf16[4,192] {
  %p0 = bf16[64,192]{0,1} parameter(0), metadata={op_name="params[\\'wte\\'][\\'embedding\\']"}
  %p1 = s32[4]{0} parameter(1), metadata={op_name="tokens"}
  %copy.1 = bf16[64,192]{1,0} copy(%p0), metadata={op_name="params[\\'wte\\'][\\'embedding\\']"}
  ROOT %gather.1 = bf16[4,192]{1,0} gather(%copy.1, %p1), metadata={op_name="jit(serve_decode_slots)/embed/gather"}
}
"""
    table = parse_provenance(text)
    assert table["copy.1"]["param"] == "params['wte']['embedding']"
    assert "param" not in table["gather.1"]
    assert param_copy_bytes(table) == 64 * 192 * 2


def test_registry_records_pool_copy_bytes_with_the_provenance():
    from deepspeed_tpu.telemetry.metrics import MetricsRegistry
    text = """HloModule jit_serve_decode_slots

ENTRY %main.1 (p0: bf16[4,9,4,32]) -> bf16[4,9,4,32] {
  %p0 = bf16[4,9,4,32]{3,2,1,0} parameter(0)
  ROOT %copy.1 = bf16[4,9,4,32]{1,3,2,0} copy(%p0)
}
"""
    reg = ProgramCostRegistry()
    metrics = MetricsRegistry()
    reg.export_gauges(metrics)
    assert reg.add_provenance("decode_slots", text, pool_blocks=(9, 36)) \
        == 4 * 9 * 4 * 32 * 2
    assert reg.to_json()["programs"]["decode_slots"]["pool_copy_bytes"] \
        == 9216
    assert metrics.gauge("program_pool_copy_bytes_decode_slots").value == 9216
    # p0 is no weight: it is not under the program's ``params``
    assert reg.entries["decode_slots"]["param_copy_bytes"] == 0
    # without the pool's block counts nothing is claimed of the pool
    assert reg.add_provenance("cow_blocks", text) == 0
    assert "pool_copy_bytes" not in reg.entries.get("cow_blocks", {})
    weight = text.replace("parameter(0)", "parameter(0), metadata={"
                          "op_name=\"params[\\'wte\\'][\\'embedding\\']\"}")
    reg.add_provenance("prefill_slot", weight, pool_blocks=(9, 36))
    assert reg.to_json()["programs"]["prefill_slot"]["param_copy_bytes"] \
        == 9216
    assert metrics.gauge("program_param_copy_bytes_prefill_slot").value \
        == 9216
    assert metrics.gauge("program_param_copy_bytes_decode_slots").value == 0


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
