"""One HBM layout for the paged KV pool, read off the compiled programs.

The two serving programs of the benchmark's cells are compiled ahead of
time for a TPU v5e from this CPU host (libtpu compiles for a described
topology without a chip), at the GPT-2 XL cell's own sizes with abstract
arguments and in the form the engine runs (behind the packed operand of
tests/test_dispatch_operands.py), and the executable is asked what the mechanism is: does any
``copy`` hold a pool-shaped value (``pool_copy_bytes``), does the layer
loop slice a layer's pool out or write it back, and how large are the
program's temporaries. Before the pools were folded to ``Hkv*Dh`` rows
and carried through the layer loop, ``serve_decode_slots`` read 5.9 GB of
pool copies and 6.24 GB of temporaries here, and the chip spent 68-74% of
its serving time in them (PERF.md, PR 25). The same reading for the
weights (``param_copy_bytes``): until the engine stored its looked-up
tables with whole rows of 128 lanes, both programs copied the 161 MB token
embedding and the 3.3 MB positional table on every dispatch (PERF.md,
PR 39). The dialects that keep a state beside the pools are compiled in
tests/test_pool_layout_aot_state.py, SmallThinker's cell in
tests/test_pool_layout_aot_smallthinker.py: a file a worker (``--dist
loadfile``), and each compiles its own programs once.
"""

import json
import math
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.engine import (InferenceEngine, _packed,
                                            pack_operands, whole_lane_tables)
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
    """(name, jitted program, abstract arguments, (N, L*N)) for the
    prefill and decode programs at the cell's sizes, AS THE ENGINE JITS
    THEM: behind the packed operand, with its jit options and the layouts
    its wrappers build (``InferenceEngine.__init__`` ``program``,
    ``prefill_into_slot`` / ``decode_slots``). The engine is a skeleton:
    the programs read its configuration and take the weights as an
    argument, so nothing of GPT-2 XL's size is ever allocated."""
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

    def ints(*shapes):
        return tuple(("i", np.zeros(shape)) for shape in shapes)

    def shelled(stem, fn, *parts):
        packed, layout = pack_operands(*parts)
        jitted = jax.jit(_packed(fn, f"serve_{stem}"),
                         static_argnames=("layout",),
                         donate_argnames=("k_pool", "v_pool", "scales"))
        return stem, jitted, (params, pool, pool,
                              S(packed.shape, jnp.int32), layout,
                              S((B, V), jnp.bool_))

    return (L, N), [
        shelled("prefill_slot", eng._prefill_slot_fn,
                *ints((NB,), (C,), (), ()),
                *InferenceEngine._samp_lanes(None, 1, V, scalar=True)[0]),
        shelled("decode_slots", eng._decode_slots_fn,
                *ints((B, NB), (B,), (B,)), ("b", np.zeros((B,))),
                ("static", "pallas"),
                *InferenceEngine._samp_lanes(None, B, V)[0])]


@pytest.fixture(scope="module")
def compiled_cell(v5e):
    """The one v5e compile of GPT-2 XL's two serving programs that every
    reader below (and nothing else in the suite) shares."""
    (L, N), programs = _cell_programs(v5e)
    out = {}
    for name, fn, args in programs:
        exe = fn.trace(*args).lower(lowering_platforms=("tpu",)).compile()
        out[name] = (exe, parse_provenance(exe.as_text()))
    return (L, N), out


@pytest.mark.parametrize("program", ["prefill_slot", "decode_slots"])
def test_shelled_programs_hold_no_copy_of_the_pool(compiled_cell, program):
    """The cell's two programs behind the packed operand: the shell of
    static slices and bitcasts is inside the SAME program, under the
    module name a profile finds it by, and copies nothing of the pool."""
    (L, N), exes = compiled_cell
    exe, table = exes[program]
    text = exe.as_text()
    assert f"HloModule jit_serve_{program}" in text
    assert pool_copy_bytes(table, (N, L * N)) == 0
    if program == "decode_slots":
        assert "paged_decode" in text
    assert exe.memory_analysis().temp_size_in_bytes < TEMP_LIMIT


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
