"""The provenance table (telemetry/costs.py): which program, named scope
and source line a compiled instruction comes from; the stable program
names; the train step's compile counter; the HBM peak beside the FLOP
peak. All on the CPU backend."""

import json
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.inference.engine import InferenceEngine
from deepspeed_tpu.inference.serving import ServeRequest, ServingEngine
from deepspeed_tpu.models import gpt
from deepspeed_tpu.telemetry import Telemetry
from deepspeed_tpu.telemetry.costs import (ProgramCostRegistry,
                                           device_peak_flops,
                                           device_peak_hbm_bytes_per_s,
                                           parse_provenance)
from deepspeed_tpu.utils.compile_guard import CompileWatch

THIS_FILE = __file__


def _small_program():
    def f(p, pool, idx):
        with jax.named_scope("kv_write"):
            pool = pool.at[idx].set(p.sum())            # LINE_WRITE
        with jax.named_scope("mlp"):
            y = jnp.tanh(pool @ pool.T)                 # LINE_MLP
        return y, pool
    f.__name__ = "small_program"
    return jax.jit(f).lower(jnp.ones((4,)), jnp.zeros((64, 64)),
                            jnp.int32(3)).compile().as_text()


def _line_of(marker):
    with open(THIS_FILE) as fh:
        return next(i for i, ln in enumerate(fh, 1)
                    if ln.rstrip().endswith("# " + marker))


@pytest.mark.parametrize("opcode,op,scope,marker", [
    ("dot", "dot_general", "mlp", "LINE_MLP"),          # a plain op
    ("fusion", "tanh", "mlp", "LINE_MLP"),              # a fusion: its root
    ("fusion", "scatter", "kv_write", "LINE_WRITE"),
])
def test_parser_gives_scope_and_source_line(opcode, op, scope, marker):
    table = parse_provenance(_small_program())
    hits = [e for e in table.values()
            if e["opcode"] == opcode and e["op"] == op]
    assert hits, sorted((e["opcode"], e["op"]) for e in table.values())
    for e in hits:
        assert e["scope"] == scope and "inferred" not in e
        assert e["source"] == f"{THIS_FILE}:{_line_of(marker)}"
        assert e["shape"].startswith("f32[64,64]")


def test_parser_infers_compiler_inserted_copy_and_skips_fused_bodies():
    text = _small_program()
    table = parse_provenance(text)
    # the copy XLA puts in front of the in-place update has no metadata
    # of its own: it takes its user's scope, marked as inferred
    copies = [e for e in table.values() if e["opcode"] == "copy"
              and e["shape"].startswith("f32[64,64]")]
    assert copies and all(c.get("inferred") and c["scope"] == "kv_write"
                          for c in copies)
    # nothing from inside a fused computation, no parameters or constants
    assert not any(e["opcode"] in ("parameter", "constant")
                   for e in table.values())
    assert "fused_computation" in text
    json.dumps(table)


def test_parser_reads_inline_source_metadata():
    """The older text form: source_file/source_line inside metadata."""
    text = '''HloModule jit_old, is_scheduled=true

ENTRY %main (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  %copy.1 = f32[4]{0} copy(f32[4]{0} %p)
  ROOT %add.2 = f32[4]{0} add(%copy.1, %copy.1), metadata={op_name="jit(old)/while/body/attn_out/add" source_file="/x/model.py" source_line=42}
}
'''
    table = parse_provenance(text)
    assert table["add.2"] == {"opcode": "add", "shape": "f32[4]{0}",
                              "scope": "while/body/attn_out", "op": "add",
                              "source": "/x/model.py:42"}
    assert table["copy.1"]["inferred"] and \
        table["copy.1"]["scope"] == "while/body/attn_out"
    assert "p" not in table


def _tiny_engine(max_seq_len=64):
    cfg = gpt.GPTConfig(vocab_size=128, n_layers=2, n_heads=4, d_model=32,
                        max_seq_len=max_seq_len, use_flash_attention=False,
                        remat=False, dtype=jnp.float32)
    params = gpt.init_params(jax.random.PRNGKey(0), cfg)
    return InferenceEngine(config=cfg, params=params, dtype=jnp.float32)


def _serve(eng, telemetry):
    r = np.random.default_rng(1)
    srv = ServingEngine(eng, num_slots=2, block_size=4, num_blocks=24,
                        prefill_chunk=8, telemetry=telemetry)
    with CompileWatch() as watch:
        out = srv.run([ServeRequest(
            rid=i, prompt=r.integers(1, 128, n).astype(np.int32),
            max_new_tokens=4) for i, n in enumerate((9, 5))])
    return srv, out, watch.compiles


@pytest.fixture(scope="module")
def served(devices):
    off = _serve(_tiny_engine(), False)
    on = _serve(_tiny_engine(), True)
    return off, on


@pytest.mark.parametrize("pid,module,scopes", [
    ("prefill_slot", "jit_serve_prefill_slot",
     ("embed", "attn_qkv", "kv_write", "kv_gather", "paged_attn",
      "attn_out", "mlp", "logits", "sample")),
    ("decode_slots", "jit_serve_decode_slots",
     ("embed", "attn_qkv", "kv_write", "kv_gather", "paged_attn",
      "attn_out", "mlp", "logits", "sample")),
])
def test_serving_programs_have_stable_names_and_scopes(served, pid, module,
                                                       scopes):
    srv = served[1][0]
    prov = srv.cost_registry.provenance[pid]
    assert prov["module"] == module
    seen = {part for e in prov["instructions"].values()
            for part in e["scope"].split("/")}
    assert set(scopes) <= seen, sorted(seen)
    sources = {e["source"].rsplit(":", 1)[0]
               for e in prov["instructions"].values() if e["source"]}
    assert any(s.endswith("inference/engine.py") for s in sources)
    assert pid in json.loads(srv.cost_registry.dumps())["provenance"]


@pytest.mark.parametrize("bs,max_seq_len,per_step", [
    (4, 64, 16), (128, 2048, 8)], ids=["block4-table16", "block128-table16"])
def test_kernel_programs_say_how_the_paged_grid_is_cut(
        devices, pallas_interpret, bs, max_seq_len, per_step):
    """With telemetry on, a decode program asked for the kernel records
    how ``paged_decode``'s grid is cut (registry entry, gauges), and each
    ``serve.decode`` span the steps of it that run for the live slots:
    never more than the grid, and one a live slot here (16 blocks of 4
    tokens are one tile; of 16 blocks of 128 x 128 bytes a tile holds the
    8 that a step may view, where 128 tokens would make it one block). A
    gather program records neither."""
    from deepspeed_tpu.ops.attention.paged import blocks_per_step
    r = np.random.default_rng(1)
    reqs = [ServeRequest(rid=i, prompt=r.integers(1, 128, n).astype(np.int32),
                         max_new_tokens=4) for i, n in enumerate((9, 5))]
    tel = Telemetry(sample_every=1)     # kv_steps rides the sampled steps
    srv = ServingEngine(_tiny_engine(max_seq_len), num_slots=2,
                        block_size=bs, num_blocks=24, prefill_chunk=8,
                        telemetry=tel, decode_impl="pallas")
    srv.run(reqs)
    entry = srv.cost_registry.entries["decode_slots"]
    nb = srv.cache.blocks_per_slot
    P = blocks_per_step(nb, bs, srv.cache.tile_row_bytes)
    assert (nb, srv.cache.tile_row_bytes, P) == (16, 128, per_step)
    assert entry["paged_blocks_per_step"] == P
    assert entry["paged_grid_steps"] == 2 * -(-nb // P)
    # (pool_copy_bytes is the compiled chip program's to show: the
    # interpreter this CPU run takes the kernel through copies its operands)
    assert "paged_grid_steps" not in srv.cost_registry.entries["prefill_slot"]
    gauge = srv.telemetry.registry.gauge
    assert gauge("program_paged_grid_steps_decode_slots").value \
        == entry["paged_grid_steps"]
    assert gauge("program_paged_blocks_per_step_decode_slots").value == P
    decodes = [s[5] for s in tel.tracer.spans() if s[1] == "serve.decode"
               and s[5].get("live")]
    assert decodes
    for attrs in decodes:
        assert attrs["kv_steps"] == attrs["live"] * 1
        assert attrs["kv_steps"] <= entry["paged_grid_steps"]
        assert attrs["blocks"] <= attrs["kv_steps"] * P * 2
    off = ServingEngine(_tiny_engine(max_seq_len), num_slots=2,
                        block_size=bs, num_blocks=24, prefill_chunk=8,
                        telemetry=Telemetry())
    off.run([ServeRequest(rid=9, prompt=reqs[0].prompt.copy(),
                          max_new_tokens=2)])
    assert "paged_grid_steps" not in off.cost_registry.entries["decode_slots"]


def test_provenance_costs_no_compile_and_nothing_when_off(served):
    (srv_off, out_off, compiles_off), (srv_on, out_on, compiles_on) = served
    # lowering with the dispatch's own arguments compiles each program
    # once, as the plain call does
    assert compiles_on == compiles_off
    assert srv_off.cost_registry is None
    assert srv_off.engine.provenance is None
    assert set(srv_on.cost_registry.provenance) == {"prefill_slot",
                                                    "decode_slots"}
    for rid in out_off:
        np.testing.assert_array_equal(out_on[rid], out_off[rid])


def test_train_compiles_counts_a_forced_second_compile(devices):
    def loss_fn(p, b, rng):
        return jnp.mean((b["x"].mean(axis=-1) * p["w"] - b["y"]) ** 2)
    cfg = {"train_batch_size": 8,
           "optimizer": {"type": "adamw", "params": {"lr": 1e-2}}}
    eng, *_ = deepspeed_tpu.initialize(
        model=loss_fn, model_parameters={"w": jnp.ones(())}, config=cfg)
    assert eng.train_compiles == 0

    def batch(width):
        return {"x": np.ones((8, width), np.float32),
                "y": np.zeros((8,), np.float32)}
    eng.train_batch(batch(16))
    first = eng.train_compiles
    assert first >= 1
    eng.train_batch(batch(16))
    assert eng.train_compiles == first          # steady: nothing compiles
    eng.train_batch(batch(24))                  # a new shape recompiles
    assert eng.train_compiles > first


@pytest.mark.parametrize("kind,flops,hbm", [
    ("TPU v5 lite", 197e12, 819e9),
    ("TPU v4", 275e12, None),           # no source on record: no default
    ("cpu", None, None),
])
def test_hbm_peak_beside_flop_peak(kind, flops, hbm):
    dev = types.SimpleNamespace(device_kind=kind)
    assert device_peak_flops(dev) == flops
    assert device_peak_hbm_bytes_per_s(dev) == hbm
    reg = ProgramCostRegistry()
    reg.entries["decode_slots"] = {"flops": 197e12, "bytes_accessed": 819e9}
    roof = reg.roofline("decode_slots", dev)
    if hbm is None:
        assert roof is None
    else:
        assert roof["min_seconds"] == 1.0 and roof["bound"] == "compute"
