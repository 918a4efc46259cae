"""Sizes a ``serve_longcat_flash`` configuration without the chip: compiles
both serving programs ahead of time for one described TPU v5e (libtpu
compiles for a topology it is told about) at the configuration's own sizes
with abstract arguments, and prints, per program, the compiler's
``memory_analysis`` (arguments, outputs, aliased, temporaries, peak),
whether any copy of a pool-shaped value was compiled in
(``pool_copy_bytes``), and whether a stacked kernel is re-laid: the bytes
of ``copy`` instructions that carry a weight's name (``param_copy_bytes``)
and every instruction that moves a weight-sized value outside a product
(``relaid``: a ``copy`` / ``transpose`` / ``dynamic-slice`` of 8 MB or more;
PERF.md 7(ah) found Kimi-Linear's query kernel sliced out of its stack and
re-laid every layer). The configuration's ``serving.sizing`` entry is this
tool's output:

    python3 benchmark/tools/size_longcat_flash.py \\
        --config benchmark/configs/longcat-flash-chat-serve-ep32.json \\
        [--slots 64] [--chunk 512] [--block 512] [--blocks 512]

Run on the CPU host (``JAX_PLATFORMS=cpu``). Not part of a cell's run."""

import argparse
import json
import os
import sys

os.environ.setdefault("TPU_ACCELERATOR_TYPE", "v5litepod-4")
os.environ.setdefault("TPU_WORKER_HOSTNAMES", "localhost")
os.environ.setdefault("TPU_SKIP_MDS_QUERY", "1")

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH_DIR)
sys.path.insert(0, os.path.dirname(BENCH_DIR))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from harness import cells  # noqa: E402

GIB = float(1 << 30)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--slots", type=int, default=None)
    ap.add_argument("--chunk", type=int, default=None)
    ap.add_argument("--block", type=int, default=None)
    ap.add_argument("--blocks", type=int, default=None)
    ap.add_argument("--impl", default="pallas")
    args = ap.parse_args()

    from jax.experimental import topologies
    from deepspeed_tpu.inference import latent
    from deepspeed_tpu.inference.engine import InferenceEngine, _named
    from deepspeed_tpu.models import longcat_flash
    from deepspeed_tpu.telemetry.costs import (_shape_bytes, param_copy_bytes,
                                               parse_provenance,
                                               pool_copy_bytes)
    dev = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0]
    sh = jax.sharding.SingleDeviceSharding(dev)
    conf = json.load(open(args.config))
    sv = conf["serving"]
    driver = cells.load_module(os.path.join(
        BENCH_DIR, "drivers", conf["kind"] + ".py"), "size_driver")
    cfg = driver.model_config(conf, jnp.bfloat16)
    B = args.slots or int(sv["num_slots"])
    C = args.chunk or int(sv["prefill_chunk"])
    bs = args.block or int(sv["block_size"])
    NB = -(-cfg.max_seq_len // bs)
    # the pool is smaller than slots x table: blocks are held as the mix
    # needs them
    N = (args.blocks or int(sv["num_blocks"])) + 1

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)

    params = jax.tree_util.tree_map(
        lambda a: S(a.shape, jnp.bfloat16),
        jax.eval_shape(lambda: longcat_flash.init_params(
            jax.random.PRNGKey(0), cfg)))
    weight_bytes = sum(a.size * 2 for a in jax.tree_util.tree_leaves(params))
    rows = S((cfg.n_full_layers, N, bs, cfg.latent_lanes), jnp.bfloat16)
    state = latent.LatentState(rows)
    eng = InferenceEngine.__new__(InferenceEngine)
    eng.cfg, eng.max_seq_len, eng.dtype = cfg, cfg.max_seq_len, jnp.bfloat16
    eng.decode_impl = args.impl
    i32, f32, u32, V = jnp.int32, jnp.float32, jnp.uint32, cfg.vocab_size
    prefill = jax.jit(_named(eng._prefill_slot_fn, "serve_prefill_slot"),
                      donate_argnums=(1,))
    decode = jax.jit(_named(eng._decode_slots_fn, "serve_decode_slots"),
                     donate_argnums=(1,), static_argnums=(7,))
    programs = [
        ("prefill_slot", prefill,
         (params, state, None, S((NB,), i32), S((C,), i32), S((), i32),
          S((), i32), S((2,), u32), S((), i32), S((), f32), S((), i32),
          S((), f32), S((), f32), S((V,), jnp.bool_))),
        ("decode_slots", decode,
         (params, state, None, S((B, NB), i32), S((B,), i32),
          S((B,), i32), S((B,), jnp.bool_), args.impl, S((B, 2), u32),
          S((B,), i32), S((B,), f32), S((B,), i32), S((B,), f32),
          S((B,), f32), S((B, V), jnp.bool_)))]
    print(json.dumps({"slots": B, "chunk": C, "block": bs,
                      "pool_blocks": N - 1, "table_entries": NB,
                      "latent_rows_per_token": cfg.n_full_layers,
                      "weights_gib": weight_bytes / GIB,
                      "parameters": weight_bytes // 2,
                      "latent_pool_gib": 2 * rows.size / GIB}))
    for name, fn, a in programs:
        exe = fn.trace(*a).lower(lowering_platforms=("tpu",)).compile()
        m = exe.memory_analysis()
        text = exe.as_text()
        table = parse_provenance(text)
        print(json.dumps({
            "program": name,
            "argument_gib": m.argument_size_in_bytes / GIB,
            "output_gib": m.output_size_in_bytes / GIB,
            "alias_gib": m.alias_size_in_bytes / GIB,
            "temp_gib": m.temp_size_in_bytes / GIB,
            "peak_gib": (m.argument_size_in_bytes + m.output_size_in_bytes
                         - m.alias_size_in_bytes + m.temp_size_in_bytes)
            / GIB,
            "pool_copy_bytes": pool_copy_bytes(
                table, (N, cfg.n_full_layers * N)),
            "param_copy_bytes": param_copy_bytes(table),
            "relaid": sorted(
                (f"{ins['opcode']} {ins['shape']} {ins.get('op', '')}"[:160]
                 for ins in table.values()
                 if ins["opcode"] in ("copy", "transpose", "dynamic-slice")
                 and _shape_bytes(ins["shape"]) >= 8 << 20)),
            "kernels_in_program": [k for k in ("mla_decode", "mla_prefill",
                                               "gmm") if k in text]}))


if __name__ == "__main__":
    main()
