"""Sizes a ``serve_qwen3_next`` configuration without the chip: compiles
both serving programs ahead of time for one described TPU v5e (libtpu
compiles for a topology it is told about) at the configuration's own sizes
with abstract arguments, and prints, per program, the compiler's
``memory_analysis`` (arguments, outputs, aliased, temporaries, peak, and
what is left of the chip's 15.75 GiB), whether any copy of a value shaped
like a K/V pool OR like the recurrent state or the tails was compiled in
(all four are donated and updated in place: a copy of the state alone is
1.4 GiB), and whether the prefill program holds a per-channel pair decay
(``[32, 64, 64, 128]`` float32: KDA's chunk form; the scalar-decay form has
none). The configuration's ``serving.sizing`` entry is this tool's output:

    python3 benchmark/tools/size_qwen3_next.py \\
        --config benchmark/configs/qwen3-next-80b-a3b-serve-ep16pp2.json \\
        [--slots 40] [--chunk 512] [--block 512] [--blocks 800]

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
CHIP_GIB = 15.75


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
    from deepspeed_tpu.inference import linear
    from deepspeed_tpu.inference.engine import InferenceEngine, _named
    from deepspeed_tpu.models import qwen3_next
    from deepspeed_tpu.telemetry.costs import (parse_provenance,
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
    N = (args.blocks or int(sv["num_blocks"])) + 1

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)

    params = jax.tree_util.tree_map(
        lambda a: S(a.shape, jnp.bfloat16),
        jax.eval_shape(lambda: qwen3_next.init_params(
            jax.random.PRNGKey(0), cfg)))
    weight_bytes = sum(a.size * 2 for a in jax.tree_util.tree_leaves(params))
    La, Ls = cfg.n_full_layers, cfg.n_recurrent_layers
    pool = S((La, N, bs, cfg.kv_heads * cfg.head_dim), jnp.bfloat16)
    state = linear.LinearState(
        pool, S((Ls, B) + tuple(cfg.recurrent_state_shape), jnp.float32),
        S((Ls, B, cfg.conv_tail_width), jnp.bfloat16))
    eng = InferenceEngine.__new__(InferenceEngine)
    eng.cfg, eng.max_seq_len, eng.dtype = cfg, cfg.max_seq_len, jnp.bfloat16
    eng.decode_impl = args.impl
    i32, f32, u32, V = jnp.int32, jnp.float32, jnp.uint32, cfg.vocab_size
    prefill = jax.jit(_named(eng._prefill_slot_fn, "serve_prefill_slot"),
                      donate_argnums=(1, 2))
    decode = jax.jit(_named(eng._decode_slots_fn, "serve_decode_slots"),
                     donate_argnums=(1, 2), static_argnums=(7,))
    programs = [
        ("prefill_slot", prefill,
         (params, state, pool, S((NB,), i32), S((C,), i32), S((), i32),
          S((), i32), S((2,), u32), S((), i32), S((), f32), S((), i32),
          S((), f32), S((), f32), S((V,), jnp.bool_), None, None,
          S((), i32))),
        ("decode_slots", decode,
         (params, state, pool, S((B, NB), i32), S((B,), i32),
          S((B,), i32), S((B,), jnp.bool_), args.impl, S((B, 2), u32),
          S((B,), i32), S((B,), f32), S((B,), i32), S((B,), f32),
          S((B,), f32), S((B, V), jnp.bool_)))]
    print(json.dumps({"slots": B, "chunk": C, "block": bs, "blocks": N - 1,
                      "weights_gib": weight_bytes / GIB,
                      "parameters": weight_bytes // 2,
                      "kv_pools_gib": 2 * 2 * pool.size / GIB,
                      "recurrent_state_bytes": 4 * state.state.size,
                      "recurrent_state_gib": 4 * state.state.size / GIB,
                      "conv_tails_gib": 2 * state.tail.size / GIB}))
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
            "pool_copy_bytes": pool_copy_bytes(table, (N, La * N)),
            # the state's and the tails' leading dimension: all layers'
            # slots (one layer's slots are also the decode batch, which
            # activations have)
            "state_copy_bytes": pool_copy_bytes(table, (Ls * B,)),
            "free_gib": CHIP_GIB - (
                m.argument_size_in_bytes + m.output_size_in_bytes
                - m.alias_size_in_bytes + m.temp_size_in_bytes) / GIB,
            # KDA's pair decay a key channel: absent from the scalar form
            "per_channel_pair_decay": "f32[%d,64,64,%d]" % (
                cfg.linear_value_heads, cfg.linear_head_dim) in text,
            "kernels_in_program": [k for k in ("paged_decode", "kda_step",
                                               "gmm") if k in text]}))


if __name__ == "__main__":
    main()
