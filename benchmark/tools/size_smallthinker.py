"""Sizes a ``serve_smallthinker`` configuration without the chip: compiles
both serving programs ahead of time for one described TPU v5e (libtpu
compiles for a topology it is told about) at the configuration's own sizes
with abstract arguments, and prints, per program, the compiler's
``memory_analysis`` (arguments, outputs, aliased, temporaries, peak) and
whether any copy of a pool-shaped value was compiled in. The
configuration's ``serving.sizing`` entry is this tool's output:

    python3 benchmark/tools/size_smallthinker.py \\
        --config benchmark/configs/smallthinker-21b-a3b-serve-pp4.json \\
        [--slots 24] [--chunk 512] [--block 128]

``free_gib`` is what is left of a v5e's 15.75 GiB at the program's peak;
``score_tensor_bytes`` the largest float32 value shaped like a chunk's
attention scores over a full layer's whole row for MORE than one KV head
(0: a full layer attends a KV head at a time).

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
HBM_GIB = 15.75         # what a v5e chip leaves a program (harness/peaks.py)


def score_tensor_bytes(text: str, chunk: int, group: int, row: int) -> int:
    """Bytes of the largest float32 value in the compiled text whose shape
    holds ``chunk`` queries and a whole ``row`` of keys for more than
    ``group`` query heads (one KV head's)."""
    import re
    worst = 0
    for dims in set(re.findall(r"f32\[([0-9,]+)\]", text)):
        shape = [int(x) for x in dims.split(",")]
        if row in shape and chunk in shape:
            size = 4
            for x in shape:
                size *= x
            if size > 4 * chunk * group * row:
                worst = max(worst, size)
    return worst


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--slots", type=int, default=None)
    ap.add_argument("--chunk", type=int, default=None)
    ap.add_argument("--block", type=int, default=None)
    ap.add_argument("--impl", default="pallas")
    args = ap.parse_args()

    from jax.experimental import topologies
    from deepspeed_tpu.inference import hybrid
    from deepspeed_tpu.inference.engine import InferenceEngine, _named
    from deepspeed_tpu.models import exaone_moe, smallthinker
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
    NB = cfg.max_seq_len // bs
    N = B * NB + 1
    RB = exaone_moe.window_blocks(cfg, bs)

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)

    params = jax.tree_util.tree_map(
        lambda a: S(a.shape, jnp.bfloat16),
        jax.eval_shape(lambda: smallthinker.init_params(
            jax.random.PRNGKey(0), cfg)))
    weight_bytes = sum(a.size * 2 for a in jax.tree_util.tree_leaves(params))
    row = cfg.kv_heads * cfg.head_dim
    full = S((cfg.n_full_layers, N, bs, row), jnp.bfloat16)
    win = S((cfg.n_window_layers, 1 + B * RB, bs, row), jnp.bfloat16)
    state = hybrid.PagedState(full, win)
    state_bytes = 2 * 2 * (full.size + win.size)
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
         (params, state, state, S((NB + RB,), i32), S((C,), i32), S((), i32),
          S((), i32), S((2,), u32), S((), i32), S((), f32), S((), i32),
          S((), f32), S((), f32), S((V,), jnp.bool_))),
        ("decode_slots", decode,
         (params, state, state, S((B, NB + RB), i32), S((B,), i32),
          S((B,), i32), S((B,), jnp.bool_), args.impl, S((B, 2), u32),
          S((B,), i32), S((B,), f32), S((B,), i32), S((B,), f32),
          S((B,), f32), S((B, V), jnp.bool_)))]
    print(json.dumps({"slots": B, "chunk": C, "block": bs,
                      "ring_blocks": RB, "weights_gib": weight_bytes
                      / GIB, "kv_state_gib": state_bytes / GIB,
                      "full_pool_gib": 4 * full.size / GIB,
                      "window_state_gib": 4 * win.size / GIB}))
    for name, fn, a in programs:
        exe = fn.trace(*a).lower(lowering_platforms=("tpu",)).compile()
        m = exe.memory_analysis()
        text = exe.as_text()
        table = parse_provenance(text)
        peak = (m.argument_size_in_bytes + m.output_size_in_bytes
                - m.alias_size_in_bytes + m.temp_size_in_bytes)
        print(json.dumps({
            "program": name,
            "argument_gib": m.argument_size_in_bytes / GIB,
            "output_gib": m.output_size_in_bytes / GIB,
            "alias_gib": m.alias_size_in_bytes / GIB,
            "temp_gib": m.temp_size_in_bytes / GIB,
            "peak_gib": peak / GIB, "free_gib": HBM_GIB - peak / GIB,
            "score_tensor_bytes": score_tensor_bytes(
                text, C, cfg.n_heads // cfg.kv_heads, NB * bs),
            "full_pool_copy_bytes": pool_copy_bytes(
                table, (N, cfg.n_full_layers * N)),
            "window_pool_copy_bytes": pool_copy_bytes(
                table, (1 + B * RB, cfg.n_window_layers * (1 + B * RB)))}))


if __name__ == "__main__":
    main()
