"""GPT-MoE training with expert parallelism — the GPT-MoE NLG workload
analog (ref: BASELINE.json config #5; reference wiring
DeepSpeedExamples Megatron-MoE via deepspeed/moe/layer.py).

Experts shard one-per-device over the data axes (GShard expert-data
parallelism); the per-layer dispatch all-to-all is emitted by XLA from
the shardings. Runs on one chip, a CPU mesh, or any slice:

  python examples/train_moe.py --steps 30
  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python examples/train_moe.py --experts 8
"""

import argparse
import sys
import time

sys.path.insert(0, ".")

import jax
import numpy as np

import deepspeed_tpu
from deepspeed_tpu.models import moe_gpt


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--experts", type=int, default=0,
                    help="0 = one expert per device")
    ap.add_argument("--top_k", type=int, default=1)
    args = ap.parse_args()

    n_dev = len(jax.devices())
    experts = args.experts or max(2, n_dev)
    cfg = moe_gpt.MoEGPTConfig(
        vocab_size=8192, n_layers=4, n_heads=8, d_model=256,
        max_seq_len=args.seq, num_experts=experts, moe_k=args.top_k,
        capacity_factor=1.25, use_flash_attention=True)
    params = moe_gpt.init_params(jax.random.PRNGKey(0), cfg)
    print(f"MoE GPT: {experts} experts over {n_dev} device(s), "
          f"top-{args.top_k}")

    ds_config = {
        "train_batch_size": args.batch,
        "bf16": {"enabled": True},
        "zero_optimization": {"stage": 1},
        "optimizer": {"type": "adamw", "params": {"lr": 3e-4}},
        "steps_per_print": 10,
    }
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=moe_gpt.make_loss_fn(cfg), model_parameters=params,
        config=ds_config,
        partition_rules=moe_gpt.moe_gpt_partition_rules())

    r = np.random.default_rng(0)
    base = r.zipf(1.5, size=(args.batch, args.seq + 1)).clip(
        0, cfg.vocab_size - 1)
    t0 = time.perf_counter()
    for step in range(args.steps):
        noise = r.integers(0, cfg.vocab_size, base.shape)
        keep = r.random(base.shape) < 0.9
        toks = np.where(keep, base, noise).astype(np.int32)
        m = engine.train_batch({"tokens": toks})
        if step % 10 == 0 or step == args.steps - 1:
            print(f"step {step:4d} loss {float(m['loss']):.4f}")
    dt = time.perf_counter() - t0
    print(f"{args.steps} steps in {dt:.1f}s "
          f"({args.steps * args.batch * args.seq / dt:.0f} tokens/s)")


if __name__ == "__main__":
    main()
