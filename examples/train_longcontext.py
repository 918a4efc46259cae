"""Long-context GPT training with ring sequence parallelism.

The reference's long-sequence story is block-sparse attention (ref:
README.md:38 "10x longer sequences"); this framework's is EXACT
attention over a sequence sharded across chips: each device holds S/n
tokens, K/V blocks rotate over the ICI ring, and the local block runs
the Pallas flash kernel — peak attention memory per chip is
O(S_loc · block), so max trainable context scales LINEARLY with chips.

  # 8-way virtual CPU mesh, 8k tokens, ring SP (smoke: a few minutes)
  python examples/train_longcontext.py --seq 8192 --sp 8

  # Ulysses all-to-all SP instead of the ring
  python examples/train_longcontext.py --seq 8192 --sp 8 --impl ulysses

  # sliding-window attention: the ring stops rotating past the band
  python examples/train_longcontext.py --seq 8192 --sp 8 --window 1024

On a real v4/v5 pod slice, drop the CPU forcing (run under the TPU
runtime) and raise --seq into the 64k-512k range with --preset
gpt2-medium and bf16.
"""

import argparse
import sys
import time

sys.path.insert(0, ".")

import jax
import jax.numpy as jnp
import numpy as np

import deepspeed_tpu
from deepspeed_tpu.utils import on_tpu
from deepspeed_tpu.models import gpt
from deepspeed_tpu.parallel.mesh import MeshSpec, make_mesh


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="gpt2-small")
    ap.add_argument("--seq", type=int, default=8192)
    ap.add_argument("--sp", type=int, default=8,
                    help="sequence-parallel degree (devices in the ring)")
    ap.add_argument("--impl", default="ring", choices=["ring", "ulysses"])
    ap.add_argument("--layout", default="contiguous",
                    choices=["contiguous", "zigzag"],
                    help="ring data layout; zigzag balances the causal "
                         "triangle across the ring (~2x at large rings)")
    ap.add_argument("--window", type=int, default=None,
                    help="optional sliding-window size")
    ap.add_argument("--batch", type=int, default=1)
    ap.add_argument("--steps", type=int, default=5)
    args = ap.parse_args()

    if len(jax.devices()) % args.sp or len(jax.devices()) < args.sp:
        raise SystemExit(
            f"have {len(jax.devices())} devices; sp={args.sp} needs a "
            f"multiple of it. For a virtual mesh run with "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={args.sp} "
            f"JAX_PLATFORMS=cpu")

    tpu = on_tpu()
    mesh = make_mesh(MeshSpec(data=len(jax.devices()) // args.sp,
                              sequence=args.sp))
    if args.layout == "zigzag" and args.impl != "ring":
        ap.error("--layout zigzag is a ring layout; use --impl ring")
    zig = args.layout == "zigzag"
    cfg = gpt.preset(args.preset, max_seq_len=args.seq,
                     dtype=jnp.bfloat16 if tpu else jnp.float32,
                     use_flash_attention=tpu,
                     sequence_parallel=True, sp_impl=args.impl,
                     sp_layout="zigzag" if zig else "contiguous",
                     attn_window=args.window, mesh=mesh,
                     loss_chunk=2048)
    params = gpt.init_params(jax.random.PRNGKey(0), cfg)
    n_params = sum(x.size for x in jax.tree.leaves(params))

    engine, _, _, _ = deepspeed_tpu.initialize(
        model=gpt.make_loss_fn(cfg), model_parameters=params,
        config={"train_batch_size": args.batch,
                "bf16": {"enabled": tpu},
                "mesh": {"data_parallel_size":
                         len(jax.devices()) // args.sp,
                         "sequence_parallel_size": args.sp},
                "optimizer": {"type": "adamw", "params": {"lr": 1e-4}},
                "steps_per_print": 1000},
        mesh=mesh)

    r = np.random.default_rng(0)
    tokens = r.integers(0, cfg.vocab_size,
                        (args.batch, args.seq + 1)).astype(np.int32)
    if zig:
        # zigzag layout: derive targets, then permute tokens/targets/
        # positions once on the host (the mean loss is permutation-
        # invariant)
        from deepspeed_tpu.runtime.dataloader import zigzag_batch
        batch = zigzag_batch({"tokens": tokens}, args.sp)
    else:
        batch = {"tokens": tokens}
    print(f"{args.preset}: {n_params / 1e6:.1f}M params, seq {args.seq} "
          f"over {args.sp}-way {args.impl} SP "
          f"({args.seq // args.sp} tokens/device)"
          + (", zigzag layout" if zig else "")
          + (f", window {args.window}" if args.window else ""))

    for step in range(args.steps):
        t0 = time.perf_counter()
        loss = float(engine.train_batch(batch)["loss"])
        dt = time.perf_counter() - t0
        tps = args.batch * args.seq / dt
        print(f"step {step}: loss {loss:.4f}  {dt * 1e3:.0f}ms  "
              f"{tps:,.0f} tok/s")


if __name__ == "__main__":
    main()
