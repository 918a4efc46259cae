"""Minimal GPT pretraining loop (the DeepSpeedExamples analog).

Runs on one TPU chip or any JAX backend (CPU smoke: ~a minute).

  python examples/train_gpt.py --preset gpt2-small --steps 20
  python examples/train_gpt.py --deepspeed_config examples/ds_config.json
"""

import argparse
import json
import sys
import time

sys.path.insert(0, ".")

import jax
import jax.numpy as jnp
import numpy as np

import deepspeed_tpu
from deepspeed_tpu.models import gpt


def synthetic_batches(vocab, batch, seq, seed=0):
    """Stand-in corpus: a repeating Zipf-ish stream so loss decreases."""
    r = np.random.default_rng(seed)
    base = r.zipf(1.5, size=(batch, seq + 1)).clip(0, vocab - 1)
    while True:
        noise = r.integers(0, vocab, (batch, seq + 1))
        keep = r.random((batch, seq + 1)) < 0.9
        yield {"tokens": np.where(keep, base, noise).astype(np.int32)}


def main():
    ap = argparse.ArgumentParser()
    deepspeed_tpu.add_config_arguments(ap)
    ap.add_argument("--preset", default="gpt2-small")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--packed", action="store_true",
                    help="pack variable-length synthetic documents per row "
                         "(segment-ids flash attention)")
    args = ap.parse_args()

    on_tpu = jax.devices()[0].platform == "tpu"
    cfg = gpt.preset(args.preset, max_seq_len=args.seq,
                     dtype=jnp.bfloat16, use_flash_attention=on_tpu,
                     # fused chunked CE: skips the [B,S,V] logits tensor
                     loss_chunk=2048)
    params = gpt.init_params(jax.random.PRNGKey(0), cfg)

    ds_config = args.deepspeed_config or {
        "train_batch_size": args.batch,
        "bf16": {"enabled": True},
        "zero_optimization": {"stage": 3},
        "optimizer": {"type": "adamw",
                      "params": {"lr": 3e-4, "weight_decay": 0.1}},
        "scheduler": {"type": "WarmupLR",
                      "params": {"warmup_num_steps": 10}},
        "steps_per_print": 10,
    }
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=gpt.make_loss_fn(cfg), model_parameters=params,
        config=ds_config, partition_rules=gpt.gpt_partition_rules())

    if args.packed:
        # variable-length documents packed into fixed rows — attention is
        # block-diagonal per doc, positions restart, boundaries masked
        from deepspeed_tpu.runtime.dataloader import pack_documents
        r = np.random.default_rng(0)

        def packed_batches():
            while True:
                docs = []
                out = {"tokens": np.zeros((0, 0))}
                while out["tokens"].shape[0] < args.batch:
                    docs += [r.integers(0, cfg.vocab_size,
                                        int(n)).astype(np.int32)
                             for n in r.integers(16, args.seq, args.batch)]
                    out = pack_documents(docs, args.seq + 1)
                yield {k: v[:args.batch] for k, v in out.items()}

        data = packed_batches()
    else:
        data = synthetic_batches(cfg.vocab_size, args.batch, args.seq)
    t0 = time.perf_counter()
    real_tokens = 0
    for step in range(args.steps):
        batch = next(data)
        # packed rows carry padding — count only loss-contributing tokens
        real_tokens += int(batch["loss_mask"].sum()) \
            if "loss_mask" in batch else args.batch * args.seq
        m = engine.train_batch(batch)
        if step % 5 == 0 or step == args.steps - 1:
            print(f"step {step:4d} loss {float(m['loss']):.4f} "
                  f"lr {float(m['lr']):.2e}")
    dt = time.perf_counter() - t0
    print(json.dumps({"steps": args.steps,
                      "tokens_per_sec": round(real_tokens / dt, 1)}))


if __name__ == "__main__":
    main()
