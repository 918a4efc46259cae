"""CIFAR-10 ResNet training through deepspeed_tpu.initialize — the
workload analog of the reference's first example
(ref: DeepSpeedExamples/cifar driven by docs/_tutorials/cifar-10.md;
BASELINE.json config #1: ResNet CIFAR-10, ZeRO stage 1, single host).

Runs on synthetic CIFAR-shaped data by default (this environment has no
egress to download the dataset); pass ``--data path.npz`` with arrays
``images [N,32,32,3] uint8`` / ``labels [N]`` to train on real data.

Usage: python examples/train_cifar.py [--steps 100] [--batch 128]
"""

import argparse
import sys
import time

sys.path.insert(0, ".")

import jax
import numpy as np

import deepspeed_tpu
from deepspeed_tpu.models import resnet


def load_data(path, n=2048):
    if path:
        with np.load(path) as z:
            return (z["images"].astype(np.float32) / 127.5 - 1.0,
                    z["labels"].astype(np.int32))
    r = np.random.default_rng(0)
    # synthetic but learnable: class-dependent channel means + noise
    labels = r.integers(0, 10, n).astype(np.int32)
    means = r.standard_normal((10, 1, 1, 3)).astype(np.float32)
    images = means[labels] + 0.5 * r.standard_normal(
        (n, 32, 32, 3)).astype(np.float32)
    return images, labels


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--data", default=None)
    args = ap.parse_args()

    cfg = resnet.ResNetConfig()
    params = resnet.init_params(jax.random.PRNGKey(0), cfg)
    print(f"ResNet {resnet.num_params(cfg) / 1e6:.2f}M params")

    ds_config = {
        "train_batch_size": args.batch,
        "bf16": {"enabled": True},
        "zero_optimization": {"stage": 1},
        "optimizer": {"type": "adamw",
                      "params": {"lr": 1e-3, "weight_decay": 5e-4}},
        "scheduler": {"type": "WarmupLR",
                      "params": {"warmup_num_steps": 50}},
        "steps_per_print": 20,
    }
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=resnet.make_loss_fn(cfg), model_parameters=params,
        config=ds_config)

    images, labels = load_data(args.data)
    n = len(labels)
    r = np.random.default_rng(1)
    t0 = time.perf_counter()
    for step in range(args.steps):
        idx = r.integers(0, n, args.batch)
        m = engine.train_batch({"images": images[idx],
                                "labels": labels[idx]})
        if step % 20 == 0 or step == args.steps - 1:
            print(f"step {step:4d} loss {float(m['loss']):.4f}")
    dt = time.perf_counter() - t0
    print(f"{args.steps} steps in {dt:.1f}s "
          f"({args.steps * args.batch / dt:.0f} samples/s)")

    acc = float(resnet.accuracy(
        engine.state.params,
        {"images": images[:512], "labels": labels[:512]}, cfg))
    print(f"train-set accuracy (512 samples): {acc:.3f}")


if __name__ == "__main__":
    main()
