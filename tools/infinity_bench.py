"""Throughput bench for the ZeRO-Infinity streamed tier (gpt2-4b / 8b).

The 4B/8B regression configs
(ref: tests/model/Megatron_GPT2/run_perf_baseline.py:33,48 — 64L/2304h
and 72L/3072h on 16 GPUs; ref capacity claim "13B on one 32GB V100 at
>30 TFLOPS", docs/_pages/features.md:116) have only ever been run here
as a CAPACITY demo. This tool measures the streamed tier for SPEED:

- measured host<->device link bandwidths (h2d via device_put of a
  pinned block, d2h via copy_to_host of a device buffer);
- per-step wall time -> tokens/s + MFU (Megatron flops accounting);
- the analytic transfer floor for the measured link: bytes streamed
  per step (2x block h2d + 1x grads d2h per micro-batch) / bandwidth —
  so the report separates "engine overhead" from "link physics":
  overlap_quality = transfer_floor / step_time (→1.0 means the step is
  fully transfer-bound with compute hidden behind DMA, the best any
  schedule can do on this link; small values mean the engine, not the
  link, is the bottleneck).

Prints one JSON line per phase. Needs a TPU; one process.

Usage: python tools/infinity_bench.py [preset] [steps] [micro_batch] [seq]
"""

import json
import sys
import time

sys.path.insert(0, ".")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402


def measure_bandwidths(mb=256):
    """Measured h2d / d2h GB/s with a mb-MB fp32 buffer (median of 3)."""
    n = mb * (1 << 20) // 4
    host = np.ones(n, np.float32)
    h2d = []
    for _ in range(3):
        t0 = time.perf_counter()
        d = jax.block_until_ready(jax.device_put(host))
        h2d.append(time.perf_counter() - t0)
    d2h = []
    for _ in range(3):
        t0 = time.perf_counter()
        np.asarray(d)
        d2h.append(time.perf_counter() - t0)
    gb = host.nbytes / 1e9
    return gb / sorted(h2d)[1], gb / sorted(d2h)[1]


def main():
    import deepspeed_tpu
    from deepspeed_tpu.models import gpt

    preset = sys.argv[1] if len(sys.argv) > 1 else "gpt2-4b"
    steps = int(sys.argv[2]) if len(sys.argv) > 2 else 3
    batch = int(sys.argv[3]) if len(sys.argv) > 3 else 4
    seq = int(sys.argv[4]) if len(sys.argv) > 4 else 1024

    h2d_gbs, d2h_gbs = measure_bandwidths()
    print(json.dumps({"phase": "link", "h2d_gb_s": round(h2d_gbs, 3),
                      "d2h_gb_s": round(d2h_gbs, 4)}), flush=True)

    on_tpu = "tpu" in (jax.devices()[0].platform +
                       jax.devices()[0].device_kind).lower()
    cfg = gpt.preset(preset, max_seq_len=seq, dtype=jnp.bfloat16,
                     remat=True, use_flash_attention=on_tpu,
                     flash_block_q=512, flash_block_kv=512)
    fac = gpt.host_param_factory(0, cfg)
    eng, _, _, _ = deepspeed_tpu.initialize(
        model=gpt.layered_model(cfg), model_parameters=fac,
        config={
            "train_batch_size": batch,
            "bf16": {"enabled": True},
            "zero_optimization": {"stage": 3,
                                  "offload_param": {"device": "cpu"}},
            "optimizer": {"type": "adamw", "params": {"lr": 1e-4}},
        })

    # streamed bytes per optimizer step (see module docstring):
    # h2d 2x bf16 block per micro-batch, d2h 1x bf16 grads per micro-batch
    block_bytes = sum(sum(a.nbytes for a in grp) for grp in eng.host_bf16)
    gas = eng.gas
    h2d_bytes = 2 * block_bytes * gas
    d2h_bytes = block_bytes * gas
    floor_s = h2d_bytes / 1e9 / h2d_gbs + d2h_bytes / 1e9 / d2h_gbs

    r = np.random.default_rng(0)
    data = {"tokens": r.integers(0, cfg.vocab_size,
                                 (batch, seq + 1)).astype(np.int32)}
    m = eng.train_batch(data)                       # warmup / compile
    times = []
    for _ in range(steps):
        t0 = time.perf_counter()
        m = eng.train_batch(data)
        times.append(time.perf_counter() - t0)
    times.sort()
    dt = times[len(times) // 2]
    tps = batch * seq / dt
    flops = gpt.train_flops_per_token(cfg, seq)
    from bench import peak_flops
    mfu = tps * flops / peak_flops()
    print(json.dumps({
        "phase": "train", "metric": f"{preset}_streamed_tokens_per_s",
        "value": round(tps, 2), "unit": "tokens/s/chip",
        "model": preset, "n_params": eng.n_params, "batch": batch,
        "seq": seq, "step_s": round(dt, 2), "mfu": round(mfu, 5),
        "loss": round(m["loss"], 4),
        "streamed_gb_per_step": round((h2d_bytes + d2h_bytes) / 1e9, 2),
        "transfer_floor_s": round(floor_s, 2),
        "overlap_quality": round(min(1.0, floor_s / dt), 4)}), flush=True)


if __name__ == "__main__":
    main()
