"""Benchmark: GPT training throughput (tokens/sec/chip) on the local device.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}

Needs a TPU: with none it exits non-zero and prints no result. Every
configuration runs in this one process (a chip belongs to one process),
and a configuration that fails fails the run.

Headline metric (the BASELINE.json north star): GPT-2 **1.5B**
(48 layers / 1600 hidden / seq 1024 — the reference's own perf-harness
config, ref tests/model/Megatron_GPT2/run_perf_baseline.py:17) training
tokens/sec on ONE chip. The full training state (bf16 params + bf16 Adam
moments with stochastic-rounding updates, bf16.memory_efficient) lives
on-device — 9.3GB of state on a 16GB v5e.

vs_baseline: achieved model-flops utilization / 0.40 — the "A100 MFU
parity" bar from BASELINE.md. MFU uses Megatron-style flops accounting
(6*N_matmul + attention, logit layer included; gpt.train_flops_per_token).

Secondary (detail): gpt2-medium ZeRO-1 fp32-master number — same config
as round 1, for cross-round comparability.
"""

import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, ".")

MFU_BAR = 0.40  # A100-parity bar (see BASELINE.md north star)


def peak_flops() -> float:
    """Peak bf16 FLOP/s of the first device, from the one table in
    telemetry/costs.py. A device the table does not know is an error."""
    from deepspeed_tpu.telemetry.costs import device_peak_flops
    peak = device_peak_flops()
    if peak is None:
        raise SystemExit(
            f"no peak FLOP/s for device_kind="
            f"{jax.devices()[0].device_kind!r}: add it to PEAK_FLOPS in "
            f"deepspeed_tpu/telemetry/costs.py with its source")
    return peak


def run_config(preset, batch, seq, steps, ds_overrides,
               flash_block=1024, remat_pol="selective", loss_chunk=0,
               remat=True, flash_block_kv=None,
               bwd_block_q=None, bwd_block_kv=None):
    import deepspeed_tpu
    from deepspeed_tpu.models import gpt
    from deepspeed_tpu.utils import hbm as hbm_guard, require_tpu

    require_tpu("bench.py")
    cfg = gpt.preset(preset, max_seq_len=seq, dtype=jnp.bfloat16,
                     remat=remat, remat_policy=remat_pol,
                     flash_block_q=flash_block,
                     flash_block_kv=flash_block_kv or flash_block,
                     flash_block_bwd_q=bwd_block_q,
                     flash_block_bwd_kv=bwd_block_kv,
                     loss_chunk=loss_chunk)
    # refuse a configuration whose estimate does not fit the device
    # before compiling it (utils/hbm.py)
    hbm_guard.guard_gpt_config(
        cfg, batch, seq,
        precision="bf16" if ds_overrides.get("bf16", {}).get(
            "enabled", True) else "fp32",
        memory_efficient=ds_overrides.get("bf16", {}).get(
            "memory_efficient", False))
    params = gpt.init_params(jax.random.PRNGKey(0), cfg)
    ds_config = {
        "train_batch_size": batch,
        "bf16": {"enabled": True},
        "zero_optimization": {"stage": 1},
        "optimizer": {"type": "adamw", "params": {"lr": 1e-4,
                                                  "weight_decay": 0.1}},
        "steps_per_print": 10_000,
    }
    for k, v in ds_overrides.items():
        if isinstance(v, dict):
            ds_config.setdefault(k, {}).update(v)
        else:
            ds_config[k] = v
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=gpt.make_loss_fn(cfg), model_parameters=params,
        config=ds_config)
    del params

    tokens = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (batch, seq + 1)).astype(np.int32)
    data = {"tokens": tokens}

    # warmup / compile — block so compile cost stays out of the timed loop
    jax.block_until_ready(engine.train_batch(data)["loss"])
    # per-step sync + median: robust to a queue transient that inflates an
    # async window and to a single slow outlier
    times = []
    for _ in range(steps):
        t0 = time.perf_counter()
        m = engine.train_batch(data)
        float(m["loss"])
        times.append(time.perf_counter() - t0)
    times.sort()
    dt = times[len(times) // 2]

    tps = batch * seq / dt
    mfu = tps * gpt.train_flops_per_token(cfg, seq) / peak_flops()
    del engine
    return dt, tps, mfu


def main():
    from deepspeed_tpu.utils import require_tpu, setup_compile_cache
    dev = require_tpu("bench.py")
    setup_compile_cache()

    # --- headline: GPT-2 1.5B, full training state on one chip --------
    preset, batch15, seq = "gpt2-1.5b", 16, 1024
    dt15, tps15, mfu15 = run_config(
        preset, batch15, seq, 10,
        {"bf16": {"enabled": True, "memory_efficient": True},
         "zero_optimization": {"stage": 3}},
        remat_pol="full", flash_block=1024, loss_chunk=2048)

    # --- secondary: gpt2-medium ZeRO-1 (round-1 comparable) -----------
    dt_m, tps_m, mfu_m = run_config(
        "gpt2-medium", 8, 1024, 20, {"zero_optimization": {"stage": 1}},
        flash_block=1024)

    # --- BERT-large seq512: the reference's own V100 headline ---------
    # (ref docs/_tutorials/bert-pretraining.md:388 — 52 samples/s,
    # 53 TFLOPS on 1x V100)
    from tools.bert_bench import run as bert_run
    _, sps, tf = bert_run(512, 32, 8)

    print(json.dumps({
        "metric": f"{preset.replace('-', '_')}"
                  f"_seq{seq}_train_tokens_per_sec_per_chip",
        "value": round(tps15, 1),
        "unit": "tokens/s/chip",
        "vs_baseline": round(mfu15 / MFU_BAR, 3),
        "detail": {
            "headline": {
                "model": preset + " (48L/1600h, ref run_perf_baseline.py:17)",
                "batch": batch15, "seq": seq,
                "step_ms": round(dt15 * 1e3, 2),
                "mfu": round(mfu15, 4),
                "mode": "bf16 memory_efficient (bf16 params+moments, "
                        "stochastic rounding), zero_stage=3, full remat, "
                        "flash attention, chunked CE",
            },
            "secondary_gpt2_medium": {
                "tokens_per_sec": round(tps_m, 1),
                "step_ms": round(dt_m * 1e3, 2),
                "mfu": round(mfu_m, 4),
                "zero_stage": 1,
            },
            "bert_large_seq512_vs_ref_headline": {
                "samples_per_sec": round(sps, 1),
                "model_tflops": round(tf, 1),
                "vs_reference_v100": round(sps / 52.0, 2)},
            "device": {"platform": dev.platform, "kind": dev.device_kind,
                       "count": len(jax.devices())},
            "flops_accounting": "Megatron-style 6*N_matmul+attn "
                                "(logit layer included)",
        },
    }))


if __name__ == "__main__":
    main()
