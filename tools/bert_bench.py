"""BERT-large pretraining throughput — the reference's headline benchmark
(ref: docs/_tutorials/bert-pretraining.md:388 — 64 TFLOPS / 272
samples/s/GPU at seq128, 53 TFLOPS / 52 samples/s at seq512 on one V100).

Prints one JSON line per (seq, batch) config with samples/s and achieved
model TFLOPS on this chip (per-step-synced median timing, see PERF.md).

Usage: python tools/bert_bench.py [steps]
"""

import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, ".")


def mlm_batch(rng, vocab, batch, seq, mask_frac=0.15):
    tokens = rng.integers(0, vocab, (batch, seq)).astype(np.int32)
    labels = np.where(rng.random((batch, seq)) < mask_frac, tokens, -1)
    return {"tokens": tokens, "mlm_labels": labels.astype(np.int32)}


def flops_per_sample(cfg, seq):
    """Megatron-style fwd+bwd matmul flops for one MLM sample."""
    d, L, ff, V = cfg.d_model, cfg.n_layers, 4 * cfg.d_model, cfg.vocab_size
    per_layer = 4 * d * d + 2 * d * ff          # qkv+proj + mlp
    attn = 2 * L * d * seq                      # scores + weighted sum
    head = d * V + d * d                        # mlm decoder + transform
    return 6.0 * seq * (L * per_layer + head) + 6.0 * seq * attn


def run(seq, batch, steps):
    import deepspeed_tpu
    from deepspeed_tpu.models import bert

    from deepspeed_tpu.utils import hbm, require_tpu

    require_tpu("bert_bench")
    cfg = bert.preset("bert-large", max_seq_len=max(seq, 128),
                      dropout=0.0, dtype=jnp.bfloat16,
                      remat=True, remat_policy="full", loss_chunk=2048)
    # refuse a configuration whose estimate does not fit (utils/hbm.py)
    hbm.guard_bert_config(cfg, batch, seq)
    params = bert.init_params(jax.random.PRNGKey(0), cfg)
    eng, _, _, _ = deepspeed_tpu.initialize(
        model=bert.make_loss_fn(cfg), model_parameters=params,
        config={"train_batch_size": batch,
                "bf16": {"enabled": True},
                "zero_optimization": {"stage": 1},
                "optimizer": {"type": "adamw", "params": {"lr": 1e-4}},
                "steps_per_print": 100000})
    del params
    r = np.random.default_rng(0)
    data = mlm_batch(r, cfg.vocab_size, batch, seq)
    float(eng.train_batch(data)["loss"])
    ts = []
    for _ in range(steps):
        t0 = time.perf_counter()
        m = eng.train_batch(data)
        float(m["loss"])
        ts.append(time.perf_counter() - t0)
    ts.sort()
    dt = ts[len(ts) // 2]
    sps = batch / dt
    tflops = sps * flops_per_sample(cfg, seq) / 1e12
    del eng
    return dt, sps, tflops


def main():
    # each config builds its own engine and wants the whole device, so each
    # runs in a child; this parent stays off JAX
    if len(sys.argv) > 1 and sys.argv[1] == "--one":
        from deepspeed_tpu.utils.hbm import MemoryGuardError
        seq, batch, steps = (int(x) for x in sys.argv[2:5])
        try:
            dt, sps, tf = run(seq, batch, steps)
        except MemoryGuardError as e:
            print(json.dumps({"model": "bert-large", "seq": seq,
                              "batch": batch, "skipped": "memory guard",
                              "why": str(e)[:300]}), flush=True)
            return
        print(json.dumps({
            "model": "bert-large", "seq": seq, "batch": batch,
            "step_ms": round(dt * 1e3, 1),
            "samples_per_sec": round(sps, 1),
            "model_tflops": round(tf, 1),
            "ref_v100": {"128": "64 TFLOPS / 272 samples/s",
                         "512": "53 TFLOPS / 52 samples/s"}.get(str(seq)),
        }), flush=True)
        return
    steps = int(sys.argv[1]) if len(sys.argv) > 1 else 8
    from tools._subproc import run_json

    ok = [run_json([sys.executable, __file__, "--one", str(seq), str(batch),
                    str(steps)], 1500, {"seq": seq, "batch": batch})
          for seq, batch in [(128, 128), (128, 256), (128, 512),
                             (512, 16), (512, 32), (512, 64)]]
    if not all(ok):
        sys.exit(1)


if __name__ == "__main__":
    main()
