"""MoE GPT training-step benchmark: dispatch overhead vs dense.

Measures the GPT-MoE NLG workload (ref capability: BASELINE.json config #5)
on the local chip: a dense GPT layer stack vs the same stack with GShard
MoE FFNs (top-1 / top-2), same d_model — reporting step time and the MoE
dispatch overhead ratio. Each config runs in a fresh subprocess.

Usage: python tools/moe_bench.py [steps]
"""

import sys

sys.path.insert(0, ".")

CODE = """
import sys, json, time
sys.path.insert(0, '.')
import jax, numpy as np, jax.numpy as jnp
import deepspeed_tpu

kind = {kind!r}
batch, seq, steps = {batch}, {seq}, {steps}
on_tpu = 'tpu' in (jax.devices()[0].platform + jax.devices()[0].device_kind).lower()

if kind == 'dense':
    from deepspeed_tpu.models import gpt as M
    # match the MoE path's cost model: moe_gpt remats with
    # nothing_saveable (full) and uses the dense CE — keep both equal so
    # the ratio isolates DISPATCH cost, not remat/CE differences
    cfg = M.preset('gpt2-small', max_seq_len=seq, dtype=jnp.bfloat16,
                   remat=True, remat_policy='full', use_flash_attention=on_tpu,
                   loss_chunk=0)
else:
    from deepspeed_tpu.models import moe_gpt as M
    cfg = M.MoEGPTConfig(n_layers=12, n_heads=12, d_model=768,
                         max_seq_len=seq, dtype=jnp.bfloat16, remat=True,
                         use_flash_attention=on_tpu,
                         num_experts={experts}, moe_k={k},
                         capacity_factor=1.25)
if on_tpu:
    # refuse a configuration whose estimate does not fit (utils/hbm.py)
    from deepspeed_tpu.utils import hbm
    try:
        if kind == 'dense':
            hbm.guard_gpt_config(cfg, batch, seq)
        else:
            hbm.guard_moe_config(cfg, batch, seq)
    except hbm.MemoryGuardError as e:
        print(json.dumps({{"kind": kind, "experts": {experts},
            "skipped": "memory guard", "why": str(e)[:300]}}))
        sys.exit(0)
params = M.init_params(jax.random.PRNGKey(0), cfg)
n_params = sum(x.size for x in jax.tree.leaves(params))
engine, _, _, _ = deepspeed_tpu.initialize(
    model=M.make_loss_fn(cfg), model_parameters=params,
    config={{"train_batch_size": batch, "bf16": {{"enabled": True}},
            "zero_optimization": {{"stage": 1}},
            "optimizer": {{"type": "adamw", "params": {{"lr": 1e-4}}}},
            "steps_per_print": 10_000}})
tokens = np.random.default_rng(0).integers(
    0, cfg.vocab_size, (batch, seq + 1)).astype(np.int32)
jax.block_until_ready(engine.train_batch({{"tokens": tokens}})["loss"])
ts = []
for _ in range(steps):
    t0 = time.perf_counter()
    float(engine.train_batch({{"tokens": tokens}})["loss"])
    ts.append(time.perf_counter() - t0)
ts.sort()
dt = ts[len(ts)//2]
print(json.dumps({{"kind": kind, "experts": {experts}, "k": {k},
    "params_M": round(n_params/1e6, 1), "batch": batch, "seq": seq,
    "step_ms": round(dt*1e3, 1),
    "tokens_per_s": round(batch*seq/dt, 1)}}))
"""


def main():
    steps = int(sys.argv[1]) if len(sys.argv) > 1 else 8
    batch, seq = 8, 1024
    grid = [("dense", 0, 0), ("moe", 8, 1), ("moe", 8, 2), ("moe", 16, 1)]
    from tools._subproc import run_json

    ok = [run_json([sys.executable, "-c",
                    CODE.format(kind=kind, experts=experts, k=k, batch=batch,
                                seq=seq, steps=steps)],
                   1500, {"kind": kind, "experts": experts})
          for kind, experts, k in grid]
    if not all(ok):
        sys.exit(1)


if __name__ == "__main__":
    main()
