"""Fine-tune a llama-family model end to end: HF checkpoint -> TPU
training engine -> generation.

The reference's story for foreign checkpoints is inference-only
injection (ref: deepspeed/module_inject/replace_module.py); here the
SAME policy conversion feeds the training engine, because a model
dialect is just a GPTConfig — ZeRO, TP, SP, offload all compose.

  # tiny random llama on the virtual CPU mesh (smoke, ~2 min)
  python examples/finetune_llama.py

  # a real HF checkpoint directory (e.g. a llama-2-7b export) on TPU:
  python examples/finetune_llama.py --hf-path /path/to/llama --zero 3

With no --hf-path this builds a small random-weight LlamaForCausalLM
(no network access needed) — the point is the plumbing: convert, train
with ZeRO-2 + bf16 on TPU (fp32 on CPU), save a checkpoint, reload it
into the inference engine, generate.
"""

import argparse
import sys
import tempfile

sys.path.insert(0, ".")

import jax
import jax.numpy as jnp
import numpy as np

import deepspeed_tpu
from deepspeed_tpu.utils import on_tpu
from deepspeed_tpu.inference.policy import resolve_model
from deepspeed_tpu.models import gpt


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--hf-path", default=None,
                    help="HF llama checkpoint dir (default: tiny random)")
    ap.add_argument("--steps", type=int, default=6)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--zero", type=int, default=2)
    args = ap.parse_args()

    import transformers
    if args.hf_path:
        hf_model = transformers.AutoModelForCausalLM.from_pretrained(
            args.hf_path)
    else:
        import torch
        torch.manual_seed(0)
        hf_model = transformers.LlamaForCausalLM(transformers.LlamaConfig(
            vocab_size=512, hidden_size=128, intermediate_size=344,
            num_hidden_layers=4, num_attention_heads=8,
            num_key_value_heads=4, max_position_embeddings=128,
            rms_norm_eps=1e-6))

    cfg, params = resolve_model(hf_model)
    tpu = on_tpu()
    import dataclasses
    cfg = dataclasses.replace(cfg, dtype=jnp.bfloat16 if tpu else jnp.float32,
                              use_flash_attention=tpu)
    n = sum(x.size for x in jax.tree.leaves(params))
    print(f"converted llama: {cfg.n_layers}L/{cfg.d_model}d "
          f"kv={cfg.kv_heads} {n/1e6:.1f}M params")

    engine, _, _, _ = deepspeed_tpu.initialize(
        model=gpt.make_loss_fn(cfg), model_parameters=params,
        config={"train_batch_size": args.batch,
                "bf16": {"enabled": tpu},
                "zero_optimization": {"stage": args.zero},
                "optimizer": {"type": "adamw", "params": {"lr": 1e-4}},
                "steps_per_print": 1000})

    r = np.random.default_rng(0)
    toks = r.integers(0, cfg.vocab_size,
                      (args.batch, min(cfg.max_seq_len, 64) + 1))
    toks = toks.astype(np.int32)
    for i in range(args.steps):
        print(f"step {i}: loss "
              f"{float(engine.train_batch({'tokens': toks})['loss']):.4f}")

    with tempfile.TemporaryDirectory() as d:
        engine.save_checkpoint(d)
        # reload the TRAINED weights from the sharded checkpoint (the
        # checkpoint= path reshards zero shards into the skeleton)
        eng = deepspeed_tpu.init_inference(
            model=(cfg, engine.module_state_dict()), checkpoint=d,
            dtype=jnp.bfloat16 if tpu else jnp.float32)
        out = eng.generate(toks[:2, :8], max_new_tokens=8, temperature=0.0)
        print(f"generated: {out.shape[1] - 8} new tokens/row "
              f"(prefill {eng.latency_ms.get('prefill', float('nan')):.0f}ms, "
              f"decode {eng.latency_ms.get('decode_per_token', float('nan')):.1f}"
              f"ms/token)")


if __name__ == "__main__":
    main()
