"""Inference example: HF checkpoint injection + KV-cache generation
(the init_inference analog of the reference's inference tutorials).

  python examples/generate.py            # tiny random HF GPT-2
  python examples/generate.py --hf gpt2  # a real HF checkpoint if cached
"""

import argparse
import sys

sys.path.insert(0, ".")

import numpy as np

import deepspeed_tpu


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--hf", default=None,
                    help="HF model name (needs local cache; no egress)")
    ap.add_argument("--tokens", type=int, default=16)
    args = ap.parse_args()

    import transformers
    if args.hf:
        model = transformers.GPT2LMHeadModel.from_pretrained(args.hf)
    else:
        cfg = transformers.GPT2Config(
            vocab_size=128, n_positions=64, n_embd=64, n_layer=2, n_head=4)
        model = transformers.GPT2LMHeadModel(cfg).eval()

    engine = deepspeed_tpu.init_inference(model=model)
    prompt = np.array([[1, 2, 3, 4]], np.int32)
    # generate_fused runs the whole decode loop as ONE compiled program
    # (no host round-trip per token); generate() is the host-driven loop
    out = engine.generate_fused(prompt, max_new_tokens=args.tokens,
                                temperature=0.8, seed=0)
    print("prompt:", prompt[0].tolist())
    print("generated:", np.asarray(out)[0].tolist())
    print("latency:", {k: round(v, 2)
                       for k, v in engine.latency_ms.items()})


if __name__ == "__main__":
    main()
