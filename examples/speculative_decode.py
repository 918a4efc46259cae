"""Speculative decoding demo: a small draft accelerates a big target
with provably identical greedy output.

  # virtual CPU mesh smoke (~2 min)
  python examples/speculative_decode.py

  # on TPU, with real model scales:
  python examples/speculative_decode.py --target gpt2-large \\
      --draft gpt2-small --new-tokens 128 --gamma 5

The demo builds both models with random weights (shared vocabulary),
compares plain target generation with speculative generation, and
asserts the outputs are IDENTICAL — the speedup (reported) comes only
from verifying gamma+1 tokens per target step instead of one.
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
from deepspeed_tpu.inference.speculative import generate_speculative
from deepspeed_tpu.models import gpt


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--target", default="gpt2-medium")
    ap.add_argument("--draft", default=None,
                    help="draft preset (default: self-draft — see "
                         "module docstring)")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=32)
    ap.add_argument("--gamma", type=int, default=4)
    ap.add_argument("--temperature", type=float, default=0.0)
    args = ap.parse_args()

    tpu = on_tpu()
    dtype = jnp.bfloat16 if tpu else jnp.float32
    seq = args.prompt_len + args.new_tokens + args.gamma + 8

    def build(preset, seed):
        cfg = gpt.preset(preset, max_seq_len=seq, dtype=dtype,
                         use_flash_attention=tpu)
        return deepspeed_tpu.init_inference(
            model=(cfg, gpt.init_params(jax.random.PRNGKey(seed), cfg)),
            dtype=dtype)

    target = build(args.target, 0)
    draft = build(args.draft, 1) if args.draft else target
    toks = np.random.default_rng(0).integers(
        0, target.cfg.vocab_size, (1, args.prompt_len)).astype(np.int32)

    # warm both paths (compiles), then measure
    target.generate(toks, max_new_tokens=args.new_tokens,
                    temperature=args.temperature)
    generate_speculative(target, draft, toks,
                         max_new_tokens=args.new_tokens, gamma=args.gamma,
                         temperature=args.temperature)

    t0 = time.perf_counter()
    ref = target.generate(toks, max_new_tokens=args.new_tokens,
                          temperature=args.temperature)
    plain_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    got, stats = generate_speculative(
        target, draft, toks, max_new_tokens=args.new_tokens,
        gamma=args.gamma, temperature=args.temperature, return_stats=True)
    spec_s = time.perf_counter() - t0

    same = bool((got == ref).all())
    if args.temperature == 0.0:
        assert same, "greedy speculative output MUST equal the target's"
    print(f"target={args.target} "
          f"draft={args.draft or 'self (see docstring)'} "
          f"gamma={args.gamma}")
    print(f"plain: {args.new_tokens / plain_s:.1f} tok/s | speculative: "
          f"{args.new_tokens / spec_s:.1f} tok/s "
          f"(speedup {plain_s / spec_s:.2f}x)")
    print(f"accepted/round {stats['accepted_per_round']:.2f}, "
          f"target steps {stats['target_steps']} for {stats['tokens']} "
          f"tokens; outputs identical: {same}")


if __name__ == "__main__":
    main()
