"""Shows what the train cells' forward check (``drivers/train.py``
``forward_check``) catches: runs it on the cell's weights as they are, then
with every weight matrix rounded to float8 (e4m3) and back, which is what
fp8 matmul weights would compute. The first has to pass and the second to
fail. Run once, on the chip, by a PR that changes the check or a tolerance:

    chiprun -- python3 benchmark/tools/train_check_sensitivity.py \\
        --workload train-gpt2xl-1chip --seed 11

Prints one JSON row per variant. Not part of a cell's run."""

import argparse
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH_DIR)
sys.path.insert(0, os.path.dirname(BENCH_DIR))

import numpy as np  # noqa: E402

from harness import cells, weights  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=11)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    if jax.devices()[0].platform != "tpu" and not args.rehearse:
        sys.exit("train_check_sensitivity: no TPU")
    from deepspeed_tpu.models import gpt
    cell = cells.Cell(args.workload)
    if args.rehearse:
        cell.use_rehearsal_size()
    driver = cell.driver()
    hp, tr, mix = cell.config["model"], cell.config["training"], cell.traffic
    seq = int(mix["seq"])
    cfg = gpt.GPTConfig(
        vocab_size=int(hp["vocab_size"]), n_layers=int(hp["n_layer"]),
        n_heads=int(hp["n_head"]), d_model=int(hp["n_embd"]),
        max_seq_len=seq, dtype=jnp.bfloat16,
        flash_block_q=int(tr["flash_block"]),
        flash_block_kv=int(tr["flash_block"]))
    params = weights.gpt2_params(args.seed, hp, jnp.bfloat16)
    sample = np.random.default_rng(args.seed).integers(
        0, int(hp["vocab_size"]),
        (driver.SAMPLE_SEQUENCES, seq + 1)).astype(np.int32)

    def fp8(w):
        return w.astype(jnp.float8_e4m3fn).astype(w.dtype) \
            if w.ndim >= 2 else w

    for name, p in (("as_served", params),
                    ("weights_rounded_to_fp8_e4m3",
                     jax.tree_util.tree_map(fp8, params))):
        # the reference always sees the true weights
        ok, detail, _ = driver.forward_check(
            p, sample, cfg, int(hp["n_head"]), cell.reference(),
            reference_params=params)
        print(json.dumps(dict(variant=name, **detail)), flush=True)


if __name__ == "__main__":
    main()
