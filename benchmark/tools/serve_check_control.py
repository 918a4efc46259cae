"""The control of the serving cells' check after the window: builds the
cell's engine once and, for each of a few seeds, drives a short window at
the cell's own load, takes the sample a run would take, and reads two
numbers from the reference on the same prompts and tokens: the widest gap
of the tokens the PROGRAM served (sound: under the configuration's
``check.served_gap_limit``), and the widest gap of the tokens that the
reference itself puts first when every weight matrix is rounded to float8
(e4m3) and back, the nearest precision below the served bf16 (the control:
over the limit). Run once, on the chip, by a PR that changes the check or
the limit:

    chiprun -- python3 benchmark/tools/serve_check_control.py \\
        --workload serve-gpt2xl-chat --seeds 11,12,13 --seconds 15

One engine and one set of weights (the first seed's) serve all the seeds:
each seed draws its own prompts. Prints one JSON row per seed. Not part of
a cell's run."""

import argparse
import json
import os
import sys
import time
import types

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH_DIR)
sys.path.insert(0, os.path.dirname(BENCH_DIR))

import numpy as np  # noqa: E402

from harness import cells  # noqa: E402


def rounded_to_fp8(params):
    import jax
    import jax.numpy as jnp
    return jax.tree_util.tree_map(
        lambda w: w.astype(jnp.float8_e4m3fn).astype(w.dtype)
        if w.ndim >= 2 else w, params)


def control_chooser(reference, low_params, n_head):
    """The tokens the reference puts first with ``low_params``."""
    import jax.numpy as jnp

    def chosen(padded, first, end):
        lg = reference.logits(low_params, jnp.asarray(padded), n_head)[0]
        return lg[first:end].argmax(-1)
    return chosen


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="11,12,13")
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--sample", type=int, default=None,
                    help="requests compared per seed (default: a run's)")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    import jax
    if jax.devices()[0].platform != "tpu" and not args.rehearse:
        sys.exit("serve_check_control: no TPU")
    cell = cells.Cell(args.workload)
    if args.rehearse:
        cell.use_rehearsal_size()
    from deepspeed_tpu.utils import setup_compile_cache
    setup_compile_cache()
    from harness.compiles import CompileCounter

    def say(**row):
        print(json.dumps(row), flush=True)

    seeds = [int(s) for s in args.seeds.split(",")]
    ctx = types.SimpleNamespace(
        cell=cell, seed=seeds[0], say=lambda **row: None,
        compiles=CompileCounter(), trace=False, trace_seconds=0.0,
        rehearsal=cell.config if args.rehearse else {})
    driver = cell.driver()
    hp = cell.config["model"]
    limit = float(cell.config["check"]["served_gap_limit"])
    srv, log, counts, _, _, _, params = driver.build(ctx)
    reference = cell.reference()
    chosen = control_chooser(reference, rounded_to_fp8(params),
                             int(hp["n_head"]))
    for seed in seeds:
        log.spans.clear()
        counts["prefill_tokens"].clear()
        res = driver.drive(ctx, srv, log, counts, cell.traffic, args.seconds,
                           np.random.default_rng([seed, 1]))
        while srv.busy:
            srv.step(time.perf_counter())
        sample = driver.sample_finished(
            res["finished_in_window"], seed,
            args.sample or driver.SAMPLE_REQUESTS)
        sound = driver.served_token_gaps(sample, params, hp, reference)
        control = driver.served_token_gaps(sample, params, hp, reference,
                                           chosen=chosen)
        tokens = int(sum(len(g) for g in sound.values()))
        s_max = max(float(g.max()) for g in sound.values())
        c_max = max(float(g.max()) for g in control.values())
        say(workload=cell.name, seed=seed, seconds=res["seconds"],
            finished_in_window=len(res["finished_in_window"]),
            requests_compared=len(sample), served_tokens_compared=tokens,
            limit=limit, sound_gap_max=s_max, control_gap_max=c_max,
            sound_tokens_off=int(sum((g > 0).sum() for g in sound.values())),
            control_tokens_off=int(sum((g > 0).sum()
                                       for g in control.values())),
            sound_correct=bool(s_max <= limit),
            control_correct=bool(c_max <= limit))


if __name__ == "__main__":
    main()
