"""The control of the SmallThinker cell's checks
(``drivers/serve_smallthinker.py``): builds the cell's engine once, repeats
the warm-up comparison with the reference's weights rounded to float8
(e4m3) and back, the nearest precision below the served bf16 (it has to
come out NOT correct), then for each of a few seeds drives a short window
at the cell's own load, takes the sample a run would take and, once the KV
state is freed, reads two numbers from the reference: the share of the
tokens the PROGRAM served that are not the reference's first (sound: under
``check.served_off_share_limit``) and the same share of the tokens the
float8 reference puts first (the control: over it). Run once, on the chip,
by a PR that changes the checks or their limits:

    chiprun -- python3 benchmark/tools/smallthinker_check_control.py \\
        --seeds 11,12 --seconds 15

One engine and one set of weights (the first seed's) serve all the seeds:
each seed draws its own prompts. One JSON row per reading. Not part of a
cell's run."""

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


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload",
                    default="serve-smallthinker-mixed-context-backlog")
    ap.add_argument("--seeds", default="11,12")
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    import jax
    if jax.devices()[0].platform != "tpu" and not args.rehearse:
        sys.exit("smallthinker_check_control: no TPU")
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
    limits = cell.config["check"]
    limit = float(limits["served_off_share_limit"])
    b = driver.build(ctx)
    srv, params, cfg = b["srv"], b["params"], b["cfg"]
    reference = cell.reference()
    check, cap = b["checked"]
    for name, kw in (("sound", {}), ("float8", {"fp8": True})):
        ok, d = driver.check_warmup(check, cap, params, cfg, reference,
                                    limits, **kw)
        say(what="warmup", reference=name, correct=ok, **{
            k: d[k] for k in ("max_abs_logit_error", "tolerance",
                              "largest_reference_logit",
                              "route_decisions_compared",
                              "route_decisions_disputed",
                              "route_worst_margin", "route_tie_eps")})
    samples = {}
    for seed in seeds:
        b["log"].spans.clear()
        b["counts"]["prefill_tokens"].clear()
        res = driver.serve.drive(ctx, srv, b["log"], b["counts"],
                                 cell.traffic, args.seconds,
                                 np.random.default_rng([seed, 1]))
        while srv.busy:
            srv.step(time.perf_counter())
        samples[seed] = (res, driver.serve.sample_finished(
            res["finished_in_window"], seed))
    state = (srv.cache.k, srv.cache.v)
    del srv, b
    for s in state:
        s.delete()
    hp = driver.reference_hp(cfg)
    head = min(int(cell.traffic["answer"]["max"]), cfg.max_seq_len)

    def chosen(padded, at):
        return reference.logits(params, padded, hp, fp8=True, at=at)[0]

    for seed, (res, sample) in samples.items():
        sound = driver.served_token_gaps(sample, params, cfg, reference,
                                         head)
        control = driver.served_token_gaps(sample, params, cfg, reference,
                                           head, chosen=chosen)
        s_ok, s_row, _ = driver.judge_served(sound, limit)
        c_ok, c_row, _ = driver.judge_served(control, limit)
        say(what="after_window", workload=cell.name, seed=seed,
            seconds=res["seconds"],
            finished_in_window=len(res["finished_in_window"]),
            requests_compared=len(sample), limit=limit,
            sound=s_row, control=c_row, sound_correct=s_ok,
            control_correct=c_ok)


if __name__ == "__main__":
    main()
