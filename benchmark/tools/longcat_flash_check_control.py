"""The controls of the LongCat-Flash cell's checks
(``drivers/serve_longcat_flash.py``): builds the cell's engine once and
repeats the warm-up comparison (checks 1 and 2) against the sound reference
and against four references that are wrong on purpose, each of which has to
come out NOT correct by at least one limit:

- ``float8``: every weight matrix rounded to float8 e4m3's 3 mantissa bits
  (``lax.reduce_precision``, not a pair of converts, which the chip's
  compiler removes: PERF.md 7(am)), the nearest precision below the served
  bf16;
- ``float8_up_projection``: ONLY ``k_up`` / ``v_up`` (the matrices the
  decode path absorbs) so rounded;
- ``no_zero_term``: the zero-compute (identity) experts add nothing;
- ``no_kv_scale``: ``mla_scale_kv_lora`` left out (the latent as the cache
  row holds it 3.46 times too small).

Then, for each seed, it drives a short window at the cell's own load, takes
the sample a run would take and, once the latent pool is freed, reads check
3 from the sound reference: for the tokens the PROGRAM served (sound: under
``check.served_off_share_limit``) and for the tokens each control's
reference puts first (over it). Run once, on the chip, by a PR that changes
the checks or their limits:

    chiprun --timeout 1800 -- python3 \\
        benchmark/tools/longcat_flash_check_control.py --seeds 11 --seconds 20

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

CONTROLS = (("float8", {"fp8": True}),
            ("float8_up_projection", {"variant": ("fp8_up",)}),
            ("no_zero_term", {"variant": ("no_zero_term",)}),
            ("no_kv_scale", {"variant": ("no_kv_scale",)}))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload",
                    default="serve-longcat-flash-agent-backlog")
    ap.add_argument("--seeds", default="11")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    if jax.devices()[0].platform != "tpu" and not args.rehearse:
        sys.exit("longcat_flash_check_control: no TPU")
    cell = cells.Cell(args.workload)
    if args.rehearse:
        cell.use_rehearsal_size()
    from deepspeed_tpu.utils import setup_compile_cache
    setup_compile_cache()
    from harness.compiles import CompileCounter

    def say(**row):
        print(json.dumps(row), flush=True)

    seeds = [int(s) for s in args.seeds.split(",")]
    rh = cell.config if args.rehearse else {}
    ctx = types.SimpleNamespace(
        cell=cell, seed=seeds[0], say=lambda **row: None,
        compiles=CompileCounter(), trace=False, trace_seconds=0.0,
        rehearsal=rh)
    driver = cell.driver()
    limits = cell.config["check"]
    limit = float(limits["served_off_share_limit"])
    b = driver.build(ctx)
    srv, params, cfg = b["srv"], b["params"], b["cfg"]
    reference = cell.reference()
    check, cap = b["checked"]
    pad = int(rh.get("check_pad", driver.CHECK_PAD))
    for name, kw in (("sound", {}),) + CONTROLS:
        ok, d = driver.check_warmup(check, cap, params, cfg, reference,
                                    limits, pad=pad, **kw)
        say(what="warmup", reference=name, correct=ok, **{
            k: d[k] for k in (
                "max_abs_logit_error", "tolerance", "largest_reference_logit",
                "route_decisions_compared", "route_decisions_disputed",
                "route_pairs_on_zero_experts_share", "route_worst_margin",
                "route_tie_eps")})
    samples = {}
    pad_to = int(rh.get("served_pad", driver.SERVED_PAD))
    for seed in seeds:
        b["log"].spans.clear()
        b["counts"]["prefill_tokens"].clear()
        res = driver.serve.drive(ctx, srv, b["log"], b["counts"],
                                 cell.traffic, args.seconds,
                                 np.random.default_rng([seed, 1]))
        while srv.busy:
            srv.step(time.perf_counter())
        samples[seed] = (res, driver.dots.sample_served(
            res["finished_in_window"], seed, pad_to, pad_to))
    state = srv.cache.k
    del srv, b
    state.delete()
    hp = driver.reference_hp(cfg)

    def chosen_by(kw):
        def chosen(padded, first, end):
            lg, _ = reference.logits(params, padded, hp, **kw)
            return jnp.argmax(lg[first:end], -1)
        return chosen

    for seed, (res, sample) in samples.items():
        row, verdicts = {}, {}
        for name, kw in (("sound", None),) + CONTROLS:
            gaps = driver.served_token_gaps(
                sample, params, cfg, reference, pad_to,
                chosen=None if kw is None else chosen_by(kw))
            verdicts[name], row[name], _ = driver.exaone.judge_served(
                gaps, limit)
        say(what="after_window", workload=cell.name, seed=seed,
            seconds=res["seconds"],
            finished_in_window=len(res["finished_in_window"]),
            request_tokens=[len(r.prompt) + len(r.out) for r in sample],
            limit=limit, correct=verdicts, **row)


if __name__ == "__main__":
    main()
