"""The controls of the Qwen3-Next cell's checks
(``drivers/serve_qwen3_next.py``): builds the cell's engine once and repeats
the warm-up comparison (checks 1 and 2) against the sound reference and
against three references: every weight rounded to float8 (e4m3) and back
(the nearest precision below the served bf16); the RECURRENT STATE rounded
to bfloat16 after every token (the configuration states float32); and the
full-attention layers' OUTPUT GATE left out (the term of this model that the
engine's paged attention gained). Each has to come out NOT correct. Then,
for each seed, it drives a short window at the cell's own load, takes the
sample a run would take and, once the pools and the state are freed, reads
check 3 from the sound reference: for the tokens the PROGRAM served (sound:
under ``check.served_off_share_limit``) and for the tokens each control's
reference puts first. Run once, on the chip, by a PR that changes the
checks or their limits:

    chiprun --timeout 3000 -- python3 \\
        benchmark/tools/qwen3_next_check_control.py --seeds 11 --seconds 20

One engine and one set of weights (the first seed's) serve all the seeds:
each seed draws its own prompts; ``--seeds ""`` stops after the warm-up
readings. One JSON row per reading. Not part of a cell's run."""

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
    ap.add_argument("--workload", default="serve-qwen3next-context-qa-backlog")
    ap.add_argument("--seeds", default="11")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    if jax.devices()[0].platform != "tpu" and not args.rehearse:
        sys.exit("qwen3_next_check_control: no TPU")
    cell = cells.Cell(args.workload)
    if args.rehearse:
        cell.use_rehearsal_size()
    from deepspeed_tpu.utils import setup_compile_cache
    setup_compile_cache()
    from harness.compiles import CompileCounter

    def say(**row):
        print(json.dumps(row), flush=True)

    seeds = [int(s) for s in args.seeds.split(",") if s]
    rh = cell.config if args.rehearse else {}
    ctx = types.SimpleNamespace(
        cell=cell, seed=seeds[0] if seeds else 11, say=lambda **row: None,
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
    controls = (("float8", {"fp8": True}),
                ("bfloat16_recurrent_state", {"variant": ("state_bf16",)}),
                ("no_output_gate", {"variant": ("no_attn_gate",)}))
    for name, kw in (("sound", {}),) + controls:
        ok, d = driver.check_warmup(check, cap, params, cfg, reference,
                                    limits, pad=pad, **kw)
        say(what="warmup", reference=name, correct=ok, **{
            k: d[k] for k in (
                "max_abs_logit_error", "tolerance", "rms_logit_error",
                "tolerance_rms", "largest_reference_logit",
                "route_decisions_compared", "route_decisions_disputed",
                "route_worst_margin",
                "route_tie_eps")})
    samples = {}
    pad_to = int(rh.get("served_pad", driver.SERVED_PAD))
    rows = int(rh.get("served_rows", driver.SERVED_ROWS))
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
    pools = srv.cache.pools
    del srv, b
    for p in pools:
        if p is not None:
            p.delete()
    hp = driver.reference_hp(cfg)

    def chosen(**kw):
        def first_tokens(padded, first, end):
            lg, _ = reference.logits(params, padded, hp, first=first,
                                     rows=end - first, with_route=False,
                                     **kw)
            return jnp.argmax(lg, -1)
        return first_tokens

    for seed, (res, sample) in samples.items():
        sound = driver.served_token_gaps(sample, params, cfg, reference,
                                         pad_to, rows)
        s_ok, s_row, _ = driver.exaone.judge_served(sound, limit)
        row = {"sound": s_row, "sound_correct": s_ok}
        for name, kw in controls:
            gaps = driver.served_token_gaps(sample, params, cfg, reference,
                                            pad_to, rows,
                                            chosen=chosen(**kw))
            c_ok, c_row, _ = driver.exaone.judge_served(gaps, limit)
            row[name] = c_row
            row[name + "_correct"] = c_ok
        say(what="after_window", workload=cell.name, seed=seed,
            seconds=res["seconds"],
            finished_in_window=len(res["finished_in_window"]),
            request_tokens=[len(r.prompt) + len(r.out) for r in sample],
            limit=limit, **row)


if __name__ == "__main__":
    main()
