"""Finds an open-loop cell's knee: builds the serving engine once and
offers the cell's traffic at each of a few fixed rates, one window each,
draining in between. A rate is sustained when the queue is (nearly) empty
at the end of its window and the finished requests keep up with the
arrivals; the cell's traffic file then fixes 0.8 of the highest sustained
rate. Run once, on the chip, by the PR that adds the cell:

    chiprun -- python3 benchmark/tools/sweep_rate.py --workload <cell> \\
        --rates 0.3,0.4,0.5 --seconds 40

Prints one JSON row per rate. Not part of a cell's run."""

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
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--ramp-seconds", type=float, default=None,
                    help="ramp of this many seconds at each rate (default: "
                    "the mix's ramp_requests at every rate)")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    import jax
    if jax.devices()[0].platform != "tpu" and not args.rehearse:
        sys.exit("sweep_rate: no TPU")
    cell = cells.Cell(args.workload)
    if args.rehearse:
        cell.use_rehearsal_size()
    from deepspeed_tpu.utils import setup_compile_cache
    setup_compile_cache()
    from harness.compiles import CompileCounter

    def say(**row):
        print(json.dumps(row), flush=True)

    ctx = types.SimpleNamespace(
        cell=cell, seed=args.seed, say=say, compiles=CompileCounter(),
        rehearsal=cell.config if args.rehearse else {}, trace=False,
        trace_seconds=0.0)
    driver = cell.driver()
    srv, log, counts, _, correct, _, _ = driver.build(ctx)
    for i, rate in enumerate(float(x) for x in args.rates.split(",")):
        mix = dict(cell.traffic, rate_rps=rate)
        if args.ramp_seconds is not None:
            mix["ramp_requests"] = int(round(rate * args.ramp_seconds))
        log.spans.clear()
        counts["prefill_tokens"].clear()
        res = driver.drive(ctx, srv, log, counts, mix, args.seconds,
                           np.random.default_rng([args.seed, i]))
        recs = res["run"]["records"]
        row = res["window_row"]
        say(sweep_rate_rps=rate, seconds=res["seconds"],
            ramp_requests=int(mix["ramp_requests"]), arrivals=len(recs),
            finished_by_end=len(recs) - res["unfinished_at_end"],
            queued_at_end=res["backlog_at_end"],
            unfinished_at_end=res["unfinished_at_end"],
            failed=res["failed"],
            correct=bool(res["correct"] and correct),
            **{k: row[k] for k in (
                "ttft_p50_ms", "ttft_p95_ms", "itl_p50_ms", "itl_p95_ms",
                "itl_p99_ms", "itl_mean_ms", "tpot_p50_ms", "tpot_p90_ms",
                "token_gaps", "requests_finished_in_window", "output_tok_s",
                "decode_occupancy", "decode_occupancy_per_5s",
                "gen_late_p99_ms", "kv_blocks_peak")})
        t = time.perf_counter()
        while srv.busy:
            srv.step(time.perf_counter())
        say(drained_s=time.perf_counter() - t)


if __name__ == "__main__":
    main()
