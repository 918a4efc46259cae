"""Runs one cell N times, each run a process of its own through the
benchmark's command as the driver's are, and prints every run with the
spreads of the set: the measurement behind a bound. The parent stays off
JAX (a chip belongs to one process). Put a set into one chip call so that
its runs share the compilation cache:

    chiprun -- python3 benchmark/tools/run_set.py --workload <cell> \\
        --seeds 2147483659,2147483777,... --sets 2 --label chat-r2p4

``--traffic-override`` is handed on to ``run.py`` (a neighbouring rate).
Rows go to stdout and to ``chiprun_out/<label>.jsonl``. Not part of a
cell's run."""

import argparse
import json
import os
import statistics
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
WINDOW_KEYS = (
    "seconds", "requests_submitted", "requests_finished_in_window",
    "token_gaps", "itl_p95_ms", "itl_p50_ms", "itl_mean_ms", "itl_p99_ms",
    "itl_p92_to_p98_ms", "tpot_p50_ms", "tpot_p90_ms", "ttft_p50_ms",
    "ttft_p95_ms", "serve_tok_s", "gen_late_p99_ms", "decode_occupancy", "decode_occupancy_per_5s",
    "tok_s_per_5s", "submitted_per_5s", "prefill_share_of_step_s",
    "prefill_dispatch_ms_p50", "decode_dispatch_ms_p50",
    "host_outside_dispatch_ms_per_step", "queued_at_end",
    "unfinished_at_end", "compiles_inside", "kv_blocks_peak", "steps",
    # the cause table's columns (PERF.md section 6, PR 56)
    "dispatches", "longest_dispatch_s", "prompt_tokens_prefilled",
    "output_tokens", "longest_steps_at_s_ms_dispatched_ms",
    "losses", "step_ms_median")


def iqr_spread(values):
    """The contract's spread: third minus first quartile
    (``statistics.quantiles(values, n=4)``) as a share of the median."""
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def range_spread(values, leave_out=1):
    """The range of the runs as a share of the median, leaving out the
    ``leave_out`` runs farthest from the median: no kinder than the
    driver's reading of a set."""
    med = statistics.median(values)
    kept = sorted(values, key=lambda v: abs(v - med))
    kept = kept[:len(kept) - leave_out] if len(kept) > 2 else kept
    return (max(kept) - min(kept)) / med


def summarise(rows, keys):
    out = {}
    for k in keys:
        vals = [r[k] for r in rows if isinstance(r.get(k), (int, float))]
        if len(vals) >= 3:
            out[k] = {"n": len(vals), "median": statistics.median(vals),
                      "min": min(vals), "max": max(vals),
                      "iqr_spread": iqr_spread(vals),
                      "range_spread_all": range_spread(vals, 0),
                      "range_spread_less_farthest": range_spread(vals, 1)}
    return out


def one_run(manifest, args, seed):
    cmd = [sys.executable] + manifest["command"][1:] + [
        "--workload", args.workload, "--seed", str(seed),
        "--seconds", str(args.seconds or manifest["run_seconds"]),
        "--trace", str(args.trace)]
    if args.traffic_override:
        cmd += ["--traffic-override", args.traffic_override]
    proc = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True)
    row = {"seed": seed, "rc": proc.returncode}
    lines = [ln for ln in proc.stdout.splitlines() if ln.startswith("{")]
    for ln in lines:
        obj = json.loads(ln)
        if obj.get("info") == "window":
            row.update({k: obj.get(k) for k in WINDOW_KEYS})
        elif obj.get("info") == "setup":
            row["setup_items"] = {k: v for k, v in obj.items()
                                  if k != "info"}
        elif obj.get("info") == "moe_counters":
            # device counters of a traced run: the held experts' share of
            # the routed pairs and the busiest held expert over the mean
            row["moe_counters"] = {k: v for k, v in obj.items()
                                   if k != "info"}
        elif obj.get("info") == "stalled_dispatches":
            row["stalls_at_s_ms"] = [[st.get("at_s"), st.get("ms"),
                                      st.get("name")]
                                     for st in obj.get("stalls", [])]
        elif obj.get("info") == "correctness":
            row["max_abs_logit_error"] = obj.get("max_abs_logit_error")
        elif obj.get("info") == "correctness_after_window":
            row.update({k: obj.get(k) for k in (
                "served_gap_max", "served_tokens_compared",
                "tokens_not_the_references_first",
                "longest_request_tokens", "reference_s")})
    if proc.returncode == 0 and lines:
        last = json.loads(lines[-1])
        row.update(correct=last["correct"], attempted=last["attempted"],
                   failed=last["failed"],
                   memory_peak_bytes=last["device"].get("memory_peak_bytes"),
                   busy_s=last["device"].get("busy_s"),
                   window_s=last["device"].get("window_s"))
        for name, m in last["metrics"].items():
            row["metric:" + name] = m["value"]
        if "breakdown" in last:
            row["breakdown"] = last["breakdown"]
    else:
        row["stderr_tail"] = proc.stderr[-1500:]
    return row


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--sets", type=int, default=1,
                    help="sets of the same seeds, one after another")
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, default=0, choices=(0, 1))
    ap.add_argument("--traffic-override", default=None)
    ap.add_argument("--label", default="set")
    args = ap.parse_args()
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    seeds = [int(s) for s in args.seeds.split(",")]
    with open(os.path.join(out_dir, args.label + ".jsonl"), "w") as f:
        def say(**row):
            line = json.dumps(row)
            print(line, flush=True)
            f.write(line + "\n")
            f.flush()
        for k in range(args.sets):
            rows = []
            for seed in seeds:
                row = one_run(manifest, args, seed)
                row.update(set=k, label=args.label)
                rows.append(row)
                say(**row)
            good = [r for r in rows if r["rc"] == 0]
            keys = sorted(k2 for k2 in (good[0] if good else {})
                          if k2.startswith("metric:")) + [
                "itl_p95_ms", "tpot_p90_ms", "tpot_p50_ms", "itl_mean_ms",
                "ttft_p50_ms", "serve_tok_s", "decode_occupancy",
                "served_gap_max", "dispatches", "longest_dispatch_s"]
            say(summary_of_set=k, label=args.label, runs=len(rows),
                all_correct=all(r.get("correct") for r in rows),
                failed=sum(r.get("failed", 0) for r in good),
                # after each set's first run every program is in the cache
                spreads_without_first_run=summarise(good[1:], keys[:1]),
                spreads=summarise(good, keys))


if __name__ == "__main__":
    main()
