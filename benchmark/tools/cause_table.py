"""Reads the rows ``run_set.py`` left under ``chiprun_out/`` and prints the
cause table of PERF.md section 6 (PR 56): per run the end-to-end metric,
the dispatches of the window, the longest single one, the median decode and
prefill dispatch, the host's time outside them and the slowest 5 s bin; per
set the spread by quartiles and by range, over all runs and without those
whose longest dispatch stalled. Reads files only; needs no chip.

    python3 benchmark/tools/cause_table.py chiprun_out/<label>.jsonl ..."""

import json
import statistics
import sys

STALL_S = 0.5


def spread(values):
    if len(values) < 3:
        return None, None
    q = statistics.quantiles(values, n=4)
    med = statistics.median(values)
    return (q[2] - q[0]) / med, (max(values) - min(values)) / med


def main(paths):
    for path in paths:
        rows = [json.loads(ln) for ln in open(path)]
        runs = [r for r in rows if "seed" in r and r.get("rc") == 0]
        if not runs:
            print(f"{path}: no run")
            continue
        name = next(k for k in runs[0] if k.startswith("metric:")
                    and k != "metric:setup_s")
        print(f"\n{path}: {name[7:]}")
        print("| set | seed | value | setup_s | dispatches | longest_s | "
              "decode_ms_p50 | prefill_ms_p50 | host_ms_step | slowest_5s | "
              "finished | correct |")
        print("|---|---|---|---|---|---|---|---|---|---|---|---|")
        for r in runs:
            bins = [b for b in (r.get("tok_s_per_5s") or [])[:-1] if b]
            print("| {} | {} | {:.6g} | {:.1f} | {} | {} | {} | {} | {} | {} "
                  "| {} | {} |".format(
                      r.get("set"), r["seed"], r[name],
                      r.get("metric:setup_s", float("nan")),
                      r.get("dispatches"),
                      _f(r.get("longest_dispatch_s"), 3),
                      _f(r.get("decode_dispatch_ms_p50"), 3),
                      _f(r.get("prefill_dispatch_ms_p50"), 3),
                      _f(r.get("host_outside_dispatch_ms_per_step"), 3),
                      _f(min(bins), 0) if bins else None,
                      r.get("requests_finished_in_window"),
                      r.get("correct")))
        for k in sorted({r.get("set") for r in runs}):
            mine = [r for r in runs if r.get("set") == k]
            vals = [r[name] for r in mine]
            calm = [r[name] for r in mine
                    if (r.get("longest_dispatch_s") or 0) < STALL_S]
            iqr, rng = spread(vals)
            iqr_c, rng_c = spread(calm)
            print(f"set {k}: n {len(vals)} median "
                  f"{statistics.median(vals):.6g} iqr {_p(iqr)} range "
                  f"{_p(rng)}; without {len(vals) - len(calm)} stalled: iqr "
                  f"{_p(iqr_c)} range {_p(rng_c)}")
            for key in ("decode_dispatch_ms_p50", "prefill_dispatch_ms_p50",
                        "dispatches"):
                v = [r[key] for r in mine
                     if isinstance(r.get(key), (int, float))]
                if len(v) >= 3:
                    print(f"   {key}: range {_p((max(v) - min(v)) / statistics.median(v))}")


def _f(x, n):
    return None if x is None else round(x, n)


def _p(x):
    return "n/a" if x is None else f"{100 * x:.2f}%"


if __name__ == "__main__":
    main(sys.argv[1:])
