"""One run of one cell of BENCHMARK.json, in a new process:

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Requires a TPU with at least the chips the cell asks for (exit 2 without
one: no result is printed). Places JAX's persistent compilation cache at the
fixed path ``<checkout>/.jax_cache``, builds weights on the device from the
seed, warms only the cell's own shapes, checks correctness outside the
window, measures for ``--seconds``, prints earlier lines freely and the
contract's one JSON object last. ``--trace 0`` reports the cell's end-to-end
metrics, ``--trace 1`` its per-layer metrics (a profiler trace of the last
seconds of the window, written under ``<checkout>/.bench_out``).

``--rehearse`` is for the tests: a tiny size from the configuration's and
the traffic's ``rehearsal`` overrides, on whatever platform JAX has. It
never prints a result, on a TPU either: it writes what it computed to
stderr, marked as not a measurement of the cell, and exits 3. (The tiny
sizes are for the CPU: on a TPU the program refuses flash attention at the
train cells' 64 tokens and the rehearsal ends in that error.)"""

T_PROCESS = __import__("time").perf_counter()

import argparse  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import time  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, BENCH_DIR)
sys.path.insert(0, ROOT)

from harness import (cells, peaks, provenance, rooflines, spans,  # noqa: E402
                     tracereduce)

TRACE_SECONDS = 6.0     # serving: the traced tail of the window
TRACE_STEPS = 2         # training: the traced last steps


class Context:
    def __init__(self, cell, args, compiles, say, peak_table):
        self.cell, self.seed = cell, args.seed
        self.seconds, self.trace = float(args.seconds), bool(args.trace)
        self.compiles, self.say, self.peaks = compiles, say, peak_table
        self.rooflines = rooflines
        # tiny-size knobs of the drivers (merged into the configuration)
        self.rehearsal = cell.config if args.rehearse else {}
        self.trace_seconds = min(TRACE_SECONDS, self.seconds / 3.0)
        self.trace_steps = TRACE_STEPS
        self.trace_dir = os.path.join(cell.root, ".bench_out", "trace",
                                      cell.name)
        self.trace_path = None

    def start_trace(self):
        import jax
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        os.makedirs(self.trace_dir, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        ann = jax.profiler.TraceAnnotation(spans.WINDOW_SPAN)
        ann.__enter__()
        return {"ann": ann, "t_start": time.perf_counter()}

    def stop_trace(self, tracing):
        import jax
        tracing["ann"].__exit__(None, None, None)
        tracing["t_stop"] = time.perf_counter()
        jax.profiler.stop_trace()
        found = sorted(glob.glob(os.path.join(
            self.trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
        self.trace_path = found[-1] if found else None
        self.host_trace_window = (tracing["t_start"], tracing["t_stop"])


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, default=0, choices=(0, 1))
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--traffic-override", default=None,
                    help="JSON laid over the cell's traffic mix: for the "
                    "tools (a neighbouring rate); never part of a check")
    args = ap.parse_args()

    cell = cells.Cell(args.workload)
    if args.rehearse:
        cell.use_rehearsal_size()
    if args.traffic_override:
        cell.traffic = dict(cell.traffic, **json.loads(args.traffic_override))

    def say(**row):
        print(json.dumps(row), flush=True)

    import jax
    devs = jax.devices()
    on_tpu = devs[0].platform == "tpu"
    if not on_tpu and not args.rehearse:
        print(f"benchmark: no TPU: JAX reports platform="
              f"{devs[0].platform!r} ({devs[0].device_kind}). Nothing is "
              f"measured off the chip.", file=sys.stderr)
        sys.exit(2)
    if len(devs) < cell.chips:
        print(f"benchmark: workload {cell.name} needs {cell.chips} chips, "
              f"JAX found {len(devs)}", file=sys.stderr)
        sys.exit(2)
    peak_table = peaks.peaks_for(devs[0].device_kind) if on_tpu else \
        {"bf16_flops": float("nan"), "hbm_bytes_per_s": float("nan")}

    # the program places the cache itself (<checkout>/.jax_cache, or where
    # JAX_COMPILATION_CACHE_DIR says); every program is worth keeping
    from deepspeed_tpu.utils import setup_compile_cache
    cache_dir = setup_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    from harness.compiles import CompileCounter
    compiles = CompileCounter()
    say(info="start", workload=cell.name, seed=args.seed,
        seconds=args.seconds, trace=args.trace,
        traffic_override=args.traffic_override,
        device={"platform": devs[0].platform, "kind": devs[0].device_kind,
                "count": len(devs)},
        compile_cache_dir=cache_dir, import_s=time.perf_counter() - T_PROCESS)

    ctx = Context(cell, args, compiles, say, peak_table)
    t_driver = time.perf_counter()
    res = cell.driver().run(ctx)
    setup_s = res["window_start"] - T_PROCESS
    say(info="setup", setup_s=setup_s, import_s=t_driver - T_PROCESS,
        unaccounted_s=setup_s - (t_driver - T_PROCESS)
        - sum(res["setup_items"].values()), **res["setup_items"],
        compiles_total=compiles.count, compile_or_cache_load_s=compiles.seconds)

    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    peaks_b = [(d.memory_stats() or {}).get("peak_bytes_in_use")
               for d in devs[:cell.chips]]
    device["memory_peak_bytes"] = max([p for p in peaks_b if p] or [0])
    limits = [(d.memory_stats() or {}).get("bytes_limit")
              for d in devs[:cell.chips]]
    run = res["run"]
    run.update(peaks=peak_table, rooflines=rooflines, cell=cell,
               memory_peak_bytes=device["memory_peak_bytes"],
               memory_limit_bytes=max([x for x in limits if x] or [0]),
               end_to_end=res["end_to_end"], trace=None, say=say,
               trace_host_window=getattr(ctx, "host_trace_window", None))

    # the comparison with the reference that needs the window's results
    # runs here: after the window, after the peak was read
    compared = list(res.get("compared", []))
    correct = res["correct"]
    if "after_window" in res:
        ok, more = res["after_window"]()
        correct = bool(correct and ok)
        compared += more
    out = {"correct": correct, "attempted": res["attempted"],
           "failed": res["failed"]}
    if not args.trace:
        values = dict(res["end_to_end"], setup_s=setup_s)
        metrics = {}
        for m in cell.end_to_end:
            if m["name"] not in values:
                raise SystemExit(f"benchmark: driver gave no {m['name']}")
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    else:
        if ctx.trace_path:
            t = time.perf_counter()
            names = spans.SERVING_SPANS + spans.TRAIN_SPANS
            run["trace"] = tracereduce.load(ctx.trace_path, names,
                                            spans.WINDOW_SPAN)
            say(info="trace", path=os.path.relpath(ctx.trace_path, cell.root),
                bytes=os.path.getsize(ctx.trace_path),
                reduce_s=time.perf_counter() - t,
                device_planes=len(run["trace"].devices) if run["trace"]
                else 0)
        tr = run["trace"]
        if tr is not None:
            device["busy_s"] = tr.busy_s
            device["window_s"] = tr.window_s
            # where the trace carries the programs' HLO: the operations as
            # <op>@<program>/<scope> <file>:<line>, and the idle gaps by the
            # program's own innermost span (telemetry is on when traced)
            pt = provenance.of_run(run)
            if pt is not None and pt.tables:
                sums, _ = pt.idle_gaps(tr.host_spans)
                out["breakdown"] = {
                    "device_ops": pt.top_ops(10),
                    "idle_gaps": [[k, v] for k, v in sorted(
                        sums.items(), key=lambda kv: -kv[1])[:10]]}
            else:
                out["breakdown"] = {"device_ops": tr.top_ops(10),
                                    "idle_gaps": tr.idle_gaps(10)}
        metrics = {}
        for m in cell.per_layer:
            reader = cell.layer_reader(m["name"])
            value = reader.read(run) if reader is not None else None
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    out["metrics"] = metrics
    out["device"] = device
    # a serving run's longest single dispatch of its window, so that a
    # reader can tell a stalled run from a slow program (ISSUE 56 B.3); the
    # contract's reader ignores the key
    if "longest_dispatch_s" in res:
        out["longest_dispatch_s"] = res["longest_dispatch_s"]
    # every number compared beside its limit, last on standard error too
    say(info="compared", correct=correct,
        compared=[[n, v, lim] for n, v, lim in compared])
    for n, v, lim in compared:
        print(f"compared: {n} = {v} (limit {lim})", file=sys.stderr)
    print(f"correct: {correct}", file=sys.stderr, flush=True)
    # and in the result's line, under a key of its own that comes last (a
    # number that is not finite goes as text: the line stays strict JSON)
    out["compared"] = {
        n: [v if not isinstance(v, float) or math.isfinite(v) else repr(v), lim]
        for n, v, lim in compared}
    if args.rehearse:
        print("REHEARSAL on " + devs[0].platform + " (tiny size, NOT a "
              "measurement of the cell; no result is printed): "
              + json.dumps(out), file=sys.stderr)
        sys.exit(3)
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
