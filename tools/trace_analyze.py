"""Capture + analyze a device trace of the training step.

Runs N traced train steps (any bench config) and aggregates the XPlane
Chrome-trace events into a per-op-category time breakdown — the tool that
turns "MFU is X%" into "Y ms goes to fusions / dots / the flash custom
call / copies". TPU analog of reading an nsys timeline of the reference's
NVTX ranges (ref: deepspeed/utils/nvtx.py + docs/_tutorials/pytorch-profiler.md).

Usage:
  python tools/trace_analyze.py run [preset] [batch] [remat] [loss_chunk]
      — trains 2 traced steps on the local chip, writes /tmp/dstrace,
        then analyzes it.
  python tools/trace_analyze.py read /tmp/dstrace
      — re-analyze an existing capture.
  python tools/trace_analyze.py serve /tmp/serve_trace.json
      — analyze a serving-telemetry Perfetto export
        (deepspeed_tpu/telemetry, docs/OBSERVABILITY.md): per-request
        lifecycle spans, step-phase breakdown, injected-fault timeline.
  python tools/trace_analyze.py fleet /tmp/router_trace.json
      — analyze a ROUTER-level export: per-replica dispatch counts,
        breaker/health timeline, drains/restarts/fleet-shape changes
        and the autoscale decision timeline with each decision's
        triggering window metrics.
  python tools/trace_analyze.py cost <artifact-or-snapshot.json>
      — per-phase / per-tenant cost summary (FLOPs, HBM bytes, KV
        block-seconds) from either a flight-recorder postmortem
        artifact (CRC-verified) or a live ``CostAccountant.snapshot()``
        JSON dump.

``serve``/``fleet``/``cost`` accept ``--json``: print the full summary
dict as one JSON document (stable schema — the same dict the tests
assert on) instead of the human report.
"""

import collections
import glob
import gzip
import json
import os
import sys

sys.path.insert(0, ".")


def categorize(name: str) -> str:
    n = name.lower()
    if "custom-call" in n or "tpu_custom_call" in n or "pallas" in n:
        return "pallas kernels (flash etc.)"
    if n.startswith("fusion") or ".fusion" in n:
        return "XLA fusions (elementwise/LN/softmax)"
    if "convolution" in n or n.startswith("dot") or "einsum" in n or \
            "matmul" in n or ".dot" in n:
        return "matmuls (MXU)"
    if "all-reduce" in n or "all-gather" in n or "reduce-scatter" in n or \
            "all-to-all" in n or "collective" in n or "permute" in n:
        return "collectives"
    if "copy" in n or "transpose" in n or "reshape" in n or "bitcast" in n:
        return "copies/transposes"
    if "dynamic-update-slice" in n or "dynamic-slice" in n or "slice" in n \
            or "scatter" in n or "gather" in n or "pad" in n or "concat" in n:
        return "slice/gather/pad"
    if "infeed" in n or "outfeed" in n or "host" in n or "transfer" in n:
        return "host transfer"
    return "other"


def analyze(log_dir: str, top: int = 25):
    files = glob.glob(os.path.join(
        log_dir, "**", "*.trace.json.gz"), recursive=True)
    assert files, f"no trace.json.gz under {log_dir}"
    path = max(files, key=os.path.getmtime)
    with gzip.open(path, "rt") as f:
        trace = json.load(f)
    events = trace.get("traceEvents", [])

    # device-lane complete events only (TensorCore ops have 'dur')
    pid_names = {e["pid"]: e["args"].get("name", "")
                 for e in events if e.get("ph") == "M"
                 and e.get("name") == "process_name" and "args" in e}
    dev_pids = {p for p, n in pid_names.items()
                if "/device:TPU" in n or "TPU Core" in n or "TensorCore" in n}

    if not dev_pids:
        print("WARNING: no TPU device lane matched — totals below include "
              "HOST lanes and are not a device-time breakdown",
              file=sys.stderr)

    by_op = collections.Counter()
    by_cat = collections.Counter()
    total = 0.0
    for e in events:
        if e.get("ph") != "X" or "dur" not in e:
            continue
        if dev_pids and e.get("pid") not in dev_pids:
            continue
        name = e.get("name", "?")
        dur = e["dur"]  # microseconds
        by_op[name] += dur
        by_cat[categorize(name)] += dur
        total += dur

    print(json.dumps({"trace": os.path.relpath(path, log_dir),
                      "total_device_us": round(total, 1)}))
    print("\n-- by category --")
    for cat, us in by_cat.most_common():
        print(f"{us/1e3:10.2f} ms  {100*us/max(total,1e-9):5.1f}%  {cat}")
    print(f"\n-- top {top} ops --")
    for name, us in by_op.most_common(top):
        print(f"{us/1e3:10.2f} ms  {100*us/max(total,1e-9):5.1f}%  {name[:110]}")


def _load_trace(path: str) -> dict:
    if path.endswith(".gz"):
        with gzip.open(path, "rt") as f:
            return json.load(f)
    with open(path) as f:
        return json.load(f)


def analyze_serving_trace(path: str, quiet: bool = False) -> dict:
    """Summarize a serving-telemetry Chrome-trace export
    (``RequestTracer.to_chrome_trace``): per-request lifecycle span
    sequences (queued/prefill/decode, terminal state), the self time of
    the program's spans by name (``serve.step`` and below: duration
    minus children), and the injected-fault timeline. Returns the
    summary dict (tests assert on it); prints it unless ``quiet``."""
    trace = _load_trace(path)
    events = trace.get("traceEvents", [])
    requests, span_self_us, faults = {}, collections.Counter(), []
    for e in events:
        ph, cat = e.get("ph"), e.get("cat")
        if ph == "X" and cat == "request":
            rid = e.get("args", {}).get("rid")
            requests.setdefault(rid, {"spans": [], "state": None,
                                      "span_us": 0.0})
            requests[rid]["spans"].append((e["ts"], e["name"]))
            requests[rid]["span_us"] += e.get("dur", 0.0)
            state = e.get("args", {}).get("state")
            if state:
                requests[rid]["state"] = state
        elif ph == "X" and cat == "span":
            span_self_us[e["name"]] += e.get("args", {}).get("self_us", 0.0)
        elif ph == "i" and cat == "fault":
            faults.append(dict(e.get("args", {}), ts=e.get("ts")))
    for r in requests.values():
        r["spans"] = [name for _, name in sorted(r["spans"],
                                                 key=lambda s: s[0])]
    summary = {
        "n_events": len(events),
        "dropped_events": trace.get("dropped_events", 0),
        "requests": requests,
        "span_self_us": {k: round(v, 1) for k, v in span_self_us.items()},
        "faults": faults,
    }
    if not quiet:
        print(json.dumps({"trace": path, "n_events": len(events),
                          "requests": len(requests),
                          "faults": len(faults)}))
        print("\n-- span self time --")
        total = sum(span_self_us.values())
        for name, us in span_self_us.most_common():
            print(f"{us/1e3:10.2f} ms  {100*us/max(total,1e-9):5.1f}%  {name}")
        print("\n-- requests --")
        for rid, r in requests.items():
            print(f"  {rid}: {' > '.join(r['spans'])}"
                  f"  [{r['state'] or 'in flight'}]"
                  f"  {r['span_us']/1e3:.2f} ms")
        if faults:
            print("\n-- injected faults --")
            for f in faults:
                print(f"  step {f.get('step')}: {f.get('site')}"
                      f":{f.get('kind')} (visit {f.get('visit')})")
    return summary


def analyze_fleet_trace(path: str, quiet: bool = False) -> dict:
    """Summarize a ROUTER-level Perfetto export: per-replica dispatch
    occupancy, the breaker/health timeline, drains, warm restarts,
    fleet-shape (``scale``) changes, router-side sheds and the full
    autoscale decision timeline (each decision instant carries the
    windowed metrics that triggered it — the reconstructability
    contract of docs/OBSERVABILITY.md). Returns the summary dict
    (tests assert on it); prints it unless ``quiet``."""
    trace = _load_trace(path)
    events = trace.get("traceEvents", [])
    dispatch_per_replica = collections.Counter()
    resumed = 0
    breaker, drains, restarts, scale, decisions, degraded = \
        [], [], [], [], [], []
    sheds = 0
    for e in events:
        if e.get("ph") != "i" or e.get("cat") != "scheduler":
            continue
        name, a = e.get("name"), dict(e.get("args", {}))
        a["ts"] = e.get("ts")
        if name == "dispatch":
            dispatch_per_replica[a.get("replica")] += 1
            resumed += bool(a.get("resumed"))
        elif name == "breaker":
            breaker.append(a)
        elif name == "drain":
            drains.append(a)
        elif name == "restart":
            restarts.append(a)
        elif name == "scale":
            scale.append(a)
        elif name == "autoscale":
            decisions.append(a)
        elif name == "shed":
            sheds += 1
        elif name == "degraded":
            degraded.append(a)
    by_action = collections.Counter(d.get("action") for d in decisions)
    summary = {
        "n_events": len(events),
        "dispatch": {
            "total": sum(dispatch_per_replica.values()),
            "per_replica": {str(k): v for k, v
                            in sorted(dispatch_per_replica.items())},
            "resumed": resumed,
        },
        "breaker": breaker,
        "drains": drains,
        "restarts": restarts,
        "scale": scale,
        "autoscale": {"decisions": decisions,
                      "by_action": dict(by_action)},
        "sheds": sheds,
        "degraded": degraded,
    }
    if not quiet:
        print(json.dumps({
            "trace": path, "n_events": len(events),
            "dispatched": summary["dispatch"]["total"],
            "breaker_transitions": len(breaker), "drains": len(drains),
            "restarts": len(restarts), "scale_changes": len(scale),
            "autoscale_decisions": len(decisions), "sheds": sheds}))
        if dispatch_per_replica:
            print("\n-- dispatches by replica --")
            for idx, n in sorted(dispatch_per_replica.items()):
                print(f"  replica {idx}: {n}"
                      + (f"  ({resumed} resumed fleet-wide)"
                         if idx == min(dispatch_per_replica) and resumed
                         else ""))
        if breaker:
            print("\n-- health timeline --")
            for b in breaker:
                print(f"  step {b.get('step')}: replica {b.get('replica')}"
                      f" {b.get('prev')} -> {b.get('state')}"
                      f" ({b.get('reason', '')})")
        if scale:
            print("\n-- fleet shape --")
            for s in scale:
                print(f"  step {s.get('step')}: {s.get('action')}"
                      f" replica {s.get('replica')}"
                      f" ({s.get('reason', '')})")
        acted = [d for d in decisions if d.get("action") != "noop"]
        if decisions:
            print(f"\n-- autoscale decisions "
                  f"({len(decisions)} evals, {len(acted)} actions) --")
            for d in acted:
                print(f"  step {d.get('step')}: {d.get('action')}"
                      f"  p99_ttft={d.get('p99_ttft'):.4g}"
                      f" (slo {d.get('ttft_slo')},"
                      f" {int(d.get('window_count', 0))} obs)"
                      f" load={d.get('load')}"
                      f" active={d.get('active_replicas')}")
    return summary


def analyze_cost(path: str, quiet: bool = False) -> dict:
    """Per-phase / per-tenant cost summary from a cost-accounting
    snapshot. Accepts either a flight-recorder postmortem artifact
    (``{"version", "crc32", "body"}`` — CRC-verified via
    ``tools/postmortem.py``'s stdlib reader) or a raw
    ``CostAccountant.snapshot()`` JSON file. Returns the summary dict
    (tests assert on it); prints it unless ``quiet``."""
    with open(path) as f:
        doc = json.load(f)
    if isinstance(doc, dict) and "body" in doc and "crc32" in doc:
        from tools.postmortem import verify_artifact
        verify_artifact(doc)
        costs = doc["body"].get("costs") or {}
        source = "postmortem"
    else:
        costs = doc
        source = "snapshot"
    per_class = costs.get("totals") or {}
    tenants = costs.get("tenants") or {}

    def _fold(fp):
        out = {"flops": 0, "hbm_bytes": 0, "dispatches": 0,
               "block_seconds": int(fp.get("block_seconds", 0))}
        for cls, c in fp.items():
            if isinstance(c, dict):
                for k in ("flops", "hbm_bytes", "dispatches"):
                    out[k] += int(c.get(k, 0))
        return out

    summary = {
        "source": source,
        "flops_total": int(costs.get("flops_total") or 0),
        "hbm_bytes_total": int(costs.get("hbm_bytes_total") or 0),
        "block_seconds_total": int(costs.get("block_seconds_total") or 0),
        "per_class": per_class,
        "per_tenant": {tid: _fold(fp) for tid, fp
                       in sorted(tenants.items())},
    }
    if not quiet:
        print(json.dumps({"file": path, "source": source,
                          "flops_total": summary["flops_total"],
                          "hbm_bytes_total": summary["hbm_bytes_total"],
                          "kv_block_seconds":
                          summary["block_seconds_total"]}))
        if per_class:
            print("\n-- by dispatch class --")
            for cls, c in sorted(per_class.items()):
                print(f"  {cls:<8} {c.get('dispatches', 0):>8} dispatches"
                      f" {c.get('flops', 0):>16} flops"
                      f" {c.get('hbm_bytes', 0):>16} bytes")
        if summary["per_tenant"]:
            print("\n-- by tenant --")
            for tid, t in summary["per_tenant"].items():
                print(f"  {tid:<14} {t['flops']:>16} flops"
                      f" {t['hbm_bytes']:>16} bytes"
                      f" {t['block_seconds']:>8} block-s")
    return summary


def run():
    import jax
    import numpy as np

    preset = sys.argv[2] if len(sys.argv) > 2 else "gpt2-1.5b"
    batch = int(sys.argv[3]) if len(sys.argv) > 3 else 16
    remat = sys.argv[4] if len(sys.argv) > 4 else "full"
    loss_chunk = int(sys.argv[5]) if len(sys.argv) > 5 else 2048

    import deepspeed_tpu
    from deepspeed_tpu.models import gpt
    import jax.numpy as jnp

    cfg = gpt.preset(preset, max_seq_len=1024, dtype=jnp.bfloat16,
                     remat=True, remat_policy=remat,
                     use_flash_attention=True, flash_block_q=1024,
                     flash_block_kv=1024, loss_chunk=loss_chunk)
    params = gpt.init_params(jax.random.PRNGKey(0), cfg)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=gpt.make_loss_fn(cfg), model_parameters=params,
        config={"train_batch_size": batch,
                "bf16": {"enabled": True, "memory_efficient": True},
                "zero_optimization": {"stage": 3},
                "optimizer": {"type": "adamw", "params": {"lr": 1e-4}},
                "steps_per_print": 10_000})
    del params
    tokens = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (batch, 1025)).astype(np.int32)
    data = {"tokens": tokens}
    jax.block_until_ready(engine.train_batch(data)["loss"])  # compile

    log_dir = "/tmp/dstrace"
    engine.start_trace(log_dir, steps=2)
    for _ in range(2):
        float(engine.train_batch(data)["loss"])
    analyze(log_dir)


if __name__ == "__main__":
    _as_json = "--json" in sys.argv[2:]
    if sys.argv[1:] and sys.argv[1] == "read":
        analyze(sys.argv[2])
    elif sys.argv[1:] and sys.argv[1] == "serve":
        s = analyze_serving_trace(sys.argv[2], quiet=_as_json)
        if _as_json:
            print(json.dumps(s, sort_keys=True))
    elif sys.argv[1:] and sys.argv[1] == "fleet":
        s = analyze_fleet_trace(sys.argv[2], quiet=_as_json)
        if _as_json:
            print(json.dumps(s, sort_keys=True))
    elif sys.argv[1:] and sys.argv[1] == "cost":
        s = analyze_cost(sys.argv[2], quiet=_as_json)
        if _as_json:
            print(json.dumps(s, sort_keys=True))
    else:
        run()
