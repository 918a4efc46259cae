"""The arithmetic behind the per-layer readers of ``serve_exaone_moe``
cells (``layer_metrics/moe_*.py``, ``attn_*_time_share.py``,
``paged_decode_mixed_roofline.py``). A function that finds nothing to read
(no device trace, no scope of that name, no counters: an end-to-end run, or
a program that lacks what PR 28 added) returns None and the metric is left
out of the line; none raises."""

from harness import provenance, rooflines_moe

MOE_SCOPES = ("moe_router", "moe_experts", "moe_shared")


def _scope_seconds(run, names, program=None):
    """Device self seconds (mean over devices) of the operations whose
    named-scope path holds one of ``names``, or None without provenance."""
    pt = provenance.of_run(run)
    if pt is None or not pt.tables or run.get("kind") != "serve":
        return None

    def pick(p, o, e):
        if e is None or (program and program not in p):
            return False
        return bool(set((e.get("scope") or "").split("/")) & set(names))

    return pt.seconds(pick)


def scope_time_share(run, names):
    """Device seconds under the named scopes over busy seconds, %."""
    tr = run.get("trace")
    s = _scope_seconds(run, names)
    if s is None or tr is None or tr.busy_s <= 0 or s <= 0:
        return None
    return 100.0 * s / tr.busy_s


def moe_load_max_over_mean(run):
    """Decode dispatches: the busiest held expert's pairs over the mean
    pairs per held expert (1.0 = even load), from the device counters."""
    c = (run.get("moe_counters") or {}).get("decode")
    if not c or not c["pairs_held"]:
        return None
    return c["busiest_expert_pairs"] * run["moe"]["held"] / c["pairs_held"]


def _traced_decodes(run):
    if run.get("trace") is None or not run.get("trace_host_window"):
        return []
    t0, t1 = run["trace_host_window"]
    return run["log"].named("decode_dispatch", t0, t1)


def moe_experts_roofline(run):
    """Decode dispatches: the least time one sparse layer's grouped product
    could take (touched experts' weights and the rows over peak bandwidth,
    the pairs' FLOPs over peak FLOP/s; the larger), times the layer calls
    traced, over the measured device time under ``moe_experts`` in the
    decode program, %."""
    c = (run.get("moe_counters") or {}).get("decode")
    traced = _traced_decodes(run)
    measured = _scope_seconds(run, ("moe_experts",), "serve_decode_slots")
    if not c or not c["layer_calls"] or not traced or not measured:
        return None
    m = run["moe"]
    pairs = c["pairs_held"] / c["layer_calls"]
    touched = c["experts_touched"] / c["layer_calls"]
    flops, nbytes = rooflines_moe.held_experts_decode(
        pairs, touched, m["d_model"], m["d_ff"], m["itemsize"])
    least, bound = run["rooflines"].min_seconds(flops, nbytes, run["peaks"])
    calls = len(traced) * m["sparse_layers"]
    run["say"](info="moe_experts_roofline", bound=bound, layer_calls=calls,
               pairs_per_call=pairs, experts_touched_per_call=touched,
               flops_per_call=flops, bytes_per_call=nbytes,
               least_us=least * 1e6, measured_us_mean=measured / calls * 1e6)
    return 100.0 * least * calls / measured


def paged_decode_mixed_roofline(run):
    """Bytes of the two kinds of KV state a decode step has to read (every
    occupied block of the full layers, min(length + 1, window) tokens of the
    window layers) over peak HBM bandwidth, over the ``paged_decode``
    kernel's device time, %."""
    tr = run.get("trace")
    traced = [s for s in _traced_decodes(run) if len(s[3]) > 2]
    if tr is None or not traced or "window_layers" not in run:
        return None
    kernel_s = tr.kernel_seconds("paged_decode")
    calls = tr.kernel_calls("paged_decode")
    if kernel_s <= 0 or not calls:
        return None
    full = sum(s[3][1] for s in traced) / len(traced)
    win = sum(s[3][2] for s in traced) / len(traced)
    per_dispatch = rooflines_moe.paged_decode_mixed_bytes(
        full, win, run["block_size"], run["kv_heads"], run["head_dim"],
        run["full_layers"], run["window_layers"])
    # one kernel call per layer per dispatch; the trace may cut a dispatch
    # at either end, so take bytes per call from the dispatches seen whole
    per_call = per_dispatch / run["layers"]
    least = per_call / run["peaks"]["hbm_bytes_per_s"]
    run["say"](info="paged_decode_mixed_roofline", bound="memory",
               kernel_calls=calls, kernel_us_mean=kernel_s / calls * 1e6,
               least_us_mean=least * 1e6, full_blocks_per_dispatch=full,
               window_tokens_per_dispatch=win)
    return 100.0 * least * calls / kernel_s
