"""The arithmetic behind the per-layer readers of ``serve_jamba`` cells
(``layer_metrics/ssm_*.py``, ``paged_decode_mqa_roofline.py``). A function
that finds nothing to read (no device trace, no scope or kernel of that
name, no counter: an end-to-end run, or a program that lacks what PR 42
added) returns None and the metric is left out of the line; none raises."""

from harness import readers_moe, rooflines_cca, rooflines_ssm


def scope_share(run, scope):
    """Device seconds under ``scope`` over busy seconds, %."""
    return readers_moe.scope_time_share(run, (scope,))


def _traced(run, name):
    if run.get("trace") is None or not run.get("trace_host_window") \
            or "ssm" not in run:
        return []
    t0, t1 = run["trace_host_window"]
    return [s for s in run["log"].named(name, t0, t1)
            if isinstance(s[3], tuple)]


def _kernel(run, name):
    tr = run["trace"]
    return tr.kernel_seconds(name), tr.kernel_calls(name)


def ssm_step_roofline(run):
    """The ``ssm_step`` kernel's device time against the least time its
    calls could take by the BYTES: the rewritten slots' state (read and
    written) and their rows over peak bandwidth, for the slots the traced
    decode dispatches decoded (one call a state-space layer), %."""
    traced = _traced(run, "decode_dispatch")
    if not traced:
        return None
    kernel_s, calls = _kernel(run, "ssm_step")
    if kernel_s <= 0 or not calls:
        return None
    m = run["ssm"]
    slots = sum(s[3][0] for s in traced) / len(traced)
    ops, nbytes = rooflines_ssm.ssm_step(
        slots, m["d_inner"], m["d_state"], m["state_itemsize"])
    least = nbytes / run["peaks"]["hbm_bytes_per_s"]
    run["say"](info="ssm_step_roofline", bound="memory", kernel_calls=calls,
               kernel_us_mean=kernel_s / calls * 1e6, least_us=least * 1e6,
               slots_per_call=slots, bytes_per_call=nbytes,
               element_ops_per_s=ops * calls / kernel_s)
    return 100.0 * least * calls / kernel_s


def ssm_scan_roofline(run):
    """The ``ssm_scan`` kernel's device time against the least time the
    RECURRENCE could take by the BYTES for the chunks the traced prefill
    dispatches carried (the slot's state read and written once a chunk,
    every token's rows), in every state-space layer, %. Its true ceiling is
    the vector unit, which has no published peak: the line beside it says
    element operations a second."""
    traced = _traced(run, "prefill_dispatch")
    if not traced:
        return None
    kernel_s, calls = _kernel(run, "ssm_scan")
    if kernel_s <= 0 or not calls:
        return None
    m = run["ssm"]
    least = ops = 0.0
    for s in traced:
        o, nbytes = rooflines_ssm.ssm_scan(
            s[3][0], m["d_inner"], m["d_state"], m["state_itemsize"])
        least += nbytes / run["peaks"]["hbm_bytes_per_s"]
        ops += o
    least *= m["layers"]
    ops *= m["layers"]
    run["say"](info="ssm_scan_roofline", bound="memory", chunks=len(traced),
               kernel_calls=calls,
               tokens_mean=sum(s[3][0] for s in traced) / len(traced),
               kernel_us_mean=kernel_s / calls * 1e6,
               least_us_per_call=least / calls * 1e6,
               element_ops_per_s=ops / kernel_s)
    return 100.0 * least / kernel_s


def ssm_state_share(run):
    """At the window's peak of occupied blocks: the recurrent state's bytes
    (every slot's, held whole whatever it holds) over those and the
    occupied K and V rows' bytes, %."""
    m = run.get("ssm")
    if run.get("kind") != "serve" or not m \
            or not m.get("recurrent_state_bytes"):
        return None
    h0, h1 = run["host_window"]
    used = [u for t, u in run["kv_used"] if h0 <= t <= h1]
    if not used:
        return None
    state = float(m["recurrent_state_bytes"])
    return 100.0 * state / (state + max(used) * m["kv_bytes_per_block"])


def paged_decode_mqa_roofline(run):
    """The ``paged_decode`` kernel's device time against the least time its
    calls could take: the larger of the scores' and values' FLOPs over peak
    FLOP/s and the occupied K and V rows' bytes over peak bandwidth, for
    the rows the traced decode dispatches read (one call an attention
    layer), %."""
    traced = [s for s in _traced(run, "decode_dispatch") if len(s[3]) > 2]
    if not traced:
        return None
    kernel_s, calls = _kernel(run, "paged_decode")
    if kernel_s <= 0 or not calls:
        return None
    m = run["ssm"]
    rows = sum(s[3][2] for s in traced) / len(traced)
    flops, nbytes = rooflines_cca.paged_decode_gqa(
        rows, m["heads"], m["kv_heads"], m["head_dim"], m["itemsize"])
    least, bound = run["rooflines"].min_seconds(flops, nbytes, run["peaks"])
    run["say"](info="paged_decode_mqa_roofline", bound=bound,
               kernel_calls=calls, kernel_us_mean=kernel_s / calls * 1e6,
               least_us=least * 1e6, rows_per_call=rows,
               flops_per_call=flops, bytes_per_call=nbytes)
    return 100.0 * least * calls / kernel_s
