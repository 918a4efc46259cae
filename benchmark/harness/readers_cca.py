"""The arithmetic behind the per-layer readers of ``serve_zaya`` cells
(``layer_metrics/cca_*.py``, ``paged_decode_cca_roofline.py``,
``moe_router_share.py``, ``moe_skip_share.py``). A function that finds
nothing to read (no device trace, no scope or kernel of that name, no
counter: an end-to-end run, or a program that lacks what PR 34 added)
returns None and the metric is left out of the line; none raises."""

from harness import readers_moe, rooflines_cca


def scope_share(run, scope):
    """Device seconds under ``scope`` over busy seconds, %."""
    return readers_moe.scope_time_share(run, (scope,))


def paged_decode_cca_roofline(run):
    """The ``paged_decode`` kernel's device time against the least time its
    calls could take: the larger of the scores' and values' FLOPs over peak
    FLOP/s and the occupied K and V rows' bytes over peak bandwidth, for
    the rows the traced decode dispatches read (one call a layer), %."""
    tr = run.get("trace")
    if tr is None or not run.get("trace_host_window") or "cca" not in run:
        return None
    t0, t1 = run["trace_host_window"]
    traced = [s for s in run["log"].named("decode_dispatch", t0, t1)
              if isinstance(s[3], tuple) and len(s[3]) > 2]
    kernel_s = tr.kernel_seconds("paged_decode")
    calls = tr.kernel_calls("paged_decode")
    if not traced or kernel_s <= 0 or not calls:
        return None
    m = run["cca"]
    rows = sum(s[3][2] for s in traced) / len(traced)
    flops, nbytes = rooflines_cca.paged_decode_gqa(
        rows, m["heads"], m["kv_heads"], m["head_dim"], m["itemsize"])
    least, bound = run["rooflines"].min_seconds(flops, nbytes, run["peaks"])
    run["say"](info="paged_decode_cca_roofline", bound=bound,
               kernel_calls=calls, kernel_us_mean=kernel_s / calls * 1e6,
               least_us=least * 1e6, rows_per_call=rows,
               flops_per_call=flops, bytes_per_call=nbytes)
    return 100.0 * least * calls / kernel_s


def moe_skip_share(run):
    """Decode dispatches: the share of routed tokens whose top-1 choice was
    the skip output (no expert runs for them), from the device counters, %."""
    c = (run.get("moe_counters") or {}).get("decode")
    if not c or not c.get("pairs_total") or "pairs_skipped" not in c:
        return None
    return 100.0 * c["pairs_skipped"] / c["pairs_total"]
