"""The arithmetic behind the per-layer readers of ``serve_dots_vlm`` cells
(``layer_metrics/mla_*.py``). A function that finds nothing to read (no
device trace, no scope or kernel of that name: an end-to-end run, or a
program that lacks what PR 32 added) returns None and the metric is left
out of the line; none raises."""

from harness import provenance, readers_moe, rooflines_mla

PREFILL = "serve_prefill_slot"


def _program_seconds(run, program):
    """Device self seconds (mean over devices) of all of ``program``'s
    operations; None without provenance."""
    pt = provenance.of_run(run)
    if pt is None or not pt.tables or run.get("kind") != "serve":
        return None
    return pt.seconds(lambda p, o, e: program in p)


def _traced(run, name):
    if run.get("trace") is None or not run.get("trace_host_window") \
            or "mla" not in run:
        return []
    t0, t1 = run["trace_host_window"]
    return [s for s in run["log"].named(name, t0, t1)
            if isinstance(s[3], tuple)]


def mla_decode_roofline(run):
    """The ``mla_decode`` kernel's device time against the least time its
    calls could take: the larger of the scores' and values' FLOPs over
    peak FLOP/s and the latent rows' bytes over peak bandwidth, for the
    rows the traced decode dispatches read (one call a layer), %."""
    tr = run.get("trace")
    traced = [s for s in _traced(run, "decode_dispatch") if len(s[3]) > 2]
    if tr is None or not traced:
        return None
    kernel_s = tr.kernel_seconds("mla_decode")
    calls = tr.kernel_calls("mla_decode")
    if kernel_s <= 0 or not calls:
        return None
    m = run["mla"]
    rows = sum(s[3][2] for s in traced) / len(traced)
    flops, nbytes = rooflines_mla.mla_decode(
        rows, m["heads"], m["latent"], m["d_r"], m["itemsize"])
    least, bound = run["rooflines"].min_seconds(flops, nbytes, run["peaks"])
    run["say"](info="mla_decode_roofline", bound=bound, kernel_calls=calls,
               kernel_us_mean=kernel_s / calls * 1e6, least_us=least * 1e6,
               rows_per_call=rows, flops_per_call=flops,
               bytes_per_call=nbytes,
               flops_us=flops / run["peaks"]["bf16_flops"] * 1e6,
               bytes_us=nbytes / run["peaks"]["hbm_bytes_per_s"] * 1e6)
    return 100.0 * least * calls / kernel_s


def _prefill_least(run, traced):
    m = run["mla"]
    least = expand_least = 0.0
    for s in traced:
        n, history = s[3][0], s[3][1]
        flops, nbytes, expand = rooflines_mla.mla_prefill(
            n, history, m["heads"], m["latent"], m["d_n"], m["d_r"],
            m["d_v"], m["itemsize"])
        least += run["rooflines"].min_seconds(flops, nbytes,
                                              run["peaks"])[0]
        expand_least += expand / run["peaks"]["bf16_flops"]
    return least * m["layers"], expand_least * m["layers"]


def mla_prefill_roofline(run):
    """The prefill program's device time under ``attn_mla`` (the
    re-expansion of the occupied history and of the chunk, scores, softmax,
    values) against the least time those products could take for the
    chunks the traced prefill dispatches carried, %."""
    traced = _traced(run, "prefill_dispatch")
    measured = readers_moe._scope_seconds(run, ("attn_mla",), PREFILL)
    if not traced or not measured:
        return None
    least, expand_least = _prefill_least(run, traced)
    run["say"](info="mla_prefill_roofline", chunks=len(traced),
               history_mean=sum(s[3][1] for s in traced) / len(traced),
               measured_ms_per_chunk=measured / len(traced) * 1e3,
               least_ms_per_chunk=least / len(traced) * 1e3,
               expand_least_ms_per_chunk=expand_least / len(traced) * 1e3)
    return 100.0 * least / measured


def mla_expand_share(run):
    """Device time under ``mla_expand`` (the up-projection of cached rows to
    per-head keys and values) over the prefill program's device time, %."""
    expand = readers_moe._scope_seconds(run, ("mla_expand",), PREFILL)
    whole = _program_seconds(run, PREFILL)
    if not expand or not whole:
        return None
    return 100.0 * expand / whole
