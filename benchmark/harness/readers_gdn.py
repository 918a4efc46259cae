"""The arithmetic behind the per-layer readers of ``serve_qwen3_next`` cells
(``layer_metrics/gdn_*.py``, ``attn_gated_prefill_roofline.py``,
``paged_decode_gqa256_roofline.py``). A function that finds nothing to read
(no device trace, no scope or kernel of that name: an end-to-end run, or a
program that lacks what PR 54 added) returns None and the metric is left
out of the line; none raises."""

from harness import (readers_moe, rooflines_cca, rooflines_gdn,
                     rooflines_window)

PREFILL = "serve_prefill_slot"
DECODE = "serve_decode_slots"
STEP_KERNEL = "kda_step"        # one step kernel serves both delta rules


def scope_share(run, scope):
    """Device seconds under ``scope`` over busy seconds, %."""
    return readers_moe.scope_time_share(run, (scope,))


def _traced(run, name):
    if run.get("trace") is None or not run.get("trace_host_window") \
            or "gdn" not in run:
        return []
    t0, t1 = run["trace_host_window"]
    return [s for s in run["log"].named(name, t0, t1)
            if isinstance(s[3], tuple)]


def gdn_step_roofline(run):
    """The step kernel's device time against the least time its calls could
    take: the larger of the recurrence's FLOPs over peak FLOP/s and the
    rewritten slots' state (read and written) and rows over peak bandwidth,
    for the slots the traced decode dispatches decoded (one call a linear
    layer), %."""
    traced = _traced(run, "decode_dispatch")
    if not traced:
        return None
    tr = run["trace"]
    kernel_s = tr.kernel_seconds(STEP_KERNEL)
    calls = tr.kernel_calls(STEP_KERNEL)
    if kernel_s <= 0 or not calls:
        return None
    m = run["gdn"]
    slots = sum(s[3][0] for s in traced) / len(traced)
    flops, nbytes = rooflines_gdn.gdn_step(
        slots, m["heads"], m["head_dim"], m["state_itemsize"])
    least, bound = run["rooflines"].min_seconds(flops, nbytes, run["peaks"])
    run["say"](info="gdn_step_roofline", kernel=STEP_KERNEL, bound=bound,
               kernel_calls=calls, kernel_us_mean=kernel_s / calls * 1e6,
               least_us=least * 1e6, slots_per_call=slots,
               flops_per_call=flops, bytes_per_call=nbytes)
    return 100.0 * least * calls / kernel_s


def gdn_chunk_roofline(run):
    """The prefill program's device time under the scope ``gdn_chunk`` (the
    scalar-decay chunk form, whatever implements it) against the least time
    the RECURRENCE could take for the valid tokens the traced prefill
    dispatches carried, in every linear layer, %."""
    traced = _traced(run, "prefill_dispatch")
    measured = readers_moe._scope_seconds(run, ("gdn_chunk",), PREFILL)
    if not traced or not measured:
        return None
    m = run["gdn"]
    least = 0.0
    for s in traced:
        flops, nbytes = rooflines_gdn.gdn_chunk(
            s[3][0], m["heads"], m["head_dim"], m["state_itemsize"])
        least += run["rooflines"].min_seconds(flops, nbytes,
                                              run["peaks"])[0]
    least *= m["layers"]
    run["say"](info="gdn_chunk_roofline", chunks=len(traced),
               tokens_mean=sum(s[3][0] for s in traced) / len(traced),
               measured_ms_per_chunk=measured / len(traced) * 1e3,
               least_ms_per_chunk=least / len(traced) * 1e3)
    return 100.0 * least / measured


def attn_gated_prefill_roofline(run):
    """The causal triangle's work of the gated full-attention layers (a
    query at ``t`` sees ``t + 1`` keys: ``4 H Dh`` FLOPs a pair, the K and V
    rows it can see read once) over the chip's peaks, over the device time
    under ``attn_gated`` in the prefill program (projections, norms, rotary,
    the pool write, the attention, the gate), for the chunks traced, %."""
    traced = _traced(run, "prefill_dispatch")
    measured = readers_moe._scope_seconds(run, ("attn_gated",), PREFILL)
    if not traced or not measured:
        return None
    m = run["gdn"]
    least = flops = 0.0
    for s in traced:
        n, start = s[3][0], s[3][1]
        f, b = rooflines_window.prefill_attention(
            start, n, m["attn_heads"], m["kv_heads"], m["attn_head_dim"],
            None, m["itemsize"])
        least += run["rooflines"].min_seconds(f, b, run["peaks"])[0]
        flops += f
    layers = m["attention_layers"]
    run["say"](info="attn_gated_prefill_roofline", chunks=len(traced),
               layers=layers, flops_per_chunk_layer=flops / len(traced),
               start_mean=sum(s[3][1] for s in traced) / len(traced),
               least_us_per_chunk_layer=least / len(traced) * 1e6,
               measured_us_per_chunk_layer=measured / len(traced) / layers
               * 1e6)
    return 100.0 * least * layers / measured


def paged_decode_gqa256_roofline(run):
    """The ``paged_decode`` kernel's device time against the least time its
    calls could take: the larger of the scores' and values' FLOPs over peak
    FLOP/s and the occupied K and V rows' bytes over peak bandwidth, for
    the rows the traced decode dispatches read (one call a full layer),
    %."""
    traced = [s for s in _traced(run, "decode_dispatch") if len(s[3]) > 2]
    if not traced:
        return None
    tr = run["trace"]
    kernel_s = tr.kernel_seconds("paged_decode")
    calls = tr.kernel_calls("paged_decode")
    if kernel_s <= 0 or not calls:
        return None
    m = run["gdn"]
    rows = sum(s[3][2] for s in traced) / len(traced)
    flops, nbytes = rooflines_cca.paged_decode_gqa(
        rows, m["attn_heads"], m["kv_heads"], m["attn_head_dim"],
        m["itemsize"])
    least, bound = run["rooflines"].min_seconds(flops, nbytes, run["peaks"])
    run["say"](info="paged_decode_gqa256_roofline", bound=bound,
               kernel_calls=calls, kernel_us_mean=kernel_s / calls * 1e6,
               least_us=least * 1e6, rows_per_call=rows,
               flops_per_call=flops, bytes_per_call=nbytes)
    return 100.0 * least * calls / kernel_s
