"""The arithmetic behind the per-layer readers of ``serve_kimi_linear``
cells (``layer_metrics/kda_*.py``, ``recurrent_state_share.py``). A function
that finds nothing to read (no device trace, no scope or kernel of that
name, no counter: an end-to-end run, or a program that lacks what PR 40
added) returns None and the metric is left out of the line; none raises."""

from harness import readers_moe, rooflines_kda

PREFILL = "serve_prefill_slot"


def scope_share(run, scope):
    """Device seconds under ``scope`` over busy seconds, %."""
    return readers_moe.scope_time_share(run, (scope,))


def _traced(run, name):
    if run.get("trace") is None or not run.get("trace_host_window") \
            or "kda" not in run:
        return []
    t0, t1 = run["trace_host_window"]
    return [s for s in run["log"].named(name, t0, t1)
            if isinstance(s[3], tuple)]


def kda_step_roofline(run):
    """The ``kda_step`` kernel's device time against the least time its
    calls could take: the larger of the recurrence's FLOPs over peak FLOP/s
    and the rewritten slots' state (read and written) and rows over peak
    bandwidth, for the slots the traced decode dispatches decoded (one call
    a linear-attention layer), %."""
    tr = run.get("trace")
    traced = _traced(run, "decode_dispatch")
    if tr is None or not traced:
        return None
    kernel_s = tr.kernel_seconds("kda_step")
    calls = tr.kernel_calls("kda_step")
    if kernel_s <= 0 or not calls:
        return None
    m = run["kda"]
    slots = sum(s[3][0] for s in traced) / len(traced)
    flops, nbytes = rooflines_kda.kda_step(
        slots, m["heads"], m["head_dim"], m["state_itemsize"])
    least, bound = run["rooflines"].min_seconds(flops, nbytes, run["peaks"])
    run["say"](info="kda_step_roofline", bound=bound, kernel_calls=calls,
               kernel_us_mean=kernel_s / calls * 1e6, least_us=least * 1e6,
               slots_per_call=slots, flops_per_call=flops,
               bytes_per_call=nbytes)
    return 100.0 * least * calls / kernel_s


def kda_chunk_roofline(run):
    """The prefill program's device time under the scope ``kda_chunk`` (the
    chunkwise-parallel rule: plain XLA operations, not one kernel) against
    the least time the RECURRENCE could take for the chunks the traced
    prefill dispatches carried, in every linear-attention layer, %."""
    traced = _traced(run, "prefill_dispatch")
    measured = readers_moe._scope_seconds(run, ("kda_chunk",), PREFILL)
    if not traced or not measured:
        return None
    m = run["kda"]
    least = 0.0
    for s in traced:
        flops, nbytes = rooflines_kda.kda_chunk(
            s[3][0], m["heads"], m["head_dim"], m["state_itemsize"])
        least += run["rooflines"].min_seconds(flops, nbytes,
                                              run["peaks"])[0]
    least *= m["layers"]
    run["say"](info="kda_chunk_roofline", chunks=len(traced),
               tokens_mean=sum(s[3][0] for s in traced) / len(traced),
               measured_ms_per_chunk=measured / len(traced) * 1e3,
               least_ms_per_chunk=least / len(traced) * 1e3)
    return 100.0 * least / measured


def recurrent_state_share(run):
    """At the window's peak of occupied blocks: the recurrent state's bytes
    (every slot's, held whole whatever it holds) over those and the
    occupied latent rows' bytes, %."""
    m = run.get("kda")
    if run.get("kind") != "serve" or not m \
            or not m.get("recurrent_state_bytes"):
        return None
    h0, h1 = run["host_window"]
    used = [u for t, u in run["kv_used"] if h0 <= t <= h1]
    if not used:
        return None
    state = float(m["recurrent_state_bytes"])
    return 100.0 * state / (state + max(used) * m["latent_bytes_per_block"])
