"""The arithmetic behind the per-layer readers that PR 50 added for
``serve_smallthinker`` cells (``layer_metrics/attn_window_prefill_*.py``,
``attn_full_prefill_roofline.py``, ``window_ring_fill_share.py``,
``moe_act_zero_share.py``). A function that finds nothing to read (no
device trace, no scope of that name, no counter, a run whose driver kept no
record of its chunks: an end-to-end run, or a program that lacks what PR 50
added) returns None and the metric is left out of the line; none raises."""

import collections

from harness import provenance, readers_moe, rooflines_window

PREFILL = "serve_prefill_slot"


def _traced_chunks(run):
    """(start, tokens) of the prefill dispatches inside the traced part of
    the window, from the driver's record."""
    w = run.get("window_attn")
    if not w or run.get("trace") is None or not run.get("trace_host_window"):
        return []
    t0, t1 = run["trace_host_window"]
    return [(s, n) for t, s, n in w["chunks"] if t0 <= t <= t1]


ORDERED = ("moe_router", "attn_qkv", "moe_experts")


def scope_order(run):
    """How the layer's three marked scopes follow one another on the first
    device, in the order its operations START: {"a>b": count} over the
    runs of operations under one scope. A layer whose router reads its
    input shows ``moe_router>attn_qkv`` once a layer and never
    ``moe_experts>attn_qkv``. None without provenance."""
    pt = provenance.of_run(run)
    if pt is None or not pt.tables or not pt.ops:
        return None
    seq = []
    for (program, op), _, _, _ in sorted(pt.ops[0], key=lambda r: r[1]):
        entry = pt.entry(program, op)
        parts = set(((entry or {}).get("scope") or "").split("/"))
        tag = next((t for t in ORDERED if t in parts), None)
        if tag and (not seq or seq[-1] != tag):
            seq.append(tag)
    pairs = collections.Counter(zip(seq, seq[1:]))
    return {a + ">" + b: n for (a, b), n in sorted(pairs.items())}


def attn_window_prefill_share(run):
    """Device seconds under ``attn_window`` in the prefill program over
    busy seconds, %. Says the layer's scope order on an earlier line."""
    tr = run.get("trace")
    s = readers_moe._scope_seconds(run, ("attn_window",), PREFILL)
    if not s or tr is None or tr.busy_s <= 0:
        return None
    run["say"](info="layer_scope_order", follows=scope_order(run))
    return 100.0 * s / tr.busy_s


def _prefill_roofline(run, scope, layers_key, windowed):
    w = run.get("window_attn")
    chunks = _traced_chunks(run)
    measured = readers_moe._scope_seconds(run, (scope,), PREFILL)
    if not w or not chunks or not measured:
        return None
    window = w["attn_window"] if windowed else None
    least = flops = nbytes = 0.0
    for start, n in chunks:
        f, b = rooflines_window.prefill_attention(
            start, n, w["heads"], w["kv_heads"], w["head_dim"], window,
            w["itemsize"])
        least += run["rooflines"].min_seconds(f, b, run["peaks"])[0]
        flops, nbytes = flops + f, nbytes + b
    layers = w[layers_key]
    run["say"](info=scope + "_prefill_roofline", chunks=len(chunks),
               layers=layers, flops_per_chunk_layer=flops / len(chunks),
               bytes_per_chunk_layer=nbytes / len(chunks),
               least_us_per_chunk_layer=least / len(chunks) * 1e6,
               measured_us_per_chunk_layer=measured / len(chunks) / layers
               * 1e6)
    return 100.0 * least * layers / measured


def attn_window_prefill_roofline(run):
    """The band's OWN work (a query sees ``min(t + 1, window)`` keys) over
    the chip's peaks, over the device time under ``attn_window`` in the
    prefill program, for the chunks traced, %."""
    return _prefill_roofline(run, "attn_window", "window_layers", True)


def attn_full_prefill_roofline(run):
    """The causal triangle's work (a query sees ``t + 1`` keys) over the
    chip's peaks, over the device time under ``attn_full`` in the prefill
    program, for the chunks traced, %. Reads LOW where the program attends
    a slot's whole row whatever it holds."""
    return _prefill_roofline(run, "attn_full", "full_layers", False)


def window_ring_fill_share(run):
    """Ring rows that hold a token a query can still see over the rows the
    slots' rings have, at the moment of the window when the most slots
    were seated (the fullest such moment), from the program's own count,
    %."""
    w = run.get("window_attn")
    if not w or not run.get("window"):
        return None
    t0, t1 = run["window"]
    seen = [(live, used, alloc) for t, live, used, alloc in w["ring"]
            if t0 <= t <= t1 and used is not None and alloc]
    if not seen:
        return None
    live, used, alloc = max(seen)
    return 100.0 * used / alloc


def moe_act_zero_share(run):
    """Decode dispatches: gate activations of the held pairs that the ReLU
    left exactly 0, over all of them, from the device counters, %."""
    c = (run.get("moe_counters") or {}).get("decode")
    if not c or not c.get("act_total") or "act_zero" not in c:
        return None
    return 100.0 * c["act_zero"] / c["act_total"]
