"""The arithmetic behind the per-layer readers of ``serve_longcat_flash``
cells (``layer_metrics/moe_zero_*.py``, ``moe_real_max_over_mean.py``,
``scmoe_dense_share.py``): a shortcut-connected double layer whose router
has zero-compute experts. A function that finds nothing to read (no device
trace, no scope of that name, no counters: an end-to-end run, or a program
that lacks what PR 46 added) returns None and the metric is left out of the
line; none raises."""

from harness import provenance

HALVES = ("scmoe_a", "scmoe_b")


def _decode_counters(run):
    c = (run.get("moe_counters") or {}).get("decode")
    if not c or "pairs_zero" not in c or not c.get("pairs_total"):
        return None
    return c


def moe_zero_share(run):
    """Decode dispatches: pairs on zero-compute experts of all the routed
    pairs, %, from the device counters. A reading of the selection bias
    (a third at rest), not a target."""
    c = _decode_counters(run)
    return None if c is None else 100.0 * c["pairs_zero"] / c["pairs_total"]


def moe_real_max_over_mean(run):
    """Decode dispatches: the most real experts any one token of a layer
    call chose (mean over the calls) over the mean real experts a token:
    how far the busiest token's work is from the mean."""
    c = _decode_counters(run)
    if c is None or not c.get("layer_calls"):
        return None
    tokens = c["pairs_total"] / run["moe"]["k"]
    real = c["pairs_total"] - c["pairs_zero"]
    if tokens <= 0 or real <= 0:
        return None
    return (c["real_pairs_max_token"] / c["layer_calls"]) / (real / tokens)


def _share(run, pick):
    """Device seconds of the operations ``pick(scope components)`` keeps,
    over busy seconds, %; None without provenance or where nothing ran
    under such a scope."""
    tr = run.get("trace")
    pt = provenance.of_run(run)
    if pt is None or not pt.tables or run.get("kind") != "serve" \
            or tr is None or tr.busy_s <= 0:
        return None
    s = pt.seconds(lambda p, o, e: e is not None and pick(
        set((e.get("scope") or "").split("/"))))
    return 100.0 * s / tr.busy_s if s and s > 0 else None


def scmoe_dense_share(run):
    """Device time under ``mlp`` inside ``scmoe_a`` / ``scmoe_b`` (the two
    dense FFNs of a double layer) over busy time, %."""
    return _share(run, lambda parts: "mlp" in parts
                  and bool(parts & set(HALVES)))


def moe_zero_time_share(run):
    """Device time under ``moe_zero`` (the identity experts' term: what
    "zero-compute" costs on the device) over busy time, %."""
    return _share(run, lambda parts: "moe_zero" in parts)
