"""What the telemetry plane took of the host itself: the arithmetic behind
``telemetry_self_ms`` / ``telemetry_self_ms_tput`` and the ``telemetry_self``
line (PR 52).

Every per-layer number of a serving cell is read in a ``--trace 1`` run,
with the program's telemetry ON; the end-to-end metrics are read with it
OFF. From PR 52 on the program stamps, on the ring's clock, the host time
that its spans, its cost accountant, its histograms, its span counts and
its sampled gauges spent since the previous dispatch's wait returned, and
writes it on the ``serve.dispatch`` ring record beside ``gap_us``:
``self_us`` (microseconds, at most ``gap_us``) and ``self_parts`` (by part,
``PARTS``' order; the last, ``hidden``, ran under a running program and is
not in ``self_us``). The metric is the median ``self_us`` over the
population that ``host_gap_ms`` reads (``gaps.ring_rows`` over
``run["host_window"]``: the untraced part of the window, whole gaps, those
after an empty engine left out), in ms: how far ``host_gap_ms``,
``gap_sched_ms``, ``sched_host_share`` and ``idle_share`` of the same line
overstate the step that the cell's end-to-end metric was timed on.

A program that keeps no such count (the parent of PR 52, an end-to-end run
with telemetry off) gives None and no line; nothing here raises."""

from harness import gaps
from harness.stats import median, pct

PARTS = ("spans", "accountant", "histograms", "counts", "gauges", "hidden")


def summary(rows):
    """Of ``gaps.ring_rows``' records, those that carry ``self_us``:
    how many, p50 / mean / p90 of ``self_us`` (us), the median of each
    part (us), the median gap and the plane's share of it by the medians.
    None where no record has the count."""
    have = [c for c in rows if "self_us" in c and "self_parts" in c]
    if not have:
        return None
    selfs = [c["self_us"] for c in have]
    gap = median([c["gap_us"] for c in have])
    return {
        "dispatches": len(have),
        "self_us_p50": median(selfs),
        "self_us_mean": sum(selfs) / len(selfs),
        "self_us_p90": pct(selfs, 90),
        "parts_us_p50": {name: median([c["self_parts"][i] for c in have])
                         for i, name in enumerate(PARTS)},
        "gap_us_p50": gap,
        "self_over_gap": median(selfs) / gap if gap else None,
    }


def of_run(run):
    """The run's account (made once; says the ``telemetry_self`` line):
    {"untraced": summary over the untraced window, "traced_tail": the same
    over the traced tail, where the annotations are live and a span costs
    more}; None for a run that is not serving or has no such record."""
    if "telemetry_self" in run:
        return run["telemetry_self"]
    run["telemetry_self"] = None
    if run.get("kind") != "serve":
        return None
    untraced = summary(gaps.ring_rows(run, run.get("host_window")))
    if untraced is None:
        return None
    out = {"untraced": untraced, "traced_tail": summary(
        gaps.ring_rows(run, run.get("trace_host_window")))}
    run["telemetry_self"] = out
    run["say"](info="telemetry_self", parts=list(PARTS), **out)
    return out


def telemetry_self_ms(run):
    """Median ``self_us`` before a dispatch over the untraced part of the
    window, ms."""
    out = of_run(run)
    return None if out is None else out["untraced"]["self_us_p50"] * 1e-3
