"""Mean gap between consecutive output tokens over the whole window: the
same gaps as `itl_p95_ms`, off the staircase that percentile sits on."""


def read(run):
    return run["end_to_end"].get("itl_mean_ms")
