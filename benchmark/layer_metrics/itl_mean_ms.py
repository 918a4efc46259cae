"""Mean gap between consecutive output tokens over the whole window: every
gap of every request, where `tpot_p90_ms` takes the tail over requests."""


def read(run):
    return run["end_to_end"].get("itl_mean_ms")
