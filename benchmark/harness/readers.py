"""The arithmetic behind the per-layer readers in ``layer_metrics/``. Each
reader there is a few lines that pick one of these for one metric; a
function that finds nothing to read returns None and the harness leaves the
metric out of the line.

Host-span metrics are taken over the untraced part of the window
(``run["host_window"]``), on whole scheduler steps; device metrics over the
traced part (``run["trace"]``)."""

from harness.stats import median


def _whole_steps(run):
    """(start of the first, end of the last) scheduler step that lies wholly
    inside the host window, or None."""
    if run.get("kind") != "serve":
        return None
    steps = run["log"].named("step", *run["host_window"])
    if not steps:
        return None
    return steps[0][1], steps[-1][2]


def span_share(run, name):
    """Seconds inside spans ``name`` over scheduler-step wall seconds, %."""
    b = _whole_steps(run)
    if b is None:
        return None
    return 100.0 * run["log"].total(name, *b) / run["log"].total("step", *b)


def step_self_share(run):
    """The step span's self time (step minus its child spans: admission and
    the two kinds of dispatch, which block on the device) over step wall
    time, %: the scheduler's own host work."""
    b = _whole_steps(run)
    if b is None:
        return None
    log = run["log"]
    step = log.total("step", *b)
    child = sum(log.total(n, *b)
                for n in ("admit", "prefill_dispatch", "decode_dispatch"))
    return 100.0 * (step - child) / step


def median_span_ms(run, name):
    b = _whole_steps(run)
    if b is None:
        return None
    d = [(s[2] - s[1]) * 1e3 for s in run["log"].named(name, *b)]
    return median(d)


def decode_occupancy(run):
    """Mean decoding slots per decode dispatch."""
    b = _whole_steps(run)
    if b is None:
        return None
    live = [s[3][0] for s in run["log"].named("decode_dispatch", *b)]
    return sum(live) / len(live) if live else None


def kv_blocks_peak_share(run):
    if run.get("kind") != "serve":
        return None
    h0, h1 = run["host_window"]
    used = [u for t, u in run["kv_used"] if h0 <= t <= h1]
    return 100.0 * max(used) / run["pool_blocks"] if used else None


def kernel_time_share(run, kernels):
    """Device seconds inside the named kernels over device busy seconds, %."""
    tr = run.get("trace")
    if tr is None or tr.busy_s <= 0:
        return None
    return 100.0 * sum(tr.kernel_seconds(k) for k in kernels) / tr.busy_s


def paged_decode_roofline(run):
    """Bytes of the occupied KV blocks (from shapes and the block counts of
    the traced decode dispatches) over peak HBM bandwidth, over the
    ``paged_decode`` kernel's device time, %."""
    tr = run.get("trace")
    if tr is None or run.get("kind") != "serve":
        return None
    kernel_s = tr.kernel_seconds("paged_decode")
    calls = tr.kernel_calls("paged_decode")
    if kernel_s <= 0 or not calls:
        return None
    t0, t1 = run["trace_host_window"]
    traced = [s for s in run["log"].named("decode_dispatch", t0, t1)]
    if not traced:
        return None
    # one kernel call per layer per dispatch; the trace may cut a dispatch
    # at either end, so take bytes per call from the dispatches seen whole
    blocks_per_dispatch = sum(s[3][1] for s in traced) / len(traced)
    per_call = run["rooflines"].paged_decode_bytes(
        blocks_per_dispatch, run["block_size"], run["kv_heads"],
        run["head_dim"], layers=1)
    least = per_call / run["peaks"]["hbm_bytes_per_s"]
    run["say"](info="paged_decode_roofline", bound="memory",
               kernel_calls=calls, kernel_us_mean=kernel_s / calls * 1e6,
               least_us=least * 1e6,
               occupied_blocks_per_dispatch=blocks_per_dispatch)
    return 100.0 * least * calls / kernel_s


def flash_fwd_roofline(run):
    """The causal attention forward's operations and bytes from the local
    shapes over the chip's peaks (the larger of the two times), over the
    ``flash_fwd`` kernel's device time per call, %."""
    tr = run.get("trace")
    if tr is None or run.get("kind") != "train":
        return None
    kernel_s = tr.kernel_seconds("flash_fwd")
    calls = tr.kernel_calls("flash_fwd")
    if kernel_s <= 0 or not calls:
        return None
    rf = run["rooflines"]
    flops, nbytes = rf.causal_attention_fwd(
        run["batch"] // run["chips"], run["heads"], run["seq"],
        run["head_dim"])
    least, bound = rf.min_seconds(flops, nbytes, run["peaks"])
    run["say"](info="flash_fwd_roofline", bound=bound, kernel_calls=calls,
               kernel_us_mean=kernel_s / calls * 1e6, least_us=least * 1e6,
               flops_per_call=flops, bytes_per_call=nbytes)
    return 100.0 * least * calls / kernel_s


def collective_exposed_share(run):
    """Seconds in which a collective is the innermost operation running on
    a device (so nothing computes there), over the traced window, %."""
    tr = run.get("trace")
    if tr is None or run.get("chips", 1) < 2:
        return None
    return 100.0 * tr.collective_exposed_s() / tr.window_s


def train_step_ms(run):
    return run.get("step_ms_median") if run.get("kind") == "train" else None


def train_peak_hbm_share(run):
    if run.get("kind") != "train" or not run.get("memory_limit_bytes"):
        return None
    return 100.0 * run["memory_peak_bytes"] / run["memory_limit_bytes"]


def program_span_median_ms(run, name):
    """Median of the program's OWN span ``name`` over the host window, ms,
    from its tracer's ring (records start with the start stamp and carry
    the end stamp at index 6, on ``perf_counter``: telemetry/tracer.py).
    None with telemetry off (an end-to-end run) or with no such span."""
    tracer = run.get("tracer")
    if tracer is None:
        return None
    h0, h1 = run["host_window"]
    return median([(r[6] - r[0]) * 1e3 for r in tracer.spans(name)
                   if r[0] >= h0 and r[6] <= h1])
