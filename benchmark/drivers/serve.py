"""Driver for configurations of ``"kind": "serve"``: GPT-2 through
``deepspeed_tpu.init_inference`` -> ``ServingEngine`` under a ``requests``
traffic mix, open or closed loop, on the wall clock.

Set-up (itemised on an earlier line): weights on the device from the seed,
the engine and its KV pool, a seeded sample of requests that warms both
serving programs AND is checked against the plain reference, then the ramp.
The measured window follows; nothing may compile inside it."""

import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from harness import spans as spans_lib
from harness import traffic as traffic_lib
from harness import weights
from harness.stats import binned, pct, request_mean_gaps_ms

TERMINAL = ("done", "timeout", "shed", "error")
# Served logits (bf16 weights and activations, fp32 accumulation, 48 layers
# of bf16 residual adds) against the float32 reference on the same bf16
# weights. Random GPT-2 weights give logits of standard deviation ~0.8 and a
# largest magnitude near 4; bf16 rounding of the residual stream (2**-9
# relative per element per layer, accumulated over 48 layers) gives ~2% of
# that on the worst of 50,257 logits. Measured on the chip: see PERF.md.
LOGIT_TOL_ABS = 0.25
CHECK_REQUESTS = ((72, 6), (150, 5))     # (prompt, answer) tokens
# After the window: this many of the requests it finished (the longest and
# a seeded draw of the others) go through the reference once each, prompt
# and served tokens together (PERF.md section 4 has the readings the
# configuration's `check.served_gap_limit` was set from).
SAMPLE_REQUESTS = 6
FIRST_TOKEN_DRAIN_S = 15.0


class _Req:
    """The benchmark's own record of one request."""
    __slots__ = ("req", "due", "submitted", "seen", "first", "last",
                 "gaps", "done")

    def __init__(self, req, due):
        self.req, self.due = req, due
        self.submitted = self.first = self.last = self.done = None
        self.seen = 0
        self.gaps = []      # (earlier stamp, later stamp) of its own tokens


def _gpt_config(hp, dtype):
    from deepspeed_tpu.models import gpt
    return gpt.GPTConfig(
        vocab_size=int(hp["vocab_size"]), n_layers=int(hp["n_layer"]),
        n_heads=int(hp["n_head"]), d_model=int(hp["n_embd"]),
        max_seq_len=int(hp["n_positions"]), dtype=dtype)


def build(ctx):
    """Weights, engine, pool, instrumentation and the checked warm-up.
    Returns (srv, log, counts, setup items, correct, compared, params);
    ``compared`` is [(name, value, limit)]."""
    t_imp = time.perf_counter()
    import deepspeed_tpu
    from deepspeed_tpu.inference.serving import ServeRequest, ServingEngine

    cell, say = ctx.cell, ctx.say
    hp, sv, mix = cell.config["model"], cell.config["serving"], cell.traffic
    clock = time.perf_counter
    rng = np.random.default_rng(ctx.seed)
    setup = {"program_import_s": clock() - t_imp}

    # ---- weights, engine, pool -----------------------------------------
    t = clock()
    params = weights.gpt2_params(ctx.seed, hp, jnp.bfloat16)
    jax.block_until_ready(params)
    setup["weights_s"] = clock() - t
    t = clock()
    eng = deepspeed_tpu.init_inference((_gpt_config(hp, jnp.bfloat16),
                                        params), dtype=jnp.bfloat16)
    srv = ServingEngine(eng, num_slots=int(sv["num_slots"]),
                        block_size=int(sv["block_size"]),
                        num_blocks=int(sv["num_blocks"]),
                        prefill_chunk=int(sv["prefill_chunk"]),
                        # a traced run carries the program's own spans
                        # (serve.*) and provenance; end-to-end runs do not
                        telemetry=bool(ctx.trace))
    jax.block_until_ready((srv.cache.k, srv.cache.v))
    setup["engine_s"] = clock() - t
    say(info="serving_engine", decode_impl=srv.decode_impl,
        num_slots=srv.num_slots, pool_blocks=srv.cache.num_blocks - 1,
        block_size=srv.cache.block_size, prefill_chunk=srv.prefill_chunk)

    log = spans_lib.SpanLog()
    counts = {"prefill_tokens": [], "capture": None}

    def on_dispatch(name, t0, t1, args, out):
        """Sees every dispatch after it returned and before the scheduler
        emits its tokens. Returns the span's value: prompt tokens of a
        prefill chunk; (live slots, occupied KV blocks) of a decode."""
        cap = counts["capture"]
        if name == "prefill_dispatch":
            n = int(args[5])
            counts["prefill_tokens"].append((t1, n))
            if cap is not None:
                for s, r in enumerate(srv.slots):
                    if r is not None and r.state == "prefill" \
                            and np.array_equal(srv.cache.tables[s], args[2]) \
                            and int(args[4]) + n == len(r.prompt):
                        cap.append((r.rid, len(r.prompt) - 1,
                                    np.asarray(out[0], np.float32).reshape(-1)))
            return n
        if name == "decode_dispatch":
            active = np.asarray(args[5])
            lengths = np.asarray(args[3])
            bs = srv.cache.block_size
            blocks = int(((lengths[active] + bs) // bs).sum())
            if cap is not None:
                lg = np.asarray(out[0], np.float32)
                for s in np.flatnonzero(active):
                    r = srv.slots[s]
                    # fed out[m - 1], predicts out[m]: position P + m - 1
                    cap.append((r.rid, len(r.prompt) + len(r.out) - 1,
                                lg[s].reshape(-1)))
            return (int(active.sum()), blocks)
        return None

    spans_lib.instrument_serving(srv, log, on_dispatch)

    # ---- warm-up that is also the correctness sample ---------------------
    t = clock()
    vocab = int(hp["vocab_size"])
    counts["capture"] = cap = []
    check = [ServeRequest(rid=f"check{i}", max_new_tokens=a,
                          prompt=traffic_lib.prompt_tokens(p, vocab, rng))
             for i, (p, a) in enumerate(ctx.rehearsal.get(
                 "check_requests", CHECK_REQUESTS))]
    srv.submit(check[0], now=clock())
    srv.step(clock())
    srv.submit(check[1], now=clock())
    guard = 0
    while srv.busy:
        srv.step(clock())
        guard += 1
        assert guard < 10_000, "check requests did not drain"
    counts["capture"] = None
    setup["warmup_s"] = clock() - t
    t = clock()
    correct, detail = _check(check, cap, params, hp, cell.reference())
    del cap
    setup["reference_check_s"] = clock() - t
    say(info="correctness", **detail)
    log.spans.clear()
    counts["prefill_tokens"].clear()
    compared = [("warmup_max_abs_logit_error",
                 detail["max_abs_logit_error"], LOGIT_TOL_ABS)]
    return srv, log, counts, setup, correct, compared, params


def drive(ctx, srv, log, counts, mix, seconds, rng, trace=False):
    """Ramp, then the measured window of ``seconds`` under ``mix``; returns
    the driver's result without the set-up items."""
    from deepspeed_tpu.inference.serving import ServeRequest
    hp = ctx.cell.config["model"]
    vocab = int(hp["vocab_size"])
    clock = time.perf_counter
    say = ctx.say
    setup = {}
    t_plan = clock()
    # ---- the plan ----------------------------------------------------------
    # with `schedule_seed` the mix fixes its own schedule (which lengths, in
    # which order, arriving when) and `--seed` draws only the token ids and
    # the weights: every seed then does the same work
    sched = np.random.default_rng(int(mix["schedule_seed"])) \
        if "schedule_seed" in mix else rng
    closed = mix["loop"] == "closed"
    if closed:
        outstanding = srv.num_slots if mix["outstanding"] == "num_slots" \
            else int(mix["outstanding"])
        plan = [(0.0, p, a) for p, a in traffic_lib.closed_loop_plan(
            mix, outstanding, sched)]
        ramp_done_after = int(mix["ramp_requests"])
        ramp_s = None
    else:
        ramp_s, plan = traffic_lib.open_loop_plan(mix, seconds, sched)
    records = [_Req(ServeRequest(rid=i, max_new_tokens=a,
                                 prompt=traffic_lib.prompt_tokens(p, vocab,
                                                                  rng)), due)
               for i, (due, p, a) in enumerate(plan)]

    tracked = {}
    out_tokens, kv_used = [], []
    finished = [0]

    def harvest(t_now):
        for key in list(tracked):
            rec = tracked[key]
            n = len(rec.req.out)
            for _ in range(n - rec.seen):
                out_tokens.append(t_now)
                if rec.last is None:
                    rec.first = t_now
                else:
                    rec.gaps.append((rec.last, t_now))
                rec.last = t_now
            rec.seen = n
            if rec.req.state in TERMINAL:
                rec.done = t_now
                finished[0] += 1
                del tracked[key]

    def submit(rec, t_now):
        rec.submitted = t_now
        srv.submit(rec.req, now=t_now)
        tracked[rec.req.rid] = rec

    def one_step():
        with log.span("step"):
            srv.step(clock())
        t_now = clock()
        harvest(t_now)
        kv_used.append((t_now, srv.cache.used_blocks))
        return t_now

    # ---- ramp, then window -------------------------------------------------
    setup["plan_s"] = clock() - t_plan
    t_gc = clock()
    gc.collect()
    gc.freeze()
    gc.disable()
    setup["gc_s"] = clock() - t_gc
    nxt = 0
    t0 = clock()
    if closed:
        # the backlog is there at time zero; `outstanding` stay in flight
        while finished[0] < ramp_done_after:
            while nxt < len(records) and len(tracked) < outstanding:
                submit(records[nxt], clock())
                nxt += 1
            one_step()
        ws = clock()
    else:
        ws = t0 + ramp_s
    setup["ramp_s"] = ws - t0
    we = ws + seconds
    trace_from = we - ctx.trace_seconds if trace else None
    tracing = None
    compiles_before = None
    t_now = clock()
    while True:
        t_now = clock()
        if compiles_before is None and t_now >= ws:
            compiles_before = ctx.compiles.count
        if t_now >= we:
            break
        if trace_from is not None and tracing is None and t_now >= trace_from:
            tracing = ctx.start_trace()
        if closed:
            while nxt < len(records) and len(tracked) < outstanding:
                submit(records[nxt], clock())
                nxt += 1
            if nxt >= len(records) and not tracked:
                raise SystemExit("serve: the backlog ran dry inside the "
                                 "window; raise `backlog` in the traffic file")
        else:
            while nxt < len(records) and t0 + records[nxt].due <= t_now:
                submit(records[nxt], clock())
                nxt += 1
        if srv.busy:
            one_step()
        else:
            due_next = t0 + records[nxt].due if nxt < len(records) else we
            time.sleep(max(0.0, min(due_next, we) - clock()))
    t_end = t_now
    queued_at_end = len(srv.queue)
    unfinished_at_end = len(tracked)
    if tracing is not None:
        ctx.stop_trace(tracing)
    compiled_inside = ctx.compiles.count - (compiles_before or 0)
    # open loop: every request due inside the window gets its first token
    if not closed:
        deadline = clock() + FIRST_TOKEN_DRAIN_S
        while clock() < deadline and any(
                r.first is None for r in records[:nxt]
                if r.req.state not in TERMINAL):
            one_step()
    gc.enable()
    gc.unfreeze()

    # ---- reduction -----------------------------------------------------------
    # host-span metrics use the untraced part of the window
    h1 = trace_from if trace_from is not None else t_end
    sub = records[:nxt]
    in_win = [r for r in sub if ws <= t0 + r.due < we] if not closed else sub
    failed = sum(r.req.state in ("timeout", "shed", "error") for r in sub)
    itl = [(b - a) * 1e3 for r in sub for a, b in r.gaps
           if a >= ws and b <= t_end]
    t_drained = clock()
    # a request still without its first token counts with the wait so far
    ttft = [((r.first if r.first is not None else t_drained)
             - (t0 + r.due)) * 1e3 for r in in_win]
    prefilled = sum(n for t1, n in counts["prefill_tokens"]
                    if ws <= t1 <= t_end)
    emitted = sum(ws <= x <= t_end for x in out_tokens)
    # how late the load generator ran (requests are submitted between steps)
    late = [(r.submitted - (t0 + r.due)) * 1e3 for r in in_win]
    kv_peak = max([u for t, u in kv_used if ws <= t <= t_end] or [0])
    kv_block_bytes = 2 * int(hp["n_layer"]) * int(hp["n_embd"]) * 2 \
        * srv.cache.block_size
    tpot = request_mean_gaps_ms([(r.done, r.gaps) for r in sub], ws, t_end)
    work = counts["prefill_tokens"] + [(x, 1) for x in out_tokens]
    decodes = [(s[2], s[3][0]) for s in log.named("decode_dispatch",
                                                  ws, t_end)]
    e2e = {}
    if itl:
        e2e["itl_p95_ms"] = pct(itl, 95)
        # the mean of the same gaps moves with any shift in the mix of
        # steps, where the percentile moves by a whole chunk or not at all
        e2e["itl_mean_ms"] = sum(itl) / len(itl)
    if tpot:
        e2e["tpot_p90_ms"] = pct(tpot, 90)
    if not closed and ttft:
        e2e["ttft_p50_ms"] = pct(ttft, 50)
    e2e["serve_tok_s"] = (prefilled + emitted) / (t_end - ws)
    step_s = log.total("step", ws, t_end)
    disp_s = {n: log.total(n, ws, t_end)
              for n in ("prefill_dispatch", "decode_dispatch")}
    # a stall shows here: the longest steps (when, how long, how much of it
    # inside the dispatches) and the longest stretches between two steps
    steps = log.named("step", ws, t_end)
    n_steps = len(steps)
    # every dispatch that lay inside the window: how many, and the longest
    # one (a stall of the machine's shows here and in no median; it stays
    # in every end-to-end metric, and the result's line says it)
    dispatched = [s[2] - s[1] for s in log.spans
                  if s[0].endswith("_dispatch") and ws <= s[1]
                  and s[2] <= t_end]
    longest_dispatch_s = max(dispatched or [0.0])
    longest_steps = [
        [a - ws, 1e3 * (b - a), 1e3 * sum(
            s[2] - s[1] for s in log.spans
            if s[0].endswith("_dispatch") and a <= s[1] and s[2] <= b)]
        for _, a, b, _ in sorted(steps, key=lambda s: s[1] - s[2])[:3]]
    between = sorted(((b[1] - a[2], a[2] - ws)
                      for a, b in zip(steps, steps[1:])), reverse=True)[:3]
    row = dict(
        seconds=t_end - ws, requests_submitted=len(sub),
        requests_due_in_window=len(in_win), requests_finished=finished[0],
        requests_finished_in_window=len(tpot),
        token_gaps=len(itl), prompt_tokens_prefilled=prefilled,
        output_tokens=emitted, compiles_inside=compiled_inside,
        queued_at_end=queued_at_end, unfinished_at_end=unfinished_at_end,
        itl_mean_ms=e2e.get("itl_mean_ms"),
        itl_p50_ms=pct(itl, 50), itl_p90_ms=pct(itl, 90),
        itl_p95_ms=pct(itl, 95), itl_p99_ms=pct(itl, 99),
        itl_p92_to_p98_ms=[pct(itl, q) for q in range(92, 99)],
        tpot_p50_ms=pct(tpot, 50), tpot_p90_ms=pct(tpot, 90),
        ttft_p50_ms=pct(ttft, 50) if not closed else None,
        ttft_p95_ms=pct(ttft, 95) if not closed else None,
        serve_tok_s=e2e["serve_tok_s"],
        output_tok_s=emitted / (t_end - ws),
        steps=n_steps, dispatches=len(dispatched),
        longest_dispatch_s=longest_dispatch_s,
        gen_late_p99_ms=pct(late, 99) if not closed else None,
        # slots decoding per decode dispatch: over the window, and per 5 s
        # of it (the first bin against the rest shows whether the ramp was
        # long enough)
        decode_occupancy=(sum(n for _, n in decodes) / len(decodes)
                          if decodes else None),
        decode_occupancy_per_5s=binned(decodes, ws, t_end, 5.0, mean=True),
        # the closed loop's phase (work and admissions over time), and
        # where a step's time goes
        tok_s_per_5s=binned(work, ws, t_end, 5.0),
        submitted_per_5s=binned([(r.submitted, 1) for r in sub], ws, t_end,
                                5.0),
        prefill_share_of_step_s=disp_s["prefill_dispatch"] / step_s
        if step_s else None,
        prefill_dispatch_ms_p50=pct([(s[2] - s[1]) * 1e3 for s in log.named(
            "prefill_dispatch", ws, t_end)], 50),
        decode_dispatch_ms_p50=pct([(s[2] - s[1]) * 1e3 for s in log.named(
            "decode_dispatch", ws, t_end)], 50),
        host_outside_dispatch_ms_per_step=1e3 * (
            step_s - sum(disp_s.values())) / n_steps if n_steps else None,
        longest_steps_at_s_ms_dispatched_ms=longest_steps,
        longest_between_steps_ms_at_s=[[1e3 * d, at] for d, at in between],
        kv_blocks_peak=kv_peak, kv_blocks_pool=srv.cache.num_blocks - 1,
        kv_bytes_filled_peak=kv_peak * kv_block_bytes,
        kv_bytes_pool=(srv.cache.num_blocks - 1) * kv_block_bytes)
    say(info="window", **row)
    if itl:
        hist = np.bincount(np.minimum((np.asarray(itl) / 20.0).astype(int),
                                      60))
        say(info="itl_staircase_20ms_bins",
            bins={int(i * 20): int(c) for i, c in enumerate(hist) if c})
    run = {
        "kind": "serve", "log": log, "records": sub,
        "host_window": (ws, h1), "window": (ws, t_end),
        "kv_used": kv_used, "pool_blocks": srv.cache.num_blocks - 1,
        "block_size": srv.cache.block_size,
        "kv_heads": int(hp["n_head"]),
        "head_dim": int(hp["n_embd"]) // int(hp["n_head"]),
        "layers": int(hp["n_layer"]),
        "num_slots": srv.num_slots,
        # the program's span ring (perf_counter), with telemetry on
        "tracer": srv.telemetry.tracer if srv.telemetry.enabled else None,
    }
    return {
        "correct": bool(compiled_inside == 0),
        "attempted": len(sub), "failed": failed,
        "end_to_end": e2e, "setup_items": setup, "window_start": ws,
        "backlog_at_end": queued_at_end, "seconds": t_end - ws,
        "unfinished_at_end": unfinished_at_end,
        "window_row": row, "run": run,
        "compiles_inside": compiled_inside,
        # beside the metrics in the result's line (run.py)
        "longest_dispatch_s": longest_dispatch_s,
        # requests the window finished, for the check after it
        "finished_in_window": [r.req for r in sub if r.done is not None
                               and ws <= r.done <= t_end
                               and r.req.state == "done" and r.req.out],
    }


def sample_finished(reqs, seed, k=SAMPLE_REQUESTS):
    """``k`` of the finished requests: the longest (prompt plus answer) and
    a draw from ``seed`` of the others, in their order."""
    if not reqs:
        return []
    longest = max(range(len(reqs)),
                  key=lambda i: len(reqs[i].prompt) + len(reqs[i].out))
    rest = [i for i in range(len(reqs)) if i != longest]
    rng = np.random.default_rng([int(seed), 2])
    drawn = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [reqs[longest]] + [reqs[rest[i]] for i in sorted(drawn)]


def served_token_gaps(reqs, params, hp, reference, chosen=None):
    """For every served token of ``reqs``: how far its logit lies below the
    reference's best logit at that position (0 where the reference puts the
    same token first). The reference runs once per request over the prompt
    with its served tokens, padded to the model's positions (it is causal,
    so the padding changes nothing before it; one shape, one compile).
    ``chosen(padded tokens [1, S], first, end) -> tokens [end - first]``
    puts other tokens in the served ones' place (the control's: see
    ``tools/serve_check_control.py``).
    Returns {rid: float32 gaps, one per served token}."""
    n_head, n_pos = int(hp["n_head"]), int(hp["n_positions"])
    out = {}
    for r in reqs:
        toks = np.concatenate([np.asarray(r.prompt, np.int32),
                               np.asarray(r.out, np.int32)])
        padded = np.zeros((1, n_pos), np.int32)
        padded[0, :len(toks) - 1] = toks[:-1]
        ref = reference.logits(params, jnp.asarray(padded), n_head)[0]
        # position j predicts token j + 1: the served ones are the last
        first = len(r.prompt) - 1
        at = ref[first:len(toks) - 1]
        served = jnp.asarray(toks[first + 1:]) if chosen is None \
            else chosen(padded, first, len(toks) - 1)
        gap = at.max(-1) - jnp.take_along_axis(at, served[:, None], -1)[:, 0]
        out[r.rid] = np.asarray(gap, np.float32)
    return out


def run(ctx):
    srv, log, counts, setup, correct, compared, params = build(ctx)
    res = drive(ctx, srv, log, counts, ctx.cell.traffic, ctx.seconds,
                np.random.default_rng([ctx.seed, 1]), trace=ctx.trace)
    compared.append(("compiles_inside_window", res["compiles_inside"], 0))
    res["correct"] = bool(res["correct"] and correct)
    res["setup_items"] = dict(setup, **res["setup_items"])
    res["compared"] = compared
    cell, hp = ctx.cell, ctx.cell.config["model"]
    limit = float(cell.config["check"]["served_gap_limit"])
    sample = sample_finished(res.pop("finished_in_window"), ctx.seed)
    pools = (srv.cache.k, srv.cache.v)
    del srv

    def after_window():
        """Once ``memory_peak_bytes`` has been read: frees the KV pools and
        holds the sample's served tokens to the reference. Returns (ok,
        [(name, value, limit)])."""
        t = time.perf_counter()
        for a in pools:
            a.delete()
        gaps = served_token_gaps(sample, params, hp, cell.reference())
        tokens = int(sum(len(g) for g in gaps.values()))
        worst = max([float(g.max()) for g in gaps.values()] or [float("nan")])
        ok = bool(tokens > 0 and worst <= limit)
        ctx.say(info="correctness_after_window", requests=len(gaps),
                served_tokens_compared=tokens,
                longest_request_tokens=max(
                    [len(r.prompt) + len(r.out) for r in sample] or [0]),
                served_gap_max=worst, limit=limit,
                tokens_not_the_references_first=int(sum(
                    (g > 0).sum() for g in gaps.values())),
                gap_max_by_request={str(k): float(g.max())
                                    for k, g in gaps.items()},
                reference_s=time.perf_counter() - t, ok=ok)
        return ok, [("served_tokens_compared", tokens, ">0"),
                    ("served_gap_max", worst, limit)]

    res["after_window"] = after_window
    return res


def _check(check, cap, params, hp, reference):
    """Served logits against the plain reference's full forward on the same
    tokens: after the final prefill chunk and after every decoded token."""
    n_head = int(hp["n_head"])
    worst, scale, agree, total = 0.0, 0.0, 0, 0
    complete = all(r.state == "done" and len(r.out) == r.max_new_tokens
                   for r in check)
    for r in check:
        toks = np.concatenate([r.prompt, np.asarray(r.out, np.int32)])
        ref = np.asarray(reference.logits(
            params, jnp.asarray(toks[None, :-1]), n_head))[0]
        served = {pos: lg for rid, pos, lg in cap if rid == r.rid}
        # one set of logits for every emitted token
        complete = complete and sorted(served) == list(range(
            len(r.prompt) - 1, len(toks) - 1))
        for pos, lg in served.items():
            want = ref[pos]
            worst = max(worst, float(np.max(np.abs(lg - want))))
            scale = max(scale, float(np.max(np.abs(want))))
            agree += int(np.argmax(lg) == np.argmax(want))
            # the served token is the argmax of the served logits (greedy)
            complete = complete and int(np.argmax(lg)) == int(toks[pos + 1])
            total += 1
    ok = complete and total > 0 and worst < LOGIT_TOL_ABS
    return ok, {"requests": len(check), "positions_compared": total,
                "max_abs_logit_error": worst, "tolerance": LOGIT_TOL_ABS,
                "largest_reference_logit": scale,
                "argmax_agreement_with_reference": agree / max(total, 1),
                "every_token_has_logits_and_is_their_argmax": bool(complete),
                "ok": bool(ok)}
