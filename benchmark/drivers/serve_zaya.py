"""Driver for configurations of ``"kind": "serve_zaya"``: ZAYA1-8B
(attention inside a compressed latent with convolutions over time, a top-1
expert layer with a skip chosen by an MLP router that carries state from
layer to layer) as ONE STAGE of a two-stage pipeline, through
``deepspeed_tpu.init_inference`` -> ``ServingEngine`` under a ``requests``
traffic mix.

The ramp, the measured window, its reduction and the draw of finished
requests are ``drivers/serve.py``'s, the judgement of the served tokens is
``drivers/serve_exaone_moe.py``'s and the stalled-dispatch probe and the
after-window sample are ``drivers/serve_dots_vlm.py``'s (all loaded, not
copied: ``drive``, ``sample_finished``, ``judge_served``, ``host_probe``,
``sample_served``); this file brings the model's configuration from the
file's published keys, its weights, and the checks against the plain
reference (``benchmark/reference/zaya.py``).

**The checks** are K-EXAONE's three (``serve_exaone_moe.py``'s docstring:
bf16 rounding swaps near-tied routing decisions, so the program keeps its
last dispatch's selection, ``CCAState.route``), with one expert a token:

1. before the window, two requests, one longer than four prefill chunks
   (so that the per-slot tail crosses chunk borders and then resumes in
   decode) and one short; logits at every emitted token against the
   reference FORCED to the program's selection, held to
   ``check.logit_tol_abs``;
2. every top-1 decision in dispute is held to a near-tie in the
   reference's biased probabilities: the reference's own choice leads the
   program's by at most ``check.route_tie_eps``;
3. after the window, three finished requests (the longest and two drawn
   from the seed) through the UNFORCED reference, held by the SHARE of
   served tokens that are not the reference's first
   (``check.served_off_share_limit``); the widest gap is printed, not held.

The vocabulary is whole (262,272), so logits are never formed for a whole
sequence: the reference hands back its final stream and the head is applied
to the rows that are compared, a block at a time.
"""

import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from harness import cells
from harness import spans as spans_lib
from harness import traffic as traffic_lib
from harness import weights_zaya

_HERE = os.path.dirname(os.path.abspath(__file__))
dots = cells.load_module(os.path.join(_HERE, "serve_dots_vlm.py"),
                         "bench_driver_serve_dots_vlm")
serve, exaone = dots.serve, dots.exaone

CHECK_REQUESTS = ((2300, 6), (300, 5))  # 4.5 chunks of 512; short
CHECK_PAD = 2560                        # one reference shape for both
SERVED_PAD = 6144                       # the after-window sample's shape
HEAD_ROWS = 512                         # rows of logits alive at a time


def model_config(conf, dtype):
    """The program's configuration from the file's keys as they are run."""
    from deepspeed_tpu.models.zaya import ZayaConfig
    rope = conf["rope_parameters"]["hybrid"]
    assert all(t == "hybrid" for t in conf["layer_types"])
    assert conf["tie_word_embeddings"] and not conf["attention_bias"]
    share = conf["deployment_share"]
    return ZayaConfig(
        vocab_size=int(conf["vocab_size"]),
        n_layers=int(conf["num_hidden_layers"]),
        n_heads=int(conf["num_attention_heads"]),
        n_kv_heads=int(conf["num_key_value_heads"]),
        head_size=int(conf["head_dim"]), d_model=int(conf["hidden_size"]),
        max_seq_len=int(conf["serving"]["max_total"]), dtype=dtype,
        cca_time0=int(conf["cca_time0"]), cca_time1=int(conf["cca_time1"]),
        partial_rotary_factor=float(rope["partial_rotary_factor"]),
        rope_theta=float(rope["rope_theta"]),
        num_experts=int(conf["num_experts"]),
        moe_k=int(conf["num_experts_per_tok"]),
        moe_d_ff=int(conf["moe_intermediate_size"]),
        router_hidden=int(conf["router_hidden_size"]),
        experts_held=(int(share["first_expert"]), int(share["experts_held"])),
        norm_eps=float(conf["rms_norm_eps"]),
        use_flash_attention=False, remat=False)


def reference_hp(cfg):
    """The reference's plain numbers, from the same configuration."""
    return {"n_heads": cfg.n_heads, "kv_heads": cfg.kv_heads,
            "head_dim": cfg.head_dim, "rotary_dim": cfg.rotary_channels,
            "rope_theta": cfg.rope_theta, "n_layers": cfg.n_layers,
            "num_experts": cfg.num_experts, "held": tuple(cfg.held),
            "eps": cfg.norm_eps}


def base_driver_model(cfg):
    """What ``serve.drive`` reads of ``config["model"]`` (GPT-2's keys), so
    that its arithmetic comes out right here: the vocabulary to draw ids
    from, and the pools' bytes per block = 2 x n_layer x n_embd x 2 x block
    with K and V rows of ``kv_heads x head_dim`` values."""
    return {"vocab_size": cfg.vocab_size, "n_layer": cfg.n_layers,
            "n_embd": cfg.kv_heads * cfg.head_dim, "n_head": cfg.kv_heads,
            "n_positions": cfg.max_seq_len}


def build(ctx):
    """Weights, engine, the pools and tails, instrumentation and the
    checked warm-up. Returns a dict of what ``run`` needs."""
    cell, say = ctx.cell, ctx.say
    if not os.path.exists(os.path.join(cell.root, "deepspeed_tpu", "models",
                                       "zaya.py")):
        # a program from before PR 34: fail at once, before the 45 s import
        raise SystemExit("serve_zaya: this checkout's program has no zaya "
                         "dialect (deepspeed_tpu/models/zaya.py)")
    t_imp = time.perf_counter()
    import deepspeed_tpu
    from deepspeed_tpu.inference.serving import ServeRequest, ServingEngine

    conf, sv = cell.config, cell.config["serving"]
    clock = time.perf_counter
    rng = np.random.default_rng(ctx.seed)
    setup = {"program_import_s": clock() - t_imp}
    dtype = jnp.dtype(sv["dtype"])
    cfg = model_config(conf, dtype)
    conf["model"] = base_driver_model(cfg)

    t = clock()
    params = weights_zaya.zaya_params(
        ctx.seed, cfg, dtype, std=float(conf.get("weights_std", 0.02)))
    jax.block_until_ready(params)
    setup["weights_s"] = clock() - t
    t = clock()
    rb = conf["router_bias"]
    params, loads = weights_zaya.balance_router_bias(
        params, cfg, ctx.seed, cell.reference(), reference_hp(cfg),
        tokens=int(rb["calibration_tokens"]),
        skip_share=float(rb["skip_share"]))
    jax.block_until_ready(params)
    setup["balance_s"] = clock() - t
    say(info="router_bias_balanced",
        worst_load_over_mean_before_after_and_skip_share_by_layer=loads)
    t = clock()
    eng = deepspeed_tpu.init_inference((cfg, params), dtype=dtype)
    srv = ServingEngine(eng, num_slots=int(sv["num_slots"]),
                        block_size=int(sv["block_size"]),
                        num_blocks=int(sv["num_blocks"]),
                        prefill_chunk=int(sv["prefill_chunk"]),
                        telemetry=bool(ctx.trace))
    jax.block_until_ready(srv.cache.pools)
    setup["engine_s"] = clock() - t
    bs = srv.cache.block_size
    say(info="serving_engine", decode_impl=srv.decode_impl,
        num_slots=srv.num_slots, pool_blocks=srv.cache.num_blocks - 1,
        block_size=bs, prefill_chunk=srv.prefill_chunk,
        kv_pool_bytes=srv.cache.num_blocks * bs * srv.cache.bytes_per_token,
        cca_tail_bytes=srv.cache.cca_tail_bytes,
        weight_bytes=int(sum(a.nbytes for a in jax.tree_util.tree_leaves(
            params))))

    # the bias again, at rest on what the model itself DECODES: it emits few
    # tokens again and again, and those are what the window routes. A new
    # bias changes what is emitted, and every sequence falls into a loop of
    # its own, so this goes round more than once and each round rests the
    # bias on ALL the continuations so far
    t = clock()
    own = rb["own_continuations"]
    n_prompt, n_answer = int(own["prompt"]), int(own["answer"])
    ids = np.zeros((0, n_prompt + n_answer), np.int32)
    for k in range(int(own["rounds"])):
        seqs = [ServeRequest(rid=f"own{k}.{i}", max_new_tokens=n_answer,
                             prompt=traffic_lib.prompt_tokens(
                                 n_prompt, cfg.vocab_size, rng))
                for i in range(int(own["requests"]))]
        for r in seqs:
            srv.submit(r, now=clock())
        while srv.busy:
            srv.step(clock())
        ids = np.concatenate([ids] + [np.concatenate(
            [r.prompt, np.asarray(r.out, np.int32)])[None, :ids.shape[1]]
            for r in seqs])
        params, loads = weights_zaya.balance_router_bias(
            params, cfg, ctx.seed, cell.reference(), reference_hp(cfg),
            skip_share=float(rb["skip_share"]), sequences=ids,
            counted=np.broadcast_to(np.arange(ids.shape[1]) >= n_prompt,
                                    ids.shape))
        live = eng.params["block"]["moe"]["router"]
        live["bias"] = jax.device_put(
            params["block"]["moe"]["router"]["bias"], live["bias"].sharding)
        say(info="router_bias_balanced_on_own_continuations", round=k,
            sequences=len(ids), tokens_counted=len(ids) * n_answer,
            worst_load_over_mean_before_after_and_skip_share_by_layer=loads)
    stats = getattr(srv.cache.k, "stats", None)
    if stats is not None:          # the counters start with the final bias
        srv.cache.k = srv.cache.k._replace(stats=jnp.zeros_like(stats))
    setup["balance_own_s"] = clock() - t

    log = spans_lib.SpanLog()
    counts = {"prefill_tokens": [], "capture": None, "stalls": []}

    def on_dispatch(name, t0, t1, args, out):
        """As the base driver's: prompt tokens of a prefill chunk, with the
        history it attended; (live slots, occupied blocks, cached rows
        read) of a decode. While capturing, also the logits and the
        dispatch's routing of the check requests."""
        cap = counts["capture"]
        if t1 - t0 > dots.STALL_S and cap is None:
            counts["stalls"].append({"name": name, "t0": t0,
                                     "ms": 1e3 * (t1 - t0),
                                     "host": dots.host_probe()})
        if name == "prefill_dispatch":
            n, start = int(args[5]), int(args[4])
            counts["prefill_tokens"].append((t1, n))
            if cap is not None:
                route = np.asarray(out[3].route)            # [L, C, 1]
                for s, r in enumerate(srv.slots):
                    if r is not None and r.state == "prefill" \
                            and np.array_equal(srv.cache.tables[s], args[2]):
                        cap["route"].append((r.rid, start, route[:, :n]))
                        if start + n == len(r.prompt):
                            cap["logits"].append((
                                r.rid, len(r.prompt) - 1, np.asarray(
                                    out[0], np.float32).reshape(-1)))
            return (n, start)
        if name == "decode_dispatch":
            active = np.asarray(args[5])
            lengths = np.asarray(args[3])
            blocks = int(((lengths[active] + bs) // bs).sum())
            if cap is not None:
                lg = np.asarray(out[0], np.float32)
                route = np.asarray(out[3].route)            # [L, B, 1]
                for s in np.flatnonzero(active):
                    r = srv.slots[s]
                    pos = len(r.prompt) + len(r.out) - 1
                    cap["logits"].append((r.rid, pos, lg[s].reshape(-1)))
                    cap["route"].append((r.rid, pos, route[:, s:s + 1]))
            # rows a layer's kernel call reads: each live slot's tokens and
            # the one it has just written
            return (int(active.sum()), blocks,
                    int((lengths[active] + 1).sum()))
        return None

    spans_lib.instrument_serving(srv, log, on_dispatch)

    # ---- warm-up that is also the correctness sample ---------------------
    t = clock()
    counts["capture"] = cap = {"logits": [], "route": []}
    check = [ServeRequest(rid=f"check{i}", max_new_tokens=a,
                          prompt=traffic_lib.prompt_tokens(p, cfg.vocab_size,
                                                           rng))
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
    ok, detail = check_warmup(
        check, cap, params, cfg, cell.reference(), conf["check"],
        pad=int(ctx.rehearsal.get("check_pad", CHECK_PAD)))
    setup["reference_check_s"] = clock() - t
    say(info="correctness", **detail)
    log.spans.clear()
    counts["prefill_tokens"].clear()
    counts["host_before"] = dots.host_probe()
    compared = [
        ("warmup_max_abs_logit_error", detail["max_abs_logit_error"],
         float(conf["check"]["logit_tol_abs"])),
        ("warmup_route_worst_disagreement", detail["route_worst_margin"],
         float(conf["check"]["route_tie_eps"]))]
    return {"srv": srv, "log": log, "counts": counts, "setup": setup,
            "correct": ok, "compared": compared, "params": params,
            "cfg": cfg, "checked": (check, cap)}


def check_warmup(check, cap, params, cfg, reference, limits, pad=CHECK_PAD,
                 fp8=False, variant=()):
    """Checks 1 and 2 of the module docstring on the warm-up requests."""
    hp = reference_hp(cfg)
    L = cfg.n_layers
    tol, eps = float(limits["logit_tol_abs"]), float(limits["route_tie_eps"])
    worst, scale, agree, total = 0.0, 0.0, 0, 0
    disputed, worst_margin, routed, skipped = 0, 0.0, 0, 0
    complete = all(r.state == "done" and len(r.out) == r.max_new_tokens
                   for r in check)
    for r in check:
        toks = np.concatenate([r.prompt, np.asarray(r.out, np.int32)])
        S = len(toks) - 1
        width = max(pad, S)
        forced = -np.ones((L, width, 1), np.int32)
        for rid, start, sel in cap["route"]:
            if rid == r.rid and start < S:
                n = min(sel.shape[1], S - start)
                forced[:, start:start + n] = sel[:, :n]
        complete = complete and bool((forced[:, :S] >= 0).all())
        padded = np.zeros((width,), np.int32)
        padded[:S] = toks[:-1]
        x, route = reference.hidden(params, padded, hp, forced=forced,
                                    fp8=fp8, variant=variant)
        # 2: the program's top-1 choice against the reference's own
        own = np.asarray(route["sel"])[:, :S]                   # [L, S]
        biased = np.asarray(route["biased"])[:, :S]             # [L, S, E+1]
        mine = forced[:, :S, 0]
        routed += mine.size
        skipped += int((mine == cfg.num_experts).sum())
        for l, t in zip(*np.nonzero(mine != own)):
            margin = float(biased[l, t, own[l, t]] - biased[l, t, mine[l, t]])
            worst_margin = max(worst_margin, margin)
            disputed += 1
        # 1: logits at every emitted token, selection forced
        served = {pos: lg for rid, pos, lg in cap["logits"] if rid == r.rid}
        complete = complete and sorted(served) == list(range(
            len(r.prompt) - 1, len(toks) - 1))
        at = sorted(served)
        ref = np.asarray(reference.head(params, x[np.asarray(at)], fp8=fp8)) \
            if at else np.zeros((0, cfg.vocab_size), np.float32)
        for want, pos in zip(ref, at):
            lg = served[pos]
            worst = max(worst, float(np.max(np.abs(lg - want))))
            scale = max(scale, float(np.max(np.abs(want))))
            agree += int(np.argmax(lg) == np.argmax(want))
            complete = complete and int(np.argmax(lg)) == int(toks[pos + 1])
            total += 1
    ok = bool(complete and total > 0 and worst < tol and worst_margin <= eps)
    return ok, {"requests": len(check), "positions_compared": total,
                "max_abs_logit_error": worst, "tolerance": tol,
                "largest_reference_logit": scale,
                "argmax_agreement_with_reference": agree / max(total, 1),
                "route_decisions_compared": routed,
                "route_decisions_on_the_skip": skipped,
                "route_decisions_disputed": disputed,
                "route_worst_margin": worst_margin, "route_tie_eps": eps,
                "every_token_has_logits_routes_and_is_their_argmax":
                    bool(complete), "ok": ok}


@jax.jit
def _block_gaps(lg, served):
    """Rows of logits ``[n, V]`` -> how far below the row's best each
    ``served`` token lies, and the row's first token."""
    best = lg.max(-1)
    return best - jnp.take_along_axis(lg, served[:, None], -1)[:, 0], \
        jnp.argmax(lg, -1).astype(jnp.int32)


def served_token_gaps(reqs, params, cfg, reference, pad_to, fp8=False,
                      variant=(), chosen_fp8=False):
    """Check 3: for every served token of ``reqs``, how far its logit lies
    below the unforced reference's best at that position. One padded shape
    (the reference is causal); the head a block of ``HEAD_ROWS`` rows at a
    time. ``chosen_fp8`` puts the tokens the float8 reference puts first in
    the served ones' place (the control's). Returns {rid: float32 gaps}."""
    hp = reference_hp(cfg)
    out = {}
    for r in reqs:
        toks = np.concatenate([np.asarray(r.prompt, np.int32),
                               np.asarray(r.out, np.int32)])
        padded = np.zeros((max(pad_to, len(toks) - 1),), np.int32)
        padded[:len(toks) - 1] = toks[:-1]
        x, _ = reference.hidden(params, padded, hp, fp8=fp8, variant=variant)
        x8 = reference.hidden(params, padded, hp, fp8=True)[0] \
            if chosen_fp8 else None
        first, end = len(r.prompt) - 1, len(toks) - 1
        gaps = []
        for at in range(first, end, HEAD_ROWS):
            rows = np.minimum(np.arange(at, at + HEAD_ROWS), end - 1)
            served = jnp.asarray(toks[rows + 1])
            if chosen_fp8:
                served = _block_gaps(reference.head(params, x8[rows],
                                                    fp8=True), served)[1]
            gap, _ = _block_gaps(reference.head(params, x[rows], fp8=fp8),
                                 served)
            gaps.append(np.asarray(gap, np.float32)[:end - at])
        out[r.rid] = np.concatenate(gaps)
    return out


def run(ctx):
    b = build(ctx)
    srv, cfg, params = b["srv"], b["cfg"], b["params"]
    cell = ctx.cell
    res = serve.drive(ctx, srv, b["log"], b["counts"], cell.traffic,
                      ctx.seconds, np.random.default_rng([ctx.seed, 1]),
                      trace=ctx.trace)
    compared = b["compared"]
    compared.append(("compiles_inside_window", res["compiles_inside"], 0))
    res["correct"] = bool(res["correct"] and b["correct"])
    res["setup_items"] = dict(b["setup"], **res["setup_items"])
    res["compared"] = compared
    # what the readers need beside the base driver's keys
    itemsize = jnp.dtype(cfg.dtype).itemsize
    res["run"].update(
        layers=cfg.n_layers,
        cca={"heads": cfg.n_heads, "kv_heads": cfg.kv_heads,
             "head_dim": cfg.head_dim, "layers": cfg.n_layers,
             "itemsize": itemsize},
        moe={"held": cfg.held[1], "k": cfg.moe_k, "d_model": cfg.d_model,
             "d_ff": cfg.moe_d_ff, "sparse_layers": cfg.n_sparse_layers,
             "itemsize": itemsize},
        # device counters, pulled once, after the window (telemetry on)
        moe_counters=srv.read_expert_counters(),
        cca_tail_bytes=srv.cache.cca_tail_bytes)
    if res["run"]["moe_counters"]:
        ctx.say(info="moe_counters", **res["run"]["moe_counters"])
    ws, we = res["run"]["window"]
    longest = sorted((s for s in b["log"].spans if s[0].endswith("_dispatch")
                      and ws <= s[1] and s[2] <= we),
                     key=lambda s: s[1] - s[2])[:3]
    ctx.say(info="longest_dispatches", at_s_ms_name_value=[
        [s[1] - ws, 1e3 * (s[2] - s[1]), s[0], s[3]] for s in longest])
    stalls = b["counts"]["stalls"]
    if stalls:
        ctx.say(info="stalled_dispatches", threshold_s=dots.STALL_S, stalls=[
            dict(st, at_s=st["t0"] - ws) for st in stalls],
            host_before_ramp=b["counts"]["host_before"],
            host_after_window=dots.host_probe())
    limit = float(cell.config["check"]["served_off_share_limit"])
    pad_to = int(ctx.rehearsal.get("served_pad", SERVED_PAD))
    sample = dots.sample_served(res.pop("finished_in_window"), ctx.seed,
                                pad_to, pad_to)
    state, v_pool = srv.cache.k, srv.cache.v
    del srv, b

    def after_window():
        """Once ``memory_peak_bytes`` has been read: frees the pools and
        holds the sample's served tokens to the reference."""
        t = time.perf_counter()
        state.delete()
        v_pool.delete()
        gaps = served_token_gaps(sample, params, cfg, cell.reference(),
                                 pad_to)
        ok, row, compared = exaone.judge_served(gaps, limit)
        ctx.say(info="correctness_after_window", requests=len(gaps),
                request_tokens=[len(r.prompt) + len(r.out) for r in sample],
                gap_max_by_request={str(k): float(g.max())
                                    for k, g in gaps.items()},
                reference_s=time.perf_counter() - t, ok=ok, **row)
        return ok, compared

    res["after_window"] = after_window
    return res
