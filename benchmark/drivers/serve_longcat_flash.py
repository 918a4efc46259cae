"""Driver for configurations of ``"kind": "serve_longcat_flash"``:
LongCat-Flash-Chat (shortcut-connected double layers: two latent-attention
sublayers and two dense SwiGLUs a layer beside ONE expert layer whose
softmax router spreads 12 choices over 512 experts and 256 zero-compute
identity experts) as one chip of 32-way expert parallelism, through
``deepspeed_tpu.init_inference`` -> ``ServingEngine`` under a ``requests``
traffic mix.

The ramp, the measured window, its reduction and the draw of finished
requests are ``drivers/serve.py``'s, the judgement of the served tokens is
``drivers/serve_exaone_moe.py``'s, and the stalled-dispatch probe and the
after-window sample are ``drivers/serve_dots_vlm.py``'s (all loaded, not
copied: ``drive``, ``sample_finished``, ``judge_served``, ``host_probe``,
``sample_served``); this file brings the model's configuration from the
file's published keys, its weights, the accounting of two latent rows a
layer and the checks against the plain reference
(``benchmark/reference/longcat_flash.py``).

**The checks** are K-EXAONE's three (``serve_exaone_moe.py``'s docstring:
bf16 rounding swaps near-tied routing decisions, so the program keeps its
last dispatch's selection, ``LatentState.route``):

1. before the window, two requests, one of 2,300 + 6 tokens (four and a
   half prefill chunks: both sublayers' rows re-expanded from the pool) and
   one short, through prefill and then decode; logits at every emitted
   token against the reference FORCED to the program's selection, held to
   ``check.logit_tol_abs``;
2. every routing decision in dispute is held to a near-tie in the
   reference's biased probabilities (``check.route_tie_eps``): the lead of
   the best output the program passed over, over the weakest it chose;
3. after the window, three finished requests (the longest and two drawn
   from the seed) through the UNFORCED reference in blocks, held by the
   SHARE of served tokens that are not the reference's first
   (``check.served_off_share_limit``); the widest gap is printed, not held.

And one of the cell's own: the scheduler's ``evictions`` counter may not
move inside the window (the pool is smaller than slots x table and the
sizing says nothing is ever preempted): a run in which it does is not
``correct``.
"""

import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from harness import cells
from harness import spans as spans_lib
from harness import traffic as traffic_lib
from harness import weights_longcat_flash

_HERE = os.path.dirname(os.path.abspath(__file__))
dots = cells.load_module(os.path.join(_HERE, "serve_dots_vlm.py"),
                         "bench_driver_serve_dots_vlm")
serve, exaone = dots.serve, dots.exaone

CHECK_REQUESTS = ((2300, 6), (300, 5))  # 4.5 chunks of 512; short
CHECK_PAD = 2560                        # one reference shape for both
SERVED_PAD = 6144                       # the after-window sample's shape


def model_config(conf, dtype):
    """The program's configuration from the file's keys as they are run."""
    if not os.path.exists(os.path.join(cells.ROOT, "deepspeed_tpu", "models",
                                       "longcat_flash.py")):
        # a program from before PR 46: fail at once, before the 45 s import
        raise SystemExit(
            "serve_longcat_flash: this checkout's program has no "
            "longcat_flash dialect (deepspeed_tpu/models/longcat_flash.py)")
    from deepspeed_tpu.models.longcat_flash import (LongcatFlashConfig,
                                                    lora_scale)
    assert conf["attention_method"] == "MLA" and not conf["attention_bias"]
    assert conf["zero_expert_type"] == "identity"
    d, rq, rkv = (int(conf[k]) for k in ("hidden_size", "q_lora_rank",
                                         "kv_lora_rank"))
    return LongcatFlashConfig(
        vocab_size=int(conf["vocab_size"]), n_layers=int(conf["num_layers"]),
        n_heads=int(conf["num_attention_heads"]), d_model=d,
        d_ff=int(conf["ffn_hidden_size"]),
        max_seq_len=int(conf["serving"]["max_total"]), dtype=dtype,
        q_lora_rank=rq, kv_lora_rank=rkv,
        qk_nope_head_dim=int(conf["qk_nope_head_dim"]),
        qk_rope_head_dim=int(conf["qk_rope_head_dim"]),
        v_head_dim=int(conf["v_head_dim"]),
        q_lora_scale=lora_scale(d, rq) if conf["mla_scale_q_lora"] else 1.0,
        kv_lora_scale=lora_scale(d, rkv) if conf["mla_scale_kv_lora"]
        else 1.0,
        rope_theta=float(conf["rope_theta"]),
        num_experts=int(conf["published"]["n_routed_experts"]),
        n_zero_experts=int(conf["zero_expert_num"]),
        moe_k=int(conf["moe_topk"]),
        moe_d_ff=int(conf["expert_ffn_hidden_size"]),
        routed_scaling=float(conf["routed_scaling_factor"]),
        experts_held=(int(conf["deployment_share"]["first_expert"]),
                      int(conf["n_routed_experts"])),
        norm_eps=float(conf["rms_norm_eps"]),
        use_flash_attention=False, remat=False)


def reference_hp(cfg):
    """The reference's plain numbers, from the same configuration."""
    return {"n_heads": cfg.n_heads, "d_n": cfg.qk_nope_head_dim,
            "d_r": cfg.qk_rope_head_dim, "d_v": cfg.v_head_dim,
            "n_layers": cfg.n_layers, "num_experts": cfg.num_experts,
            "zero_experts": cfg.n_zero_experts, "top_k": cfg.moe_k,
            "held": tuple(cfg.held), "routed_scale": cfg.routed_scaling,
            "q_scale": cfg.q_lora_scale, "kv_scale": cfg.kv_lora_scale,
            "eps": cfg.norm_eps, "rope_theta": cfg.rope_theta}


def base_driver_model(cfg):
    """What ``serve.drive`` reads of ``config["model"]`` (GPT-2's keys), so
    that its arithmetic comes out right here: the vocabulary to draw ids
    from, and the pool's bytes per block = 2 x n_layer x n_embd x 2 x block,
    with ONE row of ``latent_lanes`` values a token in each of the
    ``n_full_layers`` attention sublayers (two a layer; no V pool)."""
    return {"vocab_size": cfg.vocab_size, "n_layer": cfg.n_full_layers,
            "n_embd": cfg.latent_lanes // 2, "n_head": 1,
            "n_positions": cfg.max_seq_len}


def build(ctx):
    """Weights, engine, the latent pool, the bias at rest, instrumentation
    and the checked warm-up. Returns a dict of what ``run`` needs."""
    cell, say = ctx.cell, ctx.say
    conf, sv = cell.config, cell.config["serving"]
    dtype = jnp.dtype(sv["dtype"])
    cfg = model_config(conf, dtype)
    t_imp = time.perf_counter()
    import deepspeed_tpu
    from deepspeed_tpu.inference.serving import ServeRequest, ServingEngine

    clock = time.perf_counter
    rng = np.random.default_rng(ctx.seed)
    setup = {"program_import_s": clock() - t_imp}
    conf["model"] = base_driver_model(cfg)
    reference, hp = cell.reference(), reference_hp(cfg)

    t = clock()
    params = weights_longcat_flash.longcat_flash_params(
        ctx.seed, cfg, dtype, std=float(conf.get("weights_std", 0.02)))
    jax.block_until_ready(params)
    setup["weights_s"] = clock() - t
    t = clock()
    rb = conf["router_bias"]
    params, loads = weights_longcat_flash.balance_router_bias(
        params, cfg, ctx.seed, reference, hp,
        tokens=int(rb["calibration_tokens"]))
    jax.block_until_ready(params)
    setup["balance_s"] = clock() - t
    say(info="router_bias_balanced",
        worst_load_over_mean_and_zero_share_before_after_by_layer=loads)
    t = clock()
    eng = deepspeed_tpu.init_inference((cfg, params), dtype=dtype)
    srv = ServingEngine(eng, num_slots=int(sv["num_slots"]),
                        block_size=int(sv["block_size"]),
                        num_blocks=int(sv["num_blocks"]),
                        prefill_chunk=int(sv["prefill_chunk"]),
                        telemetry=bool(ctx.trace))
    jax.block_until_ready(srv.cache.k)
    setup["engine_s"] = clock() - t
    bs = srv.cache.block_size
    say(info="serving_engine", decode_impl=srv.decode_impl,
        num_slots=srv.num_slots, pool_blocks=srv.cache.num_blocks - 1,
        block_size=bs, prefill_chunk=srv.prefill_chunk,
        latent_rows_per_token=cfg.n_full_layers,
        latent_row_values=cfg.latent_row, latent_row_lanes=cfg.latent_lanes,
        latent_pool_bytes=srv.cache.num_blocks * bs
        * srv.cache.bytes_per_token,
        weight_bytes=int(sum(a.nbytes for a in jax.tree_util.tree_leaves(
            params))))

    # the bias again, at rest on what the model itself DECODES (PERF.md
    # 7(z): greedy decoding of a random model emits few tokens again and
    # again, and those are what a decode-heavy window routes). Each round
    # rests the bias on ALL the continuations so far
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
        params, loads = weights_longcat_flash.balance_router_bias(
            params, cfg, ctx.seed, reference, hp, sequences=ids,
            counted=np.broadcast_to(np.arange(ids.shape[1]) >= n_prompt,
                                    ids.shape))
        live = eng.params["block"]["moe"]["router"]
        live["bias"] = jax.device_put(
            params["block"]["moe"]["router"]["bias"], live["bias"].sharding)
        say(info="router_bias_balanced_on_own_continuations", round=k,
            sequences=len(ids), tokens_counted=len(ids) * n_answer,
            worst_load_over_mean_and_zero_share_before_after_by_layer=loads)
    stats = getattr(srv.cache.k, "stats", None)
    if stats is not None:          # the counters start with the final bias
        srv.cache.k = srv.cache.k._replace(stats=jnp.zeros_like(stats))
    setup["balance_own_s"] = clock() - t

    log = spans_lib.SpanLog()
    counts = {"prefill_tokens": [], "capture": None, "stalls": []}

    def on_dispatch(name, t0, t1, args, out):
        """As the base driver's: prompt tokens of a prefill chunk, with the
        history it attended; (live slots, occupied blocks, latent rows
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
                route = np.asarray(out[3].route)            # [L, C, k]
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
                route = np.asarray(out[3].route)            # [L, B, k]
                for s in np.flatnonzero(active):
                    r = srv.slots[s]
                    pos = len(r.prompt) + len(r.out) - 1
                    cap["logits"].append((r.rid, pos, lg[s].reshape(-1)))
                    cap["route"].append((r.rid, pos, route[:, s:s + 1]))
            # rows an attention sublayer's kernel call reads: each live
            # slot's tokens and the one it has just written
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
        check, cap, params, cfg, reference, conf["check"],
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


def dispute_margin(mine, biased):
    """How far apart the reference holds what the two sides dispute at one
    (layer, token): the lead, in the reference's biased probabilities, of
    the best output the program passed over, over the weakest it chose (0
    or less: the reference would choose alike)."""
    chosen = np.zeros(biased.shape[0], bool)
    chosen[mine] = True
    return max(0.0, float(biased[~chosen].max() - biased[chosen].min()))


def check_warmup(check, cap, params, cfg, reference, limits, pad=CHECK_PAD,
                 fp8=False, variant=()):
    """Checks 1 and 2 of the module docstring on the warm-up requests."""
    hp = reference_hp(cfg)
    L, K = cfg.n_layers, cfg.moe_k
    tol, eps = float(limits["logit_tol_abs"]), float(limits["route_tie_eps"])
    worst, scale, agree, total = 0.0, 0.0, 0, 0
    disputed, worst_margin, routed, zero_pairs = 0, 0.0, 0, 0
    complete = all(r.state == "done" and len(r.out) == r.max_new_tokens
                   for r in check)
    for r in check:
        toks = np.concatenate([r.prompt, np.asarray(r.out, np.int32)])
        S = len(toks) - 1
        width = max(pad, S)
        forced = -np.ones((L, width, K), np.int32)
        for rid, start, sel in cap["route"]:
            if rid == r.rid and start < S:
                n = min(sel.shape[1], S - start)
                forced[:, start:start + n] = sel[:, :n]
        complete = complete and bool((forced[:, :S] >= 0).all())
        padded = np.zeros((width,), np.int32)
        padded[:S] = toks[:-1]
        ref, route = reference.logits(params, padded, hp, forced=forced,
                                      fp8=fp8, variant=variant)
        ref = np.asarray(ref)[:S]
        # 2: the program's selection against the reference's own
        own = np.asarray(route["sel"])[:, :S]
        biased = np.asarray(route["biased"])[:, :S]
        mine = np.sort(forced[:, :S], -1)
        differ = (mine != np.sort(own, -1)).any(-1)          # [L, S]
        routed += differ.size
        zero_pairs += int((mine >= cfg.num_experts).sum())
        for l, t in zip(*np.nonzero(differ)):
            worst_margin = max(worst_margin,
                               dispute_margin(mine[l, t], biased[l, t]))
            disputed += 1
        # 1: logits at every emitted token, selection forced
        served = {pos: lg for rid, pos, lg in cap["logits"] if rid == r.rid}
        complete = complete and sorted(served) == list(range(
            len(r.prompt) - 1, len(toks) - 1))
        for pos, lg in served.items():
            want = ref[pos]
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
                "route_decisions_disputed": disputed,
                "route_pairs_on_zero_experts_share":
                    zero_pairs / max(routed * K, 1),
                "route_worst_margin": worst_margin, "route_tie_eps": eps,
                "every_token_has_logits_routes_and_is_their_argmax":
                    bool(complete), "ok": ok}


def served_token_gaps(reqs, params, cfg, reference, pad_to, fp8=False,
                      variant=(), chosen=None):
    """Check 3: for every served token of ``reqs``, how far its logit lies
    below the unforced reference's best at that position. One padded shape
    (the reference is causal). ``chosen(padded, first, end)`` puts other
    tokens in the served ones' place (the control's).
    Returns {rid: float32 gaps}."""
    hp = reference_hp(cfg)
    out = {}
    for r in reqs:
        toks = np.concatenate([np.asarray(r.prompt, np.int32),
                               np.asarray(r.out, np.int32)])
        padded = np.zeros((max(pad_to, len(toks) - 1),), np.int32)
        padded[:len(toks) - 1] = toks[:-1]
        ref, _ = reference.logits(params, padded, hp, fp8=fp8,
                                  variant=variant)
        first = len(r.prompt) - 1
        at = ref[first:len(toks) - 1]
        served = jnp.asarray(toks[first + 1:]) if chosen is None \
            else chosen(padded, first, len(toks) - 1)
        gap = at.max(-1) - jnp.take_along_axis(at, served[:, None], -1)[:, 0]
        out[r.rid] = np.asarray(gap, np.float32)
    return out


def run(ctx):
    b = build(ctx)
    srv, cfg, params = b["srv"], b["cfg"], b["params"]
    cell = ctx.cell
    evictions = srv._stat["evictions"]
    evicted_before = evictions.value
    res = serve.drive(ctx, srv, b["log"], b["counts"], cell.traffic,
                      ctx.seconds, np.random.default_rng([ctx.seed, 1]),
                      trace=ctx.trace)
    compared = b["compared"]
    compared.append(("compiles_inside_window", res["compiles_inside"], 0))
    # nothing is ever preempted, from the first request of the ramp on
    evicted = int(evictions.value - evicted_before)
    compared.append(("evictions_since_the_ramp_began", evicted, 0))
    res["correct"] = bool(res["correct"] and b["correct"] and evicted == 0)
    res["setup_items"] = dict(b["setup"], **res["setup_items"])
    res["compared"] = compared
    # what the readers need beside the base driver's keys
    bs = srv.cache.block_size
    itemsize = jnp.dtype(cfg.dtype).itemsize
    res["run"].update(
        layers=cfg.n_layers,
        mla={"heads": cfg.n_heads, "d_n": cfg.qk_nope_head_dim,
             "d_r": cfg.qk_rope_head_dim, "d_v": cfg.v_head_dim,
             "latent": cfg.kv_lora_rank, "row_lanes": cfg.latent_lanes,
             "layers": cfg.n_full_layers, "block_size": bs,
             "itemsize": itemsize},
        moe={"held": cfg.held[1], "k": cfg.moe_k, "d_model": cfg.d_model,
             "d_ff": cfg.moe_d_ff, "sparse_layers": cfg.n_sparse_layers,
             "zero_experts": cfg.n_zero_experts, "itemsize": itemsize},
        # device counters, pulled once, after the window (telemetry on)
        moe_counters=srv.read_expert_counters(),
        latent_pool_bytes=srv.cache.num_blocks * bs
        * srv.cache.bytes_per_token)
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
    state = srv.cache.k
    del srv, b

    def after_window():
        """Once ``memory_peak_bytes`` has been read: frees the latent pool
        and holds the sample's served tokens to the reference."""
        t = time.perf_counter()
        state.delete()
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
