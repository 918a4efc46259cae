"""Driver for configurations of ``"kind": "serve_exaone_moe"``: the
``exaone_moe`` decoder (K-EXAONE) as one chip's share of an expert-parallel
deployment, through ``deepspeed_tpu.init_inference`` -> ``ServingEngine``
under a ``requests`` traffic mix.

The ramp, the measured window, its reduction and the sampling of finished
requests are ``drivers/serve.py``'s (loaded, not copied: ``drive``,
``sample_finished``); this file brings what that driver builds for GPT-2 by
name: the model's configuration from the file's published keys, its
weights, the KV accounting of two kinds of state, and both checks against
the plain reference (``benchmark/reference/exaone_moe.py``).

**The checks, and routing near-ties.** bf16 rounding of a layer's input can
swap a token's k-th and (k+1)-th expert, and from there the logits move as
far as a wrong router would move them. So the program keeps, with its
state, the selection of its last dispatch (``PagedState.route``), and

1. the warm-up requests' logits (after the final prefill chunk and after
   every decoded token) are compared with the reference FORCED to the
   program's selection at every token of the request: what is left is
   arithmetic, held to ``check.logit_tol_abs``;
2. the selection itself is held to the reference's own: wherever the two
   differ at a token, the reference's biased scores of the experts in
   dispute may lie no further apart than ``check.route_tie_eps`` (a
   near-tie; counted and printed). A wrong router (another score function,
   a missing bias) disagrees by far more, and a wrong weighting, scale or
   held set fails 1;
3. after the window, six of the requests it finished go through the
   UNFORCED reference, prompt and served tokens together. The selection of
   the window's dispatches is not kept, so this comparison carries the
   near-ties: the WIDEST gap of a served token's logit under the
   reference's best is then set by the one worst swap of a few thousand
   tokens and does not tell bf16 from float8 (PERF.md, PR 28: 0.47-0.84
   against 0.76-1.18), so it is printed and not held to a limit. What is
   held to ``check.served_off_share_limit`` is the SHARE of served tokens
   whose logit lies below the reference's best at all: rounding moves a
   first place at a rate that follows the precision (bf16 5.5-5.9%, float8
   16.4-17.4%), and a wrong token path moves nearly all of them.
"""

import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from harness import cells
from harness import spans as spans_lib
from harness import traffic as traffic_lib
from harness import weights_exaone_moe

_HERE = os.path.dirname(os.path.abspath(__file__))
serve = cells.load_module(os.path.join(_HERE, "serve.py"),
                          "bench_driver_serve_base")

CHECK_REQUESTS = ((700, 6), (300, 5))   # past the window, over a chunk edge
CHECK_PAD = 1024                        # one reference shape for both


def model_config(conf, dtype):
    """The program's configuration from the file's keys as they are run."""
    from deepspeed_tpu.models.exaone_moe import ExaoneMoEConfig
    n = int(conf["num_hidden_layers"])
    kinds = tuple("sliding" if t == "sliding_attention" else "full"
                  for t in conf["layer_types"][:n])
    return ExaoneMoEConfig(
        vocab_size=int(conf["vocab_size"]), n_layers=n,
        n_heads=int(conf["num_attention_heads"]),
        n_kv_heads=int(conf["num_key_value_heads"]),
        d_model=int(conf["hidden_size"]), head_size=int(conf["head_dim"]),
        d_ff=int(conf["intermediate_size"]),
        max_seq_len=int(conf["serving"]["max_total"]), dtype=dtype,
        attn_window=int(conf["sliding_window"]), layer_kinds=kinds,
        n_dense_layers=int(conf["first_k_dense_replace"]),
        num_experts=int(conf["published"]["num_experts"]),
        moe_k=int(conf["num_experts_per_tok"]),
        moe_d_ff=int(conf["moe_intermediate_size"]),
        n_shared_experts=int(conf["num_shared_experts"]),
        routed_scaling=float(conf["routed_scaling_factor"]),
        experts_held=(int(conf["deployment_share"]["first_expert"]),
                      int(conf["num_experts"])),
        norm_eps=float(conf["rms_norm_eps"]),
        rope_theta=float(conf["rope_parameters"]["rope_theta"]),
        use_flash_attention=False, remat=False)


def reference_hp(cfg):
    """The reference's plain numbers, from the same configuration."""
    return {"n_heads": cfg.n_heads, "n_kv_heads": cfg.kv_heads,
            "head_dim": cfg.head_dim, "window": cfg.attn_window,
            "kinds": tuple(cfg.layer_kinds), "n_dense": cfg.n_dense_layers,
            "num_experts": cfg.num_experts, "top_k": cfg.moe_k,
            "held": tuple(cfg.held), "routed_scale": cfg.routed_scaling,
            "eps": cfg.norm_eps, "rope_theta": cfg.rope_theta}


def base_driver_model(cfg):
    """What ``serve.drive`` reads of ``config["model"]`` (GPT-2's keys), so
    that its arithmetic comes out right here: the vocabulary to draw ids
    from, and the paged pool's bytes per block = 2 x n_layer x n_embd x 2 x
    block, with the FULL layers as its layers and a token's KV row
    (Hkv x Dh) as its width."""
    return {"vocab_size": cfg.vocab_size, "n_layer": cfg.n_full_layers,
            "n_embd": cfg.kv_heads * cfg.head_dim, "n_head": cfg.kv_heads,
            "n_positions": cfg.max_seq_len}


def build(ctx):
    """Weights, engine, both kinds of KV state, instrumentation and the
    checked warm-up. Returns a dict of what ``run`` needs."""
    t_imp = time.perf_counter()
    import deepspeed_tpu
    from deepspeed_tpu.inference.serving import ServeRequest, ServingEngine

    cell, say = ctx.cell, ctx.say
    conf, sv = cell.config, cell.config["serving"]
    clock = time.perf_counter
    rng = np.random.default_rng(ctx.seed)
    setup = {"program_import_s": clock() - t_imp}
    dtype = jnp.dtype(sv["dtype"])
    cfg = model_config(conf, dtype)
    conf["model"] = base_driver_model(cfg)

    t = clock()
    params = weights_exaone_moe.exaone_moe_params(
        ctx.seed, cfg, dtype, std=float(conf.get("weights_std", 0.02)))
    jax.block_until_ready(params)
    setup["weights_s"] = clock() - t
    t = clock()
    eng = deepspeed_tpu.init_inference((cfg, params), dtype=dtype)
    srv = ServingEngine(eng, num_slots=int(sv["num_slots"]),
                        block_size=int(sv["block_size"]),
                        num_blocks=int(sv["num_blocks"]),
                        prefill_chunk=int(sv["prefill_chunk"]),
                        telemetry=bool(ctx.trace))
    jax.block_until_ready((srv.cache.k, srv.cache.v))
    setup["engine_s"] = clock() - t
    say(info="serving_engine", decode_impl=srv.decode_impl,
        num_slots=srv.num_slots, pool_blocks=srv.cache.num_blocks - 1,
        block_size=srv.cache.block_size, prefill_chunk=srv.prefill_chunk,
        ring_blocks=srv.cache.ring_blocks,
        full_pool_bytes=srv.cache.num_blocks * srv.cache.block_size
        * srv.cache.bytes_per_token,
        window_state_bytes=srv.cache.window_bytes,
        weight_bytes=int(sum(a.nbytes for a in jax.tree_util.tree_leaves(
            params))))

    log = spans_lib.SpanLog()
    counts = {"prefill_tokens": [], "capture": None}

    def on_dispatch(name, t0, t1, args, out):
        """As the base driver's: prompt tokens of a prefill chunk; (live
        slots, occupied full-layer blocks) of a decode. While capturing,
        also the logits and the dispatch's routing of the check requests."""
        cap = counts["capture"]
        if name == "prefill_dispatch":
            n, start = int(args[5]), int(args[4])
            counts["prefill_tokens"].append((t1, n))
            if cap is not None:
                route = np.asarray(out[3].route)            # [Ls, C, k]
                for s, r in enumerate(srv.slots):
                    if r is not None and r.state == "prefill" \
                            and np.array_equal(srv.cache.tables[s], args[2]):
                        cap["route"].append((r.rid, start, route[:, :n]))
                        if start + n == len(r.prompt):
                            cap["logits"].append((
                                r.rid, len(r.prompt) - 1, np.asarray(
                                    out[0], np.float32).reshape(-1)))
            return n
        if name == "decode_dispatch":
            active = np.asarray(args[5])
            lengths = np.asarray(args[3])
            bs = srv.cache.block_size
            blocks = int(((lengths[active] + bs) // bs).sum())
            if cap is not None:
                lg = np.asarray(out[0], np.float32)
                route = np.asarray(out[3].route)            # [Ls, B, k]
                for s in np.flatnonzero(active):
                    r = srv.slots[s]
                    pos = len(r.prompt) + len(r.out) - 1
                    cap["logits"].append((r.rid, pos, lg[s].reshape(-1)))
                    cap["route"].append((r.rid, pos, route[:, s:s + 1]))
            # tokens the window layers read: min(length + 1, window) a slot
            win = int(np.minimum(lengths[active] + 1, cfg.attn_window).sum())
            return (int(active.sum()), blocks, win)
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
    ok, detail = check_warmup(check, cap, params, cfg, cell.reference(),
                              conf["check"])
    setup["reference_check_s"] = clock() - t
    say(info="correctness", **detail)
    log.spans.clear()
    counts["prefill_tokens"].clear()
    compared = [
        ("warmup_max_abs_logit_error", detail["max_abs_logit_error"],
         float(conf["check"]["logit_tol_abs"])),
        ("warmup_route_worst_disagreement", detail["route_worst_margin"],
         float(conf["check"]["route_tie_eps"]))]
    return {"srv": srv, "log": log, "counts": counts, "setup": setup,
            "correct": ok, "compared": compared, "params": params,
            "cfg": cfg, "checked": (check, cap)}


def check_warmup(check, cap, params, cfg, reference, limits, fp8=False,
                 variant=()):
    """Checks 1 and 2 of the module docstring on the warm-up requests."""
    hp = reference_hp(cfg)
    Ls, K = cfg.n_sparse_layers, cfg.moe_k
    tol, eps = float(limits["logit_tol_abs"]), float(limits["route_tie_eps"])
    worst, scale, agree, total = 0.0, 0.0, 0, 0
    disputed, worst_margin, routed = 0, 0.0, 0
    complete = all(r.state == "done" and len(r.out) == r.max_new_tokens
                   for r in check)
    for r in check:
        toks = np.concatenate([r.prompt, np.asarray(r.out, np.int32)])
        S = len(toks) - 1
        pad = max(CHECK_PAD, S) if S > 256 else S
        forced = -np.ones((Ls, pad, K), np.int32)
        for rid, start, sel in cap["route"]:
            if rid == r.rid and start < S:
                n = min(sel.shape[1], S - start)
                forced[:, start:start + n] = sel[:, :n]
        complete = complete and bool((forced[:, :S] >= 0).all())
        padded = np.zeros((pad,), np.int32)
        padded[:S] = toks[:-1]
        ref, route = reference.logits(params, padded, hp, forced=forced,
                                      fp8=fp8, variant=variant)
        ref = np.asarray(ref)[:S]
        # 2: the program's selection against the reference's own
        own = np.asarray(route["sel"])[:, :S]
        biased = np.asarray(route["biased"])[:, :S]
        mine = np.sort(forced[:, :S], -1)
        theirs = np.sort(own, -1)
        differ = (mine != theirs).any(-1)                    # [Ls, S]
        routed += differ.size
        for l, t in zip(*np.nonzero(differ)):
            only_prog = np.setdiff1d(mine[l, t], theirs[l, t])
            only_ref = np.setdiff1d(theirs[l, t], mine[l, t])
            margin = float(biased[l, t, only_ref].max()
                           - biased[l, t, only_prog].min())
            worst_margin = max(worst_margin, margin)
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
                "route_worst_margin": worst_margin, "route_tie_eps": eps,
                "every_token_has_logits_routes_and_is_their_argmax":
                    bool(complete), "ok": ok}


def served_token_gaps(reqs, params, cfg, reference, pad_to, fp8=False,
                      chosen=None):
    """Check 3: for every served token of ``reqs``, how far its logit lies
    below the unforced reference's best at that position. One padded shape
    (the reference is causal). ``chosen(logits [S, V], first, end)`` puts
    other tokens in the served ones' place (the control's).
    Returns {rid: float32 gaps}."""
    hp = reference_hp(cfg)
    out = {}
    for r in reqs:
        toks = np.concatenate([np.asarray(r.prompt, np.int32),
                               np.asarray(r.out, np.int32)])
        padded = np.zeros((pad_to,), np.int32)
        padded[:len(toks) - 1] = toks[:-1]
        ref, _ = reference.logits(params, padded, hp, fp8=fp8)
        first = len(r.prompt) - 1
        at = ref[first:len(toks) - 1]
        served = jnp.asarray(toks[first + 1:]) if chosen is None \
            else chosen(padded, first, len(toks) - 1)
        gap = at.max(-1) - jnp.take_along_axis(at, served[:, None], -1)[:, 0]
        out[r.rid] = np.asarray(gap, np.float32)
    return out


def judge_served(gaps, limit):
    """(ok, the numbers, [(name, value, limit)]) of check 3 from
    ``served_token_gaps``."""
    allg = np.concatenate(list(gaps.values())) if gaps else np.zeros((0,))
    tokens = int(allg.size)
    share = float((allg > 0).mean()) if tokens else float("nan")
    ok = bool(tokens > 0 and share <= limit)
    row = {"served_tokens_compared": tokens,
           "served_off_share": share, "limit": limit,
           "tokens_not_the_references_first": int((allg > 0).sum()),
           "served_gap_max": float(allg.max()) if tokens else float("nan"),
           "served_gap_mean": float(allg.mean()) if tokens else float("nan")}
    return ok, row, [("served_tokens_compared", tokens, ">0"),
                     ("served_off_share", share, limit),
                     ("served_gap_max_not_held", row["served_gap_max"],
                      "none")]


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
    # what the new readers need beside the base driver's keys
    bs = srv.cache.block_size
    res["run"].update(
        kv_heads=cfg.kv_heads, head_dim=cfg.head_dim,
        layers=cfg.n_layers, full_layers=cfg.n_full_layers,
        window_layers=cfg.n_window_layers, attn_window=cfg.attn_window,
        moe={"held": cfg.held[1], "k": cfg.moe_k, "d_model": cfg.d_model,
             "d_ff": cfg.moe_d_ff, "sparse_layers": cfg.n_sparse_layers,
             "itemsize": jnp.dtype(cfg.dtype).itemsize},
        # device counters, pulled once, after the window (telemetry on)
        moe_counters=srv.read_expert_counters(),
        window_state_bytes=srv.cache.window_bytes,
        full_pool_bytes=srv.cache.num_blocks * bs
        * srv.cache.bytes_per_token)
    if res["run"]["moe_counters"]:
        ctx.say(info="moe_counters", **res["run"]["moe_counters"])
    # a stall names itself: the window's longest dispatches, which program
    # each was and what it carried
    ws, we = res["run"]["window"]
    longest = sorted((s for s in b["log"].spans if s[0].endswith("_dispatch")
                      and ws <= s[1] and s[2] <= we),
                     key=lambda s: s[1] - s[2])[:3]
    ctx.say(info="longest_dispatches", at_s_ms_name_value=[
        [s[1] - ws, 1e3 * (s[2] - s[1]), s[0], s[3]] for s in longest])
    limit = float(cell.config["check"]["served_off_share_limit"])
    sample = serve.sample_finished(res.pop("finished_in_window"), ctx.seed)
    state = (srv.cache.k, srv.cache.v)
    pad_to = cfg.max_seq_len
    del srv, b

    def after_window():
        """Once ``memory_peak_bytes`` has been read: frees both kinds of
        KV state and holds the sample's served tokens to the reference."""
        t = time.perf_counter()
        for s in state:
            s.delete()
        gaps = served_token_gaps(sample, params, cfg, cell.reference(),
                                 pad_to)
        ok, row, compared = judge_served(gaps, limit)
        ctx.say(info="correctness_after_window", requests=len(gaps),
                longest_request_tokens=max(
                    [len(r.prompt) + len(r.out) for r in sample] or [0]),
                gap_max_by_request={str(k): float(g.max())
                                    for k, g in gaps.items()},
                reference_s=time.perf_counter() - t, ok=ok, **row)
        return ok, compared

    res["after_window"] = after_window
    return res
