"""Driver for configurations of ``"kind": "serve_smallthinker"``: the
SmallThinker decoder (models/smallthinker.py: window-4,096 rotary layers
3 : 1 with position-free full layers, a router that reads the layer's input
before attention, ReLU-gated experts) as the first pipeline stage holds it,
through ``deepspeed_tpu.init_inference`` -> ``ServingEngine`` under a
``requests`` traffic mix.

The ramp, the measured window, its reduction and the sampling of finished
requests are ``drivers/serve.py``'s (loaded, not copied: ``drive``,
``sample_finished``); the three checks are ``drivers/serve_exaone_moe.py``'s
in form (PERF.md D14: an eighth driver), against
``benchmark/reference/smallthinker.py``:

1. the warm-up requests' logits (after the final prefill chunk and after
   every decoded token) against the reference FORCED to the program's
   selection at every token of the request, held to ``check.logit_tol_abs``;
2. the selection itself against the reference's own: wherever the two
   differ at a token, the reference's router LOGITS of the experts in
   dispute may lie no further apart than ``check.route_tie_eps``;
3. after the window, six of the requests it finished through the UNFORCED
   reference, prompt and served tokens together: the SHARE of served tokens
   whose logit lies below the reference's best, held to
   ``check.served_off_share_limit``; the widest gap is printed, not held.

What differs from that driver: the reference runs its head only at the
positions compared (a ``[16384, 151936]`` float32 logit table is 10 GB),
each request is padded to the next multiple of 4,096 and not to the model's
16,384, and every decode dispatch samples the rings' fill.
"""

import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from harness import cells
from harness import spans as spans_lib
from harness import traffic as traffic_lib
from harness import weights_smallthinker

_HERE = os.path.dirname(os.path.abspath(__file__))
serve = cells.load_module(os.path.join(_HERE, "serve.py"),
                          "bench_driver_serve_base")

# past the window AND over a chunk's edge; short of both
CHECK_REQUESTS = ((4200, 6), (300, 5))
CHECK_PAD = 4608                        # one reference shape for both
CHECK_HEAD = 8                          # positions the warm-up's head runs at
AFTER_PAD = 4096                        # served requests: next multiple


def model_config(conf, dtype):
    """The program's configuration from the file's keys as they are run."""
    from deepspeed_tpu.models import smallthinker
    n = int(conf["num_hidden_layers"])
    assert conf["moe_primary_router_apply_softmax"] and conf["norm_topk_prob"]
    return smallthinker.SmallThinkerConfig(
        vocab_size=int(conf["vocab_size"]), n_layers=n,
        n_heads=int(conf["num_attention_heads"]),
        n_kv_heads=int(conf["num_key_value_heads"]),
        d_model=int(conf["hidden_size"]), head_size=int(conf["head_dim"]),
        max_seq_len=int(conf["serving"]["max_total"]), dtype=dtype,
        attn_window=int(conf["sliding_window_size"]),
        layer_kinds=smallthinker.layer_kinds(
            conf["sliding_window_layout"], conf["rope_layout"], n),
        num_experts=int(conf["moe_num_primary_experts"]),
        moe_k=int(conf["moe_num_active_primary_experts"]),
        moe_d_ff=int(conf["moe_ffn_hidden_size"]),
        norm_eps=float(conf["rms_norm_eps"]),
        rope_theta=float(conf["rope_theta"]),
        use_flash_attention=False, remat=False)


def reference_hp(cfg):
    """The reference's plain numbers, from the same configuration."""
    return {"n_heads": cfg.n_heads, "n_kv_heads": cfg.kv_heads,
            "head_dim": cfg.head_dim, "window": cfg.attn_window,
            "kinds": tuple(cfg.layer_kinds), "num_experts": cfg.num_experts,
            "top_k": cfg.moe_k, "eps": cfg.norm_eps,
            "rope_theta": cfg.rope_theta}


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
    params = weights_smallthinker.smallthinker_params(
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
    # chunks: (end stamp, start, tokens) of every prefill dispatch; ring:
    # (end stamp, live slots, ring rows used, allocated) of every decode
    counts = {"prefill_tokens": [], "capture": None, "chunks": [],
              "ring": []}

    def on_dispatch(name, t0, t1, args, out):
        """As the base driver's: prompt tokens of a prefill chunk; (live
        slots, occupied full-layer blocks) of a decode. While capturing,
        also the logits and the dispatch's routing of the check requests."""
        cap = counts["capture"]
        if name == "prefill_dispatch":
            n, start = int(args[5]), int(args[4])
            counts["prefill_tokens"].append((t1, n))
            counts["chunks"].append((t1, start, n))
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
            counts["ring"].append((
                t1, int(sum(r is not None for r in srv.slots)),
                getattr(srv.cache, "ring_rows_used", None),
                getattr(srv.cache, "ring_rows_allocated", None)))
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
    for name in ("prefill_tokens", "chunks", "ring"):
        counts[name].clear()
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
    Ls, K = cfg.n_layers, cfg.moe_k
    tol, eps = float(limits["logit_tol_abs"]), float(limits["route_tie_eps"])
    worst, scale, agree, total = 0.0, 0.0, 0, 0
    disputed, worst_margin, routed = 0, 0.0, 0
    complete = all(r.state == "done" and len(r.out) == r.max_new_tokens
                   for r in check)
    for r in check:
        toks = np.concatenate([r.prompt, np.asarray(r.out, np.int32)])
        S = len(toks) - 1
        pad = max(CHECK_PAD, -(-S // 512) * 512) if S > 256 else S
        forced = -np.ones((Ls, pad, K), np.int32)
        for rid, start, sel in cap["route"]:
            if rid == r.rid and start < S:
                n = min(sel.shape[1], S - start)
                forced[:, start:start + n] = sel[:, :n]
        complete = complete and bool((forced[:, :S] >= 0).all())
        padded = np.zeros((pad,), np.int32)
        padded[:S] = toks[:-1]
        # the head runs at the emitted positions only
        head = min(CHECK_HEAD, pad)
        at = min(len(r.prompt) - 1, pad - head)
        ref, route = reference.logits(params, padded, hp, forced=forced,
                                      fp8=fp8, variant=variant,
                                      at=(at, head))
        ref = np.asarray(ref)
        # 2: the program's selection against the reference's own, a
        # disagreement measured in the reference's router logits
        own = np.asarray(route["sel"])[:, :S]
        biased = np.asarray(route["z"])[:, :S]
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
            want = ref[pos - at]
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


def served_token_gaps(reqs, params, cfg, reference, head, fp8=False,
                      chosen=None):
    """Check 3: for every served token of ``reqs``, how far its logit lies
    below the unforced reference's best at that position. Each request is
    padded to the next multiple of ``AFTER_PAD`` (the reference is causal;
    at most four shapes) and the head runs at ``head`` positions, the
    longest answer's. ``chosen(padded, at) -> logits [head, V]`` puts other
    tokens in the served ones' place (the control's).
    Returns {rid: float32 gaps}."""
    hp = reference_hp(cfg)
    out = {}
    for r in reqs:
        toks = np.concatenate([np.asarray(r.prompt, np.int32),
                               np.asarray(r.out, np.int32)])
        S = len(toks) - 1
        pad = min(-(-S // AFTER_PAD) * AFTER_PAD, cfg.max_seq_len)
        padded = np.zeros((pad,), np.int32)
        padded[:S] = toks[:-1]
        first = len(r.prompt) - 1
        at = (min(first, pad - head), head)
        ref, _ = reference.logits(params, padded, hp, fp8=fp8, at=at)
        rows = slice(first - at[0], S - at[0])
        served = jnp.asarray(toks[first + 1:]) if chosen is None \
            else jnp.argmax(chosen(padded, at)[rows], -1)
        gap = ref[rows].max(-1) - jnp.take_along_axis(
            ref[rows], served[:, None], -1)[:, 0]
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
        # what the window readers count from (harness/readers_window.py)
        window_attn={"heads": cfg.n_heads, "kv_heads": cfg.kv_heads,
                "head_dim": cfg.head_dim, "attn_window": cfg.attn_window,
                "window_layers": cfg.n_window_layers,
                "full_layers": cfg.n_full_layers,
                "itemsize": jnp.dtype(cfg.dtype).itemsize,
                "chunks": list(b["counts"]["chunks"]),
                "ring": list(b["counts"]["ring"])},
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
    head = min(int(cell.traffic["answer"]["max"]), cfg.max_seq_len)
    del srv, b

    def after_window():
        """Once ``memory_peak_bytes`` has been read: frees both kinds of
        KV state and holds the sample's served tokens to the reference."""
        t = time.perf_counter()
        for s in state:
            s.delete()
        gaps = served_token_gaps(sample, params, cfg, cell.reference(),
                                 head)
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
