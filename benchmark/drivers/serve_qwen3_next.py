"""Driver for configurations of ``"kind": "serve_qwen3_next"``:
Qwen3-Next-80B-A3B (Gated DeltaNet linear attention with one decay a head
in three layers of four, whose per-slot recurrent state lives beside the
two paged K/V pools of the output-gated full-attention layers; 512
softmax-routed experts and a gated shared one) as one chip's share (24 of
48 layers, 32 of 512 experts, an eighth of the vocabulary) of a 32-chip
deployment, through ``deepspeed_tpu.init_inference`` -> ``ServingEngine``
under a ``requests`` traffic mix.

The ramp, the measured window, its reduction and the draw of finished
requests are ``drivers/serve.py``'s, the judgement of the served tokens is
``drivers/serve_exaone_moe.py``'s, and the stalled-dispatch probe, the
after-window sample and the margin of a disputed routing decision are
``drivers/serve_dots_vlm.py``'s (all loaded, not copied: ``drive``,
``sample_finished``, ``judge_served``, ``host_probe``, ``sample_served``,
``_dispute_margin``); this file brings the model's configuration from the
file's published keys, its weights, the accounting of the caches and the
checks against the plain reference (``benchmark/reference/qwen3_next.py``).
The router has no bias, so nothing is balanced before the window.

**The checks** are Kimi-Linear's three (bf16 rounding swaps near-tied
routing decisions, so the program keeps its last dispatch's selection,
``LinearState.route``):

1. before the window, two requests, one of 2,300 + 6 tokens (four and a
   half prefill chunks of 512, two and a quarter of 1,024: the recurrent
   state and the convolution tail cross the chunk borders THROUGH the state
   buffer and then resume in decode; ``check_requests`` of the file where
   the chunk is larger) and one short; logits at every emitted token
   against the reference FORCED to the program's selection, the largest
   difference held to ``check.logit_tol_abs`` and the root mean square over
   every compared position and entry to ``check.logit_tol_rms`` (a mean
   repeats from seed to seed where a largest value does not: it is the
   limit a recurrent state rounded to bfloat16 fails);
2. every routing decision in dispute is held to a near-tie in the
   reference's router LOGITS (``check.route_tie_eps``);
3. after the window, three finished requests (the longest and two drawn
   from the seed) through the UNFORCED reference, held by the SHARE of
   served tokens that are not the reference's first
   (``check.served_off_share_limit``); the widest gap is printed, not held.

A run in which a request is preempted is not correct: the pool is sized so
that this traffic never preempts.
"""

import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from harness import cells
from harness import spans as spans_lib
from harness import traffic as traffic_lib
from harness import weights_qwen3_next

_HERE = os.path.dirname(os.path.abspath(__file__))
dots = cells.load_module(os.path.join(_HERE, "serve_dots_vlm.py"),
                         "bench_driver_serve_dots_vlm")
serve, exaone = dots.serve, dots.exaone

CHECK_REQUESTS = ((2300, 6), (300, 5))  # 4.5 chunks of 512; short
CHECK_PAD = 2560                        # one reference shape for both
SERVED_PAD = 24576                      # the after-window sample's shape
SERVED_ROWS = 1024                      # its head: the longest answer


def model_config(conf, dtype):
    """The program's configuration from the file's keys as they are run."""
    from deepspeed_tpu.models.qwen3_next import Qwen3NextConfig
    assert conf["model_type"] == "qwen3_next" and conf["hidden_act"] == "silu"
    assert conf["norm_topk_prob"] and conf["decoder_sparse_step"] == 1 \
        and not conf["mlp_only_layers"]
    assert conf["rope_scaling"] is None and not conf["tie_word_embeddings"] \
        and not conf["use_sliding_window"]
    assert conf["linear_key_head_dim"] == conf["linear_value_head_dim"]
    assert conf["shared_expert_intermediate_size"] \
        == conf["moe_intermediate_size"]
    return Qwen3NextConfig(
        vocab_size=int(conf["vocab_size"]),
        n_layers=int(conf["num_hidden_layers"]),
        n_heads=int(conf["num_attention_heads"]),
        n_kv_heads=int(conf["num_key_value_heads"]),
        head_size=int(conf["head_dim"]),
        d_model=int(conf["hidden_size"]), d_ff=int(conf["intermediate_size"]),
        max_seq_len=int(conf["serving"]["max_total"]), dtype=dtype,
        full_attention_interval=int(conf["full_attention_interval"]),
        linear_key_heads=int(conf["linear_num_key_heads"]),
        linear_value_heads=int(conf["linear_num_value_heads"]),
        linear_head_dim=int(conf["linear_key_head_dim"]),
        conv_kernel=int(conf["linear_conv_kernel_dim"]),
        rotary_dim=int(round(conf["partial_rotary_factor"]
                             * conf["head_dim"])),
        rope_theta=float(conf["rope_theta"]),
        num_experts=int(conf["published"]["num_experts"]),
        moe_k=int(conf["num_experts_per_tok"]),
        moe_d_ff=int(conf["moe_intermediate_size"]),
        experts_held=(int(conf["deployment_share"]["first_expert"]),
                      int(conf["num_experts"])),
        norm_eps=float(conf["rms_norm_eps"]),
        use_flash_attention=False, remat=False)


def reference_hp(cfg):
    """The reference's plain numbers, from the same configuration."""
    return {"kinds": tuple(int(k) for k in cfg.attn_kinds),
            "key_heads": cfg.linear_key_heads,
            "value_heads": cfg.linear_value_heads,
            "lin_dim": cfg.linear_head_dim, "taps": cfg.conv_kernel,
            "l2_eps": cfg.l2_eps, "n_heads": cfg.n_heads,
            "n_kv_heads": cfg.kv_heads, "head_dim": cfg.head_dim,
            "rotary_dim": cfg.rotary_dim, "rope_theta": cfg.rope_theta,
            "num_experts": cfg.num_experts, "top_k": cfg.moe_k,
            "held": tuple(cfg.held), "eps": cfg.norm_eps}


def base_driver_model(cfg):
    """What ``serve.drive`` reads of ``config["model"]`` (GPT-2's keys), so
    that its arithmetic comes out right here: the vocabulary to draw ids
    from, and the pools' bytes per block = 2 x n_layer x n_embd x 2 x block,
    with K and V rows of ``kv_heads x head_dim`` values a token in each of
    the ``n_full_layers`` attention layers."""
    return {"vocab_size": cfg.vocab_size, "n_layer": cfg.n_full_layers,
            "n_embd": cfg.kv_heads * cfg.head_dim, "n_head": cfg.kv_heads,
            "n_positions": cfg.max_seq_len}


def build(ctx):
    """Weights, engine, the two caches, instrumentation and the checked
    warm-up. Returns a dict of what ``run`` needs."""
    cell, say = ctx.cell, ctx.say
    if not os.path.exists(os.path.join(cell.root, "deepspeed_tpu", "models",
                                       "qwen3_next.py")):
        # a program from before PR 54: fail at once, before the 45 s import
        raise SystemExit("serve_qwen3_next: this checkout's program has no "
                         "qwen3_next configuration "
                         "(deepspeed_tpu/models/qwen3_next.py)")
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
    params = weights_qwen3_next.qwen3_next_params(
        ctx.seed, cfg, dtype, std=float(conf.get("weights_std", 0.02)),
        norm_std=float(conf.get("norm_std", 0.02)))
    jax.block_until_ready(params)
    setup["weights_s"] = clock() - t
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
        attention_layers=cfg.n_full_layers,
        linear_layers=cfg.n_recurrent_layers,
        kv_pool_bytes=srv.cache.num_blocks * bs * srv.cache.bytes_per_token,
        recurrent_state_bytes=srv.cache.recurrent_state_bytes,
        conv_tail_bytes=srv.cache.conv_tail_bytes,
        weight_bytes=int(sum(a.nbytes for a in jax.tree_util.tree_leaves(
            params))))

    log = spans_lib.SpanLog()
    counts = {"prefill_tokens": [], "capture": None, "stalls": []}

    def on_dispatch(name, t0, t1, args, out):
        """As the base driver's: prompt tokens of a prefill chunk, with the
        history it attended; (live slots, occupied blocks, K and V rows
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
                route = np.asarray(out[3].route)            # [Ls, C, k]
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
                route = np.asarray(out[3].route)            # [Ls, B, k]
                for s in np.flatnonzero(active):
                    r = srv.slots[s]
                    pos = len(r.prompt) + len(r.out) - 1
                    cap["logits"].append((r.rid, pos, lg[s].reshape(-1)))
                    cap["route"].append((r.rid, pos, route[:, s:s + 1]))
            # rows a full layer's kernel call reads: each live slot's
            # tokens and the one it has just written; a linear layer's call
            # rewrites the live slots' state
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
        ("warmup_rms_logit_error", detail["rms_logit_error"],
         float(conf["check"]["logit_tol_rms"])),
        ("warmup_route_worst_disagreement", detail["route_worst_margin"],
         float(conf["check"]["route_tie_eps"]))]
    return {"srv": srv, "log": log, "counts": counts, "setup": setup,
            "correct": ok, "compared": compared, "params": params,
            "cfg": cfg, "checked": (check, cap)}


def check_warmup(check, cap, params, cfg, reference, limits, pad=CHECK_PAD,
                 fp8=False, variant=()):
    """Checks 1 and 2 of the module docstring on the warm-up requests."""
    hp = reference_hp(cfg)
    Ls, K = cfg.n_sparse_layers, cfg.moe_k
    tol, eps = float(limits["logit_tol_abs"]), float(limits["route_tie_eps"])
    tol_rms = float(limits["logit_tol_rms"])
    worst, scale, agree, total, squares = 0.0, 0.0, 0, 0, 0.0
    disputed, worst_margin, routed = 0, 0.0, 0
    complete = all(r.state == "done" and len(r.out) == r.max_new_tokens
                   for r in check)
    for r in check:
        toks = np.concatenate([r.prompt, np.asarray(r.out, np.int32)])
        S = len(toks) - 1
        width = max(pad, S)
        forced = -np.ones((Ls, width, K), np.int32)
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
        group = np.asarray(route["group"])[:, :S]
        mine = np.sort(forced[:, :S], -1)
        theirs = np.sort(own, -1)
        differ = (mine != theirs).any(-1)                    # [Ls, S]
        routed += differ.size
        for l, t in zip(*np.nonzero(differ)):
            margin, _ = dots._dispute_margin(cfg, mine[l, t], theirs[l, t],
                                             biased[l, t], group[l, t])
            worst_margin = max(worst_margin, margin)
            disputed += 1
        # 1: logits at every emitted token, selection forced
        served = {pos: lg for rid, pos, lg in cap["logits"] if rid == r.rid}
        complete = complete and sorted(served) == list(range(
            len(r.prompt) - 1, len(toks) - 1))
        for pos, lg in served.items():
            want = ref[pos]
            worst = max(worst, float(np.max(np.abs(lg - want))))
            squares += float(np.mean(np.square(lg - want, dtype=np.float64)))
            scale = max(scale, float(np.max(np.abs(want))))
            agree += int(np.argmax(lg) == np.argmax(want))
            complete = complete and int(np.argmax(lg)) == int(toks[pos + 1])
            total += 1
    # over every compared position and vocabulary entry: a mean, so it
    # repeats from seed to seed where the largest value does not, and a
    # noise added to every token (a rounded state) shows in it
    rms = float(np.sqrt(squares / max(total, 1)))
    ok = bool(complete and total > 0 and worst < tol and rms < tol_rms
              and worst_margin <= eps)
    return ok, {"requests": len(check), "positions_compared": total,
                "max_abs_logit_error": worst, "tolerance": tol,
                "rms_logit_error": rms, "tolerance_rms": tol_rms,
                "largest_reference_logit": scale,
                "argmax_agreement_with_reference": agree / max(total, 1),
                "route_decisions_compared": routed,
                "route_decisions_disputed": disputed,
                "route_worst_margin": worst_margin, "route_tie_eps": eps,
                "every_token_has_logits_routes_and_is_their_argmax":
                    bool(complete), "ok": ok}


def served_token_gaps(reqs, params, cfg, reference, pad_to, rows, fp8=False,
                      variant=(), chosen=None):
    """Check 3: for every served token of ``reqs``, how far its logit lies
    below the unforced reference's best at that position. One padded shape
    (the reference is causal) and one head of ``rows`` positions from each
    request's first answer position on. ``chosen(padded, first, end)``
    puts other tokens in the served ones' place (the control's).
    Returns {rid: float32 gaps}."""
    hp = reference_hp(cfg)
    out = {}
    for r in reqs:
        toks = np.concatenate([np.asarray(r.prompt, np.int32),
                               np.asarray(r.out, np.int32)])
        padded = np.zeros((max(pad_to, len(toks) - 1),), np.int32)
        padded[:len(toks) - 1] = toks[:-1]
        first = len(r.prompt) - 1
        n = max(rows, len(r.out))
        start = min(first, len(padded) - n)
        ref, _ = reference.logits(params, padded, hp, fp8=fp8,
                                  variant=variant, first=start, rows=n,
                                  with_route=False)
        at = ref[first - start:first - start + len(r.out)]
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
    state_bytes = srv.cache.recurrent_state_bytes
    res["run"].update(
        layers=cfg.n_layers,
        gdn={"heads": cfg.linear_value_heads, "key_heads":
             cfg.linear_key_heads, "head_dim": cfg.linear_head_dim,
             "layers": cfg.n_recurrent_layers, "state_itemsize": 4,
             "attn_heads": cfg.n_heads, "kv_heads": cfg.kv_heads,
             "attn_head_dim": cfg.head_dim,
             "attention_layers": cfg.n_full_layers, "itemsize": itemsize,
             "recurrent_state_bytes": state_bytes,
             "conv_tail_bytes": srv.cache.conv_tail_bytes},
        # the keys ssm_state_share reads (harness/readers_ssm.py): a
        # recurrent state beside the occupied K and V rows, as Jamba's
        ssm={"recurrent_state_bytes": state_bytes,
             "kv_bytes_per_block": bs * srv.cache.bytes_per_token},
        moe={"held": cfg.held[1], "k": cfg.moe_k, "d_model": cfg.d_model,
             "d_ff": cfg.moe_d_ff, "sparse_layers": cfg.n_sparse_layers,
             "itemsize": itemsize},
        # device counters, pulled once, after the window (telemetry on)
        moe_counters=srv.read_expert_counters())
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
    rows = int(ctx.rehearsal.get("served_rows", SERVED_ROWS))
    sample = dots.sample_served(res.pop("finished_in_window"), ctx.seed,
                                pad_to, pad_to)
    pools = srv.cache.pools
    del srv, b

    def after_window():
        """Once ``memory_peak_bytes`` has been read: frees the pools and
        the recurrent state and holds the sample's served tokens to the
        reference."""
        t = time.perf_counter()
        for p in pools:
            if p is not None:
                p.delete()
        gaps = served_token_gaps(sample, params, cfg, cell.reference(),
                                 pad_to, rows)
        ok, row, compared = exaone.judge_served(gaps, limit)
        ctx.say(info="correctness_after_window", requests=len(gaps),
                request_tokens=[len(r.prompt) + len(r.out) for r in sample],
                gap_max_by_request={str(k): float(g.max())
                                    for k, g in gaps.items()},
                reference_s=time.perf_counter() - t, ok=ok, **row)
        return ok, compared

    res["after_window"] = after_window
    return res
