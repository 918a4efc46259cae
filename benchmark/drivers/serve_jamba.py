"""Driver for configurations of ``"kind": "serve_jamba"``: AI21-Jamba2-3B
(Mamba-1 state-space mixers in 26 of 28 layers, whose per-slot float32 state
and convolution tail live beside the two paged K/V pools of the 2 un-rotated
single-KV-head attention layers; a dense SwiGLU behind every mixer) WHOLE on
one chip, through ``deepspeed_tpu.init_inference`` -> ``ServingEngine``
under a ``requests`` traffic mix.

The ramp, the measured window, its reduction and the draw of finished
requests are ``drivers/serve.py``'s, the judgement of the served tokens is
``drivers/serve_exaone_moe.py``'s, and the stalled-dispatch probe and the
after-window sample are ``drivers/serve_dots_vlm.py``'s (all loaded, not
copied: ``drive``, ``sample_finished``, ``judge_served``, ``host_probe``,
``sample_served``); this file brings the model's configuration from the
file's published keys, its weights, the accounting of the caches and the
checks against the plain reference (``benchmark/reference/jamba.py``).

**The checks** are Kimi-Linear's without the routing parts (no router
here), on what the timed path produced at the timed sizes:

1. before the window, two requests, one of 2,300 + 6 tokens (four and a
   half prefill chunks: the recurrent state and the convolution tail cross
   four chunk borders THROUGH the state buffer and then resume in decode)
   and one short; logits at every emitted token against the reference, held
   to ``check.logit_tol_abs``;
2. after the window, three finished requests (the longest and two drawn
   from the seed) through the reference, prompt and served tokens together:
   the SHARE of served tokens that lie more than ``check.served_tie_eps``
   below the reference's first (not its choice, outside a near-tie) is held
   to ``check.served_off_share_limit``; the widest gap is printed, not
   held.
"""

import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from harness import cells
from harness import spans as spans_lib
from harness import traffic as traffic_lib
from harness import weights_jamba

_HERE = os.path.dirname(os.path.abspath(__file__))
dots = cells.load_module(os.path.join(_HERE, "serve_dots_vlm.py"),
                         "bench_driver_serve_dots_vlm")
serve, exaone = dots.serve, dots.exaone

CHECK_REQUESTS = ((2300, 6), (300, 5))  # 4.5 chunks of 512; short
CHECK_PAD = 2560                        # one reference shape for both
SERVED_PAD = 12288                      # the after-window sample's shape
SERVED_ROWS = 4096                      # its head: the longest answer


def model_config(conf, dtype):
    """The program's configuration from the file's keys as they are run."""
    from deepspeed_tpu.models.jamba import JambaConfig
    assert conf["model_type"] == "jamba" and conf["hidden_act"] == "silu"
    assert conf["num_experts"] == 1 and conf["tie_word_embeddings"]
    assert conf["sliding_window"] is None and conf["mamba_conv_bias"] \
        and not conf["mamba_proj_bias"]
    return JambaConfig(
        vocab_size=int(conf["vocab_size"]),
        n_layers=int(conf["num_hidden_layers"]),
        n_heads=int(conf["num_attention_heads"]),
        n_kv_heads=int(conf["num_key_value_heads"]),
        d_model=int(conf["hidden_size"]),
        d_ff=int(conf["intermediate_size"]),
        max_seq_len=int(conf["serving"]["max_total"]), dtype=dtype,
        attn_layer_period=int(conf["attn_layer_period"]),
        attn_layer_offset=int(conf["attn_layer_offset"]),
        mamba_expand=int(conf["mamba_expand"]),
        mamba_d_state=int(conf["mamba_d_state"]),
        mamba_dt_rank=int(conf["mamba_dt_rank"]),
        conv_kernel=int(conf["mamba_d_conv"]),
        norm_eps=float(conf["rms_norm_eps"]),
        use_flash_attention=False, remat=False)


def reference_hp(cfg):
    """The reference's plain numbers, from the same configuration."""
    return {"kinds": tuple(int(k) for k in cfg.attn_kinds),
            "n_heads": cfg.n_heads, "n_kv_heads": cfg.kv_heads,
            "d_state": cfg.mamba_d_state, "dt_rank": cfg.mamba_dt_rank,
            "eps": cfg.norm_eps}


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
    """Weights, engine, the caches, instrumentation and the checked
    warm-up. Returns a dict of what ``run`` needs."""
    cell, say = ctx.cell, ctx.say
    if not os.path.exists(os.path.join(cell.root, "deepspeed_tpu", "models",
                                       "jamba.py")):
        # a program from before PR 42: fail at once, before the 45 s import
        raise SystemExit("serve_jamba: this checkout's program has no "
                         "jamba dialect (deepspeed_tpu/models/jamba.py)")
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
    params = weights_jamba.jamba_params(
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
    jax.block_until_ready(srv.cache.k)
    setup["engine_s"] = clock() - t
    bs = srv.cache.block_size
    say(info="serving_engine", decode_impl=srv.decode_impl,
        num_slots=srv.num_slots, pool_blocks=srv.cache.num_blocks - 1,
        block_size=bs, prefill_chunk=srv.prefill_chunk,
        attention_layers=cfg.n_full_layers,
        state_space_layers=cfg.n_recurrent_layers,
        kv_pool_bytes=srv.cache.num_blocks * bs * srv.cache.bytes_per_token,
        recurrent_state_bytes=srv.cache.recurrent_state_bytes,
        recurrent_state_shape=list(srv.cache.k.state.shape),
        conv_tail_bytes=srv.cache.conv_tail_bytes,
        weight_bytes=int(sum(a.nbytes for a in jax.tree_util.tree_leaves(
            params))))

    log = spans_lib.SpanLog()
    counts = {"prefill_tokens": [], "capture": None, "stalls": []}

    def on_dispatch(name, t0, t1, args, out):
        """As the base driver's: prompt tokens of a prefill chunk, with the
        history it attended; (live slots, occupied blocks, K and V rows
        read) of a decode. While capturing, also the logits of the check
        requests."""
        cap = counts["capture"]
        if t1 - t0 > dots.STALL_S and cap is None:
            counts["stalls"].append({"name": name, "t0": t0,
                                     "ms": 1e3 * (t1 - t0),
                                     "host": dots.host_probe()})
        if name == "prefill_dispatch":
            n, start = int(args[5]), int(args[4])
            counts["prefill_tokens"].append((t1, n))
            if cap is not None:
                for s, r in enumerate(srv.slots):
                    if r is not None and r.state == "prefill" \
                            and np.array_equal(srv.cache.tables[s], args[2]) \
                            and start + n == len(r.prompt):
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
                for s in np.flatnonzero(active):
                    r = srv.slots[s]
                    cap["logits"].append((r.rid, len(r.prompt) + len(r.out)
                                          - 1, lg[s].reshape(-1)))
            # rows an attention layer's kernel call reads: each live slot's
            # tokens and the one it has just written; a state-space layer's
            # call rewrites the live slots' state
            return (int(active.sum()), blocks,
                    int((lengths[active] + 1).sum()))
        return None

    spans_lib.instrument_serving(srv, log, on_dispatch)

    # ---- warm-up that is also the correctness sample ---------------------
    t = clock()
    counts["capture"] = cap = {"logits": []}
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
    compared = [("warmup_max_abs_logit_error", detail["max_abs_logit_error"],
                 float(conf["check"]["logit_tol_abs"]))]
    return {"srv": srv, "log": log, "counts": counts, "setup": setup,
            "correct": ok, "compared": compared, "params": params,
            "cfg": cfg, "checked": (check, cap)}


def check_warmup(check, cap, params, cfg, reference, limits, pad=CHECK_PAD,
                 fp8=False, variant=()):
    """Check 1 of the module docstring on the warm-up requests."""
    hp = reference_hp(cfg)
    tol = float(limits["logit_tol_abs"])
    worst, scale, agree, total = 0.0, 0.0, 0, 0
    complete = all(r.state == "done" and len(r.out) == r.max_new_tokens
                   for r in check)
    # one compiled reference for both requests: the head over the longest
    # answer's rows, from each request's own first
    n_rows = max(len(r.out) for r in check)
    for r in check:
        toks = np.concatenate([r.prompt, np.asarray(r.out, np.int32)])
        S = len(toks) - 1
        padded = np.zeros((max(pad, S),), np.int32)
        padded[:S] = toks[:-1]
        first = len(r.prompt) - 1
        start = min(first, len(padded) - n_rows)
        ref = np.asarray(reference.logits(
            params, padded, hp, fp8=fp8, variant=variant, first=start,
            rows=n_rows))[first - start:][:len(r.out)]
        served = {pos: lg for rid, pos, lg in cap["logits"] if rid == r.rid}
        complete = complete and sorted(served) == list(range(
            first, len(toks) - 1))
        for pos, lg in served.items():
            want = ref[pos - first]
            # a NaN (a control that blows up) is an error past any limit
            err = float(np.max(np.abs(lg - want)))
            worst = max(worst, err if np.isfinite(err) else float("inf"))
            scale = max(scale, float(np.max(np.abs(want))))
            agree += int(np.argmax(lg) == np.argmax(want))
            complete = complete and int(np.argmax(lg)) == int(toks[pos + 1])
            total += 1
    ok = bool(complete and total > 0 and worst < tol)
    return ok, {"requests": len(check), "positions_compared": total,
                "max_abs_logit_error": worst, "tolerance": tol,
                "largest_reference_logit": scale,
                "argmax_agreement_with_reference": agree / max(total, 1),
                "every_token_has_logits_and_is_their_argmax": bool(complete),
                "ok": ok}


def served_token_gaps(reqs, params, cfg, reference, pad_to, rows,
                      fp8=False, variant=(), chosen=None):
    """Check 2: for every served token of ``reqs``, how far its logit lies
    below the reference's best at that position. One padded shape (the
    reference is causal) and one head of ``rows`` positions from each
    request's first answer position on. ``chosen(padded, first, end)``
    puts other tokens in the served ones' place (the control's). Returns
    {rid: float32 gaps}."""
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
        ref = reference.logits(params, padded, hp, fp8=fp8, variant=variant,
                               first=start, rows=n)
        at = ref[first - start:first - start + len(r.out)]
        served = jnp.asarray(toks[first + 1:]) if chosen is None \
            else chosen(padded, first, len(toks) - 1)
        gap = at.max(-1) - jnp.take_along_axis(at, served[:, None], -1)[:, 0]
        out[r.rid] = np.asarray(gap, np.float32)
    return out


def judge_served(gaps, limits):
    """``exaone.judge_served`` on the gaps beyond a near-tie: a served
    token within ``served_tie_eps`` of the reference's first is its
    choice."""
    eps = float(limits["served_tie_eps"])
    ok, row, compared = exaone.judge_served(
        {k: np.maximum(g - eps, 0.0) for k, g in gaps.items()},
        float(limits["served_off_share_limit"]))
    allg = np.concatenate(list(gaps.values())) if gaps else np.zeros((0,))
    row.update(served_tie_eps=eps,
               served_not_first_share=float((allg > 0).mean())
               if allg.size else float("nan"),
               served_gap_max=float(allg.max()) if allg.size
               else float("nan"))
    return ok, row, compared


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
    bs = srv.cache.block_size
    res["run"].update(
        layers=cfg.n_layers,
        ssm={"d_inner": cfg.d_inner, "d_state": cfg.mamba_d_state,
             "layers": cfg.n_recurrent_layers, "state_itemsize": 4,
             "heads": cfg.n_heads, "kv_heads": cfg.kv_heads,
             "head_dim": cfg.head_dim, "attention_layers": cfg.n_full_layers,
             "itemsize": jnp.dtype(cfg.dtype).itemsize,
             "recurrent_state_bytes": srv.cache.recurrent_state_bytes,
             "conv_tail_bytes": srv.cache.conv_tail_bytes,
             "kv_bytes_per_block": bs * srv.cache.bytes_per_token})
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
        ok, row, compared = judge_served(gaps, cell.config["check"])
        ctx.say(info="correctness_after_window", requests=len(gaps),
                request_tokens=[len(r.prompt) + len(r.out) for r in sample],
                gap_max_by_request={str(k): float(g.max())
                                    for k, g in gaps.items()},
                reference_s=time.perf_counter() - t, ok=ok, **row)
        return ok, compared

    res["after_window"] = after_window
    return res
