"""On-chip inference benchmark: prefill + decode throughput/latency.

The reference's inference headline is kernel-injection latency speedups
(ref: deepspeed/inference/engine.py + docs/_tutorials/inference-tutorial.md
"2.3x faster GPT-2 latency on 1 GPU"). TPU analog measured here:

- prefill: tokens/s through the fused flash-prefill program;
- decode (host loop): per-token latency of the compiled, cache-donating
  decode step — pays one host round-trip per token;
- decode (fused): per-token latency inside `generate_fused` (the whole
  loop is ONE lax.scan program — the host round-trip amortizes away,
  which is the TPU-native answer to the reference's fused-kernel claim);
- feature matrix timings: GQA cache, sliding-window cache.

One JSON line per (config, mode), every configuration in this one
process. ``main`` needs a TPU and exits non-zero without one, and a
configuration that fails fails the run. The ``-smoke`` rows are imported
by tests and tools/gate.sh on the CPU for their counts only.

Usage: python tools/infer_bench.py
"""

import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, ".")


def bench_config(name, preset, batch, prompt_len, new_tokens,
                 n_kv_heads=None, attn_window=None, int8=False,
                 int8_fused=False):
    from deepspeed_tpu.models import gpt
    import deepspeed_tpu

    on_tpu = "tpu" in (jax.devices()[0].platform +
                       jax.devices()[0].device_kind).lower()
    cfg = gpt.preset(preset, max_seq_len=prompt_len + new_tokens + 8,
                     dtype=jnp.bfloat16, use_flash_attention=on_tpu,
                     n_kv_heads=n_kv_heads, attn_window=attn_window)
    if int8_fused:
        os.environ["DS_INT8_FUSED"] = "1"
    else:
        os.environ.pop("DS_INT8_FUSED", None)
    if on_tpu:
        # refuse borderline-HBM compiles before any backend contact
        # (utils/hbm.py, PERF.md incident log)
        from deepspeed_tpu.utils import hbm
        hbm.guard_infer_config(cfg, batch, cfg.max_seq_len)
    params = gpt.init_params(jax.random.PRNGKey(0), cfg)
    eng = deepspeed_tpu.init_inference(
        model=(cfg, params),
        dtype=jnp.int8 if int8 else jnp.bfloat16)

    toks = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (batch, prompt_len)).astype(np.int32)

    # warmup at the MEASURED lengths: the fused scan executable is keyed
    # on n_steps, so a shorter warmup would leave the full compile inside
    # the timed call
    eng.generate(toks, max_new_tokens=new_tokens)
    eng.generate_fused(toks, max_new_tokens=new_tokens)

    # measured pass — report the engine's own per-token latencies, which
    # exclude prefill and compile by construction
    eng.generate(toks, max_new_tokens=new_tokens)
    host_ms = eng.latency_ms["decode_per_token"]
    eng.generate_fused(toks, max_new_tokens=new_tokens)
    fused_ms = eng.latency_ms["decode_per_token_fused"]

    print(json.dumps({
        "config": name, "preset": preset, "batch": batch,
        "prompt_len": prompt_len, "new_tokens": new_tokens,
        "prefill_ms": round(eng.latency_ms.get("prefill", 0.0), 2),
        "decode_ms_per_token_hostloop": round(host_ms, 3),
        "decode_ms_per_token_fused": round(fused_ms, 3),
        "fused_speedup": round(host_ms / max(fused_ms, 1e-9), 2),
        "decode_tokens_per_s_fused": round(batch * 1e3 / fused_ms, 1),
    }), flush=True)


CONFIGS = [
    ("gpt2-medium-b8", dict(preset="gpt2-medium", batch=8,
                            prompt_len=512, new_tokens=64)),
    ("gpt2-medium-b32", dict(preset="gpt2-medium", batch=32,
                             prompt_len=512, new_tokens=64)),
    ("gpt2-large-b8", dict(preset="gpt2-large", batch=8,
                           prompt_len=512, new_tokens=64)),
    ("medium-gqa4", dict(preset="gpt2-medium", batch=8, prompt_len=512,
                         new_tokens=64, n_kv_heads=4)),
    ("medium-window256", dict(preset="gpt2-medium", batch=8,
                              prompt_len=512, new_tokens=64,
                              attn_window=256)),
    # weight-only int8: kernels at 1 byte/param — decode is HBM-bound
    # on weight reads, so this targets the reference's int8 inference
    # claim (vs the bf16 gpt2-medium-b8 row)
    ("gpt2-medium-b8-int8", dict(preset="gpt2-medium", batch=8,
                                 prompt_len=512, new_tokens=64,
                                 int8=True)),
    # same row through the Pallas fused dequant-matmul (VERDICT r4 weak
    # #6): if XLA's dequant fusion already recovers the bandwidth win
    # this ties the row above; if not, this is the shipping fallback
    ("gpt2-medium-b8-int8-fused", dict(preset="gpt2-medium", batch=8,
                                       prompt_len=512, new_tokens=64,
                                       int8=True, int8_fused=True)),
]


def bench_speculative(name, target_preset, draft_preset, batch,
                      prompt_len, new_tokens, gamma):
    """Speculative vs plain greedy decode on the same target: wall-clock
    tokens/s for identical output (the greedy exactness contract)."""
    from deepspeed_tpu.models import gpt
    import deepspeed_tpu
    from deepspeed_tpu.inference.speculative import generate_speculative

    on_tpu = "tpu" in (jax.devices()[0].platform +
                       jax.devices()[0].device_kind).lower()
    mk = lambda preset: gpt.preset(
        preset, max_seq_len=prompt_len + new_tokens + gamma + 8,
        dtype=jnp.bfloat16, use_flash_attention=on_tpu)
    cfg_t, cfg_d = mk(target_preset), mk(draft_preset)
    if on_tpu:
        # BOTH engines are resident simultaneously: guard the SUM of
        # their footprints, not each alone
        from deepspeed_tpu.utils import hbm
        est = hbm.estimate_infer_bytes(cfg_t, batch, cfg_t.max_seq_len)
        est_d = hbm.estimate_infer_bytes(cfg_d, batch, cfg_d.max_seq_len)
        for k, v in est_d.contributions.items():
            est.contributions[f"draft_{k}"] = v
        hbm._guard(est, None, hbm.DEFAULT_HEADROOM_GIB)
    t_eng = deepspeed_tpu.init_inference(
        model=(cfg_t, gpt.init_params(jax.random.PRNGKey(0), cfg_t)),
        dtype=jnp.bfloat16)
    d_eng = deepspeed_tpu.init_inference(
        model=(cfg_d, gpt.init_params(jax.random.PRNGKey(1), cfg_d)),
        dtype=jnp.bfloat16)
    toks = np.random.default_rng(0).integers(
        0, cfg_t.vocab_size, (batch, prompt_len)).astype(np.int32)
    # warmup both paths (compiles)
    t_eng.generate(toks, max_new_tokens=new_tokens)
    generate_speculative(t_eng, d_eng, toks, max_new_tokens=new_tokens,
                         gamma=gamma)
    t0 = time.perf_counter()
    ref = t_eng.generate(toks, max_new_tokens=new_tokens)
    plain_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    got, stats = generate_speculative(t_eng, d_eng, toks,
                                      max_new_tokens=new_tokens,
                                      gamma=gamma, return_stats=True)
    spec_s = time.perf_counter() - t0
    print(json.dumps({
        "config": name, "target": target_preset, "draft": draft_preset,
        "batch": batch, "gamma": gamma, "output_identical":
        bool((got == ref).all()),
        "plain_tokens_per_s": round(batch * new_tokens / plain_s, 1),
        "spec_tokens_per_s": round(batch * new_tokens / spec_s, 1),
        "speedup": round(plain_s / spec_s, 2),
        "accepted_per_round": round(stats["accepted_per_round"], 2),
    }), flush=True)


SPEC_CONFIGS = [
    ("spec-large-from-small", dict(target_preset="gpt2-large",
                                   draft_preset="gpt2-small", batch=1,
                                   prompt_len=128, new_tokens=64,
                                   gamma=4)),
]


def bench_serving(name, preset=None, num_requests=16, mean_gap_steps=2.0,
                  prompt_lens=(8, 48), new_tokens=24, num_slots=4,
                  block_size=16, num_blocks=None, prefill_chunk=32,
                  int8=False, int8_fused=False, seed=0, decode_impl=None,
                  prefix_cache=None, shared_prefix_len=0,
                  spec_decode=None, spec_k=None, kv_quant=None,
                  host_tier=None, host_budget_bytes=None,
                  spill_watermark=None, prefix_families=1,
                  temperature=0.0, top_p=1.0, sample_seed=0,
                  decode_horizon=None, chip_peak_flops=None, emit=True):
    """Continuous-batching serving row: synthetic Poisson arrivals driven
    through ServingEngine.step, wall-clock tokens/s, TTFT/TPOT latency
    percentiles from the telemetry registry's histograms, decode-slot
    utilization, and the paged-vs-static KV HBM accounting.

    Arrivals are in SCHEDULER-STEP units (deterministic under ``seed``):
    request i is submitted before the first step >= its exponential-gap
    cumsum. ``preset=None`` runs a CPU-smoke-sized model.

    ``decode_impl`` pins the paged attention path ("gather" | "pallas",
    None = platform default); every row reports which one actually ran
    plus the analytic cache HBM traffic per decoded token for that path
    (the gather path moves the whole virtual cache 3x; pallas reads only
    occupied blocks, once). Returns the row dict so the impl-comparison
    row can reuse it (``emit=False`` suppresses the JSON line).

    ``shared_prefix_len`` > 0 prepends a fixed system prompt to every
    request (the shared-prefix workload); ``prefix_cache`` pins the
    shared-prefix KV cache on/off (None = ``DS_PREFIX_CACHE``). Rows
    report ``prefix_hit_rate``/``prefix_tokens_saved``/``prefill_chunks``
    so the on/off comparison shows the prefill work the cache removes.

    ``spec_decode``/``spec_k`` pin speculative decoding inside the batch
    (None = ``DS_SPEC_DECODE``/``DS_SPEC_K``); rows report the registry-
    sourced ``accept_rate`` (drafts the target agreed with) and
    ``tokens_per_step`` (emitted per slot per verify step — the
    speculative speedup factor; 1.0 with speculation off).

    ``kv_quant`` pins int8 KV-cache block quantization ("int8" | "off",
    None = ``DS_KV_QUANT``). The HBM columns are derived from the
    ACTUAL pool dtype plus the per-block scale overhead, and
    ``slots_admittable`` reports how many decode slots the unquantized
    pool's HBM budget admits at the row's pool layout — the capacity-
    per-chip headline (~2x for int8 over bf16).

    ``host_tier`` pins the host-DRAM KV second tier on/off (None =
    ``DS_KV_HOST_TIER``); ``prefix_families`` > 1 rotates requests
    through that many DISTINCT system prompts in two passes each, so a
    family's chain goes cold between visits — at a constrained
    ``num_blocks`` the device-only cache must evict it, while the host
    tier spills and restores it (``spill_watermark`` pins the daemon's
    pressure threshold). Rows report the host transfer counters.

    ``decode_horizon`` pins the fused multi-step decode horizon N
    (None = ``DS_DECODE_HORIZON``, docs/MULTISTEP.md); rows split
    ``ms_per_token`` into ``host_ms_per_token`` vs
    ``device_ms_per_token`` (device = the seconds the engine was blocked
    on a program, its ``serving_dispatch_wait_seconds_total``; host =
    the rest of the wall time: enqueue, pull and the scheduler's loop)
    so the ~N× host amortization is visible even on CPU.

    ``temperature``/``top_p`` > defaults turn the drive into a SAMPLED
    workload (every request seeded ``sample_seed + rid``, so a row is
    reproducible run-to-run); rows report ``sampled``/``temperature``/
    ``top_p`` plus the ``sampled_tokens`` counter, and the fused
    in-program sampler keeps the compile/latency profile of the greedy
    drive (docs/SAMPLING.md).
    """
    from deepspeed_tpu.models import gpt
    import deepspeed_tpu
    from deepspeed_tpu.inference.serving import ServeRequest, ServingEngine
    from deepspeed_tpu.telemetry import Telemetry

    on_tpu = "tpu" in (jax.devices()[0].platform +
                       jax.devices()[0].device_kind).lower()
    max_seq = prompt_lens[1] + shared_prefix_len + new_tokens + 8
    if preset:
        cfg = gpt.preset(preset, max_seq_len=max_seq, dtype=jnp.bfloat16,
                         use_flash_attention=on_tpu)
    else:
        cfg = gpt.GPTConfig(vocab_size=512, n_layers=4, n_heads=8,
                            d_model=256, max_seq_len=max_seq,
                            use_flash_attention=False, remat=False,
                            dtype=jnp.float32)
    if int8_fused:
        os.environ["DS_INT8_FUSED"] = "1"
    else:
        os.environ.pop("DS_INT8_FUSED", None)
    act_dtype = jnp.bfloat16 if on_tpu else jnp.float32
    eng = deepspeed_tpu.init_inference(
        model=(cfg, gpt.init_params(jax.random.PRNGKey(0), cfg)),
        dtype=jnp.int8 if int8 else act_dtype)
    # telemetry on for the timed drive: the latency columns come from
    # the registry's TTFT/TPOT histograms (scheduler clock = perf_counter
    # seconds here), not from ad-hoc timestamp lists
    srv = ServingEngine(eng, num_slots=num_slots, block_size=block_size,
                        num_blocks=num_blocks, prefill_chunk=prefill_chunk,
                        decode_impl=decode_impl, prefix_cache=prefix_cache,
                        spec_decode=spec_decode, spec_k=spec_k,
                        kv_quant=kv_quant, host_tier=host_tier,
                        host_budget_bytes=host_budget_bytes,
                        spill_watermark=spill_watermark,
                        decode_horizon=decode_horizon,
                        telemetry=Telemetry())

    rng = np.random.default_rng(seed)
    arrive = np.floor(np.cumsum(
        rng.exponential(mean_gap_steps, num_requests))).astype(int)
    # the shared-prefix workload: requests open with a deterministic
    # system prompt (independent of the tail rng stream). family 0 is
    # bit-identical to the single-family formula; prefix_families > 1
    # rotates groups A A.. B B.. A A.. so chains go cold between visits
    if not shared_prefix_len:
        fams = None
    elif prefix_families <= 1:
        fams = [(1 + np.arange(shared_prefix_len)
                 % (cfg.vocab_size - 1)).astype(np.int32)]
    else:
        fams = [((1 + 131 * f + np.arange(shared_prefix_len))
                 % (cfg.vocab_size - 1)).astype(np.int32)
                for f in range(prefix_families)]
    group = max(1, -(-num_requests // (2 * max(1, prefix_families))))

    def mk_prompt(i):
        tail = rng.integers(0, cfg.vocab_size,
                            rng.integers(*prompt_lens)).astype(np.int32)
        if fams is None:
            return tail
        sys_prompt = fams[(i // group) % len(fams)]
        return np.concatenate([sys_prompt, tail])

    reqs = [ServeRequest(rid=i, prompt=mk_prompt(i),
                         max_new_tokens=new_tokens,
                         temperature=temperature, top_p=top_p,
                         seed=sample_seed + i)
            for i in range(num_requests)]

    # warmup: compile both slot programs before the timed drive
    w = ServingEngine(eng, num_slots=num_slots, block_size=block_size,
                      num_blocks=num_blocks, prefill_chunk=prefill_chunk,
                      decode_impl=decode_impl, prefix_cache=prefix_cache,
                      spec_decode=spec_decode, spec_k=spec_k,
                      kv_quant=kv_quant, host_tier=host_tier,
                      host_budget_bytes=host_budget_bytes,
                      spill_watermark=spill_watermark,
                      decode_horizon=decode_horizon)
    w.run([ServeRequest(rid="w", prompt=reqs[0].prompt.copy(),
                        max_new_tokens=2)])

    # device time is the registry's count of the seconds blocked in
    # serve.dispatch.wait (telemetry is on): a counter over the engine's
    # lifetime, so a drive bills itself the DELTA
    waited = srv.metrics.counter("serving_dispatch_wait_seconds_total")
    dev0 = waited.value
    t0 = time.perf_counter()
    step = 0
    nxt = 0
    while nxt < num_requests or srv.busy:
        while nxt < num_requests and arrive[nxt] <= step:
            srv.submit(reqs[nxt], now=time.perf_counter())
            nxt += 1
        srv.step(now=time.perf_counter())
        step += 1
    wall_s = time.perf_counter() - t0
    device_s = waited.value - dev0

    ttft_h = srv.metrics.histogram("serving_ttft")
    tpot_h = srv.metrics.histogram("serving_tpot")
    gen_tokens = sum(len(r.out) for r in srv.finished)
    st = srv.stats
    cache = srv.cache
    # per-block bytes from the ACTUAL pool dtype (int8 under kv_quant)
    # plus the fp32 per-block scale sidecar — not the activation dtype
    blk_bytes = cache.bytes_per_token * block_size \
        + cache.scale_bytes_per_block
    # capacity at fixed HBM: the budget the UNQUANTIZED pool would spend
    # on num_slots full slots, re-divided by the row's actual per-slot
    # cost — bf16/fp32 rows report num_slots back, int8 rows ~2x it
    fp_slot_bytes = cache.blocks_per_slot * block_size \
        * gpt.kv_bytes_per_token(cfg, cache.dtype)
    slots_admittable = int(num_slots * fp_slot_bytes
                           // (cache.blocks_per_slot * blk_bytes))
    from deepspeed_tpu.ops.attention.paged import paged_hbm_bytes_per_token
    mean_len = float(np.mean([len(r.prompt) + len(r.out) / 2
                              for r in srv.finished])) if srv.finished else 0
    # serve-cost-* attribution columns (telemetry/costs.py): the exact
    # integer FLOPs/HBM bytes the accountant charged this drive, the
    # analytic per-token model cost, and a roofline MFU against the
    # chip peak (``chip_peak_flops`` overrides; default = this device's
    # spec-sheet peak, None on CPU -> mfu_analytic null)
    from deepspeed_tpu.telemetry.costs import (device_peak_flops,
                                               model_flops_per_token)
    cost_snap = srv.costs.snapshot() if srv.costs.enabled else None
    peak = (chip_peak_flops if chip_peak_flops is not None
            else device_peak_flops())
    cost_flops = cost_snap["flops_total"] if cost_snap else 0
    row = {
        "config": name, "preset": preset or "cpu-smoke",
        "num_requests": num_requests, "new_tokens": new_tokens,
        "num_slots": num_slots, "block_size": block_size,
        "decode_impl": srv.decode_impl,
        "tokens_per_s": round(gen_tokens / wall_s, 1),
        "tpot_ms_p50": round(tpot_h.percentile(50) * 1e3, 3),
        "tpot_ms_p99": round(tpot_h.percentile(99) * 1e3, 3),
        "ttft_p50_ms": round(ttft_h.percentile(50) * 1e3, 3),
        "ttft_p99_ms": round(ttft_h.percentile(99) * 1e3, 3),
        "tpot_p50_ms": round(tpot_h.percentile(50) * 1e3, 3),
        "mean_occupancy": round(st["occupancy_sum"]
                                / max(st["decode_steps"], 1), 2),
        "peak_occupancy": st["peak_occupancy"],
        "slot_utilization": round(st["occupancy_sum"]
                                  / (max(st["steps"], 1) * num_slots), 2),
        "evictions": st["evictions"],
        "peak_kv_bytes_paged": int(cache.peak_used_blocks * blk_bytes),
        "static_kv_bytes": int(cache.static_equivalent_bytes(num_slots)),
        "kv_hbm_bytes_per_token": paged_hbm_bytes_per_token(
            cfg, num_slots, mean_len, cache.tokens_per_slot,
            dtype=cache.pool_dtype, impl=srv.decode_impl,
            block_size=block_size,
            scale_bytes_per_block=cache.scale_bytes_per_block),
        # int8 KV-cache columns: pool dtype actually allocated, write
        # bytes per cached token (pool + amortized scale sidecar), and
        # the fixed-budget slot capacity defined above
        "kv_quant": srv.kv_quant,
        "kv_pool_dtype": str(np.dtype(cache.pool_dtype)),
        "kv_cache_bytes_per_token": round(
            cache.bytes_per_token
            + cache.scale_bytes_per_block / block_size, 1),
        "slots_admittable": slots_admittable,
        "completed": st["completed"],
        # robustness counters: zero in a clean run, nonzero under
        # deadlines/bounded queues/chaos (DS_FAULTS) — a bench row that
        # silently dropped work would otherwise report inflated tokens/s
        "timeouts": st["timeouts"],
        "shed": st["shed"],
        "evict_capped": st["evict_capped"],
        # shared-prefix KV cache columns: hit rate over admissions,
        # prompt tokens whose prefill was skipped, and total prefill
        # chunks (the on/off delta is the work the cache removed)
        "prefix_cache": bool(srv.prefix_cache),
        "prefix_hit_rate": round(
            st["prefix_hits"] / max(st["admitted"], 1), 3),
        "prefix_tokens_saved": st["prefix_tokens_saved"],
        "prefill_chunks": st["prefill_chunks"],
        # host-DRAM KV tier columns (all zero with the tier off): how
        # many cold prefix blocks were spilled off-device, how many a
        # later prefix hit pulled back instead of re-prefilling, and
        # restores the CRC/fault degrade path turned into cold misses
        "host_tier": bool(srv.host_tier),
        "prefix_families": prefix_families,
        "host_spills": cache.host_spills,
        "host_restores": cache.host_restores,
        "host_restore_failures": cache.host_restore_failures,
        "host_blocks": cache.host_blocks,
        "host_bytes": cache.host_bytes,
        # speculative-decode columns, registry-sourced: accept_rate is
        # drafts-the-target-agreed-with over drafts offered;
        # tokens_per_step is emitted tokens per slot per verify step
        # (the speedup factor — 1.0 exactly when speculation is off);
        # ms_per_token is the TPOT histogram mean, the wall-clock the
        # acceptance actually buys down
        # sampling columns: whether the drive sampled (temperature>0),
        # the knobs, and how many emitted tokens came off sampled lanes
        "sampled": temperature > 0.0,
        "temperature": temperature,
        "top_p": top_p,
        "sampled_tokens": st["sampled_tokens"],
        "spec_decode": bool(srv.spec_decode),
        "spec_k": srv.spec_k if srv.spec_decode else 0,
        "decode_steps": st["decode_steps"],
        "accept_rate": round(
            st["spec_accepted"] / max(st["spec_proposed"], 1), 3),
        "tokens_per_step": round(
            st["spec_emitted"] / st["spec_slot_steps"], 2)
        if st["spec_slot_steps"] else 1.0,
        "spec_fallbacks": st["spec_fallbacks"],
        "ms_per_token": round(tpot_h.sum / tpot_h.count * 1e3, 3)
        if tpot_h.count else 0.0,
        # host/device wall split (docs/MULTISTEP.md): device is the
        # wall time blocked on a program (serve.dispatch.wait), host
        # is everything else the scheduler loop did — the horizon
        # amortizes the host share ~N×
        "decode_horizon": srv.decode_horizon,
        "device_ms_per_token": round(
            device_s / max(gen_tokens, 1) * 1e3, 3),
        "host_ms_per_token": round(
            max(0.0, wall_s - device_s)
            / max(gen_tokens, 1) * 1e3, 3),
        "horizon_fallbacks": st["horizon_fallbacks"],
        "model_flops_per_token": model_flops_per_token(cfg),
        "serve_cost_flops_total": cost_flops,
        "serve_cost_hbm_bytes_total": (cost_snap["hbm_bytes_total"]
                                       if cost_snap else 0),
        "serve_cost_kv_block_seconds": (cost_snap["block_seconds_total"]
                                        if cost_snap else 0),
        "serve_cost_flops_per_token": round(
            cost_flops / max(gen_tokens, 1), 1),
        "chip_peak_tflops": round(peak / 1e12, 1) if peak else None,
        "mfu_analytic": round(cost_flops / device_s / peak, 4)
        if (peak and device_s > 0) else None,
        "cache_stats": cache.stats(),
        # per-request lifecycle timestamps (seconds relative to drive
        # start): submit/first-token/finish per rid, so SLO attainment
        # under any TTFT budget is recomputable OFFLINE from the row —
        # the aggregate percentiles above are a digest, not the record
        "requests_detail": [
            {"rid": r.rid,
             "submitted_at": round(r.submitted_at - t0, 6)
             if r.submitted_at is not None else None,
             "first_token_at": round(r.first_token_at - t0, 6)
             if r.first_token_at is not None else None,
             "finished_at": round(r.finished_at - t0, 6)
             if r.finished_at is not None else None,
             "state": r.state, "generated": len(r.out)}
            for r in srv.finished],
    }
    if emit:
        print(json.dumps(row), flush=True)
    # greedy streams for comparison rows (post-emit: never serialized)
    row["_results"] = {r.rid: r.tokens.tolist() for r in srv.finished}
    return row


def bench_serving_impl_compare(name, **kw):
    """Same serving drive under both paged-decode attention paths:
    gather (dense virtual-cache copy per token) vs pallas (flash-decode
    through the block table). Greedy streams must be identical; the row
    is the decode-latency and cache-traffic delta the kernel buys."""
    g = bench_serving(f"{name}[gather]", decode_impl="gather", **kw)
    p = bench_serving(f"{name}[pallas]", decode_impl="pallas", **kw)
    print(json.dumps({
        "config": name, "preset": g["preset"],
        "decode_impl": "gather-vs-pallas",
        "tpot_ms_p50_gather": g["tpot_ms_p50"],
        "tpot_ms_p50_pallas": p["tpot_ms_p50"],
        "tpot_speedup": round(g["tpot_ms_p50"]
                              / max(p["tpot_ms_p50"], 1e-9), 2),
        "tokens_per_s_gather": g["tokens_per_s"],
        "tokens_per_s_pallas": p["tokens_per_s"],
        "kv_hbm_bytes_per_token_gather": g["kv_hbm_bytes_per_token"],
        "kv_hbm_bytes_per_token_pallas": p["kv_hbm_bytes_per_token"],
        "hbm_traffic_ratio": round(
            g["kv_hbm_bytes_per_token"]
            / max(p["kv_hbm_bytes_per_token"], 1), 1),
    }), flush=True)


def bench_serving_prefix_compare(name, shared_prefix_len=64, **kw):
    """Same shared-system-prompt drive with the prefix cache OFF then
    ON: greedy streams must be identical (the cache changes work done,
    never tokens produced); the row is the prefill work and KV-sharing
    delta the cache buys."""
    off = bench_serving(f"{name}[off]", prefix_cache=False,
                        shared_prefix_len=shared_prefix_len, **kw)
    on = bench_serving(f"{name}[on]", prefix_cache=True,
                       shared_prefix_len=shared_prefix_len, **kw)
    print(json.dumps({
        "config": name, "preset": off["preset"],
        "prefix_cache": "off-vs-on",
        "shared_prefix_len": shared_prefix_len,
        "output_identical": off["_results"] == on["_results"],
        "prefix_hit_rate": on["prefix_hit_rate"],
        "prefix_tokens_saved": on["prefix_tokens_saved"],
        "prefill_chunks_off": off["prefill_chunks"],
        "prefill_chunks_on": on["prefill_chunks"],
        "prefill_chunks_saved": off["prefill_chunks"]
        - on["prefill_chunks"],
        "tokens_per_s_off": off["tokens_per_s"],
        "tokens_per_s_on": on["tokens_per_s"],
        "cow_copies": on["cache_stats"]["cow_copies"],
    }), flush=True)


def bench_serving_horizon_compare(name, horizons=(1, 4, 8), repeats=1,
                                  **kw):
    """Same drive at fused decode horizons N ∈ ``horizons``: token
    streams must be identical at every N (the docs/MULTISTEP.md
    bit-parity contract — the horizon changes how many host round-trips
    the same tokens take, never the tokens); the row is the host-side
    ms/token the fusion amortizes, one scheduler iteration per horizon
    instead of per token. On CPU the "device" program is itself
    host-executed, so device_ms dominates and the host_amortization
    column understates the on-chip win (the ROADMAP chip-queue entry).

    ``repeats`` runs each N's drive that many times and keeps the MIN
    of the timing columns — the large-N host deltas are single-digit
    µs/token on the CPU smoke configs, inside one trial's OS jitter,
    and min-of-k is the standard way to read a floor through noise.
    Stream identity is checked on every repeat."""
    rows = []
    for n in horizons:
        best = None
        for r_i in range(max(1, int(repeats))):
            r = bench_serving(f"{name}[n{n}]" if repeats <= 1
                              else f"{name}[n{n} r{r_i}]",
                              decode_horizon=n, **kw)
            if best is None:
                best = r
            else:
                assert r["_results"] == best["_results"], \
                    f"{name}[n{n}]: stream varied across repeats"
                for col in ("host_ms_per_token", "device_ms_per_token",
                            "ms_per_token"):
                    best[col] = min(best[col], r[col])
                best["tokens_per_s"] = max(best["tokens_per_s"],
                                           r["tokens_per_s"])
        rows.append(best)
    base = rows[0]
    out = {
        "config": name, "preset": base["preset"],
        "decode_horizon": "-vs-".join(str(n) for n in horizons),
        "output_identical": all(r["_results"] == base["_results"]
                                for r in rows[1:]),
    }
    for n, r in zip(horizons, rows):
        out[f"host_ms_per_token_n{n}"] = r["host_ms_per_token"]
        out[f"device_ms_per_token_n{n}"] = r["device_ms_per_token"]
        out[f"tokens_per_s_n{n}"] = r["tokens_per_s"]
    out["host_amortization"] = round(
        base["host_ms_per_token"]
        / max(rows[-1]["host_ms_per_token"], 1e-9), 2)
    print(json.dumps(out), flush=True)
    return out


def bench_serving_hosttier_compare(name, shared_prefix_len=24,
                                   prefix_families=3, num_blocks=None,
                                   spill_watermark=None, **kw):
    """Same multi-family shared-prefix drive at the SAME constrained
    device pool, host tier OFF then ON: greedy streams must be
    identical (the tier changes where cold prefix bytes live, never
    the tokens produced); the row is the prefix hit rate the host tier
    recovers at fixed HBM — chains the device-only cache must evict to
    admit the next family instead spill to host DRAM and restore when
    the family returns."""
    off = bench_serving(f"{name}[off]", prefix_cache=True,
                        host_tier=False,
                        shared_prefix_len=shared_prefix_len,
                        prefix_families=prefix_families,
                        num_blocks=num_blocks, **kw)
    on = bench_serving(f"{name}[on]", prefix_cache=True, host_tier=True,
                       shared_prefix_len=shared_prefix_len,
                       prefix_families=prefix_families,
                       num_blocks=num_blocks,
                       spill_watermark=spill_watermark, **kw)
    print(json.dumps({
        "config": name, "preset": off["preset"],
        "host_tier": "off-vs-on",
        "shared_prefix_len": shared_prefix_len,
        "prefix_families": prefix_families,
        "num_blocks": num_blocks,
        "output_identical": off["_results"] == on["_results"],
        "prefix_hit_rate_off": off["prefix_hit_rate"],
        "prefix_hit_rate_on": on["prefix_hit_rate"],
        "prefix_tokens_saved_off": off["prefix_tokens_saved"],
        "prefix_tokens_saved_on": on["prefix_tokens_saved"],
        "host_spills": on["host_spills"],
        "host_restores": on["host_restores"],
        "host_restore_failures": on["host_restore_failures"],
        "host_bytes": on["host_bytes"],
        "tokens_per_s_off": off["tokens_per_s"],
        "tokens_per_s_on": on["tokens_per_s"],
    }), flush=True)


def bench_serving_spec_compare(name, **kw):
    """Same serving drive with speculative decoding OFF then ON: greedy
    streams must be identical (acceptance is target-argmax equality, so
    speculation changes step count, never tokens), and the row is the
    acceptance and per-token-latency delta the draft/verify loop buys."""
    off = bench_serving(f"{name}[off]", spec_decode=False, **kw)
    on = bench_serving(f"{name}[on]", spec_decode=True, **kw)
    print(json.dumps({
        "config": name, "preset": off["preset"],
        "spec_decode": "off-vs-on", "spec_k": on["spec_k"],
        "output_identical": off["_results"] == on["_results"],
        "accept_rate": on["accept_rate"],
        "tokens_per_step": on["tokens_per_step"],
        "spec_fallbacks": on["spec_fallbacks"],
        "decode_steps_off": off["decode_steps"],
        "decode_steps_on": on["decode_steps"],
        "ms_per_token_off": off["ms_per_token"],
        "ms_per_token_on": on["ms_per_token"],
        "tokens_per_s_off": off["tokens_per_s"],
        "tokens_per_s_on": on["tokens_per_s"],
    }), flush=True)


def bench_serving_sampling_compare(name, temperature=0.9, top_p=0.95,
                                   **kw):
    """The same serving drive greedy, sampled, and sampled with
    speculative decoding on. Three contracts in one row: the sampled
    drive replays bit-identically under the same per-request seeds
    (the key chain is pure data), the sampled-spec drive routes
    drafted slots through the rejection-sampling verify (accept_rate
    reports how often the target agreed — 0 when the prompt-lookup
    drafter finds nothing to propose in the workload), and the greedy
    row pins the latency baseline the fused in-program sampler must
    not regress."""
    greedy = bench_serving(f"{name}[greedy]", spec_decode=False, **kw)
    sampled = bench_serving(f"{name}[sampled]", temperature=temperature,
                            top_p=top_p, spec_decode=False, **kw)
    replay = bench_serving(f"{name}[sampled-replay]", emit=False,
                           temperature=temperature, top_p=top_p,
                           spec_decode=False, **kw)
    spec = bench_serving(f"{name}[sampled+spec]", temperature=temperature,
                         top_p=top_p, spec_decode=True, **kw)
    print(json.dumps({
        "config": name, "preset": greedy["preset"],
        "sampling": "greedy-vs-sampled-vs-sampled+spec",
        "temperature": temperature, "top_p": top_p,
        "sampled_replay_identical": sampled["_results"] == replay["_results"],
        "sampled_tokens": sampled["sampled_tokens"],
        "spec_accept_rate": spec["accept_rate"],
        "spec_tokens_per_step": spec["tokens_per_step"],
        "tokens_per_s_greedy": greedy["tokens_per_s"],
        "tokens_per_s_sampled": sampled["tokens_per_s"],
        "tokens_per_s_sampled_spec": spec["tokens_per_s"],
        "ms_per_token_greedy": greedy["ms_per_token"],
        "ms_per_token_sampled": sampled["ms_per_token"],
    }), flush=True)


def bench_serving_kvquant_compare(name, **kw):
    """Same serving drive with the int8 paged KV cache OFF then ON.
    Unlike the prefix/spec comparisons the streams are NOT bit-equal
    (int8 rounds the cache), so the row reports the greedy token match
    rate instead; the headline columns are the fixed-HBM capacity ratio
    (slots_admittable, ~2x) and the per-token cache traffic ratio."""
    off = bench_serving(f"{name}[off]", kv_quant="off", **kw)
    on = bench_serving(f"{name}[int8]", kv_quant="int8", **kw)
    tot = match = 0
    for rid, ref in off["_results"].items():
        got = on["_results"].get(rid, [])
        n = min(len(ref), len(got))
        match += sum(a == b for a, b in zip(ref[:n], got[:n]))
        tot += max(len(ref), len(got))
    print(json.dumps({
        "config": name, "preset": off["preset"],
        "kv_quant": "off-vs-int8",
        "token_match_rate": round(match / max(tot, 1), 4),
        "kv_pool_dtype_off": off["kv_pool_dtype"],
        "kv_pool_dtype_int8": on["kv_pool_dtype"],
        "kv_cache_bytes_per_token_off": off["kv_cache_bytes_per_token"],
        "kv_cache_bytes_per_token_int8": on["kv_cache_bytes_per_token"],
        "cache_bytes_ratio": round(
            off["kv_cache_bytes_per_token"]
            / max(on["kv_cache_bytes_per_token"], 1e-9), 2),
        "slots_admittable_off": off["slots_admittable"],
        "slots_admittable_int8": on["slots_admittable"],
        "capacity_ratio": round(
            on["slots_admittable"]
            / max(off["slots_admittable"], 1), 2),
        "kv_hbm_bytes_per_token_off": off["kv_hbm_bytes_per_token"],
        "kv_hbm_bytes_per_token_int8": on["kv_hbm_bytes_per_token"],
        "tokens_per_s_off": off["tokens_per_s"],
        "tokens_per_s_int8": on["tokens_per_s"],
    }), flush=True)


def bench_serving_router_compare(name, preset=None, num_requests=12,
                                 mean_gap_steps=2.0, prompt_lens=(8, 40),
                                 new_tokens=16, num_slots=2, block_size=8,
                                 num_blocks=None, prefill_chunk=16,
                                 n_replicas=3, kill_step=12, seed=0):
    """Same request set driven through ONE undisturbed ServingEngine and
    through an n_replicas ReplicaRouter fleet with one replica killed
    mid-run (injected ``router.step`` crash at a pinned visit): the row
    is the availability story — drained_requests recovered onto
    survivors, greedy-stream parity with the undisturbed run (the drain
    re-prefills prompt+partial, so tokens must be IDENTICAL), and the
    p99 TTFT delta the kill + drain costs."""
    from deepspeed_tpu.models import gpt
    import deepspeed_tpu
    from deepspeed_tpu.inference.router import ReplicaRouter
    from deepspeed_tpu.inference.serving import ServeRequest, ServingEngine
    from deepspeed_tpu.telemetry import Telemetry
    from deepspeed_tpu.utils.faults import Fault, FaultInjector

    on_tpu = "tpu" in (jax.devices()[0].platform +
                       jax.devices()[0].device_kind).lower()
    max_seq = prompt_lens[1] + new_tokens + 8
    if preset:
        cfg = gpt.preset(preset, max_seq_len=max_seq, dtype=jnp.bfloat16,
                         use_flash_attention=on_tpu)
    else:
        cfg = gpt.GPTConfig(vocab_size=512, n_layers=4, n_heads=8,
                            d_model=256, max_seq_len=max_seq,
                            use_flash_attention=False, remat=False,
                            dtype=jnp.float32)
    eng = deepspeed_tpu.init_inference(
        model=(cfg, gpt.init_params(jax.random.PRNGKey(0), cfg)),
        dtype=jnp.bfloat16 if on_tpu else jnp.float32)

    rng = np.random.default_rng(seed)
    arrive = np.floor(np.cumsum(
        rng.exponential(mean_gap_steps, num_requests))).astype(int)
    prompts = [rng.integers(0, cfg.vocab_size,
                            rng.integers(*prompt_lens)).astype(np.int32)
               for _ in range(num_requests)]

    def mk_reqs():
        return [ServeRequest(rid=i, prompt=prompts[i].copy(),
                             max_new_tokens=new_tokens)
                for i in range(num_requests)]

    def mk_srv(tel=None, faults=None):
        return ServingEngine(eng, num_slots=num_slots,
                             block_size=block_size, num_blocks=num_blocks,
                             prefill_chunk=prefill_chunk, spec_decode=False,
                             telemetry=tel, faults=faults)

    # warmup: compile the slot programs outside both timed drives
    mk_srv().run([ServeRequest(rid="w", prompt=prompts[0].copy(),
                               max_new_tokens=2)])

    def drive(submit, step, busy):
        t0 = time.perf_counter()
        s = nxt = 0
        reqs = mk_reqs()
        while nxt < num_requests or busy():
            while nxt < num_requests and arrive[nxt] <= s:
                submit(reqs[nxt], now=time.perf_counter())
                nxt += 1
            step(now=time.perf_counter())
            s += 1
        return time.perf_counter() - t0

    # undisturbed 1-replica baseline
    tel1 = Telemetry()
    solo = mk_srv(tel=tel1)
    wall1 = drive(solo.submit, solo.step, lambda: solo.busy)
    out1 = {r.rid: r.tokens.tolist() for r in solo.finished}
    ttft1 = solo.metrics.histogram("serving_ttft")

    # n-replica fleet, one replica crash-killed mid-run; the shared
    # Telemetry aggregates serving_ttft across replicas (get-or-create
    # registry), so the fleet percentile includes drained re-prefills
    inj = FaultInjector([Fault("router.step", "crash", step=kill_step)],
                        seed=seed)
    teln = Telemetry()
    fleet = [mk_srv(tel=teln, faults=inj) for _ in range(n_replicas)]
    router = ReplicaRouter(fleet, faults=inj, telemetry=teln)
    walln = drive(router.submit, router.step, lambda: router.busy)
    outn = {rid: np.asarray(t).tolist()
            for rid, t in router.results().items()}
    ttftn = fleet[0].metrics.histogram("serving_ttft")

    gen1 = sum(len(r.out) for r in solo.finished)
    genn = sum(len(outn[i]) - len(prompts[i]) for i in outn)
    print(json.dumps({
        "config": name, "preset": preset or "cpu-smoke",
        "router": f"1-vs-{n_replicas}(kill 1)",
        "num_requests": num_requests, "n_replicas": n_replicas,
        "replica_killed": bool(inj.fired),
        "drained_requests": router.stats["drained_requests"],
        "breaker_trips": router.stats["breaker_trips"],
        "redispatches": router.stats["redispatches"],
        "replica_health": router.health(),
        "output_identical": all(
            outn.get(i) == out1[i] for i in out1),
        "ttft_p99_ms_solo": round(ttft1.percentile(99) * 1e3, 3),
        "ttft_p99_ms_fleet": round(ttftn.percentile(99) * 1e3, 3),
        "ttft_p99_delta_ms": round(
            (ttftn.percentile(99) - ttft1.percentile(99)) * 1e3, 3),
        "tokens_per_s_solo": round(gen1 / wall1, 1),
        "tokens_per_s_fleet": round(genn / walln, 1),
    }), flush=True)


def bench_serving_lora_compare(name, preset=None, num_requests=10,
                               mean_gap_steps=2.0, prompt_lens=(6, 14),
                               new_tokens=8, num_slots=2, block_size=8,
                               num_blocks=None, prefill_chunk=16,
                               n_adapters=3, rank=4,
                               lora_pool_blocks=None, seed=0):
    """Multi-tenant LoRA serving (docs/ADAPTERS.md), three legs over
    one seeded tenant population: (a) merged-single — adapter 0 baked
    into the weights with ``merge_lora``, base-only serving (the
    pre-subsystem reference and the ms/token floor); (b)
    unmerged-single — the SAME requests through the adapter pool, whose
    greedy streams must be IDENTICAL to (a); (c) mixed — a
    Zipf-popular multi-adapter + base-only population in one engine,
    every stream checked against its own tenant's merged reference.
    The row is the bit-parity verdict, the pool's hit/load/eviction
    economics, and the ms/token price of the gathered low-rank
    matmuls."""
    from deepspeed_tpu.models import gpt
    import deepspeed_tpu
    from deepspeed_tpu.inference.serving import ServeRequest, ServingEngine
    from deepspeed_tpu.runtime.lora import (add_lora, adapter_state_dict,
                                            merge_lora)

    on_tpu = "tpu" in (jax.devices()[0].platform +
                       jax.devices()[0].device_kind).lower()
    max_seq = prompt_lens[1] + new_tokens + 8
    if preset:
        cfg = gpt.preset(preset, max_seq_len=max_seq, dtype=jnp.bfloat16,
                         use_flash_attention=on_tpu)
    else:
        cfg = gpt.GPTConfig(vocab_size=512, n_layers=4, n_heads=8,
                            d_model=256, max_seq_len=max_seq,
                            use_flash_attention=False, remat=False,
                            dtype=jnp.float32)
    params = gpt.init_params(jax.random.PRNGKey(0), cfg)
    dtype = jnp.bfloat16 if on_tpu else jnp.float32

    # n_adapters distinct fine-tunes: add_lora's B starts at zero (a
    # zero delta would make every leg trivially identical), so each
    # tenant gets seeded noise in B — distinct, nonzero deltas
    exports = []
    merged = []
    for t in range(n_adapters):
        lp = add_lora(params, rank=rank, alpha=2.0 * rank,
                      rng=jax.random.PRNGKey(seed + 100 + t))
        nrng = np.random.default_rng(seed + 200 + t)
        blk = dict(lp["block"])
        for tgt, entry in blk.items():
            if isinstance(entry, dict) and "lora_b" in entry:
                e = dict(entry)
                e["lora_b"] = jnp.asarray(
                    nrng.standard_normal(e["lora_b"].shape) * 0.05,
                    jnp.float32)
                blk[tgt] = e
        lp = dict(lp)
        lp["block"] = blk
        exports.append(adapter_state_dict(lp))
        merged.append(merge_lora(lp))

    rng = np.random.default_rng(seed)
    arrive = np.floor(np.cumsum(
        rng.exponential(mean_gap_steps, num_requests))).astype(int)
    prompts = [rng.integers(0, cfg.vocab_size,
                            rng.integers(*prompt_lens)).astype(np.int32)
               for _ in range(num_requests)]
    # Zipf-popular tenant per request, with a base-only fraction; rid 0
    # pinned to tenant 0 so the single-adapter legs are never empty
    tenants = [0] + [
        None if rng.random() < 0.25
        else (int(rng.zipf(1.5)) - 1) % n_adapters
        for _ in range(num_requests - 1)]

    def mk_reqs(only=None):
        return [ServeRequest(
                    rid=i, prompt=prompts[i].copy(),
                    max_new_tokens=new_tokens,
                    adapter_id=(f"tenant-{tenants[i]}"
                                if tenants[i] is not None else None))
                for i in range(num_requests)
                if only is None or tenants[i] == only]

    def drive(srv, reqs, register=()):
        for aid, sd in register:
            srv.register_adapter(aid, sd)
        t0 = time.perf_counter()
        s = nxt = 0
        byrid = {r.rid: r for r in reqs}
        order = sorted(byrid)
        while nxt < len(order) or srv.busy:
            while nxt < len(order) and arrive[order[nxt]] <= s:
                srv.submit(byrid[order[nxt]], now=time.perf_counter())
                nxt += 1
            srv.step(now=time.perf_counter())
            s += 1
        wall = time.perf_counter() - t0
        gen = sum(len(r.out) for r in srv.finished)
        return ({r.rid: r.tokens.tolist() for r in srv.finished},
                round(wall / max(gen, 1) * 1e3, 3))

    def mk_srv(eng, lora=False):
        return ServingEngine(
            eng, num_slots=num_slots, block_size=block_size,
            num_blocks=num_blocks, prefill_chunk=prefill_chunk,
            spec_decode=False, lora_serve=lora,
            lora_pool_blocks=lora_pool_blocks if lora else None)

    # per-tenant merged reference engines (+ the plain base engine for
    # base-only requests); compile outside the timed legs via warmup
    eng_base = deepspeed_tpu.init_inference(model=(cfg, params),
                                            dtype=dtype)
    engs_merged = [deepspeed_tpu.init_inference(model=(cfg, m),
                                                dtype=dtype)
                   for m in merged]
    eng_lora = deepspeed_tpu.init_inference(model=(cfg, params),
                                            dtype=dtype)
    warm = [ServeRequest(rid="w", prompt=prompts[0].copy(),
                        max_new_tokens=2)]
    mk_srv(eng_base).run([ServeRequest(rid="w", prompt=prompts[0].copy(),
                                       max_new_tokens=2)])
    for e in engs_merged:
        mk_srv(e).run([ServeRequest(rid="w", prompt=prompts[0].copy(),
                                    max_new_tokens=2)])
    wsrv = mk_srv(eng_lora, lora=True)
    wsrv.register_adapter("tenant-0", exports[0])
    warm[0].adapter_id = "tenant-0"
    wsrv.run(warm)

    # reference streams: every tenant's requests through ITS merged
    # engine, base-only requests through the base engine (burst drive —
    # greedy slot streams are batching-independent by contract)
    refs = {}
    for t in range(n_adapters):
        reqs = [ServeRequest(rid=r.rid, prompt=r.prompt,
                             max_new_tokens=r.max_new_tokens)
                for r in mk_reqs(only=t)]
        if reqs:
            refs.update(mk_srv(engs_merged[t]).run(reqs))
    base_reqs = [ServeRequest(rid=r.rid, prompt=r.prompt,
                              max_new_tokens=r.max_new_tokens)
                 for r in mk_reqs(only=None) if r.adapter_id is None]
    if base_reqs:
        refs.update(mk_srv(eng_base).run(base_reqs))
    refs = {rid: np.asarray(t).tolist() for rid, t in refs.items()}

    # leg (a): merged-single — tenant 0 baked in, base-only serving
    out_m, mspt_merged = drive(mk_srv(engs_merged[0]),
                               [ServeRequest(rid=r.rid, prompt=r.prompt,
                                             max_new_tokens=r.max_new_tokens)
                                for r in mk_reqs(only=0)])
    # leg (b): unmerged-single — same requests through the pool
    out_u, mspt_unmerged = drive(mk_srv(eng_lora, lora=True),
                                 mk_reqs(only=0),
                                 register=[("tenant-0", exports[0])])
    # leg (c): mixed-adapter batch, full population
    srv_x = mk_srv(eng_lora, lora=True)
    out_x, mspt_mixed = drive(
        srv_x, mk_reqs(),
        register=[(f"tenant-{t}", exports[t])
                  for t in range(n_adapters)])
    st = srv_x.stats
    pool = srv_x.adapters.stats()
    acq = st["adapter_hits"] + st["adapter_loads"]
    print(json.dumps({
        "config": name, "preset": preset or "cpu-smoke",
        "lora": f"merged-vs-unmerged-vs-mixed({n_adapters} adapters)",
        "num_requests": num_requests, "n_adapters": n_adapters,
        "rank": rank, "pool_blocks": pool["pool_blocks"],
        "single_adapter_identical": out_u == out_m,
        "output_identical": all(out_x.get(rid) == refs[rid]
                                for rid in refs),
        "base_only_requests": sum(1 for t in tenants if t is None),
        "adapter_hit_rate": round(st["adapter_hits"] / max(acq, 1), 3),
        "adapter_loads": st["adapter_loads"],
        "adapter_evictions": st["adapter_evictions"],
        "adapter_load_errors": st["adapter_load_errors"],
        "ms_per_token_merged_single": mspt_merged,
        "ms_per_token_unmerged_single": mspt_unmerged,
        "ms_per_token_mixed": mspt_mixed,
        "ms_per_token_delta": round(mspt_unmerged - mspt_merged, 3),
    }), flush=True)


def bench_serving_cost_attrib(name, preset=None, num_requests=10,
                              mean_gap_steps=2.0, prompt_lens=(6, 14),
                              new_tokens=8, num_slots=2, block_size=8,
                              prefill_chunk=16, n_adapters=2, rank=4,
                              seed=0):
    """Per-tenant cost attribution (telemetry/costs.py): a mixed
    base + n_adapters LoRA population through ONE engine with the cost
    accountant on, reporting each tenant's exact FLOPs/HBM-bytes/
    KV-block-seconds footprint, the per-dispatch-class totals, and the
    conservation verdict (sum of per-request footprints == the global
    counters, per class — exact integers, not approximately)."""
    from deepspeed_tpu.models import gpt
    import deepspeed_tpu
    from deepspeed_tpu.inference.serving import ServeRequest, ServingEngine
    from deepspeed_tpu.runtime.lora import add_lora, adapter_state_dict
    from deepspeed_tpu.telemetry import Telemetry
    from deepspeed_tpu.utils.jit_registry import DISPATCH_CLASSES

    on_tpu = "tpu" in (jax.devices()[0].platform +
                       jax.devices()[0].device_kind).lower()
    max_seq = prompt_lens[1] + new_tokens + 8
    if preset:
        cfg = gpt.preset(preset, max_seq_len=max_seq, dtype=jnp.bfloat16,
                         use_flash_attention=on_tpu)
    else:
        cfg = gpt.GPTConfig(vocab_size=512, n_layers=4, n_heads=8,
                            d_model=256, max_seq_len=max_seq,
                            use_flash_attention=False, remat=False,
                            dtype=jnp.float32)
    params = gpt.init_params(jax.random.PRNGKey(0), cfg)
    dtype = jnp.bfloat16 if on_tpu else jnp.float32
    eng = deepspeed_tpu.init_inference(model=(cfg, params), dtype=dtype)
    srv = ServingEngine(eng, num_slots=num_slots, block_size=block_size,
                        prefill_chunk=prefill_chunk, spec_decode=False,
                        lora_serve=True, telemetry=Telemetry())
    for t in range(n_adapters):
        srv.register_adapter(
            f"tenant-{t}",
            adapter_state_dict(add_lora(
                params, rank=rank, alpha=2.0 * rank,
                rng=jax.random.PRNGKey(seed + 100 + t))))

    rng = np.random.default_rng(seed)
    arrive = np.floor(np.cumsum(
        rng.exponential(mean_gap_steps, num_requests))).astype(int)
    reqs = [ServeRequest(
                rid=i,
                prompt=rng.integers(0, cfg.vocab_size,
                                    rng.integers(*prompt_lens)
                                    ).astype(np.int32),
                max_new_tokens=new_tokens,
                adapter_id=(f"tenant-{i % n_adapters}"
                            if i % 3 else None))
            for i in range(num_requests)]
    t0 = time.perf_counter()
    s = nxt = 0
    while nxt < num_requests or srv.busy:
        while nxt < num_requests and arrive[nxt] <= s:
            srv.submit(reqs[nxt], now=time.perf_counter())
            nxt += 1
        srv.step(now=time.perf_counter())
        s += 1
    wall_s = time.perf_counter() - t0

    snap = srv.costs.snapshot()
    # conservation check, same arithmetic the test suite pins: refold
    # every per-request footprint (plus the unowned system residue)
    # and compare against the accountant's per-class totals
    folded = {c: {"flops": 0, "hbm_bytes": 0, "dispatches": 0}
              for c in DISPATCH_CLASSES}
    for r in srv.finished:
        for c in DISPATCH_CLASSES:
            for k in folded[c]:
                folded[c][k] += r.cost[c][k]
    for c in DISPATCH_CLASSES:
        for k in folded[c]:
            folded[c][k] += srv.costs.system[c][k]
    conserved = all(folded[c][k] == srv.costs.totals[c][k]
                    for c in DISPATCH_CLASSES for k in folded[c])
    gen_tokens = sum(len(r.out) for r in srv.finished)
    row = {
        "config": name, "preset": preset or "cpu-smoke",
        "num_requests": num_requests, "n_adapters": n_adapters,
        "completed": srv.stats["completed"],
        "tokens_per_s": round(gen_tokens / max(wall_s, 1e-9), 1),
        "conservation_exact": bool(conserved),
        "serve_cost_flops_total": snap["flops_total"],
        "serve_cost_hbm_bytes_total": snap["hbm_bytes_total"],
        "serve_cost_kv_block_seconds": snap["block_seconds_total"],
        "cost_registry_programs": len(srv.cost_registry.entries),
        "per_class": {c: dict(srv.costs.totals[c])
                      for c in DISPATCH_CLASSES},
        "per_tenant": {
            tid: {"flops": sum(fp[c]["flops"] for c in DISPATCH_CLASSES),
                  "hbm_bytes": sum(fp[c]["hbm_bytes"]
                                   for c in DISPATCH_CLASSES),
                  "block_seconds": fp["block_seconds"]}
            for tid, fp in sorted(srv.costs.tenants.items())},
    }
    print(json.dumps(row), flush=True)
    return row


def bench_serving_autoscale_compare(name, preset=None, num_slots=2,
                                    block_size=8, num_blocks=None,
                                    prefill_chunk=16, max_replicas=3,
                                    ttft_slo=12.0, queue_high=2.0,
                                    mix="chat",
                                    phases=((6, 0.2), (60, 0.5), (30, 0.05)),
                                    seed=0):
    """The closed-loop SLO story (docs/OBSERVABILITY.md): ONE seeded
    load-gen population with a rate spike in the middle, driven in
    scheduler-STEP clock units through (a) a FIXED 1-replica fleet and
    (b) a policy fleet that starts at 1 replica with the
    :class:`SLOController` active. The fixed fleet queues through the
    spike and violates the stated p99-TTFT SLO; the controller sees the
    windowed p99 cross the budget, scales up via ``replica_factory``
    (sharing the one ``InferenceEngine`` — zero new compiled programs)
    and holds it. ``slo_attainment`` is recomputed from the per-request
    first-token timestamps; ``replicas_high_water`` and
    ``autoscale_decisions`` come from the fleet registry. The whole
    drive is deterministic under ``seed`` (step-unit clock, seeded
    arrivals, host-side controller), so the row regresses bit-for-bit."""
    from tools.load_gen import drive, make_requests
    from deepspeed_tpu.models import gpt
    import deepspeed_tpu
    from deepspeed_tpu.inference.autoscale import SLOController
    from deepspeed_tpu.inference.router import ReplicaRouter
    from deepspeed_tpu.inference.serving import ServeRequest, ServingEngine
    from deepspeed_tpu.telemetry import Telemetry

    on_tpu = "tpu" in (jax.devices()[0].platform +
                       jax.devices()[0].device_kind).lower()
    max_prompt = 40
    max_seq = max_prompt + 24 + 8
    if preset:
        cfg = gpt.preset(preset, max_seq_len=max_seq, dtype=jnp.bfloat16,
                         use_flash_attention=on_tpu)
    else:
        cfg = gpt.GPTConfig(vocab_size=512, n_layers=4, n_heads=8,
                            d_model=256, max_seq_len=max_seq,
                            use_flash_attention=False, remat=False,
                            dtype=jnp.float32)
    eng = deepspeed_tpu.init_inference(
        model=(cfg, gpt.init_params(jax.random.PRNGKey(0), cfg)),
        dtype=jnp.bfloat16 if on_tpu else jnp.float32)

    entries = make_requests(seed=seed, mix=mix, phases=list(phases),
                            vocab_size=cfg.vocab_size,
                            max_prompt_len=max_prompt)

    def mk_srv(tel):
        return ServingEngine(eng, num_slots=num_slots,
                             block_size=block_size, num_blocks=num_blocks,
                             prefill_chunk=prefill_chunk, spec_decode=False,
                             telemetry=tel)

    # warmup: compile the slot programs outside both drives
    mk_srv(None).run([ServeRequest(
        rid="w", prompt=np.asarray(entries[0]["prompt"], np.int32),
        max_new_tokens=2)])

    # fixed fleet: one replica, no controller — the SLO-violation shape
    tel_f = Telemetry()
    fixed = ReplicaRouter([mk_srv(tel_f)], telemetry=tel_f)
    res_f = drive(fixed, entries, mode="open", slo_ttft=ttft_slo)

    # policy fleet: same population, controller active; replicas come
    # from the factory SHARING eng, so scale-up compiles nothing
    tel_p = Telemetry()
    ctrl = SLOController(ttft_slo=ttft_slo, window=16.0, eval_every=2,
                         max_replicas=max_replicas, cooldown=4.0,
                         idle_to_retire=1e9, min_samples=3,
                         queue_high=queue_high)
    policy = ReplicaRouter([mk_srv(tel_p)],
                           replica_factory=lambda i, tag: mk_srv(tel_p),
                           telemetry=tel_p, autoscale=ctrl)
    res_p = drive(policy, entries, mode="open", slo_ttft=ttft_slo)

    snap = policy.fleet_snapshot()
    print(json.dumps({
        "config": name, "preset": preset or "cpu-smoke",
        "autoscale": f"fixed-1-vs-policy-{max_replicas}",
        "num_requests": len(entries), "mix": mix,
        "ttft_slo_steps": ttft_slo,
        "ttft_p99_fixed": round(res_f["ttft_p99"], 2),
        "ttft_p99_policy": round(res_p["ttft_p99"], 2),
        "slo_attainment_fixed": round(res_f["slo_attainment"], 3),
        "slo_attainment": round(res_p["slo_attainment"], 3),
        "slo_violated_fixed": res_f["ttft_p99"] > ttft_slo,
        "slo_holds_policy": res_p["ttft_p99"] <= ttft_slo,
        "replicas_high_water":
            1 + snap["counters"]["router_scale_ups"],
        "autoscale_decisions": snap["counters"]["autoscale_decisions"],
        "autoscale_scale_ups": snap["counters"]["autoscale_scale_ups"],
        "fleet_health": policy.health(),
        "steps_fixed": res_f["steps"], "steps_policy": res_p["steps"],
    }), flush=True)
    return res_f, res_p, policy


def bench_serving_disagg_compare(name, preset=None, num_replicas=2,
                                 num_slots=2, block_size=8,
                                 num_blocks=24, prefill_chunk=8,
                                 phases=((110, 0.27),), seed=3,
                                 max_prompt=64):
    """Disaggregated prefill/decode vs monolithic at the SAME chip
    count (docs/ROBUSTNESS.md): ONE seeded mixed rag+chat load-gen
    trace (Zipf-popular rag document prefixes) driven through (a)
    ``num_replicas`` mixed-role replicas and (b) the same replicas
    split into 1 prefill + N-1 decode roles, KV migrating between
    pools through the CRC-verified host channel. The monolithic fleet
    interleaves long rag prefills with interactive chat decodes in the
    same slots — head-of-line prefill wait and block-pressure
    preemption violate at least one per-kind p99 SLO budget
    (tools/load_gen.SLO_TARGETS); the split fleet must hold ALL of
    them, with byte-identical per-request tokens (``output_identical``
    — migration resume is exact, and every injected-fault fallback
    degrades to a cold re-prefill, never a wrong token). The disagg
    drive runs under ``CompileWatch(0)``: migration gather/scatter
    lanes are pre-warmed at router construction, so the steady state
    compiles nothing. Ambient ``DS_FAULTS`` naming the three
    ``router.migrate_*`` sites turns this row into the chaos leg:
    ``migration_fallbacks`` goes positive and every assert still
    holds."""
    from tools.load_gen import SLO_TARGETS, drive, make_requests
    from deepspeed_tpu.models import gpt
    import deepspeed_tpu
    from deepspeed_tpu.inference.router import ReplicaRouter
    from deepspeed_tpu.inference.serving import ServeRequest, ServingEngine
    from deepspeed_tpu.telemetry import Telemetry
    from deepspeed_tpu.utils.compile_guard import CompileWatch

    on_tpu = "tpu" in (jax.devices()[0].platform +
                       jax.devices()[0].device_kind).lower()
    max_seq = max_prompt + 16 + 8
    if preset:
        cfg = gpt.preset(preset, max_seq_len=max_seq, dtype=jnp.bfloat16,
                         use_flash_attention=on_tpu)
    else:
        cfg = gpt.GPTConfig(vocab_size=512, n_layers=4, n_heads=8,
                            d_model=256, max_seq_len=max_seq,
                            use_flash_attention=False, remat=False,
                            dtype=jnp.float32)
    eng = deepspeed_tpu.init_inference(
        model=(cfg, gpt.init_params(jax.random.PRNGKey(0), cfg)),
        dtype=jnp.bfloat16 if on_tpu else jnp.float32)

    entries = make_requests(seed=seed, mix="mixed", phases=list(phases),
                            vocab_size=cfg.vocab_size,
                            max_prompt_len=max_prompt)

    def mk_srv(tel):
        return ServingEngine(eng, num_slots=num_slots,
                             block_size=block_size, num_blocks=num_blocks,
                             prefill_chunk=prefill_chunk,
                             spec_decode=False, telemetry=tel)

    # warmup: compile prefill/decode slot programs outside both drives
    mk_srv(None).run([ServeRequest(
        rid="w", prompt=np.asarray(entries[0]["prompt"], np.int32),
        max_new_tokens=2)])

    def kind_p99(res, key):
        out = {}
        for kind in ("chat", "rag"):
            vals = [r[key] for r in res["per_request"]
                    if r["kind"] == kind and r[key] is not None]
            out[kind] = (float(np.percentile(np.asarray(vals), 99))
                         if vals else 0.0)
        return out

    def slo_holds(res):
        ttft, tpot = kind_p99(res, "ttft"), kind_p99(res, "tpot")
        return all(ttft[k] <= SLO_TARGETS[k]["ttft"]
                   and tpot[k] <= SLO_TARGETS[k]["tpot"]
                   for k in ("chat", "rag"))

    # (a) monolithic: every replica mixed-role — the contention shape
    tel_m = Telemetry()
    mono = ReplicaRouter([mk_srv(tel_m) for _ in range(num_replicas)],
                         telemetry=tel_m)
    res_m = drive(mono, entries, mode="open", include_tokens=True)

    # (b) same chip count, split roles: KV migrates prefill -> decode.
    # Router construction pre-warms the migration gather/scatter lanes,
    # so the watched drive must compile NOTHING.
    tel_d = Telemetry()
    roles = ["prefill"] + ["decode"] * (num_replicas - 1)
    disagg = ReplicaRouter([mk_srv(tel_d) for _ in range(num_replicas)],
                           roles=roles, telemetry=tel_d)
    watch = CompileWatch(max_compiles=0, label="disagg steady state")
    with watch:
        res_d = drive(disagg, entries, mode="open", include_tokens=True)

    toks_m = {r["rid"]: r["tokens"] for r in res_m["per_request"]}
    toks_d = {r["rid"]: r["tokens"] for r in res_d["per_request"]}
    identical = toks_m == toks_d

    ttft_m, tpot_m = kind_p99(res_m, "ttft"), kind_p99(res_m, "tpot")
    ttft_d, tpot_d = kind_p99(res_d, "ttft"), kind_p99(res_d, "tpot")
    snap = disagg.fleet_snapshot()
    row = {
        "config": name, "preset": preset or "cpu-smoke",
        "disagg": f"{num_replicas}-mixed-vs-1prefill+"
                  f"{num_replicas - 1}decode",
        "num_requests": len(entries),
        "slo_targets": {k: SLO_TARGETS[k] for k in ("chat", "rag")},
        "ttft_p99_mono": {k: round(v, 2) for k, v in ttft_m.items()},
        "tpot_p99_mono": {k: round(v, 2) for k, v in tpot_m.items()},
        "ttft_p99_disagg": {k: round(v, 2) for k, v in ttft_d.items()},
        "tpot_p99_disagg": {k: round(v, 2) for k, v in tpot_d.items()},
        "slo_violated_mono": not slo_holds(res_m),
        "slo_holds_disagg": slo_holds(res_d),
        "migrations": snap["counters"]["router_migrations"],
        "migration_fallbacks":
            snap["counters"]["router_migration_fallbacks"],
        "output_identical": identical,
        "steady_state_compiles": watch.compiles,
        "steps_mono": res_m["steps"], "steps_disagg": res_d["steps"],
    }
    print(json.dumps(row), flush=True)
    return row, res_m, res_d, disagg


SERVE_CONFIGS = [
    # CPU-verifiable smoke: staggered Poisson arrivals must batch
    # (mean_occupancy > 1) and the paged footprint must undercut the
    # static num_slots x S_max reservation
    ("serve-smoke", dict(num_requests=12, mean_gap_steps=2.0,
                         prompt_lens=(8, 40), new_tokens=16, num_slots=4,
                         block_size=8, prefill_chunk=16)),
    # on-chip rows: bf16 and weight-only int8 through the same scheduler
    # (int8-fused additionally routes dense through ops/int8_matmul)
    ("serve-gpt2-medium", dict(preset="gpt2-medium", num_requests=32,
                               mean_gap_steps=1.5, prompt_lens=(64, 384),
                               new_tokens=64, num_slots=8,
                               block_size=16, prefill_chunk=128)),
    ("serve-gpt2-medium-int8-fused", dict(
        preset="gpt2-medium", num_requests=32, mean_gap_steps=1.5,
        prompt_lens=(64, 384), new_tokens=64, num_slots=8,
        block_size=16, prefill_chunk=128, int8=True, int8_fused=True)),
]

# gather-vs-pallas comparison drives (one config, both impls): the
# on-chip row is the kernel's headline; the smoke row runs the pallas
# kernel in INTERPRET mode on CPU, so its wall-clock is meaningless but
# the identical-stream and traffic-accounting columns still verify
SERVE_COMPARE_CONFIGS = [
    ("serve-impl-smoke", dict(num_requests=6, mean_gap_steps=2.0,
                              prompt_lens=(8, 24), new_tokens=8,
                              num_slots=2, block_size=8,
                              prefill_chunk=16)),
    ("serve-impl-gpt2-medium", dict(preset="gpt2-medium", num_requests=32,
                                    mean_gap_steps=1.5,
                                    prompt_lens=(64, 384), new_tokens=64,
                                    num_slots=8, block_size=16,
                                    prefill_chunk=128)),
    # shared-system-prompt workload, DS_PREFIX_CACHE on vs off: every
    # request opens with the same shared_prefix_len tokens, so the warm
    # path must report prefix_hit_rate > 0 and fewer prefill chunks
    # while streams stay identical
    ("serve-prefix-smoke", dict(mode="prefix", num_requests=8,
                                mean_gap_steps=2.0, prompt_lens=(4, 12),
                                new_tokens=8, num_slots=2, block_size=8,
                                prefill_chunk=16, shared_prefix_len=24)),
    ("serve-prefix-gpt2-medium", dict(
        mode="prefix", preset="gpt2-medium", num_requests=32,
        mean_gap_steps=1.5, prompt_lens=(16, 128), new_tokens=64,
        num_slots=8, block_size=16, prefill_chunk=128,
        shared_prefix_len=256)),
    # host-DRAM KV tier at a CONSTRAINED device pool: three prompt
    # families rotate through two visits each, so every family's chain
    # goes cold between visits — the off row loses those chains to
    # device eviction, the on row must report host_spills > 0,
    # host_restores > 0 and a higher prefix_hit_rate at the same
    # num_blocks, with identical greedy streams
    ("serve-hosttier-smoke", dict(mode="hosttier", num_requests=12,
                                  mean_gap_steps=2.0, prompt_lens=(4, 12),
                                  new_tokens=8, num_slots=2, block_size=8,
                                  prefill_chunk=16, shared_prefix_len=24,
                                  prefix_families=3, num_blocks=14,
                                  spill_watermark=12)),
    ("serve-hosttier-gpt2-medium", dict(
        mode="hosttier", preset="gpt2-medium", num_requests=24,
        mean_gap_steps=1.5, prompt_lens=(16, 96), new_tokens=32,
        num_slots=4, block_size=16, prefill_chunk=64,
        shared_prefix_len=192, prefix_families=3, num_blocks=88,
        spill_watermark=32)),
    # speculative decoding on vs off over a self-similar greedy workload
    # (tiny-model greedy loops repeat, exactly what the prompt-lookup
    # drafter exploits): streams must be identical and the on row must
    # report accept_rate > 0 / tokens_per_step > 1.0
    ("serve-spec-smoke", dict(mode="spec", num_requests=8,
                              mean_gap_steps=2.0, prompt_lens=(6, 20),
                              new_tokens=16, num_slots=2, block_size=8,
                              prefill_chunk=16)),
    ("serve-spec-gpt2-medium", dict(
        mode="spec", preset="gpt2-medium", num_requests=32,
        mean_gap_steps=1.5, prompt_lens=(64, 384), new_tokens=64,
        num_slots=8, block_size=16, prefill_chunk=128)),
    # int8 paged KV cache on vs off: the off row must admit num_slots
    # at its own budget, the int8 row ~2x that (capacity_ratio >= 1.8
    # on bf16 pools; larger on the fp32 CPU smoke), with a high but not
    # bit-exact token_match_rate — the rounding tolerance is the price
    ("serve-kvquant-smoke", dict(mode="kvquant", num_requests=8,
                                 mean_gap_steps=2.0, prompt_lens=(8, 24),
                                 new_tokens=12, num_slots=2, block_size=8,
                                 prefill_chunk=16)),
    ("serve-kvquant-gpt2-medium", dict(
        mode="kvquant", preset="gpt2-medium", num_requests=32,
        mean_gap_steps=1.5, prompt_lens=(64, 384), new_tokens=64,
        num_slots=8, block_size=16, prefill_chunk=128)),
    # per-request sampling: greedy vs sampled vs sampled+spec over one
    # drive — the sampled row must replay bit-identically under its
    # fixed per-request seeds, and the sampled-spec row must keep a
    # nonzero accept_rate through the rejection-sampling verify
    ("serve-sampling-smoke", dict(mode="sampling", num_requests=8,
                                  mean_gap_steps=2.0, prompt_lens=(6, 20),
                                  new_tokens=12, num_slots=2, block_size=8,
                                  prefill_chunk=16)),
    ("serve-sampling-gpt2-medium", dict(
        mode="sampling", preset="gpt2-medium", num_requests=32,
        mean_gap_steps=1.5, prompt_lens=(64, 384), new_tokens=64,
        num_slots=8, block_size=16, prefill_chunk=128)),
    # fused multi-step decode horizons N=1 vs 4 vs 8: streams must be
    # identical at every N while host_ms_per_token falls — the host
    # scheduler loop runs once per horizon instead of once per token
    # (docs/MULTISTEP.md; chip-queue entry in ROADMAP for on-chip rows).
    # burst arrivals (gap 0) keep the slots saturated at every N: a
    # Poisson gap in scheduler-step units would make the faster-per-step
    # N=8 run sit through idle arrival-wait steps, billing host time
    # against zero tokens and muddying the amortization column
    # repeats=3/min-of-k: the n4→n8 host delta is a few µs/token on
    # CPU, inside one trial's OS jitter
    ("serve-horizon-smoke", dict(mode="horizon", num_requests=8,
                                 mean_gap_steps=0.0, prompt_lens=(6, 20),
                                 new_tokens=24, num_slots=2, block_size=8,
                                 prefill_chunk=16, repeats=3)),
    ("serve-horizon-gpt2-medium", dict(
        mode="horizon", preset="gpt2-medium", num_requests=32,
        mean_gap_steps=1.5, prompt_lens=(64, 384), new_tokens=64,
        num_slots=8, block_size=16, prefill_chunk=128)),
    # replica-fleet router availability: the same requests through one
    # undisturbed engine vs a 3-replica fleet with one replica crash-
    # killed mid-run — drained work must land on survivors with
    # identical greedy streams; ttft_p99_delta_ms is the drain's cost
    ("serve-router-smoke", dict(mode="router", num_requests=10,
                                mean_gap_steps=2.0, prompt_lens=(8, 24),
                                new_tokens=12, num_slots=2, block_size=8,
                                prefill_chunk=16, kill_step=12)),
    ("serve-router-gpt2-medium", dict(
        mode="router", preset="gpt2-medium", num_requests=24,
        mean_gap_steps=1.5, prompt_lens=(64, 256), new_tokens=48,
        num_slots=4, block_size=16, prefill_chunk=128, kill_step=40)),
    # SLO autoscaling: one seeded spiky load-gen population through a
    # fixed 1-replica fleet vs a policy fleet with the SLOController
    # active — the fixed fleet must violate the stated p99-TTFT SLO
    # through the spike and the policy fleet must hold it by scaling
    # up (replicas_high_water / autoscale_decisions registry-sourced)
    ("serve-autoscale-smoke", dict(mode="autoscale", num_slots=2,
                                   block_size=8, prefill_chunk=16,
                                   max_replicas=3, ttft_slo=12.0,
                                   phases=((6, 0.2), (60, 0.5),
                                           (30, 0.05)))),
    ("serve-autoscale-gpt2-medium", dict(
        mode="autoscale", preset="gpt2-medium", num_slots=4,
        block_size=16, prefill_chunk=64, max_replicas=3, ttft_slo=12.0,
        phases=((6, 0.2), (60, 0.5), (30, 0.05)))),
    # disaggregated prefill/decode at the same chip count: the mixed
    # rag+chat trace must violate at least one per-kind p99 SLO budget
    # on the monolithic fleet while the 1-prefill+1-decode split holds
    # ALL of them, with byte-identical tokens (migration resume is
    # exact) and zero compiles in the watched steady state
    ("serve-disagg-smoke", dict(mode="disagg", num_replicas=2,
                                num_slots=2, block_size=8,
                                num_blocks=24, prefill_chunk=8,
                                phases=((110, 0.27),), seed=3,
                                max_prompt=64)),
    ("serve-disagg-gpt2-medium", dict(
        mode="disagg", preset="gpt2-medium", num_replicas=2,
        num_slots=2, block_size=8, num_blocks=24, prefill_chunk=8,
        phases=((110, 0.27),), seed=3, max_prompt=64)),
    # multi-tenant LoRA serving: merged-single vs unmerged-single must
    # stream identically (the bit-parity contract), and the mixed
    # Zipf-tenant drive must match per-tenant merged references while
    # the constrained pool (smoke: 3 blocks < 4 tenants, pinned slots
    # can never exhaust it) reports loads/hits/evictions; the
    # ms_per_token delta is the gathered low-rank matmuls' price
    ("serve-lora-smoke", dict(mode="lora", num_requests=10,
                              mean_gap_steps=2.0, prompt_lens=(6, 14),
                              new_tokens=8, num_slots=2, block_size=8,
                              prefill_chunk=16, n_adapters=4, rank=4,
                              lora_pool_blocks=3)),
    ("serve-lora-gpt2-medium", dict(
        mode="lora", preset="gpt2-medium", num_requests=24,
        mean_gap_steps=1.5, prompt_lens=(16, 96), new_tokens=32,
        num_slots=4, block_size=16, prefill_chunk=64, n_adapters=4,
        rank=8)),
    # per-tenant cost attribution: a mixed base+LoRA population with
    # the cost accountant on — the row is each tenant's exact
    # FLOPs/HBM/block-seconds footprint and the conservation verdict
    # (sum of per-request footprints == global counters, per class)
    ("serve-cost-attrib-smoke", dict(mode="cost_attrib",
                                     num_requests=10,
                                     mean_gap_steps=2.0,
                                     prompt_lens=(6, 14), new_tokens=8,
                                     num_slots=2, block_size=8,
                                     prefill_chunk=16, n_adapters=2)),
    ("serve-cost-attrib-gpt2-medium", dict(
        mode="cost_attrib", preset="gpt2-medium", num_requests=24,
        mean_gap_steps=1.5, prompt_lens=(16, 96), new_tokens=32,
        num_slots=4, block_size=16, prefill_chunk=64, n_adapters=3)),
]


def _run(fn, name, **kw):
    """One configuration. The HBM guard's refusal is a printed skip; any
    other failure propagates and fails the run."""
    from deepspeed_tpu.utils.hbm import MemoryGuardError
    try:
        fn(name, **kw)
    except MemoryGuardError as e:
        print(json.dumps({"config": name, "skipped": "memory guard",
                          "why": str(e)[:300]}), flush=True)


def main():
    from deepspeed_tpu.utils import require_tpu, setup_compile_cache
    require_tpu("infer_bench")
    setup_compile_cache()
    for name, kw in CONFIGS:
        _run(bench_config, name, **kw)
    for name, kw in SPEC_CONFIGS:
        _run(bench_speculative, name, **kw)
    for name, kw in SERVE_CONFIGS:
        _run(bench_serving, name, **kw)
    for name, kw in SERVE_COMPARE_CONFIGS:
        kw = dict(kw)
        mode = kw.pop("mode", "impl")
        compare = {"prefix": bench_serving_prefix_compare,
                   "hosttier": bench_serving_hosttier_compare,
                   "spec": bench_serving_spec_compare,
                   "kvquant": bench_serving_kvquant_compare,
                   "router": bench_serving_router_compare,
                   "sampling": bench_serving_sampling_compare,
                   "autoscale": bench_serving_autoscale_compare,
                   "disagg": bench_serving_disagg_compare,
                   "lora": bench_serving_lora_compare,
                   "horizon": bench_serving_horizon_compare,
                   "cost_attrib": bench_serving_cost_attrib,
                   }.get(mode, bench_serving_impl_compare)
        _run(compare, name, **kw)


if __name__ == "__main__":
    main()
