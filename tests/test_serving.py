"""Continuous-batching serving tests: paged-cache allocator unit tests,
greedy token parity vs the static engine, staggered arrivals joining a
running decode batch, and eviction/requeue on cache exhaustion
(tentpole: inference/paged_cache.py + inference/serving.py; analog of
vLLM's PagedAttention + Orca iteration-level scheduling over the
reference's static KV-cache workspace)."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.engine import InferenceEngine
from deepspeed_tpu.inference.paged_cache import CacheExhausted, PagedKVCache
from deepspeed_tpu.inference.serving import ServeRequest, ServingEngine
from deepspeed_tpu.models import gpt


def tiny(**over):
    cfg = gpt.GPTConfig(vocab_size=128, n_layers=2, n_heads=4, d_model=32,
                        max_seq_len=64, use_flash_attention=False,
                        remat=False, dtype=jnp.float32, **over)
    params = gpt.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def prompts_of(lengths, seed=1):
    r = np.random.default_rng(seed)
    return [r.integers(1, 128, n).astype(np.int32) for n in lengths]


# ---------------------------------------------------------------------------
# allocator unit tests
# ---------------------------------------------------------------------------

def test_paged_allocator_alloc_append_free(devices):
    cfg, _ = tiny()
    c = PagedKVCache(cfg, num_slots=2, block_size=4, num_blocks=6)
    assert c.free_blocks == 6 and c.used_blocks == 0
    c.allocate(0, 5)                     # 2 blocks
    assert c.free_blocks == 4 and c.used_blocks == 2
    assert (c.tables[0, :2] > 0).all()   # block 0 is the reserved trash
    c.advance(0, 5)
    c.ensure_capacity(0, 8)              # still inside block 2
    assert c.used_blocks == 2
    c.ensure_capacity(0, 9)              # crosses into a third block
    assert c.used_blocks == 3
    c.allocate(1, 4)
    assert c.free_blocks == 2
    c.free(0)
    assert c.free_blocks == 5 and not c.active[0]
    assert (c.tables[0] == 0).all() and c.lengths[0] == 0
    # freed blocks are reusable
    c.allocate(0, 20)                    # 5 blocks
    assert c.free_blocks == 0


def test_paged_allocator_exhaustion_and_watermark(devices):
    cfg, _ = tiny()
    c = PagedKVCache(cfg, num_slots=2, block_size=4, num_blocks=3,
                     watermark=1)
    with pytest.raises(CacheExhausted):
        c.allocate(0, 16)                # 4 blocks > 3 free
    c.allocate(0, 12)
    with pytest.raises(CacheExhausted):
        c.ensure_capacity(0, 13)         # free list empty
    # admission watermark: 3 free again after free(), but 1 is reserved
    c.free(0)
    assert c.can_admit(8) and not c.can_admit(12)


def test_paged_allocator_hardening_and_stats(devices):
    """Hardened bookkeeping in the DEFAULT (prefix-off) mode: free() is
    idempotent, double-free/foreign block ids raise instead of silently
    corrupting the pool, re-allocating an occupied slot raises, and
    stats() reports block states + fragmentation for bench rows."""
    cfg, _ = tiny()
    c = PagedKVCache(cfg, num_slots=2, block_size=4, num_blocks=6)
    c.allocate(0, 5)                     # 2 blocks, 5 tokens pending
    with pytest.raises(ValueError, match="already allocated"):
        c.allocate(0, 4)
    c.advance(0, 5)
    s = c.stats()
    assert s["used_blocks"] == 2 and s["free_blocks"] == 4
    assert s["held_blocks"] == 2
    assert s["shared_blocks"] == 0 and s["cached_blocks"] == 0
    assert s["fragmentation"] == round(1 - 5 / 8, 4)  # 5 of 8 written
    bid = c._owned[0][0]
    c.free(0)
    c.free(0)                            # idempotent: freeing twice is ok
    assert c.free_blocks == 6 and c.stats()["fragmentation"] == 0.0
    with pytest.raises(ValueError, match="double free"):
        c._release(bid)
    with pytest.raises(ValueError, match="foreign block"):
        c._release(0)                    # the reserved trash block
    with pytest.raises(ValueError, match="out of range"):
        c.allocate(5, 4)


def test_paged_cache_hbm_budget_watermark(devices):
    """num_blocks derives from an HBM budget via the per-token cache
    cost, and the usage accounting scales with tokens in flight."""
    cfg, _ = tiny()
    per_tok = gpt.kv_bytes_per_token(cfg, jnp.float32)
    budget = per_tok * 4 * 10            # exactly 10 4-token blocks
    # kv_quant pinned off: this pins the FP pool's budget arithmetic
    # (the int8 layout's budget math lives in test_kv_quant.py)
    c = PagedKVCache(cfg, num_slots=2, block_size=4,
                     hbm_budget_bytes=budget, dtype=jnp.float32,
                     kv_quant="off")
    assert c.free_blocks == 10
    c.allocate(0, 6)
    assert c.used_block_bytes() == 2 * 4 * per_tok
    # static equivalent for 2 slots reserves 2 * S_max tokens
    assert c.static_equivalent_bytes(2) == 2 * 64 * per_tok
    with pytest.raises(ValueError):
        PagedKVCache(cfg, num_slots=1, block_size=4, hbm_budget_bytes=1)


# ---------------------------------------------------------------------------
# greedy token parity: paged + continuous batching == static generate
# ---------------------------------------------------------------------------

def _solo_refs(eng, prompts, n):
    return [eng.generate(p[None], max_new_tokens=n)[0] for p in prompts]


def test_serving_greedy_parity(devices):
    """Mixed prompt lengths through the paged continuous-batching path
    reproduce static-batch generate token-for-token (zero tolerance)."""
    cfg, params = tiny()
    eng = InferenceEngine(config=cfg, params=params, dtype=jnp.float32)
    prompts = prompts_of((5, 9, 12, 3))
    refs = _solo_refs(eng, prompts, 6)
    srv = ServingEngine(eng, num_slots=2, block_size=4, num_blocks=24,
                        prefill_chunk=8)
    out = srv.run([ServeRequest(rid=i, prompt=p, max_new_tokens=6)
                   for i, p in enumerate(prompts)])
    for i, ref in enumerate(refs):
        np.testing.assert_array_equal(out[i], ref)
    assert srv.stats["completed"] == 4
    # decode batching really happened (two requests in one decode step)
    assert srv.stats["peak_occupancy"] > 1


def test_serving_parity_rotary_gqa_window(devices):
    """The paged decode composes with the full serving feature stack:
    rotary positions, grouped KV heads, sliding-window masking."""
    cfg, _ = tiny()
    cfg = dataclasses.replace(cfg, rotary_dim=4, use_wpe=False,
                              n_kv_heads=2, attn_window=6)
    params = gpt.init_params(jax.random.PRNGKey(0), cfg)
    eng = InferenceEngine(config=cfg, params=params, dtype=jnp.float32)
    prompts = prompts_of((4, 10, 7), seed=7)
    refs = _solo_refs(eng, prompts, 5)
    srv = ServingEngine(eng, num_slots=3, block_size=4, num_blocks=30,
                        prefill_chunk=4)
    out = srv.run([ServeRequest(rid=i, prompt=p, max_new_tokens=5)
                   for i, p in enumerate(prompts)])
    for i, ref in enumerate(refs):
        np.testing.assert_array_equal(out[i], ref)
    # GQA pool really is grouped: a row holds 2 kv heads, not 4
    assert srv.cache.k.shape[3] == 2 * cfg.head_dim


def test_serving_prefill_chunking_long_prompt(devices):
    """A prompt longer than the chunk width prefills across iterations
    and still matches the static one-shot prefill."""
    cfg, params = tiny()
    eng = InferenceEngine(config=cfg, params=params, dtype=jnp.float32)
    prompts = prompts_of((23,), seed=3)
    refs = _solo_refs(eng, prompts, 4)
    srv = ServingEngine(eng, num_slots=2, block_size=4, num_blocks=20,
                        prefill_chunk=5)
    out = srv.run([ServeRequest(rid=0, prompt=prompts[0],
                                max_new_tokens=4)])
    np.testing.assert_array_equal(out[0], refs[0])
    assert srv.stats["prefill_chunks"] == 5  # ceil(23/5)


def test_serving_eos_stop(devices):
    """Per-request stop conditions: an eos hit frees the slot early."""
    cfg, params = tiny()
    eng = InferenceEngine(config=cfg, params=params, dtype=jnp.float32)
    p = prompts_of((6,), seed=2)[0]
    ref = _solo_refs(eng, [p], 8)[0]
    eos = int(ref[len(p) + 2])           # a token generate() really emits
    # serving stops at the FIRST eos occurrence in the generated region
    first = len(p) + int(np.argmax(ref[len(p):] == eos))
    srv = ServingEngine(eng, num_slots=1, block_size=4, num_blocks=12)
    out = srv.run([ServeRequest(rid=0, prompt=p, max_new_tokens=8,
                                eos_id=eos)])
    assert len(out[0]) < len(ref)        # it actually stopped early
    np.testing.assert_array_equal(out[0], ref[:first + 1])


# ---------------------------------------------------------------------------
# scheduler: staggered arrivals, admission, eviction
# ---------------------------------------------------------------------------

def test_serving_staggered_arrival_joins_running_batch(devices):
    """A request arriving mid-decode joins the running batch (occupancy
    2) instead of waiting for the first to drain — the continuous-
    batching acceptance gate — and both outputs stay parity-exact."""
    cfg, params = tiny()
    eng = InferenceEngine(config=cfg, params=params, dtype=jnp.float32)
    p1, p2 = prompts_of((6, 8), seed=11)
    ref1 = _solo_refs(eng, [p1], 12)[0]
    ref2 = _solo_refs(eng, [p2], 6)[0]
    # spec and the decode horizon pinned to the one-token-per-step
    # cadence: the step-4 arrival must catch r1 mid-decode (spec timing
    # and N>1 cadence have their own suites)
    srv = ServingEngine(eng, num_slots=2, block_size=4, num_blocks=24,
                        prefill_chunk=8, spec_decode=False,
                        decode_horizon=1)
    srv.submit(ServeRequest(rid="r1", prompt=p1, max_new_tokens=12), now=0)
    occ = []
    step = 0
    while srv.busy:
        if step == 4:                    # r1 is mid-decode by now
            srv.submit(ServeRequest(rid="r2", prompt=p2,
                                    max_new_tokens=6), now=step)
        occ.append(srv.step(step))
        step += 1
    assert max(occ) == 2                 # r2 decoded alongside r1
    done = {r.rid: r for r in srv.finished}
    np.testing.assert_array_equal(done["r1"].tokens, ref1)
    np.testing.assert_array_equal(done["r2"].tokens, ref2)
    # r2 produced its first token before r1 finished
    assert done["r2"].first_token_at < done["r1"].finished_at


def test_serving_admission_blocks_when_cache_full(devices):
    """Admission control: with only enough blocks for one request, the
    second waits in the queue (no slot claim, no OOM) and runs after."""
    cfg, params = tiny()
    eng = InferenceEngine(config=cfg, params=params, dtype=jnp.float32)
    p1, p2 = prompts_of((8, 8), seed=4)
    refs = [_solo_refs(eng, [p], 4)[0] for p in (p1, p2)]
    # 5 blocks: request needs 2(prompt)+1(decode); watermark=2 keeps the
    # second request queued until the first frees its blocks
    srv = ServingEngine(eng, num_slots=2, block_size=4, num_blocks=5)
    out = srv.run([ServeRequest(rid=i, prompt=p, max_new_tokens=4)
                   for i, p in enumerate((p1, p2))])
    assert srv.stats["peak_occupancy"] == 1
    for i in range(2):
        np.testing.assert_array_equal(out[i], refs[i])


def test_serving_eviction_requeue_parity(devices):
    """Cache exhaustion mid-decode evicts the youngest request and
    requeues it (recompute-on-resume) — outputs still parity-exact."""
    cfg, params = tiny()
    eng = InferenceEngine(config=cfg, params=params, dtype=jnp.float32)
    p1, p2 = prompts_of((10, 9), seed=9)
    ref1 = _solo_refs(eng, [p1], 12)[0]
    ref2 = _solo_refs(eng, [p2], 10)[0]
    # deliberately tight pool + zero watermark: both admit, then decode
    # growth exhausts the free list and forces a preemption
    srv = ServingEngine(eng, num_slots=2, block_size=4, num_blocks=7)
    srv.cache.watermark = 0
    out = srv.run([ServeRequest(rid="a", prompt=p1, max_new_tokens=12),
                   ServeRequest(rid="b", prompt=p2, max_new_tokens=10)])
    assert srv.stats["evictions"] >= 1
    np.testing.assert_array_equal(out["a"], ref1)
    np.testing.assert_array_equal(out["b"], ref2)


def test_serving_int8_compose(devices):
    """Weight-only int8 engines serve through the paged path (the
    DS_INT8_FUSED dense entries carry {"q","scale"} instead of
    {"kernel"}): parity against the SAME quantized engine's static
    generate."""
    cfg, params = tiny()
    eng = InferenceEngine(config=cfg, params=params, dtype=jnp.int8)
    assert eng.quantized
    prompts = prompts_of((6, 9), seed=13)
    refs = _solo_refs(eng, prompts, 5)
    srv = ServingEngine(eng, num_slots=2, block_size=4, num_blocks=20)
    out = srv.run([ServeRequest(rid=i, prompt=p, max_new_tokens=5)
                   for i, p in enumerate(prompts)])
    for i, ref in enumerate(refs):
        np.testing.assert_array_equal(out[i], ref)


def test_serving_compile_count_contract(devices):
    """The serving perf contract as an executable assert: steady state
    is exactly TWO compiled programs (_prefill_slot, _decode_slots) and
    ZERO recompiles across admission, chunked prefill, eviction and
    requeue.  The warmup run compiles everything once (including the
    per-slot eager emit slices — both slots see traffic); the second,
    identical workload must then compile NOTHING."""
    from deepspeed_tpu.utils.compile_guard import CompileWatch, cache_size
    cfg, params = tiny()
    eng = InferenceEngine(config=cfg, params=params, dtype=jnp.float32)
    p1, p2 = prompts_of((10, 9), seed=9)

    def run_workload():
        # tight pool + zero watermark: both requests admit, decode
        # growth exhausts the free list, the youngest evicts + requeues.
        # spec and the decode horizon pinned off: this pins the PLAIN
        # decode program contract (the spec form lives in
        # test_spec_serving.py, the _decode_horizon family in
        # test_horizon.py)
        srv = ServingEngine(eng, num_slots=2, block_size=4, num_blocks=7,
                            prefill_chunk=8, spec_decode=False,
                            decode_horizon=1)
        srv.cache.watermark = 0
        out = srv.run([ServeRequest(rid="a", prompt=p1, max_new_tokens=12),
                       ServeRequest(rid="b", prompt=p2, max_new_tokens=10)])
        return srv, out

    srv, warm_out = run_workload()
    assert srv.stats["evictions"] >= 1     # the workload really preempts
    # exactly two compiled serving programs after warmup — one prefill
    # (chunks are padded to prefill_chunk, so ONE shape) and one decode.
    # Under DS_KV_QUANT=int8 / DS_LORA_SERVE=on the active set is the
    # int8 / adapter entry of the same two callables; the program COUNT
    # contract is identical in every mode
    pf, dc = eng._prefill_slot, eng._decode_slots
    n_prefill = cache_size(pf)
    n_decode = cache_size(dc)
    if n_prefill is not None:
        assert (n_prefill, n_decode) == (1, 1), (
            f"serving steady state fragmented: prefill={n_prefill} "
            f"decode={n_decode} compiled programs (expected 1+1)")

    watch = CompileWatch(max_compiles=0, label="serving steady state")
    watch.wrap(pf)
    watch.wrap(dc)
    with watch:                            # raises RecompileError on exit
        srv2, out = run_workload()         # if anything compiled
    assert srv2.stats["evictions"] >= 1
    for rid in ("a", "b"):                 # still the right tokens
        np.testing.assert_array_equal(out[rid], warm_out[rid])
    if n_prefill is not None:
        assert cache_size(pf) == 1
        assert cache_size(dc) == 1


# One callable per program family; the int8 and adapter variants are
# cache entries of it (inference/engine.py). ``mode`` is the serving
# configuration that runs a family in steady state.
_FAMILIES = ("prefill_slot", "decode_slots", "verify_slots",
             "decode_horizon")
_MODE_OF = {"prefill_slot": "plain", "decode_slots": "plain",
            "verify_slots": "spec", "decode_horizon": "horizon"}
# cache entries of each family's callable after a run in a mode: a run is
# prefill plus ONE decode form (verify and the horizon REPLACE decode)
_ENTRIES = {"plain": (1, 1, 0, 0), "spec": (1, 0, 1, 0),
            "horizon": (1, 0, 0, 1)}


@functools.lru_cache(maxsize=None)
def _variant_run(variant, mode):
    """Serve one workload twice on a fresh engine under ``variant``
    ("fp", "q" int8 pools, "l" adapters, "ql" both) in ``mode``; returns
    ({family: cache entries after the first run, or None where jax does
    not say}, compiles counted during the second run)."""
    from deepspeed_tpu.runtime.lora import add_lora, adapter_state_dict
    from deepspeed_tpu.utils.compile_guard import CompileWatch, cache_size
    cfg, params = tiny()
    eng = InferenceEngine(config=cfg, params=params, dtype=jnp.float32)
    kw = dict(num_slots=2, block_size=4, num_blocks=16, prefill_chunk=8,
              kv_quant="int8" if "q" in variant else "off",
              spec_decode=mode == "spec",
              decode_horizon=4 if mode == "horizon" else 1)
    lora = "l" in variant
    if lora:
        kw.update(lora_serve=True, lora_pool_blocks=2, lora_max_rank=4,
                  lora_rank_block=4)
    else:
        kw["lora_serve"] = False
    p1, p2 = prompts_of((10, 9), seed=9)
    if mode == "spec":                  # repetitive: the drafter proposes
        p1, p2 = np.tile(p1[:3], 4), np.tile(p2[:3], 3)

    def run_workload():
        srv = ServingEngine(eng, **kw)
        extra = {}
        if lora:
            srv.register_adapter("t", adapter_state_dict(add_lora(
                params, rng=jax.random.PRNGKey(3), rank=4, alpha=8.0)))
            extra["adapter_id"] = "t"   # request b stays base-only
        return srv.run([
            ServeRequest(rid="a", prompt=p1, max_new_tokens=9, **extra),
            ServeRequest(rid="b", prompt=p2, max_new_tokens=7)])

    warm = run_workload()
    programs = {f: getattr(eng, "_" + f) for f in _FAMILIES}
    entries = {f: cache_size(fn) for f, fn in programs.items()}
    watch = CompileWatch(max_compiles=None,
                         label=f"{variant} {mode} steady state")
    for fn in programs.values():
        watch.wrap(fn)
    with watch:
        again = run_workload()
    for rid in warm:
        np.testing.assert_array_equal(again[rid], warm[rid])
    return entries, watch.compiles


@pytest.mark.parametrize("family", _FAMILIES)
@pytest.mark.parametrize("variant", ["fp", "q", "l", "ql"])
def test_variant_is_one_cache_entry_of_its_family(devices, variant, family):
    """Serving under a variant adds exactly ONE cache entry to the
    family's single callable, none to a family the run does not use, and
    the steady state compiles nothing: the int8 scale pools and the
    adapter operands are pytree operands of one program, not programs of
    their own."""
    mode = _MODE_OF[family]
    entries, steady_compiles = _variant_run(variant, mode)
    assert steady_compiles == 0
    if entries[family] is None:         # this jax does not expose it
        return
    assert entries[family] == 1, entries
    assert tuple(entries[f] for f in _FAMILIES) == _ENTRIES[mode], entries


@pytest.mark.parametrize("program", ["cow_blocks", "gather_blocks",
                                     "scatter_block"])
def test_block_copy_moves_the_scales_with_the_payload(devices, program):
    """Each block copy is ONE program over the tuple of pools: handed an
    int8 cache's four pools, it moves a block's scales with its payload
    (a copied, spilled or restored block dequantizes as it was written),
    and the fp call is a second entry of the same callable."""
    from deepspeed_tpu.utils.compile_guard import cache_size
    cfg, params = tiny()
    eng = InferenceEngine(config=cfg, params=params, dtype=jnp.float32)
    c = PagedKVCache(cfg, num_slots=1, block_size=4, num_blocks=6,
                     dtype=jnp.float32, kv_quant="int8")
    r = np.random.default_rng(0)
    c.pools = tuple(
        jnp.asarray(r.integers(-100, 100, p.shape), p.dtype)
        if p.dtype == jnp.int8
        else jnp.asarray(r.uniform(0.1, 1.0, p.shape), p.dtype)
        for p in c.pools)
    assert len(c.pools) == 4
    before = [np.asarray(p) for p in c.pools]
    if program == "cow_blocks":
        after = eng.cow_blocks(c.pools, 2, 5)
        for a, b in zip(after, before):
            np.testing.assert_array_equal(np.asarray(a)[:, 5], b[:, 2])
            np.testing.assert_array_equal(np.asarray(a)[:, :5], b[:, :5])
    elif program == "gather_blocks":
        got = eng.gather_blocks(c.pools, np.array([3, 1], np.int32))
        assert len(got) == 4
        for g, b in zip(got, before):
            np.testing.assert_array_equal(np.asarray(g), b[:, [3, 1]])
    else:
        blocks = tuple(jnp.asarray(b[:, 4]) for b in before)
        after = eng.scatter_block(c.pools, blocks, 1)
        for a, b in zip(after, before):
            np.testing.assert_array_equal(np.asarray(a)[:, 1], b[:, 4])
            np.testing.assert_array_equal(np.asarray(a)[:, 2:], b[:, 2:])
    fn = getattr(eng, "_" + program)
    if cache_size(fn) is not None:
        assert cache_size(fn) == 1
        # the same callable on an fp cache's two pools: one more entry
        f = PagedKVCache(cfg, num_slots=1, block_size=4, num_blocks=6,
                         dtype=jnp.float32)
        args = {"cow_blocks": (0, 0),
                "gather_blocks": (np.zeros(2, np.int32),),
                "scatter_block": (tuple(p[:, 0] for p in f.pools), 0)}
        out = getattr(eng, program)(f.pools, *args[program])
        assert len(out) == 2 and cache_size(fn) == 2


def test_serving_rejects_oversized_request(devices):
    cfg, params = tiny()
    eng = InferenceEngine(config=cfg, params=params, dtype=jnp.float32)
    srv = ServingEngine(eng, num_slots=1, block_size=4, num_blocks=8)
    with pytest.raises(ValueError, match="max_seq_len"):
        srv.submit(ServeRequest(rid=0, prompt=np.ones(60, np.int32),
                                max_new_tokens=30))


def test_serving_wall_clock_latency_stamps_share_one_clock(devices):
    """run(wall_clock=True) stamps submission with the SAME clock as
    token emission — submitted_at <= first_token_at <= finished_at, all
    positive perf_counter instants, so latency percentiles derived from
    the stamps are meaningful (the skew bug: submit stamped 0.0 while
    tokens got perf_counter values, making TTFT equal absolute time)."""
    cfg, params = tiny()
    eng = InferenceEngine(config=cfg, params=params, dtype=jnp.float32)
    srv = ServingEngine(eng, num_slots=2, block_size=4, num_blocks=24)
    prompts = prompts_of((5, 7), seed=21)
    srv.run([ServeRequest(rid=i, prompt=p, max_new_tokens=4)
             for i, p in enumerate(prompts)], wall_clock=True)
    for r in srv.finished:
        assert r.submitted_at > 0.0              # not the 0.0 sentinel
        assert r.submitted_at <= r.first_token_at <= r.finished_at
        # a sane TTFT: well under a minute, not "seconds since boot"
        assert r.first_token_at - r.submitted_at < 60.0
        assert all(t >= r.submitted_at for t in r.token_times)


def test_serving_non_drain_raises_degraded_with_partial_results(devices):
    """run() hitting max_steps attaches everything finished so far plus
    an in-flight snapshot instead of discarding it."""
    from deepspeed_tpu.inference.serving import DegradedError
    cfg, params = tiny()
    eng = InferenceEngine(config=cfg, params=params, dtype=jnp.float32)
    p1, p2 = prompts_of((5, 6), seed=17)
    ref2 = _solo_refs(eng, [p2], 2)[0]
    # horizon pinned: the max_steps=5 non-drain budget is calibrated to
    # one token per step (a fused horizon would drain inside it)
    srv = ServingEngine(eng, num_slots=2, block_size=4, num_blocks=24,
                        decode_horizon=1)
    with pytest.raises(DegradedError, match="did not drain") as ei:
        srv.run([ServeRequest(rid="slowpoke", prompt=p1,
                              max_new_tokens=30),
                 ServeRequest(rid="quick", prompt=p2, max_new_tokens=2)],
                max_steps=5)
    e = ei.value
    np.testing.assert_array_equal(e.results["quick"], ref2)
    assert [p["rid"] for p in e.pending] == ["slowpoke"]
    assert e.pending[0]["generated"] > 0         # its work is visible
    assert e.stats["steps"] == 6                 # ran to the cap, then raised
