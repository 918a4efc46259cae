"""Shared-prefix KV cache tests (tentpole: refcounted block sharing +
radix prefix index + copy-on-write in inference/paged_cache.py /
inference/prefix_index.py, wired through the serving scheduler).

Layers:
  1. PrefixIndex unit tests — radix insert/match, mid-block partial
     (COW candidate) matching, LRU order, leaf-only eviction;
  2. refcount allocator — sharing increments refcounts, blocks held by
     any slot are NEVER reclaimed, double-free/foreign ids raise,
     free() is idempotent, stats() reports block states;
  3. serving integration — warm-vs-cold token parity (the acceptance
     gate: prefix hits change WORK DONE, never tokens produced), COW
     divergence mid-block, preempt/requeue of a sharing request, the
     compile-count contract with the cache on, and seeded chaos on the
     ``cache.match`` / ``cache.cow`` fault sites.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.engine import InferenceEngine
from deepspeed_tpu.inference.paged_cache import (CacheExhausted,
                                                 PagedKVCache,
                                                 resolve_prefix_cache)
from deepspeed_tpu.inference.prefix_index import PrefixIndex
from deepspeed_tpu.inference.serving import ServeRequest, ServingEngine
from deepspeed_tpu.models import gpt
from deepspeed_tpu.utils import faults as faults_lib
from deepspeed_tpu.utils.faults import Fault


def tiny(**over):
    cfg = gpt.GPTConfig(vocab_size=128, n_layers=2, n_heads=4, d_model=32,
                        max_seq_len=64, use_flash_attention=False,
                        remat=False, dtype=jnp.float32, **over)
    params = gpt.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


@pytest.fixture(scope="module")
def eng(devices):
    cfg, params = tiny()
    return InferenceEngine(config=cfg, params=params, dtype=jnp.float32)


def _solo_refs(eng, prompts, n):
    return [eng.generate(p[None], max_new_tokens=n)[0] for p in prompts]


def toks(*vals):
    return np.asarray(vals, np.int32)


# ---------------------------------------------------------------------------
# PrefixIndex unit tests (pure host)
# ---------------------------------------------------------------------------

def test_index_insert_match_full_blocks():
    ix = PrefixIndex(block_size=4)
    t = np.arange(12, dtype=np.int32)
    assert ix.insert(t, [5, 6, 7]) == 3
    m = ix.match(t, max_tokens=12)
    assert m.block_ids == [5, 6, 7] and m.matched == 12
    assert m.cow_src is None
    # a shorter query stops at its own block boundary
    m = ix.match(t[:8], max_tokens=8)
    assert m.block_ids == [5, 6] and m.matched == 8
    # divergence at the FIRST token of a block: no chain past it
    t2 = t.copy()
    t2[4] = 99
    m = ix.match(t2, max_tokens=12)
    assert m.block_ids == [5] and m.matched == 4 and m.cow_src is None


def test_index_partial_match_is_cow_candidate():
    ix = PrefixIndex(block_size=4)
    t = np.arange(12, dtype=np.int32)
    ix.insert(t, [5, 6, 7])
    # diverges INSIDE block 1 (token 6): blocks [5] shared, block 6 is
    # the COW source with 2 reusable leading tokens
    t2 = t.copy()
    t2[6] = 99
    m = ix.match(t2, max_tokens=12)
    assert m.block_ids == [5] and m.cow_src == 6 and m.cow_tokens == 2
    assert m.matched == 4 + 2
    # max_tokens cap ends the match inside a fully-cached block: the
    # cached block becomes a COW source too (the len-1 admission cap)
    m = ix.match(t, max_tokens=11)
    assert m.block_ids == [5, 6] and m.cow_src == 7 and m.cow_tokens == 3
    # among sibling variants the LONGEST common run wins
    t3 = t.copy()
    t3[5] = 50
    ix.insert(t3, [5, 9, 0])              # only block 9 is new (chunk differs)
    q = t.copy()
    q[7] = 77
    m = ix.match(q, max_tokens=12)
    assert m.cow_src == 6 and m.cow_tokens == 3   # 3 common > t3's 1


def test_index_insert_dedups_and_rejects_reregistration():
    ix = PrefixIndex(block_size=4)
    t = np.arange(8, dtype=np.int32)
    assert ix.insert(t, [3, 4]) == 2
    # same chunks, different (private) blocks: nothing new registered
    assert ix.insert(t, [8, 9]) == 0
    assert ix.match(t, max_tokens=8).block_ids == [3, 4]
    # one physical block cannot serve two different chains
    with pytest.raises(ValueError, match="already registered"):
        ix.insert(toks(9, 9, 9, 9), [3])


def test_index_lru_leaf_only_eviction():
    ix = PrefixIndex(block_size=2)
    a = toks(1, 2, 3, 4)                  # chain 10 -> 11
    b = toks(1, 2, 9, 9)                  # chain 10 -> 12
    ix.insert(a, [10, 11])
    ix.insert(b, [10, 12])
    # interior node 10 is NOT evictable while its children live
    assert ix.pop_evictable(lambda bid: bid == 10) is None
    ix.match(b, max_tokens=4)             # touch 12 (and 10): 11 is LRU
    assert ix.pop_evictable(lambda bid: True) == 11
    assert ix.pop_evictable(lambda bid: True) == 12
    assert ix.pop_evictable(lambda bid: True) == 10   # exposed leaf last
    assert len(ix) == 0 and ix.pop_evictable(lambda bid: True) is None


def test_index_evictable_count_and_remove():
    ix = PrefixIndex(block_size=2)
    ix.insert(toks(1, 2, 3, 4), [5, 6])
    assert ix.evictable_count(lambda b: True) == 2
    assert ix.evictable_count(lambda b: b == 6) == 1
    assert not ix.remove_block(5)         # interior: refused
    assert ix.remove_block(6) and ix.remove_block(5)
    assert 5 not in ix and len(ix) == 0


# ---------------------------------------------------------------------------
# refcount allocator
# ---------------------------------------------------------------------------

def cache_of(num_blocks=16, block_size=4, watermark=0, **kw):
    cfg, _ = tiny()
    return PagedKVCache(cfg, num_slots=4, block_size=block_size,
                        num_blocks=num_blocks, dtype=jnp.float32,
                        watermark=watermark, prefix_cache=True, **kw)


def prefilled(c, slot, tokens):
    """allocate + pretend the prompt was prefilled + publish it."""
    m = c.allocate(slot, len(tokens), tokens=tokens)
    c.lengths[slot] = len(tokens)
    c.register_prefix(slot, tokens)
    return m


def test_allocator_sharing_increments_refcounts():
    c = cache_of()
    t = np.arange(16, dtype=np.int32)
    assert prefilled(c, 0, t) == 0                    # cold
    m = c.allocate(1, 16, tokens=t)
    # 3 full shared blocks + COW of the 4th (len-1 cap) = 15 tokens
    assert m == 15 and c.cow_copies == 1
    shared = c._owned[0][:3]
    assert c._owned[1][:3] == shared                  # same physical blocks
    assert all(c._refcount[b] == 2 for b in shared)
    assert c.shared_blocks == 3
    assert c.lengths[1] == 15                         # prefill resumes there
    c.free(1)
    assert all(c._refcount[b] == 1 for b in shared)   # slot 0 still holds
    assert c.active[0]


def test_allocator_eviction_never_reclaims_held_blocks():
    c = cache_of(num_blocks=8)
    t1 = np.arange(16, dtype=np.int32)
    prefilled(c, 0, t1)
    c.free(0)                                         # 4 blocks cached
    t2 = 100 + np.arange(16, dtype=np.int32)
    prefilled(c, 1, t2)                               # 4 fresh: pool full
    held = list(c._owned[1])
    t3 = 200 + np.arange(16, dtype=np.int32)
    c.allocate(2, 16, tokens=t3)                      # must reclaim cached LRU
    assert c.cache_block_evictions == 4
    assert c._owned[1] == held                        # held blocks untouched
    assert all(c._refcount[b] == 1 for b in held)
    assert set(c._owned[2]).isdisjoint(held)
    with pytest.raises(CacheExhausted):               # nothing reclaimable now
        c.allocate(3, 16)


def test_allocator_free_idempotent_and_hardened():
    c = cache_of()
    c.allocate(0, 8)
    bid = c._owned[0][0]
    c.free(0)
    c.free(0)                                         # idempotent no-op
    assert c.free_blocks == 16 and not c.active[0]
    with pytest.raises(ValueError, match="double free"):
        c._release(bid)
    with pytest.raises(ValueError, match="foreign block"):
        c._release(0)                                 # the trash block
    with pytest.raises(ValueError, match="foreign block"):
        c._release(999)
    with pytest.raises(ValueError, match="already allocated"):
        c.allocate(1, 4) or c.allocate(1, 4)
    with pytest.raises(ValueError, match="out of range"):
        c.allocate(7, 4)


def test_allocator_cached_blocks_revive_and_stats():
    c = cache_of()
    t = np.arange(16, dtype=np.int32)
    prefilled(c, 0, t)
    c.free(0)
    s = c.stats()
    assert s["held_blocks"] == 0 and s["cached_blocks"] == 4
    assert s["used_blocks"] == 4                      # cached still uses HBM
    m = c.allocate(1, 16, tokens=t)                   # revive from cache
    assert m == 15
    s = c.stats()
    assert s["prefix_hits"] == 1 and s["prefix_tokens_saved"] == 15
    assert s["held_blocks"] == 4                      # 3 shared + the COW copy
    assert 0.0 <= s["fragmentation"] <= 1.0
    assert s["num_blocks"] == s["free_blocks"] + s["used_blocks"]


def test_allocator_admission_charges_only_uncached_suffix():
    c = cache_of(num_blocks=6, watermark=1)
    t = np.arange(16, dtype=np.int32)                 # 4 blocks
    prefilled(c, 0, t)
    c.free(0)
    # a cold 16-token prompt needs 4 fresh of 6; cached blocks are
    # reclaimable so it fits — but the SAME prompt warm needs just 2
    # (1 COW + 1 suffix), leaving the watermark intact without reclaim
    assert c.blocks_needed(16, tokens=t) == 1         # 3 shared of 4
    assert c.can_admit(16, tokens=t)
    cold = 100 + np.arange(16, dtype=np.int32)
    assert c.blocks_needed(16, tokens=cold) == 4
    # available for a cold prompt counts reclaimable cached blocks
    assert c.available_blocks(tokens=cold) == 2 + 4   # 2 free + 4 cached
    # for the warm prompt the matched chain is excluded from reclaim
    assert c.available_blocks(tokens=t) == 2


def test_resolve_prefix_cache_env_knob(monkeypatch):
    monkeypatch.delenv("DS_PREFIX_CACHE", raising=False)
    assert resolve_prefix_cache(None) is False        # default off
    assert resolve_prefix_cache(True) is True
    monkeypatch.setenv("DS_PREFIX_CACHE", "on")
    assert resolve_prefix_cache(None) is True
    assert resolve_prefix_cache(False) is False       # explicit wins
    monkeypatch.setenv("DS_PREFIX_CACHE", "off")
    assert resolve_prefix_cache(None) is False
    monkeypatch.setenv("DS_PREFIX_CACHE", "sideways")
    with pytest.raises(ValueError, match="DS_PREFIX_CACHE"):
        resolve_prefix_cache(None)


# ---------------------------------------------------------------------------
# serving integration
# ---------------------------------------------------------------------------

SYS = np.arange(1, 25, dtype=np.int32)                # 24-token system prompt


def shared_prompts(n=4, tail=6, seed=0):
    r = np.random.default_rng(seed)
    return [np.concatenate([SYS, r.integers(1, 128, tail).astype(np.int32)])
            for _ in range(n)]


def serve(eng, prompts, prefix_cache, n_new=8, **kw):
    srv = ServingEngine(eng, num_slots=2, block_size=8, num_blocks=24,
                        prefill_chunk=16, prefix_cache=prefix_cache, **kw)
    out = srv.run([ServeRequest(rid=i, prompt=p, max_new_tokens=n_new)
                   for i, p in enumerate(prompts)])
    return srv, out


def test_serving_warm_vs_cold_token_parity(eng):
    """The acceptance gate: with a shared system prompt the warm path
    reports prefix hits and does FEWER prefill chunks, and every output
    token is identical to the cold (prefix-cache-off) run."""
    prompts = shared_prompts()
    cold, cold_out = serve(eng, prompts, prefix_cache=False)
    warm, warm_out = serve(eng, prompts, prefix_cache=True)
    for i in range(len(prompts)):
        np.testing.assert_array_equal(warm_out[i], cold_out[i])
    assert warm.stats["prefix_hits"] > 0
    assert warm.stats["prefix_tokens_saved"] > 0
    assert warm.stats["prefill_chunks"] < cold.stats["prefill_chunks"]
    assert cold.stats["prefix_hits"] == 0             # off = today's behavior
    # ... and both match the static engine exactly
    refs = _solo_refs(eng, prompts, 8)
    for i, ref in enumerate(refs):
        np.testing.assert_array_equal(warm_out[i], ref)


def test_serving_cow_divergence_mid_block_parity(eng):
    """Two prompts diverging INSIDE a block: the second request reuses
    the common full blocks, copy-on-writes the divergent one, and still
    matches its solo greedy stream bit-for-bit."""
    base = np.arange(1, 31, dtype=np.int32)           # 30 tokens, bs=8
    div = base.copy()
    div[21] = 99                                      # inside block 2
    srv = ServingEngine(eng, num_slots=2, block_size=8, num_blocks=24,
                        prefill_chunk=16, prefix_cache=True)
    out1 = srv.run([ServeRequest(rid="a", prompt=base, max_new_tokens=8)])
    out2 = srv.run([ServeRequest(rid="b", prompt=div, max_new_tokens=8)])
    assert srv.cache.cow_copies == 1
    assert srv.stats["prefix_hits"] == 1
    # blocks 0,1 shared + 5 leading tokens of block 2 via the copy
    assert srv.stats["prefix_tokens_saved"] == 2 * 8 + 5
    ref_a, ref_b = _solo_refs(eng, [base, div], 8)
    np.testing.assert_array_equal(out1["a"], ref_a)
    np.testing.assert_array_equal(out2["b"], ref_b)


def test_serving_preempt_requeue_of_sharing_request(eng):
    """A request MAPPING shared blocks can be preempted and resumed:
    free() drops its references (the donor's blocks survive), resume
    re-matches the cache and parity holds."""
    prompts = shared_prompts(n=3, tail=8, seed=3)
    refs = _solo_refs(eng, prompts, 10)
    srv = ServingEngine(eng, num_slots=2, block_size=4, num_blocks=14,
                        prefill_chunk=16, prefix_cache=True)
    srv.cache.watermark = 0
    # warm the index, then run two sharing requests in a pool tight
    # enough that decode growth forces a preemption
    out0 = srv.run([ServeRequest(rid=0, prompt=prompts[0],
                                 max_new_tokens=10)])
    out = srv.run([ServeRequest(rid=1, prompt=prompts[1],
                                max_new_tokens=10),
                   ServeRequest(rid=2, prompt=prompts[2],
                                max_new_tokens=10)])
    assert srv.stats["evictions"] >= 1                # it really preempted
    assert srv.stats["prefix_hits"] >= 2              # they really shared
    np.testing.assert_array_equal(out0[0], refs[0])
    np.testing.assert_array_equal(out[1], refs[1])
    np.testing.assert_array_equal(out[2], refs[2])
    # exactly-once, all done, and no leaked references after drain
    assert all(r.state == "done" for r in srv.finished)
    assert srv.cache.held_blocks == 0


def test_serving_compile_contract_with_prefix_cache(devices):
    """Compile-count contract, prefix cache ON: after warmup the steady
    state compiles NOTHING — admissions with prefix hits, COW copies
    and LRU block reclaim are all host-side or pre-warmed. Each slot
    program (and the COW copy) stays at exactly one executable (fresh
    engine: the strict cache_size pin needs an unshared jit cache)."""
    from deepspeed_tpu.utils.compile_guard import CompileWatch, cache_size
    cfg, params = tiny()
    eng = InferenceEngine(config=cfg, params=params, dtype=jnp.float32)
    base = np.arange(1, 31, dtype=np.int32)
    div = base.copy()
    div[21] = 99
    srv = ServingEngine(eng, num_slots=2, block_size=8, num_blocks=24,
                        prefill_chunk=16, prefix_cache=True)
    srv.run([ServeRequest(rid=0, prompt=base, max_new_tokens=4)])
    watch = CompileWatch(max_compiles=0, label="prefix-cache steady state")
    with watch:
        srv.run([ServeRequest(rid=1, prompt=base, max_new_tokens=4)])
        srv.run([ServeRequest(rid=2, prompt=div, max_new_tokens=4)])
    assert srv.cache.cow_copies >= 1                  # COW ran inside watch
    assert srv.stats["prefix_hits"] >= 2
    # under DS_KV_QUANT=int8 / DS_LORA_SERVE=on the active set is the
    # int8 / adapter entry of the same callables — the per-program count
    # contract is the same (COW copies blocks, not adapters)
    pf, dc, cw = eng._prefill_slot, eng._decode_slots, eng._cow_blocks
    n_prefill = cache_size(pf)
    if n_prefill is not None:
        assert n_prefill == 1
        assert cache_size(dc) == 1
        assert cache_size(cw) == 1


def test_serving_env_knob_smoke(eng):
    """gate.sh smoke: prefix_cache=None resolves DS_PREFIX_CACHE from
    the ambient environment; parity vs the static engine must hold
    whichever way the knob points."""
    prompts = shared_prompts(n=2, tail=4, seed=5)
    refs = _solo_refs(eng, prompts, 4)
    srv, out = serve(eng, prompts, prefix_cache=None, n_new=4)
    assert srv.prefix_cache == resolve_prefix_cache(None)
    for i, ref in enumerate(refs):
        np.testing.assert_array_equal(out[i], ref)


# ---------------------------------------------------------------------------
# chaos: the new fault sites
# ---------------------------------------------------------------------------

def test_chaos_match_fault_degrades_to_cold_miss(eng):
    """An injected ``cache.match`` exhaustion turns that admission into
    a cold miss: no sharing for THAT request, full parity for all."""
    prompts = shared_prompts(n=3, tail=4, seed=7)
    refs = _solo_refs(eng, prompts, 6)
    with faults_lib.injected(
            Fault("cache.match", "cache_exhausted", step=1), seed=0) as inj:
        srv = ServingEngine(eng, num_slots=1, block_size=8, num_blocks=24,
                            prefill_chunk=16, prefix_cache=True)
        out = srv.run([ServeRequest(rid=i, prompt=p, max_new_tokens=6)
                       for i, p in enumerate(prompts)])
    assert ("cache.match", "cache_exhausted", 1) in inj.fired
    # request 0 cold (nothing cached), request 1 degraded by the fault,
    # request 2 hits — so exactly ONE hit, not two
    assert srv.stats["prefix_hits"] == 1
    for i, ref in enumerate(refs):
        np.testing.assert_array_equal(out[i], ref)


def test_chaos_cow_fault_fails_admission_then_recovers(eng):
    """An injected ``cache.cow`` exhaustion aborts that admission BEFORE
    any bookkeeping mutates (no leaked refcounts); the request retries
    next step, the COW succeeds, and parity holds."""
    base = np.arange(1, 31, dtype=np.int32)
    div = base.copy()
    div[21] = 99
    refs = _solo_refs(eng, [base, div], 6)
    with faults_lib.injected(
            Fault("cache.cow", "cache_exhausted", step=0), seed=0) as inj:
        srv = ServingEngine(eng, num_slots=2, block_size=8, num_blocks=24,
                            prefill_chunk=16, prefix_cache=True)
        out0 = srv.run([ServeRequest(rid=0, prompt=base, max_new_tokens=6)])
        out1 = srv.run([ServeRequest(rid=1, prompt=div, max_new_tokens=6)])
    assert ("cache.cow", "cache_exhausted", 0) in inj.fired
    assert srv.cache.cow_copies == 1                  # the retry copied
    np.testing.assert_array_equal(out0[0], refs[0])
    np.testing.assert_array_equal(out1[1], refs[1])
    # no leaked references: after the drain every refcount is back to 0
    # (the faulted attempt claimed nothing — it fired before bookkeeping)
    assert srv.cache.held_blocks == 0
    assert (srv.cache._refcount == 0).all()
