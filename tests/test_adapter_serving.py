"""Multi-tenant LoRA adapter serving tests (tentpole:
inference/adapters.py + the lora-serve integration in
inference/serving.py — the S-LoRA / Punica workload shape over this
repo's paged continuous-batching stack).

Layers:
  1. adapter-pool unit tests — registration validation, rank-block
     paging, refcount pinning, LRU eviction of released residents,
     exhaustion when every block is pinned;
  2. serving parity — a single unmerged adapter streams token-identical
     to the SAME adapter merged into the weights (``merge_lora``), a
     base-only slot in a lora-on engine stays identical to the
     pre-subsystem base stream, and a heterogeneous batch (two tenants
     + base in one decode batch) matches each tenant's merged
     reference;
  3. lifecycle — eviction/reload round-trips, drain snapshots carrying
     ``adapter_id`` into a fresh engine, failed loads degrading to
     ``state="error"`` (never wrong tokens) with the pool intact;
  4. the compile contract — the ``_l`` program set holds a fixed
     steady-state count with ZERO recompiles across adapter swaps,
     base-only slots and tenants registered after warmup
     (``CompileWatch(0)``), and stays COLD with the subsystem off;
  5. interplay — prefix-cache bypass both ways for adapter-carrying
     requests, speculative decode and int8 KV pools composing with
     adapters, router adapter-affinity dispatch.

One module-scoped engine pair (base + two merged references) backs
every test except the compile contract, which needs unshared jit
caches for its strict cache_size pins.
"""

from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.adapters import AdapterLoadError, AdapterPool
from deepspeed_tpu.inference.engine import InferenceEngine
from deepspeed_tpu.inference.router import ReplicaRouter
from deepspeed_tpu.inference.serving import ServeRequest, ServingEngine
from deepspeed_tpu.models import gpt
from deepspeed_tpu.runtime.lora import (add_lora, adapter_state_dict,
                                        merge_lora)
from deepspeed_tpu.utils.compile_guard import CompileWatch, cache_size
from deepspeed_tpu.utils.faults import Fault, FaultInjector

pytestmark = pytest.mark.usefixtures("devices")


def tiny(**over):
    cfg = gpt.GPTConfig(vocab_size=128, n_layers=2, n_heads=4, d_model=32,
                        max_seq_len=64, use_flash_attention=False,
                        remat=False, dtype=jnp.float32, **over)
    params = gpt.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def prompts_of(lengths, seed=1):
    r = np.random.default_rng(seed)
    return [r.integers(1, 128, n).astype(np.int32) for n in lengths]


def mk_adapter(params, seed, rank=4):
    """A non-degenerate LoRA export. ``add_lora`` zero-inits B (a no-op
    adapter), so overwrite it with small seeded noise — the adapted
    stream must actually diverge from base for parity to mean much."""
    lp = add_lora(params, rng=jax.random.PRNGKey(seed), rank=rank,
                  alpha=2.0 * rank)
    rng = np.random.default_rng(seed)
    blk = {}
    for t, e in lp["block"].items():
        e = dict(e)
        if "lora_b" in e:
            e["lora_b"] = jnp.asarray(
                rng.standard_normal(e["lora_b"].shape) * 0.05, jnp.float32)
        blk[t] = e
    lp = dict(lp)
    lp["block"] = blk
    return lp


@pytest.fixture(scope="module")
def stack():
    """Shared base engine + two tenant adapters with merged-reference
    engines (static == serving is pinned by test_serving.py, so the
    merged generate() streams anchor the unmerged path transitively)."""
    cfg, params = tiny()
    eng = InferenceEngine(config=cfg, params=params, dtype=jnp.float32)
    lp1, lp2 = mk_adapter(params, seed=3), mk_adapter(params, seed=4)
    return SimpleNamespace(
        cfg=cfg, params=params, eng=eng, lp1=lp1, lp2=lp2,
        sd1=adapter_state_dict(lp1), sd2=adapter_state_dict(lp2),
        m1=InferenceEngine(config=cfg, params=merge_lora(lp1),
                           dtype=jnp.float32),
        m2=InferenceEngine(config=cfg, params=merge_lora(lp2),
                           dtype=jnp.float32))


def ref_of(eng, p, n):
    return eng.generate(p[None], max_new_tokens=n)[0]


def lora_srv(eng, **kw):
    defaults = dict(num_slots=2, block_size=4, num_blocks=24,
                    prefill_chunk=8, lora_serve=True, lora_pool_blocks=2,
                    lora_max_rank=4, lora_rank_block=4)
    defaults.update(kw)
    return ServingEngine(eng, **defaults)


# ---------------------------------------------------------------------------
# adapter-pool unit tests
# ---------------------------------------------------------------------------

def test_adapter_pool_register_validation(stack):
    pool = AdapterPool(stack.eng, pool_blocks=2, max_rank=4, rank_block=4)
    with pytest.raises(ValueError, match="max_rank"):
        pool.register("big", adapter_state_dict(
            mk_adapter(stack.params, seed=1, rank=8)))
    with pytest.raises(ValueError, match="unexpected export key"):
        pool.register("junk", {"not/an/export/key": np.zeros(3)})
    with pytest.raises(ValueError, match="does not expose"):
        pool.register("alien", {"block/warp_core/lora_a": np.zeros(3)})
    with pytest.raises(ValueError, match="missing"):
        pool.register("partial", {k: v for k, v in stack.sd1.items()
                                  if "lora_a" not in k})
    # registration is host-side staging only: no device pool traffic
    pool.register("t0", stack.sd1)
    assert pool.registered() == ["t0"]
    assert pool.stats()["resident"] == 0 and pool.stats()["loads"] == 0


def test_adapter_pool_paging_refcounts_lru_eviction(stack):
    # 2 usable blocks; rank 4 at rank_block 4 -> 1 block per adapter
    pool = AdapterPool(stack.eng, pool_blocks=2, max_rank=4, rank_block=4)
    assert pool.blocks_per_adapter == 1
    for aid, sd in (("t0", stack.sd1), ("t1", stack.sd2),
                    ("t2", stack.sd1)):
        pool.register(aid, sd)
    with pytest.raises(AdapterLoadError):
        pool.acquire("never-registered")
    r0 = pool.acquire("t0")
    assert r0.shape == (1,) and r0[0] > 0    # block 0 is the zero trash
    pool.acquire("t1")
    assert pool.stats()["free_blocks"] == 0 and pool.stats()["loads"] == 2
    # re-acquiring a resident adapter is a HIT (refcount 2, same row)
    assert np.array_equal(pool.acquire("t0"), r0)
    assert pool.stats()["hits"] == 1
    pool.release("t0")                       # rc 2 -> 1: still pinned
    with pytest.raises(AdapterLoadError):
        pool.acquire("t2")                   # every resident is pinned
    pool.release("t0")                       # rc 1 -> 0: LRU-evictable
    pool.acquire("t2")                       # evicts t0, loads t2
    st = pool.stats()
    assert st["evictions"] == 1 and st["loads"] == 3 and st["resident"] == 2
    pool.release("t1")
    pool.acquire("t0")                       # t0 must RELOAD (t1 evicts)
    st = pool.stats()
    assert st["evictions"] == 2 and st["loads"] == 4 and st["hits"] == 1
    with pytest.raises(ValueError):
        pool.release("t1")                   # releasing a non-held pin


# ---------------------------------------------------------------------------
# serving parity: unmerged == merged, base slot == pre-subsystem stream
# ---------------------------------------------------------------------------

def test_serving_lora_single_adapter_bit_parity(stack):
    prompts = prompts_of((5, 9), seed=2)
    ref_m = ref_of(stack.m1, prompts[0], 6)
    ref_b = [ref_of(stack.eng, p, 6) for p in prompts]
    srv = lora_srv(stack.eng)
    srv.register_adapter("t1", stack.sd1)
    out = srv.run([ServeRequest(rid="a", prompt=prompts[0],
                                max_new_tokens=6, adapter_id="t1"),
                   ServeRequest(rid="b", prompt=prompts[1],
                                max_new_tokens=6)])
    np.testing.assert_array_equal(out["a"], ref_m)
    # the base-only slot (all-zeros table row -> trash block, exactly
    # +0.0) stays identical to the engine with no subsystem at all
    np.testing.assert_array_equal(out["b"], ref_b[1])
    assert not np.array_equal(out["a"], ref_b[0])   # adapter is non-trivial
    st = srv.adapters.stats()
    assert st["loads"] == 1 and st["resident"] == 1
    assert srv.stats["adapter_loads"] == 1


def test_serving_lora_heterogeneous_batch_parity(stack):
    """Two tenants + a base request decode in ONE batch; each stream
    matches its own merged-weights reference."""
    prompts = prompts_of((5, 8, 11), seed=5)
    ref1 = ref_of(stack.m1, prompts[0], 6)
    ref2 = ref_of(stack.m2, prompts[1], 6)
    ref_b = ref_of(stack.eng, prompts[2], 6)
    srv = lora_srv(stack.eng, num_slots=3, lora_pool_blocks=3)
    srv.register_adapter("t1", stack.sd1)
    srv.register_adapter("t2", stack.sd2)
    out = srv.run([ServeRequest(rid=0, prompt=prompts[0], max_new_tokens=6,
                                adapter_id="t1"),
                   ServeRequest(rid=1, prompt=prompts[1], max_new_tokens=6,
                                adapter_id="t2"),
                   ServeRequest(rid=2, prompt=prompts[2],
                                max_new_tokens=6)])
    assert srv.stats["peak_occupancy"] == 3     # really one mixed batch
    np.testing.assert_array_equal(out[0], ref1)
    np.testing.assert_array_equal(out[1], ref2)
    np.testing.assert_array_equal(out[2], ref_b)
    assert not np.array_equal(out[0], out[1])   # tenants really diverge


def test_serving_lora_eviction_reload_parity(stack):
    """A pool smaller than the tenant population churns (load -> evict
    -> reload) and every stream still matches its merged reference."""
    prompts = prompts_of((6, 7, 6), seed=8)
    ref1 = [ref_of(stack.m1, prompts[0], 5), ref_of(stack.m1, prompts[2], 5)]
    ref2 = ref_of(stack.m2, prompts[1], 5)
    # ONE usable block and ONE slot: t1 and t2 can never be resident
    # together, so the t1 -> t2 -> t1 sequence forces two evictions
    srv = lora_srv(stack.eng, num_slots=1, lora_pool_blocks=1)
    srv.register_adapter("t1", stack.sd1)
    srv.register_adapter("t2", stack.sd2)
    out = srv.run([ServeRequest(rid="a", prompt=prompts[0],
                                max_new_tokens=5, adapter_id="t1"),
                   ServeRequest(rid="b", prompt=prompts[1],
                                max_new_tokens=5, adapter_id="t2"),
                   ServeRequest(rid="c", prompt=prompts[2],
                                max_new_tokens=5, adapter_id="t1")])
    st = srv.adapters.stats()
    assert st["evictions"] == 2 and st["loads"] == 3 and st["hits"] == 0
    np.testing.assert_array_equal(out["a"], ref1[0])
    np.testing.assert_array_equal(out["b"], ref2)
    np.testing.assert_array_equal(out["c"], ref1[1])
    assert srv.stats["adapter_evictions"] == 2


# ---------------------------------------------------------------------------
# lifecycle: drain snapshots, degraded loads
# ---------------------------------------------------------------------------

def test_serving_lora_snapshot_drain_carries_adapter(stack):
    """pending_snapshot(release=True) releases the adapter pin with the
    KV blocks and round-trips ``adapter_id``; a fresh engine resumes
    the drained request under the SAME adapter, token-identical."""
    p = prompts_of((7,), seed=10)[0]
    ref = ref_of(stack.m1, p, 8)
    srv = lora_srv(stack.eng, spec_decode=False)
    srv.register_adapter("t1", stack.sd1)
    req = ServeRequest(rid="r", prompt=p, max_new_tokens=8,
                       adapter_id="t1")
    srv.submit(req, now=0)
    step = 0
    while srv.busy and len(req.out) < 3:     # drain mid-decode
        srv.step(step)
        step += 1
    snap = srv.pending_snapshot(release=True)
    assert snap[0]["adapter_id"] == "t1"
    assert not srv._slot_arows.any()         # pin gone from the slot map
    st = srv.adapters.stats()
    assert st["resident"] == 1               # released, still warm LRU
    fresh = lora_srv(stack.eng, spec_decode=False)
    fresh.register_adapter("t1", stack.sd1)
    out = fresh.run([ServeRequest.from_snapshot(s) for s in snap])
    np.testing.assert_array_equal(out["r"], ref)


def test_serving_lora_load_fault_degrades_to_error(stack):
    """Every load-failure flavor retires the request with a structured
    ``state="error"`` — never base or another tenant's tokens — while
    co-batched requests keep serving and the pool stays intact."""
    p1, p2 = prompts_of((6, 8), seed=12)
    ref_b = ref_of(stack.eng, p2, 5)
    ref_m = ref_of(stack.m1, p1, 5)
    for kind in ("cache_exhausted", "device_error"):
        inj = FaultInjector([Fault("cache.adapter_load", kind, step=0)],
                            seed=0)
        srv = lora_srv(stack.eng, faults=inj)
        srv.register_adapter("t1", stack.sd1)
        bad = ServeRequest(rid="bad", prompt=p1, max_new_tokens=5,
                           adapter_id="t1")
        ok = ServeRequest(rid="ok", prompt=p2, max_new_tokens=5)
        out = srv.run([bad, ok])
        assert bad.state == "error" and ok.state == "done"
        np.testing.assert_array_equal(out["ok"], ref_b)
        assert srv.stats["adapter_load_errors"] == 1
        # the site fires BEFORE pool state moves: nothing leaked
        st = srv.adapters.stats()
        assert st["resident"] == 0 and st["free_blocks"] == 2
        # the injector window passed: the same tenant loads cleanly now
        retry = ServeRequest(rid="again", prompt=p1, max_new_tokens=5,
                             adapter_id="t1")
        out2 = srv.run([retry])
        assert retry.state == "done"
        np.testing.assert_array_equal(out2["again"], ref_m)


def test_serving_lora_unregistered_and_off_mode(stack):
    p1, p2 = prompts_of((5, 6), seed=14)
    ref_m = ref_of(stack.m1, p2, 4)
    # lora on, id never registered: degrade, the batch keeps serving
    srv = lora_srv(stack.eng)
    srv.register_adapter("t1", stack.sd1)
    ghost = ServeRequest(rid="g", prompt=p1, max_new_tokens=4,
                         adapter_id="nobody")
    real = ServeRequest(rid="r", prompt=p2, max_new_tokens=4,
                        adapter_id="t1")
    out = srv.run([ghost, real])
    assert ghost.state == "error" and real.state == "done"
    np.testing.assert_array_equal(out["r"], ref_m)
    assert srv.stats["adapter_load_errors"] == 1
    # lora OFF (the default): no pool is constructed, registration is a
    # loud error, and a stray adapter_id degrades instead of silently
    # serving base tokens under the tenant's name
    off = ServingEngine(stack.eng, num_slots=1, block_size=4,
                        num_blocks=12, lora_serve=False)
    assert off.adapters is None
    with pytest.raises(ValueError):
        off.register_adapter("t1", stack.sd1)
    stray = ServeRequest(rid="s", prompt=p1, max_new_tokens=4,
                         adapter_id="t1")
    off.run([stray])
    assert stray.state == "error"
    assert off.stats["adapter_load_errors"] == 1


# ---------------------------------------------------------------------------
# the compile contract
# ---------------------------------------------------------------------------

def test_serving_lora_compile_count_contract():
    """Steady state is a FIXED lora program set (one prefill, one
    decode) independent of how many adapters are registered or
    resident: a second workload over two tenants registered AFTER
    warmup — pool eviction churn included — compiles NOTHING. (Fresh
    engines: the strict cache_size pins need unshared jit caches.)"""
    cfg, params = tiny()
    eng = InferenceEngine(config=cfg, params=params, dtype=jnp.float32)
    sds = {f"t{i}": adapter_state_dict(mk_adapter(params, seed=20 + i))
           for i in range(4)}
    prompts = prompts_of((10, 9, 7), seed=15)

    def run_workload(aids):
        srv = lora_srv(eng, spec_decode=False)
        for aid in aids:
            srv.register_adapter(aid, sds[aid])
        # two tenants + a base-only slot share the decode batch
        srv.run([ServeRequest(rid=0, prompt=prompts[0], max_new_tokens=8,
                              adapter_id=aids[0]),
                 ServeRequest(rid=1, prompt=prompts[1], max_new_tokens=8,
                              adapter_id=aids[1]),
                 ServeRequest(rid=2, prompt=prompts[2], max_new_tokens=8)])
        return srv

    srv = run_workload(["t0", "t1"])
    # the adapter (or, under DS_KV_QUANT=int8, int8+adapter) variant is
    # a cache entry of the family's ONE callable
    pf, dc = eng._prefill_slot, eng._decode_slots
    n_pf, n_dc = cache_size(pf), cache_size(dc)
    if n_pf is not None:
        assert (n_pf, n_dc) == (1, 1), (
            f"lora steady state fragmented: prefill={n_pf} decode={n_dc}")
    watch = CompileWatch(max_compiles=0, label="lora serving steady state")
    watch.wrap(pf)
    watch.wrap(dc)
    with watch:                              # raises on ANY compile
        run_workload(["t2", "t3"])           # fresh tenants, post-warmup
    if n_pf is not None:
        assert cache_size(pf) == 1 and cache_size(dc) == 1
    # the variant split is total: lora-mode serving never traced the
    # base variant on this engine (each callable holds the ONE entry
    # pinned above), and with the subsystem off the adapter variant is
    # never traced at all (the off-mode bit-reference ships zero lora
    # programs): the callables hold the base entry and no other
    eng2 = InferenceEngine(config=cfg, params=params, dtype=jnp.float32)
    ServingEngine(eng2, num_slots=1, block_size=4, num_blocks=12,
                  lora_serve=False).run(
        [ServeRequest(rid=0, prompt=prompts[2], max_new_tokens=3)])
    assert (cache_size(eng2._prefill_slot) or 1) == 1
    assert (cache_size(eng2._decode_slots) or 1) == 1


# ---------------------------------------------------------------------------
# interplay: prefix cache, speculative decode, int8 KV, router affinity
# ---------------------------------------------------------------------------

def test_serving_lora_prefix_cache_bypass_both_ways(stack):
    """The prefix index keys blocks by TOKENS only, but an adapter
    slot's K/V embeds that adapter's weights — so adapter-carrying
    requests neither MATCH cached prefixes nor REGISTER their own,
    while base-only traffic keeps sharing."""
    shared = prompts_of((12,), seed=17)[0]   # 3 full blocks of 4
    ref_m = ref_of(stack.m1, shared, 4)
    ref_b = ref_of(stack.eng, shared, 4)
    srv = lora_srv(stack.eng, num_slots=1, prefix_cache=True)
    srv.register_adapter("t1", stack.sd1)
    # base pair first: the second base request hits the cached prefix
    out_b = srv.run([ServeRequest(rid=f"b{i}", prompt=shared.copy(),
                                  max_new_tokens=4) for i in range(2)])
    base_hits = srv.stats["prefix_hits"]
    assert base_hits >= 1
    # adapter pair over the SAME tokens: no match (a base-cached prefix
    # would poison the tenant stream), no registration either
    out_a = srv.run([ServeRequest(rid=f"a{i}", prompt=shared.copy(),
                                  max_new_tokens=4, adapter_id="t1")
                     for i in range(2)])
    assert srv.stats["prefix_hits"] == base_hits
    for i in range(2):
        np.testing.assert_array_equal(out_b[f"b{i}"], ref_b)
        np.testing.assert_array_equal(out_a[f"a{i}"], ref_m)
    assert not np.array_equal(ref_m, ref_b)


def test_serving_lora_spec_decode_compose(stack):
    """Greedy spec-on LoRA serving equals spec-off: drafts are verified
    under the slot's adapter through the _l verify twin."""
    prompts = prompts_of((6, 9), seed=19)
    ref_m = ref_of(stack.m1, prompts[0], 8)
    ref_b = ref_of(stack.eng, prompts[1], 8)
    srv = lora_srv(stack.eng, spec_decode=True)
    srv.register_adapter("t1", stack.sd1)
    out = srv.run([ServeRequest(rid="a", prompt=prompts[0],
                                max_new_tokens=8, adapter_id="t1"),
                   ServeRequest(rid="b", prompt=prompts[1],
                                max_new_tokens=8)])
    np.testing.assert_array_equal(out["a"], ref_m)
    np.testing.assert_array_equal(out["b"], ref_b)


def test_serving_lora_int8_kv_compose(stack):
    """Adapters thread through the int8 KV pool (_ql twins): parity
    against the SAME adapter merged and served over an int8 pool."""
    p = prompts_of((7,), seed=22)[0]
    srv_m = ServingEngine(stack.m1, num_slots=1, block_size=4,
                          num_blocks=12, kv_quant="int8")
    ref = srv_m.run([ServeRequest(rid=0, prompt=p, max_new_tokens=5)])[0]
    srv = lora_srv(stack.eng, num_slots=1, kv_quant="int8")
    srv.register_adapter("t1", stack.sd1)
    out = srv.run([ServeRequest(rid=0, prompt=p, max_new_tokens=5,
                                adapter_id="t1")])
    np.testing.assert_array_equal(out[0], ref)


def test_router_adapter_affinity_dispatch_and_parity(stack):
    """A deadline-free request naming an adapter returns to the replica
    whose pool holds it (a hit, not an H2D reload) under the same
    imbalance cap; deadline traffic goes strictly least-loaded."""
    fleet = [lora_srv(stack.eng, spec_decode=False) for _ in range(2)]
    for rep in fleet:
        rep.register_adapter("t1", stack.sd1)
    router = ReplicaRouter(fleet)
    p_b, p_a = prompts_of((8, 8), seed=24)
    ref_m = ref_of(stack.m1, p_a, 4)
    # seed: base -> replica 0 (tie-break), tenant -> replica 1
    router.submit(ServeRequest(rid="b1", prompt=p_b, max_new_tokens=4))
    router.submit(ServeRequest(rid="a1", prompt=p_a, max_new_tokens=4,
                               adapter_id="t1"))
    assert any(r.rid == "a1" for r in fleet[1].queue)
    # follow-up from the same tenant: affinity beats the least-loaded
    # tie-break (loads are 1 vs 1, which alone would pick replica 0)
    router.submit(ServeRequest(rid="a2", prompt=p_a.copy(),
                               max_new_tokens=4, adapter_id="t1"))
    assert any(r.rid == "a2" for r in fleet[1].queue)
    assert router.stats["adapter_affinity_hits"] >= 1
    # deadline traffic skips affinity: replica 1 is now busier
    router.submit(ServeRequest(rid="a3", prompt=p_a.copy(),
                               max_new_tokens=4, adapter_id="t1",
                               deadline=1e9))
    assert any(r.rid == "a3" for r in fleet[0].queue)
    out = router.run()
    for rid in ("a1", "a2", "a3"):
        np.testing.assert_array_equal(out[rid], ref_m)
    # affinity-routed traffic really lands pool hits on its home
    assert fleet[1].adapters.stats()["hits"] >= 1
