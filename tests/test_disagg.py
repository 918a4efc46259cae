"""Disaggregated prefill/decode fleet suite (tentpole: replica roles +
fault-tolerant KV migration, docs/ROBUSTNESS.md).

Layers:
  1. migration parity — a 1-prefill/1-decode fleet serves every
     request token-identically to a solo greedy run, with every
     request's KV migrating through the CRC-verified host channel
     (``router_migrations`` == requests, zero fallbacks) and both
     pools' block accounting balancing afterwards;
  2. the degradation ladder — a fault at each ``router.migrate_*``
     site (transient, CRC corruption, crash on either endpoint)
     degrades that request to a cold re-prefill on the decode side
     with parity intact, no parked entries, no ``_in_transfer``
     leaks, and no orphaned host-pool keys (DS016);
  3. retire/breaker racing an in-flight migration — a retire settles
     pending handoffs through the migrate path first; a crash mid-
     migration drains the victim and the request lands COLD on a
     survivor with parity; the last decode-capable replica refuses to
     retire;
  4. the compile contract — migration gather/scatter lanes pre-warm at
     router construction, so a migrating steady state compiles
     nothing (CompileWatch(0)).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.engine import InferenceEngine
from deepspeed_tpu.inference.router import RETIRED, ReplicaRouter
from deepspeed_tpu.inference.serving import ServeRequest, ServingEngine
from deepspeed_tpu.models import gpt
from deepspeed_tpu.utils.compile_guard import CompileWatch
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


def _solo_refs(eng, prompts, n):
    return [eng.generate(p[None], max_new_tokens=n)[0] for p in prompts]


@pytest.fixture(scope="module")
def eng():
    cfg, params = tiny()
    return InferenceEngine(config=cfg, params=params, dtype=jnp.float32)


def mk_fleet(eng, n=2, **kw):
    defaults = dict(num_slots=2, block_size=4, num_blocks=24,
                    prefill_chunk=8, spec_decode=False)
    defaults.update(kw)
    return [ServingEngine(eng, **defaults) for _ in range(n)]


def mk_reqs(prompts, n=6, **kw):
    return [ServeRequest(rid=i, prompt=p, max_new_tokens=n, **kw)
            for i, p in enumerate(prompts)]


def assert_pools_clean(router):
    """Both sides' block accounting balances after the fleet drains:
    nothing parked, nothing mid-transfer, no orphaned host keys —
    the DS016 resource-pairing invariant, observed end to end."""
    for rep in router.replicas:
        st = rep.srv.cache.stats()
        assert st["parked_blocks"] == 0, (rep.idx, st)
        assert not rep.srv.cache._in_transfer, rep.idx
        assert st["free_blocks"] + st["cached_blocks"] \
            == st["num_blocks"], (rep.idx, st)
    assert len(router._mig_pool) == 0, "leaked host staging keys"


# ---------------------------------------------------------------------------
# migration parity
# ---------------------------------------------------------------------------

def test_disagg_migration_parity(eng):
    """Every request prefills on the prefill replica, migrates its KV
    through the host channel, and resumes decode on the decode replica
    token-identically to a solo run — no re-prefill, no fallback."""
    prompts = prompts_of((6, 9, 12, 8))
    refs = _solo_refs(eng, prompts, 6)
    router = ReplicaRouter(mk_fleet(eng), roles=["prefill", "decode"],
                           telemetry=True)
    res = router.run(mk_reqs(prompts))
    for i, ref in enumerate(refs):
        assert np.array_equal(res[i], ref), f"rid {i} diverged"
    assert router.stats["migrations"] == len(prompts)
    assert router.stats["migration_fallbacks"] == 0
    assert_pools_clean(router)


def test_disagg_role_validation(eng):
    """Role vocabulary is closed and a fleet with prefill replicas
    needs somewhere to land migrations."""
    with pytest.raises(ValueError, match="role"):
        ReplicaRouter(mk_fleet(eng), roles=["prefill", "archon"])
    with pytest.raises(ValueError):
        ReplicaRouter(mk_fleet(eng), roles=["prefill", "prefill"])
    with pytest.raises(ValueError):
        ReplicaRouter(mk_fleet(eng), roles=["prefill"][:1] * 2)


# ---------------------------------------------------------------------------
# the degradation ladder, one rung per fault
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("site,kind", [
    ("router.migrate_gather", "device_error"),
    ("router.migrate_scatter", "device_error"),
    ("router.migrate_corrupt", "cache_exhausted"),
    ("router.migrate_gather", "crash"),
    ("router.migrate_scatter", "crash"),
])
def test_migration_fault_degrades_cold_with_parity(eng, site, kind):
    """Any failure mid-migration — transient on either side, a REAL
    CRC32 mismatch from a flipped host byte, or a crash that breaks
    the acting endpoint — lands the request as a cold re-prefill with
    token parity, counted in ``migration_fallbacks``, and neither
    pool leaks a parked entry, an ``_in_transfer`` pairing, or a host
    staging key."""
    prompts = prompts_of((6, 9, 12, 8))
    refs = _solo_refs(eng, prompts, 6)
    # a crash breaks one endpoint, so give the fleet a survivor on
    # each side of the channel
    n, roles = (3, ["prefill", "decode", "decode"]) if kind == "crash" \
        else (2, ["prefill", "decode"])
    inj = FaultInjector([Fault(site=site, kind=kind, step=0, count=1)],
                        seed=0)
    router = ReplicaRouter(mk_fleet(eng, n=n), roles=roles, faults=inj,
                           telemetry=True)
    res = router.run(mk_reqs(prompts))
    for i, ref in enumerate(refs):
        assert np.array_equal(res[i], ref), f"rid {i} diverged under {site}"
    assert router.stats["migration_fallbacks"] >= 1
    assert inj.fired, "fault never reached the site"
    assert_pools_clean(router)


def test_migration_corrupt_is_detected_not_served(eng):
    """The corrupt rung flips a REAL stored byte: the per-array CRC32
    verify inside the landing (not the injector) must catch it — the
    fallback reason in the migrate trace event names the corruption,
    and the poisoned bytes never reach a pool."""
    prompts = prompts_of((8,), seed=3)
    refs = _solo_refs(eng, prompts, 6)
    inj = FaultInjector([Fault(site="router.migrate_corrupt",
                               kind="cache_exhausted", step=0, count=1)],
                        seed=0)
    router = ReplicaRouter(mk_fleet(eng), roles=["prefill", "decode"],
                           faults=inj, telemetry=True)
    res = router.run(mk_reqs(prompts))
    assert np.array_equal(res[0], refs[0])
    falls = [rec for rec in router.telemetry.tracer.records()
             if rec[1] == "migrate" and not (rec[5] or {}).get("ok")]
    assert falls and "CRC32" in str(falls[0][5].get("reason")), falls


# ---------------------------------------------------------------------------
# retire / breaker racing an in-flight migration
# ---------------------------------------------------------------------------

def test_retire_prefill_settles_handoffs_first(eng):
    """A retire of the prefill replica with handoffs parked settles
    them through the migrate path BEFORE retiring — the same
    discipline as ``abort_transfers`` — and the requests finish on
    the decode side with parity."""
    prompts = prompts_of((6, 9))
    refs = _solo_refs(eng, prompts, 6)
    fleet = mk_fleet(eng)
    router = ReplicaRouter(fleet, roles=["prefill", "decode"],
                           telemetry=True)
    for req in mk_reqs(prompts):
        router.submit(req)
    # advance the prefill replica BEHIND the router's back until at
    # least one finished prefill is parked as a handoff — the router
    # has not harvested it yet, so the retire races a real in-flight
    # hand-over
    for _ in range(16):
        fleet[0].step()
        if fleet[0].ready_handoffs():
            break
    assert fleet[0].ready_handoffs(), "no handoff materialized"
    router.retire_replica(0)
    assert router.replicas[0].health == RETIRED
    assert router.stats["migrations"] >= 1
    res = router.run(max_steps=500)
    for i, ref in enumerate(refs):
        assert np.array_equal(res[i], ref), f"rid {i} diverged"
    assert_pools_clean(router)


def test_breaker_break_mid_migration_lands_cold_on_survivor(eng):
    """A crash during the gather breaks the SOURCE replica: its drain
    resumes every in-flight request — including the one whose
    migration was cut — cold on a survivor, with token parity and
    balanced accounting on both pools (no leaked ``_in_transfer`` or
    parked entries)."""
    prompts = prompts_of((6, 9, 12, 8))
    refs = _solo_refs(eng, prompts, 6)
    inj = FaultInjector([Fault(site="router.migrate_gather",
                               kind="crash", step=0, count=1)], seed=0)
    router = ReplicaRouter(mk_fleet(eng, n=3),
                           roles=["prefill", "decode", "decode"],
                           faults=inj, telemetry=True)
    res = router.run(mk_reqs(prompts))
    for i, ref in enumerate(refs):
        assert np.array_equal(res[i], ref), f"rid {i} diverged"
    # the cut migration degraded cold: fallbacks counted, and the
    # broken prefill replica's pool released every block at drain
    assert router.stats["migration_fallbacks"] >= 1
    assert router.stats["breaker_trips"] >= 1
    assert_pools_clean(router)


def test_retire_last_decode_capable_refused(eng):
    """The fleet must always keep a migration landing zone: retiring
    the only decode-capable replica is refused outright."""
    router = ReplicaRouter(mk_fleet(eng), roles=["prefill", "decode"],
                           telemetry=True)
    with pytest.raises(ValueError, match="decode-capable"):
        router.retire_replica(1)
    # the prefill replica itself can retire (decode side survives)
    router.retire_replica(0)
    assert router.replicas[0].health == RETIRED


@pytest.mark.slow
def test_parked_jump_under_bursty_open_load(eng):
    """Regression: a cold re-dispatched request at the decode
    replica's queue head once deadlocked the fleet — the blocks it
    waited for were HELD by parked migrated-in chains queued BEHIND
    it, which only free by being served. Admission now lets a parked
    request jump a blocked head (docs/ROBUSTNESS.md); this bursty
    open-load trace drives that exact interleaving and must drain
    with per-request token parity."""
    lg = pytest.importorskip("tools.load_gen")
    entries = lg.make_requests(seed=1, mix="mixed",
                               phases=[(10, 0.2), (15, 0.5), (45, 0.2)],
                               vocab_size=128, max_prompt_len=40)
    router = ReplicaRouter(mk_fleet(eng, block_size=8, num_blocks=24),
                           roles=["prefill", "decode"], telemetry=True)
    res = lg.drive(router, entries, mode="open", include_tokens=True,
                   max_steps=3000)
    by_rid = {e["rid"]: e for e in entries}
    for rec in res["per_request"]:
        e = by_rid[rec["rid"]]
        ref = eng.generate(np.asarray(e["prompt"], np.int32)[None],
                           max_new_tokens=int(e["max_new_tokens"]))[0]
        assert rec["tokens"] == [int(t) for t in ref], rec["rid"]
    assert router.stats["migrations"] >= 1
    assert_pools_clean(router)


# ---------------------------------------------------------------------------
# the per-kind SLO contrast
# ---------------------------------------------------------------------------

def test_split_holds_every_slo_the_monolithic_fleet_violates():
    """Disaggregated against monolithic at the SAME replica count
    (docs/ROBUSTNESS.md): ONE seeded mixed rag+chat load_gen trace, on
    the scheduler's step clock. Two mixed-role replicas interleave long
    rag prefills with chat decodes and break at least one per-kind p99
    budget of load_gen.SLO_TARGETS; 1 prefill + 1 decode hold ALL of
    them, with the same tokens a request (a migration resumes exactly,
    a fallback re-prefills cold) and nothing compiled in the drive."""
    lg = pytest.importorskip("tools.load_gen")
    cfg = gpt.GPTConfig(vocab_size=512, n_layers=2, n_heads=4, d_model=32,
                        max_seq_len=64 + 16 + 8, use_flash_attention=False,
                        remat=False, dtype=jnp.float32)
    eng = InferenceEngine(config=cfg, dtype=jnp.float32,
                          params=gpt.init_params(jax.random.PRNGKey(0), cfg))
    entries = lg.make_requests(seed=3, mix="mixed", phases=[(110, 0.27)],
                               vocab_size=cfg.vocab_size, max_prompt_len=64)

    def fleet():
        return mk_fleet(eng, block_size=8, num_blocks=24)

    def p99(res, key, kind):
        return float(np.percentile(
            [r[key] for r in res["per_request"]
             if r["kind"] == kind and r[key] is not None], 99))

    def over_budget(res):
        return [(kind, key, p99(res, key, kind))
                for kind in ("chat", "rag") for key in ("ttft", "tpot")
                if p99(res, key, kind) > lg.SLO_TARGETS[kind][key]]

    mono = ReplicaRouter(fleet(), telemetry=True)
    res_m = lg.drive(mono, entries, mode="open", include_tokens=True)
    split = ReplicaRouter(fleet(), roles=["prefill", "decode"],
                          telemetry=True)
    watch = CompileWatch(max_compiles=0, label="disagg steady state")
    with watch:
        res_d = lg.drive(split, entries, mode="open", include_tokens=True)
    assert over_budget(res_m), "the monolithic fleet broke no budget"
    assert not over_budget(res_d), over_budget(res_d)
    assert {r["rid"]: r["tokens"] for r in res_m["per_request"]} \
        == {r["rid"]: r["tokens"] for r in res_d["per_request"]}
    assert watch.compiles == 0
    assert split.stats["migrations"] >= 1
    assert_pools_clean(split)


# ---------------------------------------------------------------------------
# compile contract
# ---------------------------------------------------------------------------

def test_disagg_compile_contract(eng):
    """Migration rides the SAME gather/scatter programs as the host
    tier, pre-warmed at router construction — a migrating fleet's
    steady state compiles nothing."""
    router = ReplicaRouter(mk_fleet(eng), roles=["prefill", "decode"],
                           telemetry=True)
    prompts = prompts_of((6, 9, 12, 8))
    refs = _solo_refs(eng, prompts, 6)
    router.run(mk_reqs(prompts_of((7, 10), seed=9)))   # warm batch
    watch = CompileWatch(max_compiles=0, label="disagg steady state")
    with watch:
        res = router.run(mk_reqs(prompts))
    for i, ref in enumerate(refs):
        assert np.array_equal(res[i], ref)
    assert router.stats["migrations"] >= len(prompts) + 2
    assert watch.compiles == 0
