"""Replica-fleet router chaos suite (tentpole: inference/router.py).

Layers:
  1. dispatch units — least-loaded placement, prefix-affinity routing
     for deadline-free traffic, deadline traffic overriding affinity;
  2. the circuit-breaker health machine — healthy -> suspect -> broken
     on consecutive failures, broken -> recovering via checkpointed
     warm restart, recovering -> healthy on a clean probe completion,
     half-open admission caps while recovering;
  3. drain parity under chaos — a replica killed mid-decode (injected
     ``crash`` / ``device_error`` bursts / a watchdog DegradedError, at
     every new ``router.*`` site, fixed seed) drains its in-flight
     snapshot onto survivors, and every non-shed request's final
     tokens are IDENTICAL to an undisturbed solo greedy run (the
     acceptance gate);
  4. total degrade — all replicas broken raises ONE fleet-level
     DegradedError whose merged results + pending cover every rid;
  5. the compile contract — N replicas sharing one InferenceEngine
     hold the 2-program / zero-recompile steady state under active
     chaos (CompileWatch(0)).
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.engine import InferenceEngine
from deepspeed_tpu.inference.router import (BROKEN, HEALTHY, RECOVERING,
                                            SUSPECT, ReplicaRouter)
from deepspeed_tpu.inference.serving import (DegradedError, ServeRequest,
                                             ServingEngine)
from deepspeed_tpu.models import gpt
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


def mk_fleet(eng, n=3, **kw):
    """N replicas sharing ONE InferenceEngine — per-instance jits, so
    the whole fleet shares the same compiled serving programs."""
    defaults = dict(num_slots=2, block_size=4, num_blocks=24,
                    prefill_chunk=8, spec_decode=False)
    defaults.update(kw)
    return [ServingEngine(eng, **defaults) for _ in range(n)]


def mk_reqs(prompts, n=6, **kw):
    return [ServeRequest(rid=i, prompt=p, max_new_tokens=n, **kw)
            for i, p in enumerate(prompts)]


# ---------------------------------------------------------------------------
# dispatch units
# ---------------------------------------------------------------------------

def test_router_dispatch_least_loaded(eng):
    """A fresh request lands on the replica with the most headroom
    (queue depth + occupied slots), tie-broken by index."""
    fleet = mk_fleet(eng, n=2)
    router = ReplicaRouter(fleet)
    p = prompts_of((6, 7, 8, 9), seed=3)
    # preload replica 0 with two requests behind the router's back
    fleet[0].submit(ServeRequest(rid="x0", prompt=p[0]))
    fleet[0].submit(ServeRequest(rid="x1", prompt=p[1]))
    router.submit(ServeRequest(rid="a", prompt=p[2]))
    assert any(r.rid == "a" for r in fleet[1].queue)
    # loads now 2 vs 1 -> next also goes to replica 1
    router.submit(ServeRequest(rid="b", prompt=p[3]))
    assert any(r.rid == "b" for r in fleet[1].queue)
    # balanced again -> tie-break picks replica 0
    router.submit(ServeRequest(rid="c", prompt=prompts_of((5,), seed=8)[0]))
    assert any(r.rid == "c" for r in fleet[0].queue)
    assert router.stats["dispatched"] == 3


def test_router_dispatch_prefix_affinity_and_deadline(eng):
    """Deadline-free same-prefix traffic returns to the replica whose
    prefix blocks are warm; deadline traffic goes strictly
    least-loaded even when affinity points elsewhere."""
    fleet = mk_fleet(eng, n=2)
    router = ReplicaRouter(fleet)
    sys_a, sys_b = prompts_of((20, 20), seed=5)
    # first arrivals seed the affinity map: B -> replica 0 (tie-break),
    # A -> replica 1 (least loaded)
    router.submit(ServeRequest(rid="b1", prompt=sys_b))
    router.submit(ServeRequest(rid="a1", prompt=sys_a))
    assert any(r.rid == "a1" for r in fleet[1].queue)
    # same-prefix follow-up: affinity beats the least-loaded tie-break
    # (loads are 1 vs 1, so least-loaded alone would pick replica 0)
    router.submit(ServeRequest(rid="a2", prompt=sys_a.copy()))
    assert any(r.rid == "a2" for r in fleet[1].queue)
    assert router.stats["affinity_hits"] >= 1
    # a deadline-carrying request with the SAME prefix skips affinity:
    # replica 1 now holds 2 requests, replica 0 holds 1
    router.submit(ServeRequest(rid="a3", prompt=sys_a.copy(),
                               deadline=1e9))
    assert any(r.rid == "a3" for r in fleet[0].queue)


def test_router_prefix_affinity_warms_shared_blocks(eng):
    """With the prefix cache on, affinity-routed traffic actually hits
    shared blocks on its home replica."""
    fleet = mk_fleet(eng, n=2, prefix_cache=True, num_blocks=32)
    router = ReplicaRouter(fleet)
    sys_p = prompts_of((16,), seed=6)[0]
    tails = prompts_of((4, 4, 4), seed=7)
    reqs = [ServeRequest(rid=i, prompt=np.concatenate([sys_p, t]),
                         max_new_tokens=4) for i, t in enumerate(tails)]
    refs = _solo_refs(eng, [r.prompt for r in reqs], 4)
    # serialize arrivals so each later request sees the published prefix
    router.submit(reqs[0])
    out = router.run()
    for r in reqs[1:]:
        router.submit(r)
        out.update(router.run())
    home = router._affinity[router._affinity_key(sys_p)]
    assert fleet[home].stats["prefix_hits"] >= 1
    for i, ref in enumerate(refs):
        np.testing.assert_array_equal(out[i], ref)


# ---------------------------------------------------------------------------
# circuit-breaker health machine
# ---------------------------------------------------------------------------

def test_router_breaker_state_machine(eng):
    """healthy -> suspect on one failure, back to healthy on a clean
    step, broken at the consecutive-failure threshold — and the broken
    replica's work drains onto the survivor with token parity."""
    inj = FaultInjector(
        [Fault("router.step", "device_error", step=0),
         Fault("router.step", "device_error", step=2, count=2)], seed=0)
    fleet = mk_fleet(eng, n=2, faults=inj)
    router = ReplicaRouter(fleet, breaker_threshold=2, faults=inj)
    prompts = prompts_of((6, 9), seed=11)
    refs = _solo_refs(eng, prompts, 8)
    reqs = mk_reqs(prompts, n=8)
    # both requests to replica 0: submit directly so only r0 is busy
    # (router.step visits then target r0 alone -> deterministic)
    fleet[0].submit(reqs[0])
    fleet[0].submit(reqs[1])
    router.step()                       # visit 0: failure
    assert router.health() == [SUSPECT, HEALTHY]
    router.step()                       # visit 1: clean
    assert router.health() == [HEALTHY, HEALTHY]
    router.step()                       # visit 2: failure
    assert router.health() == [SUSPECT, HEALTHY]
    router.step()                       # visit 3: threshold -> broken
    assert router.health() == [BROKEN, HEALTHY]
    assert router.stats["breaker_trips"] == 1
    assert router.stats["drained_requests"] == 2
    out = router.run()
    assert len(inj.fired) == 3
    for i, ref in enumerate(refs):
        np.testing.assert_array_equal(out[i], ref)
    assert all(r.state == "done" for r in fleet[1].finished)


def test_router_recovering_half_open_admissions(eng):
    """A recovering replica admits at most probe_admissions in-flight
    requests; overflow routes to healthy replicas."""
    fleet = mk_fleet(eng, n=2)
    router = ReplicaRouter(
        fleet, probe_admissions=1,
        replica_factory=lambda i, tag: mk_fleet(eng, n=1)[0])
    router.replicas[0].health = BROKEN       # unit-level: force the state
    router.restart_replica(0)
    assert router.health() == [RECOVERING, HEALTHY]
    p = prompts_of((5, 6, 7), seed=13)
    router.submit(ServeRequest(rid="p0", prompt=p[0]))   # probe -> r0
    assert any(r.rid == "p0" for r in router.replicas[0].srv.queue)
    # half-open window full: the rest go to the healthy replica even
    # though r0 has equal-or-less load
    router.submit(ServeRequest(rid="p1", prompt=p[1]))
    router.submit(ServeRequest(rid="p2", prompt=p[2]))
    assert {r.rid for r in fleet[1].queue} == {"p1", "p2"}
    out = router.run()
    # the probe completed cleanly -> breaker closes
    assert router.health() == [HEALTHY, HEALTHY]
    assert set(out) == {"p0", "p1", "p2"}


def test_router_warm_restart_checkpoint_walkback(eng, tmp_path):
    """restart_replica resolves the newest VALID checkpoint tag with
    walk-back semantics: a torn `latest` tag is skipped, the factory
    gets the newest tag that validates, and the rebuilt replica
    rejoins through recovering to healthy."""
    root = tmp_path / "ckpts"
    good = root / "t_good" / "state"
    good.mkdir(parents=True)                  # legacy-valid tag
    time.sleep(0.01)
    (root / "t_torn").mkdir()                 # no state dir: invalid
    (root / "latest").write_text("t_torn")    # pointer at the torn tag
    calls = []

    def factory(idx, tag):
        calls.append((idx, tag))
        return mk_fleet(eng, n=1)[0]

    inj = FaultInjector([Fault("router.step", "crash", step=1)], seed=0)
    fleet = mk_fleet(eng, n=2, faults=inj)
    router = ReplicaRouter(fleet, replica_factory=factory,
                           ckpt_dir=str(root), faults=inj)
    prompts = prompts_of((7, 8), seed=17)
    refs = _solo_refs(eng, prompts, 6)
    out = router.run(mk_reqs(prompts, n=6))
    assert router.health().count(BROKEN) == 1
    broken = router.health().index(BROKEN)
    for i, ref in enumerate(refs):
        np.testing.assert_array_equal(out[i], ref)
    # warm restart: newest valid tag, NOT the torn latest
    tag = router.restart_replica(broken)
    assert tag == "t_good" and calls == [(broken, "t_good")]
    assert router.health()[broken] == RECOVERING
    # a probe request completes on the rebuilt replica -> healthy
    probe = ServeRequest(rid="probe", prompt=prompts_of((5,), seed=19)[0],
                         max_new_tokens=4)
    # point dispatch at the recovering replica by loading the other one
    fleet = [rep.srv for rep in router.replicas]
    fleet[1 - broken].submit(ServeRequest(
        rid="ballast", prompt=prompts_of((5,), seed=23)[0],
        max_new_tokens=4))
    router.submit(probe)
    assert any(r.rid == "probe"
               for r in router.replicas[broken].srv.queue)
    router.run()
    assert router.health()[broken] == HEALTHY
    assert router.stats["restarts"] == 1


# ---------------------------------------------------------------------------
# drain parity under chaos (the acceptance gate)
# ---------------------------------------------------------------------------

def _parity_run(eng, faults, n_replicas=3, n_reqs=6, max_new=8, **fleet_kw):
    """Run a fleet under the given injected faults; assert every
    request finishes done with tokens identical to a solo greedy run."""
    prompts = prompts_of(tuple(5 + (i % 4) * 3 for i in range(n_reqs)),
                         seed=29)
    refs = _solo_refs(eng, prompts, max_new)
    inj = FaultInjector(faults, seed=0)
    fleet = mk_fleet(eng, n=n_replicas, faults=inj, **fleet_kw)
    router = ReplicaRouter(fleet, faults=inj)
    out = router.run(mk_reqs(prompts, n=max_new))
    assert inj.fired, "the chaos never actually fired"
    assert set(out) == set(range(n_reqs))
    for i, ref in enumerate(refs):
        np.testing.assert_array_equal(
            out[i], ref,
            err_msg=f"request {i} lost drain parity under {faults}")
    return router


def test_router_drain_parity_crash_mid_decode(eng):
    """The headline acceptance: 3 replicas, one killed mid-decode by an
    injected crash — every request completes token-identical to an
    undisturbed run, with >=1 request actually drained."""
    router = _parity_run(
        eng, [Fault("router.step", "crash", step=7)])
    assert router.health().count(BROKEN) == 1
    assert router.stats["drained_requests"] >= 1
    assert router.stats["breaker_trips"] == 1


def test_router_drain_parity_device_error_burst(eng):
    """A burst of transient step failures trips the breaker (threshold
    crossings, not one-off crashes) and drains with parity."""
    # 7 consecutive failures round-robin across 3 replicas: one replica
    # takes 3 strikes (-> broken), the others 2 (-> recover on the next
    # clean step); 9+ would be 3 strikes everywhere = total degrade
    router = _parity_run(
        eng, [Fault("router.step", "device_error", step=6, count=7)])
    assert router.stats["breaker_trips"] >= 1
    assert router.stats["drained_requests"] >= 1


def test_router_drain_parity_watchdog_degraded(eng):
    """A replica's own watchdog DegradedError (driven by an injected
    slow decode) is absorbed by the router: break, drain, parity."""
    # grace=1: serving.decode visits are fleet-global (shared injector),
    # so consecutive slow visits can straddle two replicas and a grace
    # of 2 would never accumulate on either. The budget is wall time: a
    # 10 ms one, and then a 50 ms one, was crossed by an undisturbed
    # decode step when the other workers of a whole run loaded the host
    # (a second replica broke); 0.25 s is out of such a step's reach, and
    # the 0.6 s fault still clears it. A process's first decode COMPILES
    # for seconds (this test alone, or first on its worker, broke a replica
    # on that): the fleet's two programs are run once before any watchdog
    mk_fleet(eng, n=1)[0].run(mk_reqs(prompts_of((5,), seed=29), n=2))
    router = _parity_run(
        eng,
        [Fault("serving.decode", "slow", step=5, param=0.6)],
        step_time_budget_s=0.25, watchdog_grace=1)
    assert router.health().count(BROKEN) == 1
    assert router.stats["drained_requests"] >= 1


def test_router_drain_parity_dispatch_site_faults(eng):
    """Faults at router.dispatch fire BEFORE the submit: a transient
    retries on the next-best replica, a crash kills the chosen replica
    (draining whatever it held) — parity either way."""
    router = _parity_run(
        eng, [Fault("router.dispatch", "device_error", step=1),
              Fault("router.dispatch", "crash", step=4)])
    assert router.stats["redispatches"] >= 1
    assert router.health().count(BROKEN) == 1


def test_router_drain_parity_drain_site_transient(eng):
    """A transient fault at router.drain retries the drain (it fires
    before any snapshot state moves) — nothing lost, parity holds."""
    router = _parity_run(
        eng, [Fault("router.step", "crash", step=7),
              Fault("router.drain", "device_error", step=0)])
    assert router.stats["drained_requests"] >= 1


def test_router_all_broken_total_degrade(eng):
    """Every replica broken: ONE fleet-level DegradedError carrying
    merged results plus pending entries — results ∪ pending covers
    every submitted rid, and nothing is double-reported."""
    prompts = prompts_of((6, 9, 12, 5, 8), seed=31)
    inj = FaultInjector(
        [Fault("router.step", "crash", step=4, count=1000)], seed=0)
    fleet = mk_fleet(eng, n=3, faults=inj)
    router = ReplicaRouter(fleet, faults=inj)
    with pytest.raises(DegradedError) as ei:
        router.run(mk_reqs(prompts, n=8))
    e = ei.value
    assert router.health() == [BROKEN, BROKEN, BROKEN]
    assert router.stats["fleet_degraded"] >= 1
    done = set(e.results)
    pending = {s["rid"] for s in e.pending}
    assert done | pending == set(range(len(prompts)))
    assert not (done & pending)
    # pending entries are cold-resume complete: a fresh single engine
    # finishes them with exact parity (the drain foundation)
    refs = _solo_refs(eng, prompts, 8)
    fresh = mk_fleet(eng, n=1)[0]
    out = fresh.run([ServeRequest.from_snapshot(s) for s in e.pending])
    out.update(e.results)
    for i, ref in enumerate(refs):
        np.testing.assert_array_equal(out[i], ref)


# ---------------------------------------------------------------------------
# compile contract
# ---------------------------------------------------------------------------

def test_router_compile_contract_under_chaos():
    """N replicas sharing one InferenceEngine share its per-instance
    jitted programs: after warmup the fleet steady state is the same
    1 prefill + 1 decode executable, and a full chaos run (crash +
    drain + redispatch) compiles NOTHING new."""
    from deepspeed_tpu.utils.compile_guard import CompileWatch, cache_size

    # fresh engine: the module fixture's jit caches carry extra pool
    # shapes from tests that use different num_blocks
    cfg, params = tiny()
    eng = InferenceEngine(config=cfg, params=params, dtype=jnp.float32)

    def run_workload(faults):
        inj = FaultInjector(faults, seed=0)
        fleet = mk_fleet(eng, n=3, faults=inj)
        router = ReplicaRouter(fleet, faults=inj)
        prompts = prompts_of((5, 9, 12, 7), seed=37)
        out = router.run(mk_reqs(prompts, n=8))
        return router, out

    run_workload([])                        # warmup: compile everything
    pf, dc = eng._prefill_slot, eng._decode_slots
    n_prefill, n_decode = cache_size(pf), cache_size(dc)
    if n_prefill is not None:
        assert (n_prefill, n_decode) == (1, 1), (
            f"fleet steady state fragmented: prefill={n_prefill} "
            f"decode={n_decode} programs (expected 1+1)")
    watch = CompileWatch(max_compiles=0, label="router steady state")
    watch.wrap(pf)
    watch.wrap(dc)
    with watch:                             # raises RecompileError if
        router, _ = run_workload(           # chaos causes ANY compile
            [Fault("router.step", "crash", step=7),
             Fault("router.dispatch", "device_error", step=9)])
    assert router.stats["drained_requests"] >= 1
