"""Chaos suite: the serving + checkpoint robustness contract driven by
the deterministic fault injector (tentpole: utils/faults.py + the
graceful-degradation paths in inference/serving.py).

Layers:
  1. injector unit tests — spec grammar, visit scheduling, the fired
     log, seeded-jitter determinism, ambient install/restore;
  2. serving under chaos — injected cache exhaustion, transient device
     errors and slow steps with a FIXED seed: every non-shed request
     must finish exactly once with token parity against the fault-free
     greedy stream (the acceptance gate), expired requests end
     ``state="timeout"``, full queues shed, the watchdog raises a
     structured DegradedError that loses nothing, retry exhaustion
     propagates, and the eviction-storm guard truncates instead of
     livelocking;
  3. the compile-count contract under chaos — deadlines, shedding,
     backoff and injected faults are host-side only, so the steady
     state stays at two compiled programs with ZERO recompiles.

Crash-mid-checkpoint scenarios live with the other checkpoint tests in
tests/test_checkpointing.py (same injector, ``checkpoint.*`` sites).
"""

import time
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.engine import InferenceEngine
from deepspeed_tpu.inference.serving import (DegradedError, ServeRequest,
                                             ServingEngine)
from deepspeed_tpu.models import gpt
from deepspeed_tpu.utils import faults as faults_lib
from deepspeed_tpu.utils.faults import (Fault, FaultInjector, InjectedCrash,
                                        TransientDeviceError,
                                        UnknownFaultSiteWarning, parse_spec)


# ---------------------------------------------------------------------------
# injector unit tests (pure host — no devices needed)
# ---------------------------------------------------------------------------

def test_fault_spec_grammar():
    fs = parse_spec("serving.decode:device_error@3;"
                    "cache.ensure:cache_exhausted@5*2~0.5")
    assert fs[0] == Fault("serving.decode", "device_error", step=3)
    assert fs[1] == Fault("cache.ensure", "cache_exhausted", step=5,
                          count=2, param=0.5)
    # ',' is accepted as a ';' synonym; blank entries are skipped
    assert parse_spec("a.b:slow@0~0.1, c.d:crash@2") == [
        Fault("a.b", "slow", param=0.1), Fault("c.d", "crash", step=2)]
    assert parse_spec("") == []
    with pytest.raises(ValueError, match="bad fault spec"):
        parse_spec("no-colon-here")
    with pytest.raises(ValueError, match="unknown fault kind"):
        parse_spec("site:meteor_strike@0")


def test_injector_visit_schedule_and_fired_log():
    inj = FaultInjector([Fault("s", "slow", step=1, count=2, param=0.0)])
    assert inj.visit("s") is None                 # visit 0: before window
    assert inj.visit("s").kind == "slow"          # visits 1, 2: inside
    assert inj.visit("s") is not None
    assert inj.visit("s") is None                 # visit 3: past it
    assert inj.visit("other") is None             # sites are independent
    assert inj.fired == [("s", "slow", 1), ("s", "slow", 2)]
    inj.reset()                                   # same timeline replays
    assert inj.visit("s") is None and inj.fired == []


def test_injector_fire_raises_generic_kinds():
    inj = FaultInjector([Fault("a", "device_error"), Fault("b", "crash"),
                         Fault("c", "cache_exhausted")])
    with pytest.raises(TransientDeviceError):
        inj.fire("a")
    with pytest.raises(InjectedCrash):
        inj.fire("b")
    # domain-specific kinds are RETURNED for the site to interpret
    f = inj.fire("c")
    assert f is not None and f.kind == "cache_exhausted"
    assert inj.fire("c") is None                  # one-shot by default


def test_jitter_is_seed_deterministic():
    a, b = FaultInjector(seed=42), FaultInjector(seed=42)
    seq = [a.jitter(1.0) for _ in range(4)]
    assert seq == [b.jitter(1.0) for _ in range(4)]
    assert all(0.0 <= j < 1.0 for j in seq)
    assert seq != [FaultInjector(seed=43).jitter(1.0) for _ in range(4)]


def test_injector_from_env_mapping():
    inj = FaultInjector.from_env({"DS_FAULTS": "x.y:crash@2",
                                  "DS_FAULT_SEED": "7"})
    assert inj.faults == [Fault("x.y", "crash", step=2)] and inj.seed == 7
    assert FaultInjector.from_env({}).faults == []


def test_injected_context_installs_and_restores():
    base = faults_lib.active()
    with faults_lib.injected(Fault("q", "slow"), seed=5) as inj:
        assert faults_lib.active() is inj and inj.seed == 5
    assert faults_lib.active() is base


# ---------------------------------------------------------------------------
# serving under chaos
# ---------------------------------------------------------------------------

def tiny(**over):
    cfg = gpt.GPTConfig(vocab_size=128, n_layers=2, n_heads=4, d_model=32,
                        max_seq_len=64, use_flash_attention=False,
                        remat=False, dtype=jnp.float32, **over)
    params = gpt.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def prompts_of(lengths, seed=1):
    r = np.random.default_rng(seed)
    return [r.integers(1, 128, n).astype(np.int32) for n in lengths]


@pytest.fixture(scope="module")
def eng(devices):
    cfg, params = tiny()
    return InferenceEngine(config=cfg, params=params, dtype=jnp.float32)


def _solo_refs(eng, prompts, n):
    return [eng.generate(p[None], max_new_tokens=n)[0] for p in prompts]


def test_chaos_parity_under_injected_faults(eng):
    """The acceptance gate: injected cache exhaustion + transient device
    errors (serving AND engine level) + a slow step, all scheduled by
    one seeded injector — every request still finishes exactly once,
    token-for-token equal to the fault-free greedy stream."""
    prompts = prompts_of((5, 9, 12, 3))
    refs = _solo_refs(eng, prompts, 6)
    chaos = [Fault("serving.prefill", "device_error", step=1),
             Fault("serving.decode", "device_error", step=2),
             Fault("engine.decode", "device_error", step=4),
             Fault("serving.decode", "slow", step=6, param=0.005),
             Fault("cache.ensure", "cache_exhausted", step=5)]
    with faults_lib.injected(*chaos, seed=0) as inj:
        # spec and the decode horizon pinned off here and below: these
        # tests exercise the PLAIN decode path's fault sites
        # (serving.decode fires per one-token dispatch, and the injected
        # visit indices are calibrated to that cadence); the speculative
        # sites' chaos contract is test_spec_serving.py's job, the
        # serving.horizon degrade is test_horizon.py's
        srv = ServingEngine(eng, num_slots=2, block_size=4, num_blocks=24,
                            prefill_chunk=8, max_retries=3,
                            retry_backoff_s=0.001, spec_decode=False,
                            decode_horizon=1)
        out = srv.run([ServeRequest(rid=i, prompt=p, max_new_tokens=6)
                       for i, p in enumerate(prompts)])
    for i, ref in enumerate(refs):
        np.testing.assert_array_equal(out[i], ref)
    # exactly-once: four terminal requests, all "done", no duplicates
    assert sorted(r.rid for r in srv.finished) == [0, 1, 2, 3]
    assert all(r.state == "done" for r in srv.finished)
    # the chaos really happened and was survived
    assert srv.stats["retries"] >= 3
    assert srv.stats["evictions"] >= 1          # injected exhaustion evicted
    kinds = {k for _s, k, _v in inj.fired}
    assert {"device_error", "cache_exhausted", "slow"} <= kinds


def test_deadline_expires_slot_holder_with_partial_tokens(eng):
    """A slot holder past its deadline retires as ``timeout`` keeping
    its partial output (a prefix of the fault-free stream) and frees
    its blocks; unaffected requests keep full parity."""
    p1, p2 = prompts_of((6, 7), seed=5)
    ref1 = _solo_refs(eng, [p1], 30)[0]
    ref2 = _solo_refs(eng, [p2], 8)[0]
    srv = ServingEngine(eng, num_slots=2, block_size=4, num_blocks=24)
    out = srv.run([ServeRequest(rid="t", prompt=p1, max_new_tokens=30,
                                deadline=4.0),     # scheduler-step clock
                   ServeRequest(rid="ok", prompt=p2, max_new_tokens=8)])
    done = {r.rid: r for r in srv.finished}
    assert done["t"].state == "timeout"
    assert 0 < len(done["t"].out) < 30
    np.testing.assert_array_equal(
        out["t"], ref1[:len(p1) + len(done["t"].out)])
    np.testing.assert_array_equal(out["ok"], ref2)
    assert srv.stats["timeouts"] == 1
    assert not srv.cache.active.any()            # timed-out blocks freed


def test_deadline_expires_queued_request_without_a_slot(eng):
    """A queued request whose deadline passes before admission times out
    in place — it never claims a slot or blocks."""
    p1, p2 = prompts_of((8, 8), seed=6)
    srv = ServingEngine(eng, num_slots=1, block_size=4, num_blocks=24)
    out = srv.run([ServeRequest(rid="long", prompt=p1, max_new_tokens=20),
                   ServeRequest(rid="q", prompt=p2, max_new_tokens=4,
                                deadline=2.0)])
    done = {r.rid: r for r in srv.finished}
    assert done["q"].state == "timeout" and done["q"].out == []
    np.testing.assert_array_equal(out["q"], p2)  # prompt only
    assert done["long"].state == "done"


def test_bounded_queue_sheds_newest(eng):
    """reject-newest load shedding: the submit into a full queue gets an
    immediate terminal answer (``shed``) and backpressure reads 1.0;
    accepted work is untouched."""
    prompts = prompts_of((5, 6, 7), seed=8)
    refs = _solo_refs(eng, prompts[:2], 4)
    srv = ServingEngine(eng, num_slots=1, block_size=4, num_blocks=24,
                        max_queue=2)
    reqs = [ServeRequest(rid=i, prompt=p, max_new_tokens=4)
            for i, p in enumerate(prompts)]
    assert srv.submit(reqs[0]) and srv.submit(reqs[1])
    assert srv.stats["backpressure"] == 1.0      # queue at capacity
    assert not srv.submit(reqs[2])               # shed, not queued
    assert reqs[2].state == "shed" and srv.stats["shed"] == 1
    out = srv.run()
    for i, ref in enumerate(refs):
        np.testing.assert_array_equal(out[i], ref)
    np.testing.assert_array_equal(out[2], prompts[2])   # no tokens
    assert srv.stats["backpressure"] == 0.0      # drained
    # exactly one terminal state per submitted request
    assert sorted(r.rid for r in srv.finished) == [0, 1, 2]


def test_watchdog_degraded_error_keeps_everything(eng):
    """Consecutive over-budget decode steps (a hung step is a ``slow``
    fault bigger than the budget) raise DegradedError with every
    finished result AND an in-flight snapshot attached; the scheduler
    state stays consistent, so continuing to step drains to full
    parity."""
    p1, p2 = prompts_of((6, 9), seed=12)
    ref1 = _solo_refs(eng, [p1], 12)[0]
    ref2 = _solo_refs(eng, [p2], 3)[0]
    with faults_lib.injected(
            Fault("serving.decode", "slow", step=4, count=2, param=0.6)):
        # the budget is wall time on a CPU that the other workers of a
        # whole run share: 0.25 s is out of an ordinary decode step's
        # reach however loaded the host (57 ms was seen), and the 0.6 s
        # injected slow fault still clears it — same calibration as the
        # drain tests
        srv = ServingEngine(eng, num_slots=2, block_size=4, num_blocks=24,
                            step_time_budget_s=0.25, watchdog_grace=2,
                            spec_decode=False, decode_horizon=1)
        with pytest.raises(DegradedError, match="over budget") as ei:
            srv.run([ServeRequest(rid="a", prompt=p1, max_new_tokens=12),
                     ServeRequest(rid="b", prompt=p2, max_new_tokens=3)])
        e = ei.value
        # "b" finished before the trip; "a" is mid-flight with its
        # tokens intact in the snapshot — nothing thrown away
        np.testing.assert_array_equal(e.results["b"], ref2)
        assert [p["rid"] for p in e.pending] == ["a"]
        assert e.pending[0]["generated"] > 0
        assert e.stats["watchdog_trips"] >= 2
        out = srv.run()                          # resume: drains cleanly
    np.testing.assert_array_equal(out["a"], ref1)
    assert all(r.state == "done" for r in srv.finished)


def test_retry_backoff_survives_transient_burst(eng):
    """A burst shorter than max_retries is absorbed: the request
    completes with parity and the retries are counted."""
    p = prompts_of((7,), seed=14)[0]
    ref = _solo_refs(eng, [p], 5)[0]
    with faults_lib.injected(
            Fault("serving.decode", "device_error", step=1, count=2)):
        srv = ServingEngine(eng, num_slots=1, block_size=4, num_blocks=24,
                            max_retries=3, retry_backoff_s=0.001,
                            spec_decode=False, decode_horizon=1)
        out = srv.run([ServeRequest(rid=0, prompt=p, max_new_tokens=5)])
    np.testing.assert_array_equal(out[0], ref)
    assert srv.stats["retries"] == 2


def test_retry_exhaustion_propagates(eng):
    """A fault outlasting the retry budget surfaces as
    TransientDeviceError — the engine does not spin forever."""
    p = prompts_of((6,), seed=15)[0]
    with faults_lib.injected(
            Fault("serving.decode", "device_error", step=0, count=10)):
        srv = ServingEngine(eng, num_slots=1, block_size=4, num_blocks=24,
                            max_retries=2, retry_backoff_s=0.001,
                            spec_decode=False)
        with pytest.raises(TransientDeviceError):
            srv.run([ServeRequest(rid=0, prompt=p, max_new_tokens=5)])
    assert srv.stats["retries"] == 2


def test_eviction_cap_truncates_instead_of_livelock(eng):
    """With every request pinned (max_evictions=0) and a pool that
    cannot grow, the engine truncate-finishes rather than thrashing:
    it drains, outputs are prefixes of the fault-free streams, and the
    guard is visible in ``evict_capped``."""
    p1, p2 = prompts_of((10, 9), seed=9)
    refs = {"a": _solo_refs(eng, [p1], 12)[0],
            "b": _solo_refs(eng, [p2], 10)[0]}
    srv = ServingEngine(eng, num_slots=2, block_size=4, num_blocks=7,
                        max_evictions=0)
    srv.cache.watermark = 0
    out = srv.run([ServeRequest(rid="a", prompt=p1, max_new_tokens=12),
                   ServeRequest(rid="b", prompt=p2, max_new_tokens=10)],
                  max_steps=500)
    assert srv.stats["evictions"] == 0           # nobody was preempted
    assert srv.stats["evict_capped"] >= 1
    for rid, req in ((r.rid, r) for r in srv.finished):
        assert req.state == "done"
        np.testing.assert_array_equal(
            out[rid], refs[rid][:len(out[rid])])  # truncated, not wrong


def test_chaos_compile_count_contract(eng):
    """The robustness features are host-side only: with deadlines,
    shedding, a watchdog budget, backoff AND injected faults all
    active, the steady state is still exactly two compiled programs
    and ZERO recompiles."""
    from deepspeed_tpu.utils.compile_guard import CompileWatch, cache_size
    p1, p2 = prompts_of((10, 9), seed=9)

    def run_workload(chaos):
        with faults_lib.injected(*chaos, seed=0):
            srv = ServingEngine(eng, num_slots=2, block_size=4,
                                num_blocks=7, prefill_chunk=8,
                                max_queue=4, max_retries=3,
                                retry_backoff_s=0.001,
                                step_time_budget_s=10.0,
                                spec_decode=False, decode_horizon=1)
            srv.cache.watermark = 0
            out = srv.run(
                [ServeRequest(rid="a", prompt=p1, max_new_tokens=12,
                              deadline=1e9),
                 ServeRequest(rid="b", prompt=p2, max_new_tokens=10)])
        return srv, out

    srv, warm = run_workload([])                 # warmup compiles all
    assert srv.stats["evictions"] >= 1
    # the module-shared engine carries one program per pool shape the
    # earlier tests used; the contract here is that chaos adds NONE
    n_before = (cache_size(eng._prefill_slot), cache_size(eng._decode_slots))
    chaos = [Fault("serving.prefill", "device_error", step=1),
             Fault("serving.decode", "device_error", step=3),
             Fault("cache.ensure", "cache_exhausted", step=4)]
    watch = CompileWatch(max_compiles=0, label="chaos steady state")
    watch.wrap(eng._prefill_slot)
    watch.wrap(eng._decode_slots)
    with watch:                                  # raises on any compile
        srv2, out = run_workload(chaos)
    assert srv2.stats["retries"] >= 2
    for rid in ("a", "b"):
        np.testing.assert_array_equal(out[rid], warm[rid])
    if n_before[0] is not None:
        assert (cache_size(eng._prefill_slot),
                cache_size(eng._decode_slots)) == n_before


def test_chaos_prefix_cache_sites_parity(eng):
    """The prefix-cache fault sites under one seeded injector: a
    ``cache.match`` exhaustion degrades an admission to a cold miss, a
    ``cache.cow`` exhaustion aborts a copy-on-write admission BEFORE
    any bookkeeping (the request retries and succeeds), and a transient
    device error rides along — parity and exactly-once still hold with
    the prefix cache on."""
    base = np.arange(1, 31, dtype=np.int32)          # 30 tokens, bs=8
    div = base.copy()
    div[21] = 99                                     # diverges mid-block
    refs = _solo_refs(eng, [base, base, div], 6)
    chaos = [Fault("cache.match", "cache_exhausted", step=1),
             Fault("cache.cow", "cache_exhausted", step=0),
             Fault("serving.decode", "device_error", step=2)]
    with faults_lib.injected(*chaos, seed=0) as inj:
        srv = ServingEngine(eng, num_slots=1, block_size=8, num_blocks=24,
                            prefill_chunk=16, prefix_cache=True,
                            max_retries=3, retry_backoff_s=0.001,
                            spec_decode=False)
        out = srv.run([ServeRequest(rid=i, prompt=p, max_new_tokens=6)
                       for i, p in enumerate((base, base, div))])
    fired_sites = {s for s, _k, _v in inj.fired}
    assert {"cache.match", "cache.cow", "serving.decode"} <= fired_sites
    # request 0 cold; request 1's lookup was degraded to a miss (visit
    # 1); request 2's first COW attempt failed and the retry landed
    assert srv.stats["prefix_hits"] == 1
    assert srv.cache.cow_copies == 1
    for i, ref in enumerate(refs):
        np.testing.assert_array_equal(out[i], ref)
    assert all(r.state == "done" for r in srv.finished)
    assert srv.cache.held_blocks == 0
    assert (srv.cache._refcount == 0).all()          # no leaked claims


def test_parse_spec_warns_once_on_unknown_site():
    """A typo'd site warns loudly (once per site) instead of silently
    injecting nothing; known sites parse quietly."""
    faults_lib._warned_sites.discard("serving.prefil")
    with pytest.warns(UnknownFaultSiteWarning, match="serving.prefil"):
        parse_spec("serving.prefil:crash@0")
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        parse_spec("serving.prefil:crash@0")     # already warned: silent
        parse_spec("serving.decode:crash@0")     # known site: silent


def test_retry_backoff_capped_by_slot_deadline(eng):
    """Backoff sleeps never outlive the tightest active-slot deadline:
    with retry_backoff_s=5.0 an uncapped burst of 3 retries would
    sleep >= 1.5 s (each pause floors at the 0.5 s clamp); the slack
    cap bounds the whole wall-clock run by the request's deadline and
    retires it as a timeout with its partial tokens."""
    pw, p = prompts_of((6, 7), seed=41)
    with faults_lib.injected(
            Fault("serving.decode", "device_error", step=8, count=3),
            seed=0) as inj:
        srv = ServingEngine(eng, num_slots=1, block_size=4, num_blocks=16,
                            prefill_chunk=8, max_retries=3,
                            retry_backoff_s=5.0, spec_decode=False,
                            decode_horizon=1)
        # warmup run (decode visits 0-3): compiles this pool shape so
        # the timed request's deadline measures backoff, not XLA
        srv.run([ServeRequest(rid="w", prompt=pw, max_new_tokens=4)],
                wall_clock=True)
        t0 = time.perf_counter()
        req = ServeRequest(rid="d", prompt=p, max_new_tokens=32,
                           deadline=t0 + 0.3)
        srv.run([req], wall_clock=True)
        elapsed = time.perf_counter() - t0
    assert inj.fired and srv.stats["retries"] >= 1
    assert elapsed < 1.0, f"backoff ignored the slot deadline: {elapsed:.2f}s"
    assert req.state == "timeout" and len(req.out) >= 1


def test_pending_snapshot_cold_resumes_into_fresh_engine(eng):
    """The degrade snapshot is cold-resume complete: feeding its
    entries (via ServeRequest.from_snapshot) to a FRESH engine finishes
    every request token-identical to an undisturbed run, and
    pending_snapshot(release=True) frees the dead engine's cache
    claims so its pool is reclaimable."""
    prompts = prompts_of((6, 9, 12), seed=43)
    refs = _solo_refs(eng, prompts, 8)
    with faults_lib.injected(
            Fault("serving.decode", "slow", step=3, param=0.6), seed=0):
        srv = ServingEngine(eng, num_slots=2, block_size=4, num_blocks=24,
                            prefill_chunk=8, step_time_budget_s=0.25,
                            watchdog_grace=1, spec_decode=False,
                            decode_horizon=1)
        with pytest.raises(DegradedError) as ei:
            srv.run([ServeRequest(rid=i, prompt=p, max_new_tokens=8)
                     for i, p in enumerate(prompts)])
    e = ei.value
    snap = srv.pending_snapshot(release=True)
    assert {s["rid"] for s in snap} == {s["rid"] for s in e.pending}
    assert srv.cache.held_blocks == 0 and not srv.queue
    assert (srv.cache._refcount == 0).all()
    fresh = ServingEngine(eng, num_slots=2, block_size=4, num_blocks=24,
                          prefill_chunk=8, spec_decode=False)
    out = fresh.run([ServeRequest.from_snapshot(s) for s in snap])
    out.update(e.results)
    assert set(out) == set(range(len(prompts)))
    for i, ref in enumerate(refs):
        np.testing.assert_array_equal(out[i], ref)
    assert all(r.state == "done" for r in fresh.finished)
