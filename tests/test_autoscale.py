"""SLO-controller suite (tentpole: inference/autoscale.py + the
router's elasticity surface).

Layers:
  1. elasticity units — add_replica/retire_replica mechanics, the
     retired state being terminal and undispatchable, retiring a BUSY
     replica draining token-losslessly onto survivors, the tightened-
     admission gate shedding exactly the batch class;
  2. the control loop — a seeded burst drives scale-up (queue pressure
     + windowed p99 over budget), sustained idle drives retire back to
     min_replicas, and the hysteretic tighten/relax admission cycle;
  3. the acceptance gates — controller OFF is token-bit-identical to a
     never-triggering controller ON; scale-up compiles ZERO new
     programs (replicas share one InferenceEngine; CompileWatch(0));
     the chaos suite stays green with the controller active; and every
     decision is reconstructable from the exported trace with the
     metric values that triggered it (tools/trace_analyze.py fleet).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.autoscale import SLOController
from deepspeed_tpu.inference.engine import InferenceEngine
from deepspeed_tpu.inference.router import RETIRED, ReplicaRouter
from deepspeed_tpu.inference.serving import ServeRequest, ServingEngine
from deepspeed_tpu.models import gpt
from deepspeed_tpu.telemetry import Telemetry
from deepspeed_tpu.utils import faults as faults_lib
from deepspeed_tpu.utils.faults import Fault
from tools.load_gen import drive, make_requests
from tools.trace_analyze import analyze_fleet_trace

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


def mk_srv(eng, telemetry=None, **kw):
    defaults = dict(num_slots=2, block_size=4, num_blocks=24,
                    prefill_chunk=8, spec_decode=False)
    defaults.update(kw)
    return ServingEngine(eng, telemetry=telemetry, **defaults)


def mk_reqs(prompts, n=6, **kw):
    return [ServeRequest(rid=i, prompt=p, max_new_tokens=n, **kw)
            for i, p in enumerate(prompts)]


# ---------------------------------------------------------------------------
# elasticity units (no controller)
# ---------------------------------------------------------------------------

def test_add_and_retire_replica_mechanics(eng):
    router = ReplicaRouter([mk_srv(eng)],
                           replica_factory=lambda i, tag: mk_srv(eng))
    assert router.add_replica(now=1.0, reason="test") == 1
    assert router.health() == ["healthy", "healthy"]
    assert router.stats["scale_ups"] == 1
    # retire drains (nothing in flight here) and parks the replica
    assert router.retire_replica(1, now=2.0) == 0
    assert router.health() == ["healthy", RETIRED]
    assert router.stats["retires"] == 1
    # retired is terminal: not re-retirable, never dispatched to
    with pytest.raises(ValueError, match="already retired"):
        router.retire_replica(1)
    with pytest.raises(ValueError, match="last dispatchable"):
        router.retire_replica(0)
    p, = prompts_of((6,))
    router.submit(ServeRequest(rid="x", prompt=p, max_new_tokens=4))
    assert len(router.replicas[1].srv.queue) == 0 \
        and all(s is None for s in router.replicas[1].srv.slots)
    # no factory and no engine => explicit error
    bare = ReplicaRouter([mk_srv(eng)])
    with pytest.raises(RuntimeError, match="replica_factory"):
        bare.add_replica()
    # an explicit engine works without a factory
    assert bare.add_replica(srv=mk_srv(eng)) == 1


def test_retire_busy_replica_token_parity(eng):
    """Retiring a replica mid-decode drains its snapshot onto the
    survivor through the breaker-drain path: every request's final
    tokens are identical to an undisturbed solo run."""
    prompts = prompts_of((6, 9, 12, 5), seed=4)
    refs = _solo_refs(eng, prompts, 6)
    router = ReplicaRouter([mk_srv(eng), mk_srv(eng)])
    for r in mk_reqs(prompts, n=6):
        router.submit(r, now=0.0)
    for _ in range(3):                       # both replicas mid-flight
        router.step()
    assert router.replicas[1].srv.busy
    drained = router.retire_replica(1, now=3.0, reason="scale-down")
    assert drained > 0
    out = router.run()
    assert sorted(out) == [0, 1, 2, 3]
    for i, ref in enumerate(refs):
        np.testing.assert_array_equal(out[i], ref)
    assert router.health()[1] == RETIRED
    assert router.stats["drained_requests"] == drained


def test_tightened_admission_sheds_batch_class_only(eng):
    """The shed_batch gate (the controller's admission actuator) sheds
    exactly priority="batch" traffic, terminally and observably;
    interactive traffic still dispatches."""
    p1, p2 = prompts_of((6, 7), seed=2)
    tel = Telemetry()
    router = ReplicaRouter([mk_srv(eng, telemetry=tel)], telemetry=tel)
    router.shed_batch = True
    batch = ServeRequest(rid="b", prompt=p1, max_new_tokens=4,
                         priority="batch")
    inter = ServeRequest(rid="i", prompt=p2, max_new_tokens=4,
                         priority="interactive")
    assert router.submit(batch, now=1.0) is False
    assert batch.state == "shed" and batch.finished_at == 1.0
    assert router.submit(inter, now=1.0) is True
    out = router.run()
    assert set(out) == {"b", "i"}
    assert len(out["b"]) == len(p1)          # prompt only, nothing new
    assert router.stats["shed"] == 1
    sheds = [r for r in tel.tracer.records() if r[1] == "shed"]
    assert len(sheds) == 1 and sheds[0][5]["priority"] == "batch"
    # gate open again: batch admits normally
    router.shed_batch = False
    b2 = ServeRequest(rid="b2", prompt=p1.copy(), max_new_tokens=4,
                      priority="batch")
    assert router.submit(b2, now=2.0) is True


def test_fleet_snapshot_and_merged_prometheus(eng):
    """fleet_snapshot / to_prometheus merge every registry in the fleet
    (router + per-replica telemetry) into one view with the fleet shape
    and by-state gauges attached."""
    tel_a, tel_b = Telemetry(), Telemetry()   # distinct registries
    router = ReplicaRouter([mk_srv(eng, telemetry=tel_a),
                            mk_srv(eng, telemetry=tel_b)],
                           telemetry=tel_a)
    prompts = prompts_of((6, 7, 8), seed=3)
    for r in mk_reqs(prompts, n=4):
        router.submit(r, now=0.0)
    router.run()
    assert len(router.fleet_registries()) == 2    # tel_a shared, tel_b
    snap = router.fleet_snapshot()
    assert snap["fleet"]["replicas"] == 2
    assert snap["fleet"]["by_state"]["healthy"] == 2
    assert snap["counters"]["serving_completed"] == 3   # summed fleet-wide
    assert snap["counters"]["router_dispatched"] == 3
    assert snap["histograms"]["serving_ttft"]["count"] == 3
    prom = router.to_prometheus()
    assert "router_replicas_healthy 2" in prom
    assert "serving_ttft_bucket" in prom and "router_dispatched 3" in prom


# ---------------------------------------------------------------------------
# the control loop
# ---------------------------------------------------------------------------

def test_controller_scales_up_under_burst(eng):
    """A burst the single replica cannot absorb trips the controller
    (queue pressure + windowed p99): the fleet grows via the factory,
    every decision lands in the log with its triggering metrics, and
    all tokens still complete."""
    tel = Telemetry()
    ctrl = SLOController(ttft_slo=2.0, window=8.0, eval_every=1,
                         cooldown=2.0, max_replicas=3, min_samples=2,
                         queue_high=1.5, idle_to_retire=1e9)
    router = ReplicaRouter([mk_srv(eng, telemetry=tel)],
                           replica_factory=lambda i, tag:
                               mk_srv(eng, telemetry=tel),
                           telemetry=tel, autoscale=ctrl)
    prompts = prompts_of((6, 8, 10, 7, 9, 6, 8, 11), seed=5)
    out = router.run(mk_reqs(prompts, n=6))
    assert sorted(out) == list(range(8))
    ups = [d for d in ctrl.decisions if d["action"] == "scale_up"]
    assert len(ups) == 2 and len(router.replicas) == 3
    assert router.health() == ["healthy"] * 3
    # each decision carries the metrics that triggered it
    for d in ups:
        assert d["queue_pressure"] or d["p99_ttft"] > 2.0
        assert {"p99_ttft", "window_count", "queue_depth", "load",
                "active_replicas", "at", "replica"} <= set(d)
    # registry-backed decision counters match the log
    snap = router.fleet_snapshot()
    assert snap["counters"]["autoscale_scale_ups"] == 2
    assert snap["counters"]["autoscale_decisions"] == len(ctrl.decisions)
    assert snap["counters"]["router_scale_ups"] == 2
    assert snap["gauges"]["autoscale_target_replicas"] == 3
    # cooldown held: fleet-shape changes are >= cooldown apart
    assert ups[1]["at"] - ups[0]["at"] >= 2.0


def test_policy_fleet_holds_the_ttft_slo_a_fixed_fleet_violates():
    """The closed-loop SLO contrast (docs/OBSERVABILITY.md): ONE seeded
    load_gen population with a rate spike in the middle, on the
    scheduler's step clock, through a FIXED 1-replica fleet and through
    a fleet that starts at 1 replica with the controller active. The
    fixed fleet queues through the spike and violates the stated
    p99-TTFT budget; the controller sees the windowed p99 cross it,
    scales up through the factory and holds it."""
    ttft_slo = 12.0
    cfg = gpt.GPTConfig(vocab_size=512, n_layers=2, n_heads=4, d_model=32,
                        max_seq_len=40 + 24 + 8, use_flash_attention=False,
                        remat=False, dtype=jnp.float32)
    eng = InferenceEngine(config=cfg, dtype=jnp.float32,
                          params=gpt.init_params(jax.random.PRNGKey(0), cfg))
    entries = make_requests(seed=0, mix="chat", vocab_size=cfg.vocab_size,
                            phases=[(6, 0.2), (60, 0.5), (30, 0.05)],
                            max_prompt_len=40)

    def srv(tel):
        return mk_srv(eng, telemetry=tel, block_size=8, num_blocks=None,
                      prefill_chunk=16)
    tel_f = Telemetry()
    fixed = ReplicaRouter([srv(tel_f)], telemetry=tel_f)
    res_f = drive(fixed, entries, mode="open", slo_ttft=ttft_slo)
    tel_p = Telemetry()
    ctrl = SLOController(ttft_slo=ttft_slo, window=16.0, eval_every=2,
                         max_replicas=3, cooldown=4.0, idle_to_retire=1e9,
                         min_samples=3, queue_high=2.0)
    policy = ReplicaRouter([srv(tel_p)],
                           replica_factory=lambda i, tag: srv(tel_p),
                           telemetry=tel_p, autoscale=ctrl)
    res_p = drive(policy, entries, mode="open", slo_ttft=ttft_slo)
    assert res_f["ttft_p99"] > ttft_slo >= res_p["ttft_p99"], (
        res_f["ttft_p99"], res_p["ttft_p99"])
    assert res_p["slo_attainment"] > res_f["slo_attainment"]
    counters = policy.fleet_snapshot()["counters"]
    assert counters["router_scale_ups"] >= 1
    assert counters["autoscale_scale_ups"] == counters["router_scale_ups"]
    assert counters["autoscale_decisions"] == len(ctrl.decisions)


def test_controller_retires_on_sustained_idle(eng):
    """A quiet fleet above min_replicas shrinks: after idle_to_retire
    consecutive idle clock units the controller drains-and-retires the
    highest-index active replica, down to min_replicas."""
    ctrl = SLOController(ttft_slo=100.0, window=4.0, eval_every=1,
                         cooldown=1.0, min_replicas=1, max_replicas=3,
                         idle_to_retire=5.0, min_samples=2)
    router = ReplicaRouter([mk_srv(eng) for _ in range(3)],
                           autoscale=ctrl)
    prompts = prompts_of((6, 7), seed=6)
    out = router.run(mk_reqs(prompts, n=4))
    assert sorted(out) == [0, 1]
    for t in range(20):                       # idle ticks
        router.step(float(100 + t))
    retires = [d for d in ctrl.decisions if d["action"] == "retire"]
    assert [d["replica"] for d in retires] == [2, 1]   # top-down
    assert router.health() == ["healthy", RETIRED, RETIRED]
    assert router.stats["retires"] == 2
    # the floor holds: replica 0 is never retired
    assert all(d["action"] != "retire" or d["replica"] != 0
               for d in ctrl.decisions)


def test_controller_tighten_relax_hysteresis(eng):
    """With the fleet already at max_replicas the controller's only
    lever is admission: sustained pressure closes the shed_batch gate,
    and it re-opens only after the window falls below relax_ratio*slo
    (or drains entirely) — the hysteresis cycle, observable in the
    decision log and the admission gauge."""
    tel = Telemetry()
    ctrl = SLOController(ttft_slo=1.0, window=6.0, eval_every=1,
                         max_replicas=1, min_samples=1, relax_ratio=0.5,
                         queue_high=0.5, idle_to_retire=1e9)
    router = ReplicaRouter([mk_srv(eng, telemetry=tel)],
                           telemetry=tel, autoscale=ctrl)   # no factory
    prompts = prompts_of((8, 9, 10, 7), seed=7)
    out = router.run(mk_reqs(prompts, n=6))
    assert sorted(out) == [0, 1, 2, 3]
    actions = [d["action"] for d in ctrl.decisions]
    assert "tighten" in actions and "scale_up" not in actions
    assert router.shed_batch is True          # still tight at drain
    # quiet ticks past the window: the gate relaxes
    for t in range(12):
        router.step(float(200 + t))
    assert router.shed_batch is False
    ti, ri = actions.index("tighten"), \
        [d["action"] for d in ctrl.decisions].index("relax")
    assert ri > ti
    assert router.metrics.gauge("autoscale_admission_tight").value == 0
    # while tight, a batch submit would have shed (the gate is live)
    assert ctrl.decisions[ti]["shed_batch"] is True


# ---------------------------------------------------------------------------
# acceptance gates
# ---------------------------------------------------------------------------

def test_controller_off_is_bit_reference(eng):
    """autoscale=None (default) and a controller that never triggers
    produce token-bit-identical output — the controller only observes
    until a threshold crosses."""
    prompts = prompts_of((6, 9, 12, 5), seed=8)
    refs = _solo_refs(eng, prompts, 6)

    def run(ctrl):
        router = ReplicaRouter([mk_srv(eng), mk_srv(eng)],
                               autoscale=ctrl)
        return router.run(mk_reqs(prompts, n=6)), router
    out_off, r_off = run(None)
    out_on, r_on = run(SLOController(ttft_slo=1e9, idle_to_retire=1e9))
    assert sorted(out_off) == sorted(out_on) == [0, 1, 2, 3]
    for i, ref in enumerate(refs):
        np.testing.assert_array_equal(out_off[i], ref)
        np.testing.assert_array_equal(out_on[i], ref)
    assert r_on.health() == r_off.health() == ["healthy", "healthy"]
    assert all(d["action"] == "noop"
               for d in r_on.autoscale.decisions)


def test_scale_up_compiles_nothing(eng):
    """The compile contract under elasticity: controller-driven
    scale-ups produce replicas sharing the fleet's InferenceEngine, so
    the whole burst-and-grow run executes under CompileWatch(0)."""
    from deepspeed_tpu.utils.compile_guard import CompileWatch
    prompts = prompts_of((6, 8, 10, 7, 9, 6), seed=9)
    # warm the slot programs outside the watch
    mk_srv(eng).run(mk_reqs(prompts[:1], n=4))
    ctrl = SLOController(ttft_slo=2.0, window=8.0, eval_every=1,
                         cooldown=2.0, max_replicas=3, min_samples=2,
                         queue_high=1.0, idle_to_retire=1e9)
    router = ReplicaRouter([mk_srv(eng)],
                           replica_factory=lambda i, tag: mk_srv(eng),
                           autoscale=ctrl)
    watch = CompileWatch(max_compiles=0, label="autoscale")
    watch.wrap(eng._prefill_slot)
    watch.wrap(eng._decode_slots)
    with watch:                               # raises on any compile
        out = router.run(mk_reqs(prompts, n=6))
    assert sorted(out) == list(range(6))
    assert router.stats["scale_ups"] >= 1     # the fleet actually grew


@pytest.mark.slow
def test_chaos_green_with_controller_active(eng):
    """The router chaos scenario (breaker trips + drains under seeded
    router.step faults) stays token-lossless with the controller
    ticking: breaks, drains, scale-ups and admission all compose."""
    prompts = prompts_of((6, 9, 12, 5, 8, 10), seed=10)
    refs = _solo_refs(eng, prompts, 6)
    chaos = [Fault("router.step", "device_error", step=4, count=3)]
    with faults_lib.injected(*chaos, seed=0) as inj:
        ctrl = SLOController(ttft_slo=2.0, window=8.0, eval_every=1,
                             cooldown=2.0, max_replicas=4, min_samples=2,
                             queue_high=1.5, idle_to_retire=1e9)
        router = ReplicaRouter([mk_srv(eng), mk_srv(eng)],
                               replica_factory=lambda i, tag: mk_srv(eng),
                               autoscale=ctrl, breaker_threshold=2)
        out = router.run(mk_reqs(prompts, n=6))
    assert inj.fired                          # the chaos happened
    assert sorted(out) == list(range(6))
    for i, ref in enumerate(refs):
        np.testing.assert_array_equal(out[i], ref)
    # breaker state and controller decisions coexist in the stats
    assert router.stats["breaker_trips"] >= 1
    assert len(ctrl.decisions) > 0


def test_decisions_reconstructable_from_trace(eng, tmp_path):
    """The observability acceptance gate: every controller evaluation
    lands in the Perfetto export as an ``autoscale`` instant carrying
    the triggering metric values, and ``trace_analyze fleet`` rebuilds
    the full decision + fleet-shape timeline from the file alone."""
    tel = Telemetry()
    ctrl = SLOController(ttft_slo=2.0, window=8.0, eval_every=1,
                         cooldown=2.0, max_replicas=3, min_samples=2,
                         queue_high=1.5, idle_to_retire=1e9)
    router = ReplicaRouter([mk_srv(eng, telemetry=tel)],
                           replica_factory=lambda i, tag:
                               mk_srv(eng, telemetry=tel),
                           telemetry=tel, autoscale=ctrl)
    prompts = prompts_of((6, 8, 10, 7, 9, 6, 8, 11), seed=11)
    router.run(mk_reqs(prompts, n=6))
    path = tel.export_trace(str(tmp_path / "fleet.json"))
    summary = analyze_fleet_trace(path, quiet=True)
    traced = summary["autoscale"]["decisions"]
    assert len(traced) == len(ctrl.decisions)
    for got, want in zip(traced, ctrl.decisions):
        assert got["action"] == want["action"]
        assert got["p99_ttft"] == want["p99_ttft"]
        assert got["queue_depth"] == want["queue_depth"]
        assert got["active_replicas"] == want["active_replicas"]
    ups = summary["autoscale"]["by_action"].get("scale_up", 0)
    assert ups == router.stats["scale_ups"] >= 1
    # the fleet-shape timeline matches: one 'scale add' per scale-up
    adds = [s for s in summary["scale"] if s["action"] == "add"]
    assert [a["replica"] for a in adds] \
        == list(range(1, 1 + ups))
    assert summary["dispatch"]["total"] == len(prompts)
