"""Cost-accounting plane tests (tentpole: deepspeed_tpu/telemetry/
costs.py + flight.py wired through inference/serving.py and
inference/router.py; docs/OBSERVABILITY.md).

Layers:
  1. program cost registry — every registered engine twin present on
     the engine gets an entry on CPU (XLA or analytic fallback), with
     the gauges exported;
  2. conservation — sum of per-request footprints (plus the unowned
     system residue) equals the accountant's per-class totals and the
     global counters EXACTLY, as integers, across eviction/requeue,
     spec-decode fallback, the fused decode horizon N=8, and a router
     fleet draining a killed replica onto survivors;
  3. off-mode — telemetry off is bit-identical with zero recompiles
     and registers none of the cost metrics;
  4. flight recorder — the chaos-induced DegradedError writes a
     versioned, CRC-stamped artifact from which tools/postmortem.py
     reconstructs the request timeline, fired faults and per-tenant
     cost summary with ZERO live objects.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.engine import InferenceEngine
from deepspeed_tpu.inference.serving import (DegradedError, ServeRequest,
                                             ServingEngine)
from deepspeed_tpu.inference.router import ReplicaRouter
from deepspeed_tpu.models import gpt
from deepspeed_tpu.telemetry import Telemetry, merge_registries
from deepspeed_tpu.telemetry.costs import (NOOP_COSTS, ProgramCostRegistry,
                                           attn_flops, infer_flops,
                                           model_flops_per_token)
from deepspeed_tpu.telemetry.flight import load_artifact
from deepspeed_tpu.utils import faults as faults_lib
from deepspeed_tpu.utils.faults import Fault, FaultInjector
from deepspeed_tpu.utils.jit_registry import (DISPATCH_CLASSES,
                                              engine_programs)

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


@pytest.fixture(scope="module")
def eng():
    cfg, params = tiny()
    return InferenceEngine(config=cfg, params=params, dtype=jnp.float32)


def _fold(requests, *accountants):
    """Re-sum per-request footprints + each accountant's unowned
    system residue into per-class totals (the conservation LHS)."""
    tot = {c: {"flops": 0, "hbm_bytes": 0, "dispatches": 0}
           for c in DISPATCH_CLASSES}
    bs = 0
    for r in requests:
        for c in DISPATCH_CLASSES:
            for k in tot[c]:
                tot[c][k] += r.cost[c][k]
        bs += r.cost["block_seconds"]
    for acc in accountants:
        for c in DISPATCH_CLASSES:
            for k in tot[c]:
                tot[c][k] += acc.system[c][k]
        bs += acc.system["block_seconds"]
    return tot, bs


def _assert_conserved(srv):
    """Footprints + system == totals == counters, exactly."""
    folded, bs = _fold(srv.finished, srv.costs)
    for c in DISPATCH_CLASSES:
        assert folded[c] == srv.costs.totals[c], \
            f"class {c}: footprints {folded[c]} != totals " \
            f"{srv.costs.totals[c]}"
    assert bs == srv.costs.block_seconds_total
    counters = srv.metrics.snapshot()["counters"]
    assert counters["serving_flops_total"] == \
        sum(folded[c]["flops"] for c in DISPATCH_CLASSES)
    assert counters["serving_hbm_bytes_total"] == \
        sum(folded[c]["hbm_bytes"] for c in DISPATCH_CLASSES)
    assert counters["serving_kv_block_seconds"] == bs


# ---------------------------------------------------------------------------
# analytic model units
# ---------------------------------------------------------------------------

def test_analytic_formulas_are_exact_integers():
    cfg, _ = tiny()
    assert model_flops_per_token(cfg) == 2 * (
        gpt.num_params(cfg) - cfg.vocab_size * cfg.d_model
        + (cfg.d_model * cfg.vocab_size if cfg.tie_embeddings else 0))
    # attention: token at position p attends p+1 keys, 4*d flops per
    # (q, k) pair per layer — check the closed form against the loop
    n, s = 5, 7
    ref = sum(4 * cfg.n_layers * cfg.d_model * (s + i + 1)
              for i in range(n))
    assert attn_flops(cfg, n, s) == ref
    assert infer_flops(cfg, n, s) == \
        n * model_flops_per_token(cfg) + ref
    # decomposition: a chunked prefill must charge the same flops as
    # one shot — conservation across chunk boundaries
    whole = infer_flops(cfg, 12, 0)
    split = infer_flops(cfg, 8, 0) + infer_flops(cfg, 4, 8)
    assert whole == split


def test_program_cost_registry_every_twin_populated_on_cpu(eng):
    """Acceptance: every registered twin that exists on the engine gets
    a registry entry on CPU — via XLA cost analysis or the analytic
    fallback — and the per-program gauges are exported."""
    tel = Telemetry()
    srv = ServingEngine(eng, num_slots=2, block_size=4, num_blocks=24,
                        telemetry=tel)
    present = {pid for pid, attr, _ in engine_programs()
               if getattr(eng, attr, None) is not None}
    assert present, "engine exposes no registered programs?"
    assert set(srv.cost_registry.entries) == present
    assert {"prefill_slot", "decode_slots"} <= present
    for pid, entry in srv.cost_registry.entries.items():
        assert entry["source"] in ("analytic", "xla")
        assert entry["flops"] >= 0
        assert entry["bytes_accessed"] > 0
        assert entry["dispatch_class"] in DISPATCH_CLASSES
        assert srv.metrics.gauge(f"program_flops_{pid}").value >= 0
        assert srv.metrics.gauge(f"program_hbm_bytes_{pid}").value > 0
    # the snapshot is JSON round-trippable
    js = json.loads(srv.cost_registry.dumps())
    assert set(js["programs"]) == present


# ---------------------------------------------------------------------------
# conservation: footprints == totals == counters, exactly
# ---------------------------------------------------------------------------

# tier-1 runs ``-m 'not slow'`` under a hard wall-clock budget
# (ROADMAP.md); the heavier conservation workloads carry the slow mark
# and run unfiltered in the gate (tools/gate.sh full + chaos legs)

@pytest.mark.slow
def test_conservation_exact_across_evict_requeue(eng):
    """The tight-pool eviction workload: a preempted request carries
    its footprint through evict -> requeue -> re-admit, and the books
    still balance to the integer."""
    p1, p2 = prompts_of((10, 9), seed=9)
    srv = ServingEngine(eng, num_slots=2, block_size=4, num_blocks=7,
                        prefill_chunk=8, telemetry=Telemetry())
    srv.cache.watermark = 0
    srv.run([ServeRequest(rid="a", prompt=p1, max_new_tokens=12),
             ServeRequest(rid="b", prompt=p2, max_new_tokens=10)])
    assert srv.stats["evictions"] >= 1
    _assert_conserved(srv)
    # the evicted request's footprint survived the round trip: its
    # prefill charges include the re-prefill after re-admission
    victim = next(r for r in srv.finished if r.evictions > 0)
    assert victim.cost["prefill"]["dispatches"] >= 2
    assert srv.costs.totals["prefill"]["flops"] > 0
    assert srv.costs.totals["decode"]["flops"] > 0


@pytest.mark.slow
def test_conservation_spec_decode_with_fallback(eng):
    """Speculative decoding charges the verify class for the full
    chunk; injected draft faults degrade steps to plain decode — the
    books balance across the mode switches."""
    prompts = prompts_of((5, 9, 12), seed=7)
    with faults_lib.injected(
            Fault("serving.spec_draft", "device_error", step=1, count=3),
            seed=0):
        srv = ServingEngine(eng, num_slots=2, block_size=4, num_blocks=24,
                            prefill_chunk=8, spec_decode=True,
                            telemetry=Telemetry())
        srv.run([ServeRequest(rid=i, prompt=p, max_new_tokens=8)
                 for i, p in enumerate(prompts)])
    assert srv.stats["spec_fallbacks"] >= 3
    assert srv.stats["spec_steps"] > 0
    _assert_conserved(srv)
    assert srv.costs.totals["verify"]["dispatches"] > 0
    assert srv.costs.totals["decode"]["dispatches"] > 0


@pytest.mark.slow
def test_conservation_decode_horizon_8(eng):
    """Acceptance: exact conservation holds with DS_DECODE_HORIZON=8 —
    one fused dispatch bills N tokens per slot, integrated at horizon
    boundaries."""
    prompts = prompts_of((6, 11, 4), seed=3)
    srv = ServingEngine(eng, num_slots=2, block_size=4, num_blocks=24,
                        prefill_chunk=8, spec_decode=False,
                        decode_horizon=8, telemetry=Telemetry())
    srv.run([ServeRequest(rid=i, prompt=p, max_new_tokens=16)
             for i, p in enumerate(prompts)])
    _assert_conserved(srv)
    gen = sum(len(r.out) for r in srv.finished)
    d = srv.costs.totals["decode"]
    # the horizon amortization is visible in the books: far fewer
    # decode dispatches than decoded tokens...
    assert 0 < d["dispatches"] < gen
    # ...while the flops cover every token (>= one per-token matmul
    # pass per generated token; prefill emits the first token of each)
    assert d["flops"] >= (gen - len(prompts)) * model_flops_per_token(
        eng.config if hasattr(eng, "config") else srv.engine.cfg)


def test_conservation_router_drain_onto_survivors(eng):
    """A replica crash-killed mid-run drains its in-flight requests
    (footprints ride the drain snapshots) onto survivors: summing the
    final per-request footprints plus every replica's system residue
    must equal the fleet-wide per-class totals — and the merged
    registries' counters."""
    prompts = prompts_of(tuple(5 + (i % 4) * 3 for i in range(6)),
                         seed=29)
    inj = FaultInjector([Fault("router.step", "crash", step=7)], seed=0)
    fleet = [ServingEngine(eng, num_slots=2, block_size=4, num_blocks=24,
                           prefill_chunk=8, spec_decode=False,
                           faults=inj, telemetry=Telemetry())
             for _ in range(3)]
    router = ReplicaRouter(fleet, faults=inj)
    out = router.run([ServeRequest(rid=i, prompt=p, max_new_tokens=8)
                      for i, p in enumerate(prompts)])
    assert inj.fired and router.stats["drained_requests"] >= 1
    assert set(out) == set(range(6))
    finished = [r for rep in router.replicas for r in rep.srv.finished]
    folded, bs = _fold(finished, *[rep.srv.costs
                                   for rep in router.replicas])
    for c in DISPATCH_CLASSES:
        fleet_tot = {k: sum(rep.srv.costs.totals[c][k]
                            for rep in router.replicas)
                     for k in folded[c]}
        assert folded[c] == fleet_tot, f"class {c} diverged across drain"
    assert bs == sum(rep.srv.costs.block_seconds_total
                     for rep in router.replicas)
    merged = merge_registries([rep.srv.metrics
                               for rep in router.replicas])
    assert merged.counter("serving_flops_total").value == \
        sum(folded[c]["flops"] for c in DISPATCH_CLASSES)
    assert merged.counter("serving_hbm_bytes_total").value == \
        sum(folded[c]["hbm_bytes"] for c in DISPATCH_CLASSES)


def test_tenant_rollup_keyed_by_adapter_id(eng):
    """Requests tagged with adapter ids roll their footprints into
    per-tenant buckets; untagged requests land in "base"; the tenant
    sums re-fold to the global totals."""
    prompts = prompts_of((6, 7, 8, 5), seed=11)
    srv = ServingEngine(eng, num_slots=2, block_size=4, num_blocks=24,
                        prefill_chunk=8, telemetry=Telemetry())
    reqs = [ServeRequest(rid=i, prompt=p, max_new_tokens=4)
            for i, p in enumerate(prompts)]
    # tag without the adapter pool: attribution keys on adapter_id
    # only, the serving path treats unknown ids as base weights when
    # lora_serve is off
    reqs[1].adapter_id = None
    srv.run(reqs)
    _assert_conserved(srv)
    tenants = srv.costs.tenants
    assert "base" in tenants
    for c in DISPATCH_CLASSES:
        for k in ("flops", "hbm_bytes", "dispatches"):
            assert sum(fp[c][k] for fp in tenants.values()) == \
                srv.costs.totals[c][k]


# ---------------------------------------------------------------------------
# off-mode: bit-identity, zero compiles, zero cost metrics
# ---------------------------------------------------------------------------

def test_off_mode_bit_identical_zero_compiles_no_metrics(eng):
    """Acceptance: telemetry/recorder off is the bit-reference — same
    tokens with CompileWatch(0) armed, the accountant is the no-op
    twin, and none of the cost metrics materialize."""
    from deepspeed_tpu.utils.compile_guard import CompileWatch
    prompts = prompts_of((5, 9, 12), seed=13)

    def drive(telemetry):
        srv = ServingEngine(eng, num_slots=2, block_size=4, num_blocks=24,
                            prefill_chunk=8, spec_decode=False,
                            telemetry=telemetry)
        out = srv.run([ServeRequest(rid=i, prompt=p.copy(),
                                    max_new_tokens=6)
                       for i, p in enumerate(prompts)])
        return srv, out

    srv_on, out_on = drive(Telemetry())          # warmup + reference
    watch = CompileWatch(max_compiles=0, label="serving+costs-off")
    watch.wrap(eng._prefill_slot)
    watch.wrap(eng._decode_slots)
    with watch:
        srv_off, out_off = drive(False)
    for rid in out_on:
        np.testing.assert_array_equal(out_off[rid], out_on[rid])
    assert srv_off.costs is NOOP_COSTS and not srv_off.costs.enabled
    assert srv_off.cost_registry is None
    assert not srv_off.flight.enabled and srv_off.flight.dump("x") is None
    for name in ("serving_flops_total", "serving_hbm_bytes_total",
                 "serving_kv_block_seconds"):
        assert name not in srv_off.metrics.names()
        assert name in srv_on.metrics.names()
    # footprints exist but stay empty off-mode (the dataclass default)
    assert all(r.cost["decode"]["dispatches"] == 0
               for r in srv_off.finished)


def test_cost_accounting_knob_without_telemetry(eng):
    """DS_COST_ACCOUNTING / the explicit ctor knob turns attribution on
    with telemetry OFF: charges land in the engine's private registry
    and the streams stay identical (host-int arithmetic only)."""
    p, = prompts_of((8,), seed=2)
    srv = ServingEngine(eng, num_slots=2, block_size=4, num_blocks=24,
                        telemetry=False, cost_accounting=True)
    srv.run([ServeRequest(rid="n", prompt=p, max_new_tokens=6)])
    assert srv.costs.enabled
    _assert_conserved(srv)
    assert srv.metrics.counter("serving_flops_total").value > 0


# ---------------------------------------------------------------------------
# flight recorder: chaos postmortem round-trip with zero live objects
# ---------------------------------------------------------------------------

def test_degraded_error_writes_postmortem_roundtrip(eng, tmp_path):
    """Acceptance: the chaos-induced watchdog DegradedError yields a
    versioned, CRC-stamped artifact from which tools/postmortem.py
    (stdlib-only — no jax, no live objects) reconstructs the request
    timeline, the fired faults, and the per-tenant cost summary."""
    outdir = str(tmp_path / "flight")
    p1, p2 = prompts_of((6, 9), seed=12)
    with faults_lib.injected(
            Fault("serving.decode", "slow", step=4, count=2, param=0.6),
            seed=0) as inj:
        # a budget no ordinary step of a loaded CPU reaches, under a
        # fault that still clears it (tests/test_chaos.py)
        srv = ServingEngine(eng, num_slots=2, block_size=4, num_blocks=24,
                            step_time_budget_s=0.25, watchdog_grace=2,
                            spec_decode=False, decode_horizon=1,
                            telemetry=Telemetry(),
                            flight_recorder=True, flight_dir=outdir)
        with pytest.raises(DegradedError, match="over budget"):
            srv.run([ServeRequest(rid="a", prompt=p1, max_new_tokens=12),
                     ServeRequest(rid="b", prompt=p2, max_new_tokens=3)])
    assert srv.flight.dumps, "degrade wrote no artifact"
    path = srv.flight.dumps[-1]
    assert os.path.exists(path)

    # the reader side: tools/postmortem.py mirrors (not imports) the
    # package's verification — both must accept the artifact
    body = load_artifact(path)
    from tools.postmortem import analyze_postmortem
    from tools.postmortem import load_artifact as load_stdlib
    assert load_stdlib(path) == body
    summary = analyze_postmortem(body)
    assert summary["incident"]["reason"].startswith("degraded:")
    assert "over budget" in summary["incident"]["reason"]
    # fired faults reconstructed exactly
    assert [tuple(f) for f in summary["faults"]] == inj.fired
    # request timeline: both rids present with their lifecycle edges
    assert {"a", "b"} <= set(summary["requests"])
    for rid in ("a", "b"):
        counts = summary["requests"][rid]["event_counts"]
        assert counts.get("enqueue") == 1 and counts.get("admit", 0) >= 1
    # "b" finished before the trip; its terminal event is in the ring
    assert summary["requests"]["b"]["event_counts"].get("finish") == 1
    # per-tenant cost summary matches the live accountant to the integer
    live = srv.costs.snapshot()
    assert summary["totals"]["per_class"] == live["totals"]
    assert summary["totals"]["flops_total"] == live["flops_total"]
    assert summary["tenants"]["base"]["footprint"] == \
        live["tenants"]["base"]
    # resolved flags and the program registry made it into the artifact
    assert summary["flags"].get("DS_FLIGHT_RECORDER") is not None
    assert summary["programs"]["count"] == len(srv.cost_registry.entries)
    # identity pins the process that died
    assert body["identity"]["backend"] in ("cpu", "tpu", "gpu")

    # trace_analyze's cost subcommand reads the same artifact
    import sys
    sys.path.insert(0, ".")
    from tools.trace_analyze import analyze_cost
    cs = analyze_cost(path, quiet=True)
    assert cs["source"] == "postmortem"
    assert cs["flops_total"] == live["flops_total"]

    # resuming after the degrade still balances the books
    srv.run()
    _assert_conserved(srv)


def test_postmortem_artifact_tamper_detected(eng, tmp_path):
    """A hand-edited or truncated artifact fails CRC verification
    loudly in BOTH readers."""
    outdir = str(tmp_path / "flight")
    p, = prompts_of((6,), seed=5)
    srv = ServingEngine(eng, num_slots=2, block_size=4, num_blocks=24,
                        telemetry=Telemetry(), flight_recorder=True,
                        flight_dir=outdir)
    srv.run([ServeRequest(rid="x", prompt=p, max_new_tokens=4)])
    path = srv.flight.dump("manual")
    body = load_artifact(path)               # valid as written
    assert body["reason"] == "manual"
    with open(path) as f:
        artifact = json.load(f)
    artifact["body"]["reason"] = "tampered"
    bad = str(tmp_path / "bad.json")
    with open(bad, "w") as f:
        json.dump(artifact, f)
    with pytest.raises(ValueError, match="CRC"):
        load_artifact(bad)
    from tools.postmortem import load_artifact as load_stdlib
    with pytest.raises(ValueError, match="CRC"):
        load_stdlib(bad)
    # version gate: an unknown schema version is refused before CRC
    artifact["version"] = 99
    with open(bad, "w") as f:
        json.dump(artifact, f)
    with pytest.raises(ValueError, match="version"):
        load_artifact(bad)


def test_router_break_writes_fleet_postmortem(eng, tmp_path):
    """A breaker break on the fleet writes a router-labeled artifact
    bundling per-replica cost snapshots and the drain timeline."""
    outdir = str(tmp_path / "fleet_flight")
    prompts = prompts_of((5, 8, 11, 6), seed=17)
    inj = FaultInjector([Fault("router.step", "crash", step=7)], seed=0)
    fleet = [ServingEngine(eng, num_slots=2, block_size=4, num_blocks=24,
                           prefill_chunk=8, spec_decode=False,
                           faults=inj, telemetry=Telemetry())
             for _ in range(3)]
    router = ReplicaRouter(fleet, faults=inj, flight_recorder=True,
                           flight_dir=outdir)
    router.run([ServeRequest(rid=i, prompt=p, max_new_tokens=8)
                for i, p in enumerate(prompts)])
    assert router.stats["breaker_trips"] >= 1
    assert router.flight.dumps
    body = load_artifact(router.flight.dumps[-1])
    assert body["label"] == "router"
    assert body["reason"].startswith("breaker:")
    assert set(body["costs"]) == {f"r{i}" for i in range(3)}
    # the drained requests' rows carry their replica of record
    assert any(row.get("replica") is not None
               for row in body["requests"])
