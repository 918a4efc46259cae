"""Serving telemetry tests (tentpole: deepspeed_tpu/telemetry/ wired
through inference/serving.py; docs/OBSERVABILITY.md).

Layers:
  1. registry unit tests — histogram bucket math vs a numpy reference,
     Prometheus exposition golden text, Monitor accepting histogram
     summaries;
  2. tracer unit tests — ring-buffer wrap accounting, Chrome-trace
     span building;
  3. serving integration — span ordering across evict/requeue, the
     read-only stats view, the deadline clock decoupled from the steps
     metric, no-op mode recording nothing (and costing nothing);
  4. chaos — a seeded fault run whose injected events land in the
     trace at their exact visit indices, and the acceptance gate:
     telemetry ON is token-bit-identical to OFF with ZERO steady-state
     recompiles, while the Perfetto + Prometheus exports reconstruct
     every request lifecycle.
"""

import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.engine import InferenceEngine
from deepspeed_tpu.inference.serving import ServeRequest, ServingEngine
from deepspeed_tpu.models import gpt
from deepspeed_tpu.inference.serving import _STAT_FIELDS
from deepspeed_tpu.telemetry import (NOOP_SPAN, Histogram, MetricsRegistry,
                                     NoopTelemetry, NoopTracer,
                                     RequestTracer, Telemetry,
                                     merge_registries, resolve_telemetry,
                                     span_self_times)
from deepspeed_tpu.utils import faults as faults_lib
from deepspeed_tpu.utils.faults import Fault
from deepspeed_tpu.utils.monitor import Monitor
from tools.trace_analyze import analyze_serving_trace


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


# ---------------------------------------------------------------------------
# registry unit tests (pure host — no devices needed)
# ---------------------------------------------------------------------------

def test_histogram_bucket_math_vs_numpy():
    """Cumulative bucket counts are exact against ``data <= le`` and the
    interpolated percentiles track numpy within one bucket width."""
    rng = np.random.default_rng(0)
    data = rng.uniform(0.0, 10.0, 2000)
    uppers = np.linspace(0.1, 10.0, 100)          # width 0.1
    h = Histogram("lat", buckets=uppers)
    for v in data:
        h.observe(v)
    cum = 0
    for i, ub in enumerate(h.uppers):
        cum += h.counts[i]
        assert cum == int((data <= ub).sum())
    assert h.count == 2000
    assert abs(h.sum - data.sum()) < 1e-6
    for q in (10, 50, 90, 95, 99):
        assert abs(h.percentile(q) - np.percentile(data, q)) <= 0.15, q
    # overflow bucket clamps to the max observed value
    h2 = Histogram("o", buckets=(1.0,))
    h2.observe(5.0)
    h2.observe(7.0)
    assert h2.counts[-1] == 2 and h2.percentile(99) == 7.0
    assert Histogram("e", buckets=(1.0,)).percentile(50) == 0.0


def test_histogram_window_summary_vs_numpy():
    """The windowed view (observability tentpole): ``window_summary``
    over the recent-observation ring is EXACT against numpy's linear
    percentile on the same sample — no bucket quantization — and the
    time filter keeps only observations inside ``[now - window, now]``."""
    rng = np.random.default_rng(7)
    data = rng.uniform(0.0, 20.0, 500)
    h = Histogram("lat")
    for i, v in enumerate(data):
        h.observe(v, at=float(i))
    # whole-ring summary (window=None) == numpy on the raw sample
    s = h.window_summary()
    for q, key in ((50, "p50"), (95, "p95"), (99, "p99")):
        assert s[key] == pytest.approx(np.percentile(data, q), abs=1e-12)
    assert s["mean"] == pytest.approx(data.mean())
    assert s["count"] == 500
    # time-filtered: only the last 100 clock units (at >= 399)
    tail = data[399:]
    sw = h.window_summary(window=100.0, now=499.0)
    assert sw["count"] == len(tail)
    for q, key in ((50, "p50"), (95, "p95"), (99, "p99")):
        assert sw[key] == pytest.approx(np.percentile(tail, q), abs=1e-12)
    # ``now`` defaults to the newest observation's clock
    assert h.window_summary(window=100.0) == sw
    # an empty window is all-zeros, not an error
    assert h.window_summary(window=1.0, now=1e9)["count"] == 0
    # cumulative view is untouched by the ring
    assert h.count == 500 and abs(h.sum - data.sum()) < 1e-6


def test_histogram_window_ring_bounded():
    """The ring is memory-bounded: only the most recent
    ``window_capacity`` observations survive; without explicit ``at``
    the observation sequence number is the clock."""
    h = Histogram("b", window_capacity=16)
    for i in range(100):
        h.observe(float(i))
    vals = h.window_values()
    assert vals == [float(i) for i in range(84, 100)]
    assert h.count == 100                       # cumulative still exact
    # sequence clock: a window of 4 keeps the last 5 observations
    # (at >= now - window, inclusive)
    assert h.window_values(window=4) == [95.0, 96.0, 97.0, 98.0, 99.0]


def test_merge_registries_fleet_fold():
    """``merge_registries`` is the fleet aggregation: counters and
    gauges sum, histograms with identical ladders merge bucket-wise and
    interleave their rings by clock; mismatched ladders refuse."""
    a, b = MetricsRegistry(), MetricsRegistry()
    a.counter("serving_completed", "done").inc(3)
    b.counter("serving_completed").inc(4)
    b.counter("only_b").inc(1)
    a.gauge("queue_depth").set(2)
    b.gauge("queue_depth").set(5)
    ha = a.histogram("serving_ttft", buckets=(1.0, 4.0))
    hb = b.histogram("serving_ttft", buckets=(1.0, 4.0))
    ha.observe(0.5, at=1.0)
    ha.observe(6.0, at=3.0)
    hb.observe(2.0, at=2.0)
    m = merge_registries([a, b])
    assert m.counter("serving_completed").value == 7
    assert m.counter("only_b").value == 1
    assert m.gauge("queue_depth").value == 7
    hm = m.histogram("serving_ttft")
    assert hm.count == 3 and hm.sum == pytest.approx(8.5)
    assert list(hm.counts) == [1, 1, 1]          # (<=1, <=4, +Inf)
    assert hm.window_values() == [0.5, 2.0, 6.0]   # clock-ordered
    # exposition of the merged registry is ordinary cumulative text
    assert 'serving_ttft_bucket{le="+Inf"} 3' in m.to_prometheus()
    # ladder mismatch is a hard error, not silent garbage
    c = MetricsRegistry()
    c.histogram("serving_ttft", buckets=(2.0,)).observe(1.0)
    with pytest.raises(ValueError):
        merge_registries([a, c])


def test_prometheus_exposition_golden():
    reg = MetricsRegistry()
    c = reg.counter("requests_total", "requests seen")
    c.inc()
    c.inc(2)
    reg.gauge("queue_depth").set(3)
    h = reg.histogram("latency_s", "request latency", buckets=(0.25, 1.0))
    for v in (0.125, 0.5, 4.0):
        h.observe(v)
    assert reg.to_prometheus() == (
        "# HELP requests_total requests seen\n"
        "# TYPE requests_total counter\n"
        "requests_total 3\n"
        "# TYPE queue_depth gauge\n"
        "queue_depth 3\n"
        "# HELP latency_s request latency\n"
        "# TYPE latency_s histogram\n"
        'latency_s_bucket{le="0.25"} 1\n'
        'latency_s_bucket{le="1"} 2\n'
        'latency_s_bucket{le="+Inf"} 3\n'
        "latency_s_sum 4.625\n"
        "latency_s_count 3\n")
    # get-or-create returns the same instance; snapshot is plain data
    assert reg.counter("requests_total") is c
    snap = reg.snapshot()
    assert snap["counters"]["requests_total"] == 3
    assert snap["histograms"]["latency_s"]["count"] == 3.0


def test_monitor_accepts_histogram_summaries(tmp_path, monkeypatch):
    """Registry scalars — including histogram summary mappings — flow
    through Monitor.write_scalars as tag/p50-style sub-scalars."""
    from deepspeed_tpu.utils import monitor as monitor_mod
    # skip the tensorboard backend probe (a multi-second torch import);
    # this test targets the csv/jsonl mirror
    monkeypatch.setattr(monitor_mod, "_try_tensorboard_writer",
                        lambda log_dir: None)
    reg = MetricsRegistry()
    reg.counter("serving_completed").inc(4)
    h = reg.histogram("serving_ttft", buckets=(1.0, 2.0, 4.0))
    for v in (0.5, 1.5, 3.0):
        h.observe(v)
    mon = Monitor(output_path=str(tmp_path), job_name="tele")
    mon.write_scalars(reg.to_scalars(step=7))
    mon.close()
    rows = [json.loads(l) for l in
            open(tmp_path / "tele" / "scalars.jsonl")]
    tags = {r["tag"]: r["value"] for r in rows}
    assert tags["serving_completed"] == 4.0
    assert {"serving_ttft/p50", "serving_ttft/p95", "serving_ttft/p99",
            "serving_ttft/mean", "serving_ttft/count"} <= set(tags)
    assert tags["serving_ttft/count"] == 3.0
    assert all(r["step"] == 7 for r in rows)


# ---------------------------------------------------------------------------
# tracer unit tests
# ---------------------------------------------------------------------------

def test_tracer_ring_buffer_wrap():
    tr = RequestTracer(capacity=8)
    for i in range(20):
        tr.event("tick", rid="r", step=i)
    recs = tr.records()
    assert len(recs) == 8 and tr.dropped == 12
    assert [r[3] for r in recs] == list(range(12, 20))   # oldest first
    assert tr.to_chrome_trace()["dropped_events"] == 12
    tr.reset()
    assert tr.records() == [] and tr.dropped == 0


def test_tracer_builds_ordered_spans():
    """A synthetic evict/requeue lifecycle renders as repeated
    queued/prefill/decode spans in timestamp order."""
    clock = iter(float(i) for i in range(100))
    tr = RequestTracer(capacity=64, clock=lambda: next(clock))
    tr.event("enqueue", rid="a", step=0)
    tr.event("admit", rid="a", step=1, slot=0, matched=4)
    tr.event("prefill_done", rid="a", step=2, slot=0)
    tr.event("evict", rid="a", step=3, slot=0)
    tr.event("admit", rid="a", step=4, slot=1, matched=0)
    tr.event("prefill_done", rid="a", step=5, slot=1)
    tr.event("finish", rid="a", step=6, slot=1, state="done", generated=3)
    spans = [(e["ts"], e["name"], e["args"]) for e in
             tr.to_chrome_trace()["traceEvents"]
             if e.get("ph") == "X" and e.get("cat") == "request"]
    spans.sort()
    assert [s[1] for s in spans] == ["queued", "prefill", "decode",
                                    "queued", "prefill", "decode"]
    assert spans[1][2]["prefix_hit"] is True
    assert spans[4][2]["prefix_hit"] is False
    assert spans[2][2]["evicted"] is True
    assert spans[5][2]["state"] == "done"


def _ticking(capacity=64):
    clock = iter(float(i) for i in range(10_000))
    return RequestTracer(capacity=capacity, clock=lambda: next(clock))


def test_spans_nest_with_parent_ids_rid_and_self_time():
    tr = _ticking()
    with tr.span("serve.step", step=3, queue=2) as step:
        with tr.span("serve.prefill", rid="a", step=3, slot=1, n=8) as pf:
            tr.event("prefill_chunk", rid="a", step=3, slot=1)
            with tr.span("serve.dispatch", site="serving.prefill") as d:
                pass
            pf.set(tokens=8)            # a count known only at the end
        with tr.span("serve.decode", step=3):
            pass
    recs = tr.records()
    # start order, the point event between the spans that surround it
    assert [r[1] for r in recs] == ["serve.step", "serve.prefill",
                                    "prefill_chunk", "serve.dispatch",
                                    "serve.decode"]
    by = {r[1]: r for r in tr.spans()}
    assert by["serve.step"][8] == 0                      # no parent
    assert by["serve.prefill"][8] == by["serve.step"][7] == step.sid
    assert by["serve.dispatch"][8] == pf.sid
    assert by["serve.decode"][8] == step.sid
    assert by["serve.prefill"][2] == "a" and by["serve.prefill"][4] == 1
    assert by["serve.prefill"][5] == {"n": 8, "tokens": 8}
    assert tr.events_of("a")[0][1] == "serve.prefill"
    # children lie inside their parents; self = duration - children
    for r in tr.spans():
        parent = next((q for q in tr.spans() if q[7] == r[8]), None)
        if parent is not None:
            assert parent[0] <= r[0] and r[6] <= parent[6]
    selfs = span_self_times(recs)
    assert selfs[step.sid] == step.dur - pf.dur - (
        by["serve.decode"][6] - by["serve.decode"][0])
    assert selfs[pf.sid] == pf.dur - d.dur and selfs[d.sid] == d.dur
    assert all(v >= 0 for v in selfs.values())


@pytest.mark.parametrize("capacity,n_steps", [(4, 3), (8, 5), (64, 5)])
def test_span_ring_wrap(capacity, n_steps):
    """Spans and point events share the ring: a wrap drops the oldest of
    either, a span whose slot was overwritten while it was open is
    dropped whole, and an orphaned child takes no time from anyone."""
    tr = _ticking(capacity)
    for i in range(n_steps):
        with tr.span("serve.step", step=i):
            tr.event("tick", step=i)
            with tr.span("serve.decode", step=i):
                tr.event("tock", step=i)
    total = 4 * n_steps
    recs = tr.records()
    assert tr.dropped == max(0, total - capacity)
    assert len(recs) <= capacity
    assert [r[0] for r in recs] == sorted(r[0] for r in recs)
    kept = {r[7] for r in tr.spans()}
    selfs = span_self_times(recs)
    assert set(selfs) == kept and all(v >= 0 for v in selfs.values())
    if total <= capacity:
        assert len(tr.spans("serve.step")) == n_steps
    # the export survives a wrapped ring and reports what fell out
    assert tr.to_chrome_trace()["dropped_events"] == tr.dropped


def test_chrome_export_renders_spans_with_self_time():
    tr = _ticking()
    with tr.span("serve.step", step=0):
        with tr.span("serve.prefill", rid="r", step=0, slot=0, n=4):
            pass
    ev = [e for e in tr.to_chrome_trace()["traceEvents"]
          if e.get("cat") == "span"]
    assert [e["name"] for e in ev] == ["serve.step", "serve.prefill"]
    step, pf = ev
    assert step["ph"] == "X" and step["tid"] == 0
    assert pf["args"]["parent_id"] == step["args"]["span_id"]
    assert pf["args"]["rid"] == "r" and pf["args"]["n"] == 4
    assert step["args"]["self_us"] == step["dur"] - pf["dur"]
    assert step["ts"] <= pf["ts"] and \
        pf["ts"] + pf["dur"] <= step["ts"] + step["dur"]


def test_noop_span_is_one_shared_object_and_records_nothing():
    tr = NoopTracer()
    a = tr.span("serve.step", step=1, queue=3)
    b = tr.span("serve.dispatch", rid="x")
    assert a is b is NOOP_SPAN
    with a as entered:
        entered.set(tokens=3)
    assert entered is NOOP_SPAN and entered.dur == 0.0
    assert tr.records() == [] and tr.spans() == []


def test_resolve_telemetry_env_and_flag(monkeypatch):
    monkeypatch.delenv("DS_TELEMETRY", raising=False)
    assert resolve_telemetry(None) is False      # default off
    monkeypatch.setenv("DS_TELEMETRY", "on")
    assert resolve_telemetry(None) is True
    monkeypatch.setenv("DS_TELEMETRY", "off")
    assert resolve_telemetry(None) is False
    assert resolve_telemetry(True) is True       # explicit flag wins
    monkeypatch.setenv("DS_TELEMETRY", "on")
    assert resolve_telemetry(False) is False


# ---------------------------------------------------------------------------
# serving integration
# ---------------------------------------------------------------------------

def test_serving_span_ordering_across_evict_requeue(eng):
    """The tight-pool eviction workload: the preempted request's
    timeline shows enqueue -> admit -> evict -> re-admit -> finish in
    order, and the Chrome-trace export renders it as repeated
    queued/prefill(/decode) spans ending in state=done."""
    p1, p2 = prompts_of((10, 9), seed=9)
    tel = Telemetry(sample_every=4)
    srv = ServingEngine(eng, num_slots=2, block_size=4, num_blocks=7,
                        prefill_chunk=8, telemetry=tel)
    srv.cache.watermark = 0
    srv.run([ServeRequest(rid="a", prompt=p1, max_new_tokens=12),
             ServeRequest(rid="b", prompt=p2, max_new_tokens=10)])
    assert srv.stats["evictions"] >= 1
    victim = next(r.rid for r in srv.finished if r.evictions > 0)
    seq = [r[1] for r in tel.tracer.events_of(victim)]
    assert seq[0] == "enqueue" and seq[-1] == "finish"
    assert seq.count("admit") == 1 + seq.count("evict")   # re-admitted
    assert 0 < seq.index("admit") < seq.index("evict") \
        < len(seq) - 1 - seq[::-1].index("admit")
    trace = tel.tracer.to_chrome_trace()
    spans = sorted((e["ts"], e["name"], e["args"]) for e in
                   trace["traceEvents"]
                   if e.get("ph") == "X" and e.get("cat") == "request"
                   and e["args"]["rid"] == victim)
    names = [s[1] for s in spans]
    assert names[0] == "queued" and names.count("queued") >= 2
    assert spans[-1][2].get("state") == "done"
    # every request's terminal span carries a terminal state
    for r in srv.finished:
        rid_spans = sorted((e["ts"], e["args"].get("state")) for e in
                           trace["traceEvents"]
                           if e.get("ph") == "X"
                           and e.get("cat") == "request"
                           and e["args"]["rid"] == str(r.rid))
        assert rid_spans[-1][1] == r.state


STEP_CHILDREN = {"serve.expire", "serve.admit", "serve.prefill",
                 "serve.decode", "serve.spill", "serve.bookkeep"}


def test_step_spans_tile_the_step_and_tokens_match_off(eng):
    """Every step's child spans lie inside it in order, self times are
    never negative, a dispatch splits into enqueue and wait, request
    spans carry their rid, the five step histograms see every step, and
    the tokens are those of the telemetry-off run."""
    prompts = prompts_of((11, 5, 9), seed=4)

    def drive(telemetry):
        srv = ServingEngine(eng, num_slots=2, block_size=4, num_blocks=24,
                            prefill_chunk=8, telemetry=telemetry)
        return srv, srv.run([ServeRequest(rid=f"r{i}", prompt=p.copy(),
                                          max_new_tokens=5)
                             for i, p in enumerate(prompts)])

    _, out_off = drive(False)
    tel = Telemetry(sample_every=1)     # the phases ride the sampled steps
    srv, out_on = drive(tel)
    for rid in out_off:
        np.testing.assert_array_equal(out_on[rid], out_off[rid])
    spans = tel.tracer.spans()
    by_id = {r[7]: r for r in spans}
    steps = [r for r in spans if r[1] == "serve.step"]
    assert len(steps) == srv.stats["steps"]
    selfs = span_self_times(spans)
    assert all(v >= 0 for v in selfs.values())
    for r in spans:
        if r[8]:
            parent = by_id[r[8]]
            assert parent[0] <= r[0] <= r[6] <= parent[6]
    for st in steps:
        kids = [r for r in spans if r[8] == st[7]]
        assert {k[1] for k in kids} <= STEP_CHILDREN
        assert [k[1] for k in kids if k[1] != "serve.prefill"] == [
            "serve.expire", "serve.admit", "serve.decode", "serve.spill",
            "serve.bookkeep"]
        # consecutive children do not overlap
        assert all(a[6] <= b[0] for a, b in zip(kids, kids[1:]))
    prefills = [r for r in spans if r[1] == "serve.prefill"]
    assert len(prefills) == srv.stats["prefill_chunks"]
    assert {r[2] for r in prefills} == {"r0", "r1", "r2"}
    assert sum(r[5]["n"] for r in prefills) == sum(map(len, prompts))
    dispatches = [r for r in spans if r[1] == "serve.dispatch"]
    assert len(dispatches) == (srv.stats["prefill_chunks"]
                               + srv.stats["decode_steps"])
    for d in dispatches:
        assert by_id[d[8]][1] in ("serve.prefill", "serve.decode")
        assert [r[1] for r in spans if r[8] == d[7]] == [
            "serve.dispatch.enqueue", "serve.dispatch.wait"]
        assert d[5]["site"] in ("serving.prefill", "serving.decode")
    emitted = sum(r[5]["tokens"] for r in spans if r[1] == "serve.emit")
    assert emitted == sum(len(r.out) for r in srv.finished) == 15
    assert all(r[5]["bytes"] > 0 for r in spans if r[1] == "serve.pull")
    admitted = sum(r[5]["admitted"] for r in spans
                   if r[1] == "serve.admit")
    assert admitted == srv.stats["admitted"] == 3
    for name in ("serving_step_s", "serving_step_admission_s",
                 "serving_step_prefill_s", "serving_step_decode_s",
                 "serving_step_bookkeeping_s"):
        assert tel.registry.histogram(name).count == srv.stats["steps"]
    assert not any(r[1] == "step_phase" for r in tel.tracer.records())


@pytest.mark.parametrize("window", [None, 160])
def test_prefill_span_counts_the_positions_its_chunk_reads(devices, window):
    """``attended`` on ``serve.prefill`` is what the program's own function
    of ``(start, n)`` says (engine.attended_tiles: whole tiles up to the
    chunk's last token, from the window's first), the two counters add it
    up beside the whole row, and after prompts of two and more chunks
    their ratio is below 1."""
    from deepspeed_tpu.inference.engine import attended_tiles
    cfg = gpt.GPTConfig(vocab_size=128, n_layers=2, n_heads=4, d_model=32,
                        max_seq_len=512, use_flash_attention=False,
                        remat=False, dtype=jnp.float32, attn_window=window)
    params = gpt.init_params(jax.random.PRNGKey(0), cfg)
    eng = InferenceEngine(config=cfg, params=params, dtype=jnp.float32)
    tel = Telemetry()
    bs, chunk = 4, 96
    srv = ServingEngine(eng, num_slots=2, block_size=bs, num_blocks=160,
                        prefill_chunk=chunk, telemetry=tel)
    nb = srv.cache.blocks_per_slot
    prompts = prompts_of((2 * chunk - 5, 3 * chunk + 1), seed=9)
    srv.run([ServeRequest(rid=f"r{i}", prompt=p, max_new_tokens=2)
             for i, p in enumerate(prompts)])
    prefills = [r[5] for r in tel.tracer.spans() if r[1] == "serve.prefill"]
    assert sorted((f["start"], f["n"]) for f in prefills) == [
        (0, 96), (0, 96), (96, 91), (96, 96), (192, 96), (288, 1)]
    for f in prefills:
        lo, hi, P = attended_tiles(f["start"], f["n"], bs, nb, window)
        assert P * bs == 128
        assert f["attended"] == (hi - lo) * P * bs
        assert f["start"] + f["n"] <= lo * P * bs + f["attended"] \
            < f["start"] + f["n"] + P * bs
    attended = srv.stats["prefill_attended_tokens_total"]
    row = srv.stats["prefill_row_tokens_total"]
    assert attended == sum(f["attended"] for f in prefills)
    assert row == len(prefills) * nb * bs == 6 * 512
    # 128 + 128 + 256 + 256 + 384 + 384 of 6 x 512; under the window the
    # chunk at 288 sees nothing below 129 and drops the first tile (the
    # one at 192 still sees position 33)
    assert attended == (1536 if window is None else 1536 - 128)
    assert attended / row <= 0.5


def test_stats_view_read_only_and_registry_backed(eng):
    p, = prompts_of((6,), seed=3)
    srv = ServingEngine(eng, num_slots=2, block_size=4, num_blocks=24)
    srv.run([ServeRequest(rid="x", prompt=p, max_new_tokens=4)])
    # same keys and values as the old dict contract
    assert srv.stats["completed"] == 1 and srv.stats["admitted"] == 1
    assert set(dict(srv.stats)) == {k for k, _, _ in _STAT_FIELDS}
    assert set(dict(srv.stats)) >= {
        "steps", "occupancy_sum", "peak_occupancy", "evictions",
        "admitted", "completed", "prefill_chunks", "decode_steps",
        "timeouts", "shed", "retries", "evict_capped", "watchdog_trips",
        "backpressure", "prefix_hits", "prefix_tokens_saved",
        "spec_steps", "spec_slot_steps", "spec_proposed",
        "spec_accepted", "spec_emitted", "spec_fallbacks",
        "sampled_tokens", "stop_hits", "spec_k_capped",
        "horizon_fallbacks", "adapter_hits", "adapter_loads",
        "adapter_evictions", "adapter_load_errors", "host_blocks",
        "host_bytes", "host_spills", "host_restores",
        "host_restore_failures"}
    with pytest.raises(TypeError):
        srv.stats["steps"] = 99          # read-only view
    # the registry is the writable surface
    assert srv.metrics.counter("serving_completed").value == 1
    assert srv.stats["completed"] == srv.metrics.snapshot()[
        "counters"]["serving_completed"]


def test_deadline_clock_decoupled_from_steps_metric(eng):
    """The satellite fix: ``stats["steps"]`` used to BE the deadline
    clock, so bumping the metric skewed every relative deadline. Now the
    clock is private — a skewed counter changes reporting only."""
    p1, p2 = prompts_of((6, 7), seed=5)
    srv = ServingEngine(eng, num_slots=2, block_size=4, num_blocks=24)
    srv.metrics.counter("serving_steps").inc(1000)   # hostile skew
    out = srv.run([ServeRequest(rid="d", prompt=p1, max_new_tokens=6,
                                deadline=50.0),
                   ServeRequest(rid="ok", prompt=p2, max_new_tokens=6)])
    done = {r.rid: r for r in srv.finished}
    # under the old clock now=1000 >= 50 would time "d" out instantly
    assert done["d"].state == "done" and len(done["d"].out) == 6
    assert done["ok"].state == "done"
    assert sorted(out) == ["d", "ok"]


def test_noop_mode_records_nothing_and_costs_nothing(eng):
    p, = prompts_of((8,), seed=2)
    srv = ServingEngine(eng, num_slots=2, block_size=4, num_blocks=24,
                        telemetry=False)
    srv.run([ServeRequest(rid="n", prompt=p, max_new_tokens=6)])
    assert isinstance(srv.telemetry, NoopTelemetry)
    assert not srv.telemetry.enabled
    assert srv.telemetry.tracer.records() == []
    # no latency histograms materialize off-mode (stats counters only)
    assert "serving_ttft" not in srv.metrics.names()
    # stats stay fully live
    assert srv.stats["completed"] == 1 and srv.stats["steps"] > 0
    # overhead guard: the no-op record path is constant-time — 50k
    # calls in well under half a second even on a loaded CI host
    t0 = time.perf_counter()
    ev = srv.telemetry.tracer.event
    for i in range(50_000):
        ev("enqueue", rid=i, step=i)
    assert time.perf_counter() - t0 < 0.5
    assert srv.telemetry.tracer.records() == []


# ---------------------------------------------------------------------------
# chaos: faults land in the trace; the acceptance gate
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_chaos_fault_events_land_in_trace_at_injected_steps(eng):
    """Every fault the seeded injector fires appears in the trace with
    its exact (site, kind, visit) identity, in firing order — the chaos
    run replays as one timeline."""
    prompts = prompts_of((5, 9, 12, 3))
    chaos = [Fault("serving.prefill", "device_error", step=1),
             Fault("serving.decode", "device_error", step=2),
             Fault("engine.decode", "device_error", step=4),
             Fault("serving.decode", "slow", step=6, param=0.005),
             Fault("cache.ensure", "cache_exhausted", step=5)]
    with faults_lib.injected(*chaos, seed=0) as inj:
        tel = Telemetry(sample_every=4)
        srv = ServingEngine(eng, num_slots=2, block_size=4, num_blocks=24,
                            prefill_chunk=8, max_retries=3,
                            retry_backoff_s=0.001, telemetry=tel)
        srv.run([ServeRequest(rid=i, prompt=p, max_new_tokens=6)
                 for i, p in enumerate(prompts)])
    assert inj.fired                              # the chaos happened
    traced = [(r[5]["site"], r[5]["kind"], r[5]["visit"])
              for r in tel.tracer.records() if r[1] == "fault"]
    assert traced == inj.fired
    # each traced fault fired at its spec's visit window
    by_spec = {(f.site, f.kind): f for f in chaos}
    for site, kind, visit in traced:
        f = by_spec[(site, kind)]
        assert f.step <= visit < f.step + f.count
    # fault records carry the scheduler step and it never runs backwards
    steps = [r[3] for r in tel.tracer.records() if r[1] == "fault"]
    assert all(s >= 0 for s in steps) and steps == sorted(steps)


@pytest.mark.slow
def test_chaos_acceptance_trace_prometheus_parity_zero_recompiles(
        eng, tmp_path):
    """The ISSUE acceptance gate: under the seeded chaos scenario with
    telemetry ON, the Perfetto + Prometheus exports reconstruct every
    request lifecycle and populate the TTFT/TPOT histograms, injected
    faults sit at their exact visits — while CompileWatch sees ZERO
    steady-state recompiles and tokens stay bit-identical to the
    telemetry-OFF run."""
    from deepspeed_tpu.utils.compile_guard import CompileWatch
    prompts = prompts_of((5, 9, 12, 3))
    chaos = [Fault("serving.decode", "device_error", step=2),
             Fault("serving.decode", "slow", step=6, param=0.002),
             Fault("cache.ensure", "cache_exhausted", step=5)]

    def drive(telemetry):
        with faults_lib.injected(*chaos, seed=0) as inj:
            srv = ServingEngine(eng, num_slots=2, block_size=4,
                                num_blocks=24, prefill_chunk=8,
                                max_retries=3, retry_backoff_s=0.001,
                                telemetry=telemetry)
            out = srv.run([ServeRequest(rid=i, prompt=p.copy(),
                                        max_new_tokens=6)
                           for i, p in enumerate(prompts)])
        return srv, out, list(inj.fired)

    _, out_off, fired_off = drive(False)          # warmup + baseline
    tel = Telemetry(sample_every=2)
    watch = CompileWatch(max_compiles=0, label="serving+telemetry")
    watch.wrap(eng._prefill_slot)
    watch.wrap(eng._decode_slots)
    with watch:                                   # raises on any compile
        srv, out_on, fired_on = drive(tel)
    # bit-identical tokens, identical fault timeline
    assert sorted(out_on) == sorted(out_off)
    for rid in out_off:
        np.testing.assert_array_equal(out_on[rid], out_off[rid])
    assert fired_on == fired_off
    # Prometheus snapshot: populated latency histograms + live counters
    prom = tel.to_prometheus()
    assert f"serving_completed {srv.stats['completed']}" in prom
    assert tel.registry.histogram("serving_ttft").count == 4
    assert tel.registry.histogram("serving_tpot").count > 0
    assert "serving_ttft_bucket" in prom and "serving_tpot_sum" in prom
    # Perfetto export: trace_analyze reconstructs every lifecycle
    path = tel.export_trace(str(tmp_path / "chaos_trace.json"))
    summary = analyze_serving_trace(path, quiet=True)
    assert set(summary["requests"]) == {"0", "1", "2", "3"}
    for rid, r in summary["requests"].items():
        assert r["spans"][0] == "queued"
        assert "prefill" in r["spans"] and "decode" in r["spans"]
        assert r["state"] == "done"
    assert [(f["site"], f["kind"], f["visit"]) for f in summary["faults"]] \
        == fired_on
    # the spans made it into the export too, each with its self time
    assert {"serve.step", "serve.admit", "serve.prefill", "serve.decode",
            "serve.dispatch.enqueue", "serve.dispatch.wait",
            "serve.bookkeep"} <= set(summary["span_self_us"])
