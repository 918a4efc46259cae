"""The program's account of the host time between two dispatches
(``ServingEngine._account_gap``; docs/OBSERVABILITY.md "The gap between two
dispatches"): every number is exact, because the tracer runs on an injected
clock that ticks one whole second a read.

Layers:
  1. the identity ``gap = enqueue + sched + caller`` on the ring's own
     stamps, for decode after decode, decode after prefill and two prefill
     chunks of one step, plain and under the fused horizon;
  2. ``after_empty`` (the first dispatch ever, the first after a drained
     engine) and the counters such a gap stays out of;
  3. a retried dispatch: the failed attempt and its backoff lie in the
     gap of the attempt that went through, under ``sched``, once;
  4. telemetry off: no record, no registry entry, no clock read in
     ``_device_call``; tokens bit-identical on and off;
  5. every profiler annotation carries its ring record's span id.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference import serving as serving_mod
from deepspeed_tpu.inference.engine import InferenceEngine
from deepspeed_tpu.inference.serving import ServeRequest, ServingEngine
from deepspeed_tpu.models import gpt
from deepspeed_tpu.telemetry import Telemetry
from deepspeed_tpu.telemetry import tracer as tracer_mod
from deepspeed_tpu.utils import faults as faults_lib
from deepspeed_tpu.utils.faults import Fault

GAP_METRICS = ("serving_dispatch_gap_s", "serving_gap_sched_seconds_total",
               "serving_gap_caller_seconds_total",
               "serving_gap_enqueue_seconds_total",
               "serving_dispatch_wait_seconds_total",
               "serving_engine_empty_seconds_total")
CALLER_S = 7.0      # what the test's loop "costs" between two steps
# clock reads (a second each) between one step's t1 and the next one's t0:
# that t0, and the plane's own stamps (self_us): the step span's exit after
# t1 and entry before t0, and the two around the step's histogram observes
BETWEEN_STEPS = 5.0
# ring record fields (telemetry/tracer.py)
TS, NAME, DATA, END, SID, PARENT = 0, 1, 5, 6, 7, 8


class TickClock:
    """One whole second a read: differences are exact in float."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        self.t += 1.0
        return self.t


@pytest.fixture(scope="module")
def eng(devices):
    cfg = gpt.GPTConfig(vocab_size=128, n_layers=2, n_heads=4, d_model=32,
                        max_seq_len=64, use_flash_attention=False,
                        remat=False, dtype=jnp.float32)
    return InferenceEngine(config=cfg,
                           params=gpt.init_params(jax.random.PRNGKey(0), cfg),
                           dtype=jnp.float32)


def requests(lengths=(20, 5), new=4, seed=1, tag="r"):
    r = np.random.default_rng(seed)
    return [ServeRequest(rid=f"{tag}{i}", max_new_tokens=new,
                         prompt=r.integers(1, 128, n).astype(np.int32))
            for i, n in enumerate(lengths)]


def serve(eng, reqs, telemetry=True, **kw):
    """A two-slot engine on a tick clock, driven to drain by a loop that
    costs ``CALLER_S`` between two steps. Returns (srv, tel, clock)."""
    clock = TickClock()
    tel = Telemetry(clock=clock) if telemetry else False
    srv = ServingEngine(eng, num_slots=2, block_size=4, num_blocks=24,
                        prefill_chunk=8, spec_decode=False, telemetry=tel,
                        **kw)
    drive(srv, reqs, clock)
    return srv, tel, clock


def drive(srv, reqs, clock):
    for r in reqs:
        srv.submit(r, now=0.0)
    while srv.busy:
        srv.step()
        clock.t += CALLER_S


def dispatch_rows(tel):
    """One row per serve.dispatch record, in order, with the stamps the
    account should have used, recomputed from the ring alone."""
    spans = tel.tracer.spans()
    by_id = {r[SID]: r for r in spans}
    steps = [r for r in spans if r[NAME] == "serve.step"]
    rows, prev_wait_t1 = [], None
    for d in (r for r in spans if r[NAME] == "serve.dispatch"):
        kids = {r[NAME]: r for r in spans if r[PARENT] == d[SID]}
        enq, wait = kids["serve.dispatch.enqueue"], \
            kids.get("serve.dispatch.wait")
        row = {"rec": d, "counts": d[DATA], "enqueue": enq, "wait": wait,
               "step": by_id[by_id[d[PARENT]][PARENT]]}
        if wait is not None and prev_wait_t1 is not None:
            a, b = prev_wait_t1, enq[END]
            row["gap"] = b - a
            # seconds of [a, b] outside every serve.step
            inside = sum(max(0.0, min(b, s[END]) - max(a, s[TS]))
                         for s in steps)
            row["caller"] = (b - a) - inside
        if wait is not None:
            prev_wait_t1 = wait[END]
        rows.append(row)
    return rows


def counted(rows):
    return [r for r in rows if "gap" in r and not r["counts"]["after_empty"]]


PAIRS = {
    # name: (previous site, this site, both in one step)
    "decode_after_decode": ("serving.decode", "serving.decode", False),
    "decode_after_prefill": ("serving.prefill", "serving.decode", True),
    "two_prefill_chunks_one_step":
        ("serving.prefill", "serving.prefill", True),
}


@pytest.mark.parametrize("horizon", [1, 2])
@pytest.mark.parametrize("pair", sorted(PAIRS))
def test_gap_is_enqueue_plus_sched_plus_caller(eng, pair, horizon):
    srv, tel, _ = serve(eng, requests(), decode_horizon=horizon)
    rows = dispatch_rows(tel)
    assert len(rows) == (srv.stats["prefill_chunks"]
                         + srv.stats["decode_steps"])
    prev_site, site, one_step = PAIRS[pair]
    seen = 0
    for before, row in zip(rows, rows[1:]):
        c = row["counts"]
        # the span's own numbers are the ring's stamps, to the microsecond
        assert c["gap_us"] == row["gap"] * 1e6
        assert c["caller_us"] == row["caller"] * 1e6
        assert c["prev"] == before["counts"]["site"]
        enqueue = row["enqueue"][END] - row["enqueue"][TS]
        sched = row["gap"] - enqueue - row["caller"]
        assert sched > 0 and enqueue > 0
        same = before["step"][SID] == row["step"][SID]
        # the caller's share is what the loop cost, and only across steps
        assert row["caller"] == (0.0 if same else CALLER_S + BETWEEN_STEPS)
        if (c["prev"], c["site"], same) == (prev_site, site, one_step):
            seen += 1
    assert seen, f"no {pair} in this drive"
    # the registry: the parts sum to the gaps, over the gaps counted
    reg = tel.registry
    n = counted(rows)
    h = reg.histogram("serving_dispatch_gap_s")
    assert h.count == len(n) == len(rows) - 1
    assert h.sum == sum(r["gap"] for r in n)
    parts = {k: reg.counter(f"serving_gap_{k}_seconds_total").value
             for k in ("sched", "caller", "enqueue")}
    assert sum(parts.values()) == h.sum
    assert parts["caller"] == sum(r["caller"] for r in n)
    assert parts["enqueue"] == sum(r["enqueue"][END] - r["enqueue"][TS]
                                   for r in n)
    assert reg.counter("serving_dispatch_wait_seconds_total").value == \
        sum(r["wait"][END] - r["wait"][TS] for r in rows)


def test_after_empty_first_dispatch_and_after_a_drained_engine(eng):
    srv, tel, clock = serve(eng, requests((5,), tag="a"))
    first = len(tel.tracer.spans("serve.dispatch"))
    clock.t += 1000.0           # nobody asks anything for a while
    drive(srv, requests((6,), tag="b", seed=2), clock)
    rows = dispatch_rows(tel)
    marks = [r["counts"]["after_empty"] for r in rows]
    assert marks == [1 if i in (0, first) else 0 for i in range(len(rows))]
    # the first dispatch ever has no dispatch before it: no gap at all
    assert rows[0]["counts"]["prev"] == ""
    assert "gap_us" not in rows[0]["counts"]
    pause = rows[first]
    assert pause["counts"]["gap_us"] == pause["gap"] * 1e6 > 1000e6
    reg = tel.registry
    assert reg.counter("serving_engine_empty_seconds_total").value \
        == pause["gap"]
    # ... and is in no other sum
    h = reg.histogram("serving_dispatch_gap_s")
    assert h.count == len(rows) - 2
    assert h.sum == sum(r["gap"] for r in counted(rows)) < 1000.0
    assert h.sum == sum(
        reg.counter(f"serving_gap_{k}_seconds_total").value
        for k in ("sched", "caller", "enqueue"))


def test_retried_dispatch_backoff_lies_in_its_gap_under_sched_once(
        eng, monkeypatch):
    clock = TickClock()
    slept = []

    def sleep(seconds):
        slept.append(seconds)
        clock.t += 5000.0       # the backoff, on the tracer's clock

    monkeypatch.setattr(serving_mod.time, "sleep", sleep)
    tel = Telemetry(clock=clock)
    with faults_lib.injected(Fault("serving.decode", "device_error", step=2),
                             seed=0) as inj:
        srv = ServingEngine(eng, num_slots=2, block_size=4, num_blocks=24,
                            prefill_chunk=8, spec_decode=False,
                            max_retries=3, retry_backoff_s=0.001,
                            telemetry=tel)
        drive(srv, requests(), clock)
    assert len(inj.fired) == len(slept) == 1 and srv.stats["retries"] == 1
    rows = dispatch_rows(tel)
    failed = [r for r in rows if r["wait"] is None]
    assert len(failed) == 1 and "gap_us" not in failed[0]["counts"]
    retried = rows[rows.index(failed[0]) + 1]
    assert retried["counts"]["attempt"] == 1
    # both attempts name the same dispatch before them
    assert retried["counts"]["prev"] == failed[0]["counts"]["prev"]
    assert retried["gap"] > 5000.0
    assert retried["counts"]["gap_us"] == retried["gap"] * 1e6
    # one gap for the dispatch, not one per attempt
    n = counted(rows)
    reg = tel.registry
    assert reg.histogram("serving_dispatch_gap_s").count == len(n) \
        == srv.stats["prefill_chunks"] + srv.stats["decode_steps"] - 1
    enqueue = sum(r["enqueue"][END] - r["enqueue"][TS] for r in n)
    assert reg.counter("serving_gap_enqueue_seconds_total").value == enqueue
    sched = reg.counter("serving_gap_sched_seconds_total").value
    assert sched == sum(r["gap"] - r["caller"] for r in n) - enqueue
    others = sum(r["gap"] for r in n if r is not retried)
    assert sched > 5000.0 > others


def test_telemetry_off_no_record_no_registry_entry_no_clock_read(
        eng, monkeypatch):
    srv, _, _ = serve(eng, requests(), telemetry=False)
    assert srv.telemetry.tracer.records() == []
    text = srv.metrics.to_prometheus()
    assert "serving_steps" in text
    assert not any(name in text for name in GAP_METRICS)
    assert srv._gap_prev_t is None and srv._gap_step_t1 is None
    ready = jax.block_until_ready(jnp.ones(3))
    reads = []
    real = serving_mod.time.perf_counter
    monkeypatch.setattr(serving_mod.time, "perf_counter",
                        lambda: reads.append(1) or real())
    out = srv._device_call("serving.decode", lambda x: x, ready)
    monkeypatch.undo()
    assert out is ready and reads == []


def test_tokens_bit_identical_on_and_off(eng):
    on, _, _ = serve(eng, requests((20, 5, 9), new=6))
    off, _, _ = serve(eng, requests((20, 5, 9), new=6), telemetry=False)
    got = {r.rid: r.tokens.tolist() for r in on.finished}
    assert got == {r.rid: r.tokens.tolist() for r in off.finished}
    assert len(got) == 3


def test_every_annotation_carries_its_ring_records_sid(eng, monkeypatch):
    made = []

    class Annotation:
        def __init__(self, name, **kw):
            made.append((name, kw))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(tracer_mod.jax.profiler, "TraceAnnotation",
                        Annotation)
    _, tel, _ = serve(eng, requests())
    spans = {r[SID]: r for r in tel.tracer.spans()}
    assert len(made) == len(spans) > 20
    assert sorted(kw["sid"] for _, kw in made) == sorted(spans)
    for name, kw in made:
        rec = spans[kw.pop("sid")]
        assert rec[NAME] == name
        # what the annotation was given is in the record; what was set at
        # exit (gap_us, live, h2d) is in the record alone
        assert kw.items() <= (rec[DATA] or {}).items()
    dispatches = [kw for name, kw in made if name == "serve.dispatch"]
    assert all({"site", "attempt", "prev", "after_empty"} == set(kw)
               for kw in dispatches)
    assert any("gap_us" in spans_rec[DATA] for spans_rec in spans.values()
               if spans_rec[NAME] == "serve.dispatch")


def test_host_exposed_share_from_a_scrape(eng):
    """What docs/OBSERVABILITY.md tells an operator to compute:
    gap / (gap + wait) from the text exposition alone."""
    _, tel, _ = serve(eng, requests())
    scrape = {}
    for line in tel.to_prometheus().splitlines():
        if line and not line.startswith("#"):
            name, value = line.rsplit(" ", 1)
            scrape[name] = float(value)
    gap = sum(scrape[f"serving_gap_{k}_seconds_total"]
              for k in ("sched", "caller", "enqueue"))
    assert gap == scrape["serving_dispatch_gap_s_sum"]
    wait = scrape["serving_dispatch_wait_seconds_total"]
    assert 0.0 < gap / (gap + wait) < 1.0
