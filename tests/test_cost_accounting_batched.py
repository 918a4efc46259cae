"""The telemetry plane does what the step does (ISSUE 52): arrays per slot,
written where a slot changes hands.

Layers:
  1. the array accountant (telemetry/costs.py ``charge_batched``, one
     ``charge_block_seconds`` a step, ``fold`` / ``flush``) against a plain
     per-slot reference accountant that THIS FILE holds (the parent's walk
     over the slots, python ints, the closed forms written out as sums):
     the same seeded traffic served under each, and every ``req.cost``,
     every tenant, ``system``, ``totals`` and the three registry counters
     equal, integer for integer, at every view (after every step);
  2. ``Histogram.observe_many`` against the same observations one by one;
  3. a plain decode step with telemetry on calls the accountant, the
     block-seconds charge and the TPOT histogram a constant number of
     times whatever the slots (8 and 64);
  4. ``self_us`` / ``self_parts`` on every ``serve.dispatch`` record that
     has ``gap_us``; nothing with telemetry off.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.engine import InferenceEngine
from deepspeed_tpu.inference.serving import ServeRequest, ServingEngine
from deepspeed_tpu.models import gpt
from deepspeed_tpu.runtime.lora import add_lora, adapter_state_dict
from deepspeed_tpu.telemetry import Telemetry
from deepspeed_tpu.telemetry.costs import (CostAccountant, new_footprint,
                                           split_even)
from deepspeed_tpu.telemetry.metrics import Histogram
from deepspeed_tpu.telemetry.tracer import SELF_PARTS
from deepspeed_tpu.utils.faults import Fault, FaultInjector
from deepspeed_tpu.utils.jit_registry import DISPATCH_CLASSES

pytestmark = pytest.mark.usefixtures("devices")

COUNTERS = ("serving_flops_total", "serving_hbm_bytes_total",
            "serving_kv_block_seconds")


def tiny():
    cfg = gpt.GPTConfig(vocab_size=128, n_layers=2, n_heads=4, d_model=32,
                        max_seq_len=64, use_flash_attention=False,
                        remat=False, dtype=jnp.float32)
    return cfg, gpt.init_params(jax.random.PRNGKey(0), cfg)


@pytest.fixture(scope="module")
def stack():
    cfg, params = tiny()
    lp = add_lora(params, rng=jax.random.PRNGKey(3), rank=4, alpha=8.0)
    return (InferenceEngine(config=cfg, params=params, dtype=jnp.float32),
            adapter_state_dict(lp))


def prompts_of(lengths, seed=1):
    r = np.random.default_rng(seed)
    return [r.integers(1, 128, n).astype(np.int32) for n in lengths]


# ---------------------------------------------------------------------------
# the reference: one python walk over the live slots a charge
# ---------------------------------------------------------------------------

class PerSlotReference:
    """The accountant as it was before the arrays: every charge walks its
    slots and adds python ints to the request's footprint, its tenant's
    and the totals at once (``fold`` and ``flush`` have nothing to do).
    It answers the engine's calls but reads the slots, the requests and
    the lengths itself, and carries its own arithmetic."""

    enabled = True
    self_s = 0.0

    def __init__(self, srv):
        real = srv.costs
        self.srv, self.cfg = srv, srv.engine.cfg
        self.kv_tok, self.block_bytes = real.kv_bytes_tok, real.block_bytes
        self.weights = gpt.num_params(self.cfg) * real.param_itemsize
        n = gpt.num_params(self.cfg) - self.cfg.vocab_size * self.cfg.d_model
        if self.cfg.tie_embeddings:
            n += self.cfg.d_model * self.cfg.vocab_size
        self.flops_tok = 2 * n
        self.totals = {c: {"dispatches": 0, "flops": 0, "hbm_bytes": 0}
                       for c in DISPATCH_CLASSES}
        self.block_seconds_total = 0
        self.system = new_footprint()
        self.tenants = {}
        self.counters = [srv.metrics.counter(n) for n in COUNTERS]

    def _cost(self, n, s):
        ctx = sum(s + i + 1 for i in range(n))
        flops = n * self.flops_tok \
            + 4 * self.cfg.n_layers * self.cfg.d_model * ctx
        return flops, self.kv_tok * (ctx + n)

    def _tenant(self, req):
        return self.tenants.setdefault(req.adapter_id or "base",
                                       new_footprint())

    def _add(self, cls, req, flops, nbytes, dispatches):
        owner = req.cost if req is not None else self.system
        tenant = self._tenant(req) if req is not None \
            else self.tenants.setdefault("system", new_footprint())
        for fp in (owner, self.totals, tenant):
            fp[cls]["flops"] += flops
            fp[cls]["hbm_bytes"] += nbytes
            fp[cls]["dispatches"] += dispatches
        self.counters[0].inc(flops)
        self.counters[1].inc(nbytes)

    def charge_prefill(self, req, n_tokens, start_pos):
        flops, kv = self._cost(int(n_tokens), int(start_pos))
        self._add("prefill", req, flops, kv + self.weights, 1)

    def charge_batched(self, cls, slots, n_tokens, start_pos):
        slots = [int(i) for i in slots]
        ns = [int(n_tokens)] * len(slots) if np.ndim(n_tokens) == 0 \
            else [int(n) for n in n_tokens]
        shares = split_even(self.weights, len(slots))
        for i, n, share in zip(slots, ns, shares):
            flops, kv = self._cost(n, int(self.srv.cache.lengths[i]))
            self._add(cls, self.srv.slots[i], flops, kv + share, 1)

    def charge_cow(self, req, n_blocks):
        if n_blocks > 0:
            self._add("cow", req, 0, 2 * self.block_bytes * n_blocks,
                      n_blocks)

    def charge_spill(self, n_blocks, req=None, restore=False):
        if n_blocks > 0:
            self._add("spill", req, 0, self.block_bytes * n_blocks, n_blocks)

    def charge_block_seconds(self, held, lengths, block_size, ticks):
        cache = self.srv.cache
        for i, req in enumerate(self.srv.slots):
            if req is None:
                continue
            bs = cache.blocks_for(int(cache.lengths[i])) * int(ticks)
            if bs > 0:
                req.cost["block_seconds"] += bs
                self._tenant(req)["block_seconds"] += bs
                self.block_seconds_total += bs
                self.counters[2].inc(bs)

    def fold(self, slot, req):
        pass

    def flush(self):
        pass


def view(srv, reqs):
    """Everything a reader of the accountant can see, after the view's
    own flush: plain data, to compare with ``==``."""
    acc = srv.costs
    acc.flush()
    counters = srv.metrics.snapshot()["counters"]
    return {
        "requests": {r.rid: (r.state, r.cost) for r in reqs},
        "tenants": dict(acc.tenants),
        "system": acc.system,
        "totals": acc.totals,
        "block_seconds_total": acc.block_seconds_total,
        "counters": {n: counters[n] for n in COUNTERS},
    }


def conserved(v):
    """sum(per-request) + system == totals, class for class."""
    for c in DISPATCH_CLASSES:
        for k in ("flops", "hbm_bytes", "dispatches"):
            assert sum(cost[c][k] for _, cost in v["requests"].values()) \
                + v["system"][c][k] == v["totals"][c][k], (c, k)
    assert sum(cost["block_seconds"] for _, cost in v["requests"].values()) \
        + v["system"]["block_seconds"] == v["block_seconds_total"]


def both(stack, make_requests, **kw):
    """The same traffic through an engine with the array accountant and
    one with the reference in its place, step by step; the two views
    compared (and each conserved) after every step."""
    eng, adapter = stack
    fault_list = kw.pop("fault_list", None)
    pair = []
    for reference in (False, True):
        # an injector each: the visits are counted by the injector
        faults = FaultInjector(list(fault_list), seed=0) \
            if fault_list else None
        srv = ServingEngine(eng, telemetry=Telemetry(), faults=faults, **kw)
        if kw.get("lora_serve"):
            srv.register_adapter("t1", adapter)
        if reference:
            srv.costs = PerSlotReference(srv)
        else:
            assert type(srv.costs) is CostAccountant
        reqs = make_requests()
        for r in reqs:
            srv.submit(r)
        pair.append((srv, reqs))
    (a, reqs_a), (b, reqs_b) = pair
    steps = 0
    while a.busy or b.busy:
        a.step()
        b.step()
        va, vb = view(a, reqs_a), view(b, reqs_b)
        assert va == vb, f"the accountants part at step {steps}"
        conserved(va)
        steps += 1
        assert steps < 500
    # the same again through the accountant's own snapshot
    snap = a.costs.snapshot()
    assert snap["totals"] == b.costs.totals
    assert snap["tenants"].keys() == b.costs.tenants.keys()
    return a, b, reqs_a


def test_plain_traffic_two_tenants_evict_and_requeue(stack):
    """Admit, chunked prefill, decode, a finish mid-batch, evict and
    requeue in a tight pool, two tenants."""
    def make():
        ps = prompts_of((10, 9, 13, 5), seed=9)
        news = (12, 10, 3, 7)
        return [ServeRequest(rid=f"r{i}", prompt=p, max_new_tokens=n,
                             adapter_id="t1" if i % 2 else None)
                for i, (p, n) in enumerate(zip(ps, news))]
    a, _, reqs = both(stack, make, num_slots=2, block_size=4, num_blocks=8,
                      prefill_chunk=8, spec_decode=False, lora_serve=True,
                      lora_pool_blocks=2, lora_max_rank=4, lora_rank_block=4)
    assert a.stats["evictions"] >= 1
    assert {"base", "t1"} <= set(a.costs.tenants)
    assert all(r.state == "done" for r in reqs)
    assert all(r.cost["decode"]["dispatches"] > 0 for r in reqs
               if r.max_new_tokens > 1)
    assert all(r.cost["block_seconds"] > 0 for r in reqs)


def test_horizon_8(stack):
    def make():
        return [ServeRequest(rid=i, prompt=p, max_new_tokens=16)
                for i, p in enumerate(prompts_of((6, 11, 4), seed=3))]
    a, _, reqs = both(stack, make, num_slots=2, block_size=4, num_blocks=24,
                      prefill_chunk=8, spec_decode=False, decode_horizon=8)
    gen = sum(len(r.out) for r in reqs)
    assert 0 < a.costs.totals["decode"]["dispatches"] < gen


def test_speculation_with_a_fallback(stack):
    def make():
        return [ServeRequest(rid=i, prompt=p, max_new_tokens=8)
                for i, p in enumerate(prompts_of((5, 9, 12), seed=7))]
    a, _, _ = both(stack, make, num_slots=2, block_size=4, num_blocks=24,
                   prefill_chunk=8, spec_decode=True, fault_list=(
                       Fault("serving.spec_draft", "device_error", step=1,
                             count=3),))
    assert a.stats["spec_fallbacks"] >= 3 and a.stats["spec_steps"] > 0
    assert a.costs.totals["verify"]["dispatches"] > 0
    assert a.costs.totals["decode"]["dispatches"] > 0


def test_a_seated_request_is_current_after_a_view_only(stack):
    """Between two views a seated request's decode charges sit in its
    slot's accumulators; ``flush`` (every view's first line) and the
    slot's vacating move them, and nothing is counted twice."""
    eng, _ = stack
    srv = ServingEngine(eng, num_slots=2, block_size=4, num_blocks=24,
                        prefill_chunk=8, telemetry=Telemetry())
    req = ServeRequest(rid="a", prompt=prompts_of((6,))[0], max_new_tokens=9)
    srv.submit(req)
    while len(req.out) < 4:
        srv.step()
    assert req.state == "decode"
    held = srv.costs.totals["decode"]["dispatches"]
    assert held >= 3 and req.cost["decode"]["dispatches"] == 0
    srv.costs.flush()
    assert req.cost["decode"]["dispatches"] == held
    srv.costs.flush()
    assert req.cost["decode"]["dispatches"] == held
    entry = srv.pending_snapshot()[0]           # a view
    assert entry["cost"] == req.cost
    srv.run()
    assert req.cost["decode"] == srv.costs.totals["decode"]
    assert req.cost["block_seconds"] == srv.costs.block_seconds_total


# ---------------------------------------------------------------------------
# observe_many
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("at", [None, 3.5])
def test_observe_many_is_observe_of_each(at):
    values = np.random.default_rng(0).lognormal(-6.0, 2.5, 700)
    values[::50] = 0.0                       # the first bucket's edge
    values[7] = 1e4                          # the overflow bucket
    one, many = Histogram("one", window_capacity=512), \
        Histogram("many", window_capacity=512)
    for v in values:
        one.observe(v, at=at)
    for part in (values[:1], values[1:300], values[300:300], values[300:]):
        many.observe_many(part, at=at)
    assert many.counts == one.counts and many.count == one.count
    assert many.sum == pytest.approx(one.sum, rel=1e-12)
    assert many._vmax == one._vmax and many._seq == one._seq
    assert list(many._ring) == list(one._ring)
    for q in (1, 50, 90, 99, 100):
        assert many.percentile(q) == one.percentile(q)
    assert many.window_summary() == one.window_summary()
    assert many.window_summary(window=100.0) == \
        one.window_summary(window=100.0)


# ---------------------------------------------------------------------------
# a constant number of calls a step, whatever the slots
# ---------------------------------------------------------------------------

def plane_calls_of_one_decode_step(eng, num_slots, monkeypatch):
    srv = ServingEngine(eng, num_slots=num_slots, block_size=4,
                        num_blocks=4 * num_slots + 8, prefill_chunk=8,
                        spec_decode=False, telemetry=Telemetry())
    for i, p in enumerate(prompts_of((5,) * num_slots, seed=5)):
        srv.submit(ServeRequest(rid=i, prompt=p, max_new_tokens=12))
    while not srv._decoding.all():
        srv.step()
    calls = {}

    def counting(owner, name, only=None):
        plain = getattr(owner, name)

        def wrapped(self, *a, **k):
            if only is None or self is only:
                calls[name] = calls.get(name, 0) + 1
            return plain(self, *a, **k)
        monkeypatch.setattr(owner, name, wrapped)

    for name in ("charge_batched", "charge_block_seconds", "fold", "_add"):
        counting(CostAccountant, name)
    for name in ("observe", "observe_many"):
        counting(Histogram, name, only=srv._h_tpot)
    assert srv.step() == num_slots
    monkeypatch.undo()
    return calls


def test_plain_decode_step_calls_the_plane_a_constant_number_of_times(
        stack, monkeypatch):
    eng, _ = stack
    small = plane_calls_of_one_decode_step(eng, 8, monkeypatch)
    large = plane_calls_of_one_decode_step(eng, 64, monkeypatch)
    assert small == large == {"charge_batched": 1, "charge_block_seconds": 1,
                              "observe_many": 1}


# ---------------------------------------------------------------------------
# self_us: the plane's own host time, on every dispatch
# ---------------------------------------------------------------------------

def test_self_us_on_every_dispatch_with_a_gap(stack):
    eng, _ = stack
    tel = Telemetry()
    srv = ServingEngine(eng, num_slots=2, block_size=4, num_blocks=24,
                        prefill_chunk=8, telemetry=tel)
    srv.run([ServeRequest(rid=i, prompt=p, max_new_tokens=6)
             for i, p in enumerate(prompts_of((20, 5, 9), seed=2))])
    rows = [r[5] for r in tel.tracer.spans("serve.dispatch")]
    with_gap = [c for c in rows if "gap_us" in c]
    assert len(with_gap) == len(rows) - 1       # all but the first ever
    assert "self_us" not in rows[0]
    for c in with_gap:
        parts = c["self_parts"]
        assert len(parts) == len(SELF_PARTS) == 6
        assert all(p >= 0.0 for p in parts)
        assert 0 < c["self_us"] <= c["gap_us"]
        # the sum of the parts less `hidden`, to the microsecond's rounding
        assert abs(c["self_us"] - sum(parts[:-1])) <= 0.5 + 1e-6
        spans, hidden = parts[0], parts[-1]
        assert spans > 0.0 and hidden > 0.0
    # the accountant's part is there where a charge lay in the gap
    assert any(c["self_parts"][1] > 0.0 for c in with_gap)
    total = tel.registry.counter("serving_telemetry_self_seconds_total").value
    assert total >= sum(sum(c["self_parts"]) for c in with_gap) * 1e-6 * 0.99
    # what was taken is not kept for the next dispatch
    assert srv.costs.self_s == 0.0 or srv.costs.self_s < 1e-3


def test_nothing_of_it_with_telemetry_off(stack):
    eng, _ = stack
    srv = ServingEngine(eng, num_slots=2, block_size=4, num_blocks=24,
                        prefill_chunk=8, telemetry=False)
    srv.run([ServeRequest(rid=0, prompt=prompts_of((9,))[0],
                          max_new_tokens=5)])
    assert srv.telemetry.tracer.records() == []      # the no-op's ring
    assert srv._tok_t is None and not srv.costs.enabled
    assert "serving_telemetry_self_seconds_total" not in srv.metrics.names()
