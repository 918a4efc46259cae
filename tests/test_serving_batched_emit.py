"""The plain decode step as array operations (ISSUE 47): capacity is one
comparison over the decoding slots, the operands are the per-slot arrays,
the emit is one ``lengths[live] += 1``, one ``tolist()`` and a bare loop
of appends; only the slots that something in the request or the cache
singles out (a block border, stop sequences, logprobs, a repetition
penalty) take the per-slot path.

Parity: the same traffic served twice, once as the program runs it and
once with EVERY slot forced into the ``_slow`` mask (a monkeypatch of
``ServingEngine._slow_emit``: the program has no knob), must agree on
every token, stamp, log-probability, terminal state, the order of
``finished``, the cache's tables and lengths after every step, the free
list and the stats. The structural guard counts calls, not seconds."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.engine import InferenceEngine
from deepspeed_tpu.inference.paged_cache import PagedKVCache
from deepspeed_tpu.inference.serving import (TERMINAL_STATES, ServeRequest,
                                             ServingEngine)
from deepspeed_tpu.models import gpt
from deepspeed_tpu.utils.faults import Fault, FaultInjector

VOCAB = 64
BS = 4          # block size: a slot meets a block border every 4th step


@pytest.fixture(scope="module")
def eng(devices):
    cfg = gpt.GPTConfig(vocab_size=VOCAB, n_layers=1, n_heads=2, d_model=16,
                        max_seq_len=48, use_flash_attention=False,
                        remat=False, dtype=jnp.float32)
    params = gpt.init_params(jax.random.PRNGKey(0), cfg)
    return InferenceEngine(config=cfg, params=params, dtype=jnp.float32)


def _prompts(n, seed):
    r = np.random.default_rng(seed)
    return [r.integers(1, VOCAB, int(r.integers(3, 10))).astype(np.int32)
            for _ in range(n)]


def _reference_outs(eng, slots, prompts, n_new):
    """Greedy continuations, to aim eos ids and stop sequences at tokens
    the requests really emit."""
    srv = ServingEngine(eng, num_slots=slots, block_size=BS,
                        prefill_chunk=16)
    srv.run([ServeRequest(rid=i, prompt=p, max_new_tokens=n_new)
             for i, p in enumerate(prompts)])
    return {r.rid: list(r.out) for r in srv.finished}


def _mix(name, eng, slots):
    """(requests, engine keywords, hook) of one traffic mix. More requests
    than slots, so slots change hands while others decode."""
    n = slots + max(2, slots // 4)
    prompts = _prompts(n, seed=len(name))
    kw, hook = {}, None
    if name == "max_new":
        reqs = [ServeRequest(rid=i, prompt=p, max_new_tokens=2 + i % 9)
                for i, p in enumerate(prompts)]
    elif name == "eos":
        ref = _reference_outs(eng, slots, prompts, 10)
        reqs = [ServeRequest(rid=i, prompt=p, max_new_tokens=10,
                             eos_id=ref[i][2 + i % 6] if i % 3 else None)
                for i, p in enumerate(prompts)]
    elif name == "stop_logprobs_penalty":
        ref = _reference_outs(eng, slots, prompts, 10)
        reqs = []
        for i, p in enumerate(prompts):
            extra = ({}, {"stop": [ref[i][3:5], [VOCAB + 1]]},
                     {"logprobs": True},
                     {"repetition_penalty": 1.3},
                     {"temperature": 0.8, "seed": i},
                     {"temperature": 0.7, "top_k": 8, "seed": i,
                      "logprobs": True, "repetition_penalty": 1.2})[i % 6]
            reqs.append(ServeRequest(rid=i, prompt=p, max_new_tokens=10,
                                     **extra))
    elif name == "deadline":
        # the internal step clock: a deadline of 5 + i % 7 ticks passes
        # while the request decodes (or waits in the queue)
        reqs = [ServeRequest(rid=i, prompt=p, max_new_tokens=12,
                             deadline=float(5 + i % 7) if i % 2 else None)
                for i, p in enumerate(prompts)]
    elif name == "at_capacity":
        reqs = [ServeRequest(rid=i, prompt=p, max_new_tokens=10)
                for i, p in enumerate(prompts)]

        def hook(srv, step):
            # drive every seventh decoding slot to its block budget by
            # hand (prompt + max_new cannot get there: submit refuses)
            if step != 4:
                return
            for slot in np.flatnonzero(srv._decoding)[::7].tolist():
                srv.cache.ensure_capacity(slot, srv.cache.tokens_per_slot)
                srv.cache.lengths[slot] = srv.cache.tokens_per_slot
    elif name == "dry_pool":
        # fewer blocks than the slots' growth: decode evicts cached
        # nothing (no prefix cache), preempts the youngest, requeues
        kw = {"num_blocks": slots * 3 + 2, "max_evictions": 2}
        reqs = [ServeRequest(rid=i, prompt=p, max_new_tokens=12)
                for i, p in enumerate(prompts)]
    else:
        raise AssertionError(name)
    return reqs, kw, hook


def _check_arrays(srv):
    """The per-slot arrays agree with the requests and the cache."""
    c = srv.cache
    for slot, req in enumerate(srv.slots):
        assert c.owned_count[slot] == len(c._owned[slot])
        assert srv._held[slot] == (req is not None)
        state = None if req is None else req.state
        assert srv._decoding[slot] == (state == "decode")
        assert srv._prefilling[slot] == (state == "prefill")
        if req is None:
            assert srv._deadline[slot] == np.inf and not srv._slow[slot]
            assert srv._last_tok[slot] == 0 and srv._gen[slot] == 0
            continue
        assert srv._deadline[slot] == (np.inf if req.deadline is None
                                       else req.deadline)
        assert srv._max_new[slot] == req.max_new_tokens
        assert srv._eos[slot] == (-1 if req.eos_id is None else req.eos_id)
        if state == "decode":
            assert srv._last_tok[slot] == req.out[-1]
            assert srv._gen[slot] == len(req.out)
        else:
            assert srv._last_tok[slot] == 0 and srv._gen[slot] == 0


def _serve(eng, slots, name, every_slot_slow, monkeypatch):
    reqs, kw, hook = _mix(name, eng, slots)
    with monkeypatch.context() as m:
        if every_slot_slow:
            m.setattr(ServingEngine, "_slow_emit", lambda self, s, r: True)
        srv = ServingEngine(eng, num_slots=slots, block_size=BS,
                            prefill_chunk=16, **kw)
        for r in reqs:
            srv.submit(r)
        trail = []
        step = 0
        while srv.busy:
            if hook is not None:
                hook(srv, step)
            srv.step()
            step += 1
            assert step < 2000
            _check_arrays(srv)
            trail.append((srv.cache.lengths.copy(), srv.cache.tables.copy(),
                          srv.cache.free_blocks,
                          [None if r is None else len(r.out)
                           for r in srv.slots]))
    return srv, reqs, trail


@pytest.mark.parametrize("name", ["max_new", "eos", "stop_logprobs_penalty",
                                  "deadline", "at_capacity", "dry_pool"])
@pytest.mark.parametrize("slots", [4, 64, 192])
def test_batched_step_equals_per_slot_path(eng, monkeypatch, slots, name):
    fast, f_reqs, f_trail = _serve(eng, slots, name, False, monkeypatch)
    slow, s_reqs, s_trail = _serve(eng, slots, name, True, monkeypatch)
    # the mix met what it was built to meet
    states = [r.state for r in f_reqs]
    assert all(s in TERMINAL_STATES for s in states)
    if name == "eos":
        assert any(len(r.out) < r.max_new_tokens for r in f_reqs)
    elif name == "stop_logprobs_penalty":
        assert fast.stats["stop_hits"] > 0 and fast.stats["sampled_tokens"] > 0
        assert any(r.out_logprobs for r in f_reqs)
    elif name == "deadline":
        assert fast.stats["timeouts"] > 0
        assert any(r.state == "timeout" and r.out for r in f_reqs)
    elif name == "at_capacity":
        assert any(r.state == "done" and len(r.out) < r.max_new_tokens
                   for r in f_reqs)
    elif name == "dry_pool":
        assert fast.stats["evictions"] > 0
    # the two runs are the same run
    assert len(f_trail) == len(s_trail)
    for (fl, ft, ff, fo), (sl, st, sf, so) in zip(f_trail, s_trail):
        np.testing.assert_array_equal(fl, sl)
        np.testing.assert_array_equal(ft, st)
        assert ff == sf and fo == so
    assert [r.rid for r in fast.finished] == [r.rid for r in slow.finished]
    for a, b in zip(f_reqs, s_reqs):
        assert a.rid == b.rid
        assert a.out == b.out and all(type(t) is int for t in a.out)
        assert a.token_times == b.token_times
        assert a.out_logprobs == b.out_logprobs
        assert (a.state, a.finished_at, a.first_token_at, a.evictions) \
            == (b.state, b.finished_at, b.first_token_at, b.evictions)
    assert dict(fast.stats) == dict(slow.stats)
    assert fast.cache.free_blocks == slow.cache.free_blocks
    assert fast.cache.stats() == slow.cache.stats()
    # what differs is the path: the counter says which slots took which
    slow_steps = slow.metrics.counter("serving_emit_slow_slots_total").value
    fast_steps = fast.metrics.counter("serving_emit_slow_slots_total").value
    assert slow_steps >= fast_steps
    if name == "stop_logprobs_penalty":
        assert 0 < fast_steps < slow_steps
    else:
        assert fast_steps == 0 < slow_steps


def _all_decoding(eng, slots, **kw):
    srv = ServingEngine(eng, num_slots=slots, block_size=BS,
                        prefill_chunk=16, **kw)
    # prompts of 5 and of 6 tokens: two of every four steps find some
    # slots at a block border, the other two find none
    r = np.random.default_rng(7)
    for i in range(slots):
        srv.submit(ServeRequest(
            rid=i, prompt=r.integers(1, VOCAB, 5 + i % 2).astype(np.int32),
            max_new_tokens=30))
    while not srv._decoding.all():
        srv.step()
    return srv


def test_plain_step_visits_only_the_slots_at_a_block_border(eng, monkeypatch):
    """192 plain greedy requests: a decode step calls ``ensure_capacity``
    for the slots at a block border and no other, ``_emit_token`` for
    none, sums the lengths at most once; the operands of the dispatch are
    what the per-slot loop built; and every live request's ``out`` has
    grown by one when ``step`` returns (benchmark/drivers/serve.py
    ``harvest`` and ``on_dispatch`` read ``len(req.out)``)."""
    slots = 192
    srv = _all_decoding(eng, slots)
    cache = srv.cache
    calls = {"ensure": [], "emit": 0, "sum": 0, "dispatch": 0}
    ensure, emit, call = (cache.ensure_capacity, srv._emit_token,
                          srv._device_call)
    in_flight = PagedKVCache.tokens_in_flight.fget

    def counted_sum(self):
        calls["sum"] += 1
        return in_flight(self)

    def counted_ensure(slot, n):
        calls["ensure"].append(slot)
        return ensure(slot, n)

    def counted_emit(*a):
        calls["emit"] += 1
        return emit(*a)

    def checked_call(site, fn, *args, now=None):
        assert site == "serving.decode"
        k, v, tables, lengths, tokens, active, impl = args
        assert tables is cache.tables and lengths is cache.lengths
        assert tokens.dtype == np.int32 and tokens.shape == (slots,)
        assert active.dtype == bool and active.shape == (slots,)
        want = [(r.out[-1], True, len(r.out)) for r in srv.slots]
        lanes = dict(zip(fn.__code__.co_freevars,
                         (c.cell_contents for c in fn.__closure__)))["lanes"]
        gen = lanes[1]          # the sampler's key-chain counter
        assert list(zip(tokens.tolist(), active.tolist(),
                        gen.tolist())) == want
        assert gen.dtype == np.int32
        calls["dispatch"] += 1
        return call(site, fn, *args, now=now)

    monkeypatch.setattr(PagedKVCache, "tokens_in_flight",
                        property(counted_sum))
    monkeypatch.setattr(cache, "ensure_capacity", counted_ensure)
    monkeypatch.setattr(srv, "_emit_token", counted_emit)
    monkeypatch.setattr(srv, "_device_call", checked_call)
    seen_border = seen_none = False
    for _ in range(2 * BS + 1):
        before = [len(r.out) for r in srv.slots]
        border = np.flatnonzero(
            cache.lengths == cache.owned_count * BS).tolist()
        calls.update(ensure=[], emit=0, sum=0)
        assert srv.step() == slots
        assert calls["ensure"] == border
        assert calls["emit"] == 0 and calls["sum"] <= 1
        assert [len(r.out) for r in srv.slots] == [n + 1 for n in before]
        seen_border |= bool(border)
        seen_none |= not border
    assert seen_border and seen_none and calls["dispatch"] == 2 * BS + 1
    assert srv.metrics.counter("serving_emit_slow_slots_total").value == 0


def test_armed_fault_keeps_one_ensure_visit_a_decoding_slot(eng):
    """``cache.ensure`` is matched by its visit index (tests/test_chaos.py
    arms visits 4 and 5): with any fault armed every decoding slot visits
    the site each step, as before; with none armed only the slots that
    grow do."""
    slots = 8
    armed = FaultInjector([Fault("serving.prefill", "device_error",
                                 step=10 ** 6)])
    for faults, every in ((armed, True), (FaultInjector(), False)):
        srv = _all_decoding(eng, slots, faults=faults)
        for _ in range(BS):
            v0 = faults.visits.get("cache.ensure", 0)
            grow = int((srv.cache.lengths
                        == srv.cache.owned_count * BS).sum())
            srv.step()
            visits = faults.visits.get("cache.ensure", 0) - v0
            assert visits == (slots if every else grow)


def test_slots_that_leave_without_finishing_clear_their_arrays(eng):
    """A prefill-only replica parks finished prefills (``handoff``) and the
    router releases them; a drained replica releases every slot: both go
    through the pair that writes the arrays."""
    srv = ServingEngine(eng, num_slots=4, block_size=BS, prefill_chunk=16,
                        prefill_only=True)
    for i, p in enumerate(_prompts(3, seed=3)):
        srv.submit(ServeRequest(rid=i, prompt=p, max_new_tokens=5))
    for _ in range(3):
        srv.step()
        _check_arrays(srv)
    parked = srv.ready_handoffs()
    assert len(parked) == 3 and not srv._decoding.any()
    assert srv._held.sum() == 3
    assert srv.release_handoff(parked[0][1].rid)
    _check_arrays(srv)
    assert srv._held.sum() == 2
    srv.pending_snapshot(release=True)
    _check_arrays(srv)
    assert not srv._held.any() and not srv.busy


@pytest.mark.parametrize("telemetry", [False, True])
def test_span_attributes_and_latency_observations(eng, telemetry):
    """With telemetry on the step's spans say how many slots emitted one
    by one (``slow``) and how many tables grew (``grown``), and the TPOT
    histogram takes one observation a decode token whichever path
    emitted it."""
    srv = ServingEngine(eng, num_slots=4, block_size=BS, prefill_chunk=16,
                        telemetry=telemetry)
    prompts = _prompts(4, seed=5)
    reqs = [ServeRequest(rid=i, prompt=p, max_new_tokens=4 if i == 1 else 9,
                         logprobs=(i == 1)) for i, p in enumerate(prompts)]
    srv.run(reqs)
    decode_tokens = sum(len(r.out) - 1 for r in reqs)
    assert srv.metrics.counter("serving_emit_slow_slots_total").value \
        == len(reqs[1].out) - 1
    if not telemetry:
        return
    assert srv.metrics.histogram("serving_tpot").count == decode_tokens
    assert srv.metrics.histogram("serving_ttft").count == len(reqs)
    tracer = srv.telemetry.tracer
    step_emits = [r[5] for r in tracer.spans("serve.emit") if r[2] is None]
    assert sum(c["tokens"] for c in step_emits) == decode_tokens
    assert sum(c["slow"] for c in step_emits) == len(reqs[1].out) - 1
    assert {c["slow"] for c in step_emits} == {0, 1}
    grown = [r[5]["grown"] for r in tracer.spans("serve.decode")]
    # a prompt of p tokens owns ceil(p / BS) blocks; p + max_new - 1
    # tokens are written in all (the last token is emitted, never written)
    assert sum(grown) == sum(
        -(-(len(r.prompt) + r.max_new_tokens - 1) // BS)
        - -(-len(r.prompt) // BS) for r in reqs)
