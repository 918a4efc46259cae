"""Speculative decoding inside continuous batching: per-slot
draft/verify over the paged KV cache (tentpole: inference/spec_decode.py
+ ServingEngine._spec_decode_step + InferenceEngine.verify_slots +
PagedKVCache.rollback; docs/SPECULATIVE.md).

The contract under test: with greedy-target-equality acceptance,
spec-on serving is TOKEN-BIT-IDENTICAL to spec-off greedy serving under
every scheduler behavior (staggered arrivals, eviction/requeue, prefix
cache hits, injected faults) — speculation changes how many verify
steps the same tokens take, never the tokens. Plus the rollback
invariant (a rejected draft chunk straddling a block edge releases the
tail block), the compile contract (ONE verify program replaces the
plain decode program; zero steady-state recompiles), and the chaos
degrade path (a draft/verify fault falls back to plain one-token
decode for that step)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.engine import InferenceEngine
from deepspeed_tpu.inference.paged_cache import PagedKVCache
from deepspeed_tpu.inference.serving import ServeRequest, ServingEngine
from deepspeed_tpu.inference.spec_decode import (NGramDraft, make_draft,
                                                 resolve_spec_decode,
                                                 resolve_spec_k)
from deepspeed_tpu.models import gpt
from deepspeed_tpu.utils import faults


def tiny(**over):
    cfg = gpt.GPTConfig(vocab_size=128, n_layers=2, n_heads=4, d_model=32,
                        max_seq_len=64, use_flash_attention=False,
                        remat=False, dtype=jnp.float32, **over)
    params = gpt.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def prompts_of(lengths, seed=1):
    r = np.random.default_rng(seed)
    return [r.integers(1, 128, n).astype(np.int32) for n in lengths]


def serve(eng, prompts, n_new=10, spec=True, **kw):
    kw.setdefault("num_slots", 2)
    kw.setdefault("block_size", 4)
    kw.setdefault("num_blocks", 24)
    kw.setdefault("prefill_chunk", 8)
    srv = ServingEngine(eng, spec_decode=spec, **kw)
    out = srv.run([ServeRequest(rid=i, prompt=p, max_new_tokens=n_new)
                   for i, p in enumerate(prompts)])
    return out, srv


# ---------------------------------------------------------------------------
# drafter + knob units
# ---------------------------------------------------------------------------

def test_ngram_draft_proposals():
    """Prompt-lookup drafting: the trailing n-gram's most recent earlier
    occurrence supplies the continuation; no match repeats the last
    token; the proposal is ALWAYS exactly (k,) int32 (static verify
    shape)."""
    d = NGramDraft(max_ngram=3)
    # trailing [1,2,3] matched at position 0 -> continuation [4,1,2]
    np.testing.assert_array_equal(
        d.propose([1, 2, 3, 4, 1, 2, 3], 3), [4, 1, 2])
    # continuation shorter than k: pad by repeating its last token
    np.testing.assert_array_equal(
        d.propose([7, 8, 9, 7, 8], 4), [9, 7, 8, 8])
    # no repetition anywhere: fall back to repeating the last token
    np.testing.assert_array_equal(d.propose([5], 3), [5, 5, 5])
    for ctx in ([], [3], [1, 2, 3, 1, 2]):
        p = d.propose(ctx, 5)
        assert p.shape == (5,) and p.dtype == np.int32


def test_spec_knob_resolution(monkeypatch):
    monkeypatch.delenv("DS_SPEC_DECODE", raising=False)
    assert resolve_spec_decode(None) is False      # default: off
    assert resolve_spec_decode(True) is True
    monkeypatch.setenv("DS_SPEC_DECODE", "on")
    assert resolve_spec_decode(None) is True
    assert resolve_spec_decode(False) is False     # explicit beats env
    monkeypatch.setenv("DS_SPEC_DECODE", "sideways")
    with pytest.raises(ValueError, match="DS_SPEC_DECODE"):
        resolve_spec_decode(None)
    monkeypatch.setenv("DS_SPEC_K", "6")
    assert resolve_spec_k(None) == 6
    with pytest.raises(ValueError, match="spec_k"):
        resolve_spec_k(0)
    assert isinstance(make_draft("ngram"), NGramDraft)
    with pytest.raises(ValueError, match="spec_draft"):
        make_draft(object())


def test_spec_accepts_sampled_requests(devices):
    """The historical greedy-only guard is gone: spec decode with
    temperature>0 constructs and drains via rejection-sampling verify,
    and the same config at the same seed is deterministic (the verify
    uniforms are counter-based Philox(seed, position) — no sequential
    state to drift)."""
    cfg, params = tiny()
    eng = InferenceEngine(config=cfg, params=params, dtype=jnp.float32)
    runs = []
    for _ in range(2):
        srv = ServingEngine(eng, num_slots=2, block_size=4, num_blocks=24,
                            spec_decode=True, spec_k=3, temperature=0.7)
        runs.append(srv.run([
            ServeRequest(rid=0, prompt=p, max_new_tokens=6, seed=11)
            for i, p in enumerate(prompts_of((9,)))]))
        assert srv.stats["spec_steps"] > 0
    assert np.array_equal(runs[0][0], runs[1][0])


# ---------------------------------------------------------------------------
# rollback hardening (satellite: paged_cache.rollback)
# ---------------------------------------------------------------------------

def test_rollback_releases_straddling_tail_block(devices):
    """A fully-rejected draft chunk that straddled a block edge must
    return the tail block to the pool: lengths shrink AND the block
    table entry clears (a leaked entry would pin one pool block per
    reject for the request's lifetime)."""
    cfg, _ = tiny()
    c = PagedKVCache(cfg, num_slots=2, block_size=4, num_blocks=8)
    c.allocate(0, 6)
    c.advance(0, 6)                      # committed length 6, 2 blocks
    # verify chunk of 5 tokens wants positions 6..10 -> a third block
    c.ensure_capacity(0, 11)
    assert c.stats()["used_blocks"] == 3
    # full reject: only the pending token commits (6 -> 7); the draft
    # suffix straddled into block 3, which only rejects were using
    c.advance(0, 1)
    c.rollback(0, 7)
    assert int(c.lengths[0]) == 7
    assert c.stats()["used_blocks"] == 2
    assert c.tables[0, 2] == 0           # table entry cleared, not leaked
    assert c.free_blocks == 6
    # partial accept inside the kept block: lengths move, blocks don't
    c.ensure_capacity(0, 12)
    c.advance(0, 2)
    c.rollback(0, 8)                     # 8 tokens == exactly 2 blocks
    assert c.stats()["used_blocks"] == 2 and int(c.lengths[0]) == 8


def test_rollback_rejects_bad_targets(devices):
    cfg, _ = tiny()
    c = PagedKVCache(cfg, num_slots=2, block_size=4, num_blocks=8)
    c.allocate(0, 5)
    c.advance(0, 5)
    with pytest.raises(ValueError, match="outside the allocated"):
        c.rollback(0, 9)                 # beyond capacity: growing is
    with pytest.raises(ValueError, match="outside the allocated"):
        c.rollback(0, -1)                # advance's job, not rollback's
    with pytest.raises(ValueError, match="not active"):
        c.rollback(1, 0)
    # legal rollbacks at the boundaries
    c.rollback(0, int(c.lengths[0]))     # no-op
    assert int(c.lengths[0]) == 5 and c.stats()["used_blocks"] == 2


# ---------------------------------------------------------------------------
# token parity: spec-on == spec-off, everywhere
# ---------------------------------------------------------------------------

def test_spec_serving_greedy_parity(devices):
    """Spec-on greedy serving is token-bit-identical to spec-off, and
    actually speculates (fewer verify dispatches than tokens, multi-
    token steps observed)."""
    cfg, params = tiny()
    eng = InferenceEngine(config=cfg, params=params, dtype=jnp.float32)
    prompts = prompts_of((5, 9, 12, 3))
    off, _ = serve(eng, prompts, spec=False)
    on, srv = serve(eng, prompts, spec=True)
    for i in range(len(prompts)):
        np.testing.assert_array_equal(off[i], on[i])
    st = srv.stats
    assert st["spec_steps"] > 0 and st["completed"] == len(prompts)
    # speculation paid off: more tokens out than per-slot verify steps
    assert st["spec_emitted"] > st["spec_slot_steps"]
    assert st["spec_accepted"] > 0


def test_spec_serving_parity_rotary_gqa_window(devices):
    """The verify program composes with rotary positions, grouped KV
    heads and sliding-window masking — same stack the decode kernel
    already covers."""
    cfg, _ = tiny()
    cfg = dataclasses.replace(cfg, rotary_dim=4, use_wpe=False,
                              n_kv_heads=2, attn_window=6)
    params = gpt.init_params(jax.random.PRNGKey(0), cfg)
    eng = InferenceEngine(config=cfg, params=params, dtype=jnp.float32)
    prompts = prompts_of((4, 10, 7), seed=7)
    off, _ = serve(eng, prompts, n_new=8, spec=False, num_slots=3,
                   num_blocks=30)
    on, _ = serve(eng, prompts, n_new=8, spec=True, num_slots=3,
                  num_blocks=30)
    for i in range(len(prompts)):
        np.testing.assert_array_equal(off[i], on[i])


def test_spec_serving_parity_pallas(devices, pallas_interpret):
    """Parity holds through the pallas verify kernel (interpret mode on
    CPU): the q_len>1 grid dimension scores the same chunk the gather
    reference does."""
    cfg, params = tiny()
    eng = InferenceEngine(config=cfg, params=params, dtype=jnp.float32)
    prompts = prompts_of((5, 11), seed=3)
    off, _ = serve(eng, prompts, spec=False, decode_impl="pallas")
    on, _ = serve(eng, prompts, spec=True, decode_impl="pallas")
    for i in range(len(prompts)):
        np.testing.assert_array_equal(off[i], on[i])


def test_spec_serving_parity_under_eviction(devices):
    """Eviction/requeue composes with speculation: a preempted slot
    re-prefills prompt+generated and resumes speculating, streams stay
    identical to spec-off under the same pool pressure."""
    cfg, params = tiny()
    eng = InferenceEngine(config=cfg, params=params, dtype=jnp.float32)
    prompts = prompts_of((5, 9, 12, 3))
    off, s0 = serve(eng, prompts, spec=False, num_blocks=7)
    on, s1 = serve(eng, prompts, spec=True, num_blocks=7)
    for i in range(len(prompts)):
        np.testing.assert_array_equal(off[i], on[i])
    assert s1.stats["evictions"] >= 1    # the pressure really preempted
    assert s1.stats["completed"] == len(prompts)


def test_spec_serving_parity_prefix_cache(devices):
    """Prefix-cache hits compose with speculation: shared prompt blocks
    map read-only into speculating slots and the verify chunk writes
    past them; streams match spec-off with the cache on."""
    cfg, params = tiny()
    eng = InferenceEngine(config=cfg, params=params, dtype=jnp.float32)
    sys_p = (1 + np.arange(12) % 126).astype(np.int32)
    tails = prompts_of((4, 7, 5), seed=11)
    prompts = [np.concatenate([sys_p, t]) for t in tails]
    off, _ = serve(eng, prompts, spec=False, prefix_cache=True)
    on, srv = serve(eng, prompts, spec=True, prefix_cache=True)
    for i in range(len(prompts)):
        np.testing.assert_array_equal(off[i], on[i])
    assert srv.stats["prefix_hits"] >= 1  # sharing really happened


# ---------------------------------------------------------------------------
# per-slot independence
# ---------------------------------------------------------------------------

class _HalfOracle:
    """Drafter with per-request quality: perfect continuations (read
    from precomputed reference streams) for requests it knows, garbage
    for the rest — so two slots in the SAME verify dispatch accept
    different prefix lengths."""

    def __init__(self, refs, vocab):
        self.refs = [np.asarray(r) for r in refs]
        self.vocab = vocab

    def propose(self, context, k):
        ctx = np.asarray(context)
        for ref in self.refs:
            if ctx.size <= ref.size and \
                    np.array_equal(ref[:ctx.size], ctx):
                cont = ref[ctx.size:ctx.size + k]
                out = np.full((k,), self.vocab - 1, np.int64)
                out[:cont.size] = cont
                return out.astype(np.int32)
        return np.full((k,), self.vocab - 1, np.int32)


def test_spec_per_slot_divergent_acceptance(devices):
    """Acceptance is per-slot, not batch-lockstep (the static
    generate_speculative takes the batch min): with an oracle drafter
    for request 0 and garbage for request 1, one verify step must
    accept >0 for slot A and 0 for slot B — and both streams still
    match spec-off."""
    cfg, params = tiny()
    eng = InferenceEngine(config=cfg, params=params, dtype=jnp.float32)
    prompts = prompts_of((6, 6), seed=5)
    off, _ = serve(eng, prompts, spec=False)
    oracle = _HalfOracle([np.concatenate([prompts[0], off[0][6:]])],
                         cfg.vocab_size)
    on, srv = serve(eng, prompts, spec=True, spec_draft=oracle,
                    telemetry=True)
    for i in range(2):
        np.testing.assert_array_equal(off[i], on[i])
    # tracer records: (ts, etype, rid, step, slot, data)
    accepted = [r[5]["accepted"]
                for r in srv.telemetry.tracer.records()
                if r[1] == "spec_verify"]
    assert accepted, "no spec_verify events traced"
    divergent = [a for a in accepted
                 if len(a) == 2 and max(a.values()) > 0
                 and min(a.values()) == 0]
    assert divergent, (
        f"no step accepted differently across slots: {accepted}")


# ---------------------------------------------------------------------------
# compile contract
# ---------------------------------------------------------------------------

def test_spec_compile_count_contract(devices):
    """With speculation on, the verify program REPLACES plain decode:
    steady state is prefill=1 + verify=1 compiled programs, decode=0,
    and a second workload (including eviction/requeue) compiles
    NOTHING."""
    from deepspeed_tpu.utils.compile_guard import CompileWatch, cache_size
    cfg, params = tiny()
    eng = InferenceEngine(config=cfg, params=params, dtype=jnp.float32)
    p1, p2 = prompts_of((10, 9), seed=9)

    def run_workload():
        srv = ServingEngine(eng, num_slots=2, block_size=4, num_blocks=7,
                            prefill_chunk=8, spec_decode=True)
        srv.cache.watermark = 0
        out = srv.run([ServeRequest(rid="a", prompt=p1, max_new_tokens=12),
                       ServeRequest(rid="b", prompt=p2, max_new_tokens=10)])
        return srv, out

    srv, warm_out = run_workload()
    assert srv.stats["evictions"] >= 1   # the workload really preempts
    # under DS_KV_QUANT=int8 / DS_LORA_SERVE=on the active set is the
    # int8 / adapter entry of the same callables; the
    # verify-replaces-decode count contract is identical in every mode
    pf, vf, dc = eng._prefill_slot, eng._verify_slots, eng._decode_slots
    n_prefill = cache_size(pf)
    n_verify = cache_size(vf)
    n_decode = cache_size(dc)
    if n_prefill is not None:
        assert (n_prefill, n_verify, n_decode) == (1, 1, 0), (
            f"spec steady state fragmented: prefill={n_prefill} "
            f"verify={n_verify} decode={n_decode} (expected 1+1+0: "
            f"verify replaces decode)")

    watch = CompileWatch(max_compiles=0, label="spec serving steady state")
    watch.wrap(pf)
    watch.wrap(vf)
    watch.wrap(dc)
    with watch:
        srv2, out = run_workload()
    assert srv2.stats["evictions"] >= 1
    for rid in ("a", "b"):
        np.testing.assert_array_equal(out[rid], warm_out[rid])


# ---------------------------------------------------------------------------
# chaos: degrade to plain decode, never to wrong output
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("site", ["engine.verify", "serving.spec_draft"])
def test_spec_chaos_degrades_to_plain_decode(devices, site):
    """An injected fault at either speculative site downgrades THAT
    step to the plain one-token path (spec_fallbacks counts it); the
    run still drains and streams stay bit-identical to the clean
    spec-off run."""
    cfg, params = tiny()
    eng = InferenceEngine(config=cfg, params=params, dtype=jnp.float32)
    prompts = prompts_of((5, 9, 12, 3))
    off, _ = serve(eng, prompts, spec=False)
    with faults.injected(faults.Fault(site, "device_error",
                                      step=1, count=3)):
        on, srv = serve(eng, prompts, spec=True)
    for i in range(len(prompts)):
        np.testing.assert_array_equal(off[i], on[i])
    assert srv.stats["spec_fallbacks"] >= 3
    # the degraded steps really ran the plain program
    assert srv.stats["decode_steps"] > srv.stats["spec_steps"]
    assert srv.stats["completed"] == len(prompts)


# ---------------------------------------------------------------------------
# telemetry (satellite: accept_rate / tokens_per_step observability)
# ---------------------------------------------------------------------------

def test_spec_telemetry_metrics_and_trace(devices):
    """With telemetry on, speculative steps feed the accept-rate and
    tokens-per-step histograms and trace one spec_verify event per
    dispatch with the per-slot accepted counts."""
    cfg, params = tiny()
    eng = InferenceEngine(config=cfg, params=params, dtype=jnp.float32)
    prompts = prompts_of((5, 9), seed=2)
    _, srv = serve(eng, prompts, spec=True, telemetry=True)
    st = srv.stats
    h_acc = srv.metrics.histogram("serving_spec_accept_rate")
    h_tps = srv.metrics.histogram("serving_spec_tokens_per_step")
    assert h_acc.count == st["spec_steps"] > 0
    assert h_tps.count == st["spec_steps"]
    # tokens/step mean > 1: speculation emitted multi-token steps
    assert h_tps.sum / h_tps.count > 1.0
    events = [r[5] for r in srv.telemetry.tracer.records()
              if r[1] == "spec_verify"]
    assert len(events) == st["spec_steps"]
    assert all("accepted" in d and "emitted" in d for d in events)
    assert sum(d["emitted"] for d in events) == st["spec_emitted"]
    # the exposition includes the new families
    prom = srv.telemetry.to_prometheus()
    assert "serving_spec_accept_rate_bucket" in prom
    assert "serving_spec_tokens_per_step_sum" in prom
