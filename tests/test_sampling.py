"""Per-request sampling subsystem tests (tentpole:
inference/sampling.py + the serving/spec wiring).

Layers:
  1. unit — SamplingParams validation, candidate-seed derivation, the
     fused ``sample_tokens`` greedy-lane bit-identity, the lax.top_k
     threshold's logits-equivalence with the old jnp.sort form, and the
     Philox position-uniform chain;
  2. serving — mixed greedy/sampled batches leave every temperature=0
     request bit-identical to plain greedy serving; same seed ->
     identical tokens across fresh engines, eviction/requeue and a
     router drain onto a survivor; distinct seeds diverge; stop
     sequences, logprobs and n>1 candidate expansion;
  3. contracts — the two-program steady state holds with zero
     recompiles across greedy<->sampled mixes (CompileWatch(0)), and
     the rejection-sampling spec verify is distribution-lossless
     (empirical marginal vs the exact fp64 target).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference import sampling
from deepspeed_tpu.inference.engine import InferenceEngine
from deepspeed_tpu.inference.router import ReplicaRouter
from deepspeed_tpu.inference.serving import ServeRequest, ServingEngine
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


def mk_srv(eng, **kw):
    defaults = dict(num_slots=2, block_size=4, num_blocks=24,
                    prefill_chunk=8, spec_decode=False)
    defaults.update(kw)
    return ServingEngine(eng, **defaults)


def run_solo(eng, prompt, max_new=8, srv_kw=None, **req_kw):
    srv = mk_srv(eng, **(srv_kw or {}))
    out = srv.run([ServeRequest(rid="r", prompt=prompt,
                                max_new_tokens=max_new, **req_kw)])
    return srv, out["r"]


# ---------------------------------------------------------------------------
# unit: params, seeds, fused sampler
# ---------------------------------------------------------------------------

def test_sampling_params_validation():
    sampling.SamplingParams().validate()           # greedy default is legal
    sampling.SamplingParams(temperature=0.7, top_k=40, top_p=0.9,
                            repetition_penalty=1.2).validate()
    for bad in (dict(temperature=-0.1), dict(top_k=-1), dict(top_p=0.0),
                dict(top_p=1.5), dict(repetition_penalty=0.0)):
        with pytest.raises(ValueError):
            sampling.SamplingParams(**bad).validate()
    # request fields win over engine defaults; None falls through
    req = ServeRequest(rid=0, prompt=np.zeros(1, np.int32),
                       temperature=0.5, seed=None)
    p = sampling.resolve_params(req, default_temperature=0.0,
                                default_seed=42)
    assert p.temperature == 0.5 and p.seed == 42 and p.sampled
    # malformed request knobs fail fast at resolve time
    req = ServeRequest(rid=0, prompt=np.zeros(1, np.int32), top_p=2.0)
    with pytest.raises(ValueError):
        sampling.resolve_params(req)


def test_candidate_seed_derivation():
    # candidate 0 IS the request seed (the original rid keeps its draw)
    assert sampling.candidate_seed(7, 0) == 7
    # derived seeds are mixed: adjacent seeds x adjacent indices stay
    # pairwise distinct (the naive seed+index scheme collides here)
    derived = {sampling.candidate_seed(s, i)
               for s in range(8) for i in range(4)}
    assert len(derived) == 8 * 4
    # deterministic: same (seed, index) -> same derived seed
    assert sampling.candidate_seed(7, 3) == sampling.candidate_seed(7, 3)


def test_sample_tokens_greedy_lane_bit_identity():
    """The core tentpole contract at unit level: in a mixed batch, the
    temperature=0 lanes return exactly argmax(logits) with softmax
    logprobs — the sampled lanes' machinery cannot perturb them — and
    an all-greedy batch returns the same thing."""
    rng = np.random.default_rng(3)
    B, V = 4, 128
    logits = jnp.asarray(rng.normal(size=(B, V)) * 3, jnp.float32)
    st = sampling.SlotSamplerState(B, V)
    st.admit(1, sampling.SamplingParams(temperature=0.8, top_k=20,
                                        top_p=0.9, seed=11,
                                        repetition_penalty=1.3),
             tokens=[5, 9])
    st.admit(3, sampling.SamplingParams(temperature=1.4, seed=12))
    keys, pos, temps, tks, tps, pens, seen = st.lanes([0, 4, 0, 2])
    toks, lps = sampling.sample_tokens(logits, jnp.asarray(keys), pos,
                                       temps, tks, tps, pens, seen)
    toks, lps = np.asarray(toks), np.asarray(lps)
    ref = np.argmax(np.asarray(logits), axis=-1)
    ref_lp = np.asarray(jax.nn.log_softmax(logits, axis=-1))
    assert toks[0] == ref[0] and toks[2] == ref[2]
    assert lps[0] == ref_lp[0, ref[0]] and lps[2] == ref_lp[2, ref[2]]
    # sampled lanes draw from the truncated distribution (still valid
    # token ids; logprob of the drawn token under the masked softmax)
    assert 0 <= toks[1] < V and 0 <= toks[3] < V
    assert np.all(lps <= 0.0)
    # all-greedy state: every lane is argmax, bitwise
    g = sampling.greedy_state(B, V)
    gt, glp = sampling.sample_tokens(logits, jnp.asarray(g[0]), *g[1:])
    np.testing.assert_array_equal(np.asarray(gt), ref)
    np.testing.assert_array_equal(np.asarray(glp),
                                  ref_lp[np.arange(B), ref])


def test_sample_tokens_seed_chain_reproducible():
    """Same (seed, position) -> same draw; the chain is a pure function
    of data, so replaying a position replays the token."""
    rng = np.random.default_rng(4)
    row = rng.normal(size=(1, 128)) * 2
    logits = jnp.asarray(np.tile(row, (2, 1)), jnp.float32)
    st = sampling.SlotSamplerState(2, 128)
    for slot, seed in ((0, 5), (1, 5)):
        st.admit(slot, sampling.SamplingParams(temperature=1.0, seed=seed))
    keys, pos, temps, tks, tps, pens, seen = st.lanes([3, 3])
    t1, _ = sampling.sample_tokens(logits, jnp.asarray(keys), pos, temps,
                                   tks, tps, pens, seen)
    t1 = np.asarray(t1)
    assert t1[0] == t1[1]        # same seed, same position, same logits
    # a different position advances the chain (draws are independent;
    # with 128 tokens at temperature 1 a collision across 4 positions
    # on BOTH slots at once is effectively impossible)
    draws = []
    for p in (4, 5, 6, 7):
        keys, pos, temps, tks, tps, pens, seen = st.lanes([p, p])
        t, _ = sampling.sample_tokens(logits, jnp.asarray(keys), pos,
                                      temps, tks, tps, pens, seen)
        draws.append(np.asarray(t))
    assert any(not np.array_equal(d, t1) for d in draws)


def test_topk_threshold_lax_topk_matches_sort():
    """Satellite 2's logits-equivalence pin: the ``jax.lax.top_k``
    k-th-largest threshold in ``engine._sample`` masks exactly the
    same logits as the historical full ``jnp.sort`` form, including
    k > vocab clamping and tied values at the boundary."""
    rng = np.random.default_rng(9)
    z = rng.normal(size=(3, 64)).astype(np.float32)
    z[0, :10] = z[0, 10]              # ties straddling the threshold
    zj = jnp.asarray(z)
    for k in (1, 4, 10, 63, 64, 500):
        k_eff = min(k, z.shape[-1])
        kth_sort = jnp.sort(zj, axis=-1)[:, -k_eff][:, None]
        kth_topk = jax.lax.top_k(zj, k_eff)[0][:, -1][:, None]
        np.testing.assert_array_equal(np.asarray(kth_sort),
                                      np.asarray(kth_topk))
        np.testing.assert_array_equal(
            np.asarray(jnp.where(zj < kth_sort, sampling.NEG_INF, zj)),
            np.asarray(jnp.where(zj < kth_topk, sampling.NEG_INF, zj)))


def test_engine_sample_topk_draws_from_truncated_support(eng):
    """engine._sample with top_k only ever emits tokens inside the
    true top-k set (the lax.top_k mask really truncates)."""
    rng = np.random.default_rng(2)
    logits = jnp.asarray(rng.normal(size=(2, 1, 128)) * 2, jnp.float32)
    top = np.argsort(-np.asarray(logits)[:, -1], axis=-1)[:, :8]
    for s in range(20):
        toks = np.asarray(eng._sample(logits, jax.random.PRNGKey(s),
                                      temperature=1.0, top_k=8))
        for b in range(2):
            assert toks[b] in top[b]


def test_position_uniforms_counter_based():
    """The verify chain's uniforms are keyed by (seed, position) alone:
    no sequential state, so replaying a position after a drain/evict
    replays the identical decision — and chunk boundaries are
    invisible by construction."""
    a = sampling.position_uniforms(11, 4)
    np.testing.assert_array_equal(a, sampling.position_uniforms(11, 4))
    assert not np.array_equal(a, sampling.position_uniforms(11, 5))
    assert not np.array_equal(a, sampling.position_uniforms(12, 4))
    assert np.all((0.0 <= a) & (a < 1.0))


def test_spec_verify_marginal_is_lossless():
    """Statistical losslessness of the rejection-sampling verify
    (Leviathan/Chen): over many seeds, the marginal of the FIRST
    emitted token equals the target distribution p exactly — whether
    the deterministic draft proposed a likely or an unlikely token."""
    rng = np.random.default_rng(0)
    V, N = 8, 4000
    p = rng.dirichlet(np.ones(V), size=3)          # 3 verify rows
    for prop_tok in (int(np.argmax(p[0])), int(np.argmin(p[0]))):
        counts = np.zeros(V)
        for seed in range(N):
            toks, lps, acc = sampling.spec_verify_tokens(
                p, [prop_tok, 0], seed, pos0=0)
            counts[toks[0]] += 1
            # invariants: accepted prefix + exactly one extra token
            assert len(toks) == acc + 1 and len(lps) == len(toks)
        tv = 0.5 * np.abs(counts / N - p[0]).sum()
        assert tv < 0.03, f"first-token TV {tv} vs target (prop={prop_tok})"


def test_spec_verify_determinism_and_acceptance():
    """Same (seed, pos0) -> identical verify outcome; a proposal with
    p(x)=1 is always accepted; p(x)=0 is always rejected and the
    correction comes from the residual (x excluded)."""
    V = 6
    sure = np.zeros(V)
    sure[2] = 1.0
    rows = np.stack([sure, np.full(V, 1 / V)])
    toks, _, acc = sampling.spec_verify_tokens(rows, [2], 7, 0)
    assert acc == 1 and toks[0] == 2
    zero = np.full(V, 1 / (V - 1))
    zero[4] = 0.0
    rows = np.stack([zero, np.full(V, 1 / V)])
    for seed in range(50):
        toks, _, acc = sampling.spec_verify_tokens(rows, [4], seed, 0)
        assert acc == 0 and toks[0] != 4
    a = sampling.spec_verify_tokens(rows, [4], 3, 5)
    assert a == sampling.spec_verify_tokens(rows, [4], 3, 5)


# ---------------------------------------------------------------------------
# serving: greedy bit-identity, seeded reproducibility, knobs
# ---------------------------------------------------------------------------

def test_serving_mixed_batch_keeps_greedy_bit_identical(eng):
    """A greedy request decoded IN THE SAME BATCH as sampled requests
    produces exactly the plain-greedy serving/static output — the
    tentpole's acceptance bit-identity, at the scheduler level."""
    prompts = prompts_of((5, 9, 7), seed=21)
    ref = _solo_refs(eng, [prompts[0]], 8)[0]
    srv = mk_srv(eng, num_slots=3)
    out = srv.run([
        ServeRequest(rid="g", prompt=prompts[0], max_new_tokens=8),
        ServeRequest(rid="s1", prompt=prompts[1], max_new_tokens=8,
                     temperature=0.9, seed=3),
        ServeRequest(rid="s2", prompt=prompts[2], max_new_tokens=8,
                     temperature=1.3, top_k=16, top_p=0.95, seed=4),
    ])
    np.testing.assert_array_equal(out["g"], ref)
    assert srv.stats["peak_occupancy"] > 1       # they really cohabited
    assert srv.stats["sampled_tokens"] > 0
    # temperature=0 makes every other knob inert: same greedy bits even
    # with top_k/top_p/penalty/seed set
    _, out2 = run_solo(eng, prompts[0], max_new=8, temperature=0.0,
                       top_k=7, top_p=0.5, seed=99,
                       repetition_penalty=1.5)
    np.testing.assert_array_equal(out2, ref)


def test_serving_same_seed_reproducible_distinct_seeds_diverge(eng):
    p, = prompts_of((8,), seed=23)
    _, a = run_solo(eng, p, max_new=10, temperature=1.0, seed=17)
    _, b = run_solo(eng, p, max_new=10, temperature=1.0, seed=17)
    np.testing.assert_array_equal(a, b)          # bit-stable replay
    outs = [run_solo(eng, p, max_new=10, temperature=1.0, seed=s)[1]
            for s in (18, 19, 20)]
    assert any(not np.array_equal(a, o) for o in outs)


def test_serving_sampled_eviction_requeue_parity(eng):
    """The key-chain survives preemption: a sampled request evicted and
    requeued (recompute-on-resume) finishes with exactly the tokens an
    undisturbed roomy-pool run produces. The per-token key is a pure
    function of (seed, tokens generated), so the resumed chain continues
    where the evicted one stopped."""
    p1, p2 = prompts_of((10, 9), seed=9)
    kw = dict(temperature=0.9, top_k=32)
    _, ref1 = run_solo(eng, p1, max_new=12, seed=5, **kw)
    _, ref2 = run_solo(eng, p2, max_new=10, seed=6, **kw)
    srv = mk_srv(eng, num_blocks=7)              # tight pool: forces evict
    srv.cache.watermark = 0
    out = srv.run([
        ServeRequest(rid="a", prompt=p1, max_new_tokens=12, seed=5, **kw),
        ServeRequest(rid="b", prompt=p2, max_new_tokens=10, seed=6, **kw)])
    assert srv.stats["evictions"] >= 1
    np.testing.assert_array_equal(out["a"], ref1)
    np.testing.assert_array_equal(out["b"], ref2)


def test_router_drain_sampled_parity(eng):
    """A replica crash mid-decode drains sampled requests onto
    survivors token-identically: the snapshot carries the sampling
    params, and the key chain replays on the survivor."""
    prompts = prompts_of((5, 8, 11, 6), seed=29)
    refs = [run_solo(eng, p, max_new=8, temperature=0.8, top_p=0.9,
                     seed=40 + i)[1]
            for i, p in enumerate(prompts)]
    # horizon pinned: the step-7 crash is calibrated to one-token
    # steps (the N=8 drain-parity twin lives in test_horizon.py)
    inj = FaultInjector([Fault("router.step", "crash", step=7)], seed=0)
    fleet = [mk_srv(eng, faults=inj, decode_horizon=1) for _ in range(3)]
    router = ReplicaRouter(fleet, faults=inj)
    out = router.run([ServeRequest(rid=i, prompt=p, max_new_tokens=8,
                                   temperature=0.8, top_p=0.9, seed=40 + i)
                      for i, p in enumerate(prompts)])
    assert inj.fired and router.stats["drained_requests"] >= 1
    for i, ref in enumerate(refs):
        np.testing.assert_array_equal(
            out[i], ref, err_msg=f"sampled request {i} lost drain parity")


def test_serving_stop_sequences(eng):
    """Generation finishes as soon as ``out`` ends with a stop
    sequence; the matched tokens stay in the output."""
    p, = prompts_of((6,), seed=31)
    _, ref = run_solo(eng, p, max_new=10)
    gen = [int(t) for t in ref[len(p):]]
    stop = gen[2:4]                      # a pair generate() really emits
    # expected cut: the FIRST generated position whose suffix matches
    # (repeated tokens can match before the pair's own position)
    cut = next(j + 1 for j in range(1, len(gen))
               if gen[j - 1:j + 1] == stop)
    srv, out = run_solo(eng, p, max_new=10, stop=[stop])
    np.testing.assert_array_equal(out, ref[:len(p) + cut])
    assert srv.stats["stop_hits"] == 1
    # a never-emitted stop sequence changes nothing
    srv2, out2 = run_solo(eng, p, max_new=10, stop=[[999999 % 128, 0, 0]])
    if not np.array_equal(out2, ref):           # only if it fired
        assert srv2.stats["stop_hits"] == 1
    else:
        assert srv2.stats["stop_hits"] == 0


def test_serving_logprobs_and_candidates(eng):
    """logprobs=True records one log-probability per emitted token;
    n>1 expands into independent candidates whose seeds derive from
    the request seed (candidate 0 IS the request)."""
    p, = prompts_of((7,), seed=33)
    srv = mk_srv(eng, num_slots=3)
    out = srv.run([ServeRequest(rid="c", prompt=p, max_new_tokens=6,
                                temperature=1.2, seed=50, n=3,
                                logprobs=True)])
    assert set(out) == {"c", "c#1", "c#2"}
    done = {r.rid: r for r in srv.finished}
    for rid in out:
        r = done[rid]
        assert len(r.out_logprobs) == len(r.out)
        assert all(lp <= 0.0 for lp in r.out_logprobs)
    # candidate 0 replays the plain n=1 run with the same seed
    _, solo = run_solo(eng, p, max_new=6, temperature=1.2, seed=50)
    np.testing.assert_array_equal(out["c"], solo)
    # high-temperature candidates diverge from one another
    assert (not np.array_equal(out["c"], out["c#1"])
            or not np.array_equal(out["c"], out["c#2"]))
    with pytest.raises(ValueError):
        mk_srv(eng).submit(ServeRequest(rid="bad", prompt=p, n=0))


def test_snapshot_roundtrip_carries_sampling_fields(eng):
    """pending_snapshot/from_snapshot round-trip the whole sampling
    surface — the params ARE the key-chain state (plus out), nothing
    device-side needs saving."""
    p, = prompts_of((6,), seed=35)
    req = ServeRequest(rid="s", prompt=p, max_new_tokens=9,
                       temperature=0.7, top_k=12, top_p=0.8, seed=77,
                       repetition_penalty=1.1, stop=[[3, 4]],
                       logprobs=True, n=1)
    # horizon pinned: "4 steps = prefill + a few decode tokens, still
    # mid-flight" assumes one token per step
    srv = mk_srv(eng, decode_horizon=1)
    srv.submit(req)
    for _ in range(4):                   # prefill + a few decode steps
        srv.step()
    snap = srv.pending_snapshot(release=True)
    assert len(snap) == 1
    back = ServeRequest.from_snapshot(snap[0])
    assert (back.temperature, back.top_k, back.top_p, back.seed,
            back.repetition_penalty) == (0.7, 12, 0.8, 77, 1.1)
    assert back.stop == [[3, 4]] and back.logprobs and back.n == 1
    assert back.out == req.out and back.out_logprobs == req.out_logprobs


# ---------------------------------------------------------------------------
# contracts: compile stability across greedy<->sampled mixes
# ---------------------------------------------------------------------------

def test_sampling_compile_contract_mixed_lanes(devices):
    """Sampling knobs are DATA: after one warmup, greedy-only, sampled-
    only and mixed workloads — including eviction/requeue — all run
    through the SAME two compiled programs with ZERO recompiles
    (CompileWatch(0)). This is the acceptance pin for 'params as
    slot-indexed arrays, not jit statics'."""
    from deepspeed_tpu.utils.compile_guard import CompileWatch, cache_size
    cfg, params = tiny()
    eng = InferenceEngine(config=cfg, params=params, dtype=jnp.float32)
    p1, p2 = prompts_of((10, 9), seed=9)

    def workload(kw1, kw2):
        # horizon pinned: this test wraps the N=1 _decode_slots program
        # (the _decode_horizon family's contract is test_horizon.py's)
        srv = ServingEngine(eng, num_slots=2, block_size=4, num_blocks=7,
                            prefill_chunk=8, spec_decode=False,
                            decode_horizon=1)
        srv.cache.watermark = 0          # tight pool: evict + requeue
        out = srv.run([
            ServeRequest(rid="a", prompt=p1, max_new_tokens=12, **kw1),
            ServeRequest(rid="b", prompt=p2, max_new_tokens=10, **kw2)])
        return srv, out

    sampled = dict(temperature=0.9, top_k=20, top_p=0.9, seed=3)
    srv, _ = workload(sampled, {})               # warmup: mixed batch
    assert srv.stats["evictions"] >= 1
    pf, dc = eng._prefill_slot, eng._decode_slots
    n_prefill, n_decode = cache_size(pf), cache_size(dc)
    if n_prefill is not None:
        assert (n_prefill, n_decode) == (1, 1), (
            f"sampled serving fragmented the steady state: "
            f"prefill={n_prefill} decode={n_decode} (expected 1+1)")

    watch = CompileWatch(max_compiles=0, label="sampled serving mixes")
    watch.wrap(pf)
    watch.wrap(dc)
    with watch:
        workload({}, {})                         # all greedy
        workload(sampled, sampled)               # all sampled
        workload({}, dict(temperature=1.4, repetition_penalty=1.2,
                          seed=8))               # mixed, new knob values
    if n_prefill is not None:
        assert cache_size(pf) == 1 and cache_size(dc) == 1


def test_spec_sampled_compile_contract(devices):
    """Spec-on twin: sampled requests keep the prefill=1 + verify=1 /
    decode=0 steady state with zero recompiles — the rejection verify
    is host math over logits the verify program already returns."""
    from deepspeed_tpu.utils.compile_guard import CompileWatch, cache_size
    cfg, params = tiny()
    eng = InferenceEngine(config=cfg, params=params, dtype=jnp.float32)
    p1, p2 = prompts_of((10, 9), seed=9)

    def workload(kw1, kw2):
        srv = ServingEngine(eng, num_slots=2, block_size=4, num_blocks=24,
                            prefill_chunk=8, spec_decode=True, spec_k=3)
        out = srv.run([
            ServeRequest(rid="a", prompt=p1, max_new_tokens=10, **kw1),
            ServeRequest(rid="b", prompt=p2, max_new_tokens=10, **kw2)])
        return srv, out

    sampled = dict(temperature=0.8, seed=5)
    srv, _ = workload(sampled, {})               # warmup
    assert srv.stats["spec_steps"] > 0
    pf, vf = eng._prefill_slot, eng._verify_slots
    watch = CompileWatch(max_compiles=0, label="sampled spec serving")
    watch.wrap(pf)
    watch.wrap(vf)
    with watch:
        workload({}, sampled)
        workload(sampled, sampled)
    if cache_size(pf) is not None:
        assert cache_size(pf) == 1 and cache_size(vf) == 1


# ---------------------------------------------------------------------------
# spec-decode x sampling: end-to-end losslessness (slow)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_spec_sampled_e2e_distribution_matches_plain(eng):
    """End-to-end statistical losslessness: with top_k=4 shrinking the
    support, the empirical distribution of the first DECODED token
    (the first spec-verified position) over many seeds matches between
    plain sampled serving and sampled spec-decode serving."""
    p, = prompts_of((6,), seed=41)
    kw = dict(temperature=1.0, top_k=4)
    N = 400
    freq = {False: {}, True: {}}
    for spec in (False, True):
        for s in range(N):
            srv_kw = (dict(spec_decode=True, spec_k=3) if spec
                      else dict(spec_decode=False))
            _, out = run_solo(eng, p, max_new=3, srv_kw=srv_kw,
                              seed=s, **kw)
            t = int(out[len(p) + 1])
            freq[spec][t] = freq[spec].get(t, 0) + 1
    support = set(freq[False]) | set(freq[True])
    # the second token mixes <=4-wide conditionals over the <=4
    # possible first tokens (which pair up by seed across the paths)
    assert len(support) <= 16           # truncation really bit
    tv = 0.5 * sum(abs(freq[False].get(t, 0) - freq[True].get(t, 0))
                   for t in support) / N
    assert tv < 0.16, f"spec vs plain sampled first-token TV {tv}"
