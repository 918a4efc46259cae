"""Fused multi-step decode tests (tentpole: ``DS_DECODE_HORIZON`` —
N decode iterations in ONE compiled ``lax.scan`` program per scheduler
step, docs/MULTISTEP.md).

The contract under test is bit-parity: a horizon only changes how many
host round-trips the same tokens take, never the tokens. Layers:

  1. knob — ``resolve_decode_horizon`` validation, env pickup, ctor
     override;
  2. parity — greedy AND sampled streams bit-equal to the N=1 serving
     run at N ∈ {2, 4, 8}, including mid-horizon stop hits (modeled and
     unmodeled), eviction/requeue on a tight pool, deadline timeouts
     (token-tick exact) and a router drain onto a survivor replica;
  3. composition — kv-quant / LoRA twins and the spec-decode precedence
     rule;
  4. contracts — zero steady-state recompiles (CompileWatch(0), one
     cached ``_decode_horizon`` entry per N) and the ``serving.horizon``
     chaos degrade to plain N=1 decode (never a wrong or missing
     token).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.engine import InferenceEngine
from deepspeed_tpu.inference.router import ReplicaRouter
from deepspeed_tpu.inference.serving import ServeRequest, ServingEngine
from deepspeed_tpu.models import gpt
from deepspeed_tpu.utils import faults
from deepspeed_tpu.utils.env import resolve_decode_horizon
from deepspeed_tpu.utils.faults import Fault, FaultInjector

pytestmark = pytest.mark.usefixtures("devices")

HORIZONS = (2, 4, 8)


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


def mk_srv(eng, **kw):
    defaults = dict(num_slots=2, block_size=4, num_blocks=24,
                    prefill_chunk=8, spec_decode=False)
    defaults.update(kw)
    return ServingEngine(eng, **defaults)


def greedy_reqs(prompts, max_new=10):
    return [ServeRequest(rid=i, prompt=p, max_new_tokens=max_new)
            for i, p in enumerate(prompts)]


def sampled_reqs(prompts, max_new=10):
    """A mixed batch: two sampled lanes with different knob sets, one
    greedy lane, one repetition-penalized lane with logprobs."""
    a, b, c, d = prompts
    return [
        ServeRequest(rid="a", prompt=a, max_new_tokens=max_new,
                     temperature=0.9, top_k=32, seed=5),
        ServeRequest(rid="b", prompt=b, max_new_tokens=max_new),
        ServeRequest(rid="c", prompt=c, max_new_tokens=max_new,
                     temperature=0.7, top_p=0.9, seed=6),
        ServeRequest(rid="d", prompt=d, max_new_tokens=max_new,
                     temperature=0.8, repetition_penalty=1.2, seed=7,
                     logprobs=True),
    ]


# ---------------------------------------------------------------------------
# knob: validation, env pickup, ctor override
# ---------------------------------------------------------------------------

def test_resolve_decode_horizon_validation():
    assert resolve_decode_horizon(1) == 1
    assert resolve_decode_horizon(8) == 8
    assert resolve_decode_horizon(32) == 32          # the cap itself
    for bad in (0, -1, 33, 1000):
        with pytest.raises(ValueError, match="DS_DECODE_HORIZON"):
            resolve_decode_horizon(bad)


def test_horizon_env_flag_and_ctor_override(eng, monkeypatch):
    monkeypatch.setenv("DS_DECODE_HORIZON", "4")
    assert mk_srv(eng).decode_horizon == 4           # env pickup
    assert mk_srv(eng, decode_horizon=2).decode_horizon == 2  # ctor wins
    monkeypatch.setenv("DS_DECODE_HORIZON", "0")
    with pytest.raises(ValueError, match="DS_DECODE_HORIZON"):
        mk_srv(eng)
    monkeypatch.delenv("DS_DECODE_HORIZON")
    with pytest.raises(ValueError, match="DS_DECODE_HORIZON"):
        mk_srv(eng, decode_horizon=33)


# ---------------------------------------------------------------------------
# parity: greedy and sampled streams bit-equal to the N=1 run
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def greedy_ref(eng):
    """The N=1 serving run IS the bit-reference the horizon must hit."""
    prompts = prompts_of((5, 9, 12, 3))
    srv = mk_srv(eng, decode_horizon=1)
    out = srv.run(greedy_reqs(prompts))
    return prompts, out, srv.stats["decode_steps"]


# tier-1 runs ``-m 'not slow'`` under a hard wall-clock budget
# (ROADMAP.md); the heavier horizon workloads carry the slow mark and
# ride gate.sh, whose full and chaos legs run this file unfiltered.  A
# sub-second parity core (sampled parity, mid-horizon stops, deadline
# partials, drain, fault degrade, knob contracts) stays in tier-1.
@pytest.mark.slow
@pytest.mark.parametrize("n", HORIZONS)
def test_horizon_greedy_parity(eng, greedy_ref, n):
    prompts, ref, ref_steps = greedy_ref
    srv = mk_srv(eng, decode_horizon=n)
    out = srv.run(greedy_reqs(prompts))
    for i in range(len(prompts)):
        np.testing.assert_array_equal(
            out[i], ref[i], err_msg=f"greedy request {i} diverged at N={n}")
    assert srv.stats["completed"] == len(prompts)
    # the gate's chaos leg reruns this test with ambient serving.horizon
    # faults injected — parity must hold regardless, but the
    # no-fallbacks claim only applies to a clean run
    if not faults.active().faults:
        assert srv.stats["horizon_fallbacks"] == 0
        # the fusion really happened: strictly fewer decode dispatches
        # than the one-token-per-step reference needed for the same
        # tokens
        assert srv.stats["decode_steps"] < ref_steps


@pytest.fixture(scope="module")
def sampled_ref(eng):
    prompts = prompts_of((6, 10, 8, 4), seed=17)
    srv = mk_srv(eng, decode_horizon=1)
    out = srv.run(sampled_reqs(prompts))
    lps = {r.rid: list(r.out_logprobs) for r in srv.finished}
    return prompts, out, lps


@pytest.mark.parametrize("n", HORIZONS)
def test_horizon_sampled_parity(eng, sampled_ref, n):
    """Mixed greedy/sampled batches stay bit-identical: the in-program
    sampler folds the same ``fold_in(seed, len(out) + i)`` key the N=1
    loop would at every emission."""
    prompts, ref, ref_lps = sampled_ref
    srv = mk_srv(eng, decode_horizon=n)
    out = srv.run(sampled_reqs(prompts))
    for rid in ("a", "b", "c", "d"):
        np.testing.assert_array_equal(
            out[rid], ref[rid],
            err_msg=f"sampled request {rid} diverged at N={n}")
    lps = {r.rid: list(r.out_logprobs) for r in srv.finished}
    np.testing.assert_allclose(lps["d"], ref_lps["d"], rtol=0, atol=1e-6)
    assert srv.stats["sampled_tokens"] > 0
    if not faults.active().faults:       # see test_horizon_greedy_parity
        assert srv.stats["horizon_fallbacks"] == 0


def test_horizon_mid_stop_parity(eng):
    """A stop sequence hit mid-horizon cuts the stream exactly where
    the N=1 loop would — both when the stop is MODELED in-program
    (lane freezes early) and when it is unmodeled surplus (the lane
    free-runs and the authoritative host check truncates)."""
    p, = prompts_of((6,), seed=31)
    srv1 = mk_srv(eng, decode_horizon=1)
    ref = srv1.run([ServeRequest(rid="r", prompt=p, max_new_tokens=10)])["r"]
    gen = [int(t) for t in ref[len(p):]]
    stop = gen[2:4]                      # a pair the run really emits
    cut = next(j + 1 for j in range(1, len(gen))
               if gen[j - 1:j + 1] == stop)
    expect = ref[:len(p) + cut]

    r1 = mk_srv(eng, decode_horizon=1).run(
        [ServeRequest(rid="r", prompt=p, max_new_tokens=10, stop=[stop])])
    np.testing.assert_array_equal(r1["r"], expect)

    # modeled: the single stop ships into the program
    srv8 = mk_srv(eng, decode_horizon=8)
    out = srv8.run([ServeRequest(rid="r", prompt=p, max_new_tokens=10,
                                 stop=[stop])])
    np.testing.assert_array_equal(out["r"], expect)
    assert srv8.stats["stop_hits"] == 1

    # unmodeled: the real stop rides 5th behind four decoys (the
    # program models at most 4) — the host check must still cut the
    # identical stream
    decoys = [[127, 126], [125, 124], [123, 122], [121, 120]]
    srv8u = mk_srv(eng, decode_horizon=8)
    outu = srv8u.run([ServeRequest(rid="r", prompt=p, max_new_tokens=10,
                                   stop=decoys + [stop])])
    np.testing.assert_array_equal(outu["r"], expect)
    assert srv8u.stats["stop_hits"] == 1


@pytest.mark.slow
def test_horizon_eviction_requeue_parity(eng):
    """A tight pool forces evict + requeue mid-run: the horizon's
    opportunistic capacity grants never change WHAT is evicted or the
    tokens the requeued request replays to."""
    p1, p2 = prompts_of((10, 9), seed=9)

    def run(n):
        srv = mk_srv(eng, num_blocks=7, decode_horizon=n)
        srv.cache.watermark = 0
        out = srv.run([ServeRequest(rid="a", prompt=p1, max_new_tokens=12),
                       ServeRequest(rid="b", prompt=p2, max_new_tokens=10)])
        return srv, out

    srv1, ref = run(1)
    assert srv1.stats["evictions"] >= 1
    for n in HORIZONS:
        srv, out = run(n)
        assert srv.stats["evictions"] >= 1, f"N={n} workload lost its evict"
        for rid in ("a", "b"):
            np.testing.assert_array_equal(
                out[rid], ref[rid],
                err_msg=f"request {rid} diverged at N={n} under eviction")


def test_horizon_deadline_timeout_parity(eng):
    """Deadlines keep their token-count meaning: the in-horizon budget
    cap stamps no token past the deadline, so the partial output at
    timeout is IDENTICAL to the N=1 run's — same tokens, same count."""
    p1, p2 = prompts_of((6, 7), seed=5)

    def run(n):
        srv = mk_srv(eng, decode_horizon=n)
        out = srv.run([ServeRequest(rid="t", prompt=p1, max_new_tokens=30,
                                    deadline=4.0),
                       ServeRequest(rid="ok", prompt=p2, max_new_tokens=8)])
        done = {r.rid: r for r in srv.finished}
        return srv, out, done

    _, ref, refd = run(1)
    assert refd["t"].state == "timeout" and 0 < len(refd["t"].out) < 30
    for n in HORIZONS:
        srv, out, done = run(n)
        assert done["t"].state == "timeout", f"N={n}"
        np.testing.assert_array_equal(out["t"], ref["t"],
                                      err_msg=f"timeout partial at N={n}")
        np.testing.assert_array_equal(out["ok"], ref["ok"])
        assert srv.stats["timeouts"] == 1
        assert not srv.cache.active.any()


def test_horizon_router_drain_partial_parity(eng):
    """A replica crash mid-decode at N=8 drains requests onto survivors
    token-identically: the snapshot carries however far into its
    horizons the dead replica got (partial horizons are just shorter
    ``out`` lists), and the survivor replays the same streams."""
    prompts = prompts_of((5, 8, 11, 6), seed=29)
    refs = []
    for i, p in enumerate(prompts):
        srv = mk_srv(eng, decode_horizon=1)
        refs.append(srv.run([ServeRequest(
            rid=i, prompt=p, max_new_tokens=8, temperature=0.8,
            top_p=0.9, seed=40 + i)])[i])
    # crash early: at N=8 the whole run takes only a handful of router
    # steps (that IS the feature), so step=7 would never be visited
    inj = FaultInjector([Fault("router.step", "crash", step=2)], seed=0)
    fleet = [mk_srv(eng, decode_horizon=8, faults=inj) for _ in range(3)]
    router = ReplicaRouter(fleet, faults=inj)
    out = router.run([ServeRequest(rid=i, prompt=p, max_new_tokens=8,
                                   temperature=0.8, top_p=0.9, seed=40 + i)
                      for i, p in enumerate(prompts)])
    assert inj.fired and router.stats["drained_requests"] >= 1
    for i, ref in enumerate(refs):
        np.testing.assert_array_equal(
            out[i], ref, err_msg=f"request {i} lost drain parity at N=8")


@pytest.mark.slow
def test_horizon_load_gen_stamps_exact(eng):
    """The load driver's latency records stay EXACT at N>1: tokens
    stamp at ``now + i * tick`` inside a horizon and the driver
    advances its clock by ``last_step_span``, so a no-queueing burst
    produces bit-identical per-request ttft/finished chains while the
    run takes strictly fewer scheduler steps. Prompts are capped to one
    prefill chunk: a slot still MID-PREFILL while others run a fused
    horizon only rejoins at the next horizon boundary — scheduling
    granularity the horizon coarsens by design (docs/MULTISTEP.md),
    not a stamp error."""
    from tools.load_gen import drive, make_requests
    entries = make_requests(seed=3, mix="chat", n=4, vocab_size=128,
                            max_prompt_len=8)

    def go(n):
        srv = mk_srv(eng, num_slots=4, num_blocks=64, decode_horizon=n)
        return drive(srv, entries, mode="closed", concurrency=4)

    r1, r8 = go(1), go(8)
    assert r8["steps"] < r1["steps"]     # the fusion really happened
    assert r1["per_request"] == r8["per_request"]
    for k in ("ttft_p50", "ttft_p95", "ttft_p99"):
        assert r1[k] == r8[k]


# ---------------------------------------------------------------------------
# composition: kv-quant / LoRA variants, spec precedence
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_horizon_kv_quant_parity(eng):
    """The int8 pool rides ``_decode_horizon``'s int8 entry: the horizon
    must be bit-identical to the N=1 run ON THE SAME quantized layout
    (int8-vs-fp tolerance is test_kv_quant_serving's business)."""
    prompts = prompts_of((5, 9, 12, 3))
    ref = mk_srv(eng, kv_quant="int8", decode_horizon=1).run(
        greedy_reqs(prompts, max_new=8))
    srv = mk_srv(eng, kv_quant="int8", decode_horizon=8)
    out = srv.run(greedy_reqs(prompts, max_new=8))
    for i in range(len(prompts)):
        np.testing.assert_array_equal(out[i], ref[i])
    from deepspeed_tpu.utils.compile_guard import cache_size
    n_q = cache_size(eng._decode_horizon)
    if n_q is not None:                  # the horizon really served
        assert n_q >= 1


@pytest.mark.slow
def test_horizon_lora_parity(eng):
    """Heterogeneous base+adapter batches decode through the
    ``_decode_horizon`` adapter entry bit-identically to N=1."""
    from deepspeed_tpu.runtime.lora import add_lora, adapter_state_dict
    cfg, params = tiny()
    e = InferenceEngine(config=cfg, params=params, dtype=jnp.float32)
    adapter = adapter_state_dict(
        add_lora(params, rng=jax.random.PRNGKey(1), rank=4, alpha=8.0))
    p1, p2 = prompts_of((7, 9), seed=11)

    def run(n):
        srv = mk_srv(e, decode_horizon=n, lora_serve=True,
                     lora_pool_blocks=2, lora_max_rank=4, lora_rank_block=4)
        srv.register_adapter("t1", adapter)
        return srv.run([
            ServeRequest(rid="ad", prompt=p1, max_new_tokens=8,
                         adapter_id="t1"),
            ServeRequest(rid="base", prompt=p2, max_new_tokens=8)])

    ref = run(1)
    out = run(8)
    for rid in ("ad", "base"):
        np.testing.assert_array_equal(out[rid], ref[rid])


@pytest.mark.slow
def test_horizon_spec_precedence(eng):
    """spec_decode already emits multiple tokens per dispatch, so it
    takes precedence: with both knobs on, the spec path runs (the knobs
    compose by configuration, not nested scans) and parity holds."""
    prompts = prompts_of((5, 9), seed=13)
    ref = mk_srv(eng, decode_horizon=1).run(greedy_reqs(prompts, max_new=8))
    srv = mk_srv(eng, spec_decode=True, decode_horizon=8)
    out = srv.run(greedy_reqs(prompts, max_new=8))
    for i in range(len(prompts)):
        np.testing.assert_array_equal(out[i], ref[i])
    assert srv.stats["spec_steps"] > 0   # the spec path really ran
    assert srv.decode_horizon == 8       # knob kept, just yielded to


# ---------------------------------------------------------------------------
# contracts: compile count, chaos degrade
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_horizon_steady_state_zero_recompiles(eng):
    """One compiled horizon program per N: after warmup a second full
    workload (admission churn, partial final horizons) compiles
    NOTHING, and the ``_decode_horizon`` cache holds one entry."""
    from deepspeed_tpu.utils.compile_guard import CompileWatch, cache_size
    cfg, params = tiny()                 # fresh engine: a clean jit cache
    e = InferenceEngine(config=cfg, params=params, dtype=jnp.float32)
    prompts = prompts_of((10, 9, 6), seed=9)

    def run_workload():
        srv = mk_srv(e, decode_horizon=4)
        return srv, srv.run(greedy_reqs(prompts, max_new=9))

    _, warm = run_workload()
    pf, dh = e._prefill_slot, e._decode_horizon
    n_h = cache_size(dh)
    watch = CompileWatch(max_compiles=0, label="horizon steady state")
    watch.wrap(pf)
    watch.wrap(dh)
    with watch:                          # raises RecompileError on exit
        _, out = run_workload()          # if anything compiled
    for i in range(len(prompts)):
        np.testing.assert_array_equal(out[i], warm[i])
    if n_h is not None:
        assert cache_size(dh) == n_h == 1


def test_horizon_fault_degrades_to_single_step(eng):
    """An injected ``serving.horizon`` fault fires BEFORE any capacity
    or slot state moves and downgrades THAT step to plain N=1 decode
    (``horizon_fallbacks`` counts it); the run still drains with
    streams bit-identical to the clean N=1 run."""
    prompts = prompts_of((5, 9, 12, 3))
    ref = mk_srv(eng, decode_horizon=1).run(greedy_reqs(prompts))
    with faults.injected(Fault("serving.horizon", "device_error",
                               step=1, count=3)) as inj:
        srv = mk_srv(eng, decode_horizon=8)
        out = srv.run(greedy_reqs(prompts))
    assert inj.fired
    assert srv.stats["horizon_fallbacks"] >= 3
    for i in range(len(prompts)):
        np.testing.assert_array_equal(
            out[i], ref[i], err_msg=f"request {i} diverged under degrade")
    assert srv.stats["completed"] == len(prompts)
