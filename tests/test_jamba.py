"""The jamba dialect (AI21 Jamba: Mamba-1 state-space mixers whose per-slot
float32 state and convolution tail live beside the two paged K/V pools of
un-rotated single-KV-head attention layers, the kinds by a period) on the
paged serving path, held to the benchmark's plain reference at small sizes:
the two kernels against the token recurrence, the state across chunk
borders, slot reuse and preemption, idle slots, the controls and what
raises. The layer loop and the per-slot store are inference/linear.py's,
which Kimi-Linear's tests (tests/test_kimi_linear.py) hold too."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import jamba_util as U
# the drive that records logits across an eviction and its replay
from test_kimi_linear import _serve_recording
from deepspeed_tpu.inference import dialect, linear, ssm as ssm_blocks
from deepspeed_tpu.models import gpt, jamba, recurrent
from deepspeed_tpu.ops.attention import ssm

# float32 program against the float32 reference: sums in another order
# (the convolution's taps, the state index's sum) over logits of size 4-5
SOUND = 2e-4
WRONG = 2e-3        # every control moves the logits by more than this


def _rule_inputs(T, Ci, N, seed):
    """x, delta, A, B, C, h0 as the recurrence takes them: steps between
    1e-3 and 1, transitions down to -16."""
    ks = jax.random.split(jax.random.key(seed), 6)
    x = jax.nn.silu(jax.random.normal(ks[0], (T, Ci)))
    delta = jnp.exp(jax.random.uniform(ks[1], (T, Ci), minval=-7.0,
                                       maxval=0.0))
    A = -jnp.exp(jax.random.uniform(ks[2], (N, Ci), minval=0.0, maxval=2.8))
    return (x, delta, A, jax.random.normal(ks[3], (T, N)),
            jax.random.normal(ks[4], (T, N)),
            jax.random.normal(ks[5], (N, Ci)))


# a chunk shorter than a group of 8 tokens, whole groups, two token blocks
# of the grid, two channel blocks; the chunk's border at every offset
@pytest.mark.parametrize("T,Ci,cuts", [
    (3, 64, (1,)), (29, 64, (8, 9, 10, 11)), (150, 64, (75,)),
    (20, 1024, (7,))])
def test_ssm_scan_is_the_token_recurrence(T, Ci, cuts):
    """1e-5: float32 on both sides, the same operations in the same order
    (the kernel multiplies delta and x before B, the scan likewise)."""
    args = _rule_inputs(T, Ci, 8, T)
    y, h = ssm.ssm_recurrence(*args)
    y2, h2 = ssm.ssm_scan(*args, interpret=True)
    assert float(jnp.abs(y).max()) > 0.05
    np.testing.assert_allclose(np.asarray(y2), np.asarray(y), atol=1e-5)
    np.testing.assert_allclose(np.asarray(h2), np.asarray(h), atol=1e-5)
    # two chunks, the state handed from one to the next, are the one
    x, d, A, B, C, h0 = args
    for cut in cuts:
        ya, ha = ssm.ssm_scan(x[:cut], d[:cut], A, B[:cut], C[:cut], h0,
                              interpret=True)
        yb, hb = ssm.ssm_scan(x[cut:], d[cut:], A, B[cut:], C[cut:], ha,
                              interpret=True)
        np.testing.assert_allclose(np.asarray(jnp.concatenate([ya, yb])),
                                   np.asarray(y), atol=1e-5)
        np.testing.assert_allclose(np.asarray(hb), np.asarray(h), atol=1e-5)
    # a token with delta = 0 (chunk padding) leaves the state as it was
    _, h3 = ssm.ssm_scan(x, d.at[T // 2:].set(0.0), A, B, C, h0,
                         interpret=True)
    _, want = ssm.ssm_recurrence(x[:T // 2], d[:T // 2], A, B[:T // 2],
                                 C[:T // 2], h0)
    np.testing.assert_allclose(np.asarray(h3), np.asarray(want), atol=1e-5)


@pytest.mark.parametrize("active", [
    (True, False, True, True, False), (False,) * 5, (True,) * 5])
def test_ssm_step_rewrites_the_active_slots_alone(active):
    """The kernel over a work list against one step of the recurrence a
    slot; every other row of the buffer bit-unchanged."""
    Ci, N, base = 64, 8, 6
    x, d, A, B, C, _ = _rule_inputs(5, Ci, N, 3)
    state = jax.random.normal(jax.random.key(9), (13, N, Ci))
    active = jnp.asarray(active)
    want_s, want_y = ssm.ssm_step_reference(state, A, x, d, B, C, base,
                                            active)
    order, count = linear.step_plan(active)

    def step(s):
        return ssm.ssm_step(s, A, *ssm.pack_step(x, d, B, C), base + order,
                            order, count, interpret=True)
    got_s, got_y = jax.jit(step)(state)
    rows = base + np.flatnonzero(np.asarray(active))
    keep = np.ones(13, bool)
    keep[rows] = False
    np.testing.assert_array_equal(np.asarray(got_s)[keep],
                                  np.asarray(state)[keep])
    np.testing.assert_allclose(np.asarray(got_s), np.asarray(want_s),
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(got_y)[np.asarray(active)],
                               np.asarray(want_y)[np.asarray(active)],
                               atol=1e-5)


@pytest.fixture(scope="module")
def model():
    cfg = U.tiny_config()
    return cfg, U.tiny_params(cfg)


@pytest.fixture(scope="module")
def served(model):
    cfg, params = model
    rng = np.random.default_rng(0)
    # across chunk boundaries (16), one of them not a multiple of the block
    prompts = [rng.integers(1, 96, 37), rng.integers(1, 96, 21),
               rng.integers(1, 96, 48)]
    srv, got = U.serve_logits(cfg, params, prompts, 7)
    return cfg, params, prompts, srv, got


def _worst(ref, cfg, params, prompts, got, only=None, **kw):
    worst = 0.0
    for rid, (toks, lg) in got.items():
        if only is not None and rid != only:
            continue
        want = np.asarray(ref.logits(params, toks[:-1], U.hp_of(cfg), **kw))
        err = np.abs(lg - want[len(prompts[rid]) - 1:]).max()
        # a control that blows up (no softplus: exp of a large step) is
        # wrong by more than any limit
        worst = max(worst, float(err) if np.isfinite(err) else np.inf)
    return worst


def test_prefill_then_decode_matches_the_reference(served):
    cfg, params, prompts, srv, got = served
    assert _worst(U.reference(), cfg, params, prompts, got) < SOUND
    # the K and V pools of the 2 attention layers, and beside them the 6
    # state-space layers' per-slot state (TRANSPOSED: the 64 channels on
    # the lanes) and convolution tails
    k, v = srv.cache.k, srv.cache.v
    assert isinstance(k, linear.LinearState)
    assert k.rows.shape == v.shape == (2, srv.cache.num_blocks, 4, 8)
    assert k.state.shape == (6, 2, 8, 64) and k.state.dtype == jnp.float32
    assert k.tail.shape == (6, 2, 3 * 64)
    assert k.stats is None and k.route is None
    assert srv.cache.recurrent_state_bytes == 6 * 2 * 8 * 64 * 4
    assert srv.cache.conv_tail_bytes == 6 * 2 * 3 * 64 * 4
    assert srv.cache.bytes_per_token == 2 * 2 * 8 * 4
    starts, counts, behind = recurrent.layer_runs(cfg)
    assert list(starts) == [0, 3] and list(counts) == [2, 3]
    assert behind == (7, 1)


def test_state_crosses_chunk_borders_and_a_reused_slot_starts_clean(model):
    """Chunks of 7 cut a sequence at every offset modulo the convolution's
    4 taps (7, 14, 21, 28), the state goes from chunk to chunk through the
    state buffer, a prompt shorter than the taps leaves a tail that is
    part zeros, and the first decode step resumes from both. ONE slot
    serves the three requests one after the other: the second and third
    find their predecessor's state and tail in the slot and give what the
    reference, which starts from zeros (a fresh engine), gives."""
    cfg, params = model
    rng = np.random.default_rng(7)
    prompts = [rng.integers(1, 96, 30), rng.integers(1, 96, 2),
               rng.integers(1, 96, 23)]
    srv, got = U.serve_logits(cfg, params, prompts, 4, prefill_chunk=7,
                              block_size=4, num_slots=1)
    assert _worst(U.reference(), cfg, params, prompts, got) < SOUND
    assert float(jnp.abs(srv.cache.k.state).max()) > 0.0
    assert float(jnp.abs(srv.cache.k.tail).max()) > 0.0


def test_the_kernels_serve_what_the_portable_path_serves(model, monkeypatch):
    """``decode_impl="pallas"`` off a TPU, the two Mosaic kernels
    interpreted (the paged attention kernel likewise): the same logits."""
    from deepspeed_tpu.ops.attention import paged
    cfg, params = model
    for mod, name in ((ssm, "ssm_scan"), (ssm, "ssm_step"),
                      (paged, "paged_decode_attention")):
        monkeypatch.setattr(mod, name, functools.partial(
            getattr(mod, name), interpret=True))
    monkeypatch.setenv("DS_PAGED_DECODE_IMPL", "pallas")
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, 96, 19), rng.integers(1, 96, 9)]
    srv, got = U.serve_logits(cfg, params, prompts, 3, prefill_chunk=8)
    assert srv.decode_impl == "pallas"
    assert _worst(U.reference(), cfg, params, prompts, got) < SOUND


def test_preempted_requests_replay_reproduces_its_logits(model):
    """A pool too small for both: one request is evicted, re-prefilled from
    position 0 (prompt + generated) and goes on; the replay rebuilds the
    recurrent state, and every logit it emits is the reference's. The
    gauges and counters of the per-slot state count this rule too."""
    cfg, params = model
    rng = np.random.default_rng(4)
    prompts = [rng.integers(1, 96, 21), rng.integers(1, 96, 20)]
    srv, got = _serve_recording(cfg, params, prompts, 12, num_slots=2,
                                num_blocks=14, telemetry=True)
    assert srv.stats["evictions"] >= 1       # the pool really ran out
    assert _worst(U.reference(), cfg, params, prompts, got) < SOUND
    text = str(srv.metrics.snapshot())
    for name in ("kv_recurrent_state_bytes", "kv_conv_tail_bytes",
                 "serving_state_resets", "serving_state_replays"):
        assert name in text, name
    tracer = srv.telemetry.tracer
    spans = tracer.spans("serve.prefill")
    # a chunk at position 0 starts from zeros, every other from the state
    assert all(s[5]["state"] == int(s[5]["start"] > 0) for s in spans)
    resets = sum(1 for s in spans if s[5]["start"] == 0)
    assert resets >= 3                       # two admissions and a replay
    assert any(s[5].get("state_slots") for s in tracer.spans("serve.decode"))
    assert srv._state_resets.value == resets
    assert srv._state_replays.value == srv.stats["evictions"]
    assert srv.read_expert_counters() == {}  # no experts: nothing to count


# each of the three inner norms, the skip, the two biases, the gate, the
# softplus, the convolution's older taps, a rotation on the attention
# layers' q and k, the state in bfloat16, the state-space side in float8
@pytest.mark.parametrize("variant", [
    "no_dt_norm", "no_b_norm", "no_c_norm", "no_skip", "no_conv_bias",
    "no_dt_bias", "no_gate", "no_softplus", "no_conv", "rotated",
    "state_bf16", "fp8_ssm"])
def test_each_dropped_term_fails(served, variant):
    cfg, params, prompts, _, got = served
    err = _worst(U.reference(), cfg, params, prompts, got, only=1,
                 variant=(variant,))
    assert err > WRONG, (variant, err)


def test_precision_control_fails(served):
    cfg, params, prompts, _, got = served
    assert _worst(U.reference(), cfg, params, prompts, got, only=1,
                  fp8=True) > WRONG


def _layer(stack, index):
    return jax.tree_util.tree_map(lambda a: a[index], stack)


def test_decode_leaves_idle_and_prefilling_slots_state_bit_unchanged(model):
    """A decode dispatch over three slots of which one decodes: the other
    two slots' state and tail, and every other layer's, are what they
    were; the one that decodes equals a one-token chunk resumed from the
    same state; a chunk with no valid token leaves its slot alone."""
    cfg, params = model
    p = _layer(params["ssm"], 1)
    slots, Di = 3, cfg.d_inner
    st, _ = linear.new_state(cfg, 9, 4, slots, jnp.float32)
    state = jax.random.normal(jax.random.key(1), st.state.shape) \
        .reshape((-1,) + st.state.shape[2:])
    tails = jax.random.normal(jax.random.key(2), st.tail.shape) \
        .reshape(-1, st.tail.shape[-1])
    at = jnp.int32(2 * slots)                  # the third state-space layer
    x = jax.random.normal(jax.random.key(3), (slots, cfg.d_model))
    active = jnp.asarray([False, True, False])

    def decode(x, s, t):
        return ssm_blocks.ssm_decode(x, s, t, active, p, cfg, at, "gather",
                                     None)

    def prefill(x, s, t, n_valid):
        return ssm_blocks.ssm_prefill(
            x, s, t, jnp.int32(1), jnp.asarray([5], jnp.int32), n_valid, p,
            cfg, at, "gather")

    # one test, one call each: jitted for speed, not for reuse
    decode, prefill = jax.jit(decode), jax.jit(prefill)
    y, s2, t2 = decode(x, state, tails)
    keep = np.ones(len(state), bool)
    keep[2 * slots + 1] = False
    np.testing.assert_array_equal(np.asarray(s2)[keep],
                                  np.asarray(state)[keep])
    np.testing.assert_array_equal(np.asarray(t2)[keep],
                                  np.asarray(tails)[keep])
    assert float(jnp.abs(s2 - state).max()) > 1e-3
    # the tail moved on by one token: the oldest row left
    np.testing.assert_array_equal(np.asarray(t2)[2 * slots + 1, :2 * Di],
                                  np.asarray(tails)[2 * slots + 1, Di:])
    y1, s3, t3 = prefill(x[1:2], state, tails, 1)
    np.testing.assert_allclose(np.asarray(y1[0]), np.asarray(y[1]),
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(s3), np.asarray(s2), atol=1e-5)
    # (one row against three through the projection: another summation)
    np.testing.assert_allclose(np.asarray(t3), np.asarray(t2), atol=1e-6)
    _, s4, t4 = prefill(x[1:2], state, tails, 0)
    np.testing.assert_allclose(np.asarray(s4), np.asarray(state), atol=1e-6)
    np.testing.assert_array_equal(np.asarray(t4), np.asarray(tails))


def test_one_kv_head_unrotated_through_the_two_pool_path(model):
    """20-on-1 in small: 4 query heads on ONE K/V head and nothing rotated
    are data of the config. The engine's own prefill read
    (``_attend_occupied``) and decode attention, called by themselves as
    ssm.py calls them, against the reference's attention layer; the
    reference with rotary differs."""
    from deepspeed_tpu.inference.engine import (_attn_decode_paged,
                                                _attn_prefill_paged)
    ref = U.reference()
    cfg, params = model
    p = _layer(params["attn"], 1)
    T, bs, NB = 11, 4, 6
    x = jax.random.normal(jax.random.key(5), (T + 1, cfg.d_model))
    pools = (jnp.zeros((1 + NB, bs, cfg.head_dim)),) * 2
    assert cfg.kv_heads == 1 and p["qkv"]["kernel"].shape[-1] == 6 * 8
    table = jnp.arange(1, NB + 1, dtype=jnp.int32)

    def prefill(x, pools):
        _, attn, pools = _attn_prefill_paged(
            x[None], pools, table, jnp.arange(T, dtype=jnp.int32), T, p,
            cfg)
        return x + attn[0], pools

    def decode(x, pools):
        _, attn, pools = _attn_decode_paged(
            x[:, None], pools, table[None], jnp.asarray([T], jnp.int32),
            jnp.asarray([True]), p, cfg)
        return x + attn[:, 0], pools

    y, pools = jax.jit(prefill)(x[:T], pools)
    yd, _ = jax.jit(decode)(x[T:], pools)
    got = np.concatenate([np.asarray(y), np.asarray(yd)])
    hp = U.hp_of(cfg)
    with jax.default_matmul_precision("highest"):
        want = ref._attention(x, p, hp, False, ())
        turned = ref._attention(x, p, hp, False, ("rotated",))
    np.testing.assert_allclose(got, np.asarray(want), atol=2e-5)
    assert float(np.abs(got - np.asarray(turned)).max()) > 1e-2


@pytest.mark.parametrize("kwargs,name", [
    (dict(prefix_cache=True), "prefix sharing"),
    (dict(prefix_cache=True, host_tier=True), "prefix sharing"),
    (dict(kv_quant="int8"), "int8 KV pools"),
    (dict(spec_decode=True), "speculative decoding"),
    (dict(decode_horizon=4), "fused decode horizon"),
    (dict(lora_serve=True), "LoRA serving"),
])
def test_unsupported_serving_options_raise_by_name(model, kwargs, name):
    import deepspeed_tpu
    from deepspeed_tpu.inference.serving import ServingEngine
    cfg, params = model
    eng = deepspeed_tpu.init_inference((cfg, params), dtype=jnp.float32)
    with pytest.raises(ValueError,
                       match=name + ".*recurrent state.*state-space"):
        ServingEngine(eng, num_slots=2, block_size=4, **kwargs)


@pytest.mark.parametrize("call,name", [
    (lambda e: e.generate(np.ones((1, 4), np.int32), max_new_tokens=2),
     "static-cache prefill"),
    (lambda e: e.forward(np.ones((1, 4), np.int32)), "cacheless forward"),
])
def test_static_cache_paths_raise_by_name(model, call, name):
    import deepspeed_tpu
    cfg, params = model
    eng = deepspeed_tpu.init_inference((cfg, params), dtype=jnp.float32)
    with pytest.raises(ValueError, match=name + ".*state-space"):
        call(eng)
    with pytest.raises(ValueError, match="per-slot state"):
        eng.prefill_into_slot(None, None, np.zeros(4, np.int32),
                              np.zeros(4, np.int32), 0, 4)
    with pytest.raises(ValueError, match="tensor parallelism.*state-space"):
        deepspeed_tpu.init_inference((cfg, params), dtype=jnp.float32,
                                     mp_size=2)


def test_no_recompile_in_steady_state(served):
    from deepspeed_tpu.inference.serving import ServeRequest
    from deepspeed_tpu.utils.compile_guard import CompileWatch
    _, _, _, srv, _ = served
    rng = np.random.default_rng(2)
    with CompileWatch(max_compiles=0, label="state-space steady state"):
        for i, n in enumerate((33, 5, 17, 40)):
            srv.submit(ServeRequest(rid=f"s{i}", max_new_tokens=4,
                                    prompt=rng.integers(1, 96, n).astype(
                                        np.int32)))
        guard = 0
        while srv.busy:
            srv.step()
            guard += 1
            assert guard < 500


def test_cache_accounting_and_the_published_sizes():
    cfg = U.tiny_config()
    d = dialect.of(cfg)
    assert d is linear.DIALECT and ssm_blocks.is_ssm(cfg)
    assert d.bytes_per_token(cfg, jnp.bfloat16) == 2 * 2 * 8 * 2
    assert d.slot_bytes(cfg, 4, jnp.bfloat16)[2:] \
        == (6 * 64 * 8 * 4, 6 * 3 * 64 * 2)
    # slots, not blocks, are what a budget buys first
    from deepspeed_tpu.inference.paged_cache import PagedKVCache
    per_slot = sum(d.slot_bytes(cfg, 4, jnp.float32))
    block = 4 * d.bytes_per_token(cfg, jnp.float32)
    cache = PagedKVCache(cfg, num_slots=3, block_size=4, dtype=jnp.float32,
                         hbm_budget_bytes=3 * per_slot + 10 * block)
    assert cache.num_blocks == 11
    with pytest.raises(ValueError, match="HBM budget"):
        PagedKVCache(cfg, num_slots=3, block_size=4, dtype=jnp.float32,
                     hbm_budget_bytes=3 * per_slot)
    # the whole model as the benchmark runs it
    real = jamba.JambaConfig(n_layers=28, n_heads=20, n_kv_heads=1,
                             d_model=2560, d_ff=8192, vocab_size=65536,
                             max_seq_len=12288)
    assert [int(i) for i in np.flatnonzero(real.attn_kinds)] == [7, 21]
    assert real.d_inner == 5120 and real.head_dim == 128
    assert real.recurrent_state_shape == (16, 5120)
    assert d.bytes_per_token(real, jnp.bfloat16) == 1024
    assert d.slot_bytes(real, 16, jnp.bfloat16)[2:] == (8_519_680, 798_720)
    starts, counts, behind = recurrent.layer_runs(real)
    assert list(starts) == [0, 8] and list(counts) == [7, 13]
    assert behind == (22, 6)
    shapes = jax.eval_shape(
        lambda: jamba.init_params(jax.random.PRNGKey(0), real))
    n = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(shapes))
    assert n == jamba.num_params(real) == 3_029_337_472, n   # 6.06 GB bf16
    mixer = sum(int(np.prod(a.shape[1:])) for a in
                jax.tree_util.tree_leaves(shapes["ssm"])) - 2560
    assert mixer == 41_241_792
    with pytest.raises(AssertionError):       # no layer would be attention
        U.tiny_config(attn_layer_period=14, attn_layer_offset=9)
    with pytest.raises(AssertionError):
        U.tiny_config(rotary_dim=8)
