"""The kimi_linear dialect (Kimi-Linear: gated delta-rule linear attention
whose per-slot recurrent state lives beside the paged pool of un-rotated
latent-attention layers, the kinds given by a list) on the paged serving
path, held to the benchmark's plain reference at small sizes: the state
across chunk borders, slot reuse and preemption, idle slots, the shares,
the controls and what raises. The two kernels against the token recurrence
are tests/test_kda_kernels.py."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import kimi_linear_util as U
from deepspeed_tpu.inference import dialect, engine, latent, linear
from deepspeed_tpu.models import gpt, kimi_linear
from deepspeed_tpu.moe import expert_share

# float32 program against the float32 reference: sums in another order
# (chunkwise against token by token, absorbed against expanded) over logits
# of size 3-4
SOUND = 2e-4
WRONG = 2e-3        # every control moves the logits by more than this


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
        want, _ = ref.logits(params, toks[:-1], U.hp_of(cfg), **kw)
        want = np.asarray(want)[len(prompts[rid]) - 1:]
        worst = max(worst, float(np.abs(lg - want).max()))
    return worst


def test_prefill_then_decode_matches_the_reference(served):
    cfg, params, prompts, srv, got = served
    assert _worst(U.reference(), cfg, params, prompts, got) < SOUND
    # the latent pool of the 2 latent layers, and beside it the 5 linear
    # layers' per-slot state and convolution tails
    k = srv.cache.k
    assert isinstance(k, linear.LinearState) and srv.cache.v is None
    assert k.rows.shape == (2, srv.cache.num_blocks, 4, 128)
    assert k.state.shape == (5, 2, 4, 8, 8) and k.state.dtype == jnp.float32
    assert k.tail.shape == (5, 2, 3 * 96)
    assert srv.cache.recurrent_state_bytes == 5 * 2 * 4 * 8 * 8 * 4
    assert srv.cache.conv_tail_bytes == 5 * 2 * 3 * 96 * 4
    assert srv.cache.bytes_per_token == 2 * 128 * 4
    assert [int(s) for s in kimi_linear.layer_runs(cfg)[0]] == [1, 3]
    assert [int(c) for c in kimi_linear.layer_runs(cfg)[1]] == [1, 3]


def test_state_crosses_chunk_borders_and_a_reused_slot_starts_clean(model):
    """Chunks of 7 cut a sequence at every offset modulo the convolution's
    4 taps (7, 14, 21, 28), the recurrent state goes from chunk to chunk
    through the state buffer, a prompt shorter than the taps leaves a tail
    that is part zeros, and the first decode step resumes from both. ONE
    slot serves the three requests one after the other: the second and
    third find their predecessor's state and tail in the slot and give
    what the reference, which starts from zeros, gives."""
    cfg, params = model
    rng = np.random.default_rng(7)
    prompts = [rng.integers(1, 96, 30), rng.integers(1, 96, 2),
               rng.integers(1, 96, 23)]
    srv, got = U.serve_logits(cfg, params, prompts, 4, prefill_chunk=7,
                              block_size=4, num_slots=1)
    assert _worst(U.reference(), cfg, params, prompts, got) < SOUND
    assert float(jnp.abs(srv.cache.k.state).max()) > 0.0
    assert float(jnp.abs(srv.cache.k.tail).max()) > 0.0


def _serve_recording(cfg, params, prompts, new_tokens, **kw):
    """Serve ``prompts`` and keep the logits behind every emitted token,
    {(rid, index of the token in the answer): logits}, also across an
    eviction and its replay (exaone_moe_util.serve_logits counts on one
    token a request a step)."""
    import deepspeed_tpu
    from deepspeed_tpu.inference.serving import ServeRequest, ServingEngine
    eng = deepspeed_tpu.init_inference(
        (cfg, jax.tree_util.tree_map(np.asarray, params)), dtype=cfg.dtype)
    srv = ServingEngine(eng, block_size=4, prefill_chunk=16, **kw)
    last, rec = {}, {}
    run_p, run_d = eng.prefill_into_slot, eng.decode_slots
    chunk, step = srv._prefill_slot_chunk, srv._decode_step

    def prefill(*a, **k):
        out = run_p(*a, **k)
        last["prefill"] = np.asarray(out[0], np.float32).reshape(-1)
        return out

    def decode(*a, **k):
        out = run_d(*a, **k)
        last["decode"] = np.asarray(out[0], np.float32)
        return out

    def prefill_chunk(slot, req, *a):
        before = len(req.out)
        chunk(slot, req, *a)
        if len(req.out) > before:
            rec[req.rid, before] = last["prefill"]

    def decode_step(now):
        before = {s: (r, len(r.out)) for s, r in enumerate(srv.slots)
                  if r is not None}
        occ = step(now)
        for s, (r, n) in before.items():
            if len(r.out) > n:
                rec[r.rid, n] = last["decode"][s].reshape(-1)
        return occ

    eng.prefill_into_slot, eng.decode_slots = prefill, decode
    srv._prefill_slot_chunk, srv._decode_step = prefill_chunk, decode_step
    reqs = [ServeRequest(rid=i, prompt=np.asarray(p, np.int32),
                         max_new_tokens=new_tokens)
            for i, p in enumerate(prompts)]
    for r in reqs:
        srv.submit(r)
    guard = 0
    while srv.busy:
        srv.step()
        guard += 1
        assert guard < 500
    got = {r.rid: (np.concatenate([r.prompt, np.asarray(r.out, np.int32)]),
                   np.stack([rec[r.rid, i] for i in range(new_tokens)]))
           for r in reqs}
    return srv, got


def test_preempted_requests_replay_reproduces_its_logits(model):
    """A pool too small for both: one request is evicted, re-prefilled from
    position 0 (prompt + generated) and goes on; the replay rebuilds the
    recurrent state, and every logit it emits is the reference's."""
    cfg, params = model
    rng = np.random.default_rng(4)
    prompts = [rng.integers(1, 96, 21), rng.integers(1, 96, 20)]
    srv, got = _serve_recording(cfg, params, prompts, 12, num_slots=2,
                                num_blocks=14, telemetry=True)
    assert srv.stats["evictions"] >= 1       # the pool really ran out
    assert _worst(U.reference(), cfg, params, prompts, got) < SOUND
    text = str(srv.metrics.snapshot())
    for name in ("kv_recurrent_state_bytes", "kv_conv_tail_bytes",
                 "kv_latent_pool_bytes", "serving_state_resets",
                 "serving_state_replays"):
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
    counters = srv.read_expert_counters()
    assert counters["decode"]["pairs_total"] > 0


@pytest.mark.parametrize("variant", [
    "state_bf16", "fp8_kda", "no_decay", "no_conv", "no_l2",
    "no_write_gate", "no_out_gate", "rotated", "no_bias", "no_scale",
    "unnormalised"])
def test_each_dropped_term_fails(served, variant):
    cfg, params, prompts, _, got = served
    err = _worst(U.reference(), cfg, params, prompts, got, only=1,
                 variant=(variant,))
    assert err > WRONG, (variant, err)


def test_precision_control_fails(served):
    cfg, params, prompts, _, got = served
    assert _worst(U.reference(), cfg, params, prompts, got, only=1,
                  fp8=True) > WRONG


def _kda_layer(params, index=1):
    return jax.tree_util.tree_map(lambda a: a[index], params["kda"])


def test_decode_leaves_idle_and_prefilling_slots_state_bit_unchanged(model):
    """A decode dispatch over three slots of which one decodes: the other
    two slots' state and tail, and every other layer's, are what they
    were; the one that decodes equals a one-token chunk resumed from the
    same state."""
    cfg, params = model
    p = _kda_layer(params)
    slots, C = 3, 96
    st, _ = linear.new_state(cfg, 9, 4, slots, jnp.float32)
    state = jax.random.normal(jax.random.key(1), st.state.shape) \
        .reshape((-1,) + st.state.shape[2:])
    tails = jax.random.normal(jax.random.key(2), st.tail.shape) \
        .reshape(-1, st.tail.shape[-1])
    at = jnp.int32(2 * slots)                      # the third linear layer
    x = jax.random.normal(jax.random.key(3), (slots, cfg.d_model))
    active = jnp.asarray([False, True, False])

    def decode(x, s, t):
        return linear.kda_decode(x, s, t, active, p, cfg, at, "gather", None)

    def prefill(x, s, t, n_valid):
        return linear.kda_prefill(x, s, t, jnp.int32(1),
                                  jnp.asarray([5], jnp.int32), n_valid, p,
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
    np.testing.assert_array_equal(np.asarray(t2)[2 * slots + 1, :2 * C],
                                  np.asarray(tails)[2 * slots + 1, C:])
    y1, s3, t3 = prefill(x[1:2], state, tails, 1)
    np.testing.assert_allclose(np.asarray(y1[0]), np.asarray(y[1]),
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(s3), np.asarray(s2), atol=1e-5)
    # (one row against three through the projection: another summation)
    np.testing.assert_allclose(np.asarray(t3), np.asarray(t2), atol=1e-6)
    # a chunk with no valid token leaves the slot's state and tail alone
    _, s4, t4 = prefill(x[1:2], state, tails, 0)
    np.testing.assert_allclose(np.asarray(s4), np.asarray(state), atol=1e-6)
    np.testing.assert_array_equal(np.asarray(t4), np.asarray(tails))


def test_direct_query_and_unrotated_row_through_latent_py(model):
    """``q_lora_rank`` None and ``mla_use_nope`` are data of the config:
    latent.py's expanded prefill and absorbed decode, with ONE query
    projection and nothing rotated, against the reference's latent layer;
    the reference with rotary differs."""
    ref = U.reference()
    cfg, params = model
    p = jax.tree_util.tree_map(lambda a: a[1], params["mla"])
    assert "q" in p and "q_a" not in p
    T, bs, NB = 11, 4, 6
    x = jax.random.normal(jax.random.key(5), (T + 1, cfg.d_model))
    pool = jnp.zeros((1 + NB, bs, cfg.latent_lanes))
    table = jnp.arange(1, NB + 1, dtype=jnp.int32)

    def prefill(x, pool):
        return latent.attend_prefill(
            x, pool, table, jnp.arange(T, dtype=jnp.int32), T, p, cfg,
            jnp.int32(0), "gather")

    def decode(x, pool):
        return latent.attend_decode(
            x, pool, table[None], jnp.asarray([T], jnp.int32),
            jnp.asarray([True]), p, cfg, jnp.int32(0), "gather")

    prefill, decode = jax.jit(prefill), jax.jit(decode)
    y, pool = prefill(x[:T], pool)
    # the cached row: the normalised latent and the UNROTATED shared key
    rows = np.asarray(pool[1:].reshape(-1, cfg.latent_lanes)[:T])
    h = ref._rms(x[:T], p["ln1"]["scale"], cfg.norm_eps)
    ckv = np.asarray(h @ p["kv_a"]["kernel"])
    np.testing.assert_allclose(rows[:, 16:20], ckv[:, 16:], atol=1e-5)
    assert not rows[:, 20:].any()
    yd, _ = decode(x[T:], pool)
    got = np.concatenate([np.asarray(y), np.asarray(yd)])
    hp = U.hp_of(cfg)
    with jax.default_matmul_precision("highest"):
        want = ref._latent_attention(x, p, hp, frozenset(), False)
        turned = ref._latent_attention(x, p, hp, frozenset(("rotated",)),
                                       False)
    np.testing.assert_allclose(got, np.asarray(want), atol=2e-5)
    assert float(np.abs(got - np.asarray(turned)).max()) > 1e-2


def test_unrotated_prefill_through_the_kernel_equals_the_plain_path(
        model, pallas_interpret):
    """The same three-chunk prompt as the dots.vlm1 dialect's test through
    a latent layer with ONE query projection and nothing rotated:
    ``attend_prefill`` with every flash step in the ``mla_prefill`` kernel
    against the plain path."""
    from test_latent_dots_vlm import prefill_three_chunks
    cfg, params = model
    p = jax.tree_util.tree_map(lambda a: a[1], params["mla"])
    got, got_pool = prefill_three_chunks(cfg, p, "pallas")
    want, want_pool = prefill_three_chunks(cfg, p, "gather")
    np.testing.assert_array_equal(got_pool, want_pool)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=2e-5)


def test_prefill_tile_counters_count_the_latent_layers_only(served):
    """``serving_mla_prefill_tiles_{kernel,plain}_total`` beside a
    recurrent state: the two latent layers' flash steps (occupied history
    blocks + the own tile) over the three prompts' eight chunks, none by
    the kernel off a TPU; the five linear-attention layers take none."""
    cfg, _, prompts, srv, _ = served
    assert cfg.n_full_layers == 2
    want = 2 * sum(-(-start // 4) + 1 for p in prompts
                   for start in range(0, len(p), 16))
    assert srv.stats["mla_prefill_tiles_plain_total"] == want == 72
    assert srv.stats["mla_prefill_tiles_kernel_total"] == 0


def test_shares_add_up_to_the_whole_layer(model):
    """8 experts held as (0, 4) and (4, 4): the shares' routed parts, the
    shared expert counted once, are the uncut reference layer."""
    from deepspeed_tpu.inference import hybrid
    ref = U.reference()
    cfg, params = model
    p = jax.tree_util.tree_map(lambda a: a[2], params["block"])
    x = jax.random.normal(jax.random.key(9), (40, cfg.d_model)) * 3.0
    h = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + cfg.norm_eps)
    whole, sel, _, _ = expert_share.sparse_ffn(
        h, p["moe"], cfg, "ragged_dot", mlp=hybrid._swiglu)
    shared = hybrid._swiglu(h, p["moe"]["shared"])
    total = shared
    for first in (0, 4):
        share = U.tiny_config(held=(first, 4))
        moe = dict(p["moe"], experts={
            n: {"kernel": p["moe"]["experts"][n]["kernel"][first:first + 4]}
            for n in ("wg", "wi", "wo")})
        part, sel_p, _, _ = expert_share.sparse_ffn(
            h, moe, share, "ragged_dot", mlp=hybrid._swiglu)
        np.testing.assert_array_equal(np.asarray(sel_p), np.asarray(sel))
        total = total + (part - shared)
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               atol=1e-5)
    pr = dict(p, ln2={"scale": jnp.ones_like(p["ln2"]["scale"])})
    free = -jnp.ones((40, cfg.moe_k), jnp.int32)
    with jax.default_matmul_precision("highest"):
        want, (own, _) = ref._sparse_ffn(x, pr, U.hp_of(cfg), frozenset(),
                                         False, free)
    np.testing.assert_array_equal(np.sort(np.asarray(own), -1),
                                  np.sort(np.asarray(sel), -1))
    np.testing.assert_allclose(np.asarray(x + whole), np.asarray(want),
                               atol=1e-4)
    assert float(jnp.abs(whole - shared).max()) > 0.1


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
    with pytest.raises(ValueError, match=name + ".*linear-attention"):
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
    with pytest.raises(ValueError, match=name + ".*linear-attention"):
        call(eng)
    with pytest.raises(ValueError, match="per-slot state"):
        eng.prefill_into_slot(None, None, np.zeros(4, np.int32),
                              np.zeros(4, np.int32), 0, 4)
    with pytest.raises(ValueError,
                       match="tensor parallelism.*linear-attention"):
        deepspeed_tpu.init_inference((cfg, params), dtype=jnp.float32,
                                     mp_size=2)


def test_no_recompile_in_steady_state(served):
    from deepspeed_tpu.inference.serving import ServeRequest
    from deepspeed_tpu.utils.compile_guard import CompileWatch
    _, _, _, srv, _ = served
    rng = np.random.default_rng(2)
    with CompileWatch(max_compiles=0, label="linear steady state"):
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
    assert d.bytes_per_token(cfg, jnp.bfloat16) == 2 * 128 * 2
    assert d.slot_bytes(cfg, 4, jnp.bfloat16)[2:] \
        == (5 * 4 * 8 * 8 * 4, 5 * 3 * 96 * 2)
    plain = dialect.of(gpt.GPTConfig())
    assert plain.slot_bytes(gpt.GPTConfig(), 4, jnp.bfloat16)[2:] == (0, 0)
    assert d is linear.DIALECT and plain is engine.DIALECT
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
    # one chip of 16-way expert parallelism as the benchmark runs it
    real = kimi_linear.KimiLinearConfig(
        n_layers=27, n_heads=32, d_model=2304, d_ff=9216, vocab_size=20480,
        max_seq_len=8192, experts_held=(0, 16),
        kda_layers=(1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19, 21,
                    22, 23, 25, 26),
        full_attn_layers=(4, 8, 12, 16, 20, 24, 27))
    assert real.latent_row == 576 and real.latent_lanes == 640
    assert d.bytes_per_token(real, jnp.bfloat16) == 8960
    assert d.slot_bytes(real, 16, jnp.bfloat16)[2:] \
        == (41_943_040, 1_474_560)
    starts, counts, behind = kimi_linear.layer_runs(real)
    assert list(counts) == [2, 3, 3, 3, 3, 3, 2] and behind == (27, 0)
    assert list(starts) == [1, 4, 8, 12, 16, 20, 24]
    shapes = jax.eval_shape(
        lambda: kimi_linear.init_params(jax.random.PRNGKey(0), real))
    n = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(shapes))
    assert n == 4_296_057_728, n          # 8.00 GiB in bf16
    with pytest.raises(AssertionError):
        U.tiny_config(kda_layers=(1, 2, 4, 5), full_attn_layers=(3, 7))
    with pytest.raises(AssertionError):   # the dense layer is a linear one
        U.tiny_config(kda_layers=(2, 4, 5, 6), full_attn_layers=(1, 3, 7))
    # linear layers behind the last latent one run as a last run
    assert kimi_linear.layer_runs(U.tiny_config(
        kda_layers=(1, 2, 4, 5, 6, 7), full_attn_layers=(3,)))[2] == (3, 4)
