"""The zaya dialect (ZAYA1: attention inside a compressed latent with
convolutions over time, a top-1 expert layer with a skip chosen by an MLP
router that carries state from layer to layer) on the paged serving path,
held to the benchmark's plain reference at small sizes: the per-slot tail
across chunk borders, slot reuse and preemption, the router, the shares,
the controls and what raises."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import zaya_util as U
from deepspeed_tpu.inference import cca, hybrid
from deepspeed_tpu.models import zaya
from deepspeed_tpu.moe import expert_share

SOUND = 2e-4        # float32 program against the float32 reference
WRONG = 2e-2        # every control moves the logits by more than this


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


def _worst(ref, cfg, params, prompts, got, **kw):
    worst = 0.0
    for rid, (toks, lg) in got.items():
        want, _ = ref.logits(params, toks[:-1], U.hp_of(cfg), **kw)
        want = np.asarray(want)[len(prompts[rid]) - 1:]
        worst = max(worst, float(np.abs(lg - want).max()))
    return worst


def test_prefill_then_decode_matches_the_reference(served):
    cfg, params, prompts, srv, got = served
    assert _worst(U.reference(), cfg, params, prompts, got) < SOUND
    # K and V pools of 2 KV heads of 8, and the per-slot tails beside them
    k = srv.cache.k
    assert isinstance(k, cca.CCAState)
    assert k.rows.shape == srv.cache.v.shape == (4, srv.cache.num_blocks, 4, 16)
    assert k.tail.shape == (4, 2, 2, 48) and k.vtail.shape == (4, 2, 8)
    assert cfg.cca_channels == 48 and cfg.cca_tail_values == 104
    assert srv.cache.cca_tail_bytes == 4 * 2 * 104 * 4


@pytest.mark.parametrize("chunk", [5, 6, 7])
def test_tail_crosses_chunk_borders_at_every_offset(model, chunk):
    """Chunks of 5, 6 and 7 tokens cut a sequence at every offset modulo
    3 (the two convolutions and the value shift see three tokens), and
    the first decode step resumes from the last chunk's tail."""
    cfg, params = model
    rng = np.random.default_rng(chunk)
    prompts = [rng.integers(1, 96, 23), rng.integers(1, 96, 3 * chunk)]
    _, got = U.serve_logits(cfg, params, prompts, 4, prefill_chunk=chunk,
                            block_size=4)
    assert _worst(U.reference(), cfg, params, prompts, got) < SOUND


def test_reused_slot_starts_from_a_zero_tail(model):
    """One slot, two requests one after the other: the second finds the
    first one's tail in its slot and must not read it."""
    cfg, params = model
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, 96, 19), rng.integers(1, 96, 26)]
    srv, got = U.serve_logits(cfg, params, prompts, 5, num_slots=1)
    assert _worst(U.reference(), cfg, params, prompts, got) < SOUND
    assert float(jnp.abs(srv.cache.k.tail).max()) > 0.0


def test_preemption_and_readmission_reproduce_the_tokens(model):
    cfg, params = model
    rng = np.random.default_rng(4)
    prompts = [rng.integers(1, 96, 21), rng.integers(1, 96, 20)]
    _, roomy = U.serve_logits(cfg, params, prompts, 12)
    import deepspeed_tpu
    from deepspeed_tpu.inference.serving import ServeRequest, ServingEngine
    eng = deepspeed_tpu.init_inference(
        (cfg, jax.tree_util.tree_map(np.asarray, params)), dtype=cfg.dtype)
    srv = ServingEngine(eng, num_slots=2, block_size=4, prefill_chunk=16,
                        num_blocks=14)
    reqs = [ServeRequest(rid=i, prompt=np.asarray(p, np.int32),
                         max_new_tokens=12) for i, p in enumerate(prompts)]
    for r in reqs:
        srv.submit(r)
    guard = 0
    while srv.busy:
        srv.step()
        guard += 1
        assert guard < 500
    assert srv.stats["evictions"] >= 1       # the pool really ran out
    for r in reqs:
        np.testing.assert_array_equal(
            np.asarray(r.out), roomy[r.rid][0][len(r.prompt):])


@pytest.mark.parametrize("variant", [
    "no_conv0", "no_conv1", "no_qk_mean", "no_value_shift", "no_temp",
    "no_unit_heads", "full_rotary", "interleaved_rotary", "no_res_bias",
    "no_res_scale", "no_router_state", "no_skip", "no_bias", "no_gate",
    "fp8_conv"])
def test_each_dropped_term_fails(served, variant):
    cfg, params, prompts, _, got = served
    err = _worst(U.reference(), cfg, params, prompts, got, variant=(variant,))
    assert err > WRONG, (variant, err)


def test_precision_control_fails(served):
    cfg, params, prompts, _, got = served
    assert _worst(U.reference(), cfg, params, prompts, got, fp8=True) > WRONG


def test_router_state_is_carried_over_layers(model):
    """Zeroing the mixing vector ``g`` of every layer changes the logits:
    layer l + 1 really receives layer l's state."""
    cfg, params = model
    rng = np.random.default_rng(5)
    prompts = [rng.integers(1, 96, 18)]
    _, with_state = U.serve_logits(cfg, params, prompts, 3)
    ro = params["block"]["moe"]["router"]
    cut = dict(params, block=dict(params["block"], moe=dict(
        params["block"]["moe"], router=dict(ro, mix=jnp.zeros_like(
            ro["mix"])))))
    _, without = U.serve_logits(cfg, cut, prompts, 3)
    assert np.abs(with_state[0][1][0] - without[0][1][0]).max() > WRONG
    # and the program without it is the reference without it
    assert _worst(U.reference(), cfg, params, prompts, without,
                  variant=("no_router_state",)) < SOUND


def _layer(params, index=1):
    return jax.tree_util.tree_map(lambda a: a[index], params["block"])


def _layer_input(cfg, T=48, seed=7):
    x = jax.random.normal(jax.random.PRNGKey(seed), (T, cfg.d_model)) * 3.0
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + cfg.norm_eps)


def test_top1_with_the_skip_output(model):
    """A token whose argmax is the skip output gets nothing from the
    experts; the others get the chosen probability times ONE expert."""
    cfg, params = model
    p = _layer(params)
    h = _layer_input(cfg)
    r0 = jnp.zeros((h.shape[0], cfg.router_hidden))
    sel, w, r = expert_share.route_mlp(h, p["moe"]["router"], r0,
                                       cfg.norm_eps)
    assert sel.shape == w.shape == (48, 1) and r.shape == (48, 12)
    assert int(sel.max()) <= cfg.num_experts
    # push every token onto the skip: the layer adds exactly nothing
    ro = p["moe"]["router"]
    bias = ro["bias"].at[cfg.num_experts].set(10.0)
    moe = dict(p["moe"], router=dict(ro, bias=bias))
    y, sel_s, stats, _ = expert_share.sparse_ffn(
        h, moe, cfg, "ragged_dot", state=r0)
    assert (np.asarray(sel_s) == cfg.num_experts).all()
    assert float(jnp.abs(y).max()) == 0.0
    names = expert_share.stat_fields(cfg)
    assert names[-1] == "pairs_skipped" and len(names) == 6
    got = dict(zip(names, np.asarray(stats)))
    assert got["pairs_skipped"] == 48 and got["pairs_held"] == 0
    assert got["pairs_total"] == 48
    # half the tokens on the skip, by hand against the one chosen expert
    y, sel, stats, _ = expert_share.sparse_ffn(h, p["moe"], cfg,
                                               "ragged_dot", state=r0)
    sel, w = np.asarray(sel)[:, 0], np.asarray(w)
    t = int(np.flatnonzero(sel < cfg.num_experts)[0])
    ex = {n: p["moe"]["experts"][n]["kernel"][sel[t]] for n in
          ("wg", "wi", "wo")}
    want = (jax.nn.silu(h[t] @ ex["wg"]) * (h[t] @ ex["wi"])) @ ex["wo"]
    np.testing.assert_allclose(np.asarray(y[t]), np.asarray(want) * w[t],
                               atol=1e-5)
    assert dict(zip(names, np.asarray(stats)))["pairs_skipped"] \
        == int((sel == cfg.num_experts).sum())


def test_shares_add_up_to_the_whole_layer(model):
    """8 experts held as (0, 4) and (4, 4): the two shares' routed parts
    are the whole layer's, which is the reference's."""
    ref = U.reference()
    cfg, params = model
    p = _layer(params)
    x = jax.random.normal(jax.random.PRNGKey(9), (40, cfg.d_model)) * 3.0
    r0 = jax.random.normal(jax.random.PRNGKey(10), (40, cfg.router_hidden))
    h = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + cfg.norm_eps)
    whole, sel, _, r = expert_share.sparse_ffn(h, p["moe"], cfg, "ragged_dot",
                                               state=r0)
    total, pairs = 0.0, 0
    for first in (0, 4):
        share = U.tiny_config(held=(first, 4))
        moe = dict(p["moe"], experts={
            n: {"kernel": p["moe"]["experts"][n]["kernel"][first:first + 4]}
            for n in ("wg", "wi", "wo")})
        part, sel_p, stats, _ = expert_share.sparse_ffn(
            h, moe, share, "ragged_dot", state=r0)
        np.testing.assert_array_equal(np.asarray(sel_p), np.asarray(sel))
        total = total + part
        pairs += int(stats[0])
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               atol=1e-6)
    assert pairs == int((np.asarray(sel) < cfg.num_experts).sum())
    # the reference's layer on the same input, its residual scaling undone
    pr = dict(p, ln2={"scale": jnp.ones_like(p["ln2"]["scale"])})
    free = -jnp.ones((40, 1), jnp.int32)
    with jax.default_matmul_precision("highest"):
        want, r_ref, (own, _) = ref._experts(
            x, r0, pr, U.hp_of(cfg), frozenset(("no_res_bias", "no_res_scale")),
            False, free)
    np.testing.assert_array_equal(np.asarray(own), np.asarray(sel)[:, 0])
    np.testing.assert_allclose(np.asarray(x + whole), np.asarray(want),
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(r), np.asarray(r_ref), atol=1e-5)
    assert float(jnp.abs(whole).max()) > 0.1


def test_decode_and_one_token_prefill_agree_on_the_same_state(model):
    """A token decoded over a slot's cache and tail equals the same token
    prefilled as a one-token chunk that resumes from the tail."""
    cfg, params = model
    p = _layer(params, 0)
    bs, NB, T, slots = 4, 8, 13, 3
    hist = jax.random.normal(jax.random.PRNGKey(5), (1, 16, cfg.d_model))
    (k0, v0) = cca.new_state(cfg, 1 + NB, bs, slots, jnp.float32)
    pools = (k0.rows[0], v0[0], k0.tail[0], k0.vtail[0])
    table = jnp.arange(1, NB + 1, dtype=jnp.int32)
    base = {"rows": jnp.int32(0), "tail": jnp.int32(0),
            "index": jnp.int32(0)}

    def aux(T):
        return {"route": jnp.zeros((cfg.n_layers, T, 1), jnp.int32),
                "stats": None,
                "r": jnp.zeros((T, cfg.router_hidden), jnp.float32)}

    experts = {n: {"kernel": e["kernel"]}
               for n, e in p["moe"]["experts"].items()}
    moe = {k: v for k, v in p["moe"].items() if k != "experts"}
    p = dict(p, moe=moe)
    slot = jnp.int32(1)

    def prefill(x, pools, positions, n_valid):
        return cca.block_prefill(
            (x, aux(x.shape[1])), pools, table, positions, n_valid, slot, p,
            cfg, base, "gather", experts)

    def decode(xs, pools, tables, lengths, active):
        return cca.block_decode((xs, aux(3)), pools, tables, lengths, active,
                                p, cfg, base, "gather", experts)

    # one test, one call each: jitted for speed, not for reuse
    prefill, decode = jax.jit(prefill), jax.jit(decode)
    _, pools = prefill(hist, pools, jnp.arange(16, dtype=jnp.int32), T)
    # only slot 1's tail was written
    assert float(jnp.abs(pools[2][1]).max()) > 0.0
    assert float(jnp.abs(pools[2][0]).max()) == 0.0
    x = jax.random.normal(jax.random.PRNGKey(6), (1, 1, cfg.d_model))
    xs = jnp.concatenate([jnp.zeros_like(x), x, jnp.zeros_like(x)], 0)
    tables = jnp.stack([jnp.zeros_like(table), table, jnp.zeros_like(table)])
    (y_dec, _), after_d = decode(xs, pools, tables,
                                 jnp.asarray([0, T, 0], jnp.int32),
                                 jnp.asarray([False, True, False]))
    (y_pre, _), after_p = prefill(x, pools, jnp.asarray([T], jnp.int32), 1)
    np.testing.assert_allclose(np.asarray(y_dec[1, 0]),
                               np.asarray(y_pre[0, 0]), atol=2e-5)
    for a, b in zip(after_d, after_p):
        # idle slots wrote nothing but the trash block's row 0
        np.testing.assert_allclose(np.asarray(a)[1:], np.asarray(b)[1:],
                                   atol=1e-6)
    # an idle slot's tail is left alone by a decode step
    np.testing.assert_array_equal(np.asarray(after_d[2][0]),
                                  np.asarray(pools[2][0]))


def _parents_sparse_ffn(h, moe, cfg, impl, valid=None, mlp=None,
                        experts=None, layer=None):
    """``sparse_ffn`` as it stood before the router became data of the
    config (the linear sigmoid router, always a shared expert)."""
    with jax.named_scope("moe_router"):
        sel, w = expert_share.route(h, moe["router"], cfg.moe_k,
                                    cfg.routed_scaling,
                                    getattr(cfg, "n_group", 1),
                                    getattr(cfg, "topk_group", 1))
    with jax.named_scope("moe_experts"):
        routed, stats = expert_share.held_experts_ffn(
            h, moe["experts"] if experts is None else experts, sel, w,
            cfg.held, impl, valid, layer)
    with jax.named_scope("moe_shared"):
        shared = mlp(h, moe["shared"])
    return routed + shared, sel, stats


@pytest.mark.parametrize("family", ["exaone_moe", "dots_vlm"])
def test_linear_router_programs_lower_to_the_parents_text(family):
    """K-EXAONE's and dots' expert layer traces the program it traced
    before: the router's kind and the missing shared expert are Python
    branches on the config."""
    import importlib
    util = importlib.import_module(family + "_util")
    cfg = util.tiny_config()
    params = util.tiny_params(cfg)
    moe = jax.tree_util.tree_map(lambda a: a[0], params["block"]["moe"])
    h = jax.random.normal(jax.random.PRNGKey(1), (24, cfg.d_model))
    valid = jnp.arange(24) < 20

    def text(layer):
        def expert_layer(h, moe, valid):
            return layer(h, moe, cfg, "ragged_dot", valid, hybrid._swiglu)[:3]
        return jax.jit(expert_layer).lower(h, moe, valid).as_text()

    assert text(expert_share.sparse_ffn) == text(_parents_sparse_ffn)
    assert expert_share.stat_fields(cfg) == expert_share.STAT_FIELDS
    assert not expert_share.has_router_state(cfg) \
        and not cca.DIALECT.owns(cfg)


def test_counters_gauges_and_spans_with_telemetry(model):
    cfg, params = model
    rng = np.random.default_rng(1)
    srv, _ = U.serve_logits(cfg, params, [rng.integers(1, 96, 30)], 5,
                            telemetry=True)
    got = srv.read_expert_counters()
    pre, dec = got["prefill"], got["decode"]
    assert pre["pairs_total"] == 30 * cfg.n_layers
    assert dec["pairs_total"] == 4 * cfg.n_layers
    # the whole layer is held: a pair is on a held expert or on the skip
    assert pre["pairs_held"] + pre["pairs_skipped"] == pre["pairs_total"]
    assert dec["pairs_held"] + dec["pairs_skipped"] == dec["pairs_total"]
    text = str(srv.metrics.snapshot())
    assert "kv_cca_tail_bytes" in text and "moe_decode_pairs_skipped" in text
    tracer = srv.telemetry.tracer
    spans = tracer.spans("serve.prefill")
    assert [s[5]["start"] for s in spans] == [0, 16]
    assert [s[5]["tail"] for s in spans] == [0, 1]
    assert [s[5]["kv_tokens"] for s in tracer.spans("serve.decode")
            if s[5].get("live")] == [31, 32, 33, 34]


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
    with pytest.raises(ValueError, match=name + ".*convolutional"):
        ServingEngine(eng, num_slots=2, block_size=4, **kwargs)


@pytest.mark.parametrize("call,name", [
    (lambda e: e.generate(np.ones((1, 4), np.int32), max_new_tokens=2),
     "static-cache prefill"),
    (lambda e: e.forward(np.ones((1, 4), np.int32)), "cacheless forward"),
    (lambda e: e.prefill_into_slot(None, None, np.zeros(4, np.int32),
                                   np.zeros(4, np.int32), 0, 4),
     "prefill for a model"),       # no sampling lane: no slot to find
])
def test_static_cache_paths_raise_by_name(model, call, name):
    import deepspeed_tpu
    cfg, params = model
    eng = deepspeed_tpu.init_inference((cfg, params), dtype=jnp.float32)
    with pytest.raises(ValueError, match=name + ".*convolutional"):
        call(eng)


def test_tensor_parallel_raises_by_name(model):
    import deepspeed_tpu
    cfg, params = model
    with pytest.raises(ValueError, match="tensor parallelism.*convolutional"):
        deepspeed_tpu.init_inference((cfg, params), dtype=jnp.float32,
                                     mp_size=2)


def test_no_recompile_in_steady_state(model):
    from deepspeed_tpu.inference.serving import ServeRequest
    from deepspeed_tpu.utils.compile_guard import CompileWatch
    cfg, params = model
    rng = np.random.default_rng(2)
    srv, _ = U.serve_logits(cfg, params, [rng.integers(1, 96, 20),
                                          rng.integers(1, 96, 9)], 3)
    with CompileWatch(max_compiles=0, label="cca steady state"):
        for i, n in enumerate((33, 5, 17, 40)):
            srv.submit(ServeRequest(rid=f"s{i}", max_new_tokens=4,
                                    prompt=rng.integers(1, 96, n).astype(
                                        np.int32)))
        guard = 0
        while srv.busy:
            srv.step()
            guard += 1
            assert guard < 500


def test_kv_accounting_and_the_published_sizes():
    from deepspeed_tpu.inference import dialect, engine
    from deepspeed_tpu.models import gpt
    cfg = U.tiny_config()
    d, bf16 = dialect.of(cfg), jnp.bfloat16
    assert d is cca.DIALECT
    assert d.bytes_per_token(cfg, bf16) == 2 * 4 * 2 * 8 * 2
    assert d.slot_bytes(cfg, 4, bf16).cca_tail == 4 * 104 * 2
    assert engine.DIALECT.slot_bytes(gpt.GPTConfig(), 4, bf16).cca_tail == 0
    assert d.slot_bytes(cfg, 4, bf16).window == 0
    assert dialect.of(gpt.GPTConfig()) is engine.DIALECT
    # stage 0 of ZAYA1-8B as the benchmark runs it
    real = zaya.ZayaConfig(n_layers=20, n_heads=8, d_model=2048,
                           vocab_size=262272, max_seq_len=6144)
    assert real.head_dim == 128 and real.kv_heads == 2
    assert real.cca_channels == 1280 and real.rotary_channels == 64
    assert d.bytes_per_token(real, bf16) == 20480
    assert real.cca_tail_values * 2 == 5376            # 5.4 KB a layer a slot
    assert d.slot_bytes(real, 16, bf16).cca_tail * 40 == 4300800
    shapes = jax.eval_shape(
        lambda: zaya.init_params(jax.random.PRNGKey(0), real))
    n = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(shapes))
    assert n == 4_688_810_364, n          # 8.73 GiB in bf16
    with pytest.raises(AssertionError):
        U.tiny_config(n_shared_experts=1)
