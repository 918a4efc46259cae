"""The exaone_moe dialect (K-EXAONE) on the paged serving path, held to the
benchmark's plain reference at small sizes: the expert share, two kinds of
attention state, the controls and what raises."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import exaone_moe_util as U
from deepspeed_tpu.inference import hybrid
from deepspeed_tpu.models import exaone_moe
from deepspeed_tpu.moe import expert_share

SOUND = 2e-4        # float32 program against the float32 reference
WRONG = 2e-2        # every control moves the logits by more than this


def _layer_input(cfg, T=24, seed=3):
    return jax.random.normal(jax.random.PRNGKey(seed), (T, cfg.d_model))


def test_shares_add_up_to_the_whole_layer():
    """8 experts in 4 shares of 2: the routed parts of all shares plus the
    shared expert counted once are the uncut reference's whole layer."""
    ref = U.reference()
    whole = U.tiny_config(held=None)
    params = U.tiny_params(whole)
    p = jax.tree_util.tree_map(lambda a: a[2], params["block"])
    h = _layer_input(whole)
    sel, w = expert_share.route(h, p["moe"]["router"], whole.moe_k,
                                whole.routed_scaling)
    total = hybrid._swiglu(h, p["moe"]["shared"])
    for first in range(0, 8, 2):
        share = {n: {"kernel": p["moe"]["experts"][n]["kernel"]
                     [first:first + 2]} for n in ("wg", "wi", "wo")}
        part, stats = expert_share.held_experts_ffn(
            h, share, sel, w, (first, 2), "ragged_dot")
        total = total + part
        assert int(stats[1]) == h.shape[0] * whole.moe_k
        assert int(stats[0]) == int(jnp.sum((sel >= first)
                                            & (sel < first + 2)))
    # the reference's layer on a pre-normed input: x + routed + shared
    pr = dict(p, ln2={"scale": jnp.ones_like(p["ln2"]["scale"])})
    hp = U.hp_of(whole)
    x = h * 3.0
    xn = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + hp["eps"])
    free = -jnp.ones((x.shape[0], whole.moe_k), jnp.int32)
    with jax.default_matmul_precision("highest"):
        want, _ = ref._sparse_ffn(x, pr, hp, frozenset(), False, free)
    sel_n, w_n = expert_share.route(xn, p["moe"]["router"], whole.moe_k,
                                    whole.routed_scaling)
    got = hybrid._swiglu(xn, p["moe"]["shared"])
    for first in range(0, 8, 2):
        share = {n: {"kernel": p["moe"]["experts"][n]["kernel"]
                     [first:first + 2]} for n in ("wg", "wi", "wo")}
        got = got + expert_share.held_experts_ffn(
            xn, share, sel_n, w_n, (first, 2), "ragged_dot")[0]
    np.testing.assert_allclose(np.asarray(x + got), np.asarray(want),
                               atol=1e-4)
    assert float(jnp.abs(total).max()) > 0.1


def test_absent_experts_and_padded_tokens_add_nothing():
    cfg = U.tiny_config(held=(6, 2))
    p = jax.tree_util.tree_map(lambda a: a[0], U.tiny_params(cfg)["block"])
    h = _layer_input(cfg)
    sel = jnp.tile(jnp.asarray([[0, 1, 2]], jnp.int32), (h.shape[0], 1))
    w = jnp.ones(sel.shape, jnp.float32)
    out, stats = expert_share.held_experts_ffn(
        h, p["moe"]["experts"], sel, w, cfg.held, "ragged_dot")
    assert float(jnp.abs(out).max()) == 0.0 and int(stats[0]) == 0
    sel = sel.at[:, 0].set(7)
    valid = jnp.arange(h.shape[0]) < 5
    out, stats = expert_share.held_experts_ffn(
        h, p["moe"]["experts"], sel, w, cfg.held, "ragged_dot", valid)
    assert float(jnp.abs(out[5:]).max()) == 0.0
    assert float(jnp.abs(out[:5]).max()) > 0.0
    assert [int(v) for v in stats] == [5, 15, 5, 1, 1]


@pytest.fixture(scope="module")
def served():
    cfg = U.tiny_config()
    params = U.tiny_params(cfg)
    rng = np.random.default_rng(0)
    # longer than the window (8), across a chunk boundary (16), one of
    # them not a multiple of the block (4)
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
    # the rings stayed what they were sized as
    RB = exaone_moe.window_blocks(cfg, 4)
    assert RB == 3
    assert srv.cache.k.win.shape[:2] == (6, 1 + 2 * RB)
    assert srv.cache.k.full.shape[0] == 2
    assert srv.cache.window_bytes == 2 * 6 * 2 * RB * 4 * 2 * 16 * 4
    assert srv.cache.bytes_per_token == 2 * 2 * 2 * 16 * 4


def test_bounded_window_state_equals_whole_history(served, monkeypatch):
    cfg, params, prompts, _, got = served
    whole = lambda cfg, bs: -(-cfg.max_seq_len // bs)
    monkeypatch.setattr(exaone_moe, "window_blocks", whole)
    monkeypatch.setattr(hybrid, "window_blocks", whole)
    srv, got_whole = U.serve_logits(cfg, params, prompts, 7)
    assert srv.cache.ring_blocks == 24
    for rid in got:
        np.testing.assert_array_equal(got[rid][0], got_whole[rid][0])
        np.testing.assert_allclose(got[rid][1], got_whole[rid][1], atol=2e-5)


# rows of 1,024 at blocks of 16 are 8 tiles of 128; the window's ring (2
# blocks) is shorter than a tile: ONE length
@pytest.fixture(scope="module")
def edges():
    cfg = U.tiny_config(max_seq_len=1024)
    params = U.tiny_params(cfg)
    return cfg, params, U.serve_edges(cfg, params)


@pytest.mark.parametrize("rid", range(len(U.EDGES)), ids=list(U.EDGES))
def test_a_chunk_attends_the_tiles_it_sees_as_the_whole_row(edges, rid):
    cfg, params, served = edges
    U.assert_tiles_as_whole(served, rid)
    prompts, _, (got, _), _ = served
    assert _worst(U.reference(), cfg, params, prompts, {rid: got[rid]}) \
        < SOUND


def test_only_the_longest_branch_gathers_a_whole_row():
    """The full layers' reads of pool blocks lie in the branches of a
    ``lax.switch``, a tile more in each; a ring shorter than a tile is
    read whole, outside any switch, beside the 4 windows of a block in
    which write_chunk lays the chunk's rows. One body for the leading
    dense layer and one for the sparse layers."""
    import program_text as PT
    cfg = U.tiny_config(max_seq_len=1024)
    params = jax.eval_shape(lambda: U.tiny_params(cfg))
    jaxpr = PT.serving_programs(cfg, params, C=40, bs=16, NB=64)
    outside, switches = U.gathered_blocks(
        jaxpr["prefill_slot"], (16, cfg.kv_heads * cfg.head_dim))
    assert outside == [4, 4, 2, 2] * 2
    assert switches == [[[8 * n] * 2 for n in range(1, 9)]] * 2


@pytest.mark.parametrize("variant", [
    "softmax_router", "no_scale", "unnormalised", "bias_in_weights",
    "no_bias", "wrong_held", "rotary_on_full", "no_qk_norm"])
def test_each_wrong_router_and_attention_fails(served, variant):
    cfg, params, prompts, _, got = served
    err = _worst(U.reference(), cfg, params, prompts, got, variant=(variant,))
    assert err > WRONG, (variant, err)


def test_precision_control_fails(served):
    cfg, params, prompts, _, got = served
    assert _worst(U.reference(), cfg, params, prompts, got, fp8=True) > WRONG


def test_last_dispatch_routing_is_kept_with_the_state(served):
    cfg, params, prompts, srv, _ = served
    route = np.asarray(srv.cache.k.route)
    assert route.shape == (cfg.n_sparse_layers, 2, cfg.moe_k)   # a decode
    assert route.min() >= 0 and route.max() < cfg.num_experts
    assert srv.cache.k.stats is None          # telemetry off: no counters


def test_counters_accumulate_on_the_device_with_telemetry():
    cfg = U.tiny_config()
    params = U.tiny_params(cfg)
    rng = np.random.default_rng(1)
    srv, _ = U.serve_logits(cfg, params, [rng.integers(1, 96, 30)], 5,
                            telemetry=True)
    got = srv.read_expert_counters()
    pre, dec = got["prefill"], got["decode"]
    assert pre["pairs_total"] == 30 * cfg.moe_k * cfg.n_sparse_layers
    assert dec["pairs_total"] == 4 * cfg.moe_k * cfg.n_sparse_layers
    assert 0 < pre["pairs_held"] < pre["pairs_total"]
    assert dec["layer_calls"] == 4 * cfg.n_sparse_layers
    assert dec["experts_touched"] <= dec["pairs_held"]
    snap = srv.metrics.snapshot()
    text = str(snap)
    assert "moe_decode_pairs_held" in text
    assert "kv_window_state_bytes" in text and "kv_full_pool_bytes" in text


@pytest.mark.parametrize("kwargs,name", [
    (dict(prefix_cache=True), "prefix sharing"),
    (dict(prefix_cache=True, host_tier=True), "prefix sharing"),
    (dict(kv_quant="int8"), "int8 KV pools"),
    (dict(spec_decode=True), "speculative decoding"),
    (dict(decode_horizon=4), "fused decode horizon"),
    (dict(lora_serve=True), "LoRA serving"),
])
def test_unsupported_serving_options_raise_by_name(kwargs, name):
    import deepspeed_tpu
    from deepspeed_tpu.inference.serving import ServingEngine
    cfg = U.tiny_config()
    eng = deepspeed_tpu.init_inference((cfg, U.tiny_params(cfg)),
                                       dtype=jnp.float32)
    with pytest.raises(ValueError, match=name):
        ServingEngine(eng, num_slots=2, block_size=4, **kwargs)


@pytest.mark.parametrize("call,name", [
    (lambda e: e.generate(np.ones((1, 4), np.int32), max_new_tokens=2),
     "static-cache prefill"),
    (lambda e: e.generate_fused(np.ones((1, 4), np.int32), max_new_tokens=2),
     "static-cache prefill"),
    (lambda e: e.forward(np.ones((1, 4), np.int32)), "cacheless forward"),
])
def test_static_cache_paths_raise_by_name(call, name):
    import deepspeed_tpu
    cfg = U.tiny_config()
    eng = deepspeed_tpu.init_inference((cfg, U.tiny_params(cfg)),
                                       dtype=jnp.float32)
    with pytest.raises(ValueError, match=name):
        call(eng)


def test_tensor_parallel_raises_by_name():
    import deepspeed_tpu
    cfg = U.tiny_config()
    with pytest.raises(ValueError, match="tensor parallelism"):
        deepspeed_tpu.init_inference((cfg, U.tiny_params(cfg)),
                                     dtype=jnp.float32, mp_size=2)


def test_no_recompile_in_steady_state():
    from deepspeed_tpu.inference.serving import ServeRequest
    from deepspeed_tpu.utils.compile_guard import CompileWatch
    cfg = U.tiny_config()
    params = U.tiny_params(cfg)
    rng = np.random.default_rng(2)
    srv, _ = U.serve_logits(cfg, params, [rng.integers(1, 96, 20),
                                          rng.integers(1, 96, 9)], 3)
    with CompileWatch(max_compiles=0, label="hybrid steady state"):
        for i, n in enumerate((33, 5, 17, 40)):
            srv.submit(ServeRequest(rid=f"s{i}", max_new_tokens=4,
                                    prompt=rng.integers(1, 96, n).astype(
                                        np.int32)))
        guard = 0
        while srv.busy:
            srv.step()
            guard += 1
            assert guard < 500


def test_head_size_and_kv_accounting_by_layer_kind():
    from deepspeed_tpu.inference import dialect
    from deepspeed_tpu.models import gpt
    cfg = U.tiny_config()
    assert cfg.head_dim == 16 and cfg.d_model // cfg.n_heads == 8
    assert cfg.qkv_dim == (4 + 2 * 2) * 16
    d = dialect.of(cfg)
    assert d.bytes_per_token(cfg, jnp.bfloat16) == 2 * 2 * 2 * 16 * 2
    assert d.slot_bytes(cfg, 4, jnp.bfloat16).window \
        == 2 * 6 * 3 * 4 * 2 * 16 * 2
    dense = gpt.GPTConfig(n_layers=3, n_heads=4, d_model=32)
    assert dialect.of(dense).slot_bytes(dense, 4, jnp.bfloat16).window == 0
    assert gpt.kv_bytes_per_token(dense) == 2 * 3 * 4 * 8 * 2
    odd = gpt.GPTConfig(n_layers=1, n_heads=3, d_model=32, head_size=16)
    assert odd.head_dim == 16
    with pytest.raises(AssertionError):
        dataclasses.replace(cfg, layer_kinds=("full",))


def test_causal_band_is_the_one_mask():
    s = jnp.zeros((4, 6))
    kpos = jnp.arange(6)[None, :]
    qpos = jnp.asarray([0, 2, 4, 5])[:, None]
    seen = np.asarray(hybrid.causal_band(s, kpos, qpos, 3)) == 0
    want = np.asarray([[1, 0, 0, 0, 0, 0], [1, 1, 1, 0, 0, 0],
                       [0, 0, 1, 1, 1, 0], [0, 0, 0, 1, 1, 1]], bool)
    np.testing.assert_array_equal(seen, want)
    full = np.asarray(hybrid.causal_band(s, kpos, qpos)) == 0
    np.testing.assert_array_equal(full, np.tril(np.ones((6, 6), bool))
                                  [[0, 2, 4, 5]])
