"""The qwen3_next configuration (Qwen3-Next: Gated DeltaNet linear
attention whose per-slot recurrent state lives beside the K/V pools of
output-gated full attention, the rule and the paged kind chosen apart) on
the paged serving path, held to the benchmark's plain reference at small
sizes: chunked prefill and decode, slots shared and reused, every line the
published keys do not pin, the shares, the gate's counter and what raises."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import qwen3_next_util as U
from deepspeed_tpu.inference import dialect, linear
from deepspeed_tpu.moe import expert_share

# float32 program against the float32 reference: sums in another order
# (chunkwise against token by token, tiles against whole rows) over logits
# of size 3
SOUND = 2e-4
WRONG = 2e-3        # every variant moves the logits by more than this


@pytest.fixture(scope="module")
def model():
    cfg = U.tiny_config()
    return cfg, U.tiny_params(cfg)


@pytest.fixture(scope="module")
def served(model):
    cfg, params = model
    rng = np.random.default_rng(0)
    # across chunk boundaries (16), one of them not a multiple of the block;
    # three requests over two slots: two share the batch, one slot is reused
    prompts = [rng.integers(1, 96, 37), rng.integers(1, 96, 21),
               rng.integers(1, 96, 48)]
    srv, got = U.serve_logits(cfg, params, prompts, 7, telemetry=True)
    return cfg, params, prompts, srv, got


def _worst(ref, cfg, params, prompts, got, only=None, **kw):
    worst = 0.0
    for rid, (toks, lg) in got.items():
        if only is not None and rid != only:
            continue
        want, _ = ref.logits(params, toks[:-1], U.hp_of(cfg), **kw)
        want = np.asarray(want)[len(prompts[rid]) - 1:]
        err = float(np.abs(lg - want).max())
        worst = max(worst, err if np.isfinite(err) else float("inf"))
    return worst


def test_prefill_then_decode_matches_the_reference(served):
    cfg, params, prompts, srv, got = served
    assert _worst(U.reference(), cfg, params, prompts, got) < SOUND
    # the K and V pools of the 2 full layers, and beside them the 6 linear
    # layers' per-slot state and convolution tails
    k = srv.cache.k
    assert isinstance(k, linear.LinearState) and srv.cache.v is not None
    assert k.rows.shape == srv.cache.v.shape \
        == (2, srv.cache.num_blocks, 4, 2 * 16)
    assert k.state.shape == (6, 2, 4, 8, 8) and k.state.dtype == jnp.float32
    assert k.tail.shape == (6, 2, 3 * 64)
    assert srv.cache.recurrent_state_bytes == 6 * 2 * 4 * 8 * 8 * 4
    assert srv.cache.conv_tail_bytes == 6 * 2 * 3 * 64 * 4
    assert srv.cache.bytes_per_token == 2 * 2 * 32 * 4
    assert dialect.of(cfg) is linear.DIALECT


def test_state_crosses_chunk_borders_and_a_reused_slot_starts_clean(model):
    """Chunks of 7 cut a sequence at every offset modulo the convolution's
    4 taps, the recurrent state goes from chunk to chunk through the state
    buffer, a prompt shorter than the taps leaves a tail that is part
    zeros, and the first decode step resumes from both. ONE slot serves the
    three requests one after the other."""
    cfg, params = model
    rng = np.random.default_rng(7)
    prompts = [rng.integers(1, 96, 30), rng.integers(1, 96, 2),
               rng.integers(1, 96, 23)]
    srv, got = U.serve_logits(cfg, params, prompts, 4, prefill_chunk=7,
                              block_size=4, num_slots=1)
    assert _worst(U.reference(), cfg, params, prompts, got) < SOUND
    assert float(jnp.abs(srv.cache.k.state).max()) > 0.0
    assert float(jnp.abs(srv.cache.k.tail).max()) > 0.0


@pytest.mark.parametrize("variant", [
    "norm_plain_scale", "gated_norm_offset", "no_attn_gate",
    "gate_before_attention", "rotary_all_channels", "rotary_interleaved",
    "no_qk_norm", "no_q_scale", "no_l2", "beta_linear",
    "decay_then_write_swapped", "key_heads_tiled", "shared_gate_off",
    "softmax_unnormalised", "sigmoid_router", "state_bf16"])
def test_each_reference_variant_is_told_apart(served, variant):
    cfg, params, prompts, _, got = served
    assert _worst(U.reference(), cfg, params, prompts, got, only=0,
                  variant=(variant,)) > WRONG


def test_precision_control_fails(served):
    cfg, params, prompts, _, got = served
    assert _worst(U.reference(), cfg, params, prompts, got, only=0,
                  fp8=True) > WRONG


def test_forced_routing_is_the_references_own_where_they_agree(served):
    cfg, params, prompts, _, got = served
    ref = U.reference()
    toks, lg = got[1]
    want, route = ref.logits(params, toks[:-1], U.hp_of(cfg))
    again, _ = ref.logits(params, toks[:-1], U.hp_of(cfg),
                          forced=np.asarray(route["sel"]), first=20, rows=3)
    np.testing.assert_allclose(np.asarray(again), np.asarray(want)[20:23],
                               atol=1e-6)
    assert route["biased"].shape == (8, len(toks) - 1, 8)


def test_shared_gate_counter_and_gauges(served):
    cfg, _, _, srv, _ = served
    assert expert_share.stat_fields(cfg)[-1] == "shared_gate_q8"
    assert "shared_gate_q8" not in expert_share.stat_fields(
        __import__("kimi_linear_util").tiny_config())
    counters = srv.read_expert_counters()
    for phase in ("prefill", "decode"):
        assert counters[phase]["shared_gate_q8"] > 0
        mean = srv.metrics.gauge(f"moe_{phase}_shared_gate_mean", "").value
        # a sigmoid of a zero-mean score: about a half
        assert 0.2 < mean < 0.8, mean


def test_all_sixteen_shares_add_up_to_the_whole_layer():
    """16 experts held one a share: the shares' routed parts, the gated
    shared expert counted once, are the uncut reference layer."""
    from deepspeed_tpu.inference import hybrid
    ref = U.reference()
    cfg = U.tiny_config(num_experts=16)
    params = U.tiny_params(cfg)
    p = jax.tree_util.tree_map(lambda a: a[2], params["block"])
    x = jax.random.normal(jax.random.key(9), (40, cfg.d_model)) * 3.0
    h = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + cfg.norm_eps)
    whole, sel, _, _ = expert_share.sparse_ffn(
        h, p["moe"], cfg, "ragged_dot", mlp=hybrid._swiglu)
    none = U.tiny_config(num_experts=16, experts_held=(0, 1))
    empty = {n: {"kernel": p["moe"]["experts"][n]["kernel"][:1] * 0.0}
             for n in ("wg", "wi", "wo")}
    shared, _, _, _ = expert_share.sparse_ffn(
        h, dict(p["moe"], experts=empty), none, "ragged_dot",
        mlp=hybrid._swiglu)
    total = shared
    for first in range(16):
        share = U.tiny_config(num_experts=16, experts_held=(first, 1))
        moe = dict(p["moe"], experts={
            n: {"kernel": p["moe"]["experts"][n]["kernel"][first:first + 1]}
            for n in ("wg", "wi", "wo")})
        part, sel_p, _, _ = expert_share.sparse_ffn(
            h, moe, share, "ragged_dot", mlp=hybrid._swiglu)
        np.testing.assert_array_equal(np.asarray(sel_p), np.asarray(sel))
        total = total + (part - shared)
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               atol=1e-5)
    # the reference's layer, its norm's stored offset at zero
    pr = dict(p, ln2={"scale": jnp.zeros_like(p["ln2"]["scale"])})
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


def test_static_paths_and_tensor_parallelism_raise_by_name(model):
    import deepspeed_tpu
    cfg, params = model
    eng = deepspeed_tpu.init_inference((cfg, params), dtype=jnp.float32)
    with pytest.raises(ValueError,
                       match="static-cache prefill.*linear-attention"):
        eng.generate(np.ones((1, 4), np.int32), max_new_tokens=2)
    with pytest.raises(ValueError,
                       match="tensor parallelism.*linear-attention"):
        deepspeed_tpu.init_inference((cfg, params), dtype=jnp.float32,
                                     mp_size=2)


def test_no_recompile_in_steady_state(served):
    from deepspeed_tpu.inference.serving import ServeRequest
    from deepspeed_tpu.utils.compile_guard import CompileWatch
    _, _, _, srv, _ = served
    rng = np.random.default_rng(2)
    with CompileWatch(max_compiles=0, label="qwen3_next steady state"):
        for i, n in enumerate((33, 5, 17, 40)):
            srv.submit(ServeRequest(rid=f"s{i}", max_new_tokens=4,
                                    prompt=rng.integers(1, 96, n).astype(
                                        np.int32)))
        guard = 0
        while srv.busy:
            srv.step()
            guard += 1
            assert guard < 500


def test_the_chunk_kernel_serves_what_the_xla_form_serves(
        monkeypatch, pallas_interpret):
    """Linear heads of 128 (the kernel's: a head is the 128 lanes), two
    value heads a key head as published, ``decode_impl="pallas"`` off a TPU
    with the kernels interpreted: the prefill chunks go through ONE
    ``gdn_chunk`` kernel a layer (chunks of 16 tokens, padded to its
    sub-chunk of 64; the state handed from chunk to chunk and on to the
    step kernel), and every logit is the XLA form's and the reference's."""
    cfg = U.tiny_config(n_layers=4, linear_head_dim=128)
    params = U.tiny_params(cfg)
    assert linear._ready_note(cfg, "pallas") == ", gdn_chunk=mosaic"
    assert linear._ready_note(cfg, "gather") == ", gdn_chunk=xla"
    assert linear._ready_note(U.tiny_config(), "pallas") == ", gdn_chunk=xla"
    rng = np.random.default_rng(5)
    prompts = [rng.integers(1, 96, 37), rng.integers(1, 96, 21)]
    _, want = U.serve_logits(cfg, params, prompts, 4)
    monkeypatch.setenv("DS_PAGED_DECODE_IMPL", "pallas")
    srv, got = U.serve_logits(cfg, params, prompts, 4)
    assert srv.decode_impl == "pallas"
    for rid in want:
        np.testing.assert_array_equal(got[rid][0], want[rid][0])
        np.testing.assert_allclose(got[rid][1], want[rid][1], atol=SOUND)
    assert _worst(U.reference(), cfg, params, prompts, got) < SOUND
