"""The dots_vlm dialect (dots.vlm1's language model: latent attention,
experts routed inside groups, YaRN) on the paged serving path, held to the
benchmark's plain reference at small sizes: the latent pool served, the
controls, the counters and what raises. The blocks and kernels below it:
tests/test_latent_dots_vlm.py."""


import jax.numpy as jnp
import numpy as np
import pytest

import dots_vlm_util as U
from deepspeed_tpu.inference import latent
from deepspeed_tpu.models import dots_vlm

SOUND = 2e-4        # float32 program against the float32 reference
WRONG = 2e-2        # every control moves the logits by more than this


@pytest.fixture(scope="module")
def served():
    cfg = U.tiny_config()
    params = U.tiny_params(cfg)
    rng = np.random.default_rng(0)
    # past YaRN's original range (16), across chunk boundaries (16), one
    # of them not a multiple of the block (4)
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
    # one pool of padded latent rows, no V pool
    assert srv.cache.dialect is latent.DIALECT and srv.cache.v is None
    assert srv.cache.k.rows.shape == (4, srv.cache.num_blocks, 4, 128)
    assert cfg.latent_row == 20 and cfg.latent_lanes == 128
    assert srv.cache.bytes_per_token == 4 * 128 * 4


@pytest.mark.parametrize("variant", [
    "no_group_limit", "no_bias", "no_scale", "unnormalised", "wrong_held",
    "no_yarn", "no_mscale", "rotate_half", "no_q_norm", "no_kv_norm",
    "fp8_up"])
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


def test_counters_gauges_and_spans_with_telemetry():
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
    snap = srv.metrics.snapshot()
    text = str(snap)
    assert "kv_latent_pool_bytes" in text and "kv_latent_row_bytes" in text
    assert "moe_decode_pairs_held" in text
    tracer = srv.telemetry.tracer
    assert [s[5]["history"] for s in tracer.spans("serve.prefill")] == [0, 16]
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
def test_unsupported_serving_options_raise_by_name(kwargs, name):
    import deepspeed_tpu
    from deepspeed_tpu.inference.serving import ServingEngine
    cfg = U.tiny_config()
    eng = deepspeed_tpu.init_inference((cfg, U.tiny_params(cfg)),
                                       dtype=jnp.float32)
    with pytest.raises(ValueError, match=name + ".*latent"):
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
    with pytest.raises(ValueError, match=name + ".*latent"):
        call(eng)


def test_tensor_parallel_raises_by_name():
    import deepspeed_tpu
    cfg = U.tiny_config()
    with pytest.raises(ValueError, match="tensor parallelism.*latent"):
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
    with CompileWatch(max_compiles=0, label="latent steady state"):
        for i, n in enumerate((33, 5, 17, 40)):
            srv.submit(ServeRequest(rid=f"s{i}", max_new_tokens=4,
                                    prompt=rng.integers(1, 96, n).astype(
                                        np.int32)))
        guard = 0
        while srv.busy:
            srv.step()
            guard += 1
            assert guard < 500


def test_kv_accounting_counts_the_latent_row():
    from deepspeed_tpu.inference import dialect, engine
    from deepspeed_tpu.inference.paged_cache import PagedKVCache
    from deepspeed_tpu.models import gpt
    cfg = U.tiny_config()
    d = dialect.of(cfg)
    assert d.bytes_per_token(cfg, jnp.bfloat16) == 4 * 128 * 2
    assert d.slot_bytes(cfg, 4, jnp.bfloat16).window == 0
    assert gpt.decode_geometry(cfg, 4) == (24, 96)
    assert PagedKVCache(cfg, num_slots=2, block_size=4,
                        num_blocks=6).bytes_per_token == 4 * 128 * 2
    real = dots_vlm.DotsVLMConfig(n_layers=6, n_heads=128, d_model=7168)
    assert d.bytes_per_token(real, jnp.bfloat16) == 6 * 640 * 2
    assert d is latent.DIALECT \
        and dialect.of(gpt.GPTConfig()) is engine.DIALECT
    with pytest.raises(AssertionError):
        U.tiny_config(n_group=3)


def _tiles_of(prompts, layers, bs=4, chunk=16):
    """Latent layers x (occupied history blocks + the own tile), summed
    over the chunks of every prompt."""
    return layers * sum(-(-start // bs) + 1 for p in prompts
                        for start in range(0, len(p), chunk))


def test_prefill_tile_counters_follow_the_chunks_plan(served):
    """``serving_mla_prefill_tiles_{kernel,plain}_total``: off a TPU every
    flash step of the three prompts' eight chunks is a plain one."""
    cfg, _, prompts, srv, _ = served
    assert _tiles_of(prompts, cfg.n_layers) == 4 * (15 + 6 + 15)
    assert srv.stats["mla_prefill_tiles_plain_total"] == 144
    assert srv.stats["mla_prefill_tiles_kernel_total"] == 0
    assert srv.engine.mla_prefill_tiles(37, 4) == 4 * 11


def test_the_kernels_serve_what_the_portable_path_serves(
        served, pallas_interpret, monkeypatch):
    """``decode_impl`` "pallas" off a TPU, every Mosaic kernel interpreted
    (``mla_prefill`` in the prefill program, ``mla_decode`` in the decode
    program, the grouped products): the reference's logits, and the flash
    steps counted under ``kernel``."""
    cfg, params, prompts, _, _ = served
    monkeypatch.setenv("DS_PAGED_DECODE_IMPL", "pallas")
    srv, got = U.serve_logits(cfg, params, prompts, 7)
    assert srv.engine.decode_impl == "pallas"
    assert _worst(U.reference(), cfg, params, prompts, got) < SOUND
    assert srv.stats["mla_prefill_tiles_kernel_total"] == 144
    assert srv.stats["mla_prefill_tiles_plain_total"] == 0
