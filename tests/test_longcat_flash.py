"""The longcat_flash dialect (LongCat-Flash-Chat: shortcut-connected double
layers, two latent cache rows a layer, a softmax router over experts and
zero-compute identity experts) held to the benchmark's plain reference at
a small size, SERVED: chunked prefill then decode through the paged engine,
a slot reused, every wrong layer, router and scale told apart, the counters
and the routing record. One small model and ONE engine a module. The layer's
parts on their own (the two low-rank scales, the router's three data, the
zero-compute term, the share of 16, the counts):
tests/test_longcat_flash_router.py; the Mosaic kernels, interpreted:
tests/test_longcat_flash_kernels.py; the pool in the programs compiled for
a v5e: tests/test_longcat_flash_aot.py."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import longcat_flash_util as U
from deepspeed_tpu.inference import dialect, latent
from deepspeed_tpu.inference.hybrid import ffn_kind
from deepspeed_tpu.models import longcat_flash
from deepspeed_tpu.moe import expert_share

SOUND = 2e-4        # float32 program against the float32 reference
WRONG = 2e-2        # every control moves the logits by more than this
PAD = 64            # one reference shape (it is causal)


@pytest.fixture(scope="module")
def served():
    cfg = U.tiny_config()
    params = U.tiny_params(cfg)
    rng = np.random.default_rng(0)
    # across chunk boundaries (16), one not a multiple of the block (4);
    # two slots, so the third request REUSES a slot the first two held
    prompts = [rng.integers(1, 96, 37), rng.integers(1, 96, 21),
               rng.integers(1, 96, 48)]
    srv, got = U.serve_logits(cfg, params, prompts, 7, telemetry=True)
    return cfg, params, prompts, srv, got


def _worst(cfg, params, prompts, got, rids=None, **kw):
    ref, worst = U.reference(), 0.0
    for rid in (got if rids is None else rids):
        toks, lg = got[rid]
        padded = np.zeros((PAD,), np.int32)
        padded[:len(toks) - 1] = toks[:-1]
        want, _ = ref.logits(params, padded, U.hp_of(cfg), **kw)
        want = np.asarray(want)[len(prompts[rid]) - 1:len(toks) - 1]
        worst = max(worst, float(np.abs(lg - want).max()))
    return worst


def test_prefill_then_decode_matches_the_reference(served):
    """Chunked prefill then decode through ServingEngine against the
    reference's full forward pass, LOGITS not tokens; the third request
    runs in a slot a finished one held."""
    cfg, params, prompts, srv, got = served
    assert _worst(cfg, params, prompts, got) < SOUND
    assert srv.num_slots == 2 and len(got) == 3
    # ONE pool of padded latent rows, two rows a token a layer, no V pool
    assert srv.cache.dialect is latent.DIALECT and srv.cache.v is None
    assert srv.cache.k.rows.shape == (6, srv.cache.num_blocks, 4, 128)
    assert srv.cache.bytes_per_token == 6 * 128 * 4
    assert latent.DIALECT.ready_note(cfg) == ", latent rows a token: 6"


@pytest.mark.parametrize("variant", [
    "no_zero_term", "no_kv_scale", "no_q_scale", "sigmoid", "renormalised",
    "no_shortcut"])
def test_each_wrong_layer_router_and_scale_fails(served, variant):
    cfg, params, prompts, _, got = served
    err = _worst(cfg, params, prompts, got, rids=(2,), variant=(variant,))
    assert err > WRONG, (variant, err)


def test_precision_control_fails(served):
    cfg, params, prompts, _, got = served
    assert _worst(cfg, params, prompts, got, rids=(2,), fp8=True) > WRONG


def test_the_dialect_is_the_latent_one_with_two_rows_a_layer():
    """No new cache dialect: the latent record owns the config; what
    differs is data (the sublayers a layer, the pool's offsets)."""
    cfg = U.tiny_config()
    assert dialect.of(cfg) is latent.DIALECT
    assert cfg.n_full_layers == 6 and cfg.n_sparse_layers == 3
    assert latent.kv_bytes_per_token(cfg, jnp.bfloat16) == 6 * 128 * 2
    assert latent.flash_steps(cfg, 20, 4) == 6 * (5 + 1)
    dense, sparse = longcat_flash.layer_bases(cfg, 10)
    assert dense["rows"].shape == (0, 2)
    np.testing.assert_array_equal(np.asarray(sparse["rows"]),
                                  [[0, 10], [20, 30], [40, 50]])
    np.testing.assert_array_equal(np.asarray(sparse["index"]), [0, 1, 2])
    layer = jax.tree_util.tree_map(lambda a: a[0],
                                   U.tiny_params(cfg)["block"])
    assert ffn_kind(layer) == "both" and ffn_kind(layer["a"]) == "dense"
    assert ffn_kind({"ln2": 0, "moe": 0}) == "sparse"
    real = longcat_flash.LongcatFlashConfig(n_layers=4, n_heads=64,
                                            d_model=6144)
    assert latent.kv_bytes_per_token(real, jnp.bfloat16) == 10240


def test_counters_gauges_and_the_routing_record(served):
    cfg, _, prompts, srv, _ = served
    got = srv.read_expert_counters()
    pre, dec = got["prefill"], got["decode"]
    assert tuple(pre) == expert_share.stat_fields(cfg)
    assert pre["pairs_total"] == sum(map(len, prompts)) * cfg.moe_k * 3
    assert dec["pairs_total"] == 3 * 6 * cfg.moe_k * 3
    for c in (pre, dec):
        assert 0 < c["pairs_zero"] < c["pairs_total"]
        assert c["pairs_held"] + c["pairs_zero"] <= c["pairs_total"]
        assert 0 < c["real_pairs_max_token"] <= cfg.moe_k * c["layer_calls"]
    text = str(srv.metrics.snapshot())
    assert "moe_decode_pairs_zero" in text \
        and "moe_prefill_pairs_zero" in text
    route = np.asarray(srv.cache.k.route)
    assert route.shape == (3, 2, cfg.moe_k)                     # a decode
    assert route.min() >= 0 and route.max() < 16 + 8
