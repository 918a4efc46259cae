"""Flash-attention kernel parity tests vs pure-jnp reference
(ref: tests/unit/test_cuda_forward.py / test_cuda_backward.py — kernel
parity within tolerances). Runs in pallas interpret mode on CPU; the same
code compiles for TPU."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.attention import flash as F


def _rand_qkv(B=2, S=256, H=4, D=64, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    shape = (B, S, H, D)
    q = jax.random.normal(ks[0], shape, dtype)
    k = jax.random.normal(ks[1], shape, dtype)
    v = jax.random.normal(ks[2], shape, dtype)
    return q, k, v


@pytest.fixture(autouse=True)
def _interpret_mode(pallas_interpret):
    """Force pallas interpret mode on CPU (shared conftest fixture)."""
    yield


@pytest.mark.parametrize("causal", [True, False])
def test_forward_parity(devices, causal):
    q, k, v = _rand_qkv()
    out = F.flash_attention(q, k, v, causal=causal, block_q=128, block_kv=128)
    ref = F.mha_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_forward_multi_block(devices):
    q, k, v = _rand_qkv(S=512)
    out = F.flash_attention(q, k, v, causal=True, block_q=128, block_kv=128)
    ref = F.mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_head_dim_padding(devices):
    """D=64 < 128 lanes must be padded transparently."""
    q, k, v = _rand_qkv(D=64)
    out = F.flash_attention(q, k, v, causal=True, block_q=128, block_kv=128)
    ref = F.mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("causal", [True, False])
def test_backward_parity(devices, causal):
    q, k, v = _rand_qkv(B=1, S=256, H=2, D=64)

    def f_flash(q, k, v):
        return jnp.sum(F.flash_attention(q, k, v, causal=causal,
                                         block_q=128, block_kv=128) ** 2)

    def f_ref(q, k, v):
        return jnp.sum(F.mha_reference(q, k, v, causal=causal) ** 2)

    g_flash = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                   rtol=2e-3, atol=2e-3, err_msg=name)


def test_bf16_forward(devices):
    q, k, v = _rand_qkv(dtype=jnp.bfloat16)
    out = F.flash_attention(q, k, v, causal=True, block_q=128, block_kv=128)
    ref = F.mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=5e-2, atol=5e-2)


@pytest.mark.parametrize("causal", [False, True])
def test_kv_mask_forward_parity(devices, causal):
    q, k, v = _rand_qkv(B=2, S=256, H=2, D=32)
    rng = np.random.default_rng(0)
    kv_mask = jnp.asarray((rng.random((2, 256)) > 0.25).astype(np.float32))
    out = F.flash_attention(q, k, v, causal=causal, block_q=128,
                            block_kv=128, kv_mask=kv_mask)
    ref = F.mha_reference(q, k, v, causal=causal, kv_mask=kv_mask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


def test_kv_mask_grads_parity(devices):
    q, k, v = _rand_qkv(B=1, S=256, H=2, D=32, seed=3)
    rng = np.random.default_rng(1)
    kv_mask = jnp.asarray((rng.random((1, 256)) > 0.3).astype(np.float32))
    # loss masks padded QUERY rows (standard contract)
    row_w = kv_mask[..., None, None]

    def loss_flash(q, k, v):
        o = F.flash_attention(q, k, v, causal=False, block_q=128,
                              block_kv=128, kv_mask=kv_mask)
        return ((o * row_w) ** 2).sum()

    def loss_ref(q, k, v):
        o = F.mha_reference(q, k, v, causal=False, kv_mask=kv_mask)
        return ((o * row_w) ** 2).sum()

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-3, atol=5e-3)


def test_encoder_layer_masked_flash_path(devices, monkeypatch):
    """The encoder attention core with a padding mask matches its jnp
    path when routed through the (interpret-mode) flash kernel — and the
    flash path must actually be TAKEN (the core's TPU gate is patched
    open; off a TPU the core stays on its jnp path)."""
    monkeypatch.setattr("deepspeed_tpu.utils.on_tpu", lambda: True)
    from deepspeed_tpu.ops.attention import flash as flash_mod
    from deepspeed_tpu.ops.transformer.encoder_layer import (
        DeepSpeedTransformerConfig, _attention_core)
    cfg = DeepSpeedTransformerConfig(hidden_size=64, heads=2,
                                     attn_dropout_ratio=0.0,
                                     hidden_dropout_ratio=0.0,
                                     num_hidden_layers=1)
    B, S, H, D = 2, 128, 2, 32
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (jax.random.normal(kk, (B, S, H, D), jnp.float32) for kk in ks)
    mask = jnp.asarray(
        (np.random.default_rng(0).random((B, S)) > 0.2).astype(np.float32))

    calls = []
    orig = flash_mod.flash_attention

    def recording(*a, **kw):
        calls.append(kw.get("kv_mask") is not None)
        return orig(*a, **kw)

    monkeypatch.setattr(flash_mod, "flash_attention", recording)
    with_flash = _attention_core(q, k, v, mask, cfg, None, True,
                                 allow_flash=True)
    assert calls == [True], "masked flash path was not taken"
    no_flash = _attention_core(q, k, v, mask, cfg, None, True,
                               allow_flash=False)
    valid = np.asarray(mask)[:, :, None, None] > 0
    np.testing.assert_allclose(np.asarray(with_flash) * valid,
                               np.asarray(no_flash) * valid,
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("causal", [True, False])
def test_gqa_forward_parity(devices, causal):
    """Grouped-query attention: 4 q heads sharing 2 kv heads == the
    repeated-kv dense reference."""
    q, _, _ = _rand_qkv(B=2, S=256, H=4, D=32)
    ks = jax.random.split(jax.random.PRNGKey(7), 2)
    k = jax.random.normal(ks[0], (2, 256, 2, 32), jnp.float32)
    v = jax.random.normal(ks[1], (2, 256, 2, 32), jnp.float32)
    out = F.flash_attention(q, k, v, causal=causal, block_q=128,
                            block_kv=128)
    ref = F.mha_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("causal", [True, False])
def test_gqa_grads_parity(devices, causal):
    q, _, _ = _rand_qkv(B=1, S=256, H=4, D=32, seed=8)
    ks = jax.random.split(jax.random.PRNGKey(9), 2)
    k = jax.random.normal(ks[0], (1, 256, 2, 32), jnp.float32)
    v = jax.random.normal(ks[1], (1, 256, 2, 32), jnp.float32)

    def loss_f(q, k, v):
        return (F.flash_attention(q, k, v, causal=causal, block_q=128,
                                  block_kv=128) ** 2).sum()

    def loss_r(q, k, v):
        return (F.mha_reference(q, k, v, causal=causal) ** 2).sum()

    gf = jax.grad(loss_f, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_r, argnums=(0, 1, 2))(q, k, v)
    for a, b, n in zip(gf, gr, "qkv"):
        assert a.shape == b.shape, n
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-3, atol=5e-3, err_msg=n)


@pytest.mark.parametrize("window", [32, 100, 256])
def test_sliding_window_forward_parity(devices, window):
    q, k, v = _rand_qkv(B=1, S=512, H=2, D=32)
    out = F.flash_attention(q, k, v, causal=True, block_q=128,
                            block_kv=128, window=window)
    ref = F.mha_reference(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


def test_sliding_window_grads_parity(devices):
    q, k, v = _rand_qkv(B=1, S=512, H=2, D=32, seed=11)
    W = 96

    def loss_f(q, k, v):
        return (F.flash_attention(q, k, v, causal=True, block_q=128,
                                  block_kv=128, window=W) ** 2).sum()

    def loss_r(q, k, v):
        return (F.mha_reference(q, k, v, causal=True, window=W) ** 2).sum()

    gf = jax.grad(loss_f, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_r, argnums=(0, 1, 2))(q, k, v)
    for a, b, n in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-3, atol=5e-3, err_msg=n)


def test_sliding_window_model_matches_reference(devices):
    """GPT with attn_window on the jnp path == windowed dense reference."""
    from deepspeed_tpu.models import gpt as gpt_lib
    cfg = gpt_lib.GPTConfig(vocab_size=64, n_layers=1, n_heads=2,
                            d_model=16, max_seq_len=64, dtype=jnp.float32,
                            use_flash_attention=False, remat=False,
                            attn_window=8)
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 32, 2, 8), jnp.float32)
    out = gpt_lib._attention(q, q, q, cfg)
    ref = F.mha_reference(q, q, q, causal=True, window=8)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("window", [32, 100, 256])
def test_sliding_window_masked_impl_forward_parity(devices, window):
    """The "masked" fallback (in-body mask over plain causal geometry —
    the Mosaic-proven construct set; see _norm_window) must match both
    the dense reference and the banded implementation exactly: the two
    impls differ only in which blocks are fetched/skipped, never in
    what any in-band block computes."""
    q, k, v = _rand_qkv(B=1, S=512, H=2, D=32)
    masked = F.flash_attention(q, k, v, causal=True, block_q=128,
                               block_kv=128, window=window,
                               window_impl="masked")
    ref = F.mha_reference(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(masked), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)
    banded = F.flash_attention(q, k, v, causal=True, block_q=128,
                               block_kv=128, window=window,
                               window_impl="banded")
    np.testing.assert_allclose(np.asarray(masked), np.asarray(banded),
                               rtol=1e-6, atol=1e-6)


def test_sliding_window_masked_impl_grads_parity(devices):
    q, k, v = _rand_qkv(B=1, S=512, H=2, D=32, seed=11)
    W = 96

    def loss_m(q, k, v):
        return (F.flash_attention(q, k, v, causal=True, block_q=128,
                                  block_kv=128, window=W,
                                  window_impl="masked") ** 2).sum()

    def loss_r(q, k, v):
        return (F.mha_reference(q, k, v, causal=True, window=W) ** 2).sum()

    gm = jax.grad(loss_m, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_r, argnums=(0, 1, 2))(q, k, v)
    for a, b, n in zip(gm, gr, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-3, atol=5e-3, err_msg=n)


def test_window_impl_env_default(devices, monkeypatch):
    """DS_FLASH_WINDOW_IMPL=masked flips the default, so hardware
    deployments can quarantine the banded kernel without code changes
    (PARITY.md note)."""
    q, k, v = _rand_qkv(B=1, S=256, H=2, D=32)
    monkeypatch.setenv("DS_FLASH_WINDOW_IMPL", "masked")
    out = F.flash_attention(q, k, v, causal=True, block_q=128,
                            block_kv=128, window=64)
    ref = F.mha_reference(q, k, v, causal=True, window=64)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)
    monkeypatch.setenv("DS_FLASH_WINDOW_IMPL", "bogus")
    with pytest.raises(ValueError, match="window impl"):
        F.flash_attention(q, k, v, causal=True, block_q=128,
                          block_kv=128, window=64)


def test_window_gqa_segments_compose(devices):
    """window + GQA + segment_ids in one call — all masks and the
    grouped kv maps compose."""
    q, _, _ = _rand_qkv(B=1, S=256, H=4, D=32, seed=13)
    ks = jax.random.split(jax.random.PRNGKey(14), 2)
    k = jax.random.normal(ks[0], (1, 256, 2, 32), jnp.float32)
    v = jax.random.normal(ks[1], (1, 256, 2, 32), jnp.float32)
    segs = jnp.asarray(np.repeat([0, 1], 128)[None], jnp.int32)
    out = F.flash_attention(q, k, v, causal=True, block_q=128,
                            block_kv=128, window=64, segment_ids=segs)
    ref = F.mha_reference(q, k, v, causal=True, window=64,
                          segment_ids=segs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


def test_bwd_block_override_parity(devices):
    """Separate backward tiles (bwd_block_q/kv != fwd blocks) must not
    change gradients — only the dq/dkv kernel tiling."""
    q, k, v = _rand_qkv(S=512)

    def loss(fn):
        return lambda q, k, v: (fn(q, k, v) ** 2).sum()

    base = functools.partial(F.flash_attention, causal=True,
                             block_q=256, block_kv=256)
    tuned = functools.partial(F.flash_attention, causal=True,
                              block_q=256, block_kv=256,
                              bwd_block_q=128, bwd_block_kv=128)
    g0 = jax.grad(loss(base), argnums=(0, 1, 2))(q, k, v)
    g1 = jax.grad(loss(tuned), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g0, g1):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)
