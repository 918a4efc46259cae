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


# (S, block): a grid of 128-blocks, none of which holds a second sub-tile
# (every block is one product), and ONE grid block a head that straddles
# the causal diagonal and is walked as sub-tiles by the backward kernels
# (flash.SUB_TILE: 3 of 4 at 512, 10 of 16 at 1,024, GPT-2 XL's training
# geometry)
GEOMETRIES = [pytest.param(256, 128, id="S256-blk128"),
              pytest.param(512, 512, id="S512-blk512"),
              pytest.param(1024, 1024, id="S1024-blk1024")]
STRADDLING = [pytest.param(256, 128, id="S256-blk128"),
              pytest.param(512, 512, id="S512-blk512")]


@pytest.mark.parametrize("S,blk", GEOMETRIES)
@pytest.mark.parametrize("causal", [True, False])
def test_forward_parity(devices, causal, S, blk):
    q, k, v = _rand_qkv(S=S, H=2)
    out = F.flash_attention(q, k, v, causal=causal, block_q=blk, block_kv=blk)
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


@pytest.mark.parametrize("S,blk", GEOMETRIES)
@pytest.mark.parametrize("causal", [True, False])
def test_backward_parity(devices, causal, S, blk):
    q, k, v = _rand_qkv(B=1, S=S, H=2, D=64)

    def f_flash(q, k, v):
        return jnp.sum(F.flash_attention(q, k, v, causal=causal,
                                         block_q=blk, block_kv=blk) ** 2)

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


@pytest.mark.parametrize("S,blk", STRADDLING)
@pytest.mark.parametrize("causal", [False, True])
def test_kv_mask_forward_parity(devices, causal, S, blk):
    q, k, v = _rand_qkv(B=2, S=S, H=2, D=32)
    rng = np.random.default_rng(0)
    kv_mask = jnp.asarray((rng.random((2, S)) > 0.25).astype(np.float32))
    out = F.flash_attention(q, k, v, causal=causal, block_q=blk,
                            block_kv=blk, kv_mask=kv_mask)
    ref = F.mha_reference(q, k, v, causal=causal, kv_mask=kv_mask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("causal,S,blk", [
    pytest.param(False, 256, 128, id="noncausal-S256-blk128"),
    pytest.param(True, 512, 512, id="causal-S512-blk512")])
def test_kv_mask_grads_parity(devices, causal, S, blk):
    q, k, v = _rand_qkv(B=1, S=S, H=2, D=32, seed=3)
    rng = np.random.default_rng(1)
    kv_mask = np.asarray(rng.random((1, S)) > 0.3, np.float32)
    kv_mask[:, 0] = 1.0      # causal: every query row keeps a valid key
    kv_mask = jnp.asarray(kv_mask)
    # loss masks padded QUERY rows (standard contract)
    row_w = kv_mask[..., None, None]

    def loss_flash(q, k, v):
        o = F.flash_attention(q, k, v, causal=causal, block_q=blk,
                              block_kv=blk, kv_mask=kv_mask)
        return ((o * row_w) ** 2).sum()

    def loss_ref(q, k, v):
        o = F.mha_reference(q, k, v, causal=causal, kv_mask=kv_mask)
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


@pytest.mark.parametrize("S,blk", STRADDLING)
@pytest.mark.parametrize("causal", [True, False])
def test_gqa_forward_parity(devices, causal, S, blk):
    """Grouped-query attention: 4 q heads sharing 2 kv heads == the
    repeated-kv dense reference."""
    q, _, _ = _rand_qkv(B=2, S=S, H=4, D=32)
    ks = jax.random.split(jax.random.PRNGKey(7), 2)
    k = jax.random.normal(ks[0], (2, S, 2, 32), jnp.float32)
    v = jax.random.normal(ks[1], (2, S, 2, 32), jnp.float32)
    out = F.flash_attention(q, k, v, causal=causal, block_q=blk,
                            block_kv=blk)
    ref = F.mha_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("S,blk", STRADDLING)
@pytest.mark.parametrize("causal", [True, False])
def test_gqa_grads_parity(devices, causal, S, blk):
    q, _, _ = _rand_qkv(B=1, S=S, H=4, D=32, seed=8)
    ks = jax.random.split(jax.random.PRNGKey(9), 2)
    k = jax.random.normal(ks[0], (1, S, 2, 32), jnp.float32)
    v = jax.random.normal(ks[1], (1, S, 2, 32), jnp.float32)

    def loss_f(q, k, v):
        return (F.flash_attention(q, k, v, causal=causal, block_q=blk,
                                  block_kv=blk) ** 2).sum()

    def loss_r(q, k, v):
        return (F.mha_reference(q, k, v, causal=causal) ** 2).sum()

    gf = jax.grad(loss_f, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_r, argnums=(0, 1, 2))(q, k, v)
    for a, b, n in zip(gf, gr, "qkv"):
        assert a.shape == b.shape, n
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-3, atol=5e-3, err_msg=n)


# 256: a block with no second sub-tile (flash._sub_tile), two a side
@pytest.mark.parametrize("blk", [128, 256, 512])
@pytest.mark.parametrize("window", [32, 100, 256])
def test_sliding_window_forward_parity(devices, window, blk):
    q, k, v = _rand_qkv(B=1, S=512, H=2, D=32)
    out = F.flash_attention(q, k, v, causal=True, block_q=blk,
                            block_kv=blk, window=window)
    ref = F.mha_reference(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("S,blk,W", [
    pytest.param(512, 128, 96, id="S512-blk128-W96"),
    # the window's lower edge crosses the one block, and its sub-tiles
    pytest.param(512, 512, 96, id="S512-blk512-W96"),
    # two blocks a row: the diagonal one, and one the lower edge crosses
    pytest.param(1024, 512, 300, id="S1024-blk512-W300"),
    # blocks of one sub-tile: the single product under a window
    pytest.param(512, 256, 96, id="S512-blk256-W96"),
    # the lower edge leaves the sub-tiles on the diagonal
    pytest.param(512, 512, 300, id="S512-blk512-W300")])
def test_sliding_window_grads_parity(devices, S, blk, W):
    q, k, v = _rand_qkv(B=1, S=S, H=2, D=32, seed=11)

    def loss_f(q, k, v):
        return (F.flash_attention(q, k, v, causal=True, block_q=blk,
                                  block_kv=blk, window=W) ** 2).sum()

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


def test_window_impl_selector_is_gone(devices):
    """There is one window geometry. A stale ``window_impl=`` /
    ``attn_window_impl=`` fails loudly; it cannot silently run another
    kernel than the one its author asked for."""
    from deepspeed_tpu.models import gpt as gpt_lib
    q, k, v = _rand_qkv(B=1, S=256, H=2, D=32)
    with pytest.raises(TypeError, match="window_impl"):
        F.flash_attention(q, k, v, causal=True, block_q=128, block_kv=128,
                          window=64, window_impl="masked")
    with pytest.raises(TypeError, match="attn_window_impl"):
        gpt_lib.GPTConfig(vocab_size=64, n_layers=1, n_heads=2, d_model=16,
                          max_seq_len=64, attn_window=8,
                          attn_window_impl="masked")


@pytest.mark.parametrize("S,blk,window", [
    pytest.param(256, 128, 64, id="S256-blk128-W64"),
    pytest.param(512, 512, 200, id="S512-blk512-W200")])
def test_window_gqa_segments_compose(devices, S, blk, window):
    """window + GQA + segment_ids in one call — all masks and the
    grouped kv maps compose, forward and all three gradients."""
    q, _, _ = _rand_qkv(B=1, S=S, H=4, D=32, seed=13)
    ks = jax.random.split(jax.random.PRNGKey(14), 2)
    k = jax.random.normal(ks[0], (1, S, 2, 32), jnp.float32)
    v = jax.random.normal(ks[1], (1, S, 2, 32), jnp.float32)
    # segment boundaries off the sub-tiles' edges
    segs = jnp.asarray(np.repeat([0, 1, 2], [S // 2 - 40, 100, S // 2 - 60])
                       [None], jnp.int32)

    def fl(q, k, v):
        return F.flash_attention(q, k, v, causal=True, block_q=blk,
                                 block_kv=blk, window=window,
                                 segment_ids=segs)

    def rf(q, k, v):
        return F.mha_reference(q, k, v, causal=True, window=window,
                               segment_ids=segs)
    np.testing.assert_allclose(np.asarray(fl(q, k, v)),
                               np.asarray(rf(q, k, v)),
                               rtol=2e-3, atol=2e-3)
    gf = jax.grad(lambda *a: (fl(*a) ** 2).sum(), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda *a: (rf(*a) ** 2).sum(), argnums=(0, 1, 2))(q, k, v)
    for a, b, n in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-3, atol=5e-3, err_msg=n)


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


@pytest.mark.parametrize("q_off,window", [
    pytest.param(0, None, id="diagonal-step"),
    pytest.param(0, 200, id="diagonal-step-W200"),
    # a later ring step: every key is behind every query, and only the
    # window's lower edge crosses the block
    pytest.param(512, 700, id="q_off512-W700"),
    pytest.param(512, None, id="q_off512-inside-the-band")])
def test_block_entry_points_q_off(devices, q_off, window):
    """flash_block_fwd_t / _bwd_t (the ring's building blocks, kernel
    layout) on ONE 512 block that the band's edge crosses, against the
    chunked jnp block the ring falls back to."""
    from deepspeed_tpu.ops.attention.ring import (_jnp_block_bwd,
                                                  _jnp_block_fwd)
    S, D = 512, 32
    ks = jax.random.split(jax.random.PRNGKey(21), 4)
    q, k, v, do = (jax.random.normal(kk, (1, 2, S, D), jnp.float32)
                   for kk in ks)
    kw = dict(causal=True, scale=1.0 / np.sqrt(D), block_q=S, block_kv=S,
              window=window, q_off=q_off)
    o, lse = F.flash_block_fwd_t(q, k, v, **kw)
    delta = jnp.sum(do * o, axis=-1)
    grads = F.flash_block_bwd_t(q, k, v, do, lse, delta=delta, **kw)

    def t(x):
        return x.transpose(0, 2, 1, 3)
    ref_kw = dict(blk_causal=True, window=window, q_off=q_off,
                  scale=kw["scale"], chunk=128)
    o_ref, lse_ref = _jnp_block_fwd(t(q), t(k), t(v), None, None, None,
                                    **ref_kw)
    g_ref = _jnp_block_bwd(t(q), t(k), t(v), t(do), lse, delta,
                           None, None, None, **ref_kw)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(lse_ref),
                               rtol=2e-4, atol=2e-4)
    for a, b, n in zip(grads, g_ref, ("dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-3, err_msg=n)


@pytest.mark.parametrize("args,want", [
    # GPT-2 XL's training geometry: one 1,024 block a head
    pytest.param((1024, 1024, 1024, 1024, True), (10, 16), id="train"),
    pytest.param((1024, 1024, 1024, 1024, False), (16, 16), id="noncausal"),
    pytest.param((512, 512, 512, 512, True), (3, 4), id="S512"),
    # a block with no second sub-tile: counted whole
    pytest.param((256, 256, 256, 256, True), (1, 1), id="blk256"),
    pytest.param((512, 512, 128, 128, True), (10, 16), id="grid-of-128"),
    # two blocks a side: the diagonal ones walked, the one below whole
    pytest.param((2048, 2048, 1024, 1024, True), (36, 64), id="S2048"),
    # the window's lower edge is skipped by the same walk
    pytest.param((1024, 1024, 1024, 1024, True, 300), (9, 16), id="W300"),
    pytest.param((1024, 1024, 512, 512, True, 300), (9, 16),
                 id="W300-blk512"),
    # ring steps: the diagonal one, and one the window's lower edge cuts
    pytest.param((512, 512, 512, 512, True, None, 0), (3, 4), id="ring0"),
    pytest.param((512, 512, 512, 512, True, 512, 512), (3, 4),
                 id="ring1-W512")])
def test_tile_census(args, want):
    assert F.tile_census(*args) == want


def test_walk_is_the_grids_predicate_at_sub_tile_scale():
    """The sub-tiles a straddling block computes are those a grid of
    SUB_TILE blocks would run, merged into runs; blocks off the band's
    edges, non-causal calls and blocks with no second sub-tile keep the
    single product."""
    t = F.SUB_TILE
    walk = F._tile_walk(1024, 1024, True, None, 0)
    cells = {(r0 // t, (c0 + c) // t)
             for r0, _, inner in walk for c0, cn, _ in inner
             for c in range(0, cn, t)}
    assert cells == {(i, j) for i in range(4) for j in range(4)
                     if F._band_run(i, j, t, t, True, None)}
    # the strip below the diagonal needs no mask, the sub-tile on it does
    assert walk[2][2] == ((0, 2 * t, False), (2 * t, t, True))
    kv_major = F._tile_walk(1024, 1024, True, None, 0, kv_major=True)
    assert kv_major[1][2] == ((t, t, True), (2 * t, 2 * t, False))
    assert F._block_walks(1024, 1024, 1024, 1024, True, None) == ((0,), False)
    assert F._block_walks(2048, 2048, 1024, 1024, True, None) == ((0,), True)
    assert F._block_walks(1024, 1024, 1024, 1024, False, None) == ((), True)
    assert F._block_walks(512, 512, 128, 128, True, None) == ((), True)
    # a ring step wholly behind the queries, no window: nothing to walk
    assert F._block_walks(512, 512, 512, 512, True, None, 512) == ((), True)
