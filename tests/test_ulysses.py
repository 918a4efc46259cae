"""Ulysses (all-to-all) sequence parallelism tests on the 8-device CPU
mesh — parity with dense attention and with ring attention
(the sp capability family; SURVEY §2.2/§5 long-context)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.ops.attention.flash import mha_reference
from deepspeed_tpu.ops.attention.ulysses import ulysses_attention
from deepspeed_tpu.parallel.mesh import MeshSpec, make_mesh


def _qkv(B=2, S=64, H=8, D=16, seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return tuple(jax.random.normal(k, (B, S, H, D), dtype) for k in ks)


@pytest.mark.parametrize("causal", [True, False])
def test_ulysses_matches_dense(devices, causal):
    q, k, v = _qkv()
    mesh = make_mesh(MeshSpec(data=1, sequence=8))
    out = ulysses_attention(q, k, v, mesh, causal=causal)
    ref = mha_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_ulysses_grads_match_dense(devices):
    q, k, v = _qkv(B=1, S=32, H=8, D=8)
    mesh = make_mesh(MeshSpec(data=1, sequence=8))
    g_u = jax.grad(lambda q, k, v: jnp.sum(
        ulysses_attention(q, k, v, mesh, causal=True) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(lambda q, k, v: jnp.sum(
        mha_reference(q, k, v, causal=True) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    for a, b, n in zip(g_u, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5, err_msg=n)


def test_ulysses_with_data_parallel_axes(devices):
    q, k, v = _qkv(S=32, H=4)
    mesh = make_mesh(MeshSpec(data=2, sequence=4))
    out = ulysses_attention(q, k, v, mesh, causal=True)
    ref = mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_ulysses_gpt_trains(devices):
    """GPT with sp_impl='ulysses' through the engine: loss parity with the
    ring implementation and finite training steps."""
    from deepspeed_tpu.models import gpt
    mesh = make_mesh(MeshSpec(data=2, sequence=4))

    def build(impl):
        cfg = gpt.GPTConfig(vocab_size=128, n_layers=2, n_heads=4,
                            d_model=32, max_seq_len=64,
                            use_flash_attention=False, remat=False,
                            dtype=jnp.float32, sequence_parallel=True,
                            sp_impl=impl, mesh=mesh)
        params = gpt.init_params(jax.random.PRNGKey(0), cfg)
        eng, _, _, _ = deepspeed_tpu.initialize(
            model=gpt.make_loss_fn(cfg), model_parameters=params,
            config={"train_batch_size": 4,
                    "mesh": {"data_parallel_size": 2,
                             "sequence_parallel_size": 4},
                    "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
                    "steps_per_print": 1000},
            mesh=mesh)
        return eng

    r = np.random.default_rng(0)
    data = {"tokens": r.integers(0, 128, (4, 33)).astype(np.int32)}
    e_u = build("ulysses")
    e_r = build("ring")
    for _ in range(3):
        lu = float(e_u.train_batch(data)["loss"])
        lr_ = float(e_r.train_batch(data)["loss"])
        np.testing.assert_allclose(lu, lr_, rtol=1e-4)
    assert np.isfinite(lu)


def test_ulysses_gqa_matches_dense(devices):
    """GQA under Ulysses: q heads 8, kv heads 4, sp=4 — matches the
    dense grouped reference."""
    mesh = make_mesh(MeshSpec(data=2, sequence=4))
    B, S, H, Hkv, D = 1, 64, 8, 4, 16
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, S, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, Hkv, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, Hkv, D), jnp.float32)
    out = ulysses_attention(q, k, v, mesh, causal=True)
    ref = mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_ulysses_packed_segments_matches_dense(devices):
    """segment_ids through the all-to-all layout: full rows are local
    after the seq->head swap, so packing must match the dense kernel."""
    mesh = make_mesh(MeshSpec(data=1, sequence=8))
    q, k, v = _qkv(B=2, S=64, H=8, D=16)
    segs = jnp.asarray(np.repeat(np.arange(4), 16)[None].repeat(2, 0),
                       jnp.int32)
    out = ulysses_attention(q, k, v, mesh, causal=True, segment_ids=segs)
    ref = mha_reference(q, k, v, causal=True, segment_ids=segs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_ulysses_window_matches_dense(devices):
    mesh = make_mesh(MeshSpec(data=1, sequence=8))
    q, k, v = _qkv(B=2, S=64, H=8, D=16)
    out = ulysses_attention(q, k, v, mesh, causal=True, window=16)
    ref = mha_reference(q, k, v, causal=True, window=16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_ulysses_kv_mask_matches_dense(devices):
    mesh = make_mesh(MeshSpec(data=2, sequence=4))
    q, k, v = _qkv(B=2, S=64, H=8, D=16)
    r = np.random.default_rng(3)
    mask = jnp.asarray((r.random((2, 64)) > 0.25).astype(np.float32))
    out = ulysses_attention(q, k, v, mesh, causal=True, kv_mask=mask)
    ref = mha_reference(q, k, v, causal=True, kv_mask=mask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_ulysses_packed_grads_match_dense(devices):
    mesh = make_mesh(MeshSpec(data=1, sequence=8))
    q, k, v = _qkv(B=1, S=32, H=8, D=8)
    segs = jnp.asarray(np.repeat(np.arange(2), 16)[None], jnp.int32)
    g_u = jax.grad(lambda q, k, v: jnp.sum(ulysses_attention(
        q, k, v, mesh, causal=True, segment_ids=segs) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(lambda q, k, v: jnp.sum(mha_reference(
        q, k, v, causal=True, segment_ids=segs) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    for a, b, n in zip(g_u, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5, err_msg=n)


def test_ulysses_packed_gpt_trains(devices):
    """End-to-end: a PACKED batch (pack_documents) through a GPT with
    sp_impl='ulysses' on a data x sequence mesh — loss parity with the
    unsharded model, finite steps. models/gpt.py's SP guard now narrows
    to ring-only."""
    from deepspeed_tpu.models import gpt
    from deepspeed_tpu.runtime.dataloader import pack_documents

    r = np.random.default_rng(0)
    docs = [r.integers(0, 128, ln).astype(np.int32)
            for ln in (20, 30, 15, 33, 9, 22)]
    packed = pack_documents(docs, seq_len=65, pad_token=0)
    packed = {k_: v_[:2] for k_, v_ in packed.items()}
    assert packed["tokens"].shape[0] >= 2

    mesh = make_mesh(MeshSpec(data=2, sequence=4))

    ref_mesh = make_mesh(MeshSpec(data=2), devices=jax.devices()[:2])

    def build(sp):
        cfg = gpt.GPTConfig(vocab_size=128, n_layers=2, n_heads=4,
                            d_model=32, max_seq_len=64,
                            use_flash_attention=False, remat=False,
                            dtype=jnp.float32, sequence_parallel=sp,
                            sp_impl="ulysses", mesh=mesh if sp else None)
        params = gpt.init_params(jax.random.PRNGKey(0), cfg)
        eng, _, _, _ = deepspeed_tpu.initialize(
            model=gpt.make_loss_fn(cfg), model_parameters=params,
            config={"train_batch_size": 2,
                    "mesh": ({"data_parallel_size": 2,
                              "sequence_parallel_size": 4} if sp
                             else {"data_parallel_size": 2}),
                    "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
                    "steps_per_print": 1000},
            mesh=mesh if sp else ref_mesh)
        return eng

    e_sp = build(True)
    e_ref = build(False)
    for _ in range(2):
        l_sp = float(e_sp.train_batch(packed)["loss"])
        l_ref = float(e_ref.train_batch(packed)["loss"])
        np.testing.assert_allclose(l_sp, l_ref, rtol=1e-4)
    assert np.isfinite(l_sp)


def test_ulysses_window_flash_matches_dense(devices, pallas_interpret):
    """The window reaches the flash kernel through the SP path: after the
    seq->head all-to-all each rank runs the banded kernel over full rows
    (blocks of 32: the window's lower edge skips whole blocks)."""
    mesh = make_mesh(MeshSpec(data=1, sequence=8))
    q, k, v = _qkv(B=2, S=64, H=8, D=16)
    out = ulysses_attention(q, k, v, mesh, causal=True, window=16,
                            use_flash=True, block_q=32, block_kv=32)
    ref = mha_reference(q, k, v, causal=True, window=16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
