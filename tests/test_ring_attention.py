"""Ring-attention (sequence parallelism) tests on the 8-device CPU mesh.
The ring's steps through the flash kernel are tests/test_ring_attention_flash.py."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.ops.attention.flash import mha_reference
from deepspeed_tpu.ops.attention.ring import ring_attention
from deepspeed_tpu.parallel.mesh import MeshSpec, make_mesh


def _qkv(B=2, S=64, H=2, D=16, seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return tuple(jax.random.normal(k, (B, S, H, D), dtype) for k in ks)


@pytest.mark.parametrize("causal", [True, False])
def test_ring_matches_dense(devices, causal):
    q, k, v = _qkv()
    mesh = make_mesh(MeshSpec(data=1, sequence=8))
    out = ring_attention(q, k, v, mesh, causal=causal)
    ref = mha_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_ring_grads_match_dense(devices):
    q, k, v = _qkv(B=1, S=32, H=2, D=8)
    mesh = make_mesh(MeshSpec(data=1, sequence=8))

    g_ring = jax.grad(lambda q, k, v: jnp.sum(
        ring_attention(q, k, v, mesh, causal=True) ** 2), argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(lambda q, k, v: jnp.sum(
        mha_reference(q, k, v, causal=True) ** 2), argnums=(0, 1, 2))(q, k, v)
    for a, b, n in zip(g_ring, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5, err_msg=n)


def test_ring_with_data_parallel_axes(devices):
    """sequence=4 combined with data=2."""
    q, k, v = _qkv(S=32)
    mesh = make_mesh(MeshSpec(data=2, sequence=4))
    out = ring_attention(q, k, v, mesh, causal=True)
    ref = mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_sequence_parallel_gpt_trains(devices):
    """GPT with sequence_parallel: loss matches dense-GPT loss and trains."""
    from deepspeed_tpu.models import gpt
    mesh = make_mesh(MeshSpec(data=2, sequence=4))
    cfg = gpt.GPTConfig(vocab_size=128, n_layers=2, n_heads=4, d_model=32,
                        max_seq_len=64, use_flash_attention=False,
                        remat=False, dtype=jnp.float32,
                        sequence_parallel=True, mesh=mesh)
    params = gpt.init_params(jax.random.PRNGKey(0), cfg)

    cfg_dense = gpt.GPTConfig(vocab_size=128, n_layers=2, n_heads=4,
                              d_model=32, max_seq_len=64,
                              use_flash_attention=False, remat=False,
                              dtype=jnp.float32)
    tokens = np.random.default_rng(0).integers(0, 128, (8, 65)).astype(np.int32)
    ref = float(gpt.loss_fn(params, {"tokens": jnp.asarray(tokens)},
                            jax.random.PRNGKey(0), cfg_dense,
                            deterministic=True))

    ds = {"train_batch_size": 8,
          "mesh": {"sequence_parallel_size": 4},
          "optimizer": {"type": "adamw", "params": {"lr": 3e-3}},
          "steps_per_print": 1000}
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=gpt.make_loss_fn(cfg), model_parameters=params, config=ds,
        mesh=mesh)
    losses = [float(engine.train_batch({"tokens": tokens})["loss"])
              for _ in range(8)]
    np.testing.assert_allclose(losses[0], ref, rtol=1e-4)
    assert losses[-1] < losses[0] - 0.3
    # divisible token arrays get sequence-sharded (the 65-long shifted input
    # intentionally stays batch-only)
    sharded = engine._shard_batch({"x": tokens[:, :64]})
    assert sharded["x"].sharding.shard_shape((8, 64))[1] == 16


def test_ring_gqa_matches_dense(devices):
    """GQA under ring SP: the small grouped k/v rotate; repeated locally
    per step — matches the dense grouped reference, forward AND grads
    (training with SP + GQA is now allowed)."""
    mesh = make_mesh(MeshSpec(data=1, sequence=8))
    B, S, H, Hkv, D = 1, 64, 4, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (B, S, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, Hkv, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, Hkv, D), jnp.float32)
    out = ring_attention(q, k, v, mesh, causal=True)
    ref = mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)

    def loss_r(q, k, v):
        return (ring_attention(q, k, v, mesh, causal=True) ** 2).sum()

    def loss_d(q, k, v):
        return (mha_reference(q, k, v, causal=True) ** 2).sum()

    gr = jax.grad(loss_r, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_d, argnums=(0, 1, 2))(q, k, v)
    for a, b, n in zip(gr, gd, "qkv"):
        assert a.shape == b.shape, n
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-3, err_msg=n)


def test_ring_segments_match_dense(devices):
    """Packed segment_ids under the ring: the metadata rotates with its
    K/V block, so block-diagonal masking is exact."""
    from deepspeed_tpu.ops.attention.flash import mha_reference
    from deepspeed_tpu.parallel.mesh import MeshSpec, make_mesh
    mesh = make_mesh(MeshSpec(data=1, sequence=8))
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (jax.random.normal(kk, (2, 64, 4, 16), jnp.float32)
               for kk in ks)
    segs = jnp.asarray(np.repeat(np.arange(4), 16)[None].repeat(2, 0),
                       jnp.int32)
    out = ring_attention(q, k, v, mesh, causal=True, segment_ids=segs)
    ref = mha_reference(q, k, v, causal=True, segment_ids=segs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_ring_kv_mask_matches_dense(devices):
    from deepspeed_tpu.ops.attention.flash import mha_reference
    from deepspeed_tpu.parallel.mesh import MeshSpec, make_mesh
    mesh = make_mesh(MeshSpec(data=2, sequence=4))
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q, k, v = (jax.random.normal(kk, (2, 64, 4, 16), jnp.float32)
               for kk in ks)
    r = np.random.default_rng(3)
    mask_np = (r.random((2, 64)) > 0.25).astype(np.float32)
    mask = jnp.asarray(mask_np)
    out = ring_attention(q, k, v, mesh, causal=True, kv_mask=mask)
    ref = mha_reference(q, k, v, causal=True, kv_mask=mask)
    # rows with NO causally-visible valid key are garbage-by-contract
    # (dense: uniform average over all keys; ring: exact 0 — it skips
    # above-diagonal blocks) — compare only defined rows, and pin the
    # ring's documented contract for the rest
    defined = np.cumsum(mask_np, axis=1) > 0              # [B, S]
    np.testing.assert_allclose(np.asarray(out)[defined],
                               np.asarray(ref)[defined],
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(out)[~defined], 0.0, atol=1e-6)


def test_ring_window_matches_dense(devices):
    from deepspeed_tpu.ops.attention.flash import mha_reference
    from deepspeed_tpu.parallel.mesh import MeshSpec, make_mesh
    mesh = make_mesh(MeshSpec(data=1, sequence=8))
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q, k, v = (jax.random.normal(kk, (2, 64, 4, 16), jnp.float32)
               for kk in ks)
    out = ring_attention(q, k, v, mesh, causal=True, window=16)
    ref = mha_reference(q, k, v, causal=True, window=16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_ring_packed_grads_match_dense(devices):
    from deepspeed_tpu.ops.attention.flash import mha_reference
    from deepspeed_tpu.parallel.mesh import MeshSpec, make_mesh
    mesh = make_mesh(MeshSpec(data=1, sequence=8))
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    q, k, v = (jax.random.normal(kk, (1, 32, 8, 8), jnp.float32)
               for kk in ks)
    segs = jnp.asarray(np.repeat(np.arange(2), 16)[None], jnp.int32)
    g_r = jax.grad(lambda q, k, v: jnp.sum(ring_attention(
        q, k, v, mesh, causal=True, segment_ids=segs) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    g_d = jax.grad(lambda q, k, v: jnp.sum(mha_reference(
        q, k, v, causal=True, segment_ids=segs) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    for a, b, nm in zip(g_r, g_d, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5, err_msg=nm)


def test_ring_multichunk_matches_dense(devices):
    """chunk < S_loc exercises the chunked online-softmax path (the
    fallback's whole point: O(S_loc*chunk) local memory, never the dense
    O(S_loc^2) score matrix), forward and grads."""
    mesh = make_mesh(MeshSpec(data=2, sequence=4))
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q, k, v = (jax.random.normal(kk, (1, 64, 2, 8), jnp.float32)
               for kk in ks)
    out = ring_attention(q, k, v, mesh, causal=True, chunk=4)
    ref = mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    g_r = jax.grad(lambda q, k, v: jnp.sum(ring_attention(
        q, k, v, mesh, causal=True, chunk=4) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    g_d = jax.grad(lambda q, k, v: jnp.sum(mha_reference(
        q, k, v, causal=True) ** 2), argnums=(0, 1, 2))(q, k, v)
    for a, b, nm in zip(g_r, g_d, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5, err_msg=nm)


def test_ring_window_multichunk_matches_dense(devices):
    """Sliding window + chunked local path + the static early-stop of the
    rotation chain (window=24 over S_loc=16 -> 3 hops, not 4)."""
    from deepspeed_tpu.ops.attention.ring import _num_steps
    assert _num_steps(4, 16, True, 24) == 3
    assert _num_steps(8, 8, True, 8) == 2
    mesh = make_mesh(MeshSpec(data=2, sequence=4))
    ks = jax.random.split(jax.random.PRNGKey(6), 3)
    q, k, v = (jax.random.normal(kk, (1, 64, 2, 8), jnp.float32)
               for kk in ks)
    out = ring_attention(q, k, v, mesh, causal=True, window=24, chunk=8)
    ref = mha_reference(q, k, v, causal=True, window=24)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_ring_packed_gpt_matches_ulysses(devices):
    """End-to-end packed batch: ring and Ulysses SP produce the same
    engine loss (both now carry packing metadata; models/gpt.py's SP
    guard is fully lifted)."""
    import deepspeed_tpu
    from deepspeed_tpu.models import gpt
    from deepspeed_tpu.parallel.mesh import MeshSpec, make_mesh
    from deepspeed_tpu.runtime.dataloader import pack_documents

    r = np.random.default_rng(0)
    docs = [r.integers(0, 128, ln).astype(np.int32)
            for ln in (20, 30, 15, 33, 9, 22)]
    packed = pack_documents(docs, seq_len=65, pad_token=0)
    packed = {k_: v_[:2] for k_, v_ in packed.items()}
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
            config={"train_batch_size": 2,
                    "mesh": {"data_parallel_size": 2,
                             "sequence_parallel_size": 4},
                    "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
                    "steps_per_print": 1000},
            mesh=mesh)
        return eng

    e_ring = build("ring")
    e_uly = build("ulysses")
    for _ in range(2):
        lr_ = float(e_ring.train_batch(packed)["loss"])
        lu = float(e_uly.train_batch(packed)["loss"])
        np.testing.assert_allclose(lr_, lu, rtol=1e-4)
    assert np.isfinite(lr_)


def test_ring_bf16_matches_dense(devices):
    """The production dtype path: bf16 q/k/v through the ring (fp32
    online-softmax accumulation internally) vs the bf16 dense reference,
    forward and grads at bf16-appropriate tolerances."""
    mesh = make_mesh(MeshSpec(data=2, sequence=4))
    q, k, v = _qkv(B=2, S=64, H=4, D=16, seed=10, dtype=jnp.bfloat16)
    out = ring_attention(q, k, v, mesh, causal=True)
    assert out.dtype == jnp.bfloat16
    ref = mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=0.05, atol=0.05)
    g_r = jax.grad(lambda q, k, v: jnp.sum(ring_attention(
        q, k, v, mesh, causal=True).astype(jnp.float32) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    g_d = jax.grad(lambda q, k, v: jnp.sum(mha_reference(
        q, k, v, causal=True).astype(jnp.float32) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    for a, b, nm in zip(g_r, g_d, "qkv"):
        assert a.dtype == jnp.bfloat16, nm
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=0.1, atol=0.1, err_msg=nm)


# ---------------------------------------------------------------------------
# property-based ring invariants (hypothesis)
# ---------------------------------------------------------------------------

try:
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:  # environment without hypothesis: collect the
    # rest of the module and skip just the property tests
    import pytest as _pytest

    def given(*a, **k):
        return _pytest.mark.skip(reason="hypothesis not installed")

    def settings(*a, **k):
        return lambda f: f

    class _NoStrategies:
        def __getattr__(self, name):
            return lambda *a, **k: None

    st = _NoStrategies()


@settings(max_examples=15, deadline=None)
@given(
    st.integers(min_value=1, max_value=2),            # batch
    st.sampled_from([32, 64]),                        # seq
    st.sampled_from([(2, 2), (4, 2), (4, 1)]),        # (H, Hkv)
    st.sampled_from([None, 8, 24, 48]),               # window
    st.booleans(),                                    # packed segments?
    st.booleans(),                                    # kv mask?
    st.integers(min_value=0, max_value=10_000),       # seed
)
def test_ring_property_parity(devices, B, S, heads, window, use_segs,
                              use_mask, seed):
    """Randomized geometry sweep: any composition of GQA, packing,
    key-validity masks and sliding windows through the ring must match
    the dense reference on all rows with >=1 visible valid key (the
    documented contract). The ring path re-derives every mask from
    rotated per-token metadata + static step offsets — the exact code
    a geometry off-by-one would live in."""
    H, Hkv = heads
    r = np.random.default_rng(seed)
    q = jnp.asarray(r.standard_normal((B, S, H, 8)), jnp.float32)
    k = jnp.asarray(r.standard_normal((B, S, Hkv, 8)), jnp.float32)
    v = jnp.asarray(r.standard_normal((B, S, Hkv, 8)), jnp.float32)
    segs = None
    if use_segs:
        n_docs = int(r.integers(1, 5))
        bounds = np.sort(r.choice(np.arange(1, S), n_docs - 1,
                                  replace=False)) if n_docs > 1 else []
        ids = np.zeros(S, np.int32)
        for b_ in bounds:
            ids[b_:] += 1
        segs = jnp.asarray(ids[None].repeat(B, 0))
    mask = None
    mask_np = np.ones((B, S), np.float32)
    if use_mask:
        mask_np = (r.random((B, S)) > 0.3).astype(np.float32)
        mask = jnp.asarray(mask_np)

    mesh = make_mesh(MeshSpec(data=2, sequence=4))
    out = ring_attention(q, k, v, mesh, causal=True, window=window,
                         segment_ids=segs, kv_mask=mask,
                         chunk=int(r.choice([4, 8, 1024])))
    ref = mha_reference(q, k, v, causal=True, window=window,
                        segment_ids=segs, kv_mask=mask)

    # defined rows: >=1 visible valid key under causal+window+segs+mask
    rows = np.arange(S)[:, None]
    cols = np.arange(S)[None, :]
    vis = rows >= cols
    if window is not None:
        vis &= rows - cols < window
    defined = np.zeros((B, S), bool)
    for b_ in range(B):
        vb = vis & (mask_np[b_][None, :] > 0)
        if segs is not None:
            ids = np.asarray(segs)[b_]
            vb &= ids[:, None] == ids[None, :]
        defined[b_] = vb.any(axis=1)
    np.testing.assert_allclose(np.asarray(out)[defined],
                               np.asarray(ref)[defined],
                               rtol=5e-4, atol=5e-4)


def test_ring_window_stops_early_and_matches_dense(devices):
    """The window rides the ring's nondiff argument as the int it is:
    the hop count comes from it (a window of 16 over shards of 8 meets
    3 of the 8 blocks), and parity with dense holds over those hops."""
    from deepspeed_tpu.ops.attention.ring import _num_steps, _step_cfg
    assert _num_steps(8, 8, True, 16) == 3
    # the self block masks, the next is wholly in band, the third is cut
    assert [_step_cfg(i, 8, True, 16) for i in range(3)] == [
        (True, 0, 16), (False, 0, None), (True, 16, 16)]
    mesh = make_mesh(MeshSpec(data=1, sequence=8))
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q, k, v = (jax.random.normal(kk, (2, 64, 4, 16), jnp.float32)
               for kk in ks)
    out = ring_attention(q, k, v, mesh, causal=True, window=16)
    ref = mha_reference(q, k, v, causal=True, window=16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
