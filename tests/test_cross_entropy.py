"""Chunked softmax cross-entropy: parity with the dense log_softmax path.

Model for these tests: the reference's kernel-vs-python parity style
(ref tests/unit/test_cuda_forward.py / test_cuda_backward.py — compare the
fused op against an unfused baseline within dtype tolerance).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.cross_entropy import (chunked_softmax_xent,
                                             softmax_xent_ll)


def dense_ll(x, w, t, bias=None):
    logits = (x @ w.T).astype(jnp.float32)
    if bias is not None:
        logits = logits + bias.astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return jnp.take_along_axis(logp, t[..., None], axis=-1)[..., 0]


@pytest.mark.parametrize("chunk", [7, 16, 64])
def test_ll_matches_dense(chunk):
    rng = np.random.default_rng(0)
    N, H, V = 48, 32, 97
    x = jnp.asarray(rng.normal(size=(N, H)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(V, H)), jnp.float32) * 0.1
    t = jnp.asarray(rng.integers(0, V, N), jnp.int32)
    got = softmax_xent_ll(x, w, t, chunk=chunk)
    want = dense_ll(x, w, t)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_ll_bias_and_leading_shape():
    rng = np.random.default_rng(1)
    B, S, H, V = 2, 12, 16, 53
    x = jnp.asarray(rng.normal(size=(B, S, H)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(V, H)), jnp.float32) * 0.1
    b = jnp.asarray(rng.normal(size=(V,)), jnp.float32)
    t = jnp.asarray(rng.integers(0, V, (B, S)), jnp.int32)
    got = softmax_xent_ll(x, w, t, bias=b, chunk=8)
    want = dense_ll(x, w, t, bias=b)
    assert got.shape == (B, S)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_grads_match_dense():
    rng = np.random.default_rng(2)
    N, H, V = 40, 24, 61
    x = jnp.asarray(rng.normal(size=(N, H)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(V, H)), jnp.float32) * 0.1
    b = jnp.asarray(rng.normal(size=(V,)), jnp.float32) * 0.1
    t = jnp.asarray(rng.integers(0, V, N), jnp.int32)

    def loss_chunked(x, w, b):
        return -softmax_xent_ll(x, w, t, bias=b, chunk=16).mean()

    def loss_dense(x, w, b):
        return -dense_ll(x, w, t, bias=b).mean()

    gc = jax.grad(loss_chunked, argnums=(0, 1, 2))(x, w, b)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(x, w, b)
    for a, c in zip(gc, gd):
        np.testing.assert_allclose(a, c, rtol=2e-4, atol=2e-5)


def test_masked_mean_loss():
    rng = np.random.default_rng(3)
    B, S, H, V = 2, 10, 16, 37
    x = jnp.asarray(rng.normal(size=(B, S, H)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(V, H)), jnp.float32) * 0.1
    t = jnp.asarray(rng.integers(0, V, (B, S)), jnp.int32)
    mask = jnp.asarray(rng.integers(0, 2, (B, S)), jnp.float32)
    got = chunked_softmax_xent(x, w, t, chunk=8, loss_mask=mask)
    ll = dense_ll(x, w, t)
    want = -(ll * mask).sum() / mask.sum()
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_padding_rows_contribute_nothing():
    # N=13 with chunk=8 pads 3 rows; grads must equal the unpadded dense ones
    rng = np.random.default_rng(4)
    N, H, V = 13, 16, 29
    x = jnp.asarray(rng.normal(size=(N, H)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(V, H)), jnp.float32) * 0.1
    t = jnp.asarray(rng.integers(0, V, N), jnp.int32)
    gc = jax.grad(lambda w: -softmax_xent_ll(x, w, t, chunk=8).sum())(w)
    gd = jax.grad(lambda w: -dense_ll(x, w, t).sum())(w)
    np.testing.assert_allclose(gc, gd, rtol=2e-4, atol=2e-5)


def test_gpt_loss_chunked_parity():
    from deepspeed_tpu.models import gpt
    cfg = gpt.GPTConfig(vocab_size=128, n_layers=2, n_heads=2, d_model=32,
                        max_seq_len=32, dtype=jnp.float32,
                        use_flash_attention=False, remat=False)
    params = gpt.init_params(jax.random.PRNGKey(0), cfg)
    batch = {"tokens": jnp.asarray(
        np.random.default_rng(5).integers(0, 128, (2, 17)), jnp.int32)}
    rng = jax.random.PRNGKey(1)
    dense = gpt.loss_fn(params, batch, rng, cfg, deterministic=True)
    import dataclasses
    cfg_c = dataclasses.replace(cfg, loss_chunk=8)
    chunked = gpt.loss_fn(params, batch, rng, cfg_c, deterministic=True)
    np.testing.assert_allclose(chunked, dense, rtol=1e-5, atol=1e-6)

    # and gradients agree end-to-end through the model
    gd = jax.grad(lambda p: gpt.loss_fn(p, batch, rng, cfg,
                                        deterministic=True))(params)
    gc = jax.grad(lambda p: gpt.loss_fn(p, batch, rng, cfg_c,
                                        deterministic=True))(params)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        a, b, rtol=5e-4, atol=5e-5), gd, gc)


def test_untied_head_with_bias():
    from deepspeed_tpu.models import gpt
    cfg = gpt.GPTConfig(vocab_size=64, n_layers=1, n_heads=2, d_model=16,
                        max_seq_len=16, dtype=jnp.float32,
                        use_flash_attention=False, remat=False,
                        tie_embeddings=False)
    params = gpt.init_params(jax.random.PRNGKey(0), cfg)
    params["lm_head"]["bias"] = jnp.asarray(
        np.random.default_rng(6).normal(size=(64,)), jnp.float32) * 0.1
    batch = {"tokens": jnp.asarray(
        np.random.default_rng(7).integers(0, 64, (2, 9)), jnp.int32)}
    rng = jax.random.PRNGKey(1)
    import dataclasses
    dense = gpt.loss_fn(params, batch, rng, cfg, deterministic=True)
    chunked = gpt.loss_fn(params, batch, rng,
                          dataclasses.replace(cfg, loss_chunk=4),
                          deterministic=True)
    np.testing.assert_allclose(chunked, dense, rtol=1e-5, atol=1e-6)


def test_bert_mlm_loss_chunked_parity():
    import dataclasses
    from deepspeed_tpu.models import bert
    cfg = bert.BertConfig(vocab_size=96, n_layers=2, n_heads=2, d_model=32,
                          max_seq_len=32, dtype=jnp.float32, dropout=0.0)
    params = bert.init_params(jax.random.PRNGKey(0), cfg)
    r = np.random.default_rng(8)
    labels = r.integers(0, 96, (2, 16)).astype(np.int32)
    labels[r.random((2, 16)) > 0.2] = -1   # ~20% masked
    batch = {"tokens": jnp.asarray(r.integers(0, 96, (2, 16)), jnp.int32),
             "mlm_labels": jnp.asarray(labels),
             "nsp_labels": jnp.asarray(r.integers(0, 2, (2,)), jnp.int32)}
    rng = jax.random.PRNGKey(1)
    dense = bert.loss_fn(params, batch, rng, cfg, deterministic=True)
    chunked = bert.loss_fn(params, batch, rng,
                           dataclasses.replace(cfg, loss_chunk=8),
                           deterministic=True)
    np.testing.assert_allclose(chunked, dense, rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# under a mesh that splits the batch: each shard scans its own tokens on a
# projection gathered once, and nothing changes in value
# ---------------------------------------------------------------------------

def _mesh(**axes):
    from deepspeed_tpu.parallel import mesh as mesh_lib
    spec = mesh_lib.MeshSpec(**{"data": 1, **axes})
    n = spec.pipe * spec.data * spec.fsdp * spec.sequence * spec.model
    return mesh_lib.make_mesh(spec, jax.devices()[:n])


def _loss_case(case, V=257):
    """(loss(x, *weights, t, mask), [x, *weights, t, mask], the weights'
    PartitionSpecs) for one head layout. 8 sequences: 2 a shard on four
    shards."""
    from jax.sharding import PartitionSpec as P
    r = np.random.default_rng(11)
    B, H = 8, 32
    S = 13 if case == "ragged_chunk" else 16    # 26 tokens a shard pad to 32
    x = jnp.asarray(r.normal(size=(B, S, H)), jnp.float32)
    t = jnp.asarray(r.integers(0, V, (B, S)), jnp.int32)
    keep = np.ones((B, S), np.float32)
    if case == "uneven_mask":
        # 1, 3, 20 and 32 counted tokens on the four shards: a mean of
        # per-shard means would be far off the global masked mean
        keep[:] = 0.0
        for row, n in enumerate((1, 0, 3, 0, 16, 4, 16, 16)):
            keep[row, :n] = 1.0
    mask = jnp.asarray(keep)
    if case == "untied_bias":
        kernel = jnp.asarray(r.normal(size=(H, V)), jnp.float32) * 0.1
        bias = jnp.asarray(r.normal(size=(V,)), jnp.float32) * 0.1
        return (lambda x, k, b, t, m: chunked_softmax_xent(
            x, k.T, t, bias=b, chunk=8, loss_mask=m),
            [x, kernel, bias, t, mask], [P("fsdp", None), P()])
    w = jnp.asarray(r.normal(size=(V, H)), jnp.float32) * 0.1
    spec = P("model", "fsdp") if case == "vocab_parallel" else P(None, "fsdp")
    return (lambda x, w, t, m: chunked_softmax_xent(
        x, w, t, chunk=8, loss_mask=m), [x, w, t, mask], [spec])


def _on_mesh(mesh, args, weight_specs):
    from jax.sharding import NamedSharding, PartitionSpec as P
    batch = P(("data", "fsdp"))
    specs = [batch] + list(weight_specs) + [batch, batch]
    return [jax.device_put(a, NamedSharding(mesh, s))
            for a, s in zip(args, specs)]


def _assert_same(got, want):
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("case", ["tied", "untied_bias", "uneven_mask",
                                  "ragged_chunk"])
@pytest.mark.parametrize("axes", [{"fsdp": 4}, {"data": 2, "fsdp": 2}],
                         ids=["fsdp4", "data2xfsdp2"])
def test_sharded_loss_matches_single_device(axes, case, devices):
    from deepspeed_tpu.ops.cross_entropy import loss_layout
    loss, args, specs = _loss_case(case)
    grad = jax.value_and_grad(loss, tuple(range(1 + len(specs))))
    want = grad(*args)
    mesh = _mesh(**axes)
    with jax.set_mesh(mesh):
        assert "projection gathered" in loss_layout(8)
        got = jax.jit(grad)(*_on_mesh(mesh, args, specs))
    _assert_same(got, want)


def test_sharded_loss_leaves_the_vocabulary_parallel_axis_to_xla(devices):
    """'model' cuts the vocabulary (``megatron_rules``) and is not a batch
    axis: the map takes 'fsdp' only and the class statistics are still
    reduced over 'model' by the partitioner."""
    from deepspeed_tpu.ops.cross_entropy import loss_layout
    loss, args, specs = _loss_case("vocab_parallel", V=256)
    grad = jax.value_and_grad(loss, (0, 1))
    want = grad(*args)
    mesh = _mesh(fsdp=2, model=2)
    with jax.set_mesh(mesh):
        assert loss_layout(8) == \
            "chunked(8)/shard over fsdp=2, projection gathered"
        got = jax.jit(grad)(*_on_mesh(mesh, args, specs))
    _assert_same(got, want)


def test_sharded_loss_inside_a_manual_data_map(devices):
    """The compressed-collective path maps the loss over 'data' by hand
    (runtime/engine.py compressed_grads): inside it the loss's own map
    takes only 'fsdp', and a replica's loss is its own tokens' mean."""
    from jax.sharding import PartitionSpec as P
    from deepspeed_tpu.ops import cross_entropy
    loss, args, specs = _loss_case("tied")
    want = jax.value_and_grad(loss, (0, 1))(*args)
    mesh = _mesh(data=2, fsdp=2)
    took = []

    def replica(x, w, t, m):
        took.append(cross_entropy._token_axes(x.shape[0]))
        val, (dx, dw) = jax.value_and_grad(loss, (0, 1))(x, w, t, m)
        # a replica holds half the tokens: the global mean is the mean of
        # the replicas', and dx is a share of the global loss's
        return (jax.lax.pmean(val, "data"),
                (dx / 2, jax.lax.pmean(dw, "data")))

    with jax.set_mesh(mesh):
        got = jax.jit(jax.shard_map(
            replica, in_specs=(P("data"), P(), P("data"), P("data")),
            out_specs=(P(), (P("data"), P())), axis_names={"data"},
            check_vma=False))(*_on_mesh(mesh, args, specs))
    assert took == [("fsdp",)]
    _assert_same(got, want)


# ---------------------------------------------------------------------------
# property-based chunked-CE invariants (hypothesis)
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


def _check_chunked_matches_dense(n, v, chunk, with_bias, with_mask):
    """The fused chunked loss and its grads against the dense log-softmax
    computation at one (rows, vocab, chunk, bias, mask)."""
    r = np.random.default_rng(n * 100 + v)
    h = 8
    x = jnp.asarray(r.standard_normal((n, h)), jnp.float32)
    w = jnp.asarray(r.standard_normal((v, h)), jnp.float32)
    b = jnp.asarray(r.standard_normal((v,)), jnp.float32) \
        if with_bias else None
    t = jnp.asarray(r.integers(0, v, (n,)), jnp.int32)
    m = jnp.asarray((r.random(n) > 0.3).astype(np.float32)) \
        if with_mask else None

    def dense(x, w):
        logits = x @ w.T + (b if b is not None else 0.0)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        nll = -jnp.take_along_axis(logp, t[:, None], 1).squeeze(-1)
        if m is not None:
            return (nll * m).sum() / jnp.maximum(m.sum(), 1.0)
        return nll.mean()

    def fused(x, w):
        return chunked_softmax_xent(x[None], w, t[None], bias=b,
                                    chunk=chunk,
                                    loss_mask=None if m is None
                                    else m[None])

    np.testing.assert_allclose(float(dense(x, w)), float(fused(x, w)),
                               rtol=1e-5, atol=1e-6)
    gd = jax.grad(dense, argnums=(0, 1))(x, w)
    gf = jax.grad(fused, argnums=(0, 1))(x, w)
    for a, c in zip(gd, gf):
        np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                   rtol=1e-4, atol=1e-5)


# the boundaries of the chunking, each twice: plain and with bias and mask,
# or with one of the two each time
@pytest.mark.parametrize("n,v,chunk,with_bias,with_mask", [
    pytest.param(6, 11, 3, False, False, id="chunk-divides-the-rows-plain"),
    pytest.param(6, 11, 3, True, True, id="chunk-divides-the-rows-bias+mask"),
    pytest.param(5, 11, 3, True, False, id="chunk-does-not-divide-bias"),
    pytest.param(5, 11, 3, False, True, id="chunk-does-not-divide-mask"),
    pytest.param(3, 37, 9, False, False,
                 id="chunk-longer-than-the-rows-plain"),
    pytest.param(3, 37, 9, True, True,
                 id="chunk-longer-than-the-rows-bias+mask"),
    pytest.param(4, 5, 1, True, False, id="chunk-1-bias"),
    pytest.param(4, 5, 1, False, True, id="chunk-1-mask"),
    pytest.param(1, 3, 4, False, False, id="one-row-plain"),
    pytest.param(1, 3, 4, True, True, id="one-row-bias+mask")])
def test_chunked_matches_dense_at_the_boundaries(n, v, chunk, with_bias,
                                                 with_mask):
    """The shapes the property test below used to find by chance, named."""
    _check_chunked_matches_dense(n, v, chunk, with_bias, with_mask)


# every example is a shape of its own, so a forward and a gradient compile
# of its own: the boundaries are the cases above, and the search keeps the
# examples a third of its time buys, the same ones every run
@settings(max_examples=8, deadline=None, derandomize=True)
@given(st.integers(min_value=1, max_value=6),     # rows N
       st.integers(min_value=3, max_value=37),    # vocab V
       st.integers(min_value=1, max_value=9),     # chunk
       st.booleans(),                              # bias
       st.booleans())                              # mask
def test_chunked_matches_dense_any_shape(n, v, chunk, with_bias,
                                         with_mask):
    """For ANY (rows, vocab, chunk, bias, mask) combination — including
    chunk sizes that don't divide the row count — the fused chunked loss
    and its grads match the dense log-softmax computation."""
    _check_chunked_matches_dense(n, v, chunk, with_bias, with_mask)
