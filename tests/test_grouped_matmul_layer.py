"""``held_experts_ffn`` (moe/expert_share.py) over the grouped product of
ops/grouped_matmul.py gives the output and the counters of the parent's whole
function (megablox's ``gmm`` over every sparse layer's stacked experts), at
each sparse configuration's tiny preset. The product and its metadata alone
are tests/test_grouped_matmul.py."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.hybrid import split_experts
from deepspeed_tpu.moe import expert_share

# the module: ``megablox.gmm`` the attribute is the function of that name
megablox = importlib.import_module(
    "jax.experimental.pallas.ops.tpu.megablox.gmm")
TM = expert_share.GMM_TILING[0]


def _parent_grouped(x, w, sizes, impl, layer=None):
    """``expert_share._grouped`` as the parent had it: the layer's sizes in
    a zero vector over every group of the stack, and megablox's ``gmm``
    (interpreted) or ``ragged_dot`` over all of them."""
    groups = sizes
    if layer is not None:
        groups = jax.lax.dynamic_update_slice(
            jnp.zeros((w.shape[0],), jnp.int32), sizes,
            (layer * sizes.shape[0],))
    if impl == "ragged_dot":
        return jax.lax.ragged_dot(x, w, groups)
    return megablox.gmm(x, w, groups, preferred_element_type=x.dtype,
                        tiling=expert_share.grouped_tiling(*w.shape[1:]),
                        interpret=True)


def _parent_held_experts_ffn(h, experts, sel, w, held, impl, valid, layer,
                             act):
    """``expert_share.held_experts_ffn`` as the parent had it, whole: the
    sizes by a scatter-add, the parent's grouped product, the un-sort by a
    scattered inverse and the weighted sum over ``[T, K, d]``."""
    T, d = h.shape
    K = sel.shape[1]
    first, count = held
    local = sel - first
    on = jnp.logical_and(jnp.logical_and(local >= 0, local < count),
                         valid[:, None])
    key = jnp.where(on, local, count).reshape(-1)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    sizes = jnp.zeros((count + 1,), jnp.int32).at[key].add(1)[:count]
    M = T * K
    pad = -M % TM if impl == "gmm" else 0
    x = h[jnp.pad(order // K, (0, pad))]
    wg, wi, wo = (experts[n]["kernel"].astype(h.dtype)
                  for n in ("wg", "wi", "wo"))
    gate = getattr(jax.nn, act)(_parent_grouped(x, wg, sizes, impl, layer))
    y = _parent_grouped(gate * _parent_grouped(x, wi, sizes, impl, layer),
                        wo, sizes, impl, layer)
    inv = jnp.zeros((M,), jnp.int32).at[order].set(
        jnp.arange(M, dtype=jnp.int32))
    pairs = y[inv].reshape(T, K, d).astype(jnp.float32)
    out = jnp.sum(jnp.where(on[..., None], pairs * w[..., None], 0.0), axis=1)
    stats = jnp.stack([
        jnp.sum(sizes), jnp.sum(valid) * K, jnp.max(sizes),
        jnp.sum(sizes > 0), jnp.int32(1)]).astype(jnp.int32)
    if act == "relu":
        held_row = jnp.arange(gate.shape[0]) < jnp.sum(sizes)
        zeros = jnp.sum(jnp.logical_and(gate == 0, held_row[:, None]),
                        dtype=jnp.int32)
        stats = jnp.concatenate([stats, jnp.stack(
            [zeros, jnp.sum(sizes) * gate.shape[1]]) // 16])
    return out.astype(h.dtype), stats


SPARSE_PRESETS = ("exaone_moe", "dots_vlm", "zaya", "kimi_linear",
                  "longcat_flash", "smallthinker", "qwen3_next")


@pytest.mark.parametrize("impl", ["gmm", "ragged_dot"])
@pytest.mark.parametrize("name", SPARSE_PRESETS)
def test_the_layer_gives_the_parents_output_and_counters(
        pallas_interpret, name, impl):
    """``held_experts_ffn`` at a sparse configuration's tiny preset, seeded
    tokens and selections, the last sparse layer of the stack and idle
    lanes among the tokens, against the parent's whole function: the
    counters are the parent's, and so is the output (the pairs and the
    float32 weighted sum are; the sum runs over the k-th choices of all
    tokens together, and the CPU's ``ragged_dot`` sums a slice's rows in
    another order than the stack's)."""
    U = __import__(name + "_util")
    cfg = U.tiny_config()
    _, experts = split_experts(U.tiny_params(cfg))
    first, count = cfg.held
    layers = experts["wg"]["kernel"].shape[0] // count
    assert layers > 1
    r = np.random.default_rng(11)
    T, K = 24, cfg.moe_k
    h = jnp.asarray(r.standard_normal((T, cfg.d_model)), jnp.float32)
    width = cfg.num_experts + expert_share.n_zero_experts(cfg)
    sel = jnp.asarray(np.stack([r.choice(width, K, replace=False)
                                for _ in range(T)]), jnp.int32)
    w = jnp.asarray(r.random((T, K)), jnp.float32)
    valid = jnp.arange(T) < 21
    args = (h, experts, sel, w, cfg.held, impl, valid, jnp.int32(layers - 1),
            expert_share.expert_act(cfg))
    got, stats = expert_share.held_experts_ffn(*args)
    want, want_stats = _parent_held_experts_ffn(*args)
    assert int(stats[0]) > 0 and float(jnp.abs(want).max()) > 1e-3
    np.testing.assert_array_equal(np.asarray(stats), np.asarray(want_stats))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-6, rtol=1e-6)
