"""The expert layer's grouped product (ops/grouped_matmul.py) against what
it replaced: megablox's ``gmm`` handed every sparse layer's stacked experts
and the layer's group sizes written into a zero vector over all of them.
The metadata the layer makes is megablox's ``make_group_metadata`` for the
layer's own groups, entry for entry; the product is megablox's bit for bit
and ``ragged_dot``'s within float tolerance; ``held_experts_ffn`` gives the
parent's output and counters at each sparse configuration's tiny preset."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.hybrid import split_experts
from deepspeed_tpu.moe import expert_share
from deepspeed_tpu.ops import grouped_matmul

# the module: ``megablox.gmm`` the attribute is the function of that name
megablox = importlib.import_module(
    "jax.experimental.pallas.ops.tpu.megablox.gmm")
TM = expert_share.GMM_TILING[0]


def _sizes(pattern: str, count: int, rows: int, seed: int = 0):
    """int32 ``[count]`` group sizes that sum to at most ``rows``."""
    r = np.random.default_rng(seed)

    def cut(total, groups):
        edges = np.sort(r.integers(0, total + 1, groups - 1))
        return np.diff(np.concatenate([[0], edges, [total]]))
    if pattern == "all_empty":
        s = np.zeros(count)
    elif pattern == "first_empty":      # and the rows end short of a tile
        s = np.concatenate([np.zeros(3), cut(rows - 37, count - 3)])
    elif pattern == "last_empty":
        s = np.concatenate([cut(rows - TM - 5, count - 3), np.zeros(3)])
    elif pattern == "one_group":        # every row in one group in the middle
        s = np.zeros(count)
        s[count // 2] = rows
    elif pattern == "tile_aligned":     # no tile is shared by two groups
        s = np.zeros(count)
        s[[1, 4, count - 1]] = TM
    elif pattern == "pad_rows":         # the last row tile holds no pair
        s = cut(rows - TM - 1, count)
    else:                               # "ragged": the last tile part filled
        s = cut(rows - 61, count)
    return jnp.asarray(s, jnp.int32)


PATTERNS = ("ragged", "all_empty", "first_empty", "last_empty", "one_group",
            "tile_aligned", "pad_rows")


@pytest.mark.parametrize("tm", [8, 128])
@pytest.mark.parametrize("pattern,count", [
    (p, c) for c in (1, 16, 32) for p in PATTERNS
    # one group has no first, last or several
    if c > 1 or p in ("ragged", "all_empty", "one_group")])
def test_the_layer_made_metadata_is_megabloxs_for_the_same_groups(
        pattern, count, tm):
    rows = 4 * TM
    sizes = _sizes(pattern, count, rows)
    (offsets, group_ids, m_tile_ids), num_tiles = \
        megablox.make_group_metadata(
            group_sizes=sizes, m=rows, tm=tm, start_group=jnp.int32(0),
            num_nonzero_groups=count, visit_empty_groups=False)
    got = grouped_matmul.group_metadata(sizes, rows, tm)
    for want, have in zip((offsets, group_ids, m_tile_ids, num_tiles), got):
        assert want.dtype == have.dtype and want.shape == have.shape
        np.testing.assert_array_equal(np.asarray(have), np.asarray(want))
    # what the stack's metadata said of the same groups, in the tiles the
    # grid visits: the same tiles, the groups behind the layer's base
    layers, layer = 3, 1
    stacked = jax.lax.dynamic_update_slice(
        jnp.zeros((layers * count,), jnp.int32), sizes, (layer * count,))
    (_, ids_all, tiles_all), n_all = megablox.make_group_metadata(
        group_sizes=stacked, m=rows, tm=tm, start_group=jnp.int32(0),
        num_nonzero_groups=layers * count, visit_empty_groups=False)
    n = int(num_tiles)
    assert n == int(n_all)
    np.testing.assert_array_equal(np.asarray(got.group_ids[:n]) + count,
                                  np.asarray(ids_all[:n]))
    np.testing.assert_array_equal(np.asarray(got.m_tile_ids[:n]),
                                  np.asarray(tiles_all[:n]))


def _operands(count, k, n, layers, rows, dtype, seed=3):
    r = np.random.default_rng(seed)
    x = jnp.asarray(r.standard_normal((rows, k)), dtype)
    w = jnp.asarray(r.standard_normal((layers * count, k, n)) * k ** -0.5,
                    dtype)
    return x, w


# (count, k, n, sparse layers stacked, the layer, dtype): a layer in the
# middle of a stack and at both ends, a stack of one; 2,304 is Kimi-Linear's
# width, which the preferred tile of 1,024 does not divide (3 x 768)
SHAPES = [
    (16, 256, 128, 3, 1, jnp.float32),
    (32, 128, 256, 3, 2, jnp.bfloat16),
    (16, 128, 128, 1, 0, jnp.bfloat16),
    (16, 2304, 256, 2, 1, jnp.bfloat16),
    (32, 256, 2304, 2, 0, jnp.bfloat16),
]


@pytest.mark.parametrize("count,k,n,layers,layer,dtype", SHAPES)
@pytest.mark.parametrize("pattern", PATTERNS)
def test_the_product_is_megabloxs_bit_for_bit(pattern, count, k, n, layers,
                                              layer, dtype):
    """The parent's form (megablox over the whole stack, the layer's sizes
    in a zero vector of ``layers * count`` groups) and this one (the
    layer's own metadata, the stack read behind ``layer * count``) on the
    same operands, interpreted: every row of a group is the same bits, and
    ``ragged_dot``'s within float tolerance."""
    rows = 3 * TM
    sizes = _sizes(pattern, count, rows, seed=count + k)
    x, w = _operands(count, k, n, layers, rows, dtype)
    tiling = expert_share.grouped_tiling(k, n)
    assert k % tiling[1] == 0 and n % tiling[2] == 0
    stacked = jax.lax.dynamic_update_slice(
        jnp.zeros((layers * count,), jnp.int32), sizes, (layer * count,))
    want = megablox.gmm(x, w, stacked, preferred_element_type=dtype,
                        tiling=tiling, interpret=True)
    meta = grouped_matmul.group_metadata(sizes, rows, tiling[0])
    got = grouped_matmul.gmm(x, w, meta, jnp.int32(layer * count), tiling,
                             interpret=True)
    assert got.dtype == want.dtype and got.shape == want.shape == (rows, n)
    held = int(jnp.sum(sizes))
    np.testing.assert_array_equal(np.asarray(got[:held], np.float32),
                                  np.asarray(want[:held], np.float32))
    if held:
        assert float(jnp.abs(got[:held].astype(jnp.float32)).max()) > 0.1
    plain = jax.lax.ragged_dot(x, w[layer * count:(layer + 1) * count],
                               sizes)
    tol = 2e-5 if dtype == jnp.float32 else 4e-2
    np.testing.assert_allclose(np.asarray(got[:held], np.float32),
                               np.asarray(plain[:held], np.float32),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("split", ["ragged_k", "ragged_n"])
def test_a_tile_that_does_not_divide_is_masked_as_megablox_masks_it(split):
    """A tiling forced past the rule (no configuration is handed one): the
    last k-tile's overhang is masked to zero, the last n-tile's is cut."""
    count, layers, layer, rows = 4, 2, 1, 2 * TM
    k, n = (384, 128) if split == "ragged_k" else (128, 384)
    tiling = (TM, 256, 256)
    sizes = _sizes("ragged", count, rows)
    x, w = _operands(count, k, n, layers, rows, jnp.float32)
    stacked = jnp.zeros((layers * count,), jnp.int32).at[count:].set(sizes)
    want = megablox.gmm(x, w, stacked, preferred_element_type=jnp.float32,
                        tiling=tiling, interpret=True)
    got = grouped_matmul.gmm(
        x, w, grouped_matmul.group_metadata(sizes, rows, TM), layer * count,
        tiling, interpret=True)
    held = int(jnp.sum(sizes))
    np.testing.assert_array_equal(np.asarray(got[:held]),
                                  np.asarray(want[:held]))


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
