"""The expert layer's grouped product (ops/grouped_matmul.py) against what
it replaced: megablox's ``gmm`` handed every sparse layer's stacked experts
and the layer's group sizes written into a zero vector over all of them.
The metadata the layer makes is megablox's ``make_group_metadata`` for the
layer's own groups, entry for entry; the product is megablox's bit for bit
and ``ragged_dot``'s within float tolerance. That ``held_experts_ffn`` gives
the parent's output and counters at each sparse configuration's tiny preset
is tests/test_grouped_matmul_layer.py."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

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
