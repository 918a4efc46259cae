"""The tiles the grouped expert product hands its kernel follow the
product's own shape (moe/expert_share.py ``grouped_tiling``): no ragged k-
or n-tile at any benchmark configuration's experts, the preferred tile
itself wherever it divides, and the same layer whatever the split."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.moe import expert_share

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark", "configs")
# the four sparse serving configurations, and whether the preferred tile
# divides their experts' [d, f] (then their programs are PR 28's, tile for
# tile)
SPARSE = (("k-exaone-236b-a23b-serve-ep8", True),
          ("dots-vlm1-inst-serve-ep16", True),
          ("zaya1-8b-serve-pp2", True),
          ("kimi-linear-48b-a3b-serve-ep16", False))
PRODUCTS = ("wg", "wi", "wo")


def _product_shape(config, product):
    """(k, n) of one of a configuration's three grouped products."""
    with open(os.path.join(CONFIGS, config + ".json")) as fh:
        c = json.load(fh)
    d, f = c["hidden_size"], c["moe_intermediate_size"]
    return (f, d) if product == "wo" else (d, f)


def _as_before(k, n):
    """What every product was handed until the tile followed the shape."""
    tm, tk, tn = expert_share.GMM_TILING
    return tm, min(tk, k), min(tn, n)


@pytest.mark.parametrize("product", PRODUCTS)
@pytest.mark.parametrize("config,preferred_divides", SPARSE)
def test_no_benchmark_configuration_fetches_a_ragged_tile(
        config, preferred_divides, product):
    k, n = _product_shape(config, product)
    tiling = expert_share.grouped_tiling(k, n)
    assert expert_share.ragged_tile_share(k, n, tiling) == 0.0
    assert tiling[0] == expert_share.GMM_TILING[0]
    assert all(t % 128 == 0 for t in tiling)
    if preferred_divides:
        # the same tiling, so the same compiled program as before
        assert tiling == (128, 1024, 1024) == _as_before(k, n)
    else:
        assert expert_share.ragged_tile_share(k, n, _as_before(k, n)) == 0.25


@pytest.mark.parametrize("dim,pref,tile", [
    (2304, 1024, 768),      # Kimi-Linear: 3 x 768, not 1,024 + 1,024 + 256
    (6144, 1024, 1024), (7168, 1024, 1024), (2048, 1024, 1024),
    (1024, 1024, 1024),
    (1024, 2048, 1024),     # a preferred tile past the dimension: all of it
    (6144, 2048, 2048), (6144, 6144, 6144), (2048, 512, 512),
    (384, 256, 128), (2304, 2048, 1152), (1536, 1024, 768),
    (32, 1024, 32), (100, 64, 64),  # no whole-lane divisor: as before
])
def test_the_tile_is_the_largest_whole_lane_divisor_within_the_preferred(
        monkeypatch, dim, pref, tile):
    monkeypatch.setattr(expert_share, "GMM_TILING", (128, pref, pref))
    assert expert_share.grouped_tiling(dim, dim) == (128, tile, tile)
    assert expert_share.grouped_tiling(dim, pref)[1:] == (tile, pref)
    assert expert_share.grouped_tiling(pref, dim)[1:] == (pref, tile)


@pytest.mark.parametrize("k,n,tiling,share", [
    (2304, 1024, (128, 1024, 1024), 0.25),
    (1024, 2304, (128, 1024, 1024), 0.25),
    (2304, 2304, (128, 1024, 1024), 1 - 2304 * 2304 / (3072 * 3072)),
    (2304, 1024, (128, 1152, 1024), 0.0),
    (2304, 1024, (128, 768, 1024), 0.0),
    (6144, 2048, (128, 1024, 1024), 0.0),
    (100, 64, (128, 64, 64), 1 - 100 / 128),
])
def test_ragged_tile_share_is_the_fetched_area_outside_the_matrix(
        k, n, tiling, share):
    assert expert_share.ragged_tile_share(k, n, tiling) == pytest.approx(
        share)


def _layer(d=384, f=256, held=4, experts=8, T=24, K=3, layers=3):
    r = np.random.default_rng(5)
    stacked = {n: {"kernel": jnp.asarray(
        r.standard_normal((layers * held,) + s) * s[0] ** -0.5, jnp.float32)}
        for n, s in (("wg", (d, f)), ("wi", (d, f)), ("wo", (f, d)))}
    h = jnp.asarray(r.standard_normal((T, d)), jnp.float32)
    sel = jnp.asarray(np.stack([r.choice(experts, K, replace=False)
                                for _ in range(T)]), jnp.int32)
    w = jnp.asarray(r.random((T, K)), jnp.float32)
    return stacked, h, sel, w, (2, held)


@pytest.mark.parametrize("split", ["rule", "as_before"])
@pytest.mark.parametrize("layer", [None, 1])
def test_the_kernel_at_a_width_the_preferred_tile_does_not_divide(
        monkeypatch, pallas_interpret, layer, split):
    """d = 384 under a preferred tile of 256: the rule hands the kernel
    three whole tiles of 128 where ``min(preferred, d)`` gave 256 + a
    masked 128; both are ``ragged_dot``'s layer, output and counters, with
    every sparse layer's experts stacked behind the ``layer`` index and
    without."""
    monkeypatch.setattr(expert_share, "GMM_TILING", (128, 256, 256))
    if split == "as_before":
        monkeypatch.setattr(expert_share, "grouped_tiling", _as_before)
    up = expert_share.grouped_tiling(384, 256)
    assert up == ((128, 128, 256) if split == "rule" else (128, 256, 256))
    assert expert_share.ragged_tile_share(384, 256, up) == (
        0.0 if split == "rule" else 0.25)
    stacked, h, sel, w, held = _layer()
    if layer is None:
        experts = {n: {"kernel": e["kernel"][held[1]:2 * held[1]]}
                   for n, e in stacked.items()}
    else:
        experts = stacked
    valid = jnp.arange(h.shape[0]) < 20
    want, want_stats = expert_share.held_experts_ffn(
        h, experts, sel, w, held, "ragged_dot", valid, layer)
    got, stats = expert_share.held_experts_ffn(
        h, experts, sel, w, held, "gmm", valid, layer)
    assert float(jnp.abs(want).max()) > 0.1
    assert int(stats[0]) > 0 and int(stats[3]) > 1
    np.testing.assert_array_equal(np.asarray(stats), np.asarray(want_stats))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("product", ("wg", "wo"))
@pytest.mark.parametrize("config", [c for c, _ in SPARSE])
def test_the_product_lowers_for_the_tpu_in_whole_tiles(config, product):
    """The grouped product at a cell's widths through the Pallas -> Mosaic
    lowering (no chip): one kernel whose grid is whole n-tiles x the visited
    row tiles x whole k-tiles, its weight block the rule's ``[tk, tn]``."""
    k, n = _product_shape(config, product)
    _, tk, tn = expert_share.grouped_tiling(k, n)
    S = jax.ShapeDtypeStruct
    args = (S((384, k), jnp.bfloat16), S((32, k, n), jnp.bfloat16),
            S((32,), jnp.int32))

    def call(x, w, sizes):
        return expert_share._grouped(x, w, sizes, "gmm")
    text = jax.jit(call).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()
    assert text.count("tpu_custom_call") == 1
    calls = []

    def walk(jaxpr):
        for e in jaxpr.eqns:
            if e.primitive.name == "pallas_call":
                calls.append(e.params["grid_mapping"])
            for sub in jax.core.jaxprs_in_params(e.params):
                walk(sub)
    walk(jax.make_jaxpr(call)(*args).jaxpr)
    (gm,) = calls
    assert (gm.grid[0], gm.grid[2]) == (n // tn, k // tk)
    assert (tk, tn) in [bm.block_aval.shape for bm in gm.block_mappings]
