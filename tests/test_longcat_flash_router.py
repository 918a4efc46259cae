"""The parts of a longcat_flash double layer on their own, against the
benchmark's plain reference at a small size: the two low-rank scales of the
latent attention, ``route`` with the config's three data (softmax, no
renormalisation, a width past the experts), the zero-compute term, the
guide's share test (all ranks' shares add up to the uncut layer, the
zero-compute term counted once), and the parameter counts at the published
and the held sizes. Served end to end: tests/test_longcat_flash.py."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import longcat_flash_util as U
from deepspeed_tpu.inference import latent
from deepspeed_tpu.models import longcat_flash
from deepspeed_tpu.moe import expert_share


def test_the_two_low_rank_scales_act_on_the_normed_low_ranks():
    """``_project`` with the scales against the same config with both at
    1.0: every head's query (both parts) is s_q times, the latent of the
    cache row s_kv times, the shared rotated key unchanged."""
    cfg, flat = U.tiny_config(), U.tiny_config(q_lora_scale=1.0,
                                               kv_lora_scale=1.0)
    p = jax.tree_util.tree_map(lambda a: a[1],
                               U.tiny_params(cfg)["block"]["a"])
    h = jax.random.normal(jax.random.PRNGKey(1), (5, cfg.d_model))
    pos = jnp.arange(5, dtype=jnp.int32)
    q_n, q_r, rows = latent._project(h, p, cfg, pos)
    q_n1, q_r1, rows1 = latent._project(h, p, flat, pos)
    s_q, s_kv = (32 / 24) ** 0.5, 2 ** 0.5
    r = cfg.kv_lora_rank
    np.testing.assert_allclose(q_n, s_q * q_n1, rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(q_r, s_q * q_r1, rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(rows[:, :r], s_kv * rows1[:, :r], rtol=1e-4,
                               atol=1e-6)
    np.testing.assert_array_equal(np.asarray(rows[:, r:]),
                                  np.asarray(rows1[:, r:]))
    assert float(jnp.abs(rows[:, :r] - rows1[:, :r]).max()) > 0.1


def _router_case(seed=3, tokens=24):
    cfg = U.tiny_config(held=None)
    p = jax.tree_util.tree_map(lambda a: a[1], U.tiny_params(cfg)["block"])
    u = jax.random.normal(jax.random.PRNGKey(seed), (tokens, cfg.d_model))
    return cfg, p, u


def test_route_softmax_unrenormalised_over_the_whole_width():
    """``route`` with the config's three data (softmax, no
    renormalisation, a kernel of 16 + 8 outputs) against the reference."""
    cfg, p, u = _router_case()
    sel, w = expert_share.route(u, p["moe"]["router"], cfg.moe_k,
                                cfg.routed_scaling, scoring="softmax",
                                renorm=False)
    with jax.default_matmul_precision("highest"):
        _, (own, biased), _ = U.reference().expert_layer(
            u, p["moe"], U.hp_of(cfg))
        probs = U.reference().router_probs(u, p["moe"])
    np.testing.assert_array_equal(np.sort(sel, -1), np.sort(own, -1))
    np.testing.assert_allclose(
        w, 6.0 * jnp.take_along_axis(probs, sel, -1), rtol=1e-5)
    assert int(sel.max()) >= cfg.num_experts       # zero experts are chosen
    assert float(jnp.sum(w, -1).max()) < 6.0       # not renormalised
    # renormalised, the k weights sum to the scale
    _, wr = expert_share.route(u, p["moe"]["router"], cfg.moe_k, 6.0,
                               scoring="softmax")
    np.testing.assert_allclose(jnp.sum(wr, -1), 6.0, rtol=1e-5)


@pytest.mark.parametrize("push,zero_pairs", [(10.0, 24 * 4), (-10.0, 0)])
def test_the_zero_compute_term(push, zero_pairs):
    """A token whose choices are ALL identity experts comes out as ``6 *
    sum(p) * u``; with none of them the layer is the held experts' routed
    part alone (the parent's arithmetic), and the counters say which."""
    cfg, p, u = _router_case()
    E = cfg.num_experts
    moe = dict(p["moe"], router=dict(
        p["moe"]["router"], bias=jnp.where(jnp.arange(E + 8) >= E, push,
                                           0.0)))
    valid = jnp.arange(24) < 20
    y, sel, stats, _ = expert_share.sparse_ffn(u, moe, cfg, "ragged_dot",
                                               valid=valid)
    stats = dict(zip(expert_share.stat_fields(cfg), np.asarray(stats)))
    w = 6.0 * jnp.take_along_axis(U.reference().router_probs(u, moe), sel,
                                  -1)
    routed, _ = expert_share.held_experts_ffn(
        u, moe["experts"], sel, w, cfg.held, "ragged_dot", valid)
    if zero_pairs:
        assert bool((sel >= E).all())
        want = jnp.where(valid[:, None], u * jnp.sum(w, -1)[:, None], 0.0)
        np.testing.assert_allclose(y, want, rtol=1e-5, atol=1e-6)
        assert float(jnp.abs(routed).max()) == 0.0
        assert stats["pairs_zero"] == 20 * 4 and stats["pairs_held"] == 0
        assert stats["real_pairs_max_token"] == 0
    else:
        assert bool((sel < E).all())
        np.testing.assert_array_equal(np.asarray(y), np.asarray(routed))
        assert stats["pairs_zero"] == 0 and stats["pairs_held"] == 20 * 4
        assert stats["real_pairs_max_token"] == 4
    assert stats["pairs_total"] == 20 * 4


def test_all_ranks_shares_add_up_to_the_whole_layer():
    """The guide's share test: 16 experts in 4 shares of 4. The routed
    parts of all shares, with the zero-compute term (which every chip
    computes alike) counted ONCE, are the uncut reference's ``M(u)``."""
    cfg, p, u = _router_case()
    with jax.default_matmul_precision("highest"):
        want, _, ref_zero = U.reference().expert_layer(u, p["moe"],
                                                       U.hp_of(cfg))
    got, zero, held_pairs, zero_pairs = 0.0, None, 0, set()
    for first in range(0, 16, 4):
        share = U.tiny_config(held=(first, 4))
        moe = dict(p["moe"], experts={
            n: {"kernel": e["kernel"][first:first + 4]}
            for n, e in p["moe"]["experts"].items()})
        y, sel, stats, _ = expert_share.sparse_ffn(u, moe, share,
                                                   "ragged_dot")
        w = 6.0 * jnp.take_along_axis(
            U.reference().router_probs(u, moe), sel, -1)
        zero, _ = expert_share.zero_experts_term(u, sel, w, cfg.num_experts)
        got = got + (y - zero)
        held_pairs += int(stats[0])
        zero_pairs.add(int(stats[5]))
    (n_zero,) = zero_pairs                         # every rank counts alike
    assert held_pairs + n_zero == u.shape[0] * cfg.moe_k
    np.testing.assert_allclose(zero, ref_zero, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(got + zero, want, atol=1e-4)


def test_num_params_published_and_held():
    held = longcat_flash.LongcatFlashConfig(
        vocab_size=16384, n_layers=4, n_heads=64, d_model=6144, d_ff=12288,
        experts_held=(0, 16))
    assert longcat_flash.num_params(held) == 5_172_749_312
    published = longcat_flash.LongcatFlashConfig(
        vocab_size=131072, n_layers=28, n_heads=64, d_model=6144,
        d_ff=12288)
    assert longcat_flash.num_params(published) == 560_664_980_480
    tiny = U.tiny_config()
    leaves = jax.tree_util.tree_leaves(jax.eval_shape(
        lambda: longcat_flash.init_params(jax.random.PRNGKey(0), tiny)))
    assert longcat_flash.num_params(tiny) == sum(a.size for a in leaves)
