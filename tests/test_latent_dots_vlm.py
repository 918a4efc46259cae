"""The dots_vlm dialect (dots.vlm1's language model: latent attention,
experts routed inside groups, YaRN) below the serving path, held to the
benchmark's plain reference at small sizes: the absorbed and the expanded
path on one cache, the kernels, the router, the share of 16. Serving, the
counters, the controls and what raises: tests/test_latent_dots_vlm_serving.py."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import dots_vlm_util as U
from deepspeed_tpu.inference import latent
from deepspeed_tpu.models import dots_vlm
from deepspeed_tpu.moe import expert_share
from deepspeed_tpu.ops.attention import mla, rotary


def _layer(cfg, params, stack="block", index=0):
    return jax.tree_util.tree_map(lambda a: a[index], params[stack])


def test_absorbed_and_expanded_paths_agree_on_the_same_cache():
    """A token decoded over a slot's cached rows (absorbed: the query
    folded through ``k_up``, the latent mean through ``v_up``) equals the
    same token prefilled as a one-token chunk (expanded: the history
    re-expanded to per-head keys and values), layer for layer."""
    cfg = U.tiny_config()
    params = U.tiny_params(cfg)
    p = _layer(cfg, params)
    bs, NB, T = 4, 8, 13
    rng = jax.random.PRNGKey(5)
    hist = jax.random.normal(rng, (1, T, cfg.d_model))
    pool = jnp.zeros((1 + NB, bs, cfg.latent_lanes))
    table = jnp.arange(1, NB + 1, dtype=jnp.int32)
    base = {"rows": jnp.int32(0), "index": jnp.int32(0)}
    aux = {"route": jnp.zeros((cfg.n_sparse_layers, 16, cfg.moe_k),
                              jnp.int32), "stats": None}
    experts = {n: {"kernel": e["kernel"]}
               for n, e in p["moe"]["experts"].items()}
    pad = jnp.zeros((1, 16 - T, cfg.d_model))
    (_, _), (pool,) = latent.block_prefill(
        (jnp.concatenate([hist, pad], 1), aux), (pool,), table,
        jnp.arange(16, dtype=jnp.int32), T, p, cfg, base, "gather", experts)
    x = jax.random.normal(jax.random.PRNGKey(6), (1, 1, cfg.d_model))
    aux1 = dict(aux, route=aux["route"][:, :1])
    (y_dec, _), (pool_d,) = latent.block_decode(
        (x, aux1), (pool,), table[None], jnp.asarray([T], jnp.int32),
        jnp.asarray([True]), p, cfg, base, "gather", experts)
    (y_pre, _), (pool_p,) = latent.block_prefill(
        (x, aux1), (pool,), table, jnp.asarray([T], jnp.int32), 1, p, cfg,
        base, "gather", experts)
    np.testing.assert_allclose(np.asarray(y_dec[:, 0]),
                               np.asarray(y_pre[0]), atol=2e-5)
    np.testing.assert_array_equal(np.asarray(pool_d), np.asarray(pool_p))
    # the padding lanes of a written row stay zero
    assert float(jnp.abs(pool_d[..., cfg.latent_row:]).max()) == 0.0
    assert float(jnp.abs(pool_d[1, 0, :cfg.latent_row]).max()) > 0.0


@functools.partial(jax.jit, static_argnames=("vw", "plan_given"))
def _kernel_interpreted(q, pool, tables, lengths, active=None, *, vw,
                        plan_given=False):
    from deepspeed_tpu.ops.attention.paged import decode_plan
    plan = decode_plan(lengths, tables.shape[1], pool.shape[1],
                       active=active) if plan_given else None
    return mla.mla_decode_attention(
        q, pool, tables, lengths, value_width=vw, scale=0.11,
        interpret=True, plan=plan)


@pytest.mark.parametrize("bs,nb", [(16, 24), (128, 3), (8, 4)])
def test_kernel_in_interpret_mode_equals_the_plain_latent_decode(bs, nb):
    """Ragged lengths, an idle slot on the trash block, table entries past
    a slot's length that name trash; tiles of several blocks (16 x 8), of
    one block (128), and a whole small table in one tile."""
    B, H, row, vw = 5, 8, 256, 128
    N = 1 + B * nb
    ks = jax.random.split(jax.random.PRNGKey(bs), 3)
    pool = jax.random.normal(ks[0], (N, bs, row))
    pool = pool.at[0].set(1e4)                   # trash: loud if attended
    q = jax.random.normal(ks[1], (B, H, row))
    cap = nb * bs
    lengths = np.asarray([0, cap - 1, cap // 2, 3, 0], np.int32)
    tables = 1 + np.arange(B * nb, dtype=np.int32).reshape(B, nb)
    for b in range(B):                           # unused entries: trash
        tables[b, lengths[b] // bs + 1:] = 0
    tables[4] = 0                                # an idle slot
    args = (q, pool, jnp.asarray(tables), jnp.asarray(lengths))
    want = mla.mla_decode_reference(*args, value_width=vw, scale=0.11)
    got = _kernel_interpreted(*args, vw=vw)
    np.testing.assert_allclose(np.asarray(got[:4]), np.asarray(want[:4]),
                               atol=2e-5, rtol=2e-5)
    assert np.isfinite(np.asarray(got)).all()
    # a plan worked out by the caller is the call's own
    again = _kernel_interpreted(*args, vw=vw, plan_given=True)
    np.testing.assert_array_equal(np.asarray(again), np.asarray(got))


@pytest.mark.parametrize("bs,nb,per_step", [(512, 48, 1), (128, 12, 1),
                                            (16, 300, 8), (64, 5, 2)])
def test_the_latent_kernels_tile_is_the_token_rules(bs, nb, per_step):
    """``mla_decode`` keeps the tile it had before ``paged_decode``'s began
    to follow its pool's bytes (PR 53): the fewest blocks of 128 positions,
    whatever a row weighs. It takes a plan of that tile and refuses one cut
    by the bytes where the two differ (12 blocks of 128 rows of 1,280
    bytes: four a step by the bytes)."""
    from deepspeed_tpu.ops.attention.paged import (blocks_per_step,
                                                   decode_plan,
                                                   pool_row_bytes)
    B, H, row = 2, 4, 640
    pool = jax.ShapeDtypeStruct((1 + B * nb, bs, row), jnp.bfloat16)
    q = jax.ShapeDtypeStruct((B, H, row), jnp.bfloat16)
    tables = jax.ShapeDtypeStruct((B, nb), jnp.int32)
    lengths = jax.ShapeDtypeStruct((B,), jnp.int32)
    assert blocks_per_step(nb, bs) == per_step

    def call(q, pool, tables, lengths, by_bytes):
        rb = pool_row_bytes(pool) if by_bytes else None
        plan = decode_plan(lengths, nb, bs, row_bytes=rb)
        assert plan.cut[-1] == blocks_per_step(nb, bs, rb)
        return mla.mla_decode_attention(q, pool, tables, lengths,
                                        value_width=512, scale=0.1,
                                        interpret=True, plan=plan)
    out = jax.eval_shape(functools.partial(call, by_bytes=False), q, pool,
                         tables, lengths)
    assert out.shape == (B, H, 512)
    if blocks_per_step(nb, bs, row * 2) != per_step:
        with pytest.raises(AssertionError):
            jax.eval_shape(functools.partial(call, by_bytes=True), q, pool,
                           tables, lengths)


@pytest.mark.parametrize("live", ["some-live", "none-live"])
@pytest.mark.parametrize("bs,nb", [(16, 24), (128, 3)])
def test_kernel_does_not_visit_a_slot_that_does_not_decode(bs, nb, live):
    """``decode_plan(active=)`` through ``mla_decode``: a slot with no
    request and one in mid-prefill (inactive, rows of its own) have no
    grid step. NaN in the trash block and in all of the prefilling slot's
    blocks moves no live slot's output by a bit, the rows of the slots
    that do not decode are exactly zero, and with no slot live the call
    gives zeros."""
    B, H, row, vw = 5, 8, 256, 128
    N = 1 + B * nb
    ks = jax.random.split(jax.random.PRNGKey(bs), 2)
    pool = np.asarray(jax.random.normal(ks[0], (N, bs, row))).copy()
    pool[0] = 0.0
    q = jax.random.normal(ks[1], (B, H, row))
    cap = nb * bs
    lengths = np.asarray([0, cap - 1, min(300, cap - 5), 3, cap // 2],
                         np.int32)
    tables = 1 + np.arange(B * nb, dtype=np.int32).reshape(B, nb)
    tables[0] = 0                                # no request
    active = np.asarray([False, True, False, True, True])  # 2: in prefill
    if live == "none-live":
        active[:] = False

    def call(pool):
        return _kernel_interpreted(q, pool, jnp.asarray(tables),
                                   jnp.asarray(lengths), jnp.asarray(active),
                                   vw=vw, plan_given=True)
    clean = np.asarray(call(jnp.asarray(pool)))
    want = np.asarray(mla.mla_decode_reference(
        q, jnp.asarray(pool), jnp.asarray(tables), jnp.asarray(lengths),
        value_width=vw, scale=0.11))
    pool[0] = np.nan
    pool[tables[2]] = np.nan
    got = np.asarray(call(jnp.asarray(pool)))
    np.testing.assert_array_equal(got, clean)
    assert (got[~active] == 0).all()
    np.testing.assert_allclose(got[active], want[active], atol=2e-5,
                               rtol=2e-5)


def _reference_selection(ref, cfg, h, router):
    """The reference's own group-limited selection for pre-normed ``h``."""
    hp = U.hp_of(cfg)
    p = {"ln2": {"scale": jnp.ones((cfg.d_model,))},
         "moe": {"router": router,
                 "experts": {"wg": {"kernel": jnp.zeros((1, cfg.d_model, 1))},
                             "wi": {"kernel": jnp.zeros((1, cfg.d_model, 1))},
                             "wo": {"kernel": jnp.zeros((1, 1, cfg.d_model))}},
                 "shared": {n: {"kernel": jnp.zeros((cfg.d_model, 1))
                                if n != "mlp_out"
                                else jnp.zeros((1, cfg.d_model))}
                            for n in ("mlp_gate", "mlp_in", "mlp_out")}}}
    hp = dict(hp, held=(0, 1))
    free = -jnp.ones((h.shape[0], cfg.moe_k), jnp.int32)
    with jax.default_matmul_precision("highest"):
        _, (own, biased, group) = ref._sparse_ffn(
            h, p, hp, frozenset(), False, free)
    return np.asarray(own), np.asarray(biased), np.asarray(group)


def test_group_limited_router_equals_the_reference():
    cfg = U.tiny_config()
    router = _layer(cfg, U.tiny_params(cfg))["moe"]["router"]
    x = jax.random.normal(jax.random.PRNGKey(3), (64, cfg.d_model)) * 3.0
    h = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + cfg.norm_eps)
    sel, w = expert_share.route(h, router, cfg.moe_k, cfg.routed_scaling,
                                cfg.n_group, cfg.topk_group)
    own, biased, group = _reference_selection(U.reference(), cfg, x, router)
    np.testing.assert_array_equal(np.sort(np.asarray(sel), -1),
                                  np.sort(own, -1))
    # every token chose inside its two best groups, and the limit binds
    per = cfg.num_experts // cfg.n_group
    kept = np.argsort(-group, -1)[:, :cfg.topk_group]
    for t in range(64):
        assert set(np.asarray(sel[t]) // per) <= set(kept[t])
    free_sel, _ = expert_share.route(h, router, cfg.moe_k,
                                     cfg.routed_scaling)
    assert (np.sort(np.asarray(free_sel), -1) != np.sort(own, -1)).any()
    np.testing.assert_allclose(np.asarray(w).sum(-1), cfg.routed_scaling,
                               rtol=1e-5)


def _route_text(h, router, *groups):
    def routed(h, router):
        return expert_share.route(h, router, 3, 2.5, *groups)
    return jax.jit(routed).lower(h, router).as_text()


def test_one_group_is_todays_route():
    cfg = U.tiny_config()
    router = _layer(cfg, U.tiny_params(cfg))["moe"]["router"]
    h = jax.random.normal(jax.random.PRNGKey(4), (32, cfg.d_model))
    sel, w = expert_share.route(h, router, 3, 2.5)
    one, w1 = expert_share.route(h, router, 3, 2.5, 1, 1)
    scores = jax.nn.sigmoid(jnp.dot(
        h, router["kernel"], precision=jax.lax.Precision.HIGHEST))
    want = jax.lax.top_k(scores + router["bias"], 3)[1]
    np.testing.assert_array_equal(np.asarray(sel), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(sel), np.asarray(one))
    np.testing.assert_array_equal(np.asarray(w), np.asarray(w1))
    # and the programs are the same: the group limit is a Python branch
    assert _route_text(h, router) == _route_text(h, router, 1, 1)


def test_yarn_frequencies_and_scale_are_the_published_numbers():
    cfg = dots_vlm.DotsVLMConfig(n_layers=2, n_heads=128, d_model=7168)
    f = cfg.rope_inv_freq
    assert f.shape == (32,)
    # low = floor(64 ln(4096 / (32 * 2 pi)) / (2 ln 1e4)) = 10,
    # high = ceil(64 ln(4096 / (2 pi)) / (2 ln 1e4)) = 23
    plain = 10000.0 ** (-np.arange(32) / 32.0)
    np.testing.assert_allclose(f[:11], plain[:11], rtol=1e-12)
    np.testing.assert_allclose(f[23:], plain[23:] / 40.0, rtol=1e-12)
    np.testing.assert_allclose(f[11], 0.03900693, rtol=1e-6)
    np.testing.assert_allclose(f[22], plain[22] * (1 / 13)
                               + plain[22] / 40 * (12 / 13), rtol=1e-9)
    np.testing.assert_allclose(f[22], 1.77827941e-04, rtol=1e-6)
    np.testing.assert_allclose(f[31], 3.33380358e-06, rtol=1e-6)
    assert abs(rotary.yarn_mscale(40.0, 1.0) - 1.3688879454) < 1e-9
    assert abs(cfg.softmax_scale - 192 ** -0.5 * 1.3688879454 ** 2) < 1e-9
    assert cfg.latent_row == 576 and cfg.latent_lanes == 640
    # the reference computes the same table by its own code
    ref_f, amp, scale = U.reference().yarn_frequencies(U.hp_of(cfg))
    np.testing.assert_allclose(ref_f, f, rtol=1e-12)
    assert amp == 1.0 and abs(scale - cfg.softmax_scale) < 1e-12
    # plain rotary where no factor is given
    flat = dots_vlm.DotsVLMConfig(n_layers=2, n_heads=4, d_model=32,
                                  rope_factor=1.0)
    np.testing.assert_allclose(flat.rope_inv_freq, plain, rtol=1e-12)
    assert abs(flat.softmax_scale - 192 ** -0.5) < 1e-12


def test_rotary_pairs_are_interleaved():
    x = jnp.arange(8, dtype=jnp.float32).reshape(1, 8) + 1.0
    f = np.asarray([0.5, 0.25, 0.125, 1.0])
    y = np.asarray(rotary.apply_rotary_freqs(x, jnp.asarray([3]), f))[0]
    for i in range(4):
        a, b, ang = x[0, 2 * i], x[0, 2 * i + 1], 3 * f[i]
        np.testing.assert_allclose(
            y[2 * i:2 * i + 2], [a * np.cos(ang) - b * np.sin(ang),
                                 b * np.cos(ang) + a * np.sin(ang)],
            rtol=1e-5)
    # over a heads axis the position is shared
    xh = jnp.stack([x, 2 * x], axis=1)                     # [1, 2, 8]
    yh = np.asarray(rotary.apply_rotary_freqs(xh, jnp.asarray([3]), f))
    np.testing.assert_allclose(yh[0, 0], y, rtol=1e-6)
    np.testing.assert_allclose(yh[0, 1], 2 * y, rtol=1e-6)


def test_sixteen_shares_add_up_to_the_whole_layer():
    """256 experts cut... at this size 16 experts in 16 shares of one: the
    routed parts of all shares plus the shared expert counted once are the
    uncut reference's whole layer."""
    ref = U.reference()
    whole = U.tiny_config(held=None)
    p = _layer(whole, U.tiny_params(whole), index=1)
    x = jax.random.normal(jax.random.PRNGKey(3), (24, whole.d_model)) * 3.0
    hp = U.hp_of(whole)
    free = -jnp.ones((x.shape[0], whole.moe_k), jnp.int32)
    pr = dict(p, ln2={"scale": jnp.ones_like(p["ln2"]["scale"])})
    with jax.default_matmul_precision("highest"):
        want, _ = ref._sparse_ffn(x, pr, hp, frozenset(), False, free)
    xn = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + hp["eps"])
    sel, w = expert_share.route(xn, p["moe"]["router"], whole.moe_k,
                                whole.routed_scaling, whole.n_group,
                                whole.topk_group)
    from deepspeed_tpu.inference.hybrid import _swiglu
    got = _swiglu(xn, p["moe"]["shared"])
    held_pairs = 0
    for first in range(16):
        share = {n: {"kernel": p["moe"]["experts"][n]["kernel"]
                     [first:first + 1]} for n in ("wg", "wi", "wo")}
        part, stats = expert_share.held_experts_ffn(
            xn, share, sel, w, (first, 1), "ragged_dot")
        got = got + part
        held_pairs += int(stats[0])
    assert held_pairs == x.shape[0] * whole.moe_k
    np.testing.assert_allclose(np.asarray(x + got), np.asarray(want),
                               atol=1e-4)


# ---- the expanded prefill's flash step as ONE kernel (mla_prefill_step) ----

def _step_case(H, C, T, blocks, start, seed=0, dn=16, dr=8, dv=16,
               dtype=jnp.bfloat16):
    """A carry that has attended the chunk's own tile, and a history tile
    of ``blocks`` blocks of ``T`` keys of which ``start`` are occupied."""
    ks = jax.random.split(jax.random.key(seed), 8)
    S = T * blocks

    def rnd(k, *shape):
        return jax.random.normal(k, shape, jnp.float32).astype(dtype)
    q_n, q_r = rnd(ks[0], H, C, dn), rnd(ks[1], H, C, dr)
    qpos = start + jnp.arange(C, dtype=jnp.int32)
    init = (jnp.full((H, C), latent.NEG_INF, jnp.float32),
            jnp.zeros((H, C), jnp.float32), jnp.zeros((H, C, dv), jnp.float32))
    carry = latent._attend_tile(
        init, jnp.concatenate([q_n, q_r], -1), rnd(ks[2], H, C, dn + dr),
        rnd(ks[3], H, C, dv), qpos, qpos, 0.2)
    kpos = jnp.arange(S, dtype=jnp.int32)
    kpos = jnp.where(kpos < start, kpos, jnp.int32(2 ** 30))
    return carry, q_n, q_r, rnd(ks[4], H, S, dn), rnd(ks[5], S, dr), \
        rnd(ks[6], H, S, dv), kpos, qpos


def _lanes(carry):
    """The plain step's carry (max ``[H, C]``, sum ``[H, C]``, accumulator
    ``[H, C, d_v]``) as the kernel holds it, the queries in the lanes."""
    m, l, acc = carry
    return m[:, None], l[:, None], acc.transpose(0, 2, 1)


def _rows(carry):
    m, l, acc = carry
    return m[:, 0], l[:, 0], acc.transpose(0, 2, 1)


@functools.partial(jax.jit, static_argnames="blocks")
def _step_kernel(carry, q_n, q_r, k_n, k_r, v, kpos, qpos, blocks=None):
    """``mla_prefill_step`` (interpreted) on the plain step's operands."""
    return _rows(mla.mla_prefill_step(
        _lanes(carry), q_n.transpose(0, 2, 1), q_r.transpose(0, 2, 1), k_n,
        k_r, v.transpose(0, 2, 1), kpos, qpos, 0.2, blocks=blocks,
        interpret=True))


def _step_plain(carry, q_n, q_r, k_n, k_r, v, kpos, qpos, scale):
    k_r = jnp.broadcast_to(k_r[None], (k_n.shape[0],) + k_r.shape)
    return latent._attend_tile(
        carry, jnp.concatenate([q_n, q_r], -1),
        jnp.concatenate([k_n, k_r], -1), v, kpos, qpos, scale)


@pytest.mark.parametrize("occupied", ["none", "mid_block", "whole"])
@pytest.mark.parametrize("T,blocks", [(16, 1), (16, 2), (16, 4), (64, 1),
                                      (64, 2), (64, 4)])
@pytest.mark.parametrize("H,C", [(4, 8), (4, 64), (32, 8)])
def test_prefill_step_kernel_equals_the_plain_flash_step(H, C, T, blocks,
                                                         occupied):
    """``mla_prefill_step`` (interpreted; its operands the plain step's
    with the queries turned into the lanes) against ``_attend_tile`` on the
    same carry: a tile of 1, 2 or 4 blocks whose keys are all masked
    (``kpos`` 2**30: the carry comes back as it went in), occupied up to
    the middle of its last block, or whole."""
    S = T * blocks
    start = {"none": 0, "mid_block": S - T // 2 - 1, "whole": S}[occupied]
    args = _step_case(H, C, T, blocks, start, seed=H + C + S)
    got = _step_kernel(*args, blocks=blocks)
    want = _step_plain(*args, 0.2)
    if occupied == "none":
        for g, c in zip(got, args[0]):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(c))
    # bf16 operands: the plain step rounds its scores and its value product
    # to bf16, the kernel keeps both in float32
    for g, w in zip(got, want):
        scale = float(jnp.abs(w).max())
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   atol=1.5e-2 * max(scale, 1.0))


@jax.jit
def _step_same_order(carry, q_n, q_r, k_n, k_r, v, kpos, qpos, scale=0.2):
    """``_attend_tile`` in the kernel's order of operations: the queries in
    the lanes, the score each head's own product plus the shared key's,
    both products left in float32."""
    m, l, acc = _lanes(carry)
    f32 = jnp.float32
    s = (jnp.einsum("hsd,hcd->hsc", k_n, q_n, preferred_element_type=f32)
         + jnp.einsum("sd,hcd->hsc", k_r, q_r,
                      preferred_element_type=f32)) * scale
    s = jnp.where(kpos[None, :, None] <= qpos[None, None, :], s,
                  latent.NEG_INF)
    m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
    pr = jnp.exp(s - m_new)
    alpha = jnp.exp(m - m_new)
    l = alpha * l + jnp.sum(pr, axis=1, keepdims=True)
    acc = acc * alpha + jnp.einsum(
        "hsd,hsc->hdc", v, pr.astype(v.dtype), preferred_element_type=f32)
    return _rows((m_new, l, acc))


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("H,C,T", [(4, 8, 16), (32, 64, 64)])
def test_prefill_step_kernel_is_the_same_arithmetic_to_the_bit(H, C, T, dtype):
    """One block a call is the plain step's order of operations once the
    score is taken as the kernel takes it and nothing is rounded below
    float32 but the probabilities: the max and the sum equal to the bit,
    the accumulator to the last bit of a product summed in another order
    (a head's ``[d_v, T] x [T, C]`` here, one batched product there)."""
    args = _step_case(H, C, T, 1, T - 3, seed=3, dtype=dtype)
    got = _step_kernel(*args, blocks=1)
    want = _step_same_order(*args)
    for g, w in zip(got[:2], want[:2]):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    np.testing.assert_allclose(np.asarray(got[2]), np.asarray(want[2]),
                               rtol=1e-6, atol=1e-6)


def prefill_three_chunks(cfg, p, impl, seed=11, bs=4, C=10, last=7, NB=10):
    """A prompt of ``2 C + last`` tokens through ``attend_prefill`` in three
    chunks (the second starts mid-block, the third past four whole blocks
    and is not full): every chunk's output and the pool after it."""
    x = jax.random.normal(jax.random.key(seed), (3, C, cfg.d_model))
    pool = jnp.zeros((1 + NB, bs, cfg.latent_lanes))
    table = jnp.arange(1, NB + 1, dtype=jnp.int32)

    def step(x, pool, start, n):
        return latent.attend_prefill(
            x, pool, table, start + jnp.arange(C, dtype=jnp.int32), n, p,
            cfg, jnp.int32(0), impl)
    step = jax.jit(step)
    ys = []
    for i, n in enumerate((C, C, last)):
        y, pool = step(x[i], pool, jnp.int32(i * C), jnp.int32(n))
        ys.append(np.asarray(y[:n]))
    return ys, np.asarray(pool)


def test_expanded_prefill_through_the_kernel_equals_the_plain_path(
        pallas_interpret):
    """``attend_prefill`` with ``impl`` "pallas" (every flash step one
    ``mla_prefill`` call: the chunk's own tile, four history blocks a call,
    what is left one a call) against the plain path over a three-chunk
    prompt with a rotated shared key."""
    cfg = U.tiny_config()
    p = _layer(cfg, U.tiny_params(cfg))
    assert latent.blocks_per_call(cfg, 4, 4) == 4
    got, got_pool = prefill_three_chunks(cfg, p, "pallas")
    want, want_pool = prefill_three_chunks(cfg, p, "gather")
    np.testing.assert_array_equal(got_pool, want_pool)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=2e-5)


def test_blocks_per_call_follow_the_shapes():
    """dots.vlm1's 128 heads expand a 512-token block to 33.6 MB of keys
    and values, Kimi-Linear's 32 to 8.4 MB: two blocks a call, and four;
    one where a block alone passes the bound."""
    class Shape:
        qk_nope_head_dim = v_head_dim = 128

        def __init__(self, heads):
            self.n_heads = heads
    assert latent.blocks_per_call(Shape(128), 512, 2) == 2
    assert latent.blocks_per_call(Shape(64), 512, 2) == 4
    assert latent.blocks_per_call(Shape(32), 512, 2) == 4
    assert latent.blocks_per_call(Shape(128), 1024, 2) == 1
    assert latent.blocks_per_call(Shape(128), 512, 4) == 1
