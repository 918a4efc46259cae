"""Gated DeltaNet's rule (ops/attention/kda.py ``gdn_*``: the delta rule
with ONE decay a head a token) in its three forms, and the published sizes
of Qwen3-Next-80B-A3B (models/qwen3_next.py)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference import linear
from deepspeed_tpu.models import qwen3_next
from deepspeed_tpu.ops.attention import kda


def _rule_inputs(T, H, D, seed, strong=False):
    """q, k, v, g ``[T, H]``, b, s0 as the rule takes them; ``strong``:
    decays down to exp(-12) a token, under which exp(-G) overflows within a
    sub-chunk; weak ones keep 0.998 of the state a token."""
    ks = jax.random.split(jax.random.key(seed), 6)

    def unit(x):
        return x / jnp.sqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)
    q = unit(jax.nn.silu(jax.random.normal(ks[0], (T, H, D)))) / np.sqrt(D)
    k = unit(jax.nn.silu(jax.random.normal(ks[1], (T, H, D))))
    v = jax.nn.silu(jax.random.normal(ks[2], (T, H, D)))
    g = -jnp.exp(jax.random.uniform(ks[3], (T, H), minval=-6.0,
                                    maxval=2.5 if strong else 0.5))
    b = jax.nn.sigmoid(jax.random.normal(ks[4], (T, H)))
    return q, k, v, g, b, jax.random.normal(ks[5], (H, D, D))


def _chunk(form, H, D, sub=64, key_heads=None):
    """``gdn_chunk`` as ``form`` ("xla" | "mosaic": the kernel, interpreted
    here) takes it, after checking that the shapes do choose that form."""
    impl = "pallas" if form == "mosaic" else "gather"
    assert kda.chunk_form(impl, H, D, D, sub, key_heads) == form
    return functools.partial(kda.gdn_chunk, sub=sub, impl=impl,
                             interpret=True)


@pytest.mark.parametrize("form", ["xla", "mosaic"])
def test_a_prefill_chunk_with_padding_behind_its_valid_tokens(form):
    """The serving chunk: 512 rows of which 389 are tokens, the rest with g
    = 0 and b = 0 as ``linear.delta_prefill`` hands them; the state after is
    the state after the valid tokens, and their outputs the recurrence's."""
    T, n_valid, H, D = 512, 389, 2, 128
    q, k, v, g, b, s0 = _rule_inputs(T, H, D, 11, True)
    valid = jnp.arange(T) < n_valid
    g, b = (jnp.where(valid[:, None], a, 0.0) for a in (g, b))
    o, s = kda.gdn_recurrence(*(a[:n_valid] for a in (q, k, v, g, b)), s0)
    o2, s2 = _chunk(form, H, D)(q, k, v, g, b, s0)
    np.testing.assert_allclose(np.asarray(o2[:n_valid]), np.asarray(o),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(s), atol=1e-5)


@pytest.mark.parametrize("form,H,Hk", [
    ("xla", 4, 2), ("mosaic", 4, 2), ("mosaic", 8, 2), ("mosaic", 16, 4)])
def test_grouped_key_heads_are_read_unrepeated(form, H, Hk):
    """q and k with ``Hk`` heads, value head ``h`` on key head ``h // (H /
    Hk)``: the recurrence fed each key head repeated. In the kernel a pair
    of value heads on ONE key head forms ``K K^T`` and ``Q K^T`` once."""
    T, D = 140, 128
    q, k, v, g, b, s0 = _rule_inputs(T, H, D, 5, True)
    q, k = q[:, :Hk], k[:, :Hk]
    o, s = kda.gdn_recurrence(jnp.repeat(q, H // Hk, 1),
                              jnp.repeat(k, H // Hk, 1), v, g, b, s0)
    o2, s2 = _chunk(form, H, D, key_heads=Hk)(q, k, v, g, b, s0)
    np.testing.assert_allclose(np.asarray(o2), np.asarray(o), atol=1e-5)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(s), atol=1e-5)


@pytest.mark.parametrize("H,Hk,D,sub", [
    (3, 3, 128, 64),        # an odd head has no partner on the lanes
    (4, 4, 64, 64),         # a head is not the 128 lanes
    (4, 4, 128, 32),        # two sub-chunks' columns are not the 128 lanes
    (6, 2, 128, 64)])       # three value heads a key head: a pair on two
def test_the_kernel_leaves_other_shapes_to_the_xla_form(H, Hk, D, sub):
    """Asked for the kernel (``impl`` "pallas") at a shape it does not
    serve, ``gdn_chunk`` is the portable form: no ``pallas_call`` in its
    program, and the same numbers bit for bit."""
    q, k, v, g, b, s0 = _rule_inputs(70, H, D, 2)
    q, k = q[:, :Hk], k[:, :Hk]
    assert kda.chunk_form("pallas", H, D, D, sub, Hk) == "xla"
    asked = functools.partial(kda.gdn_chunk, sub=sub, impl="pallas")
    assert "pallas_call" not in str(jax.make_jaxpr(asked)(q, k, v, g, b, s0))
    for got, want in zip(asked(q, k, v, g, b, s0),
                         kda.gdn_chunk(q, k, v, g, b, s0, sub=sub)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    served = functools.partial(kda.gdn_chunk, impl="pallas", interpret=True)
    assert "pallas_call" in str(jax.make_jaxpr(served)(
        *_rule_inputs(70, 4, 128, 2)))


@pytest.mark.parametrize("rule,D", [("gdn", 16), ("kda", 16),
                                    ("gdn-mosaic", 128)])
def test_a_run_of_one_repeated_key_keeps_the_chunk_form_exact(rule, D):
    """150 identical unit keys written at full strength under a weak decay:
    ``I + tril(A) Diag(b)`` is then all ones below its diagonal, the case in
    which a Neumann series over too many rows cancels large terms
    (``kda._solve_unit_lower``'s blocks of 8, which both XLA forms share;
    the kernel merges blocks pairwise and has no series)."""
    T, H = 150, 2
    k = jax.random.normal(jax.random.key(0), (1, H, D))
    k = jnp.broadcast_to(k / jnp.linalg.norm(k, axis=-1, keepdims=True),
                         (T, H, D))
    v = jax.random.normal(jax.random.key(1), (T, H, D))
    s0 = jax.random.normal(jax.random.key(2), (H, D, D))
    b = jnp.full((T, H), 0.99)
    g = jnp.full((T, H) + ((D,) if rule == "kda" else ()), -1e-3)
    recurrence, chunk = (kda.kda_recurrence, kda.kda_chunk) \
        if rule == "kda" else (kda.gdn_recurrence, _chunk(
            "mosaic" if rule == "gdn-mosaic" else "xla", H, D))
    o, s = recurrence(k / np.sqrt(D), k, v, g, b, s0)
    o2, s2 = chunk(k / np.sqrt(D), k, v, g, b, s0)
    assert float(jnp.abs(o).max()) > 0.35
    np.testing.assert_allclose(np.asarray(o2), np.asarray(o), atol=1e-5)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(s), atol=2e-5)


@pytest.mark.parametrize("strong", [False, True])
def test_scalar_chunk_form_is_kda_chunk_fed_the_broadcast_decay(strong):
    """The second chunk form gives the first one's numbers where the decay
    is the same on every key channel, without its ``[H, c, c, K]`` pairs:
    no array of the scalar form's program has four dimensions of which two
    are the sub-chunk and one the key channels."""
    q, k, v, g, b, s0 = _rule_inputs(70, 3, 8, 4, strong)
    o, s = kda.gdn_chunk(q, k, v, g, b, s0, sub=16)
    o2, s2 = kda.kda_chunk(q, k, v, jnp.broadcast_to(g[..., None], q.shape),
                           b, s0, sub=16)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o2), atol=1e-5)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s2), atol=1e-5)

    def pair_tensors(fn, g):
        text = str(jax.make_jaxpr(lambda *a: fn(*a, sub=16))(q, k, v, g, b,
                                                             s0))
        return "f32[3,16,16,8]" in text
    assert pair_tensors(kda.kda_chunk, jnp.broadcast_to(g[..., None],
                                                        q.shape))
    assert not pair_tensors(kda.gdn_chunk, g)


@pytest.mark.parametrize("active", [
    [True, False, True, True, False], [False] * 5, [True] * 5])
def test_step_kernel_serves_the_scalar_decay_on_every_lane(active):
    """``kda_step`` (interpreted here) fed a head's decay on all of its row's
    lanes against one step of ``gdn_recurrence`` a slot; every other row of
    the state buffer bit for bit as it was."""
    B, H, D, N, base = 5, 4, 16, 12, 3
    q, k, v, g, b, _ = _rule_inputs(B, H, D, 1)
    state = jax.random.normal(jax.random.key(2), (N, H, D, D))
    live = np.asarray(active)
    active = jnp.asarray(active)
    order, count = linear.step_plan(active)
    got_s, got_o = kda.kda_step(
        state, kda.pack_step(q, k, linear._on_lanes(g, q), v, b),
        base + order, order, count, interpret=True)
    for i in np.flatnonzero(live):
        o, s = kda.gdn_recurrence(q[i:i + 1], k[i:i + 1], v[i:i + 1],
                                  g[i:i + 1], b[i:i + 1], state[base + i])
        np.testing.assert_allclose(np.asarray(got_s[base + i]),
                                   np.asarray(s), atol=1e-6)
        np.testing.assert_allclose(np.asarray(got_o[i]), np.asarray(o[0]),
                                   atol=1e-6)
    untouched = np.ones(N, bool)
    untouched[base + np.flatnonzero(live)] = False
    np.testing.assert_array_equal(np.asarray(got_s)[untouched],
                                  np.asarray(state)[untouched])


def _published(**over):
    kw = dict(vocab_size=151936, n_layers=48, d_model=2048,
              max_seq_len=262144)
    kw.update(over)
    return qwen3_next.Qwen3NextConfig(**kw)


@pytest.mark.parametrize("over,want", [
    (dict(), 79_674_391_296),
    # the cell's share: layers 0-23, experts 0-31, an eighth of the
    # vocabulary (benchmark/configs/qwen3-next-80b-a3b-serve-ep16pp2.json)
    (dict(n_layers=24, experts_held=(0, 32), vocab_size=18992,
          max_seq_len=24576), 3_365_036_416)])
def test_parameter_counts_at_the_published_and_the_cells_sizes(over, want):
    cfg = _published(**over)
    assert qwen3_next.num_params(cfg) == want
    shapes = jax.eval_shape(lambda: qwen3_next.init_params(
        jax.random.key(0), cfg))
    assert sum(int(np.prod(a.shape))
               for a in jax.tree_util.tree_leaves(shapes)) == want


def test_published_layout_and_what_a_slot_keeps():
    cfg = _published()
    kinds = cfg.attn_kinds
    assert kinds.sum() == 12 and list(kinds[:8]) == [0, 0, 0, 1, 0, 0, 0, 1]
    assert cfg.recurrent_stacks == ("gdn", "attn")
    assert cfg.recurrent_state_shape == (32, 128, 128)
    assert cfg.conv_tail_width == 3 * 8192
    assert cfg.n_dense_layers == 0 and cfg.n_sparse_layers == 48
    assert linear.rule_of(cfg) == "gdn" and not linear._latent_rows(cfg)
    half = _published(n_layers=24)
    # a slot's state before it holds a token: 36 MiB + 0.84 MiB of tails
    assert 4 * half.recurrent_state_values == 36 << 20
    assert 2 * half.conv_tail_values == 18 * 3 * 8192 * 2
    assert linear.kv_bytes_per_token(half) == 12 << 10
