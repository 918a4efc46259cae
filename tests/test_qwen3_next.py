"""Gated DeltaNet's rule (ops/attention/kda.py ``gdn_*``: the delta rule
with ONE decay a head a token) in its three forms, and the published sizes
of Qwen3-Next-80B-A3B (models/qwen3_next.py)."""

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


# a chunk shorter than a sub-chunk, whole sub-chunks and a part of one under
# decays strong enough to overflow exp(-G), and sub-chunks of 4 with the
# chunk's border at every offset of one
@pytest.mark.parametrize("T,sub,strong,cuts", [
    (5, 64, False, (2,)), (150, 64, True, (75,)), (13, 4, True, (4, 5, 6, 7))])
def test_gdn_chunk_is_the_token_recurrence(T, sub, strong, cuts):
    """1e-5: float32 at the highest matmul precision on both sides; the
    chunkwise form sums a sub-chunk's writes in another order."""
    args = _rule_inputs(T, 3, 8, T, strong)
    o, s = kda.gdn_recurrence(*args)
    o2, s2 = kda.gdn_chunk(*args, sub=sub)
    assert float(jnp.abs(o).max()) > 0.05
    np.testing.assert_allclose(np.asarray(o2), np.asarray(o), atol=1e-5)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(s), atol=1e-5)
    # two chunks, the state handed from one to the next, are the one
    for cut in cuts:
        first = tuple(a[:cut] for a in args[:5])
        rest = tuple(a[cut:] for a in args[:5])
        o3, s3 = kda.gdn_chunk(*first, args[5], sub=sub)
        o4, s4 = kda.gdn_chunk(*rest, s3, sub=sub)
        np.testing.assert_allclose(np.asarray(jnp.concatenate([o3, o4])),
                                   np.asarray(o), atol=1e-5)
        np.testing.assert_allclose(np.asarray(s4), np.asarray(s), atol=1e-5)
    # a padding token (g = 0, b = 0) leaves the state alone
    pad = tuple(jnp.concatenate([a, jnp.zeros_like(a[:3])])
                for a in args[:5])
    _, s5 = kda.gdn_chunk(*pad, args[5], sub=sub)
    np.testing.assert_allclose(np.asarray(s5), np.asarray(s), atol=1e-5)


@pytest.mark.parametrize("rule", ["gdn", "kda"])
def test_a_run_of_one_repeated_key_keeps_the_chunk_form_exact(rule):
    """150 identical unit keys written at full strength under a weak decay:
    ``I + tril(A) Diag(b)`` is then all ones below its diagonal, the case in
    which a Neumann series over too many rows cancels large terms
    (``kda._solve_unit_lower``'s blocks of 8). Both rules share the solve."""
    T, H, D = 150, 2, 16
    k = jax.random.normal(jax.random.key(0), (1, H, D))
    k = jnp.broadcast_to(k / jnp.linalg.norm(k, axis=-1, keepdims=True),
                         (T, H, D))
    v = jax.random.normal(jax.random.key(1), (T, H, D))
    s0 = jax.random.normal(jax.random.key(2), (H, D, D))
    b = jnp.full((T, H), 0.99)
    g = jnp.full((T, H) + ((D,) if rule == "kda" else ()), -1e-3)
    recurrence, chunk = (kda.kda_recurrence, kda.kda_chunk) \
        if rule == "kda" else (kda.gdn_recurrence, kda.gdn_chunk)
    o, s = recurrence(k / np.sqrt(D), k, v, g, b, s0)
    o2, s2 = chunk(k / np.sqrt(D), k, v, g, b, s0)
    assert float(jnp.abs(o).max()) > 0.5
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
