"""Kimi-Linear's delta rule (``ops/attention/kda.py``) against the token
recurrence: the chunk form across chunk borders and under decays that
overflow ``exp(-G)``, and the Mosaic step kernel (interpreted here), which
rewrites the active slots' state alone. The dialect on the serving path is
tests/test_kimi_linear.py."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference import linear
from deepspeed_tpu.ops.attention import kda


def _rule_inputs(T, H, D, seed, strong=False):
    """q, k, v, g, b, s0 as the rule takes them; ``strong``: decays down to
    exp(-12) a token, under which exp(-G) overflows within a sub-chunk."""
    ks = jax.random.split(jax.random.key(seed), 6)

    def unit(x):
        return x / jnp.sqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)
    q = unit(jax.nn.silu(jax.random.normal(ks[0], (T, H, D)))) / np.sqrt(D)
    k = unit(jax.nn.silu(jax.random.normal(ks[1], (T, H, D))))
    v = jax.nn.silu(jax.random.normal(ks[2], (T, H, D)))
    g = -jnp.exp(jax.random.uniform(ks[3], (T, H, D), minval=-6.0,
                                    maxval=2.5 if strong else 0.5))
    b = jax.nn.sigmoid(jax.random.normal(ks[4], (T, H)))
    return q, k, v, g, b, jax.random.normal(ks[5], (H, D, D))


# a chunk shorter than a sub-chunk, whole sub-chunks and a part of one under
# decays strong enough to overflow exp(-G), and sub-chunks of 4 with the
# chunk's border at every offset of one
@pytest.mark.parametrize("T,sub,strong,cuts", [
    (5, 64, False, (2,)), (150, 64, True, (75,)), (13, 4, True, (4, 5, 6, 7))])
def test_kda_chunk_is_the_token_recurrence(T, sub, strong, cuts):
    """1e-5: float32 at the highest matmul precision on both sides; the
    chunkwise form sums a sub-chunk's writes in another order."""
    args = _rule_inputs(T, 3, 8, T, strong)
    o, s = kda.kda_recurrence(*args)
    o2, s2 = kda.kda_chunk(*args, sub=sub)
    assert float(jnp.abs(o).max()) > 0.05
    np.testing.assert_allclose(np.asarray(o2), np.asarray(o), atol=1e-5)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(s), atol=1e-5)
    # two chunks, the state handed from one to the next, are the one
    for cut in cuts:
        first = tuple(a[:cut] for a in args[:5])
        rest = tuple(a[cut:] for a in args[:5])
        o3, s3 = kda.kda_chunk(*first, args[5], sub=sub)
        o4, s4 = kda.kda_chunk(*rest, s3, sub=sub)
        np.testing.assert_allclose(np.asarray(jnp.concatenate([o3, o4])),
                                   np.asarray(o), atol=1e-5)
        np.testing.assert_allclose(np.asarray(s4), np.asarray(s), atol=1e-5)
    # a padding token (g = 0, b = 0) leaves the state alone
    pad = tuple(jnp.concatenate([a, jnp.zeros_like(a[:3])])
                for a in args[:5])
    _, s5 = kda.kda_chunk(*pad, args[5], sub=sub)
    np.testing.assert_allclose(np.asarray(s5), np.asarray(s), atol=1e-5)


@pytest.mark.parametrize("active", [
    [True, False, True, True, False], [False] * 5, [True] * 5])
def test_kda_step_rewrites_the_active_slots_alone(active):
    """The Mosaic kernel (interpreted here) against one step of the
    recurrence; every other row of the state buffer bit for bit as it was."""
    B, H, D, N, base = 5, 4, 16, 12, 3
    q, k, v, g, b, _ = _rule_inputs(B, H, D, 1)
    state = jax.random.normal(jax.random.key(2), (N, H, D, D))
    active = jnp.asarray(active)
    want_s, want_o = kda.kda_step_reference(state, q, k, v, g, b, base,
                                            active)
    order, count = linear.step_plan(active)
    got_s, got_o = kda.kda_step(state, kda.pack_step(q, k, g, v, b),
                                base + order, order, count, interpret=True)
    np.testing.assert_allclose(np.asarray(got_s), np.asarray(want_s),
                               atol=1e-6)
    live = np.asarray(active)
    np.testing.assert_allclose(np.asarray(got_o)[live],
                               np.asarray(want_o)[live], atol=1e-6)
    untouched = np.ones(N, bool)
    untouched[base + np.flatnonzero(live)] = False
    np.testing.assert_array_equal(np.asarray(got_s)[untouched],
                                  np.asarray(state)[untouched])
    assert live.sum() == 0 or float(jnp.abs(got_s - state).max()) > 1e-3
