"""The one balancing rule (``harness/weights.py`` ``balanced_bias``) under
each family's data. What the three private copies of the rule did, the one
statement does: every case below is a family's rule as its weight module
hands it over. Outside tier-1: `pytest benchmark/tests`."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from harness import weights as W

# family: (rule, rate, scores, stored type, the skip output's target share)
FAMILIES = {
    "dots_vlm_grouped": ((8, 4, 8), W.SIGMOID_RATE, "sigmoid", None, None),
    "kimi_linear_plain": ((1, 1, 8), W.SIGMOID_RATE, "sigmoid", None, None),
    "longcat_flash_softmax": ((1, 1, 3), W.softmax_rate(32), "softmax",
                              "bfloat16", None),
    "zaya_top1_with_skip": ((1, 1, 1), W.softmax_rate(32), "softmax",
                            "bfloat16", 0.1),
}


def _scores(kind, n=2048, n_out=32, seed=0):
    """Calibration scores with a common mode, as a random model's are: some
    outputs lead for every token."""
    rng = np.random.default_rng(seed)
    logit = rng.normal(size=(n, n_out)) * 0.5 + rng.normal(size=(1, n_out))
    f = jax.nn.sigmoid if kind == "sigmoid" else \
        (lambda x: jax.nn.softmax(0.3 * x, -1))
    return f(jnp.asarray(logit, jnp.float32))


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_the_rule_comes_to_rest_for_every_family(family):
    rule, rate, kind, stored, skip = FAMILIES[family]
    scores = _scores(kind)
    n_out = scores.shape[1]
    target = None if skip is None else jnp.asarray(
        [(1.0 - skip) / (n_out - 1)] * (n_out - 1) + [skip], jnp.float32)
    zero = jnp.zeros((n_out,), jnp.float32)
    steps = 300 if kind == "sigmoid" else 1500
    b = W.balanced_bias(scores, zero, rule, steps, rate, target=target,
                        stored=stored)
    again = W.balanced_bias(scores, zero, rule, steps, rate, target=target,
                            stored=stored)
    np.testing.assert_array_equal(np.asarray(b), np.asarray(again))
    assert b.dtype == jnp.float32 and b.shape == (n_out,)
    seen = b if stored is None else W.stored_bias(b, stored)
    n_real = n_out if skip is None else n_out - 1
    before = W.worst_load_over_mean(scores, zero, rule, n_real)
    after = W.worst_load_over_mean(scores, seen, rule, n_real)
    assert before > 1.5 and after < 1.15, (before, after)
    if skip is not None:
        sel = np.asarray(W.select_outputs(
            scores + seen.astype(jnp.float32), rule)).reshape(-1)
        assert abs(float((sel == n_out - 1).mean()) - skip) < 0.02


def test_the_stored_bias_is_centred_and_in_the_served_type():
    b = jnp.asarray([0.5, 1.5, 2.5], jnp.float32)
    s = W.stored_bias(b, jnp.bfloat16)
    assert s.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(s, np.float32), [-1.0, 0.0, 1.0])


def test_a_group_limit_keeps_the_choice_inside_the_kept_groups():
    scores = _scores("sigmoid", n=64)
    sel = np.asarray(W.select_outputs(scores, (8, 4, 8)))
    assert all(len({int(e) // 4 for e in row}) <= 4 for row in sel)
