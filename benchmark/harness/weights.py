"""GPT-2 weights made on the device in one jitted call from the seed, in the
type they are served or trained in, in the stacked layout the program takes
(``wte``/``wpe``/``block``/``ln_f``, layer weights stacked on axis 0).
GPT-2's own initialisation: normal(0.02), residual projections scaled by
1/sqrt(2 L), zero biases, unit norm scales."""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np


def gpt2_params(seed: int, hp: dict, dtype):
    """``hp``: the configuration file's published keys (``n_layer``,
    ``n_embd``, ``vocab_size``, ``n_positions``)."""
    L, d = int(hp["n_layer"]), int(hp["n_embd"])
    V, S = int(hp["vocab_size"]), int(hp["n_positions"])
    ff = 4 * d
    resid = 0.02 / math.sqrt(2.0 * L)

    def make(key):
        ks = jax.random.split(key, 6)

        def normal(k, shape, std):
            return (jax.random.normal(k, shape, jnp.float32) * std
                    ).astype(dtype)

        def norm_p(*lead):
            return {"scale": jnp.ones(lead + (d,), dtype),
                    "bias": jnp.zeros(lead + (d,), dtype)}

        def dense(k, din, dout, std):
            return {"kernel": normal(k, (L, din, dout), std),
                    "bias": jnp.zeros((L, dout), dtype)}

        return {
            "wte": {"embedding": normal(ks[0], (V, d), 0.02)},
            "wpe": {"embedding": normal(ks[1], (S, d), 0.02)},
            "block": {
                "ln1": norm_p(L),
                "qkv": dense(ks[2], d, 3 * d, 0.02),
                "attn_out": dense(ks[3], d, d, resid),
                "ln2": norm_p(L),
                "mlp_in": dense(ks[4], d, ff, 0.02),
                "mlp_out": dense(ks[5], ff, d, resid),
            },
            "ln_f": norm_p(),
        }

    # any whole number up to a little over 2**31 is a seed
    key = jax.random.PRNGKey(int(seed) % (2 ** 31 - 1))
    return jax.jit(make)(key)


# ---- the selection bias at rest ---------------------------------------------
# Every sparse configuration whose router selects by ``score + bias`` keeps
# that bias at REST under its family's auxiliary-loss-free balancing rule, as
# a trained model's is (DeepSeek-V3's: an output chosen more often than its
# share has its selection bias lowered, one chosen less often raised). The
# rule is stated once, here; a weight module hands it its family's data.

SIGMOID_RATE = (0.05, 0.02 / 0.05)   # scores are sigmoids of order one


def softmax_rate(n_out):
    """Scores are a softmax's probabilities near ``1 / n_out``: the rate
    starts at that scale and ends three orders below it."""
    return (0.2 / n_out, 1e-3)


def stored_bias(b, dtype):
    """The bias as a program that stores it centred reads it: a common
    offset chooses nothing, and costs the stored type its resolution."""
    return (b - jnp.mean(b)).astype(dtype)


def select_outputs(biased, rule):
    """The router's selection for biased scores ``[N, n_out]``; ``rule`` =
    (groups, groups kept, k): top-k inside the kept groups, those whose two
    best scores sum highest; one group is plain top-k."""
    N, E = biased.shape
    G, kept_groups, k = rule
    if G > 1:
        per = biased.reshape(N, G, E // G)
        best2 = jnp.sum(jax.lax.top_k(per, 2)[0], -1)
        kept = jnp.any(jax.lax.top_k(best2, kept_groups)[1][:, :, None]
                       == jnp.arange(G), axis=1)
        biased = jnp.where(kept[:, :, None], per, -jnp.inf).reshape(N, E)
    return jax.lax.top_k(biased, k)[1]


@functools.partial(jax.jit, static_argnames=("rule", "steps", "rate",
                                             "stored"))
def balanced_bias(scores, bias, rule, steps, rate, target=None, stored=None):
    """The balancing rule run to rest on the calibration tokens' scores
    ``[N, n_out]`` for the choice ``select_outputs(scores + bias, rule)``:
    ``steps`` steps against each output's excess load, at a rate that falls
    geometrically from ``rate[0]`` by the factor ``rate[1]`` in all.
    ``target`` ``[n_out]`` is the share of the pairs each output should
    take (None: even shares). With ``stored`` (a dtype's name) the choice is
    made with the bias AS STORED (:func:`stored_bias`), so the rule comes to
    rest among the values the served type can hold. Returns the float32
    bias ``[n_out]`` the rule kept moving.

    The families' data: ``SIGMOID_RATE`` for dots.vlm1 and Kimi-Linear;
    ``softmax_rate(n_out)`` for LongCat-Flash and ZAYA1."""
    n_out = scores.shape[1]
    start, fall = rate

    def step(i, b):
        seen = b if stored is None \
            else stored_bias(b, stored).astype(jnp.float32)
        sel = select_outputs(scores + seen, rule)
        load = jnp.zeros((n_out,), jnp.float32).at[sel.reshape(-1)].add(1.0)
        load = load / (sel.size / n_out) if target is None \
            else load / sel.size / target
        r = start * fall ** (i / max(steps - 1, 1))
        return b + r * jnp.clip(1.0 - load, -1.0, 1.0)

    return jax.lax.fori_loop(0, steps, step, bias.astype(jnp.float32))


def worst_load_over_mean(scores, bias, rule, n_real=None):
    """The busiest output's pairs over the mean, among the first ``n_real``
    outputs (all of them when None), for the choice with ``bias``."""
    sel = np.asarray(select_outputs(scores + bias.astype(jnp.float32), rule))
    load = np.bincount(sel.reshape(-1), minlength=scores.shape[1])[:n_real]
    return float(load.max() / max(load.mean(), 1e-9))
