"""GPT-2 weights made on the device in one jitted call from the seed, in the
type they are served or trained in, in the stacked layout the program takes
(``wte``/``wpe``/``block``/``ln_f``, layer weights stacked on axis 0).
GPT-2's own initialisation: normal(0.02), residual projections scaled by
1/sqrt(2 L), zero biases, unit norm scales."""

import math

import jax
import jax.numpy as jnp


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
