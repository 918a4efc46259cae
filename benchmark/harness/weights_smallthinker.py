"""SmallThinker weights made on the device from the seed, in the type they
are served in and the stacked layout the program takes
(deepspeed_tpu/models/smallthinker.py): every matrix normal(``std``), unit
norm scales, no bias anywhere. One jitted call per leaf, a layer (an
expert, a slice of the vocabulary) at a time, so that no float32 copy of a
whole stack is ever alive beside 10.4 GiB of weights."""

import jax
import jax.numpy as jnp


def smallthinker_params(seed: int, cfg, dtype, std: float = 0.02):
    """``cfg``: the program's SmallThinkerConfig (sizes as they are run)."""
    d, H, Hkv, Dh = cfg.d_model, cfg.n_heads, cfg.kv_heads, cfg.head_dim
    f, E, held, L = cfg.moe_d_ff, cfg.num_experts, cfg.held[1], cfg.n_layers
    # any whole number up to a little over 2**31 is a seed; the counter
    # generator of the chip makes 6e9 normals in seconds
    root = jax.random.key(int(seed) % (2 ** 31 - 1), impl="rbg")
    count = [0]

    def normal(shape):
        count[0] += 1
        keys = jax.random.split(jax.random.fold_in(root, count[0]), shape[0])

        @jax.jit
        def make(keys):
            return jax.lax.map(lambda k: (jax.random.normal(
                k, shape[1:], jnp.float32) * std).astype(dtype), keys)
        return make(keys)

    def ones(*shape):
        return jnp.ones(shape, dtype)

    block = {"ln1": {"scale": ones(L, d)},
             "qkv": {"kernel": normal((L, d, (H + 2 * Hkv) * Dh))},
             "attn_out": {"kernel": normal((L, H * Dh, d))},
             "ln2": {"scale": ones(L, d)},
             "moe": {"router": {"kernel": normal((L, d, E))},
                     "experts": {n: {"kernel": normal(
                         (L * held,) + shape).reshape((L, held) + shape)}
                         for n, shape in (("wg", (d, f)), ("wi", (d, f)),
                                          ("wo", (f, d)))}}}
    V = cfg.vocab_size
    rows = 64 if V % 64 == 0 else 1
    return {"wte": {"embedding": normal((rows, V // rows, d)).reshape(V, d)},
            "block": block, "ln_f": {"scale": ones(d)},
            "lm_head": {"kernel": normal((rows, d, V // rows)).transpose(
                1, 0, 2).reshape(d, V)}}
