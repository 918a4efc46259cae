"""Jamba (``jamba``) weights made on the device from the seed, in the type
they are served in and the stacked layout the program takes
(deepspeed_tpu/models/jamba.py ``init_params``, whose choices these are):
every matrix normal(``std``) but ``dt_proj`` (uniform in +-``dt_rank``^-0.5,
the published initialiser), the convolution's taps normal(0.5) and its bias
normal(``std``), unit norm scales, and what training sets drawn AS TRAINED:
``A_log[n, c] = log(n + 1)`` (the published initialiser; stored ``[N, Di]``,
the published array transposed), ``D`` = 1, ``dt_proj.bias`` the inverse
softplus of a step log-uniform in [1e-3, 1e-1], so that a channel's slowest
state index keeps 0.90-0.999 of itself a token and the state really
remembers (at a bias of 0 the step is 0.69 and every state index forgets
within a few tokens: no check would feel the recurrence). One jitted call
per leaf, a layer at a time, so that no float32 copy of a whole stack is
ever alive beside 6 GB of weights."""

import jax
import jax.numpy as jnp
import numpy as np


def jamba_params(seed: int, cfg, dtype, std: float = 0.02):
    """``cfg``: the program's JambaConfig (sizes as they are run)."""
    d, f, V = cfg.d_model, cfg.ffn_dim, cfg.vocab_size
    Di, N, R = cfg.d_inner, cfg.mamba_d_state, cfg.mamba_dt_rank
    H, Hkv, Dh = cfg.n_heads, cfg.kv_heads, cfg.head_dim
    Ls, La, L = cfg.n_recurrent_layers, cfg.n_full_layers, cfg.n_layers
    # any whole number up to a little over 2**31 is a seed
    root = jax.random.key(int(seed) % (2 ** 31 - 1), impl="rbg")
    count = [0]

    def draw(shape, one):
        count[0] += 1
        keys = jax.random.split(jax.random.fold_in(root, count[0]), shape[0])

        @jax.jit
        def make(keys):
            return jax.lax.map(lambda k: one(k, shape[1:]).astype(dtype),
                               keys)
        return make(keys)

    def normal(shape, s=std):
        return draw(shape, lambda k, sh: jax.random.normal(
            k, sh, jnp.float32) * s)

    def ones(*shape):
        return jnp.ones(shape, dtype)

    def dt_kernel(k, sh):
        return jax.random.uniform(k, sh, jnp.float32, -R ** -0.5, R ** -0.5)

    def dt_bias(k, sh):
        dt = jnp.exp(jax.random.uniform(k, sh, jnp.float32, np.log(1e-3),
                                        np.log(1e-1)))
        return dt + jnp.log(-jnp.expm1(-dt))        # softplus^-1(dt)

    ssm = {"ln1": {"scale": ones(Ls, d)},
           "in_proj": {"kernel": normal((Ls, d, 2 * Di))},
           "conv": {"kernel": normal((Ls, cfg.conv_kernel, Di), 0.5),
                    "bias": normal((Ls, Di))},
           "x_proj": {"kernel": normal((Ls, Di, R + 2 * N))},
           "dt_norm": {"scale": ones(Ls, R)},
           "b_norm": {"scale": ones(Ls, N)},
           "c_norm": {"scale": ones(Ls, N)},
           "dt_proj": {"kernel": draw((Ls, R, Di), dt_kernel),
                       "bias": draw((Ls, Di), dt_bias)},
           "A_log": jnp.broadcast_to(jnp.log(jnp.arange(
               1, N + 1, dtype=jnp.float32))[None, :, None],
               (Ls, N, Di)).astype(dtype),
           "D": ones(Ls, Di),
           "out_proj": {"kernel": normal((Ls, Di, d))}}
    attn = {"ln1": {"scale": ones(La, d)},
            "qkv": {"kernel": normal((La, d, (H + 2 * Hkv) * Dh))},
            "attn_out": {"kernel": normal((La, H * Dh, d))}}
    block = {"ln2": {"scale": ones(L, d)},
             "mlp_gate": {"kernel": normal((L, d, f))},
             "mlp_in": {"kernel": normal((L, d, f))},
             "mlp_out": {"kernel": normal((L, f, d))}}
    rows = 32 if V % 32 == 0 else 1
    return {"wte": {"embedding": normal((rows, V // rows, d)).reshape(V, d)},
            "ssm": ssm, "attn": attn, "block": block,
            "ln_f": {"scale": ones(d)}}
