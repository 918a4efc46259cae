"""Qwen3-Next (``qwen3_next``) weights made on the device from the seed, in
the type they are served in and the stacked layout the program takes
(deepspeed_tpu/models/qwen3_next.py ``init_params``, whose choices these
are): every matrix normal(``std``), the convolution's taps normal(0.5), the
offset norms' stored scales normal(``norm_std``) about ZERO (a trained
model's are not zero, and at zero the offset would not show) and the gated
norm's plain scale about ONE; the decay's ``A_log`` and ``dt_bias`` drawn
as TRAINED ones, one a value head (``A = U(1, 16)``, the step log-uniform in
[1e-3, 1e-1]: a decay then sits near 1 and the state really remembers). The
router has NO bias: nothing is balanced, and the load is what a random
softmax router gives. One jitted call per leaf, a layer (or an expert) at a
time, so that no float32 copy of a whole stack is ever alive beside the
weights."""

import jax
import jax.numpy as jnp
import numpy as np


def qwen3_next_params(seed: int, cfg, dtype, std: float = 0.02,
                      norm_std: float = 0.02):
    """``cfg``: the program's Qwen3NextConfig (sizes as they are run)."""
    d, f, E, L = cfg.d_model, cfg.moe_d_ff, cfg.num_experts, cfg.n_layers
    Hv, Dl, C = cfg.linear_value_heads, cfg.linear_head_dim, cfg.gdn_channels
    H, Hkv, Dh = cfg.n_heads, cfg.kv_heads, cfg.head_dim
    Lg, La = cfg.n_recurrent_layers, cfg.n_full_layers
    held = cfg.held[1]
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

    def normal(shape, s=std, mean=0.0):
        return draw(shape, lambda k, sh: mean + jax.random.normal(
            k, sh, jnp.float32) * s)

    def offset(*shape):
        return {"scale": normal(shape, norm_std)}

    def a_log(k, sh):
        return jnp.log(jax.random.uniform(k, sh, jnp.float32, 1.0, 16.0))

    def dt_bias(k, sh):
        dt = jnp.exp(jax.random.uniform(k, sh, jnp.float32, np.log(1e-3),
                                        np.log(1e-1)))
        return dt + jnp.log(-jnp.expm1(-dt))        # softplus^-1(dt)

    gdn = {"ln1": offset(Lg, d),
           "in_qkvz": {"kernel": normal((Lg, d, C + Hv * Dl))},
           "in_ba": {"kernel": normal((Lg, d, 2 * Hv))},
           "conv": {"kernel": normal((Lg, cfg.conv_kernel, C), 0.5)},
           "A_log": draw((Lg, Hv), a_log),
           "dt_bias": draw((Lg, Hv), dt_bias),
           "o_norm": {"scale": normal((Lg, Dl), norm_std, 1.0)},
           "attn_out": {"kernel": normal((Lg, Hv * Dl, d))}}
    attn = {"ln1": offset(La, d),
            "qkv": {"kernel": normal((La, d, (2 * H + 2 * Hkv) * Dh))},
            "q_norm": offset(La, Dh), "k_norm": offset(La, Dh),
            "attn_out": {"kernel": normal((La, H * Dh, d))}}
    width = cfg.n_shared_experts * f
    block = {"ln2": offset(L, d), "moe": {
        "router": {"kernel": normal((L, d, E))},
        "experts": {n: {"kernel": normal((L * held,) + shape).reshape(
            (L, held) + shape)} for n, shape in (
                ("wg", (d, f)), ("wi", (d, f)), ("wo", (f, d)))},
        "shared": {"mlp_gate": {"kernel": normal((L, d, width))},
                   "mlp_in": {"kernel": normal((L, d, width))},
                   "mlp_out": {"kernel": normal((L, width, d))}},
        "shared_gate": {"kernel": normal((L, d, 1))}}}
    V = cfg.vocab_size
    rows = 16 if V % 16 == 0 else 1
    return {"wte": {"embedding": normal((rows, V // rows, d)).reshape(V, d)},
            "gdn": gdn, "attn": attn, "block": block,
            "ln_f": {"scale": normal((1, d), norm_std)[0]},
            "lm_head": {"kernel": normal((rows, d, V // rows)).transpose(
                1, 0, 2).reshape(d, V)}}
