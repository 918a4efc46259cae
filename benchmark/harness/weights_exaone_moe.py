"""K-EXAONE (``exaone_moe``) weights made on the device from the seed, in
the type they are served in and the stacked layout the program takes
(deepspeed_tpu/models/exaone_moe.py): the family's initialisation, every
matrix normal(``std``), unit norm scales, the router's selection bias
normal(``bias_std``). One jitted call per leaf, a layer at a time, so that
no float32 copy of a whole stack is ever alive beside 11 GiB of weights."""

import jax
import jax.numpy as jnp


def exaone_moe_params(seed: int, cfg, dtype, std: float = 0.02,
                      bias_std: float = 0.02):
    """``cfg``: the program's ExaoneMoEConfig (sizes as they are run)."""
    d, H, Hkv, Dh = cfg.d_model, cfg.n_heads, cfg.kv_heads, cfg.head_dim
    f, E, held = cfg.moe_d_ff, cfg.num_experts, cfg.held[1]
    # any whole number up to a little over 2**31 is a seed; the counter
    # generator of the chip makes 6e9 normals in seconds
    root = jax.random.key(int(seed) % (2 ** 31 - 1), impl="rbg")
    count = [0]

    def normal(shape, s=std):
        count[0] += 1
        keys = jax.random.split(jax.random.fold_in(root, count[0]), shape[0])

        @jax.jit
        def make(keys):
            return jax.lax.map(lambda k: (jax.random.normal(
                k, shape[1:], jnp.float32) * s).astype(dtype), keys)
        return make(keys)

    def ones(*shape):
        return jnp.ones(shape, dtype)

    def attn(L):
        return {"ln1": {"scale": ones(L, d)},
                "qkv": {"kernel": normal((L, d, (H + 2 * Hkv) * Dh))},
                "q_norm": {"scale": ones(L, Dh)},
                "k_norm": {"scale": ones(L, Dh)},
                "attn_out": {"kernel": normal((L, H * Dh, d))},
                "ln2": {"scale": ones(L, d)}}

    def swiglu(L, width):
        return {"mlp_gate": {"kernel": normal((L, d, width))},
                "mlp_in": {"kernel": normal((L, d, width))},
                "mlp_out": {"kernel": normal((L, width, d))}}

    Ld, Ls = cfg.n_dense_layers, cfg.n_sparse_layers
    sparse = attn(Ls)
    sparse["moe"] = {
        "router": {"kernel": normal((Ls, d, E)),
                   "bias": normal((Ls, E), bias_std)},
        "experts": {n: {"kernel": normal((Ls * held,) + shape).reshape(
            (Ls, held) + shape)} for n, shape in (
                ("wg", (d, f)), ("wi", (d, f)), ("wo", (f, d)))},
        "shared": swiglu(Ls, cfg.n_shared_experts * f)}
    V = cfg.vocab_size
    rows = 64 if V % 64 == 0 else 1
    return {"wte": {"embedding": normal((rows, V // rows, d)).reshape(V, d)},
            "dense_block": dict(attn(Ld), **swiglu(Ld, cfg.ffn_dim)),
            "block": sparse, "ln_f": {"scale": ones(d)},
            "lm_head": {"kernel": normal((rows, d, V // rows)).transpose(
                1, 0, 2).reshape(d, V)}}
