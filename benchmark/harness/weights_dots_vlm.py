"""dots.vlm1 (``dots_vlm``) language-model weights made on the device from
the seed, in the type they are served in and the stacked layout the program
takes (deepspeed_tpu/models/dots_vlm.py): every matrix normal(``std``), unit
norm scales, the router's selection bias normal(``bias_std``). One jitted
call per leaf, a layer (or an expert) at a time, so that no float32 copy of
a whole stack is ever alive beside 10 GiB of weights. Then
``balance_router_bias`` runs the family's load-balancing rule on the
selection bias to rest."""

import jax
import jax.numpy as jnp
import numpy as np

from harness.weights import (SIGMOID_RATE, balanced_bias,
                             worst_load_over_mean)


def dots_vlm_params(seed: int, cfg, dtype, std: float = 0.02,
                    bias_std: float = 0.02):
    """``cfg``: the program's DotsVLMConfig (sizes as they are run)."""
    d, H, f, E = cfg.d_model, cfg.n_heads, cfg.moe_d_ff, cfg.num_experts
    rq, rkv = cfg.q_lora_rank, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    held = cfg.held[1]
    # any whole number up to a little over 2**31 is a seed
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
                "q_a": {"kernel": normal((L, d, rq))},
                "q_a_norm": {"scale": ones(L, rq)},
                "q_b": {"kernel": normal((L, rq, H * (dn + dr)))},
                "kv_a": {"kernel": normal((L, d, rkv + dr))},
                "kv_a_norm": {"scale": ones(L, rkv)},
                "k_up": {"kernel": normal((L, H, dn, rkv))},
                "v_up": {"kernel": normal((L, H, rkv, dv))},
                "attn_out": {"kernel": normal((L, H * dv, d))},
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
    rows = 32 if V % 32 == 0 else 1
    return {"wte": {"embedding": normal((rows, V // rows, d)).reshape(V, d)},
            "dense_block": dict(attn(Ld), **swiglu(Ld, cfg.ffn_dim)),
            "block": sparse, "ln_f": {"scale": ones(d)},
            "lm_head": {"kernel": normal((rows, d, V // rows)).transpose(
                1, 0, 2).reshape(d, V)}}


def balance_router_bias(params, cfg, seed, reference, hp, tokens=4096,
                        steps=300):
    """Replace each sparse layer's selection bias (random so far) by one
    that BALANCES the experts' load, as the published model's was trained
    to (``e_score_correction_bias`` is the state of that rule): a
    calibration sequence of ``tokens`` random ids goes through the layers
    once (the plain reference's own layer functions, at the default matmul
    precision: this is calibration, not a check), and at each sparse
    layer the bias is run to rest on that layer's scores before the layer
    is applied. With random weights a layer's input has a large component
    common to all tokens, so an unbalanced random router gives each seed
    its own hot and cold experts, and a chip that holds 16 of 256 then does
    seed-dependent work (PERF.md section 6, PR 32). Deterministic in the
    seed. Returns (params, [worst load over mean before, after] a layer)."""
    rng = np.random.default_rng([int(seed) % (2 ** 31 - 1), 7])
    ids = jnp.asarray(rng.integers(1, cfg.vocab_size, int(tokens)), jnp.int32)
    free = -jnp.ones((int(tokens), cfg.moe_k), jnp.int32)
    key = tuple(sorted(hp.items()))
    none = frozenset()
    rule = (cfg.n_group, cfg.topk_group, cfg.moe_k)

    @jax.jit
    def dense_layer(x, p):
        return reference._dense_ffn(
            reference._attention(x, p, dict(key), none, False), p, dict(key),
            False)

    @jax.jit
    def scores_of(x, p):
        x = reference._attention(x, p, dict(key), none, False)
        h = reference._rms(x, p["ln2"]["scale"], hp["eps"])
        return x, jax.nn.sigmoid(
            h @ p["moe"]["router"]["kernel"].astype(jnp.float32))

    @jax.jit
    def sparse_ffn(x, p):
        return reference._sparse_ffn(x, p, dict(key), none, False, free)[0]

    def layer(stack, l):
        return jax.tree_util.tree_map(lambda a: a[l], params[stack])

    x = params["wte"]["embedding"][ids].astype(jnp.float32)
    # one layer's slice of the stacks alive at a time (a sparse layer's is
    # 1.9 GB beside 10 GiB of weights)
    for l in range(cfg.n_dense_layers):
        p = layer("dense_block", l)
        x = dense_layer(x, p)
        del p
    biases, report = [], []
    old = params["block"]["moe"]["router"]["bias"]
    for l in range(cfg.n_sparse_layers):
        p = layer("block", l)
        x, scores = scores_of(x, p)
        b = balanced_bias(scores, old[l], rule, int(steps),
                          SIGMOID_RATE).astype(old.dtype)
        report.append([worst_load_over_mean(scores, old[l], rule),
                       worst_load_over_mean(scores, b, rule)])
        biases.append(b)
        p["moe"]["router"]["bias"] = b
        x = jax.block_until_ready(sparse_ffn(x, p))
        del p, scores
    moe = dict(params["block"]["moe"], router=dict(
        params["block"]["moe"]["router"], bias=jnp.stack(biases)))
    return dict(params, block=dict(params["block"], moe=moe)), report
