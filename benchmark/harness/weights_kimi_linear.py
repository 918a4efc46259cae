"""Kimi-Linear (``kimi_linear``) weights made on the device from the seed,
in the type they are served in and the stacked layout the program takes
(deepspeed_tpu/models/kimi_linear.py ``init_params``, whose choices these
are): every matrix normal(``std``), the convolution's taps normal(0.5), unit
norm scales, the router's selection bias normal(``bias_std``); the decay's
``A_log`` and ``dt_bias`` drawn as TRAINED ones (``A = U(1, 16)``, the step
log-uniform in [1e-3, 1e-1]: a decay then sits near 1 and the state really
remembers; at ``A_log = dt_bias = 0`` every channel forgets within two
tokens and no check would feel the recurrence). One jitted call per leaf, a
layer (or an expert) at a time, so that no float32 copy of a whole stack is
ever alive beside 8 GiB of weights. Then ``balance_router_bias`` runs the
selection bias to rest."""

import jax
import jax.numpy as jnp
import numpy as np

from harness.weights import (SIGMOID_RATE, balanced_bias,
                             worst_load_over_mean)

SEQUENCES_AT_ONCE = 8


def kimi_linear_params(seed: int, cfg, dtype, std: float = 0.02,
                       bias_std: float = 0.02):
    """``cfg``: the program's KimiLinearConfig (sizes as they are run)."""
    d, f, E = cfg.d_model, cfg.moe_d_ff, cfg.num_experts
    H, Dh, C = cfg.linear_heads, cfg.linear_head_dim, cfg.kda_channels
    Hm, rkv = cfg.n_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
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

    def normal(shape, s=std):
        return draw(shape, lambda k, sh: jax.random.normal(
            k, sh, jnp.float32) * s)

    def ones(*shape):
        return jnp.ones(shape, dtype)

    def a_log(k, sh):
        return jnp.log(jax.random.uniform(k, sh, jnp.float32, 1.0, 16.0))

    def dt_bias(k, sh):
        dt = jnp.exp(jax.random.uniform(k, sh, jnp.float32, np.log(1e-3),
                                        np.log(1e-1)))
        return dt + jnp.log(-jnp.expm1(-dt))        # softplus^-1(dt)

    def swiglu(L, width):
        return {"mlp_gate": {"kernel": normal((L, d, width))},
                "mlp_in": {"kernel": normal((L, d, width))},
                "mlp_out": {"kernel": normal((L, width, d))}}

    Lk, Lm = cfg.n_kda_layers, cfg.n_full_layers
    Ld, Ls = cfg.n_dense_layers, cfg.n_sparse_layers
    kda = {"ln1": {"scale": ones(Lk, d)},
           "qkv": {"kernel": normal((Lk, d, C))},
           "conv": {"kernel": normal((Lk, cfg.conv_kernel, C), 0.5)},
           "f_a": {"kernel": normal((Lk, d, Dh))},
           "f_b": {"kernel": normal((Lk, Dh, H * Dh))},
           "A_log": draw((Lk, H), a_log),
           "dt_bias": draw((Lk, H * Dh), dt_bias),
           "b": {"kernel": normal((Lk, d, H))},
           "g_a": {"kernel": normal((Lk, d, Dh))},
           "g_b": {"kernel": normal((Lk, Dh, H * Dh))},
           "o_norm": {"scale": ones(Lk, Dh)},
           "attn_out": {"kernel": normal((Lk, H * Dh, d))}}
    mla = {"ln1": {"scale": ones(Lm, d)},
           "q": {"kernel": normal((Lm, d, Hm * (dn + dr)))},
           "kv_a": {"kernel": normal((Lm, d, rkv + dr))},
           "kv_a_norm": {"scale": ones(Lm, rkv)},
           "k_up": {"kernel": normal((Lm, Hm, dn, rkv))},
           "v_up": {"kernel": normal((Lm, Hm, rkv, dv))},
           "attn_out": {"kernel": normal((Lm, Hm * dv, d))}}
    sparse = {"ln2": {"scale": ones(Ls, d)}, "moe": {
        "router": {"kernel": normal((Ls, d, E)),
                   "bias": normal((Ls, E), bias_std)},
        "experts": {n: {"kernel": normal((Ls * held,) + shape).reshape(
            (Ls, held) + shape)} for n, shape in (
                ("wg", (d, f)), ("wi", (d, f)), ("wo", (f, d)))},
        "shared": swiglu(Ls, cfg.n_shared_experts * f)}}
    V = cfg.vocab_size
    rows = 32 if V % 32 == 0 else 1
    return {"wte": {"embedding": normal((rows, V // rows, d)).reshape(V, d)},
            "kda": kda, "mla": mla,
            "dense_block": dict({"ln2": {"scale": ones(Ld, d)}},
                                **swiglu(Ld, cfg.ffn_dim)),
            "block": sparse, "ln_f": {"scale": ones(d)},
            "lm_head": {"kernel": normal((rows, d, V // rows)).transpose(
                1, 0, 2).reshape(d, V)}}


def balance_router_bias(params, cfg, seed, reference, hp, tokens=4096,
                        steps=300, sequences=None, counted=None):
    """Replace each sparse layer's selection bias (random so far) by one at
    REST under the family's auxiliary-loss-free balancing rule
    (``weights.balanced_bias``, with one group: plain top-k), as
    a trained model's is: calibration tokens go through the layers once
    (the plain reference's own layer functions: this is calibration, not a
    check), and at each sparse layer the bias is run to rest on that
    layer's scores before the layer is applied. The calibration tokens are
    ONE sequence of ``tokens`` random ids from the seed, or, given
    ``sequences`` ``[B, S]`` (each a sequence of its own: the recurrence
    and the attention see no other) and ``counted`` ``[B, S]`` bool, the
    positions of those sequences that count: the driver hands the model's
    OWN continuations, because greedy decoding of a random model emits few
    tokens again and again, and what a decode-heavy window routes is those
    (PERF.md section 6, PR 34). Deterministic in the seed. Returns (params,
    [worst load over mean before, after] a sparse layer)."""
    if sequences is None:
        rng = np.random.default_rng([int(seed) % (2 ** 31 - 1), 7])
        sequences = rng.integers(1, cfg.vocab_size, (1, int(tokens)))
        counted = np.ones(sequences.shape, bool)
    ids = jnp.asarray(sequences, jnp.int32)                      # [B, S]
    counted = np.asarray(counted, bool).reshape(-1)
    key, none = reference.hp_key(hp), frozenset()
    nd = cfg.n_dense_layers
    rule = (1, 1, cfg.moe_k)
    free = -jnp.ones((ids.shape[1], cfg.moe_k), jnp.int32)

    def attend(kind):
        return jax.jit(jax.vmap(lambda x, p: reference.attention_layer(
            x, p, key=key, variant=none, fp8=False, kind=kind),
            in_axes=(0, None)))
    attend = {0: attend(0), 1: attend(1)}

    @jax.jit
    def scores_of(x, p):
        h = reference._rms(x, p["ln2"]["scale"], hp["eps"])
        return jax.nn.sigmoid(
            h @ p["moe"]["router"]["kernel"].astype(jnp.float32))

    ffn = jax.jit(jax.vmap(lambda x, p: reference.ffn_layer(
        x, p, free, key=key, variant=none, fp8=False)[0], in_axes=(0, None)))

    # SEQUENCES_AT_ONCE sequences in one call; the stream stays in those
    # pieces from layer to layer
    pieces = range(0, ids.shape[0], SEQUENCES_AT_ONCE)
    xs = [params["wte"]["embedding"][ids[i:i + SEQUENCES_AT_ONCE]]
          .astype(jnp.float32) for i in pieces]                  # [b, S, d]
    old = params["block"]["moe"]["router"]["bias"]
    biases, report = [], []
    # one layer's slice of the stacks alive at a time
    for l, kind in enumerate(hp["kinds"]):
        attn, p = reference.layer_params(params, hp, l)
        xs = [attend[int(kind)](x, attn) for x in xs]
        if l >= nd:
            scores = jnp.concatenate([scores_of(x, p).reshape(
                -1, cfg.num_experts) for x in xs])[counted]
            b = balanced_bias(scores, old[l - nd], rule, int(steps),
                              SIGMOID_RATE).astype(old.dtype)
            report.append([worst_load_over_mean(scores, old[l - nd], rule),
                           worst_load_over_mean(scores, b, rule)])
            biases.append(b)
            p["moe"]["router"]["bias"] = b
            del scores
        xs = [ffn(x, p) for x in xs]
        jax.block_until_ready(xs)
        del attn, p
    moe = dict(params["block"]["moe"], router=dict(
        params["block"]["moe"]["router"], bias=jnp.stack(biases)))
    return dict(params, block=dict(params["block"], moe=moe)), report
