"""LongCat-Flash-Chat weights made on the device from the seed, in the type
they are served in and the stacked layout the program takes
(deepspeed_tpu/models/longcat_flash.py: the two sublayers of a double layer
under ``a`` and ``b``): every matrix normal(``std``), unit norm scales, the
router's selection bias zero. One jitted call per leaf, a layer (or an
expert) at a time, so that no float32 copy of a whole stack is ever alive
beside 9.6 GiB of weights. Then ``balance_router_bias`` runs the family's
load-balancing rule on the selection bias to rest."""

import jax
import jax.numpy as jnp
import numpy as np

from harness.weights import balanced_bias, softmax_rate, stored_bias

SEQUENCES_AT_ONCE = 16


def longcat_flash_params(seed: int, cfg, dtype, std: float = 0.02):
    """``cfg``: the program's LongcatFlashConfig (sizes as they are run)."""
    d, H, f = cfg.d_model, cfg.n_heads, cfg.moe_d_ff
    rq, rkv = cfg.q_lora_rank, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    L, held = cfg.n_layers, cfg.held[1]
    width = cfg.num_experts + cfg.n_zero_experts
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

    def sublayer():
        return {"ln1": {"scale": ones(L, d)},
                "q_a": {"kernel": normal((L, d, rq))},
                "q_a_norm": {"scale": ones(L, rq)},
                "q_b_t": {"kernel": normal((L, H * (dn + dr), rq))},
                "kv_a": {"kernel": normal((L, d, rkv + dr))},
                "kv_a_norm": {"scale": ones(L, rkv)},
                "k_up": {"kernel": normal((L, H, dn, rkv))},
                "v_up": {"kernel": normal((L, H, rkv, dv))},
                "attn_out": {"kernel": normal((L, H * dv, d))},
                "ln2": {"scale": ones(L, d)},
                "mlp_gate": {"kernel": normal((L, d, cfg.ffn_dim))},
                "mlp_in": {"kernel": normal((L, d, cfg.ffn_dim))},
                "mlp_out": {"kernel": normal((L, cfg.ffn_dim, d))}}

    moe = {"router": {"kernel": normal((L, d, width)),
                      "bias": jnp.zeros((L, width), dtype)},
           "experts": {n: {"kernel": normal((L * held,) + shape).reshape(
               (L, held) + shape)} for n, shape in (
                   ("wg", (d, f)), ("wi", (d, f)), ("wo", (f, d)))}}
    V = cfg.vocab_size
    rows = 32 if V % 32 == 0 else 1
    return {"wte": {"embedding": normal((rows, V // rows, d)).reshape(V, d)},
            "block": {"a": sublayer(), "b": sublayer(), "moe": moe},
            "ln_f": {"scale": ones(d)},
            "lm_head": {"kernel": normal((rows, d, V // rows)).transpose(
                1, 0, 2).reshape(d, V)}}


def balance_router_bias(params, cfg, seed, reference, hp, tokens=4096,
                        steps=1500, sequences=None, counted=None):
    """Replace each layer's selection bias by one at REST under the
    balancing rule, as a trained model's is: calibration tokens go through
    the double layers once (the plain reference's own layer functions, at
    the default matmul precision: this is calibration, not a check), and at
    each layer the bias is run to rest on that layer's probabilities before
    the expert layer is applied. The calibration tokens are ONE sequence of
    ``tokens`` random ids from the seed, or, given ``sequences`` ``[B, S]``
    (each attended on its own) and ``counted`` ``[B, S]`` bool, the
    positions of those sequences that count: the driver hands the model's
    OWN continuations, because greedy decoding of a random model emits few
    tokens again and again, and a bias at rest on random ids leaves each
    seed its own hot outputs in decode (PERF.md 7(z)). Deterministic in
    the seed. Returns (params, per layer [worst real expert's load over
    the mean before, after, zero-compute share of the pairs before,
    after])."""
    E, K = cfg.num_experts, cfg.moe_k
    if sequences is None:
        rng = np.random.default_rng([int(seed) % (2 ** 31 - 1), 7])
        sequences = rng.integers(1, cfg.vocab_size, (1, int(tokens)))
        counted = np.ones(sequences.shape, bool)
    ids = jnp.asarray(sequences, jnp.int32)                      # [B, S]
    counted = np.asarray(counted, bool).reshape(-1)
    key = tuple(sorted(hp.items()))

    def layer_of(block, l, b):
        # sliced INSIDE the jitted call: a layer's 2.5 GB of kernels are
        # read where they lie, not copied out beside the weights and the pool
        p = jax.tree_util.tree_map(lambda a: a[l], block)
        p["moe"]["router"]["bias"] = b
        return p

    def probs_one(x, block, l, b):
        p = layer_of(block, l, b)
        x1, u = reference.first_half(x, p, dict(key))
        return x1, u, reference.router_probs(u, p["moe"])

    def rest_one(x1, u, block, l, b):
        p = layer_of(block, l, b)
        m, _, _ = reference.expert_layer(u, p["moe"], dict(key))
        return reference.second_half(x1, u, m, p, dict(key))

    # every sequence is attended on its own, SEQUENCES_AT_ONCE of them in
    # one call; the stream stays in those pieces from layer to layer
    probs_of = jax.jit(jax.vmap(probs_one, in_axes=(0, None, None, None)))
    rest = jax.jit(jax.vmap(rest_one, in_axes=(0, 0, None, None, None)))

    def loads(probs, b):
        sel = np.asarray(jax.lax.top_k(
            probs + b.astype(jnp.float32), K)[1]).reshape(-1)
        load = np.bincount(sel, minlength=probs.shape[1])
        return float(load[:E].max() / max(load[:E].mean(), 1e-9)), \
            float(load[E:].sum() / sel.size)

    pieces = range(0, ids.shape[0], SEQUENCES_AT_ONCE)
    xs = [params["wte"]["embedding"][ids[i:i + SEQUENCES_AT_ONCE]]
          .astype(jnp.float32) for i in pieces]                  # [b, S, d]
    block = params["block"]
    old = block["moe"]["router"]["bias"]
    biases, report = [], []
    for l in range(cfg.n_layers):
        us, probs = [], []
        for i, x in enumerate(xs):
            xs[i], u, pr = probs_of(x, block, l, old[l])
            us.append(u)
            probs.append(pr.reshape(-1, pr.shape[-1]))
        del x, u, pr
        probs = jnp.concatenate(probs)[counted]
        # every output's share is 1 / (E + Z): the real experts level
        # among themselves and the zero-compute ones at Z / (E + Z) of the
        # pairs; the choice is made with the bias as stored
        b = stored_bias(balanced_bias(
            probs, old[l], (1, 1, K), int(steps),
            softmax_rate(probs.shape[1]), stored=jnp.dtype(old.dtype).name),
            old.dtype)
        before, after = loads(probs, old[l]), loads(probs, b)
        report.append([before[0], after[0], before[1], after[1]])
        biases.append(b)
        for i, (x1, u) in enumerate(zip(xs, us)):
            xs[i] = rest(x1, u, block, l, b)
        del x1, u, us, probs
        jax.block_until_ready(xs)
    moe = dict(params["block"]["moe"], router=dict(
        params["block"]["moe"]["router"], bias=jnp.stack(biases)))
    return dict(params, block=dict(params["block"], moe=moe)), report
