"""ZAYA1 (``zaya``) weights made on the device from the seed, in the type
they are served in and the stacked layout the program takes
(deepspeed_tpu/models/zaya.py ``init_params``, whose choices these are):
every matrix normal(``std``); the residual scalings near their rest (scales
1 + 0.1 n, biases ``std`` n), the convolutions' taps normal(0.5) and
normal(Dh^-0.5), the router's MLP normal(R^-0.5), its mixing vector 0.5 +
0.1 n, the temperatures ``TEMP_MEAN`` + 0.1 n; every bias normal(``bias_std``). One
jitted call per leaf, a layer (or an expert) at a time, so that no float32
copy of a whole stack is ever alive beside 8.7 GiB of weights. Then
``balance_router_bias`` runs the selection bias to rest."""

import jax
import jax.numpy as jnp
import numpy as np

from harness.weights import balanced_bias, softmax_rate, stored_bias


# exp(temp) multiplies the keys. At its initial 0 a random model's scores are
# N(0, 1) and its attention is near uniform over thousands of keys, so
# nothing downstream feels the mixing that makes q and k: float8
# convolutions then move the logits by 0.051 where bf16 rounding alone reads
# 0.040. A TRAINED temperature peaks the softmax; at 0.7 (scores of standard
# deviation 2) the same control reads 0.148 against 0.050, and at 1.4 the
# random model turns chaotic (bf16 against float32: 2.4). PERF.md section 6,
# my chip runs, PR 34.
TEMP_MEAN = 0.7
SEQUENCES_AT_ONCE = 16


def zaya_params(seed: int, cfg, dtype, std: float = 0.02,
                bias_std: float = 0.02):
    """``cfg``: the program's ZayaConfig (sizes as they are run)."""
    d, H, Hkv, Dh = cfg.d_model, cfg.n_heads, cfg.kv_heads, cfg.head_dim
    f, E, R, C = cfg.moe_d_ff, cfg.num_experts, cfg.router_hidden, \
        cfg.cca_channels
    L, held = cfg.n_layers, cfg.held[1]
    # any whole number up to a little over 2**31 is a seed
    root = jax.random.key(int(seed) % (2 ** 31 - 1), impl="rbg")
    count = [0]

    def normal(shape, s=std, mean=0.0):
        count[0] += 1
        keys = jax.random.split(jax.random.fold_in(root, count[0]), shape[0])

        @jax.jit
        def make(keys):
            return jax.lax.map(lambda k: (mean + jax.random.normal(
                k, shape[1:], jnp.float32) * s).astype(dtype), keys)
        return make(keys)

    def ones(*shape):
        return jnp.ones(shape, dtype)

    def res():
        return {"s_r": normal((L, d), 0.1, 1.0), "b_r": normal((L, d)),
                "s_o": normal((L, d), 0.1, 1.0), "b_o": normal((L, d))}

    def lin(shape, s, bias=True):
        out = {"kernel": normal(shape, s)}
        if bias:
            out["bias"] = normal(shape[:1] + shape[-1:], bias_std)
        return out

    block = {
        "ln1": {"scale": ones(L, d)},
        "qkv": lin((L, d, C + 2 * Dh), std, bias=False),
        "conv0": {"kernel": normal((L, cfg.cca_time0, C), 0.5),
                  "bias": normal((L, C), bias_std)},
        "conv1": {"kernel": normal((L, cfg.cca_time1, H + Hkv, Dh, Dh),
                                   Dh ** -0.5),
                  "bias": normal((L, C), bias_std)},
        "temp": normal((L, Hkv), 0.1, TEMP_MEAN),
        "attn_out": lin((L, H * Dh, d), std, bias=False),
        "res1": res(), "ln2": {"scale": ones(L, d)}, "res2": res(),
        "moe": {
            "router": {
                "down": lin((L, d, R), std),
                "mix": normal((L, R), 0.1, 0.5),
                "norm": {"scale": ones(L, R)},
                "w1": lin((L, R, R), R ** -0.5),
                "w2": lin((L, R, R), R ** -0.5),
                "w3": lin((L, R, E + 1), R ** -0.5, bias=False),
                "bias": normal((L, E + 1), bias_std)},
            "experts": {n: {"kernel": normal((L * held,) + shape).reshape(
                (L, held) + shape)} for n, shape in (
                    ("wg", (d, f)), ("wi", (d, f)), ("wo", (f, d)))}}}
    V = cfg.vocab_size
    rows = 32 if V % 32 == 0 else 1
    return {"wte": {"embedding": normal((rows, V // rows, d)).reshape(V, d)},
            "block": block, "ln_f": {"scale": ones(d)}}


def balance_router_bias(params, cfg, seed, reference, hp, tokens=4096,
                        steps=1500, skip_share=None, sequences=None,
                        counted=None):
    """Replace each layer's selection bias (random so far) by one at REST
    under the balancing rule, as a trained model's is: calibration tokens go
    through the layers once (the plain reference's own layer functions, at
    the default matmul precision: this is calibration, not a check), the
    router's state carried from layer to layer, and at each layer the bias
    is run to rest on that layer's probabilities before the layer is
    applied: the ``E`` experts level, the skip output at ``skip_share`` of
    the tokens (1 / (E + 1) when None). The calibration tokens are ONE
    sequence of ``tokens`` random ids from the seed, or, given
    ``sequences`` ``[B, S]`` (each attended on its own) and ``counted``
    ``[B, S]`` bool, the positions of those sequences that count: the
    driver hands the model's OWN continuations, because greedy decoding of a
    random model emits few tokens again and again, and a bias at rest on
    random ids leaves each seed its own hot experts in decode (PERF.md
    section 6, PR 34; PR 32 for the random bias). Deterministic in the
    seed. Returns (params, per layer [worst expert load over mean before,
    after, skip share after])."""
    E = cfg.num_experts
    share = 1.0 / (E + 1) if skip_share is None else float(skip_share)
    target = jnp.asarray([(1.0 - share) / E] * E + [share], jnp.float32)
    if sequences is None:
        rng = np.random.default_rng([int(seed) % (2 ** 31 - 1), 7])
        sequences = rng.integers(1, cfg.vocab_size, (1, int(tokens)))
        counted = np.ones(sequences.shape, bool)
    ids = jnp.asarray(sequences, jnp.int32)                      # [B, S]
    counted = np.asarray(counted, bool).reshape(-1)
    free = -jnp.ones((ids.shape[1], 1), jnp.int32)
    key = tuple(sorted(hp.items()))
    none = frozenset()

    def probs_one(x, r, p):
        # the router alone: no expert is held, so none is computed
        x = reference._attention(x, p, dict(key), none, False)
        _, _, (_, biased) = reference._experts(
            x, r, p, dict(dict(key), held=(0, 0)), none, False, free)
        return x, biased - p["moe"]["router"]["bias"].astype(jnp.float32)

    def experts_one(x, r, p):
        x, r, _ = reference._experts(x, r, p, dict(key), none, False, free)
        return x, r

    # every sequence is attended on its own, SEQUENCES_AT_ONCE of them in
    # one call; the stream stays in those pieces from layer to layer (whole,
    # its float32 copies would not fit beside the weights and the pools)
    probs_of = jax.jit(jax.vmap(probs_one, in_axes=(0, 0, None)))
    experts = jax.jit(jax.vmap(experts_one, in_axes=(0, 0, None)))

    def loads(probs, b):
        sel = np.asarray(jnp.argmax(probs + b.astype(jnp.float32), -1))
        load = np.bincount(sel, minlength=E + 1)
        return float(load[:E].max() / max(load[:E].mean(), 1e-9)), \
            float(load[E] / sel.size)

    pieces = range(0, ids.shape[0], SEQUENCES_AT_ONCE)
    xs = [params["wte"]["embedding"][ids[i:i + SEQUENCES_AT_ONCE]]
          .astype(jnp.float32) for i in pieces]                  # [b, S, d]
    rs = [jnp.zeros(x.shape[:2] + (cfg.router_hidden,), jnp.float32)
          for x in xs]
    old = params["block"]["moe"]["router"]["bias"]
    biases, report = [], []
    # one layer's slice of the stack alive at a time (0.4 GB beside 8.7 GiB)
    for l in range(cfg.n_layers):
        p = jax.tree_util.tree_map(lambda a: a[l], params["block"])
        probs = []
        for i, (x, r) in enumerate(zip(xs, rs)):
            xs[i], pr = probs_of(x, r, p)
            probs.append(pr.reshape(-1, E + 1))
        del x, pr
        probs = jnp.concatenate(probs)[counted]
        # top-1 of E + 1 outputs towards `target`; the choice is made with
        # the bias as stored
        b = stored_bias(balanced_bias(
            probs, old[l], (1, 1, 1), int(steps), softmax_rate(E + 1),
            target=target, stored=jnp.dtype(old.dtype).name), old.dtype)
        report.append([loads(probs, old[l])[0], *loads(probs, b)])
        biases.append(b)
        p["moe"]["router"]["bias"] = b
        for i, (x, r) in enumerate(zip(xs, rs)):
            xs[i], rs[i] = experts(x, r, p)
        del x, r
        jax.block_until_ready(xs)
        del p, probs
    moe = dict(params["block"]["moe"], router=dict(
        params["block"]["moe"]["router"], bias=jnp.stack(biases)))
    return dict(params, block=dict(params["block"], moe=moe)), report
