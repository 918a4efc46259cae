"""Plain reference for the ``dots_vlm`` language model (dots.vlm1.inst: the
DeepSeek-V3 block) as one chip of an expert-parallel deployment holds it:
float32 ``jax.numpy``, ``jax.default_matmul_precision("highest")``, no
kernels, no cache, no batching, no code shared with the program. A full
forward over one token sequence in the EXPANDED form, computed in blocks (a
layer at a time, a head at a time, an expert at a time, a quarter of the
dense FFN's width at a time, every weight matrix upcast when it is used) so
that 12,288 tokens fit beside bf16 weights of 10.25 GiB.

The equations (``x: [S, d]``; RMSNorm eps from ``hp``; pre-norm):

- attention: ``h = RMSNorm(x; g1)``; ``c_q = RMSNorm(h W_qa; g_q)``;
  ``[q_n | q_r]_j = c_q W_qb -> [S, H, d_n + d_r]``; ``[c | k_r] = h W_kva``;
  ``c_kv = RMSNorm(c; g_kv)``; ``q_r = rope(q_r, t)``, ``k_r = rope(k_r, t)``
  (ONE key of ``d_r`` shared by all heads); ``k_n_j = c_kv W^K_j``, ``v_j =
  c_kv W^V_j`` (the parameter tree keeps ``W^K_j`` transposed as
  ``k_up[j]`` ``[d_n, r]`` and ``W^V_j`` as ``v_up[j]`` ``[r, d_v]``);
  ``score_j(t, s) = (q_n_j(t) . k_n_j(s) + q_r_j(t) . k_r(s)) * (d_n +
  d_r)^-0.5 * m^2``, ``m = 0.1 * mscale_all_dim * ln(factor) + 1``; causal
  softmax; ``x = x + concat_j(o_j) W_o``. No biases.
- ``rope``: ``d_r / 2`` interleaved pairs ``(x_2i, x_2i+1)``, ``f_i =
  theta^(-2i/d_r)``; YaRN: ``low = floor(d_r ln(L0 / (beta_fast 2 pi)) / (2
  ln theta))``, ``high = ceil(d_r ln(L0 / (beta_slow 2 pi)) / (2 ln
  theta))``, clipped to ``[0, d_r - 1]``; ``r_i = clip((i - low) / (high -
  low), 0, 1)``; ``f'_i = (f_i / factor) r_i + f_i (1 - r_i)``; cos and sin
  times ``mscale(factor, mscale) / mscale(factor, mscale_all_dim)``.
- FFN, the leading dense layers: ``x = x + (silu(h Wg) * (h Wu)) Wd``,
  ``h = RMSNorm(x; g2)``.
- FFN, sparse layers: ``s = sigmoid(h W_r)`` over ALL published experts;
  ``c = s + b`` (to select only); group ``k`` = experts ``k E/G .. (k+1) E/G
  - 1``; ``G_k`` = the sum of the two largest ``c`` in group ``k``; keep the
  ``topk_group`` groups of largest ``G``; ``sel = top_k(c`` over the kept
  groups``)``; ``w_e = scale * s_e / sum_{e' in sel} s_e'``; ``x = x +
  sum_{e in sel, e held here} w_e FFN_e(h) + FFN_shared(h)``. What the
  absent experts would add is left out.
- head: ``logits = RMSNorm(x; gf) W_head`` over the vocabulary slice.

``hp`` (plain numbers, from the configuration file): ``n_heads``, ``d_n``,
``d_r``, ``d_v``, ``n_dense``, ``n_layers``, ``num_experts``, ``top_k``,
``n_group``, ``topk_group``, ``held`` = (first, count), ``routed_scale``,
``eps``, ``rope_theta``, ``rope_factor``, ``rope_original_max``,
``beta_fast``, ``beta_slow``, ``mscale``, ``mscale_all_dim``.

``variant`` names deliberate faults for the controls (tests and ``tools``):
"no_group_limit", "no_bias", "no_scale", "unnormalised", "wrong_held",
"no_yarn" (plain rotary frequencies), "no_mscale" (the softmax scale
without ``m^2``), "rotate_half" (the other pairing), "no_q_norm",
"no_kv_norm", "fp8_up" (only the up-projection ``k_up`` / ``v_up``, the
matrices the decode path absorbs, rounded to float8 e4m3). ``forced`` =
int32 ``[n_sparse, S, top_k]`` puts the given selection in the place of the
reference's own; a row of -1 leaves that token free. ``fp8`` rounds every
weight matrix to float8 e4m3 and back (the precision control).

Returns ``(logits [S, V] float32, route)``; ``route`` holds, per sparse
layer and token, the reference's own selection ``sel``, its biased scores
``biased [n_sparse, S, E]`` and its groups' sums ``group [n_sparse, S, G]``
(so a disagreement can be held to a near-tie at the level where it
arose)."""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

FFN_COLUMN_BLOCKS = 4


def _up(w, fp8):
    if fp8:
        w = w.astype(jnp.float8_e4m3fn)
    return w.astype(jnp.float32)


def _rms(x, scale, eps):
    x = x.astype(jnp.float32)
    y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return y * scale.astype(jnp.float32)


def yarn_frequencies(hp, variant=()):
    """(per-pair frequencies [d_r / 2] float64, the factor on cos and sin,
    the softmax scale)."""
    d_r, theta = hp["d_r"], hp["rope_theta"]
    i = np.arange(d_r // 2, dtype=np.float64)
    f = theta ** (-2.0 * i / d_r)
    factor = hp["rope_factor"]
    scale = (hp["d_n"] + d_r) ** -0.5
    if factor <= 1 or "no_yarn" in variant:
        return f, 1.0, scale

    def dim_of(turns):
        return d_r * math.log(hp["rope_original_max"] / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(dim_of(hp["beta_fast"])), 0)
    high = min(math.ceil(dim_of(hp["beta_slow"])), d_r - 1)
    if low == high:
        high += 0.001
    r = np.clip((i - low) / (high - low), 0.0, 1.0)
    f = (f / factor) * r + f * (1.0 - r)

    def mscale(m):
        return 0.1 * m * math.log(factor) + 1.0

    if "no_mscale" not in variant:
        scale *= mscale(hp["mscale_all_dim"]) ** 2
    return f, mscale(hp["mscale"]) / mscale(hp["mscale_all_dim"]), scale


def _rope(x, positions, freqs, amp, variant):
    """x ``[S, ..., d_r]``: rotate pair i by ``positions * freqs[i]``."""
    ang = positions.astype(jnp.float32)[:, None] * jnp.asarray(
        freqs, jnp.float32)                                    # [S, d_r/2]
    ang = ang.reshape((ang.shape[0],) + (1,) * (x.ndim - 2) + (-1,))
    cos, sin = jnp.cos(ang) * amp, jnp.sin(ang) * amp
    if "rotate_half" in variant:
        h = x.shape[-1] // 2
        a, b = x[..., :h], x[..., h:]
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


def _swiglu(h, wg, wu, wd):
    return (jax.nn.silu(h @ wg) * (h @ wu)) @ wd


def _attention(x, p, hp, variant, fp8):
    S = x.shape[0]
    H, d_n, d_r, d_v = hp["n_heads"], hp["d_n"], hp["d_r"], hp["d_v"]
    freqs, amp, scale = yarn_frequencies(hp, variant)
    pos = jnp.arange(S)
    h = _rms(x, p["ln1"]["scale"], hp["eps"])
    c_q = h @ _up(p["q_a"]["kernel"], fp8)
    if "no_q_norm" not in variant:
        c_q = _rms(c_q, p["q_a_norm"]["scale"], hp["eps"])
    ckv = h @ _up(p["kv_a"]["kernel"], fp8)
    r = ckv.shape[1] - d_r
    c_kv = ckv[:, :r]
    if "no_kv_norm" not in variant:
        c_kv = _rms(c_kv, p["kv_a_norm"]["scale"], hp["eps"])
    k_r = _rope(ckv[:, r:], pos, freqs, amp, variant)          # [S, d_r]
    seen = pos[:, None] >= pos[None, :]
    w_qb = p["q_b"]["kernel"].reshape(-1, H, d_n + d_r)
    w_o = p["attn_out"]["kernel"].reshape(H, d_v, -1)
    up8 = fp8 or "fp8_up" in variant

    def one_head(acc, j):
        def at(w, axis):
            return jax.lax.dynamic_index_in_dim(w, j, axis, keepdims=False)
        q = c_q @ _up(at(w_qb, 1), fp8)                        # [S, d_n+d_r]
        q_r = _rope(q[:, d_n:], pos, freqs, amp, variant)
        k_n = c_kv @ _up(at(p["k_up"]["kernel"], 0), up8).T    # [S, d_n]
        v = c_kv @ _up(at(p["v_up"]["kernel"], 0), up8)        # [S, d_v]
        s = (q[:, :d_n] @ k_n.T + q_r @ k_r.T) * scale
        s = jnp.where(seen, s, -jnp.inf)
        o = jax.nn.softmax(s, axis=-1) @ v
        return acc + o @ _up(at(w_o, 0), fp8), None

    out, _ = jax.lax.scan(one_head, jnp.zeros_like(x), jnp.arange(H))
    return x + out


def _dense_ffn(x, p, hp, fp8):
    h = _rms(x, p["ln2"]["scale"], hp["eps"])
    f = p["mlp_gate"]["kernel"].shape[-1]
    nblk = FFN_COLUMN_BLOCKS if f % FFN_COLUMN_BLOCKS == 0 else 1
    w = f // nblk

    def one_block(acc, b):
        def cols(m, axis):
            return _up(jax.lax.dynamic_slice_in_dim(m, b * w, w, axis), fp8)
        return acc + _swiglu(h, cols(p["mlp_gate"]["kernel"], 1),
                             cols(p["mlp_in"]["kernel"], 1),
                             cols(p["mlp_out"]["kernel"], 0)), None

    y, _ = jax.lax.scan(one_block, jnp.zeros_like(x), jnp.arange(nblk))
    return x + y


def _sparse_ffn(x, p, hp, variant, fp8, forced):
    E, K = hp["num_experts"], hp["top_k"]
    G, KG = hp["n_group"], hp["topk_group"]
    first, count = hp["held"]
    if "wrong_held" in variant:
        first = (first + count) % E
    moe = p["moe"]
    h = _rms(x, p["ln2"]["scale"], hp["eps"])
    s = jax.nn.sigmoid(h @ moe["router"]["kernel"].astype(jnp.float32))
    b = moe["router"]["bias"].astype(jnp.float32)
    biased = s if "no_bias" in variant else s + b
    S = x.shape[0]
    per = biased.reshape(S, G, E // G)
    group = jnp.sum(jax.lax.top_k(per, 2)[0], -1)              # [S, G]
    choose = biased
    if G > 1 and "no_group_limit" not in variant:
        kept_groups = jax.lax.top_k(group, KG)[1]              # [S, KG]
        kept = (kept_groups[:, :, None] == jnp.arange(G)).any(1)
        choose = jnp.where(kept[:, :, None], per, -jnp.inf).reshape(S, E)
    own = jax.lax.top_k(choose, K)[1]                          # [S, K]
    # a row of -1 leaves the token to the reference's own selection
    sel = jnp.where(forced[:, :1] < 0, own, forced)
    w = jnp.take_along_axis(s, sel, -1)
    if "unnormalised" not in variant:
        w = w / jnp.sum(w, -1, keepdims=True)
    if "no_scale" not in variant:
        w = w * hp["routed_scale"]
    ex = moe["experts"]

    def one_expert(acc, e):
        wg, wu, wd = (_up(jax.lax.dynamic_index_in_dim(
            ex[n]["kernel"], e, 0, keepdims=False), fp8)
            for n in ("wg", "wi", "wo"))
        we = jnp.sum(jnp.where(sel == first + e, w, 0.0), -1)   # [S]
        return acc + we[:, None] * _swiglu(h, wg, wu, wd), None

    routed, _ = jax.lax.scan(one_expert, jnp.zeros_like(h),
                             jnp.arange(count))
    sh = moe["shared"]
    shared = _swiglu(h, _up(sh["mlp_gate"]["kernel"], fp8),
                     _up(sh["mlp_in"]["kernel"], fp8),
                     _up(sh["mlp_out"]["kernel"], fp8))
    return x + routed + shared, (own, biased, group)


@functools.partial(jax.jit, static_argnames=("key", "variant", "fp8"))
def _forward(params, tokens, forced, *, key, variant, fp8):
    hp = dict(key)
    with jax.default_matmul_precision("highest"):
        x = params["wte"]["embedding"][tokens].astype(jnp.float32)

        def dense_layer(x, p):
            x = _attention(x, p, hp, variant, fp8)
            return _dense_ffn(x, p, hp, fp8), None

        def sparse_layer(x, layer):
            p, f = layer
            x = _attention(x, p, hp, variant, fp8)
            return _sparse_ffn(x, p, hp, variant, fp8, f)

        x, _ = jax.lax.scan(dense_layer, x, params["dense_block"])
        x, (sel, biased, group) = jax.lax.scan(
            sparse_layer, x, (params["block"], forced))
        x = _rms(x, params["ln_f"]["scale"], hp["eps"])
        logits = x @ _up(params["lm_head"]["kernel"], fp8)
    return logits, {"sel": sel, "biased": biased, "group": group}


def logits(params, tokens, hp, forced=None, variant=(), fp8=False):
    """tokens ``[S]`` -> (logits ``[S, V]`` float32, route)."""
    tokens = jnp.asarray(tokens, jnp.int32)
    n_sparse = hp["n_layers"] - hp["n_dense"]
    shape = (n_sparse, tokens.shape[0], hp["top_k"])
    forced = -jnp.ones(shape, jnp.int32) if forced is None \
        else jnp.asarray(forced, jnp.int32)
    assert forced.shape == shape, (forced.shape, shape)
    key = tuple(sorted((k, tuple(v) if isinstance(v, (list, tuple)) else v)
                       for k, v in hp.items()))
    return _forward(params, tokens, forced, key=key,
                    variant=frozenset(variant), fp8=bool(fp8))
