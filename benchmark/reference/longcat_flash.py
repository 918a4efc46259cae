"""Plain reference for LongCat-Flash-Chat's decoder as one chip of an
expert-parallel deployment holds it: float32 ``jax.numpy``,
``jax.default_matmul_precision("highest")``, no kernels, no cache, no
batching, no code shared with the program. A full forward over one token
sequence with the latent attention in its EXPANDED form, computed in blocks
(a layer at a time, a head at a time, an expert at a time, a quarter of a
dense FFN's width at a time, every weight matrix upcast when it is used) so
that 6,144 tokens fit beside bf16 weights of 9.63 GiB.

The equations (``x: [S, d]``; RMSNorm eps from ``hp``; no bias anywhere):

- latent attention ``MLA(u)`` on the normed ``u``: ``c_q = RMSNorm(u W_qa;
  g_q) * s_q``; ``[q_n | q_r]_j = c_q W_qb -> [S, H, d_n + d_r]``; ``[c |
  k_r] = u W_kva``; ``c_kv = RMSNorm(c; g_kv) * s_kv``; ``q_r = rope(q_r,
  t)``, ``k_r = rope(k_r, t)`` (ONE key of ``d_r`` shared by all heads, NOT
  scaled); ``k_n_j = c_kv W^K_j``, ``v_j = c_kv W^V_j`` (the parameter tree
  keeps ``W_qb`` transposed as ``q_b_t`` ``[H (d_n + d_r), r_q]``, ``W^K_j``
  transposed as ``k_up[j]`` ``[d_n, r]`` and ``W^V_j`` as ``v_up[j]`` ``[r,
  d_v]``); ``score_j(t, s) = (q_n_j(t) . k_n_j(s) +
  q_r_j(t) . k_r(s)) * (d_n + d_r)^-0.5``; causal softmax; ``concat_j(o_j)
  W_o``.
- ``rope``: ``d_r / 2`` interleaved pairs ``(x_2i, x_2i+1)``, ``f_i =
  theta^(-2i/d_r)``, no scaling of the frequencies, no ``mscale``.
- dense FFN ``F(u) = (silu(u Wg) * (u Wu)) Wd``.
- expert layer ``M(u)``: ``p = softmax(u W_r)`` over ALL ``E + Z`` outputs
  (``E`` published experts, then ``Z`` zero-compute identity experts);
  ``sel = top_k(p + b)`` (``b`` selects only); ``w_k = scale * p[sel_k]``,
  NOT renormalised over the k; ``M(u) = sum_{k: sel_k < E, held here} w_k
  FFN_{sel_k}(u) + u * sum_{k: sel_k >= E} w_k``. What the absent experts
  would add is left out; the identity experts' term is every chip's alike.
- the DOUBLE layer (norms ``ln1`` / ``ln2`` of sublayers ``a`` and ``b``)::

      x1 = x  + MLA_a(ln1a(x))
      u  = ln2a(x1);  m = M(u)          # the shortcut: not added yet
      x2 = x1 + F_a(u)
      x3 = x2 + MLA_b(ln1b(x2))
      x4 = x3 + F_b(ln2b(x3))
      out = x4 + m

- head: ``logits = RMSNorm(x; gf) W_head`` over the vocabulary slice.

Departures from the published description, each an ``assumed`` line of the
configuration's file (the published modelling code could not be read; the
catalog row's keys and ``described_as`` are what there is): the ORDER
inside the double layer and that ``M`` reads ``ln2a(x1)``; softmax scores,
the bias to select only, no renormalisation; that the two low-rank scales
act on the NORMED low ranks and are sqrt(hidden / rank); interleaved rotary
pairs; no bias term in the router.

``hp`` (plain numbers, from the configuration file): ``n_heads``, ``d_n``,
``d_r``, ``d_v``, ``n_layers``, ``num_experts``, ``zero_experts``,
``top_k``, ``held`` = (first, count), ``routed_scale``, ``q_scale``,
``kv_scale``, ``eps``, ``rope_theta``.

``variant`` names deliberate faults for the controls (tests and ``tools``):
"no_zero_term" (the identity experts add nothing), "no_kv_scale" /
"no_q_scale" (that scale 1.0), "scale_k_r" (the kv scale on the shared key
too), "sigmoid" (sigmoid scores), "renormalised" (the k weights summed to
1 before the scale), "no_bias", "no_scale" (``routed_scale`` 1),
"wrong_held", "no_shortcut" (``m`` joins the stream with ``F_a``, before
the second attention reads it), "rotate_half" (the other pairing), "fp8_up" (only ``k_up`` / ``v_up``, the
matrices the decode path absorbs, rounded to float8 e4m3's mantissa).
``forced`` = int32 ``[n_layers, S, top_k]`` puts the given selection in the
place of the reference's own; a row of -1 leaves that token free. ``fp8``
rounds every weight matrix to float8 e4m3's 3 mantissa bits
(``lax.reduce_precision``: a pair of converts is removed by the chip's
compiler).

Returns ``(logits [S, V] float32, route)``; ``route`` holds, per layer and
token, the reference's own selection ``sel`` and its biased probabilities
``biased [n_layers, S, E + Z]`` (so a disagreement can be held to a
near-tie)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np

FFN_COLUMN_BLOCKS = 4


def _up(w, fp8):
    """A weight in float32; with ``fp8`` rounded to float8 e4m3's 3
    mantissa bits first (the exponent keeps its 8 bits: a weight of 0.02 is
    below e4m3's smallest normal number and would lose more)."""
    w = w.astype(jnp.float32)
    return jax.lax.reduce_precision(w, 8, 3) if fp8 else w


def _rms(x, scale, eps):
    x = x.astype(jnp.float32)
    y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return y * scale.astype(jnp.float32)


def _rope(x, positions, hp, variant):
    """x ``[S, ..., d_r]``: rotate pair i by ``positions * theta^(-2i /
    d_r)``."""
    d_r = hp["d_r"]
    freqs = hp["rope_theta"] ** (-2.0 * np.arange(d_r // 2, dtype=np.float64)
                                 / d_r)
    ang = positions.astype(jnp.float32)[:, None] * jnp.asarray(
        freqs, jnp.float32)                                    # [S, d_r/2]
    ang = ang.reshape((ang.shape[0],) + (1,) * (x.ndim - 2) + (-1,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
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
    """``x + MLA(RMSNorm(x; ln1))`` of one sublayer ``p``."""
    S = x.shape[0]
    H, d_n, d_r, d_v = hp["n_heads"], hp["d_n"], hp["d_r"], hp["d_v"]
    scale = (d_n + d_r) ** -0.5
    s_q = 1.0 if "no_q_scale" in variant else hp["q_scale"]
    s_kv = 1.0 if "no_kv_scale" in variant else hp["kv_scale"]
    pos = jnp.arange(S)
    u = _rms(x, p["ln1"]["scale"], hp["eps"])
    c_q = _rms(u @ _up(p["q_a"]["kernel"], fp8), p["q_a_norm"]["scale"],
               hp["eps"]) * s_q
    ckv = u @ _up(p["kv_a"]["kernel"], fp8)
    r = ckv.shape[1] - d_r
    c_kv = _rms(ckv[:, :r], p["kv_a_norm"]["scale"], hp["eps"]) * s_kv
    k_r = _rope(ckv[:, r:], pos, hp, variant)                  # [S, d_r]
    if "scale_k_r" in variant:
        k_r = k_r * s_kv
    seen = pos[:, None] >= pos[None, :]
    w_qb = p["q_b_t"]["kernel"].reshape(H, d_n + d_r, -1)       # W_qb^T
    w_o = p["attn_out"]["kernel"].reshape(H, d_v, -1)
    up8 = fp8 or "fp8_up" in variant

    def one_head(acc, j):
        def at(w, axis):
            return jax.lax.dynamic_index_in_dim(w, j, axis, keepdims=False)
        q = c_q @ _up(at(w_qb, 0), fp8).T                      # [S, d_n+d_r]
        q_r = _rope(q[:, d_n:], pos, hp, variant)
        k_n = c_kv @ _up(at(p["k_up"]["kernel"], 0), up8).T    # [S, d_n]
        v = c_kv @ _up(at(p["v_up"]["kernel"], 0), up8)        # [S, d_v]
        s = (q[:, :d_n] @ k_n.T + q_r @ k_r.T) * scale
        s = jnp.where(seen, s, -jnp.inf)
        o = jax.nn.softmax(s, axis=-1) @ v
        return acc + o @ _up(at(w_o, 0), fp8), None

    out, _ = jax.lax.scan(one_head, jnp.zeros_like(x), jnp.arange(H))
    return x + out


def _dense(h, p, fp8):
    """``F(h)`` of one sublayer ``p``, a block of columns at a time."""
    f = p["mlp_gate"]["kernel"].shape[-1]
    nblk = FFN_COLUMN_BLOCKS if f % FFN_COLUMN_BLOCKS == 0 else 1
    w = f // nblk

    def one_block(acc, b):
        def cols(m, axis):
            return _up(jax.lax.dynamic_slice_in_dim(m, b * w, w, axis), fp8)
        return acc + _swiglu(h, cols(p["mlp_gate"]["kernel"], 1),
                             cols(p["mlp_in"]["kernel"], 1),
                             cols(p["mlp_out"]["kernel"], 0)), None

    y, _ = jax.lax.scan(one_block, jnp.zeros_like(h), jnp.arange(nblk))
    return y


def router_probs(u, moe, variant=frozenset()):
    """The router's scores of the normed ``u`` ``[S, d]`` over all ``E +
    Z`` outputs, float32."""
    logits = u @ moe["router"]["kernel"].astype(jnp.float32)
    return jax.nn.sigmoid(logits) if "sigmoid" in variant \
        else jax.nn.softmax(logits, axis=-1)


def expert_layer(u, moe, hp, variant=frozenset(), fp8=False, forced=None):
    """``M(u)`` for the normed ``u`` ``[S, d]`` as the chip that holds
    ``hp["held"]`` gives it: (the held experts' routed part + the
    zero-compute experts' term, (the reference's own selection, its biased
    probabilities), the zero-compute term alone)."""
    E, K = hp["num_experts"], hp["top_k"]
    first, count = hp["held"]
    if "wrong_held" in variant:
        first = (first + count) % E
    p = router_probs(u, moe, variant)
    b = moe["router"]["bias"].astype(jnp.float32)
    biased = p if "no_bias" in variant else p + b
    own = jax.lax.top_k(biased, K)[1]                          # [S, K]
    # a row of -1 leaves the token to the reference's own selection
    sel = own if forced is None else jnp.where(forced[:, :1] < 0, own,
                                               forced)
    w = jnp.take_along_axis(p, sel, -1)
    if "renormalised" in variant:
        w = w / jnp.sum(w, -1, keepdims=True)
    if "no_scale" not in variant:
        w = w * hp["routed_scale"]
    ex = moe["experts"]

    def one_expert(acc, e):
        wg, wu, wd = (_up(jax.lax.dynamic_index_in_dim(
            ex[n]["kernel"], e, 0, keepdims=False), fp8)
            for n in ("wg", "wi", "wo"))
        we = jnp.sum(jnp.where(sel == first + e, w, 0.0), -1)   # [S]
        return acc + we[:, None] * _swiglu(u, wg, wu, wd), None

    routed, _ = jax.lax.scan(one_expert, jnp.zeros_like(u),
                             jnp.arange(count))
    zero = u * jnp.sum(jnp.where(sel >= E, w, 0.0), -1)[:, None]
    if "no_zero_term" in variant:
        zero = jnp.zeros_like(zero)
    return routed + zero, (own, biased), zero


def first_half(x, p, hp, variant=frozenset(), fp8=False):
    """A double layer up to its router: (x1, u = ln2a(x1))."""
    x1 = _attention(x, p["a"], hp, variant, fp8)
    return x1, _rms(x1, p["a"]["ln2"]["scale"], hp["eps"])


def second_half(x1, u, m, p, hp, variant=frozenset(), fp8=False):
    """The rest of a double layer, given the expert layer's ``m``."""
    x2 = x1 + _dense(u, p["a"], fp8)
    if "no_shortcut" in variant:
        x2, m = x2 + m, 0.0
    x3 = _attention(x2, p["b"], hp, variant, fp8)
    x4 = x3 + _dense(_rms(x3, p["b"]["ln2"]["scale"], hp["eps"]), p["b"],
                     fp8)
    return x4 + m


def double_layer(x, p, hp, variant=frozenset(), fp8=False, forced=None):
    """One double layer: (out, (own selection, biased probabilities))."""
    x1, u = first_half(x, p, hp, variant, fp8)
    m, route, _ = expert_layer(u, p["moe"], hp, variant, fp8, forced)
    return second_half(x1, u, m, p, hp, variant, fp8), route


@functools.partial(jax.jit, static_argnames=("key", "variant", "fp8"))
def _forward(params, tokens, forced, *, key, variant, fp8):
    hp = dict(key)
    with jax.default_matmul_precision("highest"):
        x = params["wte"]["embedding"][tokens].astype(jnp.float32)

        def layer(x, xs):
            p, f = xs
            return double_layer(x, p, hp, variant, fp8, f)

        x, (sel, biased) = jax.lax.scan(layer, x, (params["block"], forced))
        x = _rms(x, params["ln_f"]["scale"], hp["eps"])
        logits = x @ _up(params["lm_head"]["kernel"], fp8)
    return logits, {"sel": sel, "biased": biased}


def logits(params, tokens, hp, forced=None, variant=(), fp8=False):
    """tokens ``[S]`` -> (logits ``[S, V]`` float32, route)."""
    tokens = jnp.asarray(tokens, jnp.int32)
    shape = (hp["n_layers"], tokens.shape[0], hp["top_k"])
    forced = -jnp.ones(shape, jnp.int32) if forced is None \
        else jnp.asarray(forced, jnp.int32)
    assert forced.shape == shape, (forced.shape, shape)
    key = tuple(sorted((k, tuple(v) if isinstance(v, (list, tuple)) else v)
                       for k, v in hp.items()))
    return _forward(params, tokens, forced, key=key,
                    variant=frozenset(variant), fp8=bool(fp8))
