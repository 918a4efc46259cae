"""Plain reference for the ``kimi_linear`` model (Kimi-Linear-48B-A3B: gated
delta-rule linear attention in most layers, un-rotated latent attention in
the rest, sigmoid-routed experts) as one chip of an expert-parallel
deployment holds it: float32 ``jax.numpy``,
``jax.default_matmul_precision("highest")``, no kernels, no cache, no
chunks, no code shared with the program. A full forward over one token
sequence, a layer at a time (each layer's weights upcast when it is used).

The equations (``x: [S, d]``; RMSNorm eps from ``hp``; pre-norm). Layer
``l`` (0-indexed) is linear attention where ``hp["kinds"][l] == 0`` and
latent attention where it is 1; the parameter tree stacks each kind's
layers by themselves, in layer order (``kda``, ``mla``).

- linear attention (KDA), ``h = RMSNorm(x; g1)``: ``[q~ | k~ | v~] = h
  W_qkv`` (each ``H x D``); each channel passes a causal convolution over
  time of ``taps`` taps, ``y_t = sum_j w_j x~_(t - taps + 1 + j)``, zeros
  before the first token, then SiLU; per head ``q = q / sqrt(|q|^2 + eps_l2)
  / sqrt(D)``, ``k = k / sqrt(|k|^2 + eps_l2)``; decay ``g = -exp(A_log_h)
  softplus((h W_fa) W_fb + dt_bias)`` per head and key channel, ``a =
  exp(g)``; write strength ``b = sigmoid(h W_b)`` per head. State ``S``
  ``[D keys, D values]`` a head, float32, zeros before the first token:
  ``S' = a (.) S`` (rows scaled), ``S = S' + b k (v - S'^T k)^T``, ``o =
  S^T q``, ONE TOKEN AT A TIME under ``lax.scan``. Then ``o = RMSNorm_head(
  o; g_o) (.) sigmoid((h W_ga) W_gb)`` and ``x = x + concat(o) W_o``.
- latent attention (MLA, no positions): ``q_j = h W_q -> [S, H, d_n +
  d_r]``; ``[c | k_r] = h W_kva``; ``c_kv = RMSNorm(c; g_kv)``; ``k_j = [c_kv
  W^K_j | k_r]`` (``k_r`` shared by all heads, NOT rotated), ``v_j = c_kv
  W^V_j``; causal softmax of ``q_j . k_j / sqrt(d_n + d_r)``; ``x = x +
  concat_j(o_j) W_o``. (``k_up[j]`` is ``W^K_j`` transposed ``[d_n, r]``,
  ``v_up[j]`` is ``W^V_j`` ``[r, d_v]``.)
- FFN, the leading ``n_dense`` layers: ``x = x + (silu(h Wg) * (h Wu)) Wd``,
  ``h = RMSNorm(x; g2)``.
- FFN, sparse layers: ``s = sigmoid(h W_r)`` over ALL published experts;
  ``sel = top_k(s + bias)`` (one group: no limit); ``w_e = scale * s_e /
  sum_{e' in sel} s_e'``; ``x = x + sum_{e in sel, e held here} w_e
  FFN_e(h) + FFN_shared(h)``. What the absent experts would add is left out.
- head: ``logits = RMSNorm(x; gf) W_head`` over the vocabulary slice.

``hp`` (plain numbers, from the configuration file): ``kinds`` (a tuple, one
entry a layer), ``n_dense``, ``lin_heads``, ``lin_dim``, ``taps``,
``l2_eps``, ``n_heads``, ``d_n``, ``d_r``, ``d_v``, ``num_experts``,
``top_k``, ``held`` = (first, count), ``routed_scale``, ``eps``.

``variant`` names deliberate faults for the controls (tests and ``tools``):
"state_bf16" (the recurrent state rounded to bfloat16 after every token),
"fp8_kda" (ONLY the linear-attention side's ``A_log``, ``dt_bias``,
``W_fa`` / ``W_fb`` and the convolution's taps rounded to float8 e4m3's
3 mantissa bits),
"no_decay" (``a = 1``), "no_conv" (the last tap alone), "no_l2",
"no_write_gate" (``b = 1``), "no_out_gate", "rotated" (rotary on the
``d_r`` values, as dots.vlm1 has it), "no_bias", "no_scale",
"unnormalised", "wrong_held". ``forced`` = int32 ``[n_sparse, S, top_k]``
puts the given selection in the place of the reference's own; a row of -1
leaves that token free. ``fp8`` rounds every weight to float8 e4m3's 3
mantissa bits (the precision control; :func:`_up`).

Returns ``(logits [S, V] float32, route)``; ``route`` holds, per sparse
layer and token, the reference's own selection ``sel``, its biased scores
``biased [n_sparse, S, E]`` and ``group [n_sparse, S, 1]`` (one group; the
key is there for ``drivers/serve_dots_vlm.py``'s dispute margin)."""

import functools

import jax
import jax.numpy as jnp


def _up(w, fp8):
    """A weight in float32; with ``fp8`` rounded to float8 e4m3's 3 mantissa
    bits first. ``lax.reduce_precision`` and not a pair of converts: on the
    chip the compiler removed ``astype(float8).astype(float32)`` on the
    linear-attention side's operands and ``astype(bfloat16).astype(float32)``
    on the state (both controls read the sound reference's numbers to 16
    digits; PERF.md, PR 40). The exponent keeps its 8 bits: a weight of
    0.02 is below e4m3's smallest normal number and would lose more."""
    w = w.astype(jnp.float32)
    return jax.lax.reduce_precision(w, 8, 3) if fp8 else w


def _rms(x, scale, eps):
    x = x.astype(jnp.float32)
    y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return y * scale.astype(jnp.float32)


def _swiglu(h, wg, wu, wd):
    return (jax.nn.silu(h @ wg) * (h @ wu)) @ wd


def _linear_attention(x, p, hp, variant, fp8):
    S = x.shape[0]
    H, D, taps = hp["lin_heads"], hp["lin_dim"], hp["taps"]
    side8 = fp8 or "fp8_kda" in variant
    h = _rms(x, p["ln1"]["scale"], hp["eps"])
    raw = h @ _up(p["qkv"]["kernel"], fp8)                     # [S, 3 H D]
    w = _up(p["conv"]["kernel"], side8)                        # [taps, C]
    # tap j meets the token taps - 1 - j steps back; zeros before token 0
    y = raw * w[taps - 1]
    if "no_conv" not in variant:
        for back in range(1, taps):
            shifted = jnp.pad(raw, ((back, 0), (0, 0)))[:S]
            y = y + shifted * w[taps - 1 - back]
    q, k, v = (a.reshape(S, H, D) for a in jnp.split(jax.nn.silu(y), 3, -1))
    if "no_l2" not in variant:
        q = q / jnp.sqrt(jnp.sum(q * q, -1, keepdims=True) + hp["l2_eps"])
        k = k / jnp.sqrt(jnp.sum(k * k, -1, keepdims=True) + hp["l2_eps"])
    q = q / jnp.sqrt(float(D))
    low = (h @ _up(p["f_a"]["kernel"], side8)) @ _up(p["f_b"]["kernel"],
                                                     side8)
    g = -jnp.exp(_up(p["A_log"], side8))[None, :, None] * jax.nn.softplus(
        (low + _up(p["dt_bias"], side8)).reshape(S, H, D))
    a = jnp.ones_like(g) if "no_decay" in variant else jnp.exp(g)
    b = jax.nn.sigmoid(h @ _up(p["b"]["kernel"], fp8))         # [S, H]
    if "no_write_gate" in variant:
        b = jnp.ones_like(b)

    def token(state, t):
        qt, kt, vt, at, bt = t                                 # [H, D], [H]
        decayed = at[:, :, None] * state                       # [H, Dk, Dv]
        held = jnp.einsum("hkv,hk->hv", decayed, kt)
        state = decayed + bt[:, None, None] * kt[:, :, None] \
            * (vt - held)[:, None, :]
        if "state_bf16" in variant:
            state = jax.lax.reduce_precision(state, 8, 7)
        return state, jnp.einsum("hkv,hk->hv", state, qt)

    _, o = jax.lax.scan(token, jnp.zeros((H, D, D), jnp.float32),
                        (q, k, v, a, b))                       # [S, H, D]
    o = _rms(o, p["o_norm"]["scale"], hp["eps"])
    if "no_out_gate" not in variant:
        gate = (h @ _up(p["g_a"]["kernel"], fp8)) @ _up(p["g_b"]["kernel"],
                                                        fp8)
        o = o * jax.nn.sigmoid(gate.reshape(S, H, D))
    return x + o.reshape(S, H * D) @ _up(p["attn_out"]["kernel"], fp8)


def _rotate(x, pos):
    """Interleaved-pair rotary, theta 10000: only the "rotated" fault."""
    d = x.shape[-1]
    f = 10000.0 ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos.astype(jnp.float32)[:, None] * f
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


def _latent_attention(x, p, hp, variant, fp8):
    S = x.shape[0]
    H, d_n, d_r, d_v = hp["n_heads"], hp["d_n"], hp["d_r"], hp["d_v"]
    pos = jnp.arange(S)
    h = _rms(x, p["ln1"]["scale"], hp["eps"])
    ckv = h @ _up(p["kv_a"]["kernel"], fp8)
    r = ckv.shape[1] - d_r
    c_kv = _rms(ckv[:, :r], p["kv_a_norm"]["scale"], hp["eps"])
    k_r = ckv[:, r:]
    if "rotated" in variant:
        k_r = _rotate(k_r, pos)
    seen = pos[:, None] >= pos[None, :]
    w_q = p["q"]["kernel"].reshape(-1, H, d_n + d_r)
    w_o = p["attn_out"]["kernel"].reshape(H, d_v, -1)
    scale = (d_n + d_r) ** -0.5

    def one_head(acc, j):
        def at(w, axis):
            return jax.lax.dynamic_index_in_dim(w, j, axis, keepdims=False)
        q = h @ _up(at(w_q, 1), fp8)                           # [S, d_n+d_r]
        q_r = _rotate(q[:, d_n:], pos) if "rotated" in variant else q[:, d_n:]
        k_n = c_kv @ _up(at(p["k_up"]["kernel"], 0), fp8).T    # [S, d_n]
        v = c_kv @ _up(at(p["v_up"]["kernel"], 0), fp8)        # [S, d_v]
        s = (q[:, :d_n] @ k_n.T + q_r @ k_r.T) * scale
        s = jnp.where(seen, s, -jnp.inf)
        o = jax.nn.softmax(s, axis=-1) @ v
        return acc + o @ _up(at(w_o, 0), fp8), None

    out, _ = jax.lax.scan(one_head, jnp.zeros_like(x), jnp.arange(H))
    return x + out


def _dense_ffn(x, p, hp, fp8):
    h = _rms(x, p["ln2"]["scale"], hp["eps"])
    return x + _swiglu(h, _up(p["mlp_gate"]["kernel"], fp8),
                       _up(p["mlp_in"]["kernel"], fp8),
                       _up(p["mlp_out"]["kernel"], fp8))


def _sparse_ffn(x, p, hp, variant, fp8, forced):
    E, K = hp["num_experts"], hp["top_k"]
    first, count = hp["held"]
    if "wrong_held" in variant:
        first = (first + count) % E
    moe = p["moe"]
    h = _rms(x, p["ln2"]["scale"], hp["eps"])
    s = jax.nn.sigmoid(h @ moe["router"]["kernel"].astype(jnp.float32))
    b = moe["router"]["bias"].astype(jnp.float32)
    biased = s if "no_bias" in variant else s + b
    own = jax.lax.top_k(biased, K)[1]                          # [S, K]
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
    return x + routed + shared, (own, biased)


@functools.partial(jax.jit, static_argnames=("key", "variant", "fp8", "kind"))
def attention_layer(x, p, *, key, variant, fp8, kind):
    """One attention sublayer of ``kind`` (0 linear, 1 latent) with its own
    layer's parameters ``p``."""
    with jax.default_matmul_precision("highest"):
        fn = _latent_attention if kind else _linear_attention
        return fn(x, p, dict(key), variant, fp8)


@functools.partial(jax.jit, static_argnames=("key", "variant", "fp8"))
def ffn_layer(x, p, forced, *, key, variant, fp8):
    """One FFN sublayer: dense where ``p`` has no ``moe``. Returns (x, the
    router's (own selection, biased scores) or None)."""
    with jax.default_matmul_precision("highest"):
        if "moe" not in p:
            return _dense_ffn(x, p, dict(key), fp8), None
        return _sparse_ffn(x, p, dict(key), variant, fp8, forced)


@functools.partial(jax.jit, static_argnames=("eps", "fp8"))
def _head(x, scale, kernel, *, eps, fp8):
    with jax.default_matmul_precision("highest"):
        return _rms(x, scale, eps) @ _up(kernel, fp8)


def hp_key(hp):
    return tuple(sorted((k, tuple(v) if isinstance(v, (list, tuple)) else v)
                        for k, v in hp.items()))


def layer_params(params, hp, l):
    """(attention parameters, FFN parameters) of layer ``l``: its row in
    its kind's attention stack and in its FFN shape's stack."""
    kinds, nd = hp["kinds"], hp["n_dense"]
    kind = kinds[l]
    own = sum(1 for k in kinds[:l] if k == kind)
    attn = jax.tree_util.tree_map(lambda a: a[own],
                                  params["mla" if kind else "kda"])
    stack, row = ("dense_block", l) if l < nd else ("block", l - nd)
    return attn, jax.tree_util.tree_map(lambda a: a[row], params[stack])


def logits(params, tokens, hp, forced=None, variant=(), fp8=False):
    """tokens ``[S]`` -> (logits ``[S, V]`` float32, route)."""
    tokens = jnp.asarray(tokens, jnp.int32)
    kinds, nd = tuple(hp["kinds"]), hp["n_dense"]
    n_sparse = len(kinds) - nd
    shape = (n_sparse, tokens.shape[0], hp["top_k"])
    forced = -jnp.ones(shape, jnp.int32) if forced is None \
        else jnp.asarray(forced, jnp.int32)
    assert forced.shape == shape, (forced.shape, shape)
    key, variant, fp8 = hp_key(hp), frozenset(variant), bool(fp8)
    x = params["wte"]["embedding"][tokens].astype(jnp.float32)
    sel, biased = [], []
    for l, kind in enumerate(kinds):
        attn, ffn = layer_params(params, hp, l)
        x = attention_layer(x, attn, key=key, variant=variant, fp8=fp8,
                            kind=int(kind))
        x, route = ffn_layer(x, ffn, forced[max(l - nd, 0)], key=key,
                             variant=variant, fp8=fp8)
        if route is not None:
            sel.append(route[0])
            biased.append(route[1])
    out = _head(x, params["ln_f"]["scale"], params["lm_head"]["kernel"],
                eps=hp["eps"], fp8=fp8)
    sel = jnp.stack(sel)
    return out, {"sel": sel, "biased": jnp.stack(biased),
                 "group": jnp.zeros(sel.shape[:2] + (1,), jnp.float32)}
