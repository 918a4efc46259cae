"""Plain reference for the ``qwen3_next`` model (Qwen3-Next-80B-A3B: Gated
DeltaNet linear attention in three layers of four, output-gated softmax
attention in the fourth, softmax-routed experts and a gated shared one) as
one chip of an expert-parallel deployment holds it: float32 ``jax.numpy``,
``jax.default_matmul_precision("highest")``, no kernels, no cache, no
chunks, no code shared with the program. A full forward over one token
sequence, a layer at a time (each layer's weights upcast when it is used).

The equations (``x: [S, d]``; pre-norm; ``N(x; g) = x rsqrt(mean x^2 + eps)
(1 + g)``: the stored scale is an OFFSET from one). Layer ``l`` (0-indexed)
is linear attention where ``hp["kinds"][l] == 0`` and full attention where
it is 1; the parameter tree stacks each kind's layers by themselves, in
layer order (``gdn``, ``attn``).

- linear attention, ``h = N(x; g1)``: ``[q~ | k~ | v~ | z] = h W_qkvz`` (q~,
  k~ of ``Hk x D``, v~ and z of ``Hv x D``), ``[b | alpha] = h W_ba`` (``Hv``
  each); each channel of ``[q~ | k~ | v~]`` passes a causal convolution
  over time of ``taps`` taps, ``y_t = sum_j w_j x~_(t - taps + 1 + j)``,
  zeros before the first token, then SiLU; per key head ``q = q / sqrt(|q|^2
  + eps_l2) / sqrt(D)``, ``k = k / sqrt(|k|^2 + eps_l2)``; value head ``h``
  reads key head ``h // (Hv / Hk)``; ``beta = sigmoid(b)``; ONE decay a
  value head a token ``a = exp(-exp(A_log) softplus(alpha + dt_bias))``.
  State ``S`` ``[D keys, D values]`` a value head, float32, zeros before
  the first token: ``S' = a S``, ``S = S' + beta k (v - S'^T k)^T``, ``o =
  S^T q``, ONE TOKEN AT A TIME under ``lax.scan``. Then ``o = o rsqrt(mean
  o^2 + eps) g_o * silu(z)`` per value head (``g_o`` a PLAIN scale) and ``x
  = x + concat(o) W_o``.
- full attention: per head ``[q_j | gate_j] = h W_q`` (``2 D_h`` columns a
  head), ``k = h W_k``, ``v = h W_v`` (``Hkv`` heads); ``q_j = N(q_j; g_q)``,
  ``k = N(k; g_k)`` over the head's ``D_h``; rotate-half rotary (channel
  ``i`` pairs with ``i + r / 2``) on the first ``r = rotary_dim`` channels
  of every head; causal softmax of ``q_j . k / sqrt(D_h)``, query head ``j``
  on K/V head ``j // (H / Hkv)``, queries a block at a time; ``x = x +
  concat_j(o_j * sigmoid(gate_j)) W_o``.
- FFN, every layer, ``h = N(x; g2)``: ``p = softmax(h W_r)`` over ALL
  published experts; ``sel = top_k(p)``; ``w_e = p_e / sum_{e' in sel}
  p_e'``; ``x = x + sum_{e in sel, e held here} w_e FFN_e(h) + sigmoid(h .
  w_sg) FFN_shared(h)``. What the absent experts would add is left out.
- head: ``logits = N(x; gf) W_head`` over the vocabulary slice.

``hp`` (plain numbers, from the configuration file): ``kinds``, ``key_heads``,
``value_heads``, ``lin_dim``, ``taps``, ``l2_eps``, ``n_heads``,
``n_kv_heads``, ``head_dim``, ``rotary_dim``, ``rope_theta``,
``num_experts``, ``top_k``, ``held`` = (first, count), ``eps``.

``variant`` names deliberate faults. One for each line the published keys do
not pin (each must read NOT correct): "norm_plain_scale" (``g``, not ``1 +
g``, in every offset norm), "gated_norm_offset" (``1 + g_o``),
"no_attn_gate", "gate_before_attention" (``sigmoid(gate)`` scales the
query), "rotary_all_channels", "rotary_interleaved" (pairs ``2i, 2i + 1``),
"no_qk_norm", "no_q_scale" (the linear q without ``1 / sqrt(D)``), "no_l2",
"beta_linear" (``beta = b``), "decay_then_write_swapped" (the write, then
the decay), "key_heads_tiled" (value head ``h`` reads key head ``h % Hk``),
"shared_gate_off", "softmax_unnormalised" (``w_e = p_e``),
"sigmoid_router". For the precision controls: "state_bf16" (the recurrent
state rounded to bfloat16 after every token) and ``fp8`` (every weight
rounded to float8 e4m3's 3 mantissa bits; :func:`_up`). ``forced`` = int32
``[layers, S, top_k]`` puts the given selection in the place of the
reference's own; a row of -1 leaves that token free. ``wrong_held`` takes
the next chip's experts.

Returns ``(logits [rows, V] float32 from position ``first`` on, route)``;
``route`` holds, per layer and token, the reference's own selection ``sel``,
the router's LOGITS ``biased [layers, S, E]`` (the selection is monotone in
them; a dispute's margin is read in logits) and ``group [layers, S, 1]``
(one group; the key is there for ``drivers/serve_dots_vlm.py``'s dispute
margin)."""

import functools

import jax
import jax.numpy as jnp

QUERY_BLOCK = 512


def _up(w, fp8):
    """A weight in float32; with ``fp8`` rounded to float8 e4m3's 3 mantissa
    bits first (``lax.reduce_precision``: a pair of converts is removed by
    the compiler on the chip; the exponent keeps its 8 bits)."""
    w = w.astype(jnp.float32)
    return jax.lax.reduce_precision(w, 8, 3) if fp8 else w


def _rms(x, scale, eps, offset=True):
    x = x.astype(jnp.float32)
    y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    scale = scale.astype(jnp.float32)
    return y * (1.0 + scale if offset else scale)


def _swiglu(h, wg, wu, wd):
    return (jax.nn.silu(h @ wg) * (h @ wu)) @ wd


def _linear_attention(x, p, hp, variant, fp8):
    S = x.shape[0]
    Hk, Hv, D, taps = (hp["key_heads"], hp["value_heads"], hp["lin_dim"],
                       hp["taps"])
    offset = "norm_plain_scale" not in variant
    h = _rms(x, _up(p["ln1"]["scale"], fp8), hp["eps"], offset)
    C = (2 * Hk + Hv) * D
    qkvz = h @ _up(p["in_qkvz"]["kernel"], fp8)                # [S, C + Hv D]
    raw, z = qkvz[:, :C], qkvz[:, C:]
    ba = h @ _up(p["in_ba"]["kernel"], fp8)
    b, alpha = ba[:, :Hv], ba[:, Hv:]
    w = _up(p["conv"]["kernel"], fp8)                          # [taps, C]
    # tap j meets the token taps - 1 - j steps back; zeros before token 0
    y = raw * w[taps - 1]
    for back in range(1, taps):
        y = y + jnp.pad(raw, ((back, 0), (0, 0)))[:S] * w[taps - 1 - back]
    y = jax.nn.silu(y)
    q = y[:, :Hk * D].reshape(S, Hk, D)
    k = y[:, Hk * D:2 * Hk * D].reshape(S, Hk, D)
    v = y[:, 2 * Hk * D:].reshape(S, Hv, D)
    if "no_l2" not in variant:
        q = q / jnp.sqrt(jnp.sum(q * q, -1, keepdims=True) + hp["l2_eps"])
        k = k / jnp.sqrt(jnp.sum(k * k, -1, keepdims=True) + hp["l2_eps"])
    if "no_q_scale" not in variant:
        q = q / jnp.sqrt(float(D))
    beta = b if "beta_linear" in variant else jax.nn.sigmoid(b)
    a = jnp.exp(-jnp.exp(_up(p["A_log"], fp8)) * jax.nn.softplus(
        alpha + _up(p["dt_bias"], fp8)))                       # [S, Hv]
    # which key head a value head reads
    heads = jnp.arange(Hv)
    of = heads % Hk if "key_heads_tiled" in variant else heads // (Hv // Hk)

    def token(state, t):
        qt, kt, vt, at, bt = t                     # [Hk, D] x2, [Hv, D], [Hv]
        qt, kt = qt[of], kt[of]                                # [Hv, D]
        if "decay_then_write_swapped" in variant:
            held = jnp.einsum("hkv,hk->hv", state, kt)
            state = at[:, None, None] * (
                state + bt[:, None, None] * kt[:, :, None]
                * (vt - held)[:, None, :])
        else:
            state = at[:, None, None] * state                  # [Hv, Dk, Dv]
            held = jnp.einsum("hkv,hk->hv", state, kt)
            state = state + bt[:, None, None] * kt[:, :, None] \
                * (vt - held)[:, None, :]
        if "state_bf16" in variant:
            state = jax.lax.reduce_precision(state, 8, 7)
        return state, jnp.einsum("hkv,hk->hv", state, qt)

    _, o = jax.lax.scan(token, jnp.zeros((Hv, D, D), jnp.float32),
                        (q, k, v, a, beta))                    # [S, Hv, D]
    o = _rms(o, _up(p["o_norm"]["scale"], fp8), hp["eps"],
             offset="gated_norm_offset" in variant)
    o = o * jax.nn.silu(z.reshape(S, Hv, D))
    return x + o.reshape(S, Hv * D) @ _up(p["attn_out"]["kernel"], fp8)


def _rotate(x, pos, r, theta, interleaved):
    """Rotary on the first ``r`` channels of ``x`` ``[S, H, D]``: channel
    ``i`` pairs with ``i + r / 2`` (rotate-half), or ``2i`` with ``2i + 1``
    (``interleaved``: a fault)."""
    f = theta ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    ang = pos.astype(jnp.float32)[:, None, None] * f           # [S, 1, r/2]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    head, rest = x[..., :r], x[..., r:]
    if interleaved:
        a, b = head[..., 0::2], head[..., 1::2]
        turned = jnp.stack([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).reshape(head.shape)
    else:
        a, b = head[..., :r // 2], head[..., r // 2:]
        turned = jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)
    return jnp.concatenate([turned, rest], axis=-1)


def _full_attention(x, p, hp, variant, fp8):
    S = x.shape[0]
    H, Hkv, D = hp["n_heads"], hp["n_kv_heads"], hp["head_dim"]
    offset = "norm_plain_scale" not in variant
    pos = jnp.arange(S)
    h = _rms(x, _up(p["ln1"]["scale"], fp8), hp["eps"], offset)
    qkv = h @ _up(p["qkv"]["kernel"], fp8)
    qg = qkv[:, :2 * H * D].reshape(S, H, 2 * D)
    q, gate = qg[..., :D], qg[..., D:]
    k = qkv[:, 2 * H * D:(2 * H + Hkv) * D].reshape(S, Hkv, D)
    v = qkv[:, (2 * H + Hkv) * D:].reshape(S, Hkv, D)
    if "no_qk_norm" not in variant:
        q = _rms(q, _up(p["q_norm"]["scale"], fp8), hp["eps"], offset)
        k = _rms(k, _up(p["k_norm"]["scale"], fp8), hp["eps"], offset)
    r = D if "rotary_all_channels" in variant else hp["rotary_dim"]
    turn = functools.partial(_rotate, pos=pos, r=r, theta=hp["rope_theta"],
                             interleaved="rotary_interleaved" in variant)
    q, k = turn(q), turn(k)
    if "gate_before_attention" in variant:
        q = q * jax.nn.sigmoid(gate)
    group = H // Hkv
    block = min(QUERY_BLOCK, S)
    n = -(-S // block)              # whole blocks: the last one padded
    qb = jnp.pad(q, ((0, n * block - S), (0, 0), (0, 0))).reshape(
        n, block, Hkv, group, D)
    pb = jnp.pad(pos, (0, n * block - S)).reshape(n, block)

    def queries(xs):
        qs, ps = xs                                # [c, Hkv, g, D], [c]
        s = jnp.einsum("ckgd,skd->kgcs", qs, k) / jnp.sqrt(float(D))
        s = jnp.where(ps[None, None, :, None] >= pos[None, None, None, :],
                      s, -jnp.inf)
        return jnp.einsum("kgcs,skd->ckgd", jax.nn.softmax(s, axis=-1), v)

    o = jax.lax.map(queries, (qb, pb)).reshape(n * block, H, D)[:S]
    if "no_attn_gate" not in variant and "gate_before_attention" \
            not in variant:
        o = o * jax.nn.sigmoid(gate)
    return x + o.reshape(S, H * D) @ _up(p["attn_out"]["kernel"], fp8)


def _sparse_ffn(x, p, hp, variant, fp8, forced):
    E, K = hp["num_experts"], hp["top_k"]
    first, count = hp["held"]
    if "wrong_held" in variant:
        first = (first + count) % E
    moe = p["moe"]
    h = _rms(x, _up(p["ln2"]["scale"], fp8), hp["eps"],
             "norm_plain_scale" not in variant)
    logit = h @ moe["router"]["kernel"].astype(jnp.float32)
    s = jax.nn.sigmoid(logit) if "sigmoid_router" in variant \
        else jax.nn.softmax(logit, axis=-1)
    own = jax.lax.top_k(s, K)[1]                               # [S, K]
    # a row of -1 leaves the token to the reference's own selection
    sel = jnp.where(forced[:, :1] < 0, own, forced)
    w = jnp.take_along_axis(s, sel, -1)
    if "softmax_unnormalised" not in variant:
        w = w / jnp.sum(w, -1, keepdims=True)
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
    if "shared_gate_off" not in variant:
        shared = shared * jax.nn.sigmoid(
            h @ _up(moe["shared_gate"]["kernel"], fp8))
    return x + routed + shared, (own, logit)


@functools.partial(jax.jit, static_argnames=("key", "variant", "fp8", "kind"))
def attention_layer(x, p, *, key, variant, fp8, kind):
    """One attention sublayer of ``kind`` (0 linear, 1 full) with its own
    layer's parameters ``p``."""
    with jax.default_matmul_precision("highest"):
        fn = _full_attention if kind else _linear_attention
        return fn(x, p, dict(key), variant, fp8)


@functools.partial(jax.jit, static_argnames=("key", "variant", "fp8"))
def ffn_layer(x, p, forced, *, key, variant, fp8):
    """One FFN sublayer. Returns (x, the router's (own selection,
    logits))."""
    with jax.default_matmul_precision("highest"):
        return _sparse_ffn(x, p, dict(key), variant, fp8, forced)


@functools.partial(jax.jit, static_argnames=("eps", "fp8", "offset"))
def _head(x, scale, kernel, *, eps, fp8, offset):
    with jax.default_matmul_precision("highest"):
        return _rms(x, _up(scale, fp8), eps, offset) @ _up(kernel, fp8)


def hp_key(hp):
    return tuple(sorted((k, tuple(v) if isinstance(v, (list, tuple)) else v)
                        for k, v in hp.items()))


def layer_params(params, hp, l):
    """(attention parameters, FFN parameters) of layer ``l``: its row in
    its kind's attention stack and in the FFNs' stack."""
    kinds = hp["kinds"]
    kind = kinds[l]
    own = sum(1 for k in kinds[:l] if k == kind)
    attn = jax.tree_util.tree_map(lambda a: a[own],
                                  params["attn" if kind else "gdn"])
    return attn, jax.tree_util.tree_map(lambda a: a[l], params["block"])


def logits(params, tokens, hp, forced=None, variant=(), fp8=False, first=0,
           rows=None, with_route=True):
    """tokens ``[S]`` -> (logits ``[rows, V]`` float32 of positions ``first
    .. first + rows`` (all of them by default), route; None without
    ``with_route``: 1.2 GB of router logits at 24 layers of 24,576)."""
    tokens = jnp.asarray(tokens, jnp.int32)
    kinds = tuple(hp["kinds"])
    shape = (len(kinds), tokens.shape[0], hp["top_k"])
    forced = -jnp.ones(shape, jnp.int32) if forced is None \
        else jnp.asarray(forced, jnp.int32)
    assert forced.shape == shape, (forced.shape, shape)
    key, variant, fp8 = hp_key(hp), frozenset(variant), bool(fp8)
    x = params["wte"]["embedding"][tokens].astype(jnp.float32)
    sel, scores = [], []
    for l, kind in enumerate(kinds):
        attn, ffn = layer_params(params, hp, l)
        x = attention_layer(x, attn, key=key, variant=variant, fp8=fp8,
                            kind=int(kind))
        x, route = ffn_layer(x, ffn, forced[l], key=key, variant=variant,
                             fp8=fp8)
        if with_route:
            sel.append(route[0])
            scores.append(route[1])
    rows = tokens.shape[0] - first if rows is None else rows
    out = _head(x[first:first + rows], params["ln_f"]["scale"],
                params["lm_head"]["kernel"], eps=hp["eps"], fp8=fp8,
                offset="norm_plain_scale" not in variant)
    if not with_route:
        return out, None
    sel = jnp.stack(sel)
    return out, {"sel": sel, "biased": jnp.stack(scores),
                 "group": jnp.zeros(sel.shape[:2] + (1,), jnp.float32)}
