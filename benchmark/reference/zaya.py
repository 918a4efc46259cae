"""Plain reference for the ``zaya`` decoder (ZAYA1-8B) as one pipeline stage
holds it: float32 ``jax.numpy``, ``jax.default_matmul_precision("highest")``,
no kernels, no cache, no chunking, no batching, no code shared with the
program. A full forward over one token sequence; the convolutions are
explicit shifts and sums over the whole sequence. Computed a layer, a head
and an expert at a time, every weight matrix upcast when it is used, and the
head a slice of the vocabulary at a time, so that 6,144 tokens fit beside
bf16 weights of 8.7 GiB.

The equations (``x: [S, d]``, the router stream ``r_prev: [S, R]``, zeros
under layer 0; RMSNorm eps from ``hp``; ``shift(a)_t = a_{t-1}``, zeros at
``t = 0``):

- attention (CCA): ``h = RMSNorm(x; g1)``; ``[q~ | k~ | v1 | v2] = h W``
  with ``q~`` ``[S, H, Dh]``, ``k~`` ``[S, Hkv, Dh]``, ``v1``, ``v2`` ``[S,
  Dh]``, no bias. With ``u = [q~ | k~]`` (C channels): ``c0 = w0[1] * u +
  w0[0] * shift(u) + b0`` (depthwise, 2 taps); ``c1_g = c0_g W1[1]_g +
  shift(c0)_g W1[0]_g + b1_g`` for each of the ``H + Hkv`` heads g (a
  ``[Dh, Dh]`` matrix a tap: all of a head's channels mix). ``m_q = (q~ +
  rep(k~)) / 2`` (``k~`` repeated to its group's ``H / Hkv`` query heads),
  ``m_k`` = the mean of ``m_q`` over each group; ``q = c1[:H] + m_q``, ``k =
  c1[H:] + m_k``. ``q``, ``k`` <- ``a / sqrt(mean(a^2) + eps)`` per head
  (L2-normalised and times ``sqrt(Dh)``); ``k`` further times
  ``exp(temp)``, a scalar a KV head. Rotary on the first ``rd`` channels of
  each head, theta from ``hp``, half-split pairs ``(i, i + rd/2)``. Values:
  KV head 0 is ``v1``, KV head 1 is ``shift(v2)``. Causal softmax attention
  at scale ``Dh^-0.5``, query head j on KV head ``j // (H / Hkv)``;
  ``a = concat_j(o_j) W_o``.
- residual scaling: ``x <- (s_r * x + b_r) + (s_o * a + b_o)``.
- experts: ``h = RMSNorm(x; g2)``; ``r = h W_d + b_d + g * r_prev`` (what
  the next layer receives); ``z = W3 gelu(W2 gelu(W1 RMSNorm(r; g_r) + b1)
  + b2)`` (erf gelu), ``E + 1`` outputs; ``p = softmax(z)``; ``e =
  argmax(p + bias)``; ``y = p_e FFN_e(h)`` for ``e < E`` held here, else 0
  (``e = E`` is the skip); ``FFN_e(h) = (silu(h Wg_e) * (h Wu_e)) Wd_e``;
  ``x <- (s_r' * x + b_r') + (s_o' * y + b_o')``.
- head: ``logits = RMSNorm(x; gf) E^T`` with the embedding.

``hp`` (plain numbers, from the configuration file): ``n_heads``,
``kv_heads``, ``head_dim``, ``rotary_dim``, ``rope_theta``, ``n_layers``,
``num_experts``, ``held`` = (first, count), ``eps``.

``variant`` names deliberate faults for the controls (tests and tools):
"no_conv0", "no_conv1", "no_qk_mean", "no_value_shift", "no_temp",
"no_unit_heads", "full_rotary", "interleaved_rotary", "no_res_bias",
"no_res_scale", "no_router_state", "no_skip" (index E taken as expert 0),
"no_bias", "no_gate" (weight 1), "wrong_held", "fp8_conv" (ONLY the two
convolutions' kernels and ``temp`` rounded to float8 e4m3). ``forced`` =
int32 ``[L, S, 1]`` puts the given selection in the place of the
reference's own; -1 leaves that token free. ``fp8`` rounds every weight
matrix to float8 e4m3 and back (the precision control).

``hidden`` returns the final normed stream and the routing; ``head`` turns
rows of it into logits; ``logits`` is both, for sequences whose ``[S, V]``
fits. ``route`` holds per layer and token the reference's own selection
``sel`` ``[L, S]`` and its biased probabilities ``biased`` ``[L, S, E + 1]``
(so a disagreement can be held to a near-tie)."""

import functools

import jax
import jax.numpy as jnp

VOCAB_BLOCKS = 16


def _up(w, fp8):
    if fp8:
        w = w.astype(jnp.float8_e4m3fn)
    return w.astype(jnp.float32)


def _rms(x, scale, eps):
    x = x.astype(jnp.float32)
    y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return y * scale.astype(jnp.float32)


def _shift(a):
    """``a[t - 1]`` along axis 0, zeros at ``t = 0``."""
    return jnp.concatenate([jnp.zeros_like(a[:1]), a[:-1]], axis=0)


def _rope(x, positions, rd, theta, variant):
    """x ``[S, heads, Dh]``: rotate the first ``rd`` channels of each head,
    pair ``(i, i + rd/2)`` by ``positions * theta^(-2i/rd)``."""
    if "full_rotary" in variant:
        rd = x.shape[-1]
    half = rd // 2
    f = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / rd)
    ang = positions.astype(jnp.float32)[:, None, None] * f       # [S, 1, half]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if "interleaved_rotary" in variant:
        a, b = x[..., 0:rd:2], x[..., 1:rd:2]
        rot = jnp.stack([a * cos - b * sin, b * cos + a * sin],
                        axis=-1).reshape(x.shape[:-1] + (rd,))
    else:
        a, b = x[..., :half], x[..., half:rd]
        rot = jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)
    return jnp.concatenate([rot, x[..., rd:]], axis=-1)


def _residual(x, f, r, variant):
    s_r, b_r, s_o, b_o = (r[n].astype(jnp.float32)
                          for n in ("s_r", "b_r", "s_o", "b_o"))
    if "no_res_bias" in variant:
        b_r, b_o = 0.0, 0.0
    if "no_res_scale" in variant:
        s_r, s_o = 1.0, 1.0
    return (s_r * x + b_r) + (s_o * f + b_o)


def _attention(x, p, hp, variant, fp8):
    S = x.shape[0]
    H, Hkv, Dh = hp["n_heads"], hp["kv_heads"], hp["head_dim"]
    group, heads, C = H // Hkv, H + Hkv, (H + Hkv) * Dh
    conv8 = fp8 or "fp8_conv" in variant
    pos = jnp.arange(S)
    h = _rms(x, p["ln1"]["scale"], hp["eps"])
    down = h @ _up(p["qkv"]["kernel"], fp8)
    u, v1, v2 = down[:, :C], down[:, C:C + Dh], down[:, C + Dh:]
    # the two causal convolutions over time, as shifts and sums
    w0 = _up(p["conv0"]["kernel"], conv8)                        # [2, C]
    c0 = u if "no_conv0" in variant else \
        w0[1] * u + w0[0] * _shift(u) + p["conv0"]["bias"].astype(jnp.float32)
    w1 = _up(p["conv1"]["kernel"], conv8)                # [2, heads, Dh, Dh]
    c0h = c0.reshape(S, heads, Dh)
    c1 = c0h if "no_conv1" in variant else \
        jnp.einsum("sgi,gio->sgo", c0h, w1[1]) \
        + jnp.einsum("sgi,gio->sgo", _shift(c0h), w1[0]) \
        + p["conv1"]["bias"].astype(jnp.float32).reshape(heads, Dh)
    uh = u.reshape(S, heads, Dh)
    q_raw, k_raw = uh[:, :H], uh[:, H:]
    m_q = (q_raw + jnp.repeat(k_raw, group, axis=1)) / 2.0       # [S, H, Dh]
    m_k = jnp.mean(m_q.reshape(S, Hkv, group, Dh), axis=2)
    if "no_qk_mean" in variant:
        m_q, m_k = 0.0, 0.0
    q, k = c1[:, :H] + m_q, c1[:, H:] + m_k
    if "no_unit_heads" not in variant:
        q = q * jax.lax.rsqrt(jnp.mean(q * q, -1, keepdims=True) + hp["eps"])
        k = k * jax.lax.rsqrt(jnp.mean(k * k, -1, keepdims=True) + hp["eps"])
    if "no_temp" not in variant:
        k = k * jnp.exp(_up(p["temp"], conv8))[None, :, None]
    q = _rope(q, pos, hp["rotary_dim"], hp["rope_theta"], variant)
    k = _rope(k, pos, hp["rotary_dim"], hp["rope_theta"], variant)
    v = jnp.stack([v1, v2 if "no_value_shift" in variant else _shift(v2)],
                  axis=1)                                        # [S, 2, Dh]
    seen = pos[:, None] >= pos[None, :]
    w_o = _up(p["attn_out"]["kernel"], fp8).reshape(H, Dh, -1)

    def one_head(acc, j):
        qj = jax.lax.dynamic_index_in_dim(q, j, 1, keepdims=False)
        kj = jax.lax.dynamic_index_in_dim(k, j // group, 1, keepdims=False)
        vj = jax.lax.dynamic_index_in_dim(v, j // group, 1, keepdims=False)
        s = jnp.where(seen, (qj @ kj.T) * Dh ** -0.5, -jnp.inf)
        o = jax.nn.softmax(s, axis=-1) @ vj
        return acc + o @ jax.lax.dynamic_index_in_dim(
            w_o, j, 0, keepdims=False), None

    a, _ = jax.lax.scan(one_head, jnp.zeros_like(x), jnp.arange(H))
    return _residual(x, a, p["res1"], variant)


def _experts(x, r_prev, p, hp, variant, fp8, forced):
    E = hp["num_experts"]
    first, count = hp["held"]
    if "wrong_held" in variant:
        first = (first + count) % E
    ro = p["moe"]["router"]
    f32 = jnp.float32
    h = _rms(x, p["ln2"]["scale"], hp["eps"])
    r = h @ ro["down"]["kernel"].astype(f32) + ro["down"]["bias"].astype(f32)
    if "no_router_state" not in variant:
        r = r + ro["mix"].astype(f32) * r_prev
    z = _rms(r, ro["norm"]["scale"], hp["eps"])
    z = jax.nn.gelu(z @ ro["w1"]["kernel"].astype(f32)
                    + ro["w1"]["bias"].astype(f32), approximate=False)
    z = jax.nn.gelu(z @ ro["w2"]["kernel"].astype(f32)
                    + ro["w2"]["bias"].astype(f32), approximate=False)
    probs = jax.nn.softmax(z @ ro["w3"]["kernel"].astype(f32), axis=-1)
    biased = probs if "no_bias" in variant \
        else probs + ro["bias"].astype(f32)
    own = jnp.argmax(biased, axis=-1).astype(jnp.int32)          # [S]
    sel = jnp.where(forced[:, 0] < 0, own, forced[:, 0])
    w = jnp.take_along_axis(probs, sel[:, None], -1)[:, 0]
    if "no_gate" in variant:
        w = jnp.ones_like(w)
    if "no_skip" in variant:
        sel = jnp.where(sel == E, 0, sel)
    ex = p["moe"]["experts"]

    def one_expert(acc, e):
        wg, wu, wd = (_up(jax.lax.dynamic_index_in_dim(
            ex[n]["kernel"], e, 0, keepdims=False), fp8)
            for n in ("wg", "wi", "wo"))
        we = jnp.where(sel == first + e, w, 0.0)                  # [S]
        return acc + we[:, None] * ((jax.nn.silu(h @ wg) * (h @ wu)) @ wd), \
            None

    y, _ = jax.lax.scan(one_expert, jnp.zeros_like(h), jnp.arange(count))
    return _residual(x, y, p["res2"], variant), r, (own, biased)


@functools.partial(jax.jit, static_argnames=("key", "variant", "fp8"))
def _hidden(params, tokens, forced, *, key, variant, fp8):
    hp = dict(key)
    with jax.default_matmul_precision("highest"):
        x = params["wte"]["embedding"][tokens].astype(jnp.float32)
        R = params["block"]["moe"]["router"]["mix"].shape[-1]

        def layer(carry, xs):
            x, r = carry
            p, f = xs
            x = _attention(x, p, hp, variant, fp8)
            x, r, route = _experts(x, r, p, hp, variant, fp8, f)
            return (x, r), route

        (x, _), (sel, biased) = jax.lax.scan(
            layer, (x, jnp.zeros((x.shape[0], R), jnp.float32)),
            (params["block"], forced))
        x = _rms(x, params["ln_f"]["scale"], hp["eps"])
    return x, {"sel": sel, "biased": biased}


@functools.partial(jax.jit, static_argnames=("fp8",))
def head(params, x, fp8=False):
    """Rows ``x`` ``[n, d]`` of the final normed stream -> logits ``[n, V]``
    with the tied embedding, a slice of the vocabulary at a time."""
    emb = params["wte"]["embedding"]
    V = emb.shape[0]
    nblk = VOCAB_BLOCKS if V % VOCAB_BLOCKS == 0 else 1
    with jax.default_matmul_precision("highest"):
        def one_block(_, b):
            rows = jax.lax.dynamic_slice_in_dim(emb, b * (V // nblk),
                                                V // nblk, 0)
            return None, x @ _up(rows, fp8).T
        _, out = jax.lax.scan(one_block, None, jnp.arange(nblk))
    return out.transpose(1, 0, 2).reshape(x.shape[0], V)


def hidden(params, tokens, hp, forced=None, variant=(), fp8=False):
    """tokens ``[S]`` -> (final normed stream ``[S, d]`` float32, route)."""
    tokens = jnp.asarray(tokens, jnp.int32)
    shape = (hp["n_layers"], tokens.shape[0], 1)
    forced = -jnp.ones(shape, jnp.int32) if forced is None \
        else jnp.asarray(forced, jnp.int32)
    assert forced.shape == shape, (forced.shape, shape)
    key = tuple(sorted((k, tuple(v) if isinstance(v, (list, tuple)) else v)
                       for k, v in hp.items()))
    return _hidden(params, tokens, forced, key=key,
                   variant=frozenset(variant), fp8=bool(fp8))


def logits(params, tokens, hp, forced=None, variant=(), fp8=False):
    """tokens ``[S]`` -> (logits ``[S, V]`` float32, route)."""
    x, route = hidden(params, tokens, hp, forced, variant, fp8)
    return head(params, x, fp8=bool(fp8)), route
