"""Plain reference for the ``exaone_moe`` decoder (K-EXAONE-236B-A23B) as one
chip of an expert-parallel deployment holds it: float32 ``jax.numpy``,
``jax.default_matmul_precision("highest")``, no kernels, no cache, no
batching, no code shared with the program. A full forward over one token
sequence, computed in blocks (a layer at a time, an expert at a time, a KV
head at a time, every weight matrix upcast when it is used) so that it fits
beside bf16 weights of 11 GiB.

The equations (``x: [S, d]``; RMSNorm eps from ``hp``):

- attention, layer ``l`` of kind ``sliding_attention`` (window W) or
  ``full_attention``: ``h = RMSNorm(x; g1)``; ``q = h Wq -> [S, H, Dh]``,
  ``k, v = h Wk, h Wv -> [S, Hkv, Dh]``, no biases; ``q = RMSNorm_Dh(q; gq)``,
  ``k = RMSNorm_Dh(k; gk)``; in sliding layers only, rotary over all Dh
  channels, theta from ``hp``, the rotate-half convention, at the token's
  absolute position (full layers carry NO positional encoding); query head
  ``j`` reads KV head ``j // (H / Hkv)``; scores ``q.k / sqrt(Dh)``, causal,
  and in sliding layers key ``s`` is visible to query ``t`` iff
  ``0 <= t - s < W``; softmax in float32; ``x = x + concat(heads) Wo``.
- FFN, the leading dense layers: ``h = RMSNorm(x; g2)``;
  ``x = x + (silu(h Wg) * (h Wu)) Wd``.
- FFN, sparse layers: ``s = sigmoid(h Wr)`` over ALL published experts;
  ``sel = top_k(s + b)`` (``b`` selects only); ``w_e = scale * s_e /
  sum_{e' in sel} s_e'``; ``x = x + sum_{e in sel, e held here} w_e FFN_e(h)
  + FFN_shared(h)``. What the absent experts would add is left out.
- head: ``logits = RMSNorm(x; gf) W_head`` over the vocabulary slice.

``hp`` (plain numbers, from the configuration file): ``n_heads``,
``n_kv_heads``, ``head_dim``, ``window``, ``kinds`` (one of "sliding" /
"full" per layer), ``n_dense``, ``num_experts``, ``top_k``,
``held`` = (first, count), ``routed_scale``, ``eps``, ``rope_theta``.

``variant`` names deliberate faults for the controls (tests and
``tools``): "softmax_router", "no_scale", "unnormalised", "bias_in_weights",
"no_bias", "wrong_held", "rotary_on_full", "no_qk_norm".
``forced`` = int32 ``[n_sparse, S, top_k]`` puts the given selection in the
place of the reference's own; a row of -1 leaves that token free (the
comparison's treatment of routing near-ties: ``drivers/serve_exaone_moe.py``).
``fp8`` rounds every weight matrix to float8 e4m3 and back (the precision
control).

Returns ``(logits [S, V] float32, route)``; ``route`` holds, per sparse
layer and token, the reference's own selection ``sel [n_sparse, S, top_k]``
and its biased scores ``biased [n_sparse, S, E]`` (so a disagreement with
the program's selection can be held to a near-tie)."""

import functools
import math

import jax
import jax.numpy as jnp


def _w(p, fp8):
    w = p["kernel"]
    if fp8:
        w = w.astype(jnp.float8_e4m3fn)
    return w.astype(jnp.float32)


def _rms(x, scale, eps):
    x = x.astype(jnp.float32)
    y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return y * scale.astype(jnp.float32)


def _rotate_half(x, positions, theta):
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions.astype(jnp.float32)[:, None] * inv        # [S, d/2]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _swiglu(h, wg, wu, wd):
    return (jax.nn.silu(h @ wg) * (h @ wu)) @ wd


def _attention(x, p, sliding, hp, variant, fp8):
    S = x.shape[0]
    H, Hkv, Dh = hp["n_heads"], hp["n_kv_heads"], hp["head_dim"]
    group = H // Hkv
    h = _rms(x, p["ln1"]["scale"], hp["eps"])
    qkv = h @ _w(p["qkv"], fp8)
    q = qkv[:, :H * Dh].reshape(S, H, Dh)
    k = qkv[:, H * Dh:(H + Hkv) * Dh].reshape(S, Hkv, Dh)
    v = qkv[:, (H + Hkv) * Dh:].reshape(S, Hkv, Dh)
    if "no_qk_norm" not in variant:
        q = _rms(q, p["q_norm"]["scale"], hp["eps"])
        k = _rms(k, p["k_norm"]["scale"], hp["eps"])
    pos = jnp.arange(S)
    rot = sliding if "rotary_on_full" not in variant else jnp.bool_(True)
    q = jnp.where(rot, _rotate_half(q, pos, hp["rope_theta"]), q)
    k = jnp.where(rot, _rotate_half(k, pos, hp["rope_theta"]), k)
    dist = pos[:, None] - pos[None, :]                          # t - s
    seen = dist >= 0
    seen = jnp.where(sliding, seen & (dist < hp["window"]), seen)

    def one_kv_head(j):
        qh = jax.lax.dynamic_slice_in_dim(q, j * group, group, 1)
        kh = jax.lax.dynamic_index_in_dim(k, j, 1, keepdims=False)
        vh = jax.lax.dynamic_index_in_dim(v, j, 1, keepdims=False)
        s = jnp.einsum("tgd,sd->gts", qh, kh) / math.sqrt(Dh)
        s = jnp.where(seen[None], s, -jnp.inf)
        return jnp.einsum("gts,sd->tgd", jax.nn.softmax(s, axis=-1), vh)

    out = jax.lax.map(one_kv_head, jnp.arange(Hkv))         # [Hkv, S, g, Dh]
    out = out.transpose(1, 0, 2, 3).reshape(S, H * Dh)
    return x + out @ _w(p["attn_out"], fp8)


def _dense_ffn(x, p, hp, fp8):
    h = _rms(x, p["ln2"]["scale"], hp["eps"])
    return x + _swiglu(h, _w(p["mlp_gate"], fp8), _w(p["mlp_in"], fp8),
                       _w(p["mlp_out"], fp8))


def _sparse_ffn(x, p, hp, variant, fp8, forced):
    E, K = hp["num_experts"], hp["top_k"]
    first, count = hp["held"]
    if "wrong_held" in variant:
        first = (first + count) % E
    moe = p["moe"]
    h = _rms(x, p["ln2"]["scale"], hp["eps"])
    logit = h @ moe["router"]["kernel"].astype(jnp.float32)     # [S, E]
    s = jax.nn.softmax(logit, -1) if "softmax_router" in variant \
        else jax.nn.sigmoid(logit)
    b = moe["router"]["bias"].astype(jnp.float32)
    biased = s if "no_bias" in variant else s + b
    own = jax.lax.top_k(biased, K)[1]                           # [S, K]
    # a row of -1 leaves the token to the reference's own selection
    sel = jnp.where(forced[:, :1] < 0, own, forced)
    src = biased if "bias_in_weights" in variant else s
    w = jnp.take_along_axis(src, sel, -1)
    if "unnormalised" not in variant:
        w = w / jnp.sum(w, -1, keepdims=True)
    if "no_scale" not in variant:
        w = w * hp["routed_scale"]
    ex = moe["experts"]

    def one_expert(acc, e):
        wg, wu, wd = (jax.lax.dynamic_index_in_dim(
            ex[n]["kernel"], e, 0, keepdims=False) for n in ("wg", "wi",
                                                            "wo"))
        if fp8:
            wg, wu, wd = (t.astype(jnp.float8_e4m3fn) for t in (wg, wu, wd))
        we = jnp.sum(jnp.where(sel == first + e, w, 0.0), -1)   # [S]
        y = _swiglu(h, wg.astype(jnp.float32), wu.astype(jnp.float32),
                    wd.astype(jnp.float32))
        return acc + we[:, None] * y, None

    routed, _ = jax.lax.scan(one_expert, jnp.zeros_like(h),
                             jnp.arange(count))
    sh = moe["shared"]
    shared = _swiglu(h, _w(sh["mlp_gate"], fp8), _w(sh["mlp_in"], fp8),
                     _w(sh["mlp_out"], fp8))
    return x + routed + shared, (own, biased)


@functools.partial(jax.jit, static_argnames=("key", "variant", "fp8"))
def _forward(params, tokens, forced, *, key, variant, fp8):
    hp = dict(key)
    kinds = hp["kinds"]
    n_dense = hp["n_dense"]
    with jax.default_matmul_precision("highest"):
        x = params["wte"]["embedding"][tokens].astype(jnp.float32)
        sliding = jnp.asarray([k == "sliding" for k in kinds])

        def dense_layer(x, layer):
            p, sl = layer
            x = _attention(x, p, sl, hp, variant, fp8)
            return _dense_ffn(x, p, hp, fp8), None

        def sparse_layer(x, layer):
            p, sl, f = layer
            x = _attention(x, p, sl, hp, variant, fp8)
            return _sparse_ffn(x, p, hp, variant, fp8, f)

        x, _ = jax.lax.scan(dense_layer, x,
                            (params["dense_block"], sliding[:n_dense]))
        x, (sel, biased) = jax.lax.scan(
            sparse_layer, x, (params["block"], sliding[n_dense:], forced))
        x = _rms(x, params["ln_f"]["scale"], hp["eps"])
        logits = x @ _w(params["lm_head"], fp8)
    return logits, {"sel": sel, "biased": biased}


def logits(params, tokens, hp, forced=None, variant=(), fp8=False):
    """tokens ``[S]`` -> (logits ``[S, V]`` float32, route)."""
    tokens = jnp.asarray(tokens, jnp.int32)
    n_sparse = len(hp["kinds"]) - hp["n_dense"]
    shape = (n_sparse, tokens.shape[0], hp["top_k"])
    forced = -jnp.ones(shape, jnp.int32) if forced is None \
        else jnp.asarray(forced, jnp.int32)
    assert forced.shape == shape, (forced.shape, shape)
    key = tuple(sorted((k, tuple(v) if isinstance(v, (list, tuple)) else v)
                       for k, v in hp.items()))
    return _forward(params, tokens, forced, key=key,
                    variant=frozenset(variant), fp8=bool(fp8))
