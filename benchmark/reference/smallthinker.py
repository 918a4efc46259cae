"""Plain reference for the SmallThinker decoder (SmallThinker-21BA3B-Instruct)
as the first pipeline stage holds it (every expert, the whole vocabulary):
float32 ``jax.numpy``, ``jax.default_matmul_precision("highest")``, no
kernels, no cache, no batching, no code shared with the program. A full
forward over one token sequence, computed in blocks (a layer at a time, an
expert at a time, a block of queries and a KV head at a time, every weight
matrix upcast when it is used, the head only at the positions asked for) so
that 12 layers at 16,384 tokens fit beside bf16 weights of 10.4 GiB.

The equations (``x_l: [S, d]``, every layer alike; RMSNorm eps from ``hp``;
no bias anywhere):

- router, FIRST, on the layer's raw input: ``z = x_l W_r`` ``[S, E]``;
  ``sel = top_k(z)``; ``w = softmax(z[sel])`` (a softmax over the chosen
  ones alone: no bias, no scale, no shared expert).
- attention: ``a = RMSNorm(x_l; g1)``; ``q = a Wq -> [S, H, Dh]``, ``k, v
  -> [S, Hkv, Dh]``, no q/k norm; in ``sliding`` layers only, rotary over
  all Dh channels, theta from ``hp``, the rotate-half convention, at the
  token's absolute position, and key ``s`` is visible to query ``t`` iff
  ``0 <= t - s < W``; ``full`` layers carry NO positional encoding and see
  every key ``s <= t``; query head ``j`` reads KV head ``j // (H / Hkv)``;
  scores ``q.k / sqrt(Dh)``, softmax in float32; ``x' = x_l + concat(heads)
  Wo``.
- experts: ``h = RMSNorm(x'; g2)``; ``x_{l+1} = x' + sum_{e in sel} w_e
  (relu(h Wg_e) * (h Wu_e)) Wd_e``.
- head: ``logits = RMSNorm(x_L; gf) W_head``.

``hp`` (plain numbers, from the configuration file): ``n_heads``,
``n_kv_heads``, ``head_dim``, ``window``, ``kinds`` (one of "sliding" /
"full" per layer), ``num_experts``, ``top_k``, ``eps``, ``rope_theta``.

``variant`` names deliberate faults for the controls (tests and ``tools``):
"router_after_attention" (routes on ``h``), "router_normed" (routes on
``a``), "silu" (the gate's activation), "softmax_all_unnormalised" (weights
are the chosen entries of a softmax over all E), "rotary_on_full",
"no_rotary", "window_off_by_one" (``0 <= t - s <= W``), "qk_norm"
(unit-scale RMSNorm over each head's q and k).
``forced`` = int32 ``[L, S, top_k]`` puts the given selection in the place
of the reference's own; a row of -1 leaves that token free.
``fp8`` rounds every weight matrix to float8 e4m3 and back (the precision
control). ``at`` = (first, n): the head runs at positions ``first ...
first + n - 1`` only (``n`` static; all ``S`` positions without it).

Returns ``(logits [n, V] float32, route)``; ``route`` holds, per layer and
token, the reference's own selection ``sel [L, S, top_k]`` and its router
logits ``z [L, S, E]`` (so a disagreement with the program's selection can
be held to a near-tie)."""

import functools
import math

import jax
import jax.numpy as jnp

QUERY_BLOCK = 512       # queries attended at a time (S a multiple, or S)


def _w(p, fp8):
    w = p["kernel"]
    if fp8:
        w = w.astype(jnp.float8_e4m3fn)
    return w.astype(jnp.float32)


def _rms(x, scale, eps):
    x = x.astype(jnp.float32)
    y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return y if scale is None else y * scale.astype(jnp.float32)


def _head(x, p, fp8):
    """``x W_head`` a slice of the vocabulary at a time: the whole head in
    float32 is 1.56 GB, more than the served state leaves free."""
    V = p["kernel"].shape[1]
    n = 16 if V % 16 == 0 and V > 16384 else 1

    def one_slice(i):
        w = jax.lax.dynamic_slice_in_dim(p["kernel"], i * (V // n), V // n, 1)
        return x @ _w({"kernel": w}, fp8)

    out = jax.lax.map(one_slice, jnp.arange(n))             # [n, S, V/n]
    return out.transpose(1, 0, 2).reshape(x.shape[0], V)


def _rotate_half(x, positions, theta):
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions.astype(jnp.float32)[:, None] * inv        # [S, d/2]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _attention(x, a, p, sliding, hp, variant, fp8):
    """``x + Attn(a)``, ``a`` the normed input."""
    S = x.shape[0]
    H, Hkv, Dh = hp["n_heads"], hp["n_kv_heads"], hp["head_dim"]
    group = H // Hkv
    qkv = a @ _w(p["qkv"], fp8)
    q = qkv[:, :H * Dh].reshape(S, H, Dh)
    k = qkv[:, H * Dh:(H + Hkv) * Dh].reshape(S, Hkv, Dh)
    v = qkv[:, (H + Hkv) * Dh:].reshape(S, Hkv, Dh)
    if "qk_norm" in variant:
        q, k = _rms(q, None, hp["eps"]), _rms(k, None, hp["eps"])
    pos = jnp.arange(S)
    rot = sliding
    if "rotary_on_full" in variant:
        rot = jnp.bool_(True)
    if "no_rotary" in variant:
        rot = jnp.bool_(False)
    q = jnp.where(rot, _rotate_half(q, pos, hp["rope_theta"]), q)
    k = jnp.where(rot, _rotate_half(k, pos, hp["rope_theta"]), k)
    reach = hp["window"] + (1 if "window_off_by_one" in variant else 0)
    QB = QUERY_BLOCK if S % QUERY_BLOCK == 0 else S

    def one_query_block(b):
        qpos = b * QB + jnp.arange(QB)
        dist = qpos[:, None] - pos[None, :]                     # t - s
        seen = dist >= 0
        seen = jnp.where(sliding, seen & (dist < reach), seen)
        qb = jax.lax.dynamic_slice_in_dim(q, b * QB, QB, 0)    # [QB, H, Dh]

        def one_kv_head(j):
            qh = jax.lax.dynamic_slice_in_dim(qb, j * group, group, 1)
            kh = jax.lax.dynamic_index_in_dim(k, j, 1, keepdims=False)
            vh = jax.lax.dynamic_index_in_dim(v, j, 1, keepdims=False)
            s = jnp.einsum("tgd,sd->gts", qh, kh) / math.sqrt(Dh)
            s = jnp.where(seen[None], s, -jnp.inf)
            return jnp.einsum("gts,sd->tgd", jax.nn.softmax(s, axis=-1), vh)

        out = jax.lax.map(one_kv_head, jnp.arange(Hkv))    # [Hkv, QB, g, Dh]
        return out.transpose(1, 0, 2, 3).reshape(QB, H * Dh)

    out = jax.lax.map(one_query_block, jnp.arange(S // QB))
    return x + out.reshape(S, H * Dh) @ _w(p["attn_out"], fp8)


def _experts(x, h, z, p, hp, variant, fp8, forced):
    """``x + sum_k w_k E_sel_k(h)`` with the selection from the router
    logits ``z`` [S, E]. Returns (x, own selection)."""
    K = hp["top_k"]
    own = jax.lax.top_k(z, K)[1]                                # [S, K]
    # a row of -1 leaves the token to the reference's own selection
    sel = jnp.where(forced[:, :1] < 0, own, forced)
    if "softmax_all_unnormalised" in variant:
        w = jnp.take_along_axis(jax.nn.softmax(z, -1), sel, -1)
    else:
        w = jax.nn.softmax(jnp.take_along_axis(z, sel, -1), -1)
    act = jax.nn.silu if "silu" in variant else jax.nn.relu
    ex = p["moe"]["experts"]

    def one_expert(acc, e):
        wg, wu, wd = (jax.lax.dynamic_index_in_dim(
            ex[n]["kernel"], e, 0, keepdims=False) for n in ("wg", "wi",
                                                            "wo"))
        if fp8:
            wg, wu, wd = (t.astype(jnp.float8_e4m3fn) for t in (wg, wu, wd))
        wg, wu, wd = (t.astype(jnp.float32) for t in (wg, wu, wd))
        we = jnp.sum(jnp.where(sel == e, w, 0.0), -1)           # [S]
        return acc + we[:, None] * ((act(h @ wg) * (h @ wu)) @ wd), None

    routed, _ = jax.lax.scan(one_expert, jnp.zeros_like(h),
                             jnp.arange(hp["num_experts"]))
    return x + routed, own


def _layer(x, p, sliding, forced, hp, variant, fp8):
    wr = p["moe"]["router"]["kernel"].astype(jnp.float32)
    a = _rms(x, p["ln1"]["scale"], hp["eps"])
    x2 = _attention(x, a, p, sliding, hp, variant, fp8)
    h = _rms(x2, p["ln2"]["scale"], hp["eps"])
    reads = h if "router_after_attention" in variant \
        else a if "router_normed" in variant else x
    z = reads @ wr
    y, own = _experts(x2, h, z, p, hp, variant, fp8, forced)
    return y, (own, z)


@functools.partial(jax.jit, static_argnames=("key", "variant", "fp8", "n"))
def _forward(params, tokens, forced, first, *, key, variant, fp8, n):
    hp = dict(key)
    with jax.default_matmul_precision("highest"):
        x = params["wte"]["embedding"][tokens].astype(jnp.float32)
        sliding = jnp.asarray([k == "sliding" for k in hp["kinds"]])

        def layer(x, xs):
            p, sl, f = xs
            return _layer(x, p, sl, f, hp, variant, fp8)

        x, (sel, z) = jax.lax.scan(layer, x,
                                   (params["block"], sliding, forced))
        x = jax.lax.dynamic_slice_in_dim(x, first, n, 0)
        x = _rms(x, params["ln_f"]["scale"], hp["eps"])
        logits = _head(x, params["lm_head"], fp8)
    return logits, {"sel": sel, "z": z}


def logits(params, tokens, hp, forced=None, variant=(), fp8=False, at=None):
    """tokens ``[S]`` -> (logits ``[n, V]`` float32, route)."""
    tokens = jnp.asarray(tokens, jnp.int32)
    S = tokens.shape[0]
    shape = (len(hp["kinds"]), S, hp["top_k"])
    forced = -jnp.ones(shape, jnp.int32) if forced is None \
        else jnp.asarray(forced, jnp.int32)
    assert forced.shape == shape, (forced.shape, shape)
    first, n = (0, S) if at is None else at
    key = tuple(sorted((k, tuple(v) if isinstance(v, (list, tuple)) else v)
                       for k, v in hp.items()))
    return _forward(params, tokens, forced, jnp.int32(first), key=key,
                    variant=frozenset(variant), fp8=bool(fp8), n=int(n))
