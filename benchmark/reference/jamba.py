"""Plain reference for the ``jamba`` model (AI21-Jamba2-3B: Mamba-1
state-space mixers in most layers, un-rotated single-KV-head attention in
the rest, a dense SwiGLU behind each): float32 ``jax.numpy``,
``jax.default_matmul_precision("highest")``, no kernels, no cache, no
chunks, no code shared with the program. A full forward over one token
sequence, a layer at a time (each layer's weights upcast when it is used).

The equations (``x: [S, d]``; RMSNorm eps from ``hp``; pre-norm). Layer
``l`` (0-indexed) is a state-space mixer where ``hp["kinds"][l] == 0`` and
attention where it is 1; the parameter tree stacks each kind's mixers by
themselves, in layer order (``ssm``, ``attn``), and all FFNs in ``block``.

- state-space mixer (Mamba-1), ``u = RMSNorm(x; g1)``:
  1. ``[x~ | z] = u W_in`` (each ``Di`` wide);
  2. ``xc_t = silu(sum_j w_j x~_(t - taps + 1 + j) + b_conv)``: a causal
     convolution over time per channel, zeros before the first token,
     written as explicit shifts and sums;
  3. ``[dt | B | C] = xc W_x`` (``R``, ``N``, ``N`` wide), then ``dt =
     RMSNorm(dt; g_dt)``, ``B = RMSNorm(B; g_B)``, ``C = RMSNorm(C; g_C)``;
  4. ``delta = softplus(dt W_dt + b_dt)`` ``[S, Di]``;
  5. ``A = -exp(A_log)`` ``[Di, N]``; state ``h`` ``[Di, N]`` float32, zeros
     before the first token, ONE TOKEN AT A TIME under ``lax.scan``:
     ``h = exp(delta_t[:, None] A) h + (delta_t xc_t)[:, None] B_t[None]``,
     ``y_t = h C_t + D xc_t``;
  6. ``x = x + (y * silu(z)) W_out``.
  (The tree keeps ``A_log`` as ``[N, Di]``, the published array transposed;
  it is transposed back here.)
- attention, ``u = RMSNorm(x; g1)``: ``[q | k | v] = u W_qkv`` with ``H``
  query heads and ``Hkv`` K/V heads of ``Dh``, nothing rotated, no
  positions; causal softmax of ``q . k / sqrt(Dh)``, every query head of a
  group on its K/V head; ``x = x + concat(o) W_o``. One query head at a
  time, so that ``[S, S]`` scores fit at 12k tokens.
- FFN, every layer: ``x = x + (silu(h Wg) * (h Wu)) Wd``, ``h = RMSNorm(x;
  g2)``.
- head: ``logits = RMSNorm(x; gf) W_emb^T`` (tied), for the ``rows``
  positions from ``first`` on (all of them with ``rows`` None).

``hp`` (plain numbers, from the configuration file): ``kinds`` (a tuple, one
entry a layer), ``n_heads``, ``n_kv_heads``, ``d_state``, ``dt_rank``,
``eps``.

``variant`` names deliberate faults for the controls (tests and ``tools``):
"state_bf16" (the state rounded to bfloat16 after every token), "fp8_ssm"
(ONLY the state-space side's ``A_log``, ``W_x``, ``W_dt``, ``b_dt``, the
convolution, ``D`` and the inner norms rounded to float8 e4m3's 3 mantissa
bits), and a term dropped each: "no_dt_norm", "no_b_norm", "no_c_norm",
"no_skip" (``D = 0``), "no_conv_bias", "no_dt_bias", "no_gate" (``silu(z)``
= 1), "no_softplus", "no_conv" (the last tap alone), "rotated" (rotary on
q and k, as a llama block has it). ``fp8`` rounds every weight so."""

import functools

import jax
import jax.numpy as jnp


def _up(w, fp8):
    """A weight in float32; with ``fp8`` rounded to float8 e4m3's 3 mantissa
    bits first (``lax.reduce_precision``, not a pair of converts, which the
    chip's compiler removes; the exponent keeps its 8 bits: a weight of
    0.02 is below e4m3's smallest normal number and would lose more)."""
    w = w.astype(jnp.float32)
    return jax.lax.reduce_precision(w, 8, 3) if fp8 else w


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale


def _mamba(x, p, hp, fp8, variant):
    low = fp8 or "fp8_ssm" in variant        # the state-space side's own
    S = x.shape[0]
    N, R, eps = hp["d_state"], hp["dt_rank"], hp["eps"]
    u = _rms(x, _up(p["ln1"]["scale"], fp8), eps)
    xz = u @ _up(p["in_proj"]["kernel"], fp8)
    Di = xz.shape[-1] // 2
    xs, z = xz[:, :Di], xz[:, Di:]
    w = _up(p["conv"]["kernel"], low)                          # [taps, Di]
    taps = w.shape[0]
    conv = w[taps - 1] * xs
    if "no_conv" not in variant:
        for back in range(1, taps):
            # the token `back` steps before each, zeros before the first
            conv = conv + w[taps - 1 - back] * jnp.pad(
                xs, ((back, 0), (0, 0)))[:S]
    if "no_conv_bias" not in variant:
        conv = conv + _up(p["conv"]["bias"], low)
    xc = jax.nn.silu(conv)
    dbc = xc @ _up(p["x_proj"]["kernel"], low)
    dt, B, C = dbc[:, :R], dbc[:, R:R + N], dbc[:, R + N:]
    if "no_dt_norm" not in variant:
        dt = _rms(dt, _up(p["dt_norm"]["scale"], low), eps)
    if "no_b_norm" not in variant:
        B = _rms(B, _up(p["b_norm"]["scale"], low), eps)
    if "no_c_norm" not in variant:
        C = _rms(C, _up(p["c_norm"]["scale"], low), eps)
    delta = dt @ _up(p["dt_proj"]["kernel"], low)
    if "no_dt_bias" not in variant:
        delta = delta + _up(p["dt_proj"]["bias"], low)
    if "no_softplus" not in variant:
        delta = jax.nn.softplus(delta)
    A = -jnp.exp(_up(p["A_log"], low)).T                       # [Di, N]

    def token(h, t):
        xt, dlt, bt, ct = t
        h = jnp.exp(dlt[:, None] * A) * h \
            + (dlt * xt)[:, None] * bt[None, :]
        if "state_bf16" in variant:
            h = jax.lax.reduce_precision(h, 8, 7)
        return h, jnp.sum(h * ct[None, :], axis=1)

    _, y = jax.lax.scan(token, jnp.zeros((Di, N), jnp.float32),
                        (xc, delta, B, C))
    if "no_skip" not in variant:
        y = y + _up(p["D"], low) * xc
    if "no_gate" not in variant:
        y = y * jax.nn.silu(z)
    return x + y @ _up(p["out_proj"]["kernel"], fp8)


def _rotate(a, theta=10000.0):
    """Rotary over all channels (rotate-half): the "rotated" fault."""
    S, _, Dh = a.shape
    inv = theta ** (-jnp.arange(0, Dh, 2, dtype=jnp.float32) / Dh)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None]
    half = jnp.concatenate([-a[..., Dh // 2:], a[..., :Dh // 2]], -1)
    return a * cos + half * sin


def _attention(x, p, hp, fp8, variant):
    S, d = x.shape
    H, Hkv, eps = hp["n_heads"], hp["n_kv_heads"], hp["eps"]
    u = _rms(x, _up(p["ln1"]["scale"], fp8), eps)
    qkv = u @ _up(p["qkv"]["kernel"], fp8)
    Dh = qkv.shape[-1] // (H + 2 * Hkv)
    q = qkv[:, :H * Dh].reshape(S, H, Dh)
    k = qkv[:, H * Dh:(H + Hkv) * Dh].reshape(S, Hkv, Dh)
    v = qkv[:, (H + Hkv) * Dh:].reshape(S, Hkv, Dh)
    if "rotated" in variant:
        q, k = _rotate(q), _rotate(k)
    seen = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]

    def head(j):
        kv = j // (H // Hkv)
        scores = (q[:, j] @ k[:, kv].T) / jnp.sqrt(jnp.float32(Dh))
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return probs @ v[:, kv]
    o = jax.lax.map(head, jnp.arange(H))                       # [H, S, Dh]
    o = jnp.moveaxis(o, 0, 1).reshape(S, H * Dh)
    return x + o @ _up(p["attn_out"]["kernel"], fp8)


def _ffn(x, p, hp, fp8):
    h = _rms(x, _up(p["ln2"]["scale"], fp8), hp["eps"])
    return x + (jax.nn.silu(h @ _up(p["mlp_gate"]["kernel"], fp8))
                * (h @ _up(p["mlp_in"]["kernel"], fp8))) \
        @ _up(p["mlp_out"]["kernel"], fp8)


def _layer_of(stack, i):
    return jax.tree_util.tree_map(lambda a: a[i], stack)


@functools.partial(jax.jit, static_argnames=("hp_items", "fp8", "variant",
                                             "rows"))
def _logits(params, tokens, first, hp_items, fp8, variant, rows):
    hp = dict(hp_items)
    with jax.default_matmul_precision("highest"):
        table = _up(params["wte"]["embedding"], fp8)
        x = table[tokens]
        own = [0, 0]
        for l, kind in enumerate(hp["kinds"]):
            stack, mixer = (("ssm", _mamba), ("attn", _attention))[kind]
            x = mixer(x, _layer_of(params[stack], own[kind]), hp, fp8,
                      variant)
            own[kind] += 1
            x = _ffn(x, _layer_of(params["block"], l), hp, fp8)
        if rows is not None:
            x = jax.lax.dynamic_slice_in_dim(x, first, rows)
        x = _rms(x, _up(params["ln_f"]["scale"], fp8), hp["eps"])
        return x @ table.T


def logits(params, tokens, hp, fp8=False, variant=(), first=0, rows=None):
    """``tokens`` int32 ``[S]`` -> float32 logits ``[S, V]``, or with
    ``rows`` (a static count) those of positions ``first .. first + rows``
    (``first`` may be traced)."""
    return _logits(params, jnp.asarray(tokens, jnp.int32),
                   jnp.asarray(first, jnp.int32),
                   tuple(sorted(hp.items())), bool(fp8), tuple(variant),
                   rows)
