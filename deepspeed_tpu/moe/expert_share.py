"""One chip's share of an expert-parallel sparse FFN, for serving.

The layer is TOLD which routed experts it holds (``held = (first, count)``
of the published ``num_experts``). It routes every token over ALL experts
(scores, a per-expert bias that only selects, top-k (inside the best groups
where the config has a group limit), weights scaled), computes only the
(token, expert) pairs that fall on the experts held here, and adds what
every chip computes alike: the shared expert, and the zero-compute experts'
term. The router is data of the config: ``router_scoring`` (sigmoid, or a
softmax over the router's whole width), ``router_renorm`` (whether the k
weights are renormalised over the k), ``n_zero_experts`` (outputs of the
router past the last expert: identity experts that return the token
unchanged, hold no weights and need no exchange; :func:`sparse_ffn`), as
``n_group`` is; with ``router_hidden`` it is an MLP with a state carried
from layer to layer, a top-1 choice over the experts and a skip, and no
shared expert (``route_mlp``; models/zaya.py). What the absent experts
would add is left out:
on one chip the layer runs without its exchange, and nothing stands in for
the other chips or their traffic (docs/EXPERT_SHARE.md).

Shapes are static and worst-case: ``T * k`` pair rows, sorted by expert
with the pairs on absent experts (and on padded tokens) last; no token is
dropped and there is no capacity factor. The grouped product over the held
experts' stacked weights ``[count, d, f]`` is ``impl="gmm"`` (the Mosaic
grouped matmul of ops/grouped_matmul.py, megablox's kernel: it visits only
the row tiles that hold pairs, so a decode step reads each touched expert's
weights once and a prefill chunk does the pairs' FLOPs; the layer makes the
kernel's group metadata ONCE, over its own ``count`` groups, and its three
products share it) or ``impl="ragged_dot"`` (``jax.lax.ragged_dot``,
portable; what the CPU tests run)."""

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops import grouped_matmul

# The PREFERRED (tm, tk, tn) of the grouped matmul, chosen on the chip at
# K-EXAONE's 6144 x 2048 experts, 48 and 512 tokens (PERF.md, PR 28). What a
# product is handed is :func:`grouped_tiling`'s: for k and for n the largest
# whole-lane tile within the preferred one that divides the dimension,
# because the kernel pays for a tile the shape does not fill (PERF.md, PR 44:
# Kimi-Linear's 2,304 took three 1,024-tiles for 2.25 tiles of weights and a
# float32 mask over the last k-tile of every visited expert; 3 x 768 is 12%
# faster a decode call there, 2 x 1,152 the same within 1%).
GMM_TILING = (128, 1024, 1024)
STAT_FIELDS = ("pairs_held", "pairs_total", "busiest_expert_pairs",
               "experts_touched", "layer_calls")


def has_router_state(cfg) -> bool:
    """Whether the config's router is the MLP that carries a state from
    layer to layer and has a skip output (:func:`route_mlp`)."""
    return bool(getattr(cfg, "router_hidden", 0))


def n_zero_experts(cfg) -> int:
    """Outputs of the config's router past its last expert that are
    zero-compute (identity) experts (:func:`sparse_ffn`)."""
    return int(getattr(cfg, "n_zero_experts", 0))


def has_shared_gate(cfg) -> bool:
    """Whether the config's shared expert is scaled by a sigmoid gate of
    its own, ``sigmoid(h . w_sg)`` (:func:`sparse_ffn`)."""
    return bool(getattr(cfg, "shared_expert_gate", False))


def expert_act(cfg) -> str:
    """The gate's activation in the config's experts: "silu" (SwiGLU) or
    "relu" (ReLU-gated: :func:`held_experts_ffn` then counts the zeros)."""
    return getattr(cfg, "expert_act", "silu")


def stat_fields(cfg) -> Tuple[str, ...]:
    """The counters a config's expert layers keep: ``STAT_FIELDS``; where
    the experts gate with a ReLU, ``act_zero`` (gate activations that are
    exactly 0 among the held pairs' ``f`` values) beside ``act_total``
    (those values), both counted in SIXTEENS: the counters are int32 and a
    minute of decode dispatches sees 4e9 values; where
    the router can choose no expert, ``pairs_skipped``; where it has
    zero-compute experts, ``pairs_zero`` (the pairs on them) and
    ``real_pairs_max_token`` (the most real experts any one token of a
    layer call chose, summed over the calls as ``busiest_expert_pairs``
    is); where the shared expert has a gate of its own, ``shared_gate_q8``
    (the gate's value in 256ths summed over a call's valid tokens, also in
    SIXTEENS: times 16 over 256 x tokens it is the gate's mean, and says
    whether the shared expert is on)."""
    return STAT_FIELDS + (("act_zero", "act_total") if expert_act(cfg)
                          == "relu" else ()) \
        + (("pairs_skipped",) if has_router_state(cfg) else ()) \
        + (("pairs_zero", "real_pairs_max_token") if n_zero_experts(cfg)
           else ()) \
        + (("shared_gate_q8",) if has_shared_gate(cfg) else ())


def route(h, router: Dict, k: int, scaling: float, n_group: int = 1,
          topk_group: int = 1, scoring: str = "sigmoid",
          renorm: bool = True) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """h ``[T, d]`` -> (selected outputs ``[T, k]`` int32, their weights
    ``[T, k]`` float32) over the router's whole width (the kernel's: the
    experts and, behind them, any zero-compute experts). Scores and
    selection in float32 at full matmul precision: a rounded score swaps
    near-tied experts. ``scoring`` "sigmoid" scores each output alone,
    "softmax" over all of them; the weights are the chosen scores,
    renormalised over the k with ``renorm``, times ``scaling``. With
    ``n_group`` > 1 the experts lie in that many equal groups in order,
    and a token selects only inside the ``topk_group`` groups whose two
    best biased scores sum highest (DeepSeek-V3's ``noaux_tc``)."""
    logits = jnp.dot(h.astype(jnp.float32),
                     router["kernel"].astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits) if scoring == "sigmoid" \
        else jax.nn.softmax(logits, axis=-1)
    # the bias only selects; a router without one selects by its scores
    biased = scores + router["bias"].astype(jnp.float32) \
        if "bias" in router else scores
    if n_group > 1:
        T, E = biased.shape
        per = biased.reshape(T, n_group, E // n_group)
        best2 = jnp.sum(jax.lax.top_k(per, 2)[0], axis=-1)      # [T, G]
        _, groups = jax.lax.top_k(best2, topk_group)
        kept = jnp.any(groups[:, :, None] == jnp.arange(n_group), axis=1)
        biased = jnp.where(kept[:, :, None], per, -jnp.inf).reshape(T, E)
    _, sel = jax.lax.top_k(biased, k)
    w = jnp.take_along_axis(scores, sel, axis=-1)
    if renorm:
        w = w / jnp.sum(w, axis=-1, keepdims=True) * scaling
    else:
        w = w * scaling
    return sel.astype(jnp.int32), w


def route_by_config(h, router: Dict, cfg):
    """:func:`route` with the config's data: the ONE statement of which
    linear router a config runs, for a layer that routes on its FFN's
    input (:func:`sparse_ffn`) and for one that routes on the layer's
    input before attention (inference/hybrid.py ``router_reads``)."""
    return route(h, router, cfg.moe_k, cfg.routed_scaling,
                 getattr(cfg, "n_group", 1), getattr(cfg, "topk_group", 1),
                 getattr(cfg, "router_scoring", "sigmoid"),
                 getattr(cfg, "router_renorm", True))


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale.astype(jnp.float32)


def route_mlp(h, router: Dict, r_prev, eps: float):
    """The router that is an MLP with a state (ZAYA1). h ``[T, d]``,
    ``r_prev`` ``[T, R]`` float32 (the layer below's state; zeros under the
    first layer) -> (selected ``[T, 1]`` int32, its weight ``[T, 1]``
    float32, this layer's state ``[T, R]`` for the layer above).

    ``r = h W_d + b_d + mix * r_prev`` is the state handed on; the logits
    are a three-matrix gelu MLP of RMSNorm(r), with ONE output more than
    there are experts: index ``E`` is the skip, a pair on no expert. The
    choice is the argmax of softmax + the selection bias, and the weight is
    the chosen probability itself, unscaled. float32 at full matmul
    precision throughout, as :func:`route`."""
    hi = jax.lax.Precision.HIGHEST
    f32 = jnp.float32

    def lin(x, p):
        y = jnp.dot(x, p["kernel"].astype(f32), precision=hi)
        return y + p["bias"].astype(f32) if "bias" in p else y

    with jax.named_scope("router_down"):
        r = lin(h.astype(f32), router["down"])
    with jax.named_scope("router_mix"):
        r = r + router["mix"].astype(f32) * r_prev
    with jax.named_scope("router_mlp"):
        z = _rms(r, router["norm"]["scale"], eps)
        z = jax.nn.gelu(lin(z, router["w1"]), approximate=False)
        z = jax.nn.gelu(lin(z, router["w2"]), approximate=False)
        probs = jax.nn.softmax(lin(z, router["w3"]), axis=-1)
        sel = jnp.argmax(probs + router["bias"].astype(f32), axis=-1)
        w = jnp.take_along_axis(probs, sel[:, None], axis=-1)
    return sel[:, None].astype(jnp.int32), w, r


def _whole_tile(dim: int, pref: int) -> int:
    """The largest multiple of 128 lanes that divides ``dim`` exactly and
    is no larger than ``pref`` (so a tile never outgrows the VMEM the
    preferred one was measured at); ``min(pref, dim)`` where no multiple
    of 128 does (the kernel then masks the ragged last tile)."""
    top = min(pref, dim)
    return next((t for t in range(top // 128 * 128, 0, -128)
                 if dim % t == 0), top)


def grouped_tiling(k: int, n: int) -> Tuple[int, int, int]:
    """The (tm, tk, tn) handed to the kernel for a product ``[M, k] x [G, k,
    n]``: ``GMM_TILING``'s row tile, and for each of ``k`` and ``n`` the
    largest whole-lane tile that divides it and is not above
    ``GMM_TILING``'s. ``GMM_TILING`` itself wherever it divides both."""
    tm, tk, tn = GMM_TILING
    return tm, _whole_tile(k, tk), _whole_tile(n, tn)


def ragged_tile_share(k: int, n: int, tiling: Tuple[int, int, int]) -> float:
    """The share of the weight-tile area a product fetches for one
    ``[k, n]`` matrix that lies outside the matrix: 0 where ``tk`` divides
    ``k`` and ``tn`` divides ``n``."""
    _, tk, tn = tiling
    return 1.0 - (k * n) / (-(-k // tk) * tk * -(-n // tn) * tn)


def _grouped(x, w, sizes, impl: str, layer=None, metadata=None):
    """Rows of ``x`` [M, a], grouped by ``sizes`` [G], times the ``G``
    matrices of ``w`` [layers * G, a, b] that are ``layer``'s (``w`` [G, a,
    b] and layer None: the first and only). Rows past the groups come back
    undefined. "gmm" reads ``w`` in place behind ``layer * G`` and takes
    ``metadata`` (ops/grouped_matmul.py ``group_metadata`` of ``sizes``;
    made here where the caller has none to share); "ragged_dot" takes the
    layer's slice."""
    G = sizes.shape[0]
    base = 0 if layer is None else layer * G
    if impl == "ragged_dot":
        if layer is not None:
            w = jax.lax.dynamic_slice_in_dim(w, base, G)
        return jax.lax.ragged_dot(x, w, sizes)
    tiling = grouped_tiling(w.shape[1], w.shape[2])
    if metadata is None:
        metadata = grouped_matmul.group_metadata(sizes, x.shape[0],
                                                 tiling[0])
    return grouped_matmul.gmm(x, w, metadata, base, tiling)


def held_experts_ffn(h, experts: Dict, sel, w, held: Tuple[int, int],
                     impl: str, valid: Optional[jnp.ndarray] = None,
                     layer=None, act: str = "silu"):
    """The routed part of the layer that THIS chip's experts give.

    h ``[T, d]``; ``experts``: ``wg`` / ``wi`` / ``wo`` kernels stacked
    over the held experts; ``sel`` / ``w`` from :func:`route`; ``valid``
    ``[T]`` bool marks real tokens (a padded lane or an idle slot meets no
    expert). With ``layer`` (a traced index) the kernels are those of ALL
    sparse layers, ``[layers * count, ...]``, and the layer's experts are
    groups ``layer * count ...`` of them: a layer loop hands the kernel
    the whole stack, because slicing a layer's experts out for a custom
    call copies them (1.2 GB a layer a dispatch: PERF.md, PR 28), and the
    kernel's weight block adds ``layer * count`` to a tile's group. The
    group metadata is made here, once, from the layer's own ``count``
    sizes, for all three products (made over the stack's groups it cost
    more than a product's kernel where the experts are small: PERF.md,
    PR 57). ``act`` is the gate's activation (:func:`expert_act`).
    Returns (``[T, d]`` in h's dtype, int32 stats in the order of
    ``STAT_FIELDS``, with "relu" followed by ``act_zero`` and ``act_total``
    in sixteens: :func:`stat_fields`)."""
    T, d = h.shape
    K = sel.shape[1]
    first, count = held
    local = sel - first
    on = jnp.logical_and(local >= 0, local < count)
    every = jnp.ones((T, 1), bool) if valid is None else valid[:, None]
    on = jnp.logical_and(on, every)
    # pairs by held expert, everything else behind them
    key = jnp.where(on, local, count).reshape(-1)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    # a compare and a sum over [pairs, count]: a scatter-add takes the
    # pairs one after the other on a TPU (45 us for 5,120: PERF.md, PR 57)
    sizes = jnp.sum(key[:, None] == jnp.arange(count, dtype=jnp.int32),
                    axis=0, dtype=jnp.int32)
    M = T * K
    pad = -M % GMM_TILING[0] if impl == "gmm" else 0
    tok = jnp.pad(order // K, (0, pad))
    x = h[tok]                                                # [M', d]
    wg, wi, wo = (experts[n]["kernel"].astype(h.dtype)
                  for n in ("wg", "wi", "wo"))
    # ONE metadata for the layer's three products, over its own groups
    meta = grouped_matmul.group_metadata(sizes, x.shape[0], GMM_TILING[0]) \
        if impl == "gmm" else None
    gate = getattr(jax.nn, act)(_grouped(x, wg, sizes, impl, layer, meta))
    y = _grouped(gate * _grouped(x, wi, sizes, impl, layer, meta), wo,
                 sizes, impl, layer, meta)                    # [M', d]
    # back to pair order, the k-th choices of all tokens together: [K, T,
    # d] is whole tiles of tokens (``[T, K, d]`` pads K to the sublanes and
    # is written out in float32 before it is summed); the inverse of the
    # permutation by a second sort, which unlike a scatter does not take
    # the pairs one by one. A pair that met no held expert reads a row
    # nobody wrote, and is masked
    inv = jnp.argsort(order).astype(jnp.int32).reshape(T, K).T.reshape(-1)
    pairs = y[inv].reshape(K, T, d).astype(jnp.float32)
    out = jnp.sum(jnp.where(on.T[..., None], pairs * w.T[..., None], 0.0),
                  axis=0)
    stats = jnp.stack([
        jnp.sum(sizes), jnp.sum(every) * K, jnp.max(sizes),
        jnp.sum(sizes > 0), jnp.int32(1)]).astype(jnp.int32)
    if act == "relu":
        # the sorted rows before the first absent pair are the held ones
        held_row = jnp.arange(gate.shape[0]) < jnp.sum(sizes)
        zeros = jnp.sum(jnp.logical_and(gate == 0, held_row[:, None]),
                        dtype=jnp.int32)
        stats = jnp.concatenate([stats, jnp.stack(
            [zeros, jnp.sum(sizes) * gate.shape[1]]) // 16])
    return out.astype(h.dtype), stats


def zero_experts_term(h, sel, w, num_experts: int, valid=None):
    """What the zero-compute (identity) experts give ``h`` ``[T, d]``: a
    pair whose selection lies past the last expert returns the token
    unchanged, so the term is ``h * sum_k w_k [sel_k >= num_experts]``. It
    needs no weights and no exchange: every chip computes it alike, as it
    does a shared expert. Returns (``[T, d]`` in h's dtype, int32
    ``[pairs_zero, real_pairs_max_token]`` over the ``valid`` tokens)."""
    zero = sel >= num_experts
    real = ~zero
    if valid is not None:
        zero = jnp.logical_and(zero, valid[:, None])
        real = jnp.logical_and(real, valid[:, None])
    share = jnp.sum(jnp.where(zero, w, 0.0), axis=-1)             # [T] f32
    out = (h.astype(jnp.float32) * share[:, None]).astype(h.dtype)
    stats = jnp.stack([jnp.sum(zero, dtype=jnp.int32),
                       jnp.max(jnp.sum(real, axis=-1, dtype=jnp.int32))])
    return out, stats


def sparse_ffn(h, moe: Dict, cfg, impl: str, valid=None, mlp=None,
               experts=None, layer=None, state=None, routed=None):
    """Router, the held experts' routed part and what every chip computes
    alike (the shared expert, the zero-compute experts' term) for ``h``
    ``[T, d]``. ``mlp(h, p) -> [T, d]`` is the dense SwiGLU the engine
    uses; ``experts`` / ``layer``: every sparse layer's expert kernels and
    this layer's index (:func:`held_experts_ffn`), else ``moe["experts"]``.
    Data of the config, not of this code: which router runs (the linear
    one, :func:`route`, with the config's ``router_scoring`` and
    ``router_renorm``; or, with ``router_hidden``, the MLP that takes the
    layer below's ``state`` and hands its own on, :func:`route_mlp`), how
    wide it is past the experts and what a selection there means: the
    MLP's one further output is its skip, a pair on no expert, sorted last
    with the absent ones and counted; ``n_zero_experts`` further outputs
    are identity experts, pairs on no expert that STILL add their weight
    times the token (:func:`zero_experts_term`, under ``moe_zero``), and
    are counted. No shared expert where the config has none; with
    ``shared_expert_gate`` its term is times ``sigmoid(h . w_sg)``, a gate
    a token (``moe["shared_gate"]``), and the gate's sum is counted.
    ``routed`` =
    (selection, weights) made earlier in the layer, from another tensor
    than ``h`` (:func:`route_by_config` on the layer's input): no router
    runs here then. Returns
    (routed + shared + zero-compute term, selection ``[T, k]``, stats as
    :func:`stat_fields`, state)."""
    stateful = has_router_state(cfg)
    if routed is not None:
        sel, w = routed
    else:
        with jax.named_scope("moe_router"):
            if stateful:
                sel, w, state = route_mlp(h, moe["router"], state,
                                          cfg.norm_eps)
            else:
                sel, w = route_by_config(h, moe["router"], cfg)
    with jax.named_scope("moe_experts"):
        routed, stats = held_experts_ffn(
            h, moe["experts"] if experts is None else experts, sel, w,
            cfg.held, impl, valid, layer, expert_act(cfg))
        if stateful:
            skipped = sel >= cfg.num_experts
            if valid is not None:
                skipped = jnp.logical_and(skipped, valid[:, None])
            stats = jnp.concatenate(
                [stats, jnp.sum(skipped, dtype=jnp.int32)[None]])
    if n_zero_experts(cfg):
        with jax.named_scope("moe_zero"):
            zero, more = zero_experts_term(h, sel, w, cfg.num_experts, valid)
            routed = routed + zero
            stats = jnp.concatenate([stats, more])
    if not cfg.n_shared_experts:
        return routed, sel, stats, state
    with jax.named_scope("moe_shared"):
        shared = mlp(h, moe["shared"])
        if has_shared_gate(cfg):
            gate = jax.nn.sigmoid(jnp.dot(
                h.astype(jnp.float32),
                moe["shared_gate"]["kernel"].astype(jnp.float32)))  # [T, 1]
            shared = (shared.astype(jnp.float32) * gate).astype(shared.dtype)
            q8 = jnp.round(gate[:, 0] * 256.0).astype(jnp.int32)
            if valid is not None:
                q8 = jnp.where(valid, q8, 0)
            stats = jnp.concatenate([stats, jnp.sum(q8)[None] // 16])
    return routed + shared, sel, stats, state
