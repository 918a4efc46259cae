"""Paged serving blocks for a model whose layers are of two attention
kinds (models/exaone_moe.py; models/smallthinker.py is the same dialect
with other data: a window of 4,096, so a ring as long as a long prompt; no
q/k norm; a router that reads the layer's input before attention,
:func:`route_layer_input`): ``full`` layers keep their history in the
paged pool behind the slot's block table, exactly as the GPT blocks do;
``sliding`` layers can never read more than ``attn_window`` tokens back, so
each slot keeps a bounded RING of ``window_blocks`` blocks per window layer
(``[L_win, 1 + slots * ring, block, Hkv*Dh]``, block 0 of a layer its trash
block) whatever the length of its history. Position ``p`` of a slot lives
in ring block ``(p // block) % ring`` at offset ``p % block``; the slot's
ring block ids ride in its block-table row, behind the full layers'
entries, so the two serving programs and the scheduler's calls are the
same as for GPT.

One compiled body per layer SHAPE: engine._scan_layers runs the leading
dense layers and then the sparse layers, each as one scan whose body
branches on the layer's kind (``lax.cond``) for the attention only. Both
pools ride in the scan's carry and every layer writes to both: the write
that does not belong to the layer's kind lands in a trash block.

Not served with window state, and refused at construction by name: prefix
sharing and copy-on-write, the host tier, int8 pools, speculation/verify,
the fused horizon, LoRA; nor the static-cache paths (generate,
generate_fused, forward)."""

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.inference import dialect
from deepspeed_tpu.inference.paged_cache import write_chunk
from deepspeed_tpu.models.exaone_moe import layer_bases, window_blocks
from deepspeed_tpu.models.gpt import _dense, _norm
from deepspeed_tpu.moe import expert_share
from deepspeed_tpu.ops.attention.paged import NEG_INF
from deepspeed_tpu.ops.attention.rotary import apply_rotary_half


class PagedState(NamedTuple):
    """The device state of one side (K or V) of a two-kind paged cache.
    ``stats`` (K side, telemetry on): int32 ``[2, len(STAT_FIELDS)]``
    expert-layer counters, row 0 summed over prefill dispatches and row 1
    over decode dispatches, kept on the device and pulled only when
    telemetry is read. ``route`` (K side, an OUTPUT only): the selection
    ``[L_sparse, T, k]`` of the last dispatch, for the correctness check."""
    full: jnp.ndarray
    win: jnp.ndarray
    stats: Optional[jnp.ndarray] = None
    route: Optional[jnp.ndarray] = None

    def delete(self):
        for a in self:
            if a is not None:
                a.delete()


def is_hybrid(cfg) -> bool:
    return bool(getattr(cfg, "layer_kinds", ()))


def causal_band(scores, kpos, qpos, window=None):
    """Mask ``scores`` to the keys a query may see: key position <= query
    position and, with ``window``, inside ``(qpos - window, qpos]``. The one
    copy of the causal and window mask of the paged blocks."""
    ok = kpos <= qpos
    if window is not None:
        ok = jnp.logical_and(ok, kpos > qpos - window)
    return jnp.where(ok, scores, NEG_INF)


def _heads(rows, n):
    return rows.reshape(rows.shape[:-1] + (n, rows.shape[-1] // n))


def _rows(heads):
    return heads.reshape(heads.shape[:-2] + (-1,))


def _qkv(h, p, cfg, positions, sliding):
    """h ``[..., T, d]`` -> q ``[..., T, H, Dh]``, k, v ``[..., T, Hkv, Dh]``:
    RMSNorm per head on q and k where the config has it (``qk_norm``),
    rotary (rotate-half, all channels) in sliding layers only."""
    H, Hkv, Dh = cfg.n_heads, cfg.kv_heads, cfg.head_dim
    qkv = _dense(h, p["qkv"])
    q, k, v = jnp.split(qkv, [H * Dh, (H + Hkv) * Dh], axis=-1)
    q, k, v = _heads(q, H), _heads(k, Hkv), _heads(v, Hkv)
    if cfg.qk_norm:
        q = _norm(q, p["q_norm"], cfg)          # over the head's Dh values
        k = _norm(k, p["k_norm"], cfg)
    q = jnp.where(sliding, apply_rotary_half(q, positions, cfg.rope_theta), q)
    k = jnp.where(sliding, apply_rotary_half(k, positions, cfg.rope_theta), k)
    return q, k, v


def _swiglu(h, p):
    return _dense(jax.nn.silu(_dense(h, p["mlp_gate"]))
                  * _dense(h, p["mlp_in"]), p["mlp_out"])


def ffn_kind(p) -> str:
    """Which FFN a layer's parameters hold: the ONE statement of the three
    cases, for every dialect's blocks and for the layer loop
    (engine._dense_then_sparse).

    - "dense": ``mlp_gate`` / ``mlp_in`` / ``mlp_out`` beside ``ln2``, no
      ``moe``: ``x + F(ln2(x))``. The ``n_dense_layers`` leading layers of
      a config (stack ``dense_block``), and the two sublayers of a "both"
      layer.
    - "sparse": ``moe`` in the dense FFN's place (stack ``block``):
      ``x + M(ln2(x))``, the expert share of moe/expert_share.py.
    - "both": a shortcut-connected layer (stack ``block``): two sublayers
      ``a`` and ``b``, each an attention and a dense FFN with their own
      norms, and ONE ``moe`` that reads ``a``'s ``ln2`` of the stream after
      the first attention and whose result joins the stream at the END of
      the layer (inference/latent.py ``_shortcut_layer``). A config of such
      layers has ``n_dense_layers`` 0: every layer is of this kind.

    :func:`split_experts` takes the expert kernels out of ``block`` for
    "sparse" and "both" alike; a config without ``moe`` anywhere never
    calls it."""
    if "moe" not in p:
        return "dense"
    return "both" if "a" in p else "sparse"


def split_experts(params):
    """(params whose ``block`` stack lacks the expert kernels, those kernels
    flat over (layer, held expert)): the layer loop scans the first and
    closes over the second, so no layer's experts are sliced out."""
    moe = params["block"]["moe"]
    flat = {n: {"kernel": e["kernel"].reshape((-1,) + e["kernel"].shape[2:])}
            for n, e in moe["experts"].items()}
    rest = dict(params["block"],
                moe={k: v for k, v in moe.items() if k != "experts"})
    return dict(params, block=rest), flat


def route_layer_input(x, p, cfg):
    """(selection, weights) of a layer whose router reads the layer's
    INPUT ``x`` (the block's, [1, C, d] or [B, 1, d]: T = C or B tokens),
    the raw stream before ``ln1`` and attention
    (``cfg.router_reads == "layer_input"``: models/smallthinker.py), for
    :func:`_ffn` to hand the experts; None for every other layer, whose
    router reads what its experts read."""
    if getattr(cfg, "router_reads", "ffn_input") != "layer_input" \
            or ffn_kind(p) == "dense":
        return None
    with jax.named_scope("moe_router"):
        return expert_share.route_by_config(
            x.reshape(-1, x.shape[-1]), p["moe"]["router"], cfg)


def _experts(h, moe, cfg, impl, valid, aux, index, experts, routed=None):
    """The expert share on the normed ``h`` [T, d], with the dispatch's
    routing record and counters kept in ``aux``; ``routed``: the selection
    made at the top of the layer (:func:`route_layer_input`). Returns
    (M(h), aux)."""
    y, sel, stats, _ = expert_share.sparse_ffn(
        h, moe, cfg, "gmm" if impl == "pallas" else "ragged_dot",
        valid=valid, mlp=_swiglu, experts=experts, layer=index,
        routed=routed)
    aux = dict(aux, route=aux["route"].at[index].set(sel))
    if aux["stats"] is not None:
        # the busiest expert and the touched count add up over layers and
        # dispatches; their means divide by layer_calls
        aux["stats"] = aux["stats"] + stats
    return y, aux


def _ffn(x2, p, cfg, impl, valid, aux, index, experts, routed=None):
    """The block's FFN on ``x2`` [T, d] (after attention), a "dense" or a
    "sparse" one (:func:`ffn_kind`). Returns (x2 + ffn, aux)."""
    h = _norm(x2, p["ln2"], cfg)
    if ffn_kind(p) == "dense":
        with jax.named_scope("mlp"):
            return x2 + _swiglu(h, p), aux
    y, aux = _experts(h, p["moe"], cfg, impl, valid, aux, index, experts,
                      routed)
    return x2 + y, aux


def _ffn_shortcut(x1, sub, moe, cfg, impl, valid, aux, index, experts,
                  scope: str):
    """The first half's FFNs of a "both" layer (:func:`ffn_kind`) on ``x1``
    [T, d] (after the first attention): ``u = ln2(x1)`` feeds the dense
    SwiGLU, which joins the stream here (under ``scope``), AND the expert
    share, which does not yet. Returns (x1 + F(u), M(u), aux)."""
    with jax.named_scope(scope):
        h = _norm(x1, sub["ln2"], cfg)
    m, aux = _experts(h, moe, cfg, impl, valid, aux, index, experts)
    with jax.named_scope(scope), jax.named_scope("mlp"):
        return x1 + _swiglu(h, sub), m, aux


# float32 scores a prefill chunk's attention holds at once, at most: half of
# a v5e's 128 MiB of VMEM, where XLA then keeps them beside their bf16
# probabilities (at 128 MiB the probabilities of a branch of 8,192 keys went
# to HBM, 59 MB of temporaries the whole-row program did not have)
SCORE_BYTES = 64 << 20


def _score_blocks(chunk: int, scores: int) -> int:
    """Into how many equal blocks of queries (a power of two, at most the
    chunk) ``scores`` float32 values are cut to fit SCORE_BYTES."""
    n = 1
    while 4 * scores > n * SCORE_BYTES and n < chunk:
        n *= 2
    return n


def _softmax_attend(scores, v, dtype, spec):
    probs = jax.nn.softmax(scores, axis=-1).astype(dtype)
    return jnp.einsum(spec, probs, v)


def _attend(qg, kc, vc, qpos, kpos, window=None):
    """Attention of a chunk's queries ``qg`` [C, Hkv, group, Dh] at
    ``qpos`` [C] over keys ``kc`` [S, Hkv, Dh] at ``kpos`` [S], masked to
    the causal band (and ``window``), a KV head at a time: [C, group, S]
    float32 scores, an eighth of the chunk's temporaries; where even those
    pass SCORE_BYTES (a row of 16,384), a block of queries at a time. A
    window layer whose scores fit it for all heads (K-EXAONE's ring of 144
    rows) attends in one pass."""
    C, Hkv, group, Dh = qg.shape
    S = kc.shape[0]
    scale = 1.0 / np.sqrt(Dh)
    if window is not None and _score_blocks(C, C * Hkv * group * S) == 1:
        s = jnp.einsum("ckgd,skd->ckgs", qg, kc).astype(jnp.float32)
        s = causal_band(s * scale, kpos[None, None, None, :],
                        qpos[:, None, None, None], window)
        return _softmax_attend(s, vc, qg.dtype, "ckgs,skd->ckgd")
    nq = _score_blocks(C, C * group * S)

    def one_kv_head(qkv):
        qh, kh, vh = qkv

        def attend(qp):
            qb, pb = qp
            s = jnp.einsum("cgd,sd->cgs", qb, kh).astype(jnp.float32)
            s = causal_band(s * scale, kpos[None, None, :],
                            pb[:, None, None], window)
            return _softmax_attend(s, vh, qg.dtype, "cgs,sd->cgd")

        if nq == 1:
            return attend((qh, qpos))
        out = jax.lax.map(attend, (qh.reshape(nq, C // nq, group, Dh),
                                   qpos.reshape(nq, C // nq)))
        return out.reshape(C, group, Dh)

    out = jax.lax.map(one_kv_head, (
        qg.transpose(1, 0, 2, 3), kc.transpose(1, 0, 2),
        vc.transpose(1, 0, 2)))                        # [Hkv, C, g, Dh]
    return out.transpose(1, 0, 2, 3)


def block_prefill(carry, pools, table_row, positions, n_valid, p, cfg, base,
                  impl, experts):
    """One layer over a PROMPT CHUNK of one slot. ``carry`` = (x ``[1, C,
    d]``, aux); ``pools`` = (k_full, v_full, k_win, v_win), flat over
    layers; ``table_row`` = the slot's full-layer block table followed by
    its ring block ids; ``base`` = this layer's offsets and kind
    (models/exaone_moe.layer_bases); ``experts`` = every sparse layer's
    expert kernels (:func:`split_experts`). Either kind attends the
    shortest run of whole tiles that holds every key a valid query may see
    (engine._attend_occupied: the lengths follow from the table's shape
    and the ring's): a full layer over its slot's row, the chunk's rows
    already in the pool; a window layer over the ring's history (read
    BEFORE the chunk's writes reuse its oldest blocks) and the chunk
    itself, of which it keeps the last ``ring * block`` tokens."""
    from deepspeed_tpu.inference.engine import _attend_occupied
    x, aux = carry
    kf, vf, kw, vw = pools
    C = x.shape[1]
    H, Hkv, Dh = cfg.n_heads, cfg.kv_heads, cfg.head_dim
    bs = kf.shape[1]
    RB = window_blocks(cfg, bs)
    NB = table_row.shape[0] - RB
    full_row, ring = table_row[:NB], table_row[NB:]
    sliding, W = base["sliding"], cfg.attn_window
    start = positions[0]
    valid = jnp.arange(C) < n_valid

    routed = route_layer_input(x, p, cfg)
    with jax.named_scope("attn_qkv"):
        h = _norm(x, p["ln1"], cfg)
        q, k, v = _qkv(h, p, cfg, positions[None], sliding)
        q, k, v = q[0], k[0], v[0]                       # [C, heads, Dh]
    qg = q.reshape(C, Hkv, H // Hkv, Dh)

    def full_attn(kf, vf, kw, vw):
        def rows(qg, kc, vc, qpos, first):
            kpos = first + jnp.arange(kc.shape[0], dtype=jnp.int32)
            return _attend(qg, kc, vc, qpos, kpos)

        with jax.named_scope("attn_full"):
            return _attend_occupied(qg, kf, vf, full_row + base["full"],
                                    positions, n_valid, cfg, None, rows)

    def window_attn(kf, vf, kw, vw):
        # the ring as a row in logical order, oldest block first (as
        # block_decode reads it): the history lies in [0, qpos[0]) of it,
        # the chunk is not in it yet
        lo, _ = _ring_span(cfg, bs, start)
        trow = ring[(lo + jnp.arange(RB, dtype=jnp.int32)) % RB] \
            + base["win"]

        def rows(qg, hk, hv, qpos, first):
            hpos = first + jnp.arange(hk.shape[0], dtype=jnp.int32)
            kpos = jnp.concatenate([
                jnp.where(hpos < qpos[0], hpos, jnp.int32(2 ** 30)), qpos])
            return _attend(qg, jnp.concatenate([hk, k], axis=0),
                           jnp.concatenate([hv, v], axis=0), qpos, kpos, W)

        with jax.named_scope("attn_window"):
            return _attend_occupied(qg, kw, vw, trow, positions - lo * bs,
                                    0, cfg, W, rows)

    with jax.named_scope("kv_write"):
        # a sliding layer keeps nothing in the full layers' pools
        n_full = jnp.where(sliding, 0, n_valid)
        kf = write_chunk(kf, full_row, start, n_full, _rows(k), base["full"])
        vf = write_chunk(vf, full_row, start, n_full, _rows(v), base["full"])
        # the ring keeps the chunk's last ring*block tokens: each of its
        # places is written once
        off = positions % bs
        keep = jnp.logical_and(valid, positions >= start + n_valid - RB * bs)
        wblk = ring[(positions // bs) % RB]
        wblk = jnp.where(jnp.logical_and(keep, sliding), wblk, 0) \
            + base["win"]
        rings = kw.at[wblk, off].set(_rows(k)), vw.at[wblk, off].set(_rows(v))
    with jax.named_scope("paged_attn"):
        # read-only operands; the rings as the chunk found them
        attn = jax.lax.cond(sliding, window_attn, full_attn, kf, vf, kw, vw)
    with jax.named_scope("attn_out"):
        x2 = x[0] + _dense(attn.reshape(C, H * Dh), p["attn_out"])
    y, aux = _ffn(x2, p, cfg, impl, valid, aux, base["index"], experts,
                  routed)
    return (y[None], aux), (kf, vf, *rings)


def _ring_span(cfg, bs: int, lengths):
    """Where a slot's ring table starts: its first block in the slot's
    logical order, and the slot's position relative to that block."""
    lo = jnp.maximum(lengths // bs - (window_blocks(cfg, bs) - 1), 0)
    return lo, lengths - lo * bs


def decode_plans(cfg, pool, tables, lengths, active):
    """The paged kernel's two grids of one decode dispatch (ops/attention/
    paged.py ``decode_plan``), worked out once outside the layer loop: the
    full layers' over the table, the window layers' over the ring in
    logical order, both from the slots that are ``active`` and both in
    tiles of what a row of ``pool`` (the full layers' K; the rings' rows
    are the same) weighs."""
    from deepspeed_tpu.ops.attention.paged import decode_plan, pool_row_bytes
    bs, row = pool.shape[2], pool_row_bytes(pool)
    RB = window_blocks(cfg, bs)
    return (decode_plan(lengths, tables.shape[1] - RB, bs, row_bytes=row,
                        active=active),
            decode_plan(_ring_span(cfg, bs, lengths)[1], RB, bs,
                        row_bytes=row, window=cfg.attn_window,
                        active=active))


def _decode_attend(q, k_pool, v_pool, tables, lengths, window, impl, scale,
                   plan=None):
    if impl == "pallas":
        from deepspeed_tpu.ops.attention.paged import paged_decode_attention
        return paged_decode_attention(q, k_pool, v_pool, tables, lengths,
                                      scale=float(scale), window=window,
                                      plan=plan)
    from deepspeed_tpu.ops.attention.paged import paged_decode_reference
    return paged_decode_reference(q, k_pool, v_pool, tables, lengths,
                                  scale=scale, window=window)


def block_decode(carry, pools, tables, lengths, active, p, cfg, base, impl,
                 experts, plans=(None, None)):
    """One layer for ONE new token per slot. ``tables`` ``[B, NB + ring]``;
    a window layer writes the token into its ring and attends through a
    table of the ring's blocks in logical order, so the paged kernel reads
    at most the window whatever the slot's length."""
    x, aux = carry
    kf, vf, kw, vw = pools
    B = x.shape[0]
    H, Hkv, Dh = cfg.n_heads, cfg.kv_heads, cfg.head_dim
    group = H // Hkv
    bs = kf.shape[1]
    RB = window_blocks(cfg, bs)
    NB = tables.shape[1] - RB
    full_tab, ring = tables[:, :NB], tables[:, NB:]
    sliding, W = base["sliding"], cfg.attn_window
    scale = 1.0 / np.sqrt(Dh)

    routed = route_layer_input(x, p, cfg)
    with jax.named_scope("attn_qkv"):
        h = _norm(x, p["ln1"], cfg)
        q, k, v = _qkv(h, p, cfg, lengths[:, None], sliding)
        q = q.reshape(B, Hkv, group, Dh)
        k, v = k[:, 0], v[:, 0]                          # [B, Hkv, Dh]

    with jax.named_scope("kv_write"):
        cur = lengths // bs
        off = lengths % bs
        fblk = jnp.take_along_axis(
            full_tab, jnp.clip(cur, 0, NB - 1)[:, None], axis=1)[:, 0]
        ok = jnp.logical_and(active, lengths < NB * bs)
        fblk = jnp.where(jnp.logical_and(ok, ~sliding), fblk, 0) \
            + base["full"]
        kf = kf.at[fblk, off].set(_rows(k))
        vf = vf.at[fblk, off].set(_rows(v))
        wblk = jnp.take_along_axis(ring, (cur % RB)[:, None], axis=1)[:, 0]
        wblk = jnp.where(jnp.logical_and(active, sliding), wblk, 0) \
            + base["win"]
        kw = kw.at[wblk, off].set(_rows(k))
        vw = vw.at[wblk, off].set(_rows(v))

    def full_attn(_):
        with jax.named_scope("attn_full"):
            return _decode_attend(q, kf, vf, full_tab + base["full"],
                                  lengths, None, impl, scale,
                                  plans[0])

    def window_attn(_):
        with jax.named_scope("attn_window"):
            lo, rel = _ring_span(cfg, bs, lengths)
            logical = lo[:, None] + jnp.arange(RB, dtype=jnp.int32)[None]
            tabs = jnp.take_along_axis(ring, logical % RB, axis=1) \
                + base["win"]
            return _decode_attend(q, kw, vw, tabs, rel, W, impl, scale,
                                  plans[1])

    with jax.named_scope("paged_attn"):
        attn = jax.lax.cond(sliding, window_attn, full_attn, None)
    with jax.named_scope("attn_out"):
        x2 = x[:, 0] + _dense(attn.reshape(B, H * Dh), p["attn_out"])
    y, aux = _ffn(x2, p, cfg, impl, active, aux, base["index"], experts,
                  routed)
    return (y[:, None], aux), (kf, vf, kw, vw)


def new_state(cfg, num_blocks: int, block_size: int, num_slots: int, dtype):
    """Zeroed (K, V) PagedState: ``num_blocks`` blocks a full layer and,
    per window layer, each slot's ring behind block 0, its trash block."""
    rows = cfg.kv_heads * cfg.head_dim
    full = jnp.zeros((cfg.n_full_layers, num_blocks, block_size, rows), dtype)
    win = jnp.zeros((cfg.n_window_layers,
                     1 + num_slots * window_blocks(cfg, block_size),
                     block_size, rows), dtype)
    return (PagedState(full, win),
            PagedState(jnp.zeros_like(full), jnp.zeros_like(win)))


def slot_bytes(cfg, block_size: int, dtype=jnp.bfloat16):
    """K+V of one slot's rings in the sliding-window layers."""
    return dialect.SlotBytes(window=int(
        2 * cfg.n_window_layers * window_blocks(cfg, block_size) * block_size
        * cfg.kv_heads * cfg.head_dim * jnp.dtype(dtype).itemsize))


def _full_reads(cfg, *a) -> int:
    """What a chunk reads of its slot's row in a full layer: the program's
    own tiles, on the host."""
    from deepspeed_tpu.inference.engine import tile_reads
    return tile_reads(cfg, *a, windowed=False)


# the full pool and the window rings side by side in the layer loop's carry
DIALECT = dialect.Dialect(
    owns=is_hybrid, new_state=new_state, pool=lambda k: k.full,
    prefill_reads=_full_reads,
    refusal=lambda cfg: ("sliding-window layers (bounded per-slot window "
                         "state)", "EXPERT_SHARE"),
    state=PagedState, bytes_per_token=dialect.full_layers_kv_bytes,
    slot_bytes=slot_bytes,
    # looked up when called: a test swaps this module's window_blocks
    ring_blocks=lambda cfg, block_size: window_blocks(cfg, block_size),
    **dialect.carried_layers(
        block_prefill, block_decode,
        plan=lambda cfg, pools, tables, lengths, active: decode_plans(
            cfg, pools[0].full, tables, lengths, active),
        flat=lambda pools: ((pools[0].full, pools[1].full, pools[0].win,
                             pools[1].win), pools[0].stats),
        layer_bases=lambda cfg, bufs: layer_bases(cfg, bufs[0].shape[1],
                                                  bufs[2].shape[1]),
        pack=lambda bufs, stats, route: (
            PagedState(bufs[0], bufs[2], stats, route),
            PagedState(bufs[1], bufs[3]))))
