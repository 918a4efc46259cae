"""Paged serving blocks for a model with latent attention (MLA;
models/dots_vlm.py): the third of the five kinds of cache state
(inference/dialect.py lists them). A token's row in a layer
is ``[c_kv | k_r | 0...]``, the normalised latent of ``kv_lora_rank``
values, the one rotated key of ``qk_rope_head_dim`` values that all heads
share, and zeros up to whole lane tiles: ONE pool ``[L, N, block, lanes]``
behind the slot's block table, no V pool, no KV heads
(:class:`LatentState` rides in ``k_pool``'s place as hybrid.PagedState
does; ``v_pool`` is None). Block tables, the allocator and the two serving
programs are the GPT blocks'.

Two attention paths over the same rows, the same numbers:

- DECODE, absorbed: the up-projection's key half is folded into the query
  (``q_n_j W^K_j^T``) and its value half applied after the attention, so a
  step reads the latent rows THROUGH the block table
  (ops/attention/mla.py) and never expands them to per-head keys and
  values;
- PREFILL, expanded: a chunk's queries attend the chunk itself, causal,
  then per-head keys and values re-expanded from the cached latents of the
  slot's OCCUPIED history, a tile at a time under a running max and sum.
  Temporaries are a tile's, not the table's: the loops' trip counts follow
  ``start``. With ``impl`` "pallas" a flash step is ONE Mosaic call
  (ops/attention/mla.py ``mla_prefill_step``): the up-projection stays
  XLA's under ``mla_expand``, the shared rotated key goes in once as the
  rows hold it, the scores live in VMEM only, the queries lie in the lanes
  from the projection to the output, and a call attends
  :func:`blocks_per_call` history blocks (from the shapes: 2 at 128 heads,
  4 at 32). Otherwise :func:`_attend_tile`, a block a turn: the portable
  path, the parity reference, and what inference/cca.py imports.

One compiled body per layer SHAPE: the leading dense layers and then the
sparse layers, each one scan of engine._scan_layers with the pool in the
carry. The FFN is inference/hybrid.py's (a dense SwiGLU or the expert
share, with the dispatch's routing record and counters). The attention
sublayer stands by itself (:func:`attend_prefill`, :func:`attend_decode`):
a model whose latent layers lie between layers of another kind
(inference/linear.py) calls it for those, with the pool's offset by its own
count of latent layers.

Not served with a latent pool, and refused at construction by name: int8
pools (a scale per KV head has no meaning here), prefix sharing and
copy-on-write, the host tier, speculation/verify, the fused horizon, LoRA,
tensor parallelism; nor the static-cache paths (generate, generate_fused,
forward). docs/LATENT_ATTENTION.md."""

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference import dialect
from deepspeed_tpu.inference.hybrid import (_ffn, _ffn_shortcut, _swiglu,
                                            ffn_kind)
from deepspeed_tpu.inference.paged_cache import write_chunk
from deepspeed_tpu.models.dots_vlm import layer_bases
from deepspeed_tpu.models.gpt import _dense, _norm
from deepspeed_tpu.ops.attention.paged import NEG_INF
from deepspeed_tpu.ops.attention.rotary import apply_rotary_freqs


class LatentState(NamedTuple):
    """The device state of a latent paged cache. ``rows`` ``[L, N, block,
    lanes]``; ``stats`` / ``route``: the expert layers' counters and the
    last dispatch's selection, as hybrid.PagedState's."""
    rows: jnp.ndarray
    stats: Optional[jnp.ndarray] = None
    route: Optional[jnp.ndarray] = None

    def delete(self):
        for a in self:
            if a is not None:
                a.delete()


def is_latent(cfg) -> bool:
    return bool(getattr(cfg, "kv_lora_rank", 0))


def _project(h, p, cfg, positions):
    """h ``[T, d]`` at ``positions`` ``[T]`` -> the heads' queries ``q_n``
    ``[T, H, d_n]`` and ``q_r`` ``[T, H, d_r]``, and the tokens' cache rows
    ``[T, lanes]``. Two things are the config's: with ``q_lora_rank`` the
    query goes through a low rank with its own norm (``q_a``, ``q_a_norm``,
    ``q_b``), without it through ONE projection ``q``; and with
    ``mla_use_nope`` the ``d_r`` values of the query and of the cached key
    are NOT rotated (no positions enter: models/kimi_linear.py). A third:
    ``q_lora_scale`` / ``kv_lora_scale`` multiply the two NORMED low ranks
    (so both parts of the query, and the latent as the row holds it, but
    not the shared key); 1.0, which is no operation, where the config has
    neither. And the query's up-projection may be stored transposed
    (``q_b_t`` ``[H (d_n + d_r), r_q]`` in ``q_b``'s place:
    models/longcat_flash.py says why)."""
    H, dn, dr = cfg.n_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    rkv = cfg.kv_lora_rank

    def rotate(x):
        if getattr(cfg, "mla_use_nope", False):
            return x
        return apply_rotary_freqs(x, positions, cfg.rope_inv_freq)

    def scaled(x, by):
        return x if by == 1.0 else x * jnp.asarray(by, x.dtype)

    with jax.named_scope("mla_q"):
        if getattr(cfg, "q_lora_rank", None):
            c_q = _norm(_dense(h, p["q_a"]), p["q_a_norm"], cfg)
            c_q = scaled(c_q, getattr(cfg, "q_lora_scale", 1.0))
            if "q_b" in p:
                q = _dense(c_q, p["q_b"])
            else:
                q = jnp.einsum("tr,nr->tn", c_q,
                               p["q_b_t"]["kernel"].astype(c_q.dtype))
        else:
            q = _dense(h, p["q"])
        q = q.reshape(-1, H, dn + dr)
        q_n = q[..., :dn]
        q_r = rotate(q[..., dn:])
    with jax.named_scope("mla_kv_down"):
        ckv = _dense(h, p["kv_a"])
        c = scaled(_norm(ckv[:, :rkv], p["kv_a_norm"], cfg),
                   getattr(cfg, "kv_lora_scale", 1.0))
        k_r = rotate(ckv[:, rkv:])
        rows = jnp.concatenate(
            [c, k_r, jnp.zeros((c.shape[0], cfg.latent_lanes
                                - cfg.latent_row), c.dtype)], axis=-1)
    return q_n, q_r, rows


def _up(rows, p, cfg, values="hsd"):
    """Cache rows ``[S, lanes]`` -> each head's own keys ``k_n`` ``[H, S,
    d_n]``, the ONE rotated key ``[S, d_r]`` that all heads share, as the
    rows hold it, and each head's values ``[H, S, d_v]`` (``values``
    "hds": ``[H, d_v, S]``): the up-projection."""
    rkv, dr = cfg.kv_lora_rank, cfg.qk_rope_head_dim
    c = rows[:, :rkv]
    k_n = jnp.einsum("sc,hdc->hsd", c, p["k_up"]["kernel"].astype(c.dtype))
    v = jnp.einsum(f"sc,hcd->{values}", c,
                   p["v_up"]["kernel"].astype(c.dtype))
    return k_n, rows[:, rkv:rkv + dr], v


def _expand(rows, p, cfg):
    """Cache rows ``[S, lanes]`` -> per-head keys ``[H, S, d_n + d_r]``
    (the shared rotated key behind each head's own) and values ``[H, S,
    d_v]``, as :func:`_attend_tile` attends them."""
    k_n, k_r, v = _up(rows, p, cfg)
    k_r = jnp.broadcast_to(k_r[None], (k_n.shape[0],) + k_r.shape)
    return jnp.concatenate([k_n, k_r], axis=-1), v


def _attend_tile(carry, q, k, v, kpos, qpos, scale):
    """One flash step: queries ``q`` ``[H, C, d]`` at ``qpos`` ``[C]``
    against a tile's ``k`` ``[H, S, d]`` / ``v`` ``[H, S, d_v]`` at ``kpos``
    ``[S]`` (a key a query may not see: ``kpos > qpos``). ``carry`` = the
    running (max ``[H, C]``, sum ``[H, C]``, accumulator ``[H, C, d_v]``),
    float32."""
    m, l, acc = carry
    s = jnp.einsum("hcd,hsd->hcs", q, k).astype(jnp.float32) * scale
    s = jnp.where(kpos[None, None, :] <= qpos[None, :, None], s, NEG_INF)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    pr = jnp.exp(s - m_new[..., None])
    alpha = jnp.exp(m - m_new)
    l = alpha * l + jnp.sum(pr, axis=-1)
    acc = acc * alpha[..., None] + jnp.einsum(
        "hcs,hsd->hcd", pr.astype(v.dtype), v).astype(jnp.float32)
    return m_new, l, acc


# expanded keys and values (bytes) a kernel call may hold at once: what
# bounds how many history blocks one call attends
_EXPANDED_BYTES = 80 << 20


def blocks_per_call(cfg, bs: int, itemsize: int) -> int:
    """History blocks one ``mla_prefill_step`` call attends, from the
    shapes: 4, 2 or 1, the most whose expanded keys and values (``H x bs x
    (d_n + d_v)`` values a block) stay inside ``_EXPANDED_BYTES``: 2 at 128
    heads and blocks of 512, 4 at 32. More blocks a call spread the
    accumulator's round trip through HBM, the query's read and a grid
    step's fixed cost over more keys (262 / 280 / 293 us a tile at 4 / 2 /
    1 and 128 heads, 345 plain); each costs its expansion, 33.6 MB there,
    in temporaries."""
    block = cfg.n_heads * bs * (cfg.qk_nope_head_dim
                                + cfg.v_head_dim) * itemsize
    return next((g for g in (4, 2) if g * block <= _EXPANDED_BYTES), 1)


def attend_prefill(x, pool, table_row, positions, n_valid, p, cfg, rows_at,
                   impl):
    """The attention sublayer over a PROMPT CHUNK of one slot, the expanded
    path: ``x`` ``[C, d]`` -> (x + attention, the pool with the chunk's rows
    written). ``pool``: every latent layer's blocks, this layer's starting
    at ``rows_at``. With ``impl`` "pallas" every flash step (the chunk's own
    tile, then the history :func:`blocks_per_call` blocks a call and what is
    left a block a call) is ONE Mosaic kernel over the expanded tile
    (ops/attention/mla.py ``mla_prefill_step``: the scores stay in VMEM,
    the queries lie in the lanes from the projection to the output);
    otherwise :func:`_attend_tile`, a block a turn."""
    C = x.shape[0]
    H, dv = cfg.n_heads, cfg.v_head_dim
    bs = pool.shape[1]
    start = positions[0]
    scale = cfg.softmax_scale
    kernel = impl == "pallas"

    with jax.named_scope("attn_qkv"):
        h = _norm(x, p["ln1"], cfg)
        q_n, q_r, rows = _project(h, p, cfg, positions)
        if kernel:                          # [H, d, C]: queries in the lanes
            q_n, q_r = q_n.transpose(1, 2, 0), q_r.transpose(1, 2, 0)
        else:
            q = jnp.concatenate([q_n, q_r], axis=-1).transpose(1, 0, 2)

    with jax.named_scope("kv_write"):
        pool = write_chunk(pool, table_row, start, n_valid, rows, rows_at)

    def attend(state, tile, kpos):
        if kernel:
            from deepspeed_tpu.ops.attention.mla import mla_prefill_step
            with jax.named_scope("mla_expand"):
                k_n, k_r, v = _up(tile, p, cfg, "hds")
            return mla_prefill_step(state, q_n, q_r, k_n, k_r, v, kpos,
                                    positions, scale)
        with jax.named_scope("mla_expand"):
            k, v = _expand(tile, p, cfg)
        return _attend_tile(state, q, k, v, kpos, positions, scale)

    def history(blocks):
        def turn(i, state):
            # blocks [i * blocks, (i + 1) * blocks) of the slot's OCCUPIED
            # history, re-expanded as they are attended; what of the last
            # lies at or past ``start`` (the chunk's own rows, a block's
            # unwritten tail) is masked. A block a slice: ONE gather of
            # them compiles to a temporary that grows with the pool
            tile = jnp.concatenate([pool[table_row[i * blocks + b] + rows_at]
                                    for b in range(blocks)])
            kpos = i * blocks * bs + jnp.arange(blocks * bs, dtype=jnp.int32)
            kpos = jnp.where(kpos < start, kpos, jnp.int32(2 ** 30))
            return attend(state, tile, kpos)
        return turn

    with jax.named_scope("paged_attn"), jax.named_scope("attn_mla"):
        # max, sum, accumulator: the kernel's with the queries in the lanes
        shapes = ((H, 1, C), (H, 1, C), (H, dv, C)) if kernel \
            else ((H, C), (H, C), (H, C, dv))
        init = tuple(jnp.full(shape, fill, jnp.float32)
                     for shape, fill in zip(shapes, (NEG_INF, 0.0, 0.0)))
        # the chunk itself, causal, from the rows it has just made
        state = attend(init, rows, positions)
        n, done = (start + bs - 1) // bs, 0
        G = blocks_per_call(cfg, bs, rows.dtype.itemsize) if kernel else 1
        if G > 1:
            state = jax.lax.fori_loop(0, n // G, history(G), state)
            done = n // G * G
        _, l, acc = jax.lax.fori_loop(done, n, history(1), state)
        if kernel:
            attn = (acc / l).astype(x.dtype).transpose(2, 0, 1)
        else:
            attn = (acc / l[..., None]).astype(x.dtype).transpose(1, 0, 2)
    with jax.named_scope("attn_out"):                    # attn [C, H, d_v]
        return x + _dense(attn.reshape(C, H * dv), p["attn_out"]), pool


def _shortcut_layer(attend, x, pool, p, cfg, base, impl, valid, aux,
                    experts):
    """A shortcut-connected DOUBLE layer (hybrid.ffn_kind "both") on the
    carry's ``x`` (``[1, C, d]`` or ``[B, 1, d]``: ``T`` tokens of ``d``),
    returned in that shape; ``attend(x [T, d], pool, sublayer params,
    rows_at) -> (x + attention, pool)`` is the prompt chunk's or the decode
    step's::

        x1 = x  + MLA_a(ln1a(x))
        u  = ln2a(x1);  m = M(u)       # the shortcut: m is not added yet
        x2 = x1 + F_a(u)
        x3 = x2 + MLA_b(ln1b(x2))
        x4 = x3 + F_b(ln2b(x3))
        out = x4 + m

    Two cache rows a token: ``base["rows"]`` ``[2]`` are the two
    sublayers' offsets into the one flat pool. On one chip the expert
    share runs where it stands; nothing here stands in for the exchange
    that the shortcut lets a deployment run under the second half."""
    shape = x.shape
    x = x.reshape(-1, shape[-1])
    with jax.named_scope("scmoe_a"):
        x1, pool = attend(x, pool, p["a"], base["rows"][0])
    x2, m, aux = _ffn_shortcut(x1, p["a"], p["moe"], cfg, impl, valid, aux,
                               base["index"], experts, "scmoe_a")
    with jax.named_scope("scmoe_b"):
        x3, pool = attend(x2, pool, p["b"], base["rows"][1])
        h = _norm(x3, p["b"]["ln2"], cfg)
        with jax.named_scope("mlp"):
            # the layer's last sum and the carry's shape are compiled into
            # the down-projection's fusion, which a trace names after its
            # LAST operation: under this scope it reads as what its time is
            return (x3 + _swiglu(h, p["b"]) + m).reshape(shape), pool, aux


def block_prefill(carry, pools, table_row, positions, n_valid, p, cfg, base,
                  impl, experts):
    """One layer over a PROMPT CHUNK of one slot. ``carry`` = (x ``[1, C,
    d]``, aux); ``pools`` = (rows,), flat over the attention sublayers;
    ``table_row`` the slot's block table; ``base`` this layer's offset(s)
    into the pool and its sparse index (models/dots_vlm.layer_bases);
    ``experts``: every sparse layer's expert kernels
    (hybrid.split_experts)."""
    x, aux = carry
    if ffn_kind(p) == "both":
        def attend(x, pool, sub, rows_at):
            return attend_prefill(x, pool, table_row, positions, n_valid,
                                  sub, cfg, rows_at, impl)
        valid = jnp.arange(x.shape[1]) < n_valid
        y, pool, aux = _shortcut_layer(attend, x, pools[0], p, cfg, base,
                                       impl, valid, aux, experts)
        return (y, aux), (pool,)
    x2, pool = attend_prefill(x[0], pools[0], table_row, positions, n_valid,
                              p, cfg, base["rows"], impl)
    valid = jnp.arange(x.shape[1]) < n_valid
    y, aux = _ffn(x2, p, cfg, impl, valid, aux, base["index"], experts)
    return (y[None], aux), (pool,)


def attend_decode(x, pool, tables, lengths, active, p, cfg, rows_at, impl,
                  plan=None):
    """The attention sublayer for ONE new token per slot, the absorbed
    path: ``x`` ``[B, d]`` -> (x + attention, the pool): the token's row is
    written at its position and every head attends the slot's rows through
    ``tables`` ``[B, NB]``."""
    B = x.shape[0]
    H, dv, rkv = cfg.n_heads, cfg.v_head_dim, cfg.kv_lora_rank
    bs = pool.shape[1]
    NB = tables.shape[1]

    with jax.named_scope("attn_qkv"):
        h = _norm(x, p["ln1"], cfg)
        q_n, q_r, rows = _project(h, p, cfg, lengths)

    with jax.named_scope("kv_write"):
        blk = jnp.take_along_axis(
            tables, jnp.clip(lengths // bs, 0, NB - 1)[:, None], axis=1)[:, 0]
        ok = jnp.logical_and(active, lengths < NB * bs)
        blk = jnp.where(ok, blk, 0) + rows_at
        pool = pool.at[blk, lengths % bs].set(rows)

    with jax.named_scope("paged_attn"), jax.named_scope("attn_mla"):
        with jax.named_scope("mla_absorb"):
            q_abs = jnp.einsum("bhd,hdc->bhc", q_n,
                               p["k_up"]["kernel"].astype(q_n.dtype))
            q = jnp.concatenate(
                [q_abs, q_r, jnp.zeros((B, H, cfg.latent_lanes
                                        - cfg.latent_row), q_n.dtype)], -1)
        if impl == "pallas":
            from deepspeed_tpu.ops.attention.mla import mla_decode_attention
            lat = mla_decode_attention(
                q, pool, tables + rows_at, lengths, value_width=rkv,
                scale=cfg.softmax_scale, plan=plan)
        else:
            from deepspeed_tpu.ops.attention.mla import mla_decode_reference
            lat = mla_decode_reference(
                q, pool, tables + rows_at, lengths, value_width=rkv,
                scale=cfg.softmax_scale)
        with jax.named_scope("mla_absorb"):
            attn = jnp.einsum("bhc,hcd->bhd", lat,
                              p["v_up"]["kernel"].astype(lat.dtype))
    with jax.named_scope("attn_out"):
        return x + _dense(attn.reshape(B, H * dv), p["attn_out"]), pool


def block_decode(carry, pools, tables, lengths, active, p, cfg, base, impl,
                 experts, plan=None):
    """One layer for ONE new token per slot (:func:`attend_decode`, then
    the FFN; or the double layer, :func:`_shortcut_layer`)."""
    x, aux = carry
    if ffn_kind(p) == "both":
        def attend(x, pool, sub, rows_at):
            return attend_decode(x, pool, tables, lengths, active, sub, cfg,
                                 rows_at, impl, plan)
        y, pool, aux = _shortcut_layer(attend, x, pools[0], p, cfg, base,
                                       impl, active, aux, experts)
        return (y, aux), (pool,)
    x2, pool = attend_decode(x[:, 0], pools[0], tables, lengths, active, p,
                             cfg, base["rows"], impl, plan)
    y, aux = _ffn(x2, p, cfg, impl, active, aux, base["index"], experts)
    return (y[:, None], aux), (pool,)


def new_state(cfg, num_blocks: int, block_size: int, num_slots: int, dtype):
    """Zeroed (LatentState, None): one pool of latent rows over every
    attention sublayer, no V pool."""
    return LatentState(jnp.zeros((cfg.n_full_layers, num_blocks, block_size,
                                  cfg.latent_lanes), dtype)), None


def kv_bytes_per_token(cfg, dtype=jnp.bfloat16) -> int:
    """One row of ``latent_lanes`` values an attention sublayer (the latent
    and the shared key, padded to whole lane tiles), no K or V heads:
    ``n_full_layers`` of them, the latent layers of a model that has
    others beside them, two a layer where layers are double."""
    return int(cfg.n_full_layers * cfg.latent_lanes
               * jnp.dtype(dtype).itemsize)


def flash_steps(cfg, start: int, bs: int) -> int:
    """Flash steps of a prefill chunk at ``start`` (:func:`attend_prefill`:
    every occupied history block of ``bs`` and the chunk's own tile, an
    attention sublayer): ``mla_prefill`` kernel blocks where
    ``decode_impl`` is "pallas", plain flash steps otherwise."""
    return cfg.n_full_layers * ((start + bs - 1) // bs + 1)


def gauges(reg, cache):
    reg.gauge("kv_latent_pool_bytes",
              "device bytes of the latent (MLA) pool: one row a token a "
              "layer as stored (padded to whole lane tiles), trash block "
              "included").set(
        cache.num_blocks * cache.block_size * cache.bytes_per_token)
    reg.gauge("kv_latent_row_bytes",
              "bytes of one token's latent row in one layer as computed: "
              "the latent and the shared rotated key, without the "
              "padding").set(cache.cfg.latent_row * cache.pool_dtype.itemsize)


DIALECT = dialect.Dialect(
    owns=is_latent, new_state=new_state, pool=lambda k: k.rows,
    prefill_reads=dialect.occupied_reads,
    refusal=lambda cfg: ("a latent (MLA) cache row (one pool of latents, no "
                         "K/V heads)", "LATENT_ATTENTION"),
    state=LatentState, bytes_per_token=kv_bytes_per_token,
    flash_steps=flash_steps, gauges=gauges,
    tile_row_bytes=lambda cfg, pool: None,
    ready_note=lambda cfg, impl=None: ", latent rows a token: "
    f"{cfg.n_full_layers}",
    **dialect.carried_layers(
        block_prefill, block_decode, plan=dialect.rows_plan,
        flat=lambda pools: ((pools[0].rows,), pools[0].stats),
        layer_bases=lambda cfg, bufs: layer_bases(cfg, bufs[0].shape[1]),
        pack=lambda bufs, stats, route: (
            LatentState(bufs[0], stats, route), None)))
