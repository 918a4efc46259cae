"""Paged serving blocks for a model whose attention runs inside a
compressed latent with convolutions over time (CCA; models/zaya.py): the
fourth of the five dialects (inference/dialect.py lists them), and the first
whose cache state is not only blocks of keys and values. What a layer
attends is plain grouped-query attention over K
and V pools ``[L, N, block, Hkv * Dh]`` behind the slot's block table, the
GPT blocks' layout, allocator, ``decode_plan`` and ``paged_decode`` kernel.
But the key and value ROWS are made from more than the token itself: two
causal convolutions see the previous token's compressed ``[q~ | k~]`` row
and its first convolution's output, and the second half of the value is the
previous token's. So a slot also keeps, per layer, a fixed-size TAIL
(:class:`CCAState` rides in ``k_pool``'s place as hybrid.PagedState and
latent.LatentState do; ``v_pool`` is the plain V pool):

- ``tail`` ``[L, slots, 2, C]``: row 0 the last cached token's ``[q~ |
  k~]`` (C = (H + Hkv) * Dh channels), row 1 its first convolution's
  output: the left context of both convolutions;
- ``vtail`` ``[L, slots, Dh]``: the last cached token's ``h W_v2``, the
  shifted half of the next token's value.

A prefill chunk reads its slot's tail when ``start > 0`` and zeros when
``start = 0`` (a sequence is left-padded with zeros, and a reused slot
starts clean without anything being cleared), and leaves the tail of its
last valid token; a decode step reads every slot's and replaces the
ACTIVE slots'. Rows are written to the pools AFTER mixing, normalisation,
temperature and rotary, so the kernel attends them as it attends any pool.
A preempted request recomputes from position 0, as any other.

Prefill attends the chunk itself and then the slot's OCCUPIED history
blocks under a running max and sum (latent.py's loop, its trip count
following ``start``), not the whole table.

The expert sublayer is moe/expert_share.py's with the MLP router, whose
state ``r`` rides in the layer loop's carry beside ``x``
(engine._dense_then_sparse); both sublayers merge by residual scaling.

Not served by this dialect, and refused at construction by name (no
program of theirs carries the tail): prefix sharing and copy-on-write, the
host tier, int8 pools, speculation/verify, the fused horizon, LoRA, tensor
parallelism; nor the static-cache paths. docs/CCA_ATTENTION.md."""

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.inference import dialect
from deepspeed_tpu.inference.hybrid import _decode_attend, _heads, _rows
from deepspeed_tpu.inference.latent import _attend_tile
from deepspeed_tpu.inference.paged_cache import write_chunk
from deepspeed_tpu.models.gpt import _dense, _norm
from deepspeed_tpu.models.zaya import layer_bases
from deepspeed_tpu.moe import expert_share
from deepspeed_tpu.ops.attention.paged import NEG_INF
from deepspeed_tpu.ops.attention.rotary import apply_rotary_half_partial


class CCAState(NamedTuple):
    """The K side of a CCA paged cache. ``rows`` ``[L, N, block, Hkv *
    Dh]`` the K pool; ``tail`` / ``vtail``: the per-slot tails (module
    docstring); ``stats`` / ``route``: the expert layers' counters and the
    last dispatch's selection, as hybrid.PagedState's."""
    rows: jnp.ndarray
    tail: jnp.ndarray
    vtail: jnp.ndarray
    stats: Optional[jnp.ndarray] = None
    route: Optional[jnp.ndarray] = None

    def delete(self):
        for a in self:
            if a is not None:
                a.delete()


def is_cca(cfg) -> bool:
    return bool(getattr(cfg, "cca_time0", 0))


def new_state(cfg, num_blocks: int, block_size: int, num_slots: int, dtype):
    """Zeroed (CCAState, V pool) for ``num_blocks`` blocks a layer."""
    L, Dh = cfg.n_layers, cfg.head_dim
    k = jnp.zeros((L, num_blocks, block_size, cfg.kv_heads * Dh), dtype)
    return (CCAState(k, jnp.zeros((L, num_slots, 2, cfg.cca_channels), dtype),
                     jnp.zeros((L, num_slots, Dh), dtype)),
            jnp.zeros_like(k))


def _scale_residual(x, f, r):
    """The learned merge of a sublayer's output ``f`` into the stream."""
    return (x * r["s_r"].astype(x.dtype) + r["b_r"].astype(x.dtype)) \
        + (f * r["s_o"].astype(x.dtype) + r["b_o"].astype(x.dtype))


def _unit_heads(x, cfg):
    """L2-normalise each head and scale by sqrt(Dh): a head's values come
    out with unit mean square (float32 inside)."""
    xf = x.astype(jnp.float32)
    return xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True)
                              + cfg.norm_eps)


def _down(h, p, cfg):
    """h ``[T, d]`` (normed) -> the compressed ``[q~ | k~]`` rows ``[T, C]``
    and the two value halves ``h W_v1``, ``h W_v2`` ``[T, Dh]``."""
    C, Dh = cfg.cca_channels, cfg.head_dim
    with jax.named_scope("cca_down"):
        down = _dense(h, p["qkv"])
    return down[:, :C], down[:, C:C + Dh], down[:, C + Dh:]


def _conv0(x, prev_x, p):
    """The depthwise convolution: each channel of a row and of the row
    before it (``prev_x``: zeros before a sequence's first token)."""
    with jax.named_scope("cca_mix"):
        w = p["conv0"]["kernel"].astype(x.dtype)             # [2, C]
        return x * w[1] + prev_x * w[0] + p["conv0"]["bias"].astype(x.dtype)


def _mix(x, c0, prev_c0, v1, prev_v2, p, cfg, positions):
    """The rest of the mixing, for rows ``x`` ``[T, C]`` at ``positions``
    ``[T]`` with their first convolution's output ``c0`` and the row
    before's (``prev_c0``), and the value halves (this token's ``v1``, the
    token before's ``prev_v2``). Returns q ``[T, H, Dh]``, k and v ``[T,
    Hkv, Dh]`` as the pools store and the attention reads them."""
    H, Hkv, Dh = cfg.n_heads, cfg.kv_heads, cfg.head_dim
    T, heads = x.shape[0], H + Hkv
    with jax.named_scope("cca_mix"):
        # the second convolution mixes all of a head's channels
        w = p["conv1"]["kernel"].astype(x.dtype)       # [2, heads, Dh, Dh]
        c1 = jnp.einsum("tgi,gio->tgo", _heads(c0, heads), w[1]) \
            + jnp.einsum("tgi,gio->tgo", _heads(prev_c0, heads), w[0]) \
            + _heads(p["conv1"]["bias"].astype(x.dtype), heads)
        # the mean of the raw query and key heads, un-convolved
        raw = _heads(x, heads)
        k_raw = raw[:, H:]
        m_q = (raw[:, :H].reshape(T, Hkv, H // Hkv, Dh)
               + k_raw[:, :, None]) * 0.5
        m_k = jnp.mean(m_q.astype(jnp.float32), axis=2).astype(x.dtype)
        q = _unit_heads(c1[:, :H] + m_q.reshape(T, H, Dh), cfg)
        k = _unit_heads(c1[:, H:] + m_k, cfg) * jnp.exp(
            p["temp"].astype(jnp.float32))[:, None]
        rd, theta = cfg.rotary_channels, cfg.rope_theta
        q = apply_rotary_half_partial(q, positions, rd, theta).astype(x.dtype)
        k = apply_rotary_half_partial(k, positions, rd, theta).astype(x.dtype)
        v = jnp.stack([v1, prev_v2], axis=1)                 # [T, 2, Dh]
    return q, k, v


def _ffn(x2, r, p, cfg, impl, valid, aux, index, experts):
    """The expert sublayer on ``x2`` [T, d] with the router's state ``r``
    from the layer below. Returns (the merged stream, this layer's state,
    aux)."""
    h = _norm(x2, p["ln2"], cfg)
    y, sel, stats, r = expert_share.sparse_ffn(
        h, p["moe"], cfg, "gmm" if impl == "pallas" else "ragged_dot",
        valid=valid, experts=experts, layer=index, state=r)
    aux = dict(aux, route=aux["route"].at[index].set(sel))
    if aux["stats"] is not None:
        aux["stats"] = aux["stats"] + stats
    return _scale_residual(x2, y, p["res2"]), r, aux


def block_prefill(carry, pools, table_row, positions, n_valid, slot, p, cfg,
                  base, impl, experts):
    """One layer over a PROMPT CHUNK of slot ``slot``. ``carry`` = (x
    ``[1, C, d]``, aux with the router's state ``r``); ``pools`` = (K, V,
    tail, vtail), flat over layers; ``base``: this layer's offsets
    (models/zaya.layer_bases)."""
    x, aux = carry
    k_pool, v_pool, tails, vtails = pools
    C = x.shape[1]
    H, Hkv, Dh = cfg.n_heads, cfg.kv_heads, cfg.head_dim
    group = H // Hkv
    bs = k_pool.shape[1]
    start = positions[0]
    valid = jnp.arange(C) < n_valid
    scale = 1.0 / np.sqrt(Dh)
    at = base["tail"] + slot
    resumed = start > 0

    with jax.named_scope("attn_qkv"):
        h = _norm(x[0], p["ln1"], cfg)
        # the slot's tail is the chunk's left context only when the chunk
        # resumes a sequence: position 0 is left-padded with zeros
        t0 = jnp.where(resumed, tails[at], 0)                # [2, C]
        v0 = jnp.where(resumed, vtails[at], 0)               # [Dh]
        # a row's left context is the row before it; the first row's is
        # the tail
        xs, v1, v2 = _down(h, p, cfg)
        c0 = _conv0(xs, jnp.concatenate([t0[:1], xs[:-1]], axis=0), p)
        q, k, v = _mix(xs, c0, jnp.concatenate([t0[1:], c0[:-1]], axis=0),
                       v1, jnp.concatenate([v0[None], v2[:-1]], axis=0),
                       p, cfg, positions)
        # what the next chunk (or the first decode step) resumes from: the
        # last VALID row's; a chunk with no valid row leaves the tail be
        last = jnp.clip(n_valid - 1, 0, C - 1)
        keep = n_valid > 0
        own = jnp.stack([xs[last], c0[last]])
        tails = tails.at[at].set(jnp.where(keep, own, tails[at]))
        vtails = vtails.at[at].set(jnp.where(keep, v2[last], vtails[at]))

    with jax.named_scope("kv_write"):
        k_pool = write_chunk(k_pool, table_row, start, n_valid, _rows(k),
                             base["rows"])
        v_pool = write_chunk(v_pool, table_row, start, n_valid, _rows(v),
                             base["rows"])

    with jax.named_scope("paged_attn"), jax.named_scope("attn_cca"):
        # a KV head's group of query heads as rows of one product
        qg = q.reshape(C, Hkv, group, Dh).transpose(1, 2, 0, 3) \
            .reshape(Hkv, group * C, Dh)
        qpos = jnp.tile(positions, group)
        init = (jnp.full((Hkv, group * C), NEG_INF, jnp.float32),
                jnp.zeros((Hkv, group * C), jnp.float32),
                jnp.zeros((Hkv, group * C, Dh), jnp.float32))
        # the chunk itself, causal, from the rows it has just made
        state = _attend_tile(init, qg, k.transpose(1, 0, 2),
                             v.transpose(1, 0, 2), positions, qpos, scale)

        def history(j, state):
            # block j of the slot's OCCUPIED history; what of it lies at
            # or past ``start`` (the chunk's own rows, a block's unwritten
            # tail) is masked
            b = table_row[j] + base["rows"]
            kpos = j * bs + jnp.arange(bs, dtype=jnp.int32)
            kpos = jnp.where(kpos < start, kpos, jnp.int32(2 ** 30))
            return _attend_tile(
                state, qg, _heads(k_pool[b], Hkv).transpose(1, 0, 2),
                _heads(v_pool[b], Hkv).transpose(1, 0, 2), kpos, qpos, scale)

        _, l, acc = jax.lax.fori_loop(0, (start + bs - 1) // bs, history,
                                      state)
        attn = (acc / l[..., None]).astype(x.dtype)      # [Hkv, g * C, Dh]
        attn = attn.reshape(Hkv, group, C, Dh).transpose(2, 0, 1, 3)
    with jax.named_scope("attn_out"):
        x2 = _scale_residual(
            x[0], _dense(attn.reshape(C, H * Dh), p["attn_out"]), p["res1"])
    y, r, aux = _ffn(x2, aux["r"], p, cfg, impl, valid, aux, base["index"],
                     experts)
    return (y[None], dict(aux, r=r)), (k_pool, v_pool, tails, vtails)


def block_decode(carry, pools, tables, lengths, active, p, cfg, base, impl,
                 experts, plan=None):
    """One layer for ONE new token per slot: every slot's tail is the
    token's left context, the token's rows are written at its position, its
    own tail replaces an ACTIVE slot's, and the heads attend the slot's
    rows through ``tables`` ``[B, NB]``."""
    x, aux = carry
    k_pool, v_pool, tails, vtails = pools
    B = x.shape[0]
    H, Hkv, Dh = cfg.n_heads, cfg.kv_heads, cfg.head_dim
    bs = k_pool.shape[1]
    NB = tables.shape[1]

    with jax.named_scope("attn_qkv"):
        h = _norm(x[:, 0], p["ln1"], cfg)
        at = base["tail"]
        t0 = jax.lax.dynamic_slice_in_dim(tails, at, B)      # [B, 2, C]
        v0 = jax.lax.dynamic_slice_in_dim(vtails, at, B)     # [B, Dh]
        xs, v1, v2 = _down(h, p, cfg)
        c0 = _conv0(xs, t0[:, 0], p)
        q, k, v = _mix(xs, c0, t0[:, 1], v1, v0, p, cfg, lengths)
        own = jnp.stack([xs, c0], axis=1)
        tails = jax.lax.dynamic_update_slice_in_dim(
            tails, jnp.where(active[:, None, None], own, t0), at, 0)
        vtails = jax.lax.dynamic_update_slice_in_dim(
            vtails, jnp.where(active[:, None], v2, v0), at, 0)

    with jax.named_scope("kv_write"):
        blk = jnp.take_along_axis(
            tables, jnp.clip(lengths // bs, 0, NB - 1)[:, None], axis=1)[:, 0]
        ok = jnp.logical_and(active, lengths < NB * bs)
        blk = jnp.where(ok, blk, 0) + base["rows"]
        k_pool = k_pool.at[blk, lengths % bs].set(_rows(k))
        v_pool = v_pool.at[blk, lengths % bs].set(_rows(v))

    with jax.named_scope("paged_attn"), jax.named_scope("attn_cca"):
        attn = _decode_attend(
            q.reshape(B, Hkv, H // Hkv, Dh), k_pool, v_pool,
            tables + base["rows"], lengths, None, impl, 1.0 / np.sqrt(Dh),
            plan)
    with jax.named_scope("attn_out"):
        x2 = _scale_residual(
            x[:, 0], _dense(attn.reshape(B, H * Dh), p["attn_out"]),
            p["res1"])
    y, r, aux = _ffn(x2, aux["r"], p, cfg, impl, active, aux, base["index"],
                     experts)
    return (y[:, None], dict(aux, r=r)), (k_pool, v_pool, tails, vtails)


def slot_bytes(cfg, block_size: int, dtype=jnp.bfloat16):
    """Per layer the previous token's compressed row, its first
    convolution's output and its half of the next value."""
    return dialect.SlotBytes(cca_tail=int(
        cfg.n_layers * cfg.cca_tail_values * jnp.dtype(dtype).itemsize))


def gauges(reg, cache):
    reg.gauge("kv_cca_tail_bytes",
              "device bytes of the per-slot tails of convolutional (CCA) "
              "attention: per layer and slot the previous token's "
              "compressed row, its first convolution's output and its half "
              "of the next value, whatever the slot's length").set(
        cache.cca_tail_bytes)


# the per-slot tails in the carry beside the two pools; no leading dense
# layers
DIALECT = dialect.Dialect(
    owns=is_cca, new_state=new_state, pool=lambda k: k.rows,
    prefill_reads=dialect.occupied_reads,
    refusal=lambda cfg: ("convolutional (CCA) attention (a per-slot tail of "
                         "the previous token rides beside the K and V "
                         "pools)", "CCA_ATTENTION"),
    state=CCAState, slot_bytes=slot_bytes, gauges=gauges,
    **dialect.carried_layers(
        block_prefill, block_decode, plan=dialect.rows_plan,
        flat=lambda pools: ((pools[0].rows, pools[1], pools[0].tail,
                             pools[0].vtail), pools[0].stats),
        layer_bases=lambda cfg, bufs: layer_bases(cfg, bufs[0].shape[1],
                                                  bufs[2].shape[1]),
        pack=lambda bufs, stats, route: (
            CCAState(bufs[0], bufs[2], bufs[3], stats, route), bufs[1]),
        needs_slot=True))
