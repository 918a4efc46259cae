"""Inference engine: TP-sharded KV-cache generation.

Capability analog of the reference inference stack
(ref: deepspeed/inference/engine.py:23 InferenceEngine — MP group creation
:143, injection :225, checkpoint load :281, forward :355; fused kernel
modules ops/transformer/inference/transformer_inference.py:113/408/549 with
KV-cache management via the global Context workspace). TPU-native design:

- "kernel injection" = running the model through our fused JAX/Pallas GPT
  blocks (flash attention prefill, fused decode attention); policies
  (inference/policy.py) map foreign checkpoints (HF GPT-2 et al) into this
  layout — the analog of replace_transformer_layer
  (module_inject/replace_module.py:123);
- tensor parallelism = the same Megatron partition rules as training; the
  attn/MLP output allreduces the reference issues by hand
  (LinearAllreduce, transformer_inference.py MP allreduce) come from XLA;
- the KV cache is a preallocated [L, B, S_max, Hkv, D] pytree (Hkv =
  cfg.kv_heads; smaller than H under grouped-query attention) threaded
  functionally through a jitted, cache-donating decode step; generation is
  a host loop over compiled prefill + decode programs.
"""

import dataclasses
import functools
import inspect
from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deepspeed_tpu.inference import dialect, paged_cache, sampling
from deepspeed_tpu.inference.hybrid import (_heads, _rows, causal_band,
                                            split_experts)
from deepspeed_tpu.models import gpt as gpt_lib
from deepspeed_tpu.moe import expert_share
from deepspeed_tpu.ops import quantizer
from deepspeed_tpu.ops.attention.paged import (blocks_per_step,
                                               decode_plan,
                                               gather_pool_blocks,
                                               pool_row_bytes)
from deepspeed_tpu.models.gpt import (GPTConfig, _dense,
                                      _norm, _qkv_split_rotary)
from deepspeed_tpu.parallel import mesh as mesh_lib
from deepspeed_tpu.parallel import sharding as sharding_lib
from deepspeed_tpu.utils.jit_registry import program_id
from deepspeed_tpu.utils.logging import log_dist, logger

PyTree = Any


@dataclass
class InferenceConfig:
    mp_size: int = 1
    dtype: Any = jnp.bfloat16
    max_seq_len: int = 2048
    max_batch_size: int = 8
    replace_with_kernel_inject: bool = True   # API parity; always fused here


def quantize_weights_int8(params):
    """Weight-only int8: every matmul kernel (block projections, MoE
    expert stacks, the untied lm_head) becomes {"q": int8, "scale":
    fp32 per-output-channel}; norms/embeddings/biases stay float.
    Dequantization happens at the matmul (gpt._kernel_of), so weights
    sit in HBM at 1 byte/param — the serving analog of the reference's
    int8 kernel-inject path (ref: replace_module.py quantize path,
    csrc/transformer/inference dequant kernels). Capability: llama-7B
    weights drop 13.5GB(bf16) -> 6.7GB on a 16GB chip."""
    def quant_leaf(w):
        a = jnp.max(jnp.abs(w), axis=-2, keepdims=True)
        scale = (a.astype(jnp.float32) / 127.0) + 1e-12
        q = jnp.clip(jnp.round(w / scale), -127, 127).astype(jnp.int8)
        return {"q": q, "scale": scale.astype(jnp.float32)}

    def walk(tree):
        if isinstance(tree, dict):
            if "kernel" in tree and tree["kernel"].ndim >= 2:
                out = {k: v for k, v in tree.items() if k != "kernel"}
                out.update(quant_leaf(tree["kernel"]))
                return out
            return {k: walk(v) for k, v in tree.items()}
        return tree

    out = dict(params)
    if "block" in out:
        out["block"] = walk(out["block"])
    if "lm_head" in out:
        out["lm_head"] = walk(out["lm_head"])
    return out


def _mlp(h, p, cfg, lora=None):
    lr = (lambda t: None) if lora is None else lora.get
    m = _dense(h, p["mlp_in"], lora=lr("mlp_in"))
    if cfg.activation == "swiglu":
        m = jax.nn.silu(_dense(h, p["mlp_gate"], lora=lr("mlp_gate"))) * m
    else:
        m = jax.nn.gelu(m, approximate=True)
    return _dense(m, p["mlp_out"], lora=lr("mlp_out"))


def _block_prefill(x, p, cfg: GPTConfig, kv_mask=None, positions=None):
    """Forward one block over the full prompt, returning (y, k, v).

    The cached k/v are post-rotary so decode never re-rotates history.
    kv_mask: [B, S] prompt validity (left-padded batched prompts);
    positions: optional [B, S] per-row rotary positions."""
    B, S, D = x.shape
    h = _norm(x, p["ln1"], cfg)
    qkv = _dense(h, p["qkv"])
    q, k, v = gpt_lib._qkv_split_rotary(qkv, cfg, positions, B, S)
    if cfg.use_flash_attention and S % 128:
        # a prompt bucket the flash kernel cannot tile prefills through
        # the dense path: chosen from the traced shape, on every platform
        cfg = dataclasses.replace(cfg, use_flash_attention=False)
    attn = gpt_lib._attention(q, k, v, cfg, kv_mask=kv_mask).reshape(B, S, D)
    attn = _dense(attn, p["attn_out"])
    if cfg.parallel_residual:
        return x + attn + _ffn(h, p, cfg), k, v
    x = x + attn
    h = _norm(x, p["ln2"], cfg)
    return x + _ffn(h, p, cfg), k, v


def _ffn(h, p, cfg, lora=None):
    """Dense MLP or MoE FFN for one block (ref MoE inference path:
    ops/transformer/inference/moe_inference.py). ``lora`` (multi-tenant
    serving, inference/adapters.py) applies to the dense MLP targets
    only — MoE expert stacks are not adaptable pool targets.

    This is the all-experts path of the ``moe_gpt`` configs (a few
    experts, all of them held here). A chip that holds a SHARE of many
    experts computes only the (token, expert) pairs that fall on them:
    moe/expert_share.py, reached through inference/hybrid.py.

    This path NEVER drops a token (GShard capacity bounds
    training dispatch; it must not change eval semantics — the gate's
    1.0-eval-capacity default silently dropped tokens here, caught by
    the Mixtral HF-parity test) and avoids the no-drop dispatch tensors
    (capacity = S makes the one-hot combine O(E*S^2)): every expert
    runs on every token — O(E*T*d) memory, E/k extra expert flops — and
    tokens mix their top-k renormalized softmax weights, exactly
    Mixtral's softmax-over-top-k router semantics."""
    if "moe" not in p:
        return _mlp(h, p, cfg, lora=lora)
    from deepspeed_tpu.moe.experts import ffn_expert_fn
    k = getattr(cfg, "moe_k", 1)
    B, S, D = h.shape
    ex = p["moe"]["experts"]
    # int8-quantized expert stacks carry "q" instead of "kernel"
    E = next(iter(ex["wi"].values())).shape[0]
    logits = h.reshape(-1, D).astype(jnp.float32) @ p["moe"]["gate"]["wg"]
    probs = jax.nn.softmax(logits, axis=-1)               # [T, E]
    top_p, top_i = jax.lax.top_k(probs, k)
    # weight convention MUST match what the checkpoint trained with
    # (cfg.gate_weighting): GShard top-1 weighs by the RAW softmax prob
    # (sharded_moe.top1gating) while Mixtral's softmax-over-top-k
    # renormalizes (1.0 at k=1); the two agree at k=2
    gshard = getattr(cfg, "gate_weighting", "gshard") == "gshard"
    w = (top_p if (k == 1 and gshard)
         else top_p / jnp.clip(top_p.sum(-1, keepdims=True), 1e-9))
    w_full = jnp.sum(jax.nn.one_hot(top_i, E) * w[..., None], axis=-2)
    outs = ffn_expert_fn(ex, jnp.broadcast_to(
        h.reshape(1, -1, D), (E, B * S, D)))              # [E, T, D]
    y = jnp.einsum("etd,te->td", outs, w_full.astype(h.dtype))
    return y.reshape(B, S, D)


def _block_decode(x, k_cache, v_cache, pos, p, cfg: GPTConfig,
                  cache_mask=None, row_pos=None):
    """One block for ONE new token. x: [B, 1, D]; caches [B, S_max, Hkv, Dh].
    Fused decode attention with positional masking over the cache
    (ref: softmax_context + KV-cache path, transformer_inference.py:113).
    cache_mask: optional [B, S_max] validity (0 = left-padding slot);
    row_pos: optional [B] per-row logical positions for rotary."""
    B, _, D = x.shape
    H, Dh = cfg.n_heads, cfg.head_dim
    S_max = k_cache.shape[1]

    Hkv = cfg.kv_heads
    group = H // Hkv
    h = _norm(x, p["ln1"], cfg)
    qkv = _dense(h, p["qkv"])
    q, k, v = jnp.split(qkv, [H * Dh, (H + Hkv) * Dh], axis=-1)
    if cfg.rotary_dim:
        from deepspeed_tpu.ops.attention.rotary import apply_rotary
        rp = pos[None] if row_pos is None else row_pos[:, None]
        q, k = apply_rotary(q.reshape(B, 1, H, Dh), k.reshape(B, 1, Hkv, Dh),
                            rp, cfg.rotary_dim, base=cfg.rope_theta)
        q = q.reshape(B, 1, H, Dh)
        k = k.reshape(B, 1, Hkv, Dh)
    q = q.reshape(B, Hkv, group, Dh)
    k_cache = jax.lax.dynamic_update_slice_in_dim(
        k_cache, k.reshape(B, 1, Hkv, Dh), pos, axis=1)
    v_cache = jax.lax.dynamic_update_slice_in_dim(
        v_cache, v.reshape(B, 1, Hkv, Dh), pos, axis=1)

    # grouped decode attention: q heads grouped per shared kv head
    scores = jnp.einsum("bkgd,bskd->bkgs", q, k_cache).astype(jnp.float32)
    scores *= cfg.attn_scale if cfg.attn_scale is not None \
        else 1.0 / np.sqrt(Dh)
    idx = jax.lax.broadcasted_iota(jnp.int32, (1, 1, 1, S_max), 3)
    scores = jnp.where(idx <= pos, scores, -1e30)
    if cfg.attn_window is not None:
        # logical distance == cache-index distance even under left
        # padding (both the query and every cached slot shift by the
        # same per-row pad)
        scores = jnp.where(idx > pos - cfg.attn_window, scores, -1e30)
    if cache_mask is not None:
        scores = jnp.where(cache_mask[:, None, None, :] > 0, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    attn = jnp.einsum("bkgs,bskd->bkgd", probs, v_cache).reshape(B, 1, D)
    attn = _dense(attn, p["attn_out"])
    if cfg.parallel_residual:
        return x + attn + _ffn(h, p, cfg), k_cache, v_cache
    x = x + attn
    h = _norm(x, p["ln2"], cfg)
    return x + _ffn(h, p, cfg), k_cache, v_cache


def _block_extend(x, k_cache, v_cache, pos, p, cfg: GPTConfig):
    """Decode block for G new tokens at STATIC cache positions
    [pos, pos+G) — the chunk-verify block of the static speculative path
    (inference/speculative.py), shared here so the paged verify block
    below and the static path dedupe one copy of the G-query decode
    math. x: [B, G, D]; caches [B, S_max, Hkv, Dh]. Causality: query i
    sees cache slots <= pos + i (its own prefix included)."""
    B, G, D = x.shape
    H, Dh = cfg.n_heads, cfg.head_dim
    Hkv = cfg.kv_heads
    group = H // Hkv
    S_max = k_cache.shape[1]

    h = _norm(x, p["ln1"], cfg)
    qkv = _dense(h, p["qkv"])
    q, k, v = _qkv_split_rotary(qkv, cfg, pos + jnp.arange(G), B, G)
    k_cache = jax.lax.dynamic_update_slice_in_dim(k_cache, k, pos, axis=1)
    v_cache = jax.lax.dynamic_update_slice_in_dim(v_cache, v, pos, axis=1)

    qg = q.reshape(B, G, Hkv, group, Dh)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qg,
                        k_cache).astype(jnp.float32)
    scores *= cfg.attn_scale if cfg.attn_scale is not None \
        else 1.0 / np.sqrt(Dh)
    idx = jax.lax.broadcasted_iota(jnp.int32, (1, 1, 1, 1, S_max), 4)
    qi = jax.lax.broadcasted_iota(jnp.int32, (1, 1, 1, G, 1), 3)
    scores = jnp.where(idx <= pos + qi, scores, -1e30)
    if cfg.attn_window is not None:
        scores = jnp.where(idx > pos + qi - cfg.attn_window, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    attn = jnp.einsum("bkgqs,bskd->bqkgd", probs, v_cache)
    attn = attn.reshape(B, G, D)
    attn = _dense(attn, p["attn_out"])
    if cfg.parallel_residual:
        return x + attn + _ffn(h, p, cfg), k_cache, v_cache
    x = x + attn
    h = _norm(x, p["ln2"], cfg)
    return x + _ffn(h, p, cfg), k_cache, v_cache


# A table that is looked up by row keeps whole rows of 128 lanes. The TPU
# stores a ``[V, d]`` bf16 parameter in the layout that pads less: for
# ``d % 128 != 0`` (GPT-2 XL's 1,600 is 12.5 x 128) that is COLUMN-major,
# ``{0,1:T(8,128)(2,1)}``, which the tied head's product reads as it is
# and a row gather cannot, so every program that looks a token up first
# copied the whole table into the row-major layout, on every dispatch
# (161 MB read and 166 MB written, 0.5 ms of a 7.6 ms decode step on a
# v5e: PERF.md, PR 39). With ``d`` a multiple of 128 (or padded to one)
# the table is stored row-major and both uses read that one layout. The
# engine therefore pads such a table ONCE, where it places the parameters
# (whole_lane_tables), with zero lanes that :func:`table_lanes` cuts off
# again before anything reads them: the mathematics is the unpadded
# table's to the bit. ``param_copy_bytes`` (telemetry/costs.py) is the
# check on a compiled program: 0 where no parameter is re-laid.
_LANES = 128


def whole_lane_tables(params, pspecs=None):
    """``params`` as the engine stores them: the looked-up tables (``wte``,
    ``wpe``; ``[rows, d]``) with zero lanes behind each row up to the next
    multiple of 128, unless a partition rule cuts a table's lanes over the
    mesh (``pspecs``, the parameters' PartitionSpecs; none does today):
    padding would move the shards' boundaries, and the table stays as it
    is. Returns (params, what was done, for the engine's log line: empty
    where every table already has whole lanes, and ``params`` is then the
    tree it was handed)."""
    note = ""
    for name in ("wte", "wpe"):
        table = params.get(name, {}).get("embedding")
        if table is None or table.shape[-1] % _LANES == 0:
            continue
        d = table.shape[-1]
        if pspecs is not None and pspecs[name]["embedding"][-1] is not None:
            note += f", {name} lanes {d} left as they are: cut over the mesh"
            continue
        table = jnp.pad(table, ((0, 0), (0, -d % _LANES)))
        params = {**params, name: {"embedding": table}}
        note += f", {name} lanes {d}->{table.shape[-1]}"
    return params, note


def table_lanes(rows, d: int):
    """The first ``d`` lanes of ``rows`` taken from a stored table (the
    table itself included): what the unpadded table holds there. For a
    table that was never padded this is ``rows``, and traces nothing."""
    return rows if rows.shape[-1] == d else rows[..., :d]


def tied_logits(x, table):
    """``x @ wte.T`` against a stored ``wte``: ``x``'s lanes are the
    unpadded table's."""
    return x @ table_lanes(table, x.shape[-1]).T


def _named(fn, name: str):
    """``fn`` under an explicit ``__name__``: jax names a compiled module
    ``jit_<name>``, and profiles, the persistent cache's entries and the
    provenance table (telemetry/costs.py) keep that name when the Python
    function is renamed."""
    @functools.wraps(fn)
    def call(*args, **kwargs):
        return fn(*args, **kwargs)
    call.__name__ = call.__qualname__ = name
    return call


# A dispatch crosses the host link once: everything that changes at step
# cadence (block tables, lengths, tokens, the active mask, the sampler's
# small lanes) travels as ONE int32 buffer, handed to the jitted call as
# numpy, so jit's own argument path is the only transfer and nothing is
# launched ahead of the program (PERF.md, PR 31: a jnp.asarray per operand
# was the serve.dispatch.enqueue span). A section's kind says how its
# words were written and how the program reads them back.
_WORDS = {"i": np.int32, "b": np.int32, "row": np.int32, "lora": np.int32,
          "u": np.uint32, "f": np.float32}


def pack_operands(*parts):
    """``(kind, value)`` pairs, in the order the program takes them, as
    ``(buffer, layout)``: one flat host int32 array holding every
    section's words (floats and key bits as their bit patterns, booleans
    as 0 / 1) and the static tuple :func:`_packed` unpacks it by. Kinds:
    ``i`` int32, ``u`` uint32, ``f`` float32, ``b`` bool; ``row``, a slot
    index for which the program takes that row of the resident sampler
    mask; ``lora``, the adapter-table rows that complete ``lora``. Two
    kinds put nothing in the buffer: ``static`` (a value the program is
    compiled for) and ``seen`` (the resident mask, whole). The layout
    depends on shapes alone, so a run has one per program."""
    layout, words = [], []
    for kind, value in parts:
        if kind in _WORDS:
            value = np.asarray(value, _WORDS[kind])
            words.append(value.reshape(-1).view(np.int32))
            value = value.shape
        layout.append((kind, value))
    return np.concatenate(words), tuple(layout)


def _packed(fn, name: str):
    """``fn`` (one of the paged serving programs, with the signature it
    has) behind the packed operand of :func:`pack_operands`, under the
    module name ``jit_<name>``: a shell of static slices and bitcasts
    inside the SAME program. ``seen`` is the sampler's resident [B, V]
    mask (None for a program that does not sample). A program that keeps
    per-slot state beside the pools (``fn`` has a ``slot`` parameter) is
    also handed the ``row`` section's slot index itself."""
    wants_slot = "slot" in inspect.signature(fn).parameters

    def call(params, k_pool, v_pool, packed, layout, seen=None, scales=None,
             lora=None):
        operands, at, extra = [], 0, {}
        for kind, what in layout:
            if kind == "static":
                operands.append(what)
                continue
            if kind == "seen":
                operands.append(seen)
                continue
            n = int(np.prod(what))
            words = jax.lax.slice_in_dim(packed, at, at + n).reshape(what)
            at += n
            if kind == "lora":
                lora = (*lora, words)
            elif kind == "row":
                operands.append(jax.lax.dynamic_index_in_dim(
                    seen, words, keepdims=False))
                if wants_slot:
                    extra["slot"] = words
            elif kind == "b":
                operands.append(words != 0)
            elif kind == "i":
                operands.append(words)
            else:
                operands.append(jax.lax.bitcast_convert_type(
                    words, _WORDS[kind]))
        return fn(params, k_pool, v_pool, *operands, scales=scales,
                  lora=lora, **extra)
    return _named(call, name)


def _scan_layers(block, x, params, pools, lora_ops=None, stack="block",
                 bases=None):
    """Run the layers of a paged program: the ONE layer loop of every
    prefill, decode, verify and horizon program.

    ``pools`` is the paged cache's state, each ``[L, N, ...]`` stacked
    over the layers: K and V ``[L, N, block, Hkv*Dh]``, then, for int8
    pools, their scales ``[L, N, Hkv]``. They ride in the scan's CARRY
    viewed as ``[L*N, ...]`` (a bitcast: the layout in HBM is row-major
    already) and layer ``l`` addresses its own blocks at ``base = l*N``:
    ``tables + base`` to read, ``blk + base`` to write, so the trash
    block of layer ``l`` is block ``l*N``. The carried buffers are the
    donated entry parameters themselves and every write is a scatter in
    place. Passing the pools as the scan's xs and ys instead sliced
    each layer's pool out, re-laid it for the body, laid it back and
    wrote it into a stacked output: every byte of the pool moved four
    times per dispatch (PERF.md, PR 25).

    ``block(x, pools, layer_p, base, lora) -> (y, pools)``; the xs are
    the stacked block parameters, ``base`` per layer and, with
    ``lora_ops = (a_pool, b_pool, ablocks)``, the adapter pools, whose
    per-slot rank blocks are gathered per layer for gpt._dense's hook.
    Returns ``(x, pools)`` with the pools back in their stacked shapes.

    A model whose layers differ in SHAPE or kind (inference/hybrid.py)
    calls this once per stack of same-shaped layers (``stack`` names it in
    ``params``), with pools of different depths side by side and
    ``bases`` = each layer's offsets into them (any pytree with a leading
    layer axis); ``x`` is then whatever the block carries from layer to
    layer.
    """
    flat = tuple(p.reshape((-1,) + p.shape[2:]) for p in pools)
    if bases is None:
        L, N = pools[0].shape[:2]
        bases = jnp.arange(L, dtype=jnp.int32) * N
    xs = (params[stack], bases)
    if lora_ops is not None:
        xs = xs + (lora_ops[0], lora_ops[1])

    def body(carry, layer):
        x, flat = carry
        lora = None
        if lora_ops is not None:
            lora = {t: (layer[2][t][lora_ops[2]], layer[3][t][lora_ops[2]])
                    for t in layer[2]}
        y, flat = block(x, flat, layer[0], layer[1], lora)
        return (y, flat), None

    (x, flat), _ = jax.lax.scan(body, (x, flat), xs)
    return x, tuple(f.reshape(p.shape) for f, p in zip(flat, pools))


def _paged_plan(pools, tables, lengths, active, cfg, q_len: int = 1):
    """The paged kernel's grid for these lengths (ops/attention/paged.py
    ``decode_plan``), worked out ONCE a dispatch, outside the layer loop,
    for every layer's call, from the slots that are ``active``: the
    tiles of a slot with no request, or of one still in prefill, are not
    in it. A few integer operations that the compiler drops from a
    program on the gather path."""
    return decode_plan(lengths, tables.shape[1], pools[0].shape[2],
                       row_bytes=pool_row_bytes(pools[0]),
                       window=cfg.attn_window, q_len=q_len, active=active)


def _block_decode_paged(x, pools, tables, lengths, active, p,
                        cfg: GPTConfig, impl: str = "gather", lora=None,
                        base=0, plan=None):
    """One block for ONE new token per slot: :func:`_attn_decode_paged`
    and the block's FFN behind it. Returns (y, pools)."""
    h, attn, pools = _attn_decode_paged(x, pools, tables, lengths, active,
                                        p, cfg, impl=impl, lora=lora,
                                        base=base, plan=plan)
    return _mlp_behind(x, h, attn, p, cfg, lora), pools


def _mlp_behind(x, h, attn, p, cfg: GPTConfig, lora=None):
    """The rest of a GPT block behind its attention: ``x`` the stream,
    ``h`` its first norm, ``attn`` the projected attention."""
    with jax.named_scope("mlp"):
        if cfg.parallel_residual:
            return x + attn + _ffn(h, p, cfg, lora=lora)
        x = x + attn
        return x + _ffn(_norm(x, p["ln2"], cfg), p, cfg, lora=lora)


def _heads_by_config(cfg) -> bool:
    """Whether the paged attention's q, k, v are more than a split and a
    rotation of one projection (:func:`_qkv_heads`)."""
    return any(getattr(cfg, name, False) for name in (
        "attn_output_gate", "qk_norm", "rotary_half"))


def _qkv_heads(qkv, p, cfg: GPTConfig, positions, B: int, S: int):
    """The fused projection ``qkv`` ``[B, S, .]`` -> q ``[B, S, H, Dh]``, k,
    v ``[B, S, Hkv, Dh]`` and the attention's output gate ``[B, S, H * Dh]``
    or None. Data of the config, each absent from a plain GPT block (whose
    prologue is ``_qkv_split_rotary``, and is traced as it was):
    ``attn_output_gate``: the query's part is twice as wide, per head ``[q |
    gate]``, and ``sigmoid(gate)`` scales the attention's output before its
    projection; ``qk_norm``: ``_norm`` (with the config's ``norm_offset``)
    over each head's q and k, scales ``q_norm`` / ``k_norm``;
    ``rotary_half``: the rotate-half convention on the first ``rotary_dim``
    channels of every head, not the interleaved one."""
    H, Dh, Hkv = cfg.n_heads, cfg.head_dim, cfg.kv_heads
    gated = getattr(cfg, "attn_output_gate", False)
    wq = (2 if gated else 1) * H * Dh
    q, k, v = jnp.split(qkv, [wq, wq + Hkv * Dh], axis=-1)
    gate = None
    if gated:
        q, gate = jnp.split(q.reshape(B, S, H, 2 * Dh), 2, axis=-1)
        gate = gate.reshape(B, S, H * Dh)
    q = q.reshape(B, S, H, Dh)
    k = k.reshape(B, S, Hkv, Dh)
    v = v.reshape(B, S, Hkv, Dh)
    if getattr(cfg, "qk_norm", False):
        q, k = _norm(q, p["q_norm"], cfg), _norm(k, p["k_norm"], cfg)
    if cfg.rotary_dim and getattr(cfg, "rotary_half", False):
        from deepspeed_tpu.ops.attention.rotary import \
            apply_rotary_half_partial
        q, k = (apply_rotary_half_partial(a, positions, cfg.rotary_dim,
                                          cfg.rope_theta) for a in (q, k))
    elif cfg.rotary_dim:
        from deepspeed_tpu.ops.attention.rotary import apply_rotary
        q, k = apply_rotary(q, k, positions, cfg.rotary_dim,
                            base=cfg.rope_theta)
    return q, k, v, gate


def _gate_output(attn, gate):
    """The attention ``[B, S, H * Dh]`` times ``sigmoid(gate)``
    (:func:`_qkv_heads`); as it came where the config has no gate."""
    if gate is None:
        return attn
    with jax.named_scope("attn_gate"):
        return (attn.astype(jnp.float32) * jax.nn.sigmoid(
            gate.astype(jnp.float32))).astype(attn.dtype)


def _attn_decode_paged(x, pools, tables, lengths, active, p,
                       cfg: GPTConfig, impl: str = "gather", lora=None,
                       base=0, plan=None):
    """The attention of one block for ONE new token per slot, K/V addressed
    through block tables — the paged generalization of _block_decode's,
    and what a model whose other layers are not attention (inference/
    ssm.py) calls by itself. x: [B, 1, D];
    ``pools`` = (k_pool, v_pool) of [N', block, Hkv*Dh] — ALL layers'
    blocks, this layer's starting at ``base`` (_scan_layers) — tables
    [B, NB] of block ids within a layer; lengths [B] per-slot cache
    positions (each slot decodes at its OWN position — the
    continuous-batching contract); active [B] bool (inactive slots'
    writes land in the layer's trash block, block ``base``, and their
    logits are ignored).

    impl="gather" materializes the virtual cache with
    gather_pool_blocks (the bit-reference, portable everywhere);
    impl="pallas" attends THROUGH the table with the flash-decode kernel (ops/attention/
    paged.py) — one pool-block DMA per occupied block, no dense copy.

    With ``pools`` = (k_pool, v_pool, k_scale, v_scale) (scales
    ``[N', Hkv]`` fp32) the pools are int8: the write becomes
    read-modify-requantize of each slot's current block (dequantize,
    insert the token, zero stale lanes, requantize — ops/quantizer KV
    helpers) and the scales update alongside. Two pools trace the exact
    pre-quant program — the bit-reference path is untouched.

    ``lora`` (multi-tenant adapter serving, inference/adapters.py) is a
    dict target -> per-slot gathered rank-block factors handed through
    to :func:`~deepspeed_tpu.models.gpt._dense`; ``lora=None`` (the
    default) traces the exact base-only program. Returns (``ln1(x)``, the
    projected attention ``[B, 1, D]``, pools)."""
    B, _, D = x.shape
    H, Dh = cfg.n_heads, cfg.head_dim
    Hkv = cfg.kv_heads
    group = H // Hkv
    k_pool, v_pool = pools[:2]
    k_scale, v_scale = pools[2:] if len(pools) == 4 else (None, None)
    bs = k_pool.shape[1]
    NB = tables.shape[1]
    lr = (lambda t: None) if lora is None else lora.get

    with jax.named_scope("attn_qkv"):
        h = _norm(x, p["ln1"], cfg)
        qkv = _dense(h, p["qkv"], lora=lr("qkv"))
        gate = None
        if _heads_by_config(cfg):
            q, k, v, gate = _qkv_heads(qkv, p, cfg, lengths[:, None], B, 1)
        else:
            q, k, v = jnp.split(qkv, [H * Dh, (H + Hkv) * Dh], axis=-1)
            if cfg.rotary_dim:
                from deepspeed_tpu.ops.attention.rotary import apply_rotary
                q, k = apply_rotary(
                    q.reshape(B, 1, H, Dh), k.reshape(B, 1, Hkv, Dh),
                    lengths[:, None], cfg.rotary_dim, base=cfg.rope_theta)
        q = q.reshape(B, Hkv, group, Dh)
        k = k.reshape(B, Hkv, Dh)
        v = v.reshape(B, Hkv, Dh)

    with jax.named_scope("kv_write"):
        # scatter the new token's K/V into each slot's current block; a slot
        # whose block budget is exhausted (lengths == NB*bs) would CLAMP to
        # the last block's live data — route it to the trash block instead
        # (serving.py finishes such slots before they reach here; the mask
        # is the engine-side belt to that suspender)
        in_cap = lengths < NB * bs
        blk = jnp.take_along_axis(
            tables, jnp.clip(lengths // bs, 0, NB - 1)[:, None], axis=1)[:, 0]
        blk = jnp.where(jnp.logical_and(active, in_cap), blk, 0) + base
        off = lengths % bs
        if k_scale is None:
            k_pool = k_pool.at[blk, off].set(_rows(k))
            v_pool = v_pool.at[blk, off].set(_rows(v))
        else:
            kb = quantizer.kv_dequantize_blocks(_heads(k_pool[blk], Hkv),
                                                k_scale[blk])
            vb = quantizer.kv_dequantize_blocks(_heads(v_pool[blk], Hkv),
                                                v_scale[blk])
            rows = jnp.arange(B)
            kb = kb.at[rows, off].set(k.astype(jnp.float32))
            vb = vb.at[rows, off].set(v.astype(jnp.float32))
            # lanes past the new token are a previous owner's garbage
            live = jnp.arange(bs)[None, :] <= off[:, None]
            kq, ksn = quantizer.kv_requantize_blocks(kb, live)
            vq, vsn = quantizer.kv_requantize_blocks(vb, live)
            k_pool = k_pool.at[blk].set(_rows(kq))
            v_pool = v_pool.at[blk].set(_rows(vq))
            k_scale = k_scale.at[blk].set(ksn)
            v_scale = v_scale.at[blk].set(vsn)

    scale = cfg.attn_scale if cfg.attn_scale is not None \
        else 1.0 / np.sqrt(Dh)
    tabs = tables + base                 # this layer's blocks
    if impl == "pallas":
        from deepspeed_tpu.ops.attention.paged import paged_decode_attention
        with jax.named_scope("paged_attn"):
            attn = paged_decode_attention(
                q, k_pool, v_pool, tabs, lengths, scale=float(scale),
                window=cfg.attn_window, k_scale=k_scale,
                v_scale=v_scale, plan=plan).reshape(B, 1, H * Dh)
    else:
        with jax.named_scope("kv_gather"):
            # [B, NB*bs, Hkv, Dh]
            kc = gather_pool_blocks(k_pool, tabs, Hkv, k_scale, x.dtype)
            vc = gather_pool_blocks(v_pool, tabs, Hkv, v_scale, x.dtype)
        with jax.named_scope("paged_attn"):
            scores = jnp.einsum("bkgd,bskd->bkgs", q, kc).astype(jnp.float32)
            scores *= scale
            idx = jax.lax.broadcasted_iota(jnp.int32, (1, 1, 1, NB * bs), 3)
            # block tables keep logical order, so cache-index distance IS
            # logical distance — same banding as the static decode
            scores = causal_band(scores, idx, lengths[:, None, None, None],
                                 cfg.attn_window)
            probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
            attn = jnp.einsum("bkgs,bskd->bkgd", probs,
                              vc).reshape(B, 1, H * Dh)
    attn = _gate_output(attn, gate)
    with jax.named_scope("attn_out"):
        attn = _dense(attn, p["attn_out"], lora=lr("attn_out"))
    if k_scale is None:
        return h, attn, (k_pool, v_pool)
    return h, attn, (k_pool, v_pool, k_scale, v_scale)


def _block_verify_paged(x, pools, tables, lengths, active, p,
                        cfg: GPTConfig, impl: str = "gather", lora=None,
                        base=0, plan=None):
    """One block for a G-token SPECULATIVE CHUNK per slot, K/V addressed
    through block tables — the q_len>1 generalization of
    _block_decode_paged for draft/verify serving. x: [B, G, D]; chunk
    token i of slot b sits at cache position lengths[b] + i. The chunk's
    K/V are scattered into the slot's CURRENT blocks before attention
    (within-chunk causality is then just the position mask); after the
    scheduler's accept/reject, ``lengths`` advances past the accepted
    prefix only — stale rejected entries are overwritten by the next
    chunk before any query can attend them, no copy needed.

    Writes beyond the slot's allocated capacity (tokens_per_slot) route
    to the trash block, mirroring _block_decode_paged: the scheduler
    caps acceptance at the allocated capacity so logits from those
    positions are never used.

    With four ``pools`` (int8 + scales) the write is a
    read-modify-requantize of the W consecutive blocks the G-token chunk
    can straddle (W = 1 + ceil((G-1)/block)). Two pools trace the exact
    pre-quant program; ``lora=None`` the exact base-only program;
    ``pools`` / ``base`` and the return as in _block_decode_paged."""
    B, G, D = x.shape
    H, Dh = cfg.n_heads, cfg.head_dim
    Hkv = cfg.kv_heads
    group = H // Hkv
    k_pool, v_pool = pools[:2]
    k_scale, v_scale = pools[2:] if len(pools) == 4 else (None, None)
    bs = k_pool.shape[1]
    NB = tables.shape[1]
    lr = (lambda t: None) if lora is None else lora.get

    h = _norm(x, p["ln1"], cfg)
    qkv = _dense(h, p["qkv"], lora=lr("qkv"))
    pos = lengths[:, None] + jnp.arange(G, dtype=jnp.int32)[None]  # [B, G]
    q, k, v = _qkv_split_rotary(qkv, cfg, pos, B, G)
    qg = q.reshape(B, G, Hkv, group, Dh)

    # scatter the chunk's K/V through the block table; out-of-capacity
    # or inactive lanes land in the layer's trash block (same
    # belt-and-suspender as the one-token decode scatter)
    in_cap = pos < NB * bs
    if k_scale is None:
        blk = jnp.take_along_axis(tables, jnp.clip(pos // bs, 0, NB - 1),
                                  axis=1)                        # [B, G]
        blk = jnp.where(jnp.logical_and(active[:, None], in_cap), blk,
                        0) + base
        off = pos % bs
        k_pool = k_pool.at[blk, off].set(_rows(k))
        v_pool = v_pool.at[blk, off].set(_rows(v))
    else:
        # read-modify-requantize the W consecutive table entries the
        # chunk can touch, starting at the block holding position
        # lengths[b]
        W = 1 + (G + bs - 2) // bs
        j0 = lengths // bs                                       # [B]
        wj = j0[:, None] + jnp.arange(W, dtype=jnp.int32)[None]  # [B, W]
        wjc = jnp.clip(wj, 0, NB - 1)
        blkw = jnp.take_along_axis(tables, wjc, axis=1)          # [B, W]
        kb = quantizer.kv_dequantize_blocks(
            _heads(k_pool[blkw + base], Hkv), k_scale[blkw + base])
        vb = quantizer.kv_dequantize_blocks(
            _heads(v_pool[blkw + base], Hkv), v_scale[blkw + base])
        # chunk token i of slot b lands at window-flat lane
        # (pos//bs - j0)*bs + pos%bs; masked lanes drop out of bounds
        tgt = (pos // bs - j0[:, None]) * bs + pos % bs          # [B, G]
        writable = jnp.logical_and(active[:, None], in_cap)
        tgt = jnp.where(writable, tgt, W * bs)
        rows = jnp.arange(B, dtype=jnp.int32)[:, None]
        kb = kb.reshape(B, W * bs, Hkv, Dh).at[rows, tgt].set(
            k.astype(jnp.float32),
            mode="drop").reshape(B, W, bs, Hkv, Dh)
        vb = vb.reshape(B, W * bs, Hkv, Dh).at[rows, tgt].set(
            v.astype(jnp.float32),
            mode="drop").reshape(B, W, bs, Hkv, Dh)
        # lanes at global positions past the chunk's end are stale
        glob = wj[:, :, None] * bs + \
            jnp.arange(bs, dtype=jnp.int32)[None, None, :]       # [B, W, bs]
        new_len = jnp.minimum(lengths + G, NB * bs)
        live = glob < new_len[:, None, None]
        kq, ksn = quantizer.kv_requantize_blocks(kb, live)
        vq, vsn = quantizer.kv_requantize_blocks(vb, live)
        # window entries past the slot's last written block (and inactive
        # slots entirely) route to the trash block
        jhi = jnp.minimum((lengths + G - 1) // bs, NB - 1)
        touched = jnp.logical_and(wj <= jhi[:, None], active[:, None])
        blkw = jnp.where(touched, blkw, 0) + base
        k_pool = k_pool.at[blkw].set(_rows(kq))
        v_pool = v_pool.at[blkw].set(_rows(vq))
        k_scale = k_scale.at[blkw].set(ksn)
        v_scale = v_scale.at[blkw].set(vsn)

    scale = cfg.attn_scale if cfg.attn_scale is not None \
        else 1.0 / np.sqrt(Dh)
    tabs = tables + base                 # this layer's blocks
    if impl == "pallas":
        from deepspeed_tpu.ops.attention.paged import paged_verify_attention
        attn = paged_verify_attention(
            qg, k_pool, v_pool, tabs, lengths, scale=float(scale),
            window=cfg.attn_window, k_scale=k_scale,
            v_scale=v_scale, plan=plan).reshape(B, G, D)
    else:
        # [B, NB*bs, Hkv, Dh]
        kc = gather_pool_blocks(k_pool, tabs, Hkv, k_scale, x.dtype)
        vc = gather_pool_blocks(v_pool, tabs, Hkv, v_scale, x.dtype)
        scores = jnp.einsum("bqkgd,bskd->bkgqs", qg, kc).astype(jnp.float32)
        scores *= scale
        idx = jax.lax.broadcasted_iota(jnp.int32, (1, 1, 1, 1, NB * bs), 4)
        scores = causal_band(scores, idx, pos[:, None, None, :, None],
                             cfg.attn_window)
        probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
        attn = jnp.einsum("bkgqs,bskd->bqkgd", probs, vc).reshape(B, G, D)
    attn = _dense(attn, p["attn_out"], lora=lr("attn_out"))
    if cfg.parallel_residual:
        y = x + attn + _ffn(h, p, cfg, lora=lora)
    else:
        x = x + attn
        h = _norm(x, p["ln2"], cfg)
        y = x + _ffn(h, p, cfg, lora=lora)
    if k_scale is None:
        return y, (k_pool, v_pool)
    return y, (k_pool, v_pool, k_scale, v_scale)


# the lengths a prefill chunk's read is compiled for: at most this many
# prefixes of a slot's row, each a whole number of tiles
PREFILL_READ_LENGTHS = 8


def attended_tiles(start, n, bs: int, nb: int, window: Optional[int] = None):
    """The part of its slot's row that a prefill chunk of ``n`` tokens at
    position ``start`` reads from the pool, in tiles of ``P`` table entries:
    (first tile, end tile, ``P``). The chunk's queries sit at ``[start,
    start + n)`` and see nothing past themselves, so the end is the tile
    past position ``start + n - 1``; with ``window`` the first is the tile
    of the oldest key the chunk's FIRST query sees. ``P`` follows from the
    shapes: the decode kernel's tile (``blocks_per_step``: 128 positions,
    or a small table whole), widened until ``PREFILL_READ_LENGTHS`` tiles
    cover the table. Integer arithmetic that holds for traced ``start`` and
    ``n`` (the program's choice of a length) and for Python ones (the
    scheduler's ``attended`` count)."""
    P = blocks_per_step(nb, bs)
    P *= -(-nb // (P * PREFILL_READ_LENGTHS))
    lo = 0
    if window is not None:
        lo = (start - window + 1) // (P * bs)
        lo = lo * (lo > 0)        # max(lo, 0), for a tracer and an int alike
    return lo, (start + n + P * bs - 1) // (P * bs), P


def _attend_rows(q, kc, vc, positions, first, cfg: GPTConfig):
    """One softmax of a prompt chunk's queries ``q`` ``[C, H, Dh]`` at
    ``positions`` over gathered rows ``kc``, ``vc`` ``[S, Hkv, Dh]`` that
    sit at positions ``first + [0, S)`` of the slot's row; the causal band
    (and ``cfg.attn_window``) masks what a query may not see. Returns
    ``[C, H * Dh]``."""
    C, H, Dh = q.shape
    S, Hkv = kc.shape[:2]
    with jax.named_scope("paged_attn"):
        qg = q.reshape(C, Hkv, H // Hkv, Dh)
        scores = jnp.einsum("ckgd,skd->ckgs", qg, kc).astype(jnp.float32)
        scores *= cfg.attn_scale if cfg.attn_scale is not None \
            else 1.0 / np.sqrt(Dh)
        sidx = first + jax.lax.broadcasted_iota(jnp.int32, (1, 1, 1, S), 3)
        scores = causal_band(scores, sidx, positions[:, None, None, None],
                             cfg.attn_window)
        probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
        return jnp.einsum("ckgs,skd->ckgd", probs, vc).reshape(C, H * Dh)


def _attend_occupied(q, k_pool, v_pool, trow, positions, n_valid,
                     cfg: GPTConfig, window: Optional[int], rows=None):
    """Attention of one slot's prompt chunk over the OCCUPIED part of its
    row. ``q`` ``[C, H, Dh]`` at ``positions``; the pools hold the chunk's
    own K and V already; ``trow`` ``[NB]`` this layer's block ids. One dense
    pass (gather, unfold to heads, ``_attend_rows``) over the shortest run
    of whole tiles that holds every key a valid query may see
    (``attended_tiles``), picked by ``lax.switch`` from the lengths the
    table's shape allows: a chunk at ``start`` 0 of a 1,024-position row
    reads 128 positions, not 1,024. What the run holds past a query's
    position (a block's unwritten tail, stale rows of the blocks' earlier
    owner) the causal band masks.
    The branches take the pools as read-only operands and return the
    attention only: a branch that returns a pool copies it (PERF.md, PR 40).
    Returns ``[C, H * Dh]``.

    ``window``: the layer's (the plain blocks': ``cfg.attn_window``).
    ``rows(q, kc, vc, positions, first)``: another dense pass than
    ``_attend_rows`` over the run's rows (inference/hybrid.py: its own cut
    of the scores; a window layer's ring, a row whose chunk is not in the
    pool: ``n_valid`` 0); what it returns is returned."""
    Hkv, Dh = cfg.kv_heads, cfg.head_dim
    bs, NB = k_pool.shape[1], trow.shape[0]
    lo, hi, P = attended_tiles(positions[0], n_valid, bs, NB, window)
    if rows is None:
        rows = partial(_attend_rows, cfg=cfg)
    tiles = -(-NB // P)
    # whole tiles of table entries: past the table's end the last entry
    # again, at positions no query reaches
    if tiles * P > NB:
        trow = jnp.concatenate(
            [trow, jnp.broadcast_to(trow[-1:], (tiles * P - NB,))])

    def dense(n, q, k_pool, v_pool, trow, positions, lo):
        blocks = jax.lax.dynamic_slice_in_dim(trow, lo * P, n * P)
        with jax.named_scope("kv_gather"):
            kc = _heads(k_pool[blocks], Hkv).reshape(n * P * bs, Hkv, Dh)
            vc = _heads(v_pool[blocks], Hkv).reshape(n * P * bs, Hkv, Dh)
        return rows(q, kc, vc, positions, lo * P * bs)

    return jax.lax.switch(
        jnp.clip(hi - lo, 1, tiles) - 1,
        [partial(dense, n) for n in range(1, tiles + 1)],
        q, k_pool, v_pool, trow, positions, jnp.int32(lo))


def _grouped_rows(cfg: GPTConfig):
    """The dense pass of :func:`_attend_occupied` for GROUPED queries on
    several K/V heads (``1 < kv_heads < n_heads``), or None for the two
    shapes ``_attend_rows``' one product serves well (every head its own
    K/V head; all heads on one): inference/hybrid.py's ``_attend``, a KV
    head at a time and, where a head's float32 scores pass its
    ``SCORE_BYTES``, a block of queries at a time. ``_attend_rows``' one
    einsum over ``[C, Hkv, group, S]`` compiles, with both a head's group
    and the K/V heads in it, to a convolution that keeps the chunk's
    queries on the lanes: 16 ms a layer for every 3,072 keys of history at
    2 K/V heads under 8 query rows of 256 (PERF.md, PR 54). ``_attend``
    scales by ``1 / sqrt(head_dim)``: a config with another ``attn_scale``
    keeps ``_attend_rows``."""
    H, Hkv = cfg.n_heads, cfg.kv_heads
    if Hkv in (1, H) or cfg.attn_scale is not None:
        return None
    from deepspeed_tpu.inference.hybrid import _attend

    def rows(q, kc, vc, positions, first):
        C = q.shape[0]
        kpos = first + jnp.arange(kc.shape[0], dtype=jnp.int32)
        with jax.named_scope("paged_attn"):
            out = _attend(q.reshape(C, Hkv, H // Hkv, -1), kc, vc,
                          positions, kpos, cfg.attn_window)
        return out.reshape(C, -1)
    return rows


def _block_prefill_paged(x, pools, table_row, positions, n_valid, p,
                         cfg: GPTConfig, lora=None, base=0):
    """One block over a PROMPT CHUNK for one slot:
    :func:`_attn_prefill_paged` and the block's FFN behind it. Returns (y,
    pools)."""
    h, attn, pools = _attn_prefill_paged(x, pools, table_row, positions,
                                         n_valid, p, cfg, lora=lora,
                                         base=base)
    return _mlp_behind(x, h, attn, p, cfg, lora), pools


def _attn_prefill_paged(x, pools, table_row, positions, n_valid, p,
                        cfg: GPTConfig, lora=None, base=0):
    """The attention of one block over a PROMPT CHUNK for one slot (what
    inference/ssm.py calls by itself, as _attn_decode_paged), writing the
    chunk's K/V through the slot's block table and attending over the
    OCCUPIED part of the slot's row (history from earlier chunks + this
    chunk, in whole tiles: ``_attend_occupied``; nothing past the tile of
    the chunk's last token is gathered, unfolded or scored) — the
    prefill-chunking path that keeps decode latency bounded for long
    prompts. x: [1, C, D]; positions: [C] global cache positions of the
    chunk tokens; n_valid: how many of the C lanes are real (the chunk is
    padded to a fixed width so ONE compiled program serves every chunk;
    the length read follows from ``positions[0]`` and ``n_valid`` inside
    it).

    With four ``pools`` (int8 + scales) the slot's whole virtual row
    (gathered for attention anyway) is dequantized, the chunk inserted,
    and ONLY the chunk-touched blocks requantized — untouched blocks
    (including shared prefix blocks mapped read-only) are written back
    byte-identical, so sharing semantics are preserved. Two pools trace
    the exact pre-quant program; ``lora=None`` the exact base-only
    program (here the gathered factors carry the prefill row's B=1
    leading dim); ``pools`` / ``base`` and the return as in
    _attn_decode_paged."""
    B, C, _ = x.shape
    Dh, Hkv = cfg.head_dim, cfg.kv_heads
    k_pool, v_pool = pools[:2]
    k_scale, v_scale = pools[2:] if len(pools) == 4 else (None, None)
    bs = k_pool.shape[1]
    NB = table_row.shape[0]
    lr = (lambda t: None) if lora is None else lora.get

    with jax.named_scope("attn_qkv"):
        h = _norm(x, p["ln1"], cfg)
        qkv = _dense(h, p["qkv"], lora=lr("qkv"))
        gate = None
        if _heads_by_config(cfg):
            q, k, v, gate = _qkv_heads(qkv, p, cfg, positions[None], B, C)
        else:
            q, k, v = gpt_lib._qkv_split_rotary(qkv, cfg, positions[None],
                                                B, C)

    valid = jnp.arange(C) < n_valid
    trow = table_row + base              # this layer's blocks
    if k_scale is None:
        with jax.named_scope("kv_write"):
            k_pool = paged_cache.write_chunk(
                k_pool, table_row, positions[0], n_valid, _rows(k[0]), base)
            v_pool = paged_cache.write_chunk(
                v_pool, table_row, positions[0], n_valid, _rows(v[0]), base)
        attn = _attend_occupied(q[0], k_pool, v_pool, trow, positions,
                                n_valid, cfg, cfg.attn_window,
                                _grouped_rows(cfg))[None]
    else:
        with jax.named_scope("kv_write"):
            kq0 = _heads(k_pool[trow], Hkv)
            vq0 = _heads(v_pool[trow], Hkv)
            kb = quantizer.kv_dequantize_blocks(kq0, k_scale[trow])
            vb = quantizer.kv_dequantize_blocks(vq0, v_scale[trow])
            tgt = jnp.where(jnp.logical_and(valid, positions < NB * bs),
                            positions, NB * bs)  # padded lanes drop
            kb = kb.reshape(NB * bs, Hkv, Dh).at[tgt].set(
                k[0].astype(jnp.float32), mode="drop").reshape(NB, bs, Hkv, Dh)
            vb = vb.reshape(NB * bs, Hkv, Dh).at[tgt].set(
                v[0].astype(jnp.float32), mode="drop").reshape(NB, bs, Hkv, Dh)
            start = positions[0]
            new_total = start + n_valid
            glob = jnp.arange(NB, dtype=jnp.int32)[:, None] * bs + \
                jnp.arange(bs, dtype=jnp.int32)[None]
            live = glob < new_total
            kq, ksn = quantizer.kv_requantize_blocks(kb, live)
            vq, vsn = quantizer.kv_requantize_blocks(vb, live)
            # requantize only the chunk-touched blocks; everything else is
            # scattered back byte-identical (shared prefix blocks included)
            j = jnp.arange(NB, dtype=jnp.int32)
            j0 = start // bs
            j1 = jnp.maximum(start + n_valid - 1, start) // bs
            touched = jnp.logical_and(j >= j0, j <= j1)
            kq = jnp.where(touched[:, None, None, None], kq, kq0)
            vq = jnp.where(touched[:, None, None, None], vq, vq0)
            ksn = jnp.where(touched[:, None], ksn, k_scale[trow])
            vsn = jnp.where(touched[:, None], vsn, v_scale[trow])
            k_pool = k_pool.at[trow].set(_rows(kq))
            v_pool = v_pool.at[trow].set(_rows(vq))
            k_scale = k_scale.at[trow].set(ksn)
            v_scale = v_scale.at[trow].set(vsn)
        with jax.named_scope("kv_gather"):
            # attend over exactly what the pool now holds
            kc = quantizer.kv_dequantize_blocks(
                kq, ksn, dtype=x.dtype).reshape(NB * bs, Hkv, Dh)
            vc = quantizer.kv_dequantize_blocks(
                vq, vsn, dtype=x.dtype).reshape(NB * bs, Hkv, Dh)
        attn = _attend_rows(q[0], kc, vc, positions, 0, cfg)[None]
    attn = _gate_output(attn, gate)
    with jax.named_scope("attn_out"):
        attn = _dense(attn, p["attn_out"], lora=lr("attn_out"))
    if k_scale is None:
        return h, attn, (k_pool, v_pool)
    return h, attn, (k_pool, v_pool, k_scale, v_scale)


def tile_reads(cfg, start: int, n: int, bs: int, nb: int,
               windowed: bool = True) -> int:
    """Positions of its slot's row a prefill chunk of the blocks above
    reads: whole :func:`attended_tiles`, its own rows among them. Not
    ``windowed``: in a layer that sees its whole history whatever
    ``cfg.attn_window`` says (inference/hybrid.py's full layers)."""
    lo, hi, P = attended_tiles(start, n, bs, nb,
                               cfg.attn_window if windowed else None)
    return min(max(hi - lo, 1) * P * bs, nb * bs)


def _plain_state(cfg, num_blocks: int, block_size: int, num_slots: int,
                 dtype):
    # one row per cached token, its kv heads folded side by side
    # (paged_cache's module docstring: one layout in HBM)
    k = jnp.zeros((cfg.n_layers, num_blocks, block_size,
                   cfg.kv_heads * cfg.head_dim), dtype)
    return k, jnp.zeros_like(k)


def _plain_prefill(eng, params, pools, x, table_row, positions, n_valid,
                   slot, lora):
    def block(x, pools, layer_p, base, lora):
        return _block_prefill_paged(x, pools, table_row, positions, n_valid,
                                    layer_p, eng.cfg, lora=lora, base=base)
    return _scan_layers(block, x, params, pools, lora)


def _plain_decode(eng, params, pools, x, tables, lengths, active, impl,
                  lora):
    plan = _paged_plan(pools, tables, lengths, active, eng.cfg)

    def block(x, pools, layer_p, base, lora):
        return _block_decode_paged(x, pools, tables, lengths, active,
                                   layer_p, eng.cfg, impl=impl, lora=lora,
                                   base=base, plan=plan)
    return _scan_layers(block, x, params, pools, lora)


# the plain K and V pools: owns every config no other dialect does, refuses
# nothing and carries int8 scales and LoRA
DIALECT = dialect.Dialect(
    owns=lambda cfg: True, new_state=_plain_state, pool=lambda k: k,
    prefill_layers=_plain_prefill, decode_layers=_plain_decode,
    prefill_reads=tile_reads)


class InferenceEngine:
    """Generation engine over a GPT-layout parameter pytree.

    Construct via ``deepspeed_tpu.init_inference(model=...)`` where model is
    either (GPTConfig, params) from this framework or anything a policy in
    inference/policy.py can convert (e.g. an HF GPT-2 checkpoint).
    """

    def __init__(self, model=None, *, config: Optional[GPTConfig] = None,
                 params: Optional[PyTree] = None, mp_size: int = 1,
                 dtype=jnp.bfloat16, max_seq_len: Optional[int] = None,
                 mesh: Optional[Mesh] = None,
                 replace_with_kernel_inject: bool = True,
                 checkpoint: Optional[str] = None,
                 decode_impl: Optional[str] = None, **kwargs):
        if model is not None and (config is None or params is None):
            from deepspeed_tpu.inference.policy import resolve_model
            config, params = resolve_model(model)
        if checkpoint is not None:
            # trained weights from a sharded training checkpoint override
            # whatever the model/policy supplied (ref: engine.py:281
            # _load_checkpoint resharding trained weights into the skeleton)
            from deepspeed_tpu.runtime.checkpointing import \
                load_fp32_state_dict_from_zero_checkpoint
            params = load_fp32_state_dict_from_zero_checkpoint(checkpoint)
        assert config is not None and params is not None, \
            "need a model config: pass (config, params), or a model a " \
            "policy understands (checkpoint= supplies weights only)"
        self.cfg = config
        self.dtype = dtype
        self.max_seq_len = max_seq_len or config.max_seq_len
        self.mp_size = mp_size
        self.latency_ms: Dict[str, float] = {}
        # paged decode attention path: "pallas" (flash-decode through the
        # block table) or "gather" (dense reference); default resolves
        # DS_PAGED_DECODE_IMPL then platform (pallas on TPU)
        from deepspeed_tpu.ops.attention.paged import resolve_decode_impl
        self.decode_impl = resolve_decode_impl(decode_impl)

        if mesh is None:
            # one engine drives mp_size devices, the first of the host by
            # default. Replicating one engine over every chip only repeats
            # one chip's work: the many-chip form is one engine per device
            # (pass ``mesh``) behind ReplicaRouter
            devs = jax.devices()
            assert len(devs) >= mp_size, (len(devs), mp_size)
            mesh = mesh_lib.make_mesh(
                mesh_lib.MeshSpec(data=1, model=mp_size), devs[:mp_size])
        self.mesh = mesh

        from deepspeed_tpu.models.bert import BertConfig as _BertConfig
        self.is_encoder = isinstance(config, _BertConfig)
        if self.is_encoder and config.dtype != dtype:
            # bert.encode casts by cfg.dtype; keep it in the engine dtype
            self.cfg = config = dataclasses.replace(config, dtype=dtype)

        # dtype conversion (ref: engine.py:335 _convert_to_dtype) + TP placement
        # dtype=jnp.int8 selects weight-only int8 (API parity with the
        # reference's init_inference(dtype=torch.int8) quantize path):
        # kernels stored int8 + per-channel scales, activations bf16
        self.quantized = (jnp.dtype(dtype) == jnp.int8)
        if self.quantized:
            from deepspeed_tpu.utils import on_tpu
            dtype = jnp.bfloat16 if on_tpu() else jnp.float32
            self.dtype = dtype
        params = jax.tree_util.tree_map(
            lambda x: jnp.asarray(x, dtype) if jnp.issubdtype(
                jnp.asarray(x).dtype, jnp.floating) else jnp.asarray(x),
            params)
        if self.quantized:
            if self.is_encoder:
                raise ValueError("weight-only int8 currently covers the "
                                 "decoder path (GPT/llama/MoE layouts)")
            params = quantize_weights_int8(params)
        if mp_size > 1:
            from deepspeed_tpu.models.bert import bert_partition_rules
            rules = bert_partition_rules() if self.is_encoder \
                else gpt_lib.gpt_partition_rules()
            if self.quantized:
                # int8 records replace kernel with q (same shape, same
                # spec) + a [..., 1, out] per-channel scale whose -2 axis
                # must stay unsharded (size 1)
                from deepspeed_tpu.parallel.sharding import PartitionRule
                extra = []
                for r in rules:
                    pat = r.pattern.pattern
                    if "/kernel" in pat:
                        extra.append(PartitionRule(
                            pat.replace("/kernel", "/q"), r.spec))
                        sc = list(r.spec)
                        if len(sc) >= 2:
                            sc[-2] = None
                        extra.append(PartitionRule(
                            pat.replace("/kernel", "/scale"), P(*sc)))
                rules = rules + extra
        else:
            rules = []
        pspecs = sharding_lib.param_specs(params, mesh, zero_stage=0,
                                          rules=rules)
        params, tables = whole_lane_tables(params, pspecs)
        self.params = jax.device_put(
            params, sharding_lib.to_named(pspecs, mesh))

        if self.is_encoder:
            self._forward = jax.jit(self._encoder_forward_fn)
            self._prefill = self._decode = None
        else:
            self._prefill = jax.jit(self._prefill_fn)
            self._decode = jax.jit(self._decode_fn, donate_argnums=(1,))
            self._forward = jax.jit(self._forward_fn)
            # paged-serving programs: ONE jitted callable per family. The
            # int8 scale pools and the adapter operands come behind the
            # positional operands as optional pytrees (``scales``, ``lora``:
            # None when absent), so the fp, int8, adapter and int8+adapter
            # variants of a family are cache entries of its one callable,
            # told apart by jax from the structure of what it is handed,
            # as a dialect's state rides in k_pool's place. A run serves
            # one variant and compiles one entry per family: steady state
            # is the same two programs (prefill, and decode or its horizon
            # or verify form) whatever the variant and the arrival pattern.
            # Pools and scales are donated, so the cache never doubles in
            # HBM across a step; the adapter pools are read-only. impl
            # ("gather" | "pallas") and the horizon's n_steps are static:
            # a run pins both. The two programs of every run carry explicit
            # module names (jit_serve_prefill_slot, jit_serve_decode_slots):
            # a profile and the provenance table name a program by them.
            # Each family is jitted behind ONE packed host operand
            # (_packed): its layout, static, also carries impl and n_steps.
            # The fused multi-step decode (DS_DECODE_HORIZON > 1,
            # docs/MULTISTEP.md) is never compiled by N=1 serving; with
            # spec_decode on, verify REPLACES the plain decode program in
            # steady state (the chunk width G is fixed per serving engine)
            def program(fn, name):
                return jax.jit(_packed(fn, name), static_argnames=("layout",),
                               donate_argnames=("k_pool", "v_pool", "scales"))
            self._prefill_slot = program(self._prefill_slot_fn,
                                         "serve_prefill_slot")
            self._decode_slots = program(self._decode_slots_fn,
                                         "serve_decode_slots")
            self._decode_horizon = program(self._decode_horizon_fn,
                                           "_decode_horizon_fn")
            self._verify_slots = program(self._verify_slots_fn,
                                         "_verify_slots_fn")
            # static-path chunk verify (inference/speculative.py): the
            # dense-cache counterpart of _verify_slots, kept here so the
            # speculative module shares the engine's compiled program
            # cache instead of duplicating the block math
            self._extend = jax.jit(self._extend_fn, donate_argnums=(1,))
            # block copies over the tuple of pools (scales travel with
            # their payload): prefix-cache copy-on-write, and the host
            # tier's spill gather (pools stay live while the copy rides
            # out) and restore scatter. Block ids are traced and widths
            # fixed per cache, so each is one entry, warmed at
            # ServingEngine construction (paged_cache.warm_cow,
            # warm_host_tier): steady state compiles nothing. Each is a
            # partial of this engine's own: jax keys a jit's cache by the
            # function it wraps, and an engine's entries are its alone
            self._cow_blocks = jax.jit(partial(paged_cache.copy_block),
                                       donate_argnums=(0,))
            self._gather_blocks = jax.jit(partial(paged_cache.gather_blocks))
            self._scatter_block = jax.jit(
                partial(paged_cache.scatter_block), donate_argnums=(0,))
        if self.dialect.refusal is not None:
            # two kinds of attention state, a latent pool, per-slot tails
            # or a recurrent state beside the pools: only the two paged
            # serving programs know them. Everything else raises by name
            # rather than grow a copy of the dialect (ROADMAP D4)
            def refused(what, *a, **k):
                dialect.refuse(config, what)
            if mp_size > 1:
                refused("tensor parallelism (mp_size > 1)")
            for attr, what in (
                    ("_prefill", "the static-cache prefill (generate)"),
                    ("_decode", "the static-cache decode (generate)"),
                    ("_forward", "the cacheless forward"),
                    ("_extend", "static speculative verify"),
                    ("_decode_horizon", "the fused decode horizon"),
                    ("_verify_slots", "speculative verify"),
                    ("_cow_blocks", "prefix-cache copy-on-write"),
                    ("_gather_blocks", "the host tier"),
                    ("_scatter_block", "the host tier")):
                setattr(self, attr, functools.partial(refused, what))
        # a ProgramCostRegistry that wants the compiled text of each
        # serving program (a ServingEngine with telemetry on sets it)
        self.provenance = None
        # (host operands, their bytes) of the last paged dispatch (_run)
        self.h2d = (0, 0)
        dev0 = mesh.devices.flat[0]
        log_dist(f"inference engine ready: {config.n_layers}L/"
                 f"{config.d_model}d mp={mp_size} "
                 f"dtype={jnp.dtype(dtype).name} "
                 f"{'encoder' if self.is_encoder else 'decoder'}, "
                 f"platform={dev0.platform}, devices={mesh.devices.size}, "
                 f"decode_impl={self.decode_impl}{tables}"
                 f"{self.dialect.ready_note(config, self.decode_impl)}",
                 ranks=[0])

    # ------------------------------------------------------------------
    # params are threaded explicitly (never via self) so jit treats the
    # weights as arguments, not baked-in constants
    # the two tables are read through _wte / _wpe / tied_logits alone: the
    # engine may hold them with padded lanes (whole_lane_tables)
    def _wte(self, params, tokens):
        return table_lanes(params["wte"]["embedding"][tokens],
                           self.cfg.d_model)

    def _wpe(self, params, positions):
        """``positions``: an index array or a slice."""
        return table_lanes(params["wpe"]["embedding"][positions],
                           self.cfg.d_model)

    def _embed(self, params, tokens):
        S = tokens.shape[1]
        x = self._wte(params, tokens)
        if self.cfg.use_wpe:
            x = x + self._wpe(params, slice(None, S))[None]
        return x

    def _logits(self, params, x):
        from deepspeed_tpu.models.gpt import _kernel_of
        with jax.named_scope("logits"):
            x = _norm(x, params["ln_f"], self.cfg)
            if self.cfg.tie_embeddings:
                return tied_logits(x, params["wte"]["embedding"])
            logits = x @ _kernel_of(params["lm_head"], x.dtype)
            if "bias" in params["lm_head"]:
                logits = logits + params["lm_head"]["bias"]
            return logits

    def _prefill_fn(self, params, tokens, attn_mask=None):
        """Run the prompt, build the cache, return last-position logits.

        attn_mask: optional [B, S] validity for LEFT-padded batched
        prompts (1 = real token); positional embeddings restart per row
        and padded keys never receive attention."""
        cfg = self.cfg
        B, S = tokens.shape
        S_max = self.max_seq_len
        positions = None
        if attn_mask is None:
            x = self._embed(params, tokens)
        else:
            # per-row positions restart after the left padding
            positions = jnp.clip(
                jnp.cumsum(attn_mask.astype(jnp.int32), axis=1) - 1,
                0, None)
            x = self._wte(params, tokens)
            if cfg.use_wpe:
                x = x + self._wpe(params, positions)

        def body(x, layer_p):
            y, k, v = _block_prefill(x, layer_p, cfg, kv_mask=attn_mask,
                                     positions=positions)
            return y, (k, v)

        x, (ks, vs) = jax.lax.scan(body, x, params["block"])
        # ks: [L, B, S, H, Dh] -> pad to S_max
        pad = [(0, 0), (0, 0), (0, S_max - S), (0, 0), (0, 0)]
        cache = {"k": jnp.pad(ks, pad), "v": jnp.pad(vs, pad)}
        if attn_mask is not None:
            # decode slots (>= S) are always valid once written
            cache["mask"] = jnp.concatenate(
                [attn_mask.astype(jnp.float32),
                 jnp.ones((B, S_max - S), jnp.float32)], axis=1)
        logits = self._logits(params, x[:, -1:])
        return logits, cache

    def _decode_fn(self, params, cache, token, pos, row_pos=None):
        """One token step. token: [B, 1]; pos: scalar cache index;
        row_pos: optional [B] per-row LOGICAL positions (left-padded
        batches, where real lengths differ from the cache index)."""
        cfg = self.cfg
        x = self._wte(params, token)
        if cfg.use_wpe:
            if row_pos is not None:
                x = x + self._wpe(params, row_pos)[:, None]
            else:
                x = x + table_lanes(jax.lax.dynamic_slice_in_dim(
                    params["wpe"]["embedding"], pos, 1), cfg.d_model)[None]
        cache_mask = cache.get("mask")

        def body(x, layer):
            layer_p, kc, vc = layer
            y, kc, vc = _block_decode(x, kc, vc, pos, layer_p, cfg,
                                      cache_mask=cache_mask,
                                      row_pos=row_pos)
            return y, (kc, vc)

        x, (ks, vs) = jax.lax.scan(body, x,
                                   (params["block"], cache["k"], cache["v"]))
        logits = self._logits(params, x)
        out = {"k": ks, "v": vs}
        if cache_mask is not None:
            out["mask"] = cache_mask
        return logits, out

    def _prefill_slot_fn(self, params, k_pool, v_pool, table_row, tokens,
                         start, n_valid, key, gen_count, temp, top_k,
                         top_p, rep_pen, seen_row, scales=None, lora=None,
                         slot=None):
        """Prefill ONE prompt chunk into one serving slot's paged cache.

        tokens: [C] fixed-width chunk (padded; n_valid real tokens);
        start: scalar — tokens already cached for this slot (0 for the
        first chunk, the resume point for later chunks / requeued
        requests, the MATCHED BOUNDARY for a prefix-cache hit whose
        shared blocks are already resident); table_row: [NB] the slot's
        block table. key..seen_row are the slot's sampling lane
        (inference/sampling.py — all DATA, so the compile contract is
        untouched); the fused sampler runs on the last valid position,
        meaningful once the final chunk lands. ``scales``: None, or the
        int8 pools' (k_scale, v_scale) of [L, N, Hkv] fp32, which then
        ride through the layers beside the pools (_scan_layers) and the
        block write is the read-modify-requantize path of
        _block_prefill_paged. ``lora``: None, or (a_pool, b_pool, the
        slot's adapter-table row [NBa]) (inference/adapters.py); an
        all-zeros row gathers the trash block — the base-only prefill
        bit-for-bit. ``slot``: the slot's index, which only a model with
        per-slot state beside the pools reads (inference/cca.py; the
        packed operand carries it anyway, for the sampler's row). Returns
        the last-valid-position logits, the
        sampled/greedy token [1], its logprob [1], and the updated
        (donated) pools, then scales."""
        cfg = self.cfg
        pools = (k_pool, v_pool) + (scales or ())
        lora_ops = None if lora is None \
            else (lora[0], lora[1], lora[2][None])
        C = tokens.shape[0]
        positions = start + jnp.arange(C, dtype=jnp.int32)
        with jax.named_scope("embed"):
            x = self._wte(params, tokens)[None]
            if cfg.use_wpe:
                safe = jnp.clip(positions, 0, self.max_seq_len - 1)
                x = x + self._wpe(params, safe)[None]
        x, pools = self.dialect.prefill_layers(
            self, params, pools, x, table_row, positions, n_valid, slot,
            lora_ops)
        last = jnp.clip(n_valid - 1, 0, C - 1)
        x_last = jax.lax.dynamic_slice_in_dim(x, last, 1, axis=1)
        logits = self._logits(params, x_last)
        tok, lp = sampling.sample_tokens(
            logits[:, -1], key.reshape(1, 2), gen_count.reshape(1),
            temp.reshape(1), top_k.reshape(1), top_p.reshape(1),
            rep_pen.reshape(1), seen_row.reshape(1, -1))
        return (logits, tok, lp) + pools

    def _decode_slots_fn(self, params, k_pool, v_pool, tables, lengths,
                         tokens, active, impl, keys, gen_counts, temps,
                         top_ks, top_ps, rep_pens, seen, scales=None,
                         lora=None):
        """One decode step for EVERY serving slot at once. tokens: [B]
        (each slot's pending token); lengths: [B] per-slot cache
        positions; active: [B] (inactive slots run but write to the
        trash block and their logits are discarded). The slot-batched
        shape is static, so any mix of requests reuses this one
        compiled program. impl is a STATIC jit argument ("gather" |
        "pallas") selecting the attention path per compiled program —
        see _block_decode_paged. keys..seen are the slot-indexed
        sampling arrays (inference/sampling.py) — DATA, never statics,
        so arbitrarily mixed greedy/sampled batches reuse this one
        program; the fused sampler emits each slot's next token (and
        its logprob) in the same dispatch as the forward step.
        ``scales`` / ``lora`` as in _prefill_slot_fn, with the per-slot
        adapter-table rows [B, NBa]: traced data like the lanes, so one
        entry decodes any mix of adapters and base-only slots."""
        cfg = self.cfg
        pools = (k_pool, v_pool) + (scales or ())
        with jax.named_scope("embed"):
            x = self._wte(params, tokens[:, None])
            if cfg.use_wpe:
                safe = jnp.clip(lengths, 0, self.max_seq_len - 1)
                x = x + self._wpe(params, safe)[:, None]
        x, pools = self.dialect.decode_layers(
            self, params, pools, x, tables, lengths, active, impl, lora)
        logits = self._logits(params, x)
        toks, lps = sampling.sample_tokens(
            logits[:, -1], keys, gen_counts, temps, top_ks, top_ps,
            rep_pens, seen)
        return (logits, toks, lps) + pools

    @functools.cached_property
    def dialect(self) -> dialect.Dialect:
        """The model's cache dialect (inference/dialect.py), asked once."""
        return dialect.of(self.cfg)

    def _dispatch_record(self, tokens: int, stats):
        """What a sparse model's layer loop carries beside ``x``: the
        dispatch's routing record ``[L_sparse, tokens, k]`` and, with
        ``stats`` (telemetry on), this dispatch's expert-layer counters."""
        cfg = self.cfg
        return {"route": jnp.zeros((cfg.n_sparse_layers, tokens, cfg.moe_k),
                                   jnp.int32),
                "stats": None if stats is None else jnp.zeros_like(stats[0])}

    def _dense_then_sparse(self, params, flat, bases, block, x, stats,
                           phase: int):
        """The leading dense layers and then the sparse layers, each ONE
        scan over _scan_layers with ``flat`` (the pools, stacked over
        layers) in the carry and ``bases`` = (dense, sparse) per-layer
        offsets into them. Beside ``x`` the block carries the dispatch's
        routing record and, with ``stats`` (telemetry on), the expert
        layers' counters, added to row ``phase`` of it; where the router
        has a state (moe/expert_share.py ``route_mlp``) that rides there
        too, ``r`` ``[T, R]`` float32, zeros under the first layer. A
        model with no leading dense layer runs the one scan. Data of the
        config, not of this loop: how many layers lead (``n_dense_layers``,
        0 where every layer holds both kinds of FFN), and which kind a
        layer of either stack holds, which its block reads off the layer's
        parameters (inference/hybrid.py ``ffn_kind``: "dense", "sparse" or
        "both"). Returns (x, flat, stats, route)."""
        cfg = self.cfg
        params, experts = split_experts(params)
        block = functools.partial(block, experts=experts)
        dense_b, sparse_b = bases
        aux = self._dispatch_record(x.shape[0] * x.shape[1], stats)
        if expert_share.has_router_state(cfg):
            aux["r"] = jnp.zeros((x.shape[0] * x.shape[1],
                                  cfg.router_hidden), jnp.float32)
        carry = (x, aux)
        if cfg.n_dense_layers:
            carry, flat = _scan_layers(block, carry, params, flat,
                                       stack="dense_block", bases=dense_b)
        (x, aux), flat = _scan_layers(block, carry, params, flat,
                                      bases=sparse_b)
        if stats is not None:
            stats = stats.at[phase].add(aux["stats"])
        return x, flat, stats, aux["route"]

    def _verify_slots_fn(self, params, k_pool, v_pool, tables, lengths,
                         tokens, active, impl="gather", scales=None,
                         lora=None):
        """One speculative VERIFY step for every serving slot at once:
        score all G chunk positions (pending token + G-1 draft tokens)
        per slot in one compiled program. tokens: [B, G] (chunk token i
        of slot b sits at cache position lengths[b] + i); returns logits
        [B, G, V] + updated (donated) pools. The slot-batched shape and
        the chunk width are static, so any mix of requests — across
        eviction, requeue and prefix-cache hits — reuses this ONE
        program; impl is a static jit argument exactly like
        _decode_slots_fn, and ``scales`` / ``lora`` are its too: each
        slot's draft chunk is scored under ITS adapter, so accept/reject
        stays lossless per tenant."""
        cfg = self.cfg
        pools = (k_pool, v_pool) + (scales or ())
        B, G = tokens.shape
        x = self._wte(params, tokens)
        if cfg.use_wpe:
            pos = lengths[:, None] + jnp.arange(G, dtype=jnp.int32)[None]
            safe = jnp.clip(pos, 0, self.max_seq_len - 1)
            x = x + self._wpe(params, safe)

        plan = _paged_plan(pools, tables, lengths, active, cfg, q_len=G)

        def block(x, pools, layer_p, base, lora):
            return _block_verify_paged(x, pools, tables, lengths, active,
                                       layer_p, cfg, impl=impl, lora=lora,
                                       base=base, plan=plan)

        x, pools = _scan_layers(block, x, params, pools, lora)
        return (self._logits(params, x),) + pools


    def _extend_fn(self, params, cache, tokens, pos):
        """G-token chunk verify over the STATIC dense cache (the
        speculative.py path): logits [B, G, V] + updated cache.
        tokens: [B, G]; pos: scalar first cache index of the chunk.
        The paged counterpart is _verify_slots_fn."""
        cfg = self.cfg

        x = self._wte(params, tokens)
        if cfg.use_wpe:
            G = tokens.shape[1]
            x = x + table_lanes(jax.lax.dynamic_slice_in_dim(
                params["wpe"]["embedding"], pos, G), cfg.d_model)[None]

        def body(x, layer):
            layer_p, kc, vc = layer
            y, kc, vc = _block_extend(x, kc, vc, pos, layer_p, cfg)
            return y, (kc, vc)

        x, (ks, vs) = jax.lax.scan(body, x,
                                   (params["block"], cache["k"],
                                    cache["v"]))
        logits = self._logits(params, x)
        return logits, {"k": ks, "v": vs}

    def _decode_horizon_fn(self, params, k_pool, v_pool, tables, lengths,
                           tokens, active, impl, n_steps, keys, gen_counts,
                           temps, top_ks, top_ps, rep_pens, seen, budgets,
                           eos_ids, stop_ids, stop_lens, tail, scales=None,
                           lora=None):
        """N fused decode iterations in ONE compiled program
        (docs/MULTISTEP.md): the _decode_slots_fn body — paged attention
        with trash-block write routing, the fused sampler with its pure
        fold_in key chain advanced per iteration — wrapped in an OUTER
        lax.scan over the step index, with the budget / eos /
        stop-sequence predicates evaluated in-program as per-slot done
        masks. A finished lane FREEZES: its length stops advancing (so
        its writes route to the trash block through the active mask),
        its carried token stops updating, and its later sampled lanes
        are dead outputs the harvest never reads (``produced`` counts
        the real ones). Iteration 0 is bit-identical to the N=1 decode
        program, and each later live iteration sees exactly the state
        the next N=1 dispatch would have seen (the key chain advances by
        the per-slot emitted count), so token streams match N=1
        bit-for-bit.

        n_steps joins impl as a STATIC jit argument — a serving run pins
        one N, so the steady-state program count is unchanged (and N=1
        serving never compiles this family at all). ``scales`` / ``lora``
        as in _decode_slots_fn: they compose through _scan_layers, the
        layer loop of every paged program, which carries the pools; the
        step scan here carries them between its iterations. budgets [B]
        (tokens this slot may emit this horizon), eos_ids [B] (-1 = none),
        stop_ids [B, S, W] right-aligned, stop_lens [B, S] (0 = unused
        row), tail [B, W] (the slot's last W emitted tokens, -1
        padded). Returns ([N, B] tokens, [N, B] logprobs, [B] produced,
        [B] done, pools...)."""
        cfg = self.cfg
        B = tokens.shape[0]
        W = tail.shape[1]
        rows = jnp.arange(B)

        def step(carry, i):
            tok, lens, live, produced, seen_c, tail_c, pools = carry
            lane_active = jnp.logical_and(active, live)
            x = self._wte(params, tok[:, None])
            if cfg.use_wpe:
                safe = jnp.clip(lens, 0, self.max_seq_len - 1)
                x = x + self._wpe(params, safe)[:, None]

            plan = _paged_plan(pools, tables, lens, lane_active, cfg)

            def block(x, pools, layer_p, base, lora):
                return _block_decode_paged(x, pools, tables, lens,
                                           lane_active, layer_p, cfg,
                                           impl=impl, lora=lora, base=base,
                                           plan=plan)

            x, pools = _scan_layers(block, x, params, pools, lora)
            logits = self._logits(params, x)
            toks_i, lps_i = sampling.sample_tokens(
                logits[:, -1], keys, gen_counts + i, temps, top_ks,
                top_ps, rep_pens, seen_c)

            emit = lane_active
            tok = jnp.where(emit, toks_i, tok)
            lens = lens + emit.astype(jnp.int32)
            produced = produced + emit.astype(jnp.int32)
            # the host mirror marks ``seen`` only on penalized lanes;
            # marking every emitting lane is bitwise-inert at pen==1.0
            # (the penalty divides by 1.0), so one program serves both
            marked = seen_c.at[rows, toks_i].set(True)
            seen_c = jnp.where(emit[:, None], marked, seen_c)
            rolled = jnp.concatenate([tail_c[:, 1:], toks_i[:, None]], 1)
            tail_c = jnp.where(emit[:, None], rolled, tail_c)

            total = gen_counts + produced
            budget_done = produced >= budgets
            eos_done = jnp.logical_and(eos_ids >= 0, toks_i == eos_ids)
            at = jnp.arange(W, dtype=jnp.int32)
            # right-aligned suffix compare, gated so the -1 tail padding
            # of a short stream can never satisfy a real stop row
            valid = at[None, None, :] >= (W - stop_lens)[:, :, None]
            hit = jnp.all(jnp.logical_or(jnp.logical_not(valid),
                                         tail_c[:, None, :] == stop_ids),
                          axis=-1)
            hit = jnp.logical_and(hit, stop_lens > 0)
            hit = jnp.logical_and(hit, total[:, None] >= stop_lens)
            done_now = jnp.logical_and(
                emit, budget_done | eos_done | jnp.any(hit, axis=-1))
            live = jnp.logical_and(live, jnp.logical_not(done_now))
            return (tok, lens, live, produced, seen_c, tail_c,
                    pools), (toks_i, lps_i)

        init = (tokens, lengths, active, jnp.zeros_like(lengths), seen,
                tail, (k_pool, v_pool) + (scales or ()))
        carry, (toks, lps) = jax.lax.scan(
            step, init, jnp.arange(n_steps, dtype=jnp.int32))
        _, _, live, produced, _, _, pools = carry
        return (toks, lps, produced, jnp.logical_not(live)) + pools

    # public wrappers: host-side numpy in, device pools threaded through
    # (``scales``: PagedKVCache.scales, None or the int8 pools' (k_scale,
    # v_scale)); what comes back ends in PagedKVCache.pools, updated: k, v
    # and then the scales. Each packs its host operands into the ONE buffer
    # its program is jitted behind (pack_operands, _packed). The
    # fault-injection sites fire BEFORE any dispatch touches the donated
    # pools, so a TransientDeviceError here is retryable by the serving
    # engine against intact buffers (utils/faults).
    _LANE_KINDS = ("u", "i", "f", "i", "f", "f")

    @classmethod
    def _samp_lanes(cls, sample_state, batch, vocab, scalar=False):
        """A ``sample_state`` tuple (sampling.SlotSamplerState ``lanes()`` /
        ``lane()``) as packed sections: keys, gen_counts, temps, top_ks,
        top_ps and rep_pens by their words, and the mask not at all: it is
        resident on the device, and a prefill (``scalar``) names its row by
        the slot. Returns (sections, the mask). None synthesizes the
        all-greedy lanes so legacy callers keep their behavior (and the one
        compiled program — greedy lanes are values, not a different
        signature); their mask is host zeros."""
        if sample_state is None:
            st = sampling.greedy_state(batch, vocab)
            sample_state = (*(a[0] for a in st[:-1]), 0, st[-1]) \
                if scalar else st
        *lanes, seen = sample_state
        parts = list(zip(cls._LANE_KINDS, lanes))   # the six small lanes
        parts.append(("row", lanes[-1]) if scalar else ("seen", None))
        return parts, seen

    def _run(self, stem: str, program, k_pool, v_pool, parts, seen=None,
             scales=None, lora=None, kernel_table=()):
        """The ONE dispatch of a paged serving program: family ``stem``'s
        jitted callable on ``(params, k_pool, v_pool, packed, layout, seen,
        scales, lora)``, ``parts`` being the program's operands as
        pack_operands takes them. ``scales`` and ``lora`` — the serving
        engine's ``(a_pool, b_pool, ablocks)`` from AdapterPool.lora_args,
        whose per-dispatch ``ablocks`` joins the buffer — are None when
        absent, and decide which cache entry of the callable runs and
        under which program id it is accounted (jit_registry.program_id).
        With int8 pools the ``cache.quantize`` site fires here, after the
        caller's ``engine.*`` site and before the dispatch touches the
        donated pools or scales. ``self.h2d`` keeps what this dispatch
        handed over from the host: (operands, bytes); (1, the buffer's)
        with a resident mask.

        Under telemetry the FIRST call of each program id also hands the
        text of its compiled module to the provenance table: lowering
        with the very arguments of the dispatch yields the executable
        the call below then runs (one compilation, not two), so the
        table is of what is loaded. The table also says whether the
        paged pool's one layout held in that executable:
        ``pool_copy_bytes``, logged here once per program, is 0 when no
        ``copy`` of a pool-shaped value was compiled in, and
        ``param_copy_bytes`` beside it 0 when none of a weight was
        (whole_lane_tables). A caller whose
        program attends through the ``paged_decode`` kernel gives its
        block tables' shape as ``kernel_table``, and the entry records
        how the kernel's grid is cut (``paged_blocks_per_step``,
        ``paged_grid_steps``: of the full table, where a model has a
        window ring's as well)."""
        if scales is not None:
            from deepspeed_tpu.utils.faults import maybe_fire
            maybe_fire("cache.quantize")
        if self.dialect.state is not None:
            k_pool = k_pool._replace(route=None)    # an output only
        if lora is not None:
            parts = (*parts, ("lora", lora[2]))
            lora = lora[:2]
        packed, layout = pack_operands(*parts)
        host = [a for a in (packed, seen) if isinstance(a, np.ndarray)]
        self.h2d = (len(host), sum(a.nbytes for a in host))
        pid = program_id(stem, scales is not None, lora is not None)
        args = (self.params, k_pool, v_pool, packed, layout, seen, scales,
                lora)
        sink = self.provenance
        if sink is not None and pid not in sink.provenance:
            pool = self.dialect.pool(k_pool)
            L, N, bs = pool.shape[:3]
            grid = ()
            if kernel_table:
                B, nb = kernel_table
                nb -= self.dialect.ring_blocks(self.cfg, bs)
                per_step = blocks_per_step(
                    nb, bs, self.dialect.tile_row_bytes(self.cfg, pool))
                grid = (per_step, B * -(-nb // per_step))
            copied = sink.add_provenance(
                pid, program.lower(*args).compile().as_text(),
                pool_blocks=(N, L * N), paged_grid=grid)
            log_dist(f"serving program {pid}: pool_copy_bytes={copied} "
                     f"param_copy_bytes="
                     f"{sink.entries[pid]['param_copy_bytes']}"
                     + (" paged_blocks_per_step={} paged_grid_steps={}"
                        .format(*grid) if grid else ""), ranks=[0])
        return program(*args)

    def _run_slots(self, stem: str, program, k_pool, v_pool, tables,
                   lengths, tokens, active, impl, parts=(), seen=None,
                   **kw):
        """_run for the slot-batched programs (decode, horizon, verify):
        leads ``parts`` with their four host arrays and ``impl`` (None:
        the engine's), and names the kernel's table when it attends
        through the kernel."""
        impl = self.decode_impl if impl is None else impl
        return self._run(
            stem, program, k_pool, v_pool,
            (("i", tables), ("i", lengths), ("i", tokens), ("b", active),
             ("static", impl), *parts), seen,
            kernel_table=np.shape(tables) if impl == "pallas" else (), **kw)

    def prefill_attended(self, start: int, n: int, bs: int, nb: int,
                         quantized: bool = False) -> int:
        """Positions of its slot's row (``nb`` blocks of ``bs``) that a
        prefill chunk of ``n`` tokens at ``start`` reads from the pool, in
        a layer that pages its history: the program's own count, on the
        host (the ``attended`` field of ``serve.prefill``). The int8
        pools' requantising write reads the whole row."""
        if quantized:
            return nb * bs
        return self.dialect.prefill_reads(self.cfg, start, n, bs, nb)

    def mla_prefill_tiles(self, start: int, bs: int) -> int:
        """Flash steps a prefill chunk at ``start`` takes in the latent
        layers, on the host; 0 for a model without latent rows."""
        return self.dialect.flash_steps(self.cfg, start, bs)

    def prefill_into_slot(self, k_pool, v_pool, table_row, tokens, start,
                          n_valid, scales=None, sample_state=None,
                          lora=None):
        from deepspeed_tpu.utils.faults import maybe_fire
        maybe_fire("engine.prefill")
        if sample_state is None and self.dialect.needs_slot:
            # the program finds the slot's tail or recurrent state by the
            # lane's slot index
            raise ValueError("a prefill for a model with per-slot state "
                             "beside the pools (convolutional attention, a "
                             "recurrent state) needs the slot's sampling lane "
                             "(sample_state): it names the slot")
        lanes, seen = self._samp_lanes(sample_state, 1, self.cfg.vocab_size,
                                       scalar=True)
        out = self._run(
            "prefill_slot", self._prefill_slot, k_pool, v_pool,
            (("i", table_row), ("i", tokens), ("i", start), ("i", n_valid),
             *lanes), seen, scales=scales, lora=lora)
        return (out[0],) + out[3:] if sample_state is None else out

    def decode_slots(self, k_pool, v_pool, tables, lengths, tokens, active,
                     impl=None, scales=None, sample_state=None, lora=None):
        from deepspeed_tpu.utils.faults import maybe_fire
        maybe_fire("engine.decode")
        lanes, seen = self._samp_lanes(sample_state, len(tokens),
                                       self.cfg.vocab_size)
        out = self._run_slots(
            "decode_slots", self._decode_slots, k_pool, v_pool, tables,
            lengths, tokens, active, impl, lanes, seen, scales=scales,
            lora=lora)
        return (out[0],) + out[3:] if sample_state is None else out

    def decode_horizon(self, k_pool, v_pool, tables, lengths, tokens,
                       active, n_steps, budgets, eos_ids, stop_ids,
                       stop_lens, tail, impl=None, scales=None,
                       sample_state=None, lora=None):
        """Fused multi-step decode for every serving slot: n_steps
        iterations of the decode body in ONE dispatch, with per-slot
        emission budgets and eos/stop predicates freezing finished
        lanes in-program (_decode_horizon_fn, docs/MULTISTEP.md).
        Returns ([n_steps, B] tokens, [n_steps, B] logprobs, [B]
        produced counts, [B] done flags, updated pools). The
        ``engine.decode`` site (and ``cache.quantize`` with int8 pools)
        fires BEFORE the dispatch touches the donated pools, so the
        serving engine can degrade a faulted horizon to single-step
        decode against intact buffers."""
        from deepspeed_tpu.utils.faults import maybe_fire
        maybe_fire("engine.decode")
        lanes, seen = self._samp_lanes(sample_state, len(tokens),
                                       self.cfg.vocab_size)
        return self._run_slots(
            "decode_horizon", self._decode_horizon, k_pool, v_pool, tables,
            lengths, tokens, active, impl,
            (("static", int(n_steps)), *lanes, ("i", budgets),
             ("i", eos_ids), ("i", stop_ids), ("i", stop_lens),
             ("i", tail)), seen, scales=scales, lora=lora)

    def verify_slots(self, k_pool, v_pool, tables, lengths, tokens, active,
                     impl=None, scales=None, lora=None):
        """Speculative chunk verify for every serving slot (tokens:
        [B, G] — each slot's pending token followed by its draft
        proposals). The ``engine.verify`` fault site (and
        ``cache.quantize`` with int8 pools) fires BEFORE the dispatch
        touches the donated pools, so the serving engine can degrade a
        faulted verify to a plain one-token decode against intact
        buffers."""
        from deepspeed_tpu.utils.faults import maybe_fire
        maybe_fire("engine.verify")
        return self._run_slots(
            "verify_slots", self._verify_slots, k_pool, v_pool, tables,
            lengths, tokens, active, impl, scales=scales, lora=lora)

    # the block-copy hooks PagedKVCache is wired with (copy_fn, gather_fn,
    # scatter_fn): pools in, pools (or the gathered blocks) out
    def cow_blocks(self, pools, src, dst):
        return self._cow_blocks(pools,  # dslint: disable=DS012 — caller paged_cache._cow fires cache.cow before delegating here
                                jnp.asarray(src, jnp.int32),
                                jnp.asarray(dst, jnp.int32))

    def gather_blocks(self, pools, ids):
        return self._gather_blocks(pools, jnp.asarray(ids, jnp.int32))

    def scatter_block(self, pools, blocks, dst):
        return self._scatter_block(pools, blocks,  # dslint: disable=DS012 — caller paged_cache._dispatch_restore fires cache.restore before delegating here
                                   jnp.asarray(dst, jnp.int32))

    def _forward_fn(self, params, tokens):
        x = self._embed(params, tokens)
        x, _ = jax.lax.scan(
            lambda c, l: (_block_prefill(c, l, self.cfg)[0], None),
            x, params["block"])
        return self._logits(params, x)

    def _encoder_forward_fn(self, params, tokens):
        """BERT-family path: encoder hidden states, or MLM logits when the
        converted checkpoint ships the prediction head
        (ref: HFBertLayerPolicy application, replace_module.py:123)."""
        from deepspeed_tpu.models import bert as bert_lib
        x = bert_lib.encode(params, tokens, self.cfg, deterministic=True)
        if "mlm" not in params:
            return x
        dtype = x.dtype
        h = bert_lib._mlm_hidden(params, x, self.cfg)
        return h @ params["embeddings"]["word"].astype(dtype).T + \
            params["mlm"]["decoder_bias"].astype(dtype)

    # ------------------------------------------------------------------
    def forward(self, tokens) -> jnp.ndarray:
        """Full-sequence logits (ref: engine.py:355 forward)."""
        import time
        t0 = time.perf_counter()
        tokens = jnp.asarray(tokens, jnp.int32)
        out = self._forward(self.params, tokens)
        jax.block_until_ready(out)
        self.latency_ms["forward"] = (time.perf_counter() - t0) * 1e3
        return out

    def __call__(self, tokens):
        return self.forward(tokens)

    def _gen_setup(self, tokens, max_new_tokens, attention_mask):
        """Shared generate() entry: prefill (+ optional left-pad mask)."""
        import time
        if self.is_encoder:
            raise NotImplementedError(
                "generate() needs a causal decoder; BERT-family models "
                "support forward() only")
        tokens = jnp.asarray(tokens, jnp.int32)
        B, S = tokens.shape
        assert S + max_new_tokens <= self.max_seq_len
        row_len = None
        if attention_mask is not None:
            attention_mask = jnp.asarray(attention_mask, jnp.float32)
            assert attention_mask.shape == (B, S)
            row_len = attention_mask.sum(axis=1).astype(jnp.int32)  # [B]

        t0 = time.perf_counter()
        logits, cache = self._prefill(self.params, tokens, attention_mask)
        jax.block_until_ready(logits)
        self.latency_ms["prefill"] = (time.perf_counter() - t0) * 1e3
        return tokens, S, logits, cache, row_len

    def generate(self, tokens, max_new_tokens: int = 32,
                 temperature: float = 0.0, top_k: int = 0,
                 seed: int = 0, attention_mask=None) -> np.ndarray:
        """Greedy (temperature=0) or sampled generation.

        attention_mask: [B, S] for LEFT-padded variable-length prompts
        (1 = real token) — rows generate as if run unpadded."""
        import time
        tokens, S, logits, cache, row_len = self._gen_setup(
            tokens, max_new_tokens, attention_mask)

        rng = jax.random.PRNGKey(seed)
        out = [np.asarray(tokens)]

        def pick(logits, rng):
            return self._sample(logits, rng, temperature, top_k)

        t0 = time.perf_counter()
        token = pick(logits, rng)
        dev_out = []
        for i in range(max_new_tokens):
            # keep the token on device: a per-step np.asarray would block
            # the dispatch queue once per token (dslint DS001); the loop
            # only enqueues work and ONE batched pull lands every token
            dev_out.append(token)
            if i == max_new_tokens - 1:
                break
            rng, r = jax.random.split(rng)
            logits, cache = self._decode(  # dslint: disable=DS012 — offline batch API; chaos coverage targets the serving dispatches (engine.decode fires in decode_slots)
                self.params, cache, token[:, None],
                jnp.asarray(S + i, jnp.int32),
                None if row_len is None else row_len + i)
            token = pick(logits, r)
        out.extend(t[:, None] for t in jax.device_get(dev_out))
        self.latency_ms["decode_per_token"] = \
            (time.perf_counter() - t0) * 1e3 / max(1, max_new_tokens - 1)
        return np.concatenate(out, axis=1)

    # ------------------------------------------------------------------
    # fused generation: the whole decode loop is ONE compiled program
    # (lax.scan over decode steps) — no host round-trip per token. The
    # reference's generation loop is host-driven (its per-token latency
    # rides PCIe/launch overheads); on TPU the scan keeps the chip busy
    # end-to-end and is the path production serving uses.
    def _sample(self, logits, rng, temperature: float, top_k: int):
        logits = logits[:, -1].astype(jnp.float32)
        if temperature <= 0.0:
            return jnp.argmax(logits, axis=-1)
        logits = logits / temperature
        if top_k > 0:
            # k-th largest via lax.top_k (O(V log k)) — same threshold
            # the full jnp.sort produced, cheaper (gshard sampler idiom)
            k_eff = min(top_k, logits.shape[-1])
            kth = jax.lax.top_k(logits, k_eff)[0][:, -1][:, None]
            logits = jnp.where(logits < kth, -1e30, logits)
        return jax.random.categorical(rng, logits, axis=-1)

    def _generate_scan_fn(self, params, cache, token, start_pos, row_len,
                          rng, n_steps: int, temperature: float,
                          top_k: int):
        def step(carry, i):
            tok, pos, cache, rng = carry
            rng, r = jax.random.split(rng)
            logits, cache = self._decode_fn(
                params, cache, tok[:, None], pos,
                None if row_len is None else row_len + i)
            nxt = self._sample(logits, r, temperature, top_k)
            return (nxt, pos + 1, cache, rng), nxt

        (_, _, _, _), toks = jax.lax.scan(
            step, (token, start_pos, cache, rng),
            jnp.arange(n_steps), length=n_steps)
        return toks  # [n_steps, B]

    def generate_fused(self, tokens, max_new_tokens: int = 32,
                       temperature: float = 0.0, top_k: int = 0,
                       seed: int = 0, attention_mask=None) -> np.ndarray:
        """generate() semantics, decode loop fused into one XLA program."""
        import time
        tokens, S, logits, cache, row_len = self._gen_setup(
            tokens, max_new_tokens, attention_mask)

        rng = jax.random.PRNGKey(seed)
        first = self._sample(logits, rng, temperature, top_k)
        n_steps = max_new_tokens - 1
        if n_steps <= 0:
            return np.concatenate([np.asarray(tokens),
                                   np.asarray(first)[:, None]], axis=1)

        # same key stream as generate(): the scan carries the ORIGINAL key
        # and splits per step, so sampled outputs match token-for-token
        args = (self.params, cache, first, jnp.asarray(S, jnp.int32),
                row_len, rng)
        # the compiled executable is shape-specialized: key on the abstract
        # shapes/dtypes of every traced arg (batch size, cache length, ...)
        # or a later call with a different batch hits a stale executable
        # and fails with an aval mismatch instead of recompiling
        avals = jax.tree_util.tree_map(
            lambda x: (x.shape, str(x.dtype)) if hasattr(x, "shape") else x,
            (cache, first, row_len))
        key = ("gen", n_steps, temperature, top_k,
               jax.tree_util.tree_structure(avals), str(avals))
        if not hasattr(self, "_gen_cache"):
            self._gen_cache = {}
        if key not in self._gen_cache:
            # AOT-compile so the per-token metric below never includes the
            # seconds-long XLA compile of the whole scan program
            t0 = time.perf_counter()
            self._gen_cache[key] = jax.jit(
                partial(self._generate_scan_fn, n_steps=n_steps,
                        temperature=temperature, top_k=top_k),
                donate_argnums=(1,)).lower(*args).compile()
            self.latency_ms["fused_generate_compile"] = \
                (time.perf_counter() - t0) * 1e3
        t0 = time.perf_counter()
        toks = np.asarray(self._gen_cache[key](*args))   # blocks
        self.latency_ms["decode_per_token_fused"] = \
            (time.perf_counter() - t0) * 1e3 / n_steps
        return np.concatenate([np.asarray(tokens),
                               np.asarray(first)[:, None], toks.T], axis=1)
