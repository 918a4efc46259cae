"""GPT-family causal transformer — the framework's flagship training model.

Capability analog of the reference's Megatron-GPT2 workloads
(ref: tests/model/Megatron_GPT2 perf harness, tests/unit/megatron_model.py)
and of the fused transformer training kernel
(ref: csrc/transformer/ds_transformer_cuda.cpp — QKV GEMM, softmax, dropout,
layernorm, gelu). TPU-first design decisions:

- **Stacked layers + lax.scan**: all L layers' weights are stacked on a
  leading axis and the block runs under ``lax.scan`` — one compiled layer
  body regardless of depth (fast compiles, natural pipeline partitioning,
  and per-layer remat).
- **bf16 matmuls on the MXU**, fp32 layernorm/softmax accumulations.
- **TP via partition rules** on the stacked weights (see
  ``gpt_partition_rules``): column-parallel QKV/MLP-in, row-parallel
  attn-out/MLP-out — XLA inserts the two allreduces per layer that
  Megatron does by hand.
- Attention dispatches to the Pallas flash kernel on TPU when enabled
  (deepspeed_tpu.ops.attention.flash), else a fused-softmax jnp path.
"""

from dataclasses import dataclass, field
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import AxisType, PartitionSpec as P, get_abstract_mesh

from deepspeed_tpu.parallel.sharding import PartitionRule


@dataclass
class GPTConfig:
    vocab_size: int = 50304           # padded to 128-multiple for the MXU
    n_layers: int = 12
    n_heads: int = 12
    d_model: int = 768
    d_ff: Optional[int] = None        # default 4*d_model
    max_seq_len: int = 1024
    dropout: float = 0.0
    dtype: Any = jnp.bfloat16
    remat: bool = True                # activation checkpointing per layer
    # 'full': recompute everything (nothing_saveable — min memory);
    # 'selective': save matmul/attention outputs, recompute layernorm/gelu/
    # elementwise only (~25% less recompute for ~8*d bytes/token/layer);
    # 'flash_only': save just the flash residuals; 'offload_flash': flash
    # residuals stream to pinned host memory — full-remat HBM footprint
    # without the flash-fwd recompute (cpu_checkpointing analog)
    remat_policy: str = "selective"
    use_flash_attention: bool = True
    # the GRID tile of the flash kernels. 1024-blocks measured fastest at
    # seq>=1024 on v5e (PERF.md): a grid step has a fixed cost and
    # re-fetches K and V, which eat what smaller blocks' skipped steps
    # give. A block that straddles the causal diagonal is walked as a
    # triangle of sub-tiles INSIDE the step by the two backward kernels
    # (flash.SUB_TILE; flash.tile_census counts them). The kernel clamps
    # to the sequence length for shorter inputs
    flash_block_q: int = 1024
    flash_block_kv: int = 1024
    # backward-kernel tiles (None = same as forward). The dq/dkv kernels
    # stream the full opposite operand per block, so their best tile can
    # differ from the forward's
    flash_block_bwd_q: Optional[int] = None
    flash_block_bwd_kv: Optional[int] = None
    tie_embeddings: bool = True
    # tokens per chunk for the fused chunked cross-entropy (0 = off, use
    # the dense log_softmax path). At large vocab×batch×seq the dense path
    # materializes multi-GB logits; chunking caps loss-path memory at
    # ~chunk×V fp32 (ops/cross_entropy.py)
    loss_chunk: int = 0
    # sequence/context parallelism: shard the token dim over the 'sequence'
    # mesh axis (set mesh too). sp_impl: 'ring' rotates K/V over ICI
    # (ops/attention/ring.py), 'ulysses' re-shards seq<->heads with two
    # all-to-alls and runs the full flash kernel locally
    # (ops/attention/ulysses.py).
    sequence_parallel: bool = False
    sp_impl: str = "ring"
    # ring data layout: "contiguous" shards the sequence in order;
    # "zigzag" balances the causal triangle across the ring (~2x at
    # large ring sizes) and expects tokens/targets/positions/segment
    # metadata pre-permuted with ops.attention.ring.zigzag_perm (the
    # rest of the model is per-token, so only attention cares)
    sp_layout: str = "contiguous"
    mesh: Any = None
    # --- architecture variants for foreign-checkpoint injection --------
    # (ref: module_inject/replace_policy.py — GPT-Neo :112 uses unscaled
    #  attention; GPT-J :157 uses rotary + parallel attn/MLP residual and
    #  no learned positions)
    attn_scale: Optional[float] = None     # None -> 1/sqrt(head_dim)
    rotary_dim: Optional[int] = None       # GPT-J rotary channels (0/None=off)
    parallel_residual: bool = False        # x + attn(h) + mlp(h), h=ln1(x)
    use_wpe: bool = True                   # learned absolute positions
    # grouped-query attention: fewer kv heads than q heads (None = MHA).
    # Shrinks the inference KV cache by n_heads/n_kv_heads; the flash
    # kernel groups kv blocks natively
    n_kv_heads: Optional[int] = None
    # sliding-window (local) attention: token i attends (i-window, i]
    # only — O(S*window) compute and HBM reads in the flash kernel
    attn_window: Optional[int] = None
    # --- llama-family architecture knobs -------------------------------
    # norm: 'layernorm' (GPT-2) or 'rmsnorm' (llama — scale only, no
    # mean subtraction); activation: 'gelu' or 'swiglu' (gated MLP with
    # a SEPARATE gate kernel so column-parallel TP shards gate/up
    # consistently); use_bias=False drops every projection bias
    norm: str = "layernorm"
    norm_eps: float = 1e-5                 # llama checkpoints use 1e-6
    activation: str = "gelu"
    use_bias: bool = True
    rope_theta: float = 10000.0            # rotary base (llama-3: 5e5)
    # a head size that is not d_model / n_heads (None = that quotient)
    head_size: Optional[int] = None

    @property
    def head_dim(self) -> int:
        if self.head_size:
            return self.head_size
        assert self.d_model % self.n_heads == 0
        return self.d_model // self.n_heads

    @property
    def kv_heads(self) -> int:
        h = self.n_kv_heads or self.n_heads
        assert self.n_heads % h == 0, (self.n_heads, h)
        return h

    @property
    def qkv_dim(self) -> int:
        """Fused qkv projection width: H*Dh + 2*Hkv*Dh."""
        return (self.n_heads + 2 * self.kv_heads) * self.head_dim

    @property
    def ffn_dim(self) -> int:
        return self.d_ff if self.d_ff is not None else 4 * self.d_model


# canonical model-size presets (GPT-2 family; 1.5B mirrors the reference
# perf harness config: 48 layers / 1600 hidden / seq 1024,
# ref: tests/model/Megatron_GPT2/run_perf_baseline.py:17)
PRESETS = {
    "gpt2-small": dict(n_layers=12, n_heads=12, d_model=768),
    "gpt2-medium": dict(n_layers=24, n_heads=16, d_model=1024),
    "gpt2-large": dict(n_layers=36, n_heads=20, d_model=1280),
    "gpt2-xl": dict(n_layers=48, n_heads=25, d_model=1600),
    "gpt2-1.5b": dict(n_layers=48, n_heads=25, d_model=1600),
    "gpt2-4b": dict(n_layers=64, n_heads=32, d_model=2304),
    "gpt2-8b": dict(n_layers=72, n_heads=32, d_model=3072),
}

# llama-family architecture: rmsnorm + swiglu + rotary + no biases,
# untied head, no learned positions (ref capability analog: the policy
# registry's per-architecture variants, module_inject/replace_policy.py)
_LLAMA_ARCH = dict(norm="rmsnorm", activation="swiglu", use_bias=False,
                   use_wpe=False, tie_embeddings=False,
                   parallel_residual=False, norm_eps=1e-6)
PRESETS.update({
    "llama-tiny": dict(n_layers=4, n_heads=8, n_kv_heads=4, d_model=256,
                       d_ff=688, rotary_dim=32, vocab_size=512,
                       max_seq_len=256, **_LLAMA_ARCH),
    "llama-7b": dict(n_layers=32, n_heads=32, d_model=4096, d_ff=11008,
                     rotary_dim=128, vocab_size=32000, max_seq_len=2048,
                     **_LLAMA_ARCH),
    "llama-13b": dict(n_layers=40, n_heads=40, d_model=5120, d_ff=13824,
                      rotary_dim=128, vocab_size=32000, max_seq_len=2048,
                      **_LLAMA_ARCH),
})


def preset(name: str, **overrides) -> GPTConfig:
    cfg = dict(PRESETS[name])
    cfg.update(overrides)
    return GPTConfig(**cfg)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_params(rng: jax.Array, cfg: GPTConfig) -> Dict:
    """fp32 master parameters; layer weights stacked on axis 0."""
    k_embed, k_pos, k_layers, k_head = jax.random.split(rng, 4)
    d, L, ff = cfg.d_model, cfg.n_layers, cfg.ffn_dim
    init = jax.nn.initializers.normal(stddev=0.02)
    # residual-branch projections scaled per GPT-2 (1/sqrt(2L))
    resid_init = jax.nn.initializers.normal(stddev=0.02 / np.sqrt(2.0 * L))

    def stacked(key, shape, initializer=init):
        return initializer(key, (L,) + shape, jnp.float32)

    ks = jax.random.split(k_layers, 6)

    def norm_p():
        if cfg.norm == "rmsnorm":
            return {"scale": jnp.ones((L, d))}
        return {"scale": jnp.ones((L, d)), "bias": jnp.zeros((L, d))}

    def maybe_bias(entry, width):
        if cfg.use_bias:
            entry["bias"] = jnp.zeros((L, width))
        return entry

    params = {
        "wte": {"embedding": init(k_embed, (cfg.vocab_size, d), jnp.float32)},
        "wpe": {"embedding": init(k_pos, (cfg.max_seq_len, d), jnp.float32)},
        "block": {
            "ln1": norm_p(),
            "qkv": maybe_bias(
                {"kernel": stacked(ks[0], (d, cfg.qkv_dim))}, cfg.qkv_dim),
            "attn_out": maybe_bias(
                {"kernel": stacked(ks[1], (d, d), resid_init)}, d),
            "ln2": norm_p(),
            "mlp_in": maybe_bias(
                {"kernel": stacked(ks[2], (d, ff))}, ff),
            "mlp_out": maybe_bias(
                {"kernel": stacked(ks[3], (ff, d), resid_init)}, d),
        },
        "ln_f": ({"scale": jnp.ones((d,))} if cfg.norm == "rmsnorm"
                 else {"scale": jnp.ones((d,)), "bias": jnp.zeros((d,))}),
    }
    if cfg.activation == "swiglu":
        params["block"]["mlp_gate"] = maybe_bias(
            {"kernel": stacked(ks[4], (d, ff))}, ff)
    if not cfg.use_wpe:
        del params["wpe"]
    if not cfg.tie_embeddings:
        params["lm_head"] = {"kernel": init(k_head, (d, cfg.vocab_size), jnp.float32)}
    return params


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def remat_policy(name: str, flash: bool = False):
    """Checkpoint policy for the per-layer remat (analog of the reference's
    activation-checkpointing variants, ref:
    runtime/activation_checkpointing/checkpointing.py).

    'selective' saves the tagged matmul/attention outputs so the backward
    pass only recomputes layernorms, gelu and elementwise ops — the
    standard save-dots/recompute-elementwise trade. When the flash kernel
    is active its packed out residual ("flash_out") IS the attention
    output, so the "attn" tag is dropped to avoid saving the same bytes
    twice. 'flash_only' keeps just the flash residuals (~d bytes/token
    per layer) and recomputes the cheap matmuls — the memory-lean setting
    that fits 1.5B-class training on a 16GB chip. 'full' recomputes
    everything.
    """
    if name == "selective":
        names = ["qkv", "mlp_pre", "flash_out", "flash_lse"]
        if not flash:
            names.append("attn")
        return jax.checkpoint_policies.save_only_these_names(*names)
    if name == "flash_only":
        return jax.checkpoint_policies.save_only_these_names(
            "flash_out", "flash_lse")
    if name == "offload_flash":
        # flash residuals move to PINNED HOST memory instead of either
        # living in HBM (flash_only) or being recomputed (full): HBM cost
        # ~0 like 'full', backward skips the flash-fwd recompute like
        # 'flash_only'. The d2h/h2d rides the same async DMA path XLA
        # schedules around compute. TPU-native analog of the reference's
        # cpu_checkpointing (ref: runtime/activation_checkpointing/
        # checkpointing.py:28 PartitionedActivations/cpu_checkpointing).
        return jax.checkpoint_policies.save_and_offload_only_these_names(
            names_which_can_be_saved=[],
            names_which_can_be_offloaded=["flash_out", "flash_lse"],
            offload_src="device", offload_dst="pinned_host")
    if name == "full":
        return jax.checkpoint_policies.nothing_saveable
    raise ValueError(f"unknown remat_policy {name!r} (expected "
                     "'selective', 'flash_only', 'offload_flash' or "
                     "'full')")


def _norm(x, p, cfg):
    """Config-dispatched normalization: GPT-2 layernorm or llama rmsnorm
    (scale-only, no mean subtraction). eps comes from cfg.norm_eps —
    llama-family checkpoints are trained with 1e-6."""
    eps = cfg.norm_eps
    if cfg.norm == "rmsnorm":
        xf = x.astype(jnp.float32)
        y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1,
                                        keepdims=True) + eps)
        scale = p["scale"].astype(jnp.float32)
        if getattr(cfg, "norm_offset", False):
            # the stored scale is an OFFSET from one (zeros at
            # initialisation: models/qwen3_next.py)
            scale = 1.0 + scale
        return (y * scale).astype(x.dtype)
    return _layernorm(x, p["scale"], p["bias"], eps=eps)


def _kernel_of(p, dtype):
    """The (possibly int8-quantized) weight of a dense entry, in compute
    dtype. Weight-only int8 entries carry {"q": int8, "scale": fp32
    per-output-channel} instead of {"kernel"} (inference/engine.py
    quantize_weights_int8); dequantization fuses into the matmul."""
    if "q" in p:
        return p["q"].astype(dtype) * p["scale"].astype(dtype)
    return p["kernel"].astype(dtype)


def _int8_fused_enabled() -> bool:
    """DS_INT8_FUSED=1 routes int8 dense entries through the Pallas
    fused dequant-matmul (ops/int8_matmul.py) instead of trusting XLA
    to fuse _kernel_of's dequant — the fallback the reference covers
    with dedicated int8 GEMM kernels (ref: csrc/transformer/inference
    pt_binding.cpp:866). TPU-only: the kernel needs Mosaic."""
    from deepspeed_tpu.utils import on_tpu
    from deepspeed_tpu.utils.env import resolve_flag
    return resolve_flag("DS_INT8_FUSED") and on_tpu()


def _dense(h, p, lora=None):
    """h @ kernel (+ bias when the config kept biases). A LoRA-adapted
    entry (runtime/lora.py) adds the low-rank path h @ A @ B * scale —
    the dense delta is never materialized.

    ``lora`` is the serving-time multi-tenant hook (inference/
    adapters.py): a pair of per-slot gathered rank-block factors
    ``(a_blk [B, NBa, in, rb], b_blk [B, NBa, rb, out])`` applied as
    batched low-rank matmuls summed over the rank-block axis. Scale is
    pre-folded into b_blk; base-only slots gather the pool's all-zeros
    trash block, so their contribution is exactly +0.0."""
    blocks = None
    if "q" in p and p["q"].ndim == 2 and _int8_fused_enabled():
        from deepspeed_tpu.ops.int8_matmul import fit_blocks, int8_matmul
        blocks = fit_blocks(*p["q"].shape)
    if blocks is not None:
        lead, K = h.shape[:-1], h.shape[-1]
        y = int8_matmul(h.reshape(-1, K), p["q"],
                        p["scale"].reshape(1, -1),
                        block_k=blocks[0], block_n=blocks[1])
        y = y.reshape(*lead, y.shape[-1])
    else:
        y = h @ _kernel_of(p, h.dtype)
    if "lora_a" in p:
        y = y + ((h @ p["lora_a"].astype(h.dtype))
                 @ p["lora_b"].astype(h.dtype))             * p["lora_scale"].astype(h.dtype)
    if lora is not None:
        a_blk, b_blk = lora
        u = jnp.einsum("bsi,bnir->bnsr", h, a_blk.astype(h.dtype))
        y = y + jnp.einsum("bnsr,bnro->bso", u, b_blk.astype(h.dtype))
    b = p.get("bias")
    return y if b is None else y + b.astype(h.dtype)


def _qkv_split_rotary(qkv, cfg, positions, B, S):
    """Split a fused qkv projection into per-head q/k/v and apply rotary
    — the ONE copy of the attention prologue shared by the dense block,
    the MoE block, and inference prefill (divergent copies previously
    left rotary dead in the MoE block)."""
    H, Dh, Hkv = cfg.n_heads, cfg.head_dim, cfg.kv_heads
    q, k, v = jnp.split(qkv, [H * Dh, (H + Hkv) * Dh], axis=-1)
    q = q.reshape(B, S, H, Dh)
    k = k.reshape(B, S, Hkv, Dh)
    v = v.reshape(B, S, Hkv, Dh)
    if cfg.rotary_dim:
        from deepspeed_tpu.ops.attention.rotary import apply_rotary
        q, k = apply_rotary(
            q, k, positions if positions is not None else jnp.arange(S),
            cfg.rotary_dim, base=cfg.rope_theta)
    return q, k, v


def _layernorm(x, scale, bias, eps=1e-5):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale + bias).astype(x.dtype)


def _flash_blocks(cfg: GPTConfig, seq_len: int):
    """(block_q, block_kv) when ``_attention`` runs the Pallas flash
    kernel for this sequence, None when it runs the dense reference.

    The kernel is the TPU implementation. Off a TPU the platform default
    is the dense path, and the engine's "engine ready" line says so. On
    a TPU a config that asks for the kernel gets it or an error: a run
    that printed an MFU never lost flash to a quiet shape gate."""
    if not cfg.use_flash_attention:
        return None
    from deepspeed_tpu.ops.attention.flash import fit_block
    from deepspeed_tpu.utils import on_tpu
    if not on_tpu():
        return None
    bq = fit_block(cfg.flash_block_q, seq_len)
    bkv = fit_block(cfg.flash_block_kv, seq_len)
    if bq is None or bkv is None:
        raise ValueError(
            f"use_flash_attention=True, but no flash block of 128 or more "
            f"divides seq_len={seq_len}: pad the sequence to a multiple of "
            f"128 or set use_flash_attention=False")
    return bq, bkv


def _flash_bwd_blocks(cfg: GPTConfig, seq_len: int):
    """(bwd_block_q, bwd_block_kv) overrides for the flash backward
    kernels, each None where the forward's block stands. They pass
    through the same divisibility normalization as the forward blocks (a
    non-dividing block would truncate the backward grid)."""
    from deepspeed_tpu.ops.attention.flash import fit_block
    return tuple(fit_block(b, seq_len) if b else None
                 for b in (cfg.flash_block_bwd_q, cfg.flash_block_bwd_kv))


def _flash_eligible(cfg: GPTConfig, seq_len: int) -> bool:
    return _flash_blocks(cfg, seq_len) is not None


def attention_impl(cfg: GPTConfig, seq_len: Optional[int] = None) -> str:
    """Name of the attention implementation ``_attention`` traces for
    ``seq_len`` (default ``cfg.max_seq_len``) on this platform."""
    S = seq_len or cfg.max_seq_len
    sp = ""
    if cfg.sequence_parallel and cfg.mesh is not None:
        sp = cfg.sp_impl + "+"
        if cfg.sp_impl == "ring":
            S //= cfg.mesh.shape["sequence"]
    blocks = _flash_blocks(cfg, S)
    if not blocks:
        return sp + "dense"
    # how often the backward kernels' sub-tile walk engages: static, from
    # the call's geometry (a ring's diagonal step under sequence parallel)
    from deepspeed_tpu.ops.attention.flash import tile_census
    bwd = [own or fwd for own, fwd in zip(_flash_bwd_blocks(cfg, S), blocks)]
    done, grid = tile_census(S, S, *bwd, True, cfg.attn_window)
    return sp + (f"flash({blocks[0]}x{blocks[1]}, backward sub-tiles "
                 f"{done}/{grid})")


def _flash_per_device(q, k, v, segment_ids, kv_mask, **kw):
    """``flash_attention`` on each device's own share of the batch and
    heads. XLA cannot partition a Mosaic custom call, and jax refuses to
    lower one inside a sharded jit, so under a mesh the call is mapped by
    hand: batch over the data-parallel axes, heads over 'model' when it
    divides them (qkv is column-parallel, so that is where they live).
    The map takes EVERY axis no enclosing shard_map holds yet, those of
    size 1 too: the kernel lowers only where the whole mesh is manual."""
    from deepspeed_tpu.ops.attention.flash import flash_attention
    m = get_abstract_mesh()
    auto = [] if m is None or m.empty else [
        n for n, ty in zip(m.axis_names, m.axis_types)
        if ty != AxisType.Manual]
    if all(m.shape[n] == 1 for n in auto):
        return flash_attention(q, k, v, segment_ids=segment_ids,
                               kv_mask=kv_mask, **kw)
    batch = tuple(a for a in ("data", "fsdp") if a in auto) or None
    tp = m.shape["model"] if "model" in auto else 1
    heads = "model" if tp > 1 and q.shape[2] % tp == 0 \
        and k.shape[2] % tp == 0 else None
    spec, tok = P(batch, None, heads, None), P(batch, None)
    # optional per-token metadata rides as extra mapped operands
    extra = {name: x for name, x in (("segment_ids", segment_ids),
                                     ("kv_mask", kv_mask)) if x is not None}

    def local(q, k, v, *meta):
        return flash_attention(q, k, v, **dict(zip(extra, meta)), **kw)

    return jax.shard_map(
        local, in_specs=(spec, spec, spec) + (tok,) * len(extra),
        out_specs=spec, axis_names=set(auto), check_vma=False)(
            q, k, v, *extra.values())


def _attention(q, k, v, cfg: GPTConfig, segment_ids=None, kv_mask=None):
    """Causal multi-head attention. q,k,v: [B, S, H, Dh].

    segment_ids: optional [B, S] packed-sequence ids — attention stays
    inside each segment (block-diagonal x causal).
    kv_mask: optional [B, S] key-validity mask (left-padded prompts)."""
    from deepspeed_tpu.ops.attention.flash import mha_reference
    scale = cfg.attn_scale  # None -> kernels default to 1/sqrt(Dh)
    if cfg.sequence_parallel and cfg.mesh is not None:
        # GQA works under both SP impls: ring rotates the small grouped
        # k/v; Ulysses needs the sp degree to divide both head counts
        if cfg.sp_impl == "ulysses":
            if cfg.sp_layout == "zigzag":
                # a contiguous causal mask applied to zigzag-permuted
                # tokens is silently wrong attention — refuse loudly
                raise ValueError(
                    "sp_layout='zigzag' is a RING layout (balances the "
                    "causal ring schedule); ulysses keeps the natural "
                    "order — use sp_layout='contiguous' with it")
            from deepspeed_tpu.ops.attention.ulysses import ulysses_attention
            S = q.shape[1]
            blocks = _flash_blocks(cfg, S)
            bwd_q, bwd_kv = _flash_bwd_blocks(cfg, S)
            return ulysses_attention(
                q, k, v, cfg.mesh, causal=True, scale=scale,
                use_flash=blocks is not None,
                block_q=blocks[0] if blocks else cfg.flash_block_q,
                block_kv=blocks[1] if blocks else cfg.flash_block_kv,
                segment_ids=segment_ids, kv_mask=kv_mask,
                window=cfg.attn_window,
                bwd_block_q=bwd_q, bwd_block_kv=bwd_kv)
        if cfg.sp_impl != "ring":
            raise ValueError(f"unknown sp_impl {cfg.sp_impl!r} "
                             "(expected 'ring' or 'ulysses')")
        from deepspeed_tpu.ops.attention.ring import ring_attention
        # packing/padding metadata rotates with the K/V blocks; the local
        # block runs the Pallas flash kernel when eligible (gated on the
        # LOCAL shard length — that is what the kernel sees per step)
        S_loc = q.shape[1] // cfg.mesh.shape["sequence"]
        blocks = _flash_blocks(cfg, S_loc)
        return ring_attention(
            q, k, v, cfg.mesh, causal=True, scale=scale,
            segment_ids=segment_ids, kv_mask=kv_mask,
            window=cfg.attn_window, use_flash=blocks is not None,
            block_q=blocks[0] if blocks else 512,
            block_kv=blocks[1] if blocks else 512,
            layout=cfg.sp_layout)
    blocks = _flash_blocks(cfg, q.shape[1])
    if blocks is not None:
        # fall back to the fwd block when no bwd override divides
        bwd_q, bwd_kv = _flash_bwd_blocks(cfg, q.shape[1])
        return _flash_per_device(
            q, k, v, segment_ids, kv_mask, causal=True, scale=scale,
            block_q=blocks[0], block_kv=blocks[1], window=cfg.attn_window,
            bwd_block_q=bwd_q, bwd_block_kv=bwd_kv)
    return mha_reference(q, k, v, causal=True, scale=scale,
                         segment_ids=segment_ids, kv_mask=kv_mask,
                         window=cfg.attn_window)


def _block(x, layer_params, cfg: GPTConfig, dropout_rng=None,
           deterministic=True, segment_ids=None, positions=None):
    """One transformer block. x: [B, S, D]. positions: optional [B, S]
    per-row rotary positions (packed batches restart per document)."""
    B, S, D = x.shape
    H, Dh = cfg.n_heads, cfg.head_dim
    p = layer_params

    if dropout_rng is not None:
        dr_attn, dr_mlp = jax.random.split(dropout_rng)
    else:
        dr_attn = dr_mlp = None

    # the scopes name the parts of a block in the compiled program's
    # metadata (profiles, the provenance table of telemetry/costs.py);
    # they change nothing that is computed
    with jax.named_scope("attn_qkv"):
        h = _norm(x, p["ln1"], cfg)
        qkv = _dense(h, p["qkv"])
        qkv = checkpoint_name(qkv, "qkv")
        q, k, v = _qkv_split_rotary(qkv, cfg, positions, B, S)
    with jax.named_scope("attn"):
        attn = _attention(q, k, v, cfg,
                          segment_ids=segment_ids).reshape(B, S, D)
        attn = checkpoint_name(attn, "attn")
    with jax.named_scope("attn_out"):
        attn = _dense(attn, p["attn_out"])
        if not deterministic and cfg.dropout > 0:
            attn = _dropout(attn, cfg.dropout, dr_attn)

    with jax.named_scope("mlp"):
        # GPT-J style parallel residual: MLP reads the SAME ln1 output
        # and both branches add to x (ref: HFGPTJLayerPolicy,
        # replace_policy.py:157)
        mlp_src = h if cfg.parallel_residual else None
        if not cfg.parallel_residual:
            x = x + attn
            mlp_src = _norm(x, p["ln2"], cfg)

        m = _dense(mlp_src, p["mlp_in"])
        m = checkpoint_name(m, "mlp_pre")
        if cfg.activation == "swiglu":
            # gated MLP: silu(x @ gate) * (x @ up) — separate kernels so
            # column-parallel TP keeps gate/up halves aligned per shard
            m = jax.nn.silu(_dense(mlp_src, p["mlp_gate"])) * m
        else:
            m = jax.nn.gelu(m, approximate=True)
        m = _dense(m, p["mlp_out"])
        if not deterministic and cfg.dropout > 0:
            m = _dropout(m, cfg.dropout, dr_mlp)
        if cfg.parallel_residual:
            return x + attn + m
        return x + m


def _dropout(x, rate, rng):
    keep = jax.random.bernoulli(rng, 1.0 - rate, x.shape)
    return jnp.where(keep, x / (1.0 - rate), 0.0).astype(x.dtype)


def forward(params: Dict, tokens: jnp.ndarray, cfg: GPTConfig,
            rng: Optional[jax.Array] = None,
            deterministic: bool = True,
            pld_theta: Optional[jnp.ndarray] = None,
            hidden_only: bool = False,
            segment_ids: Optional[jnp.ndarray] = None,
            positions: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """tokens [B, S] int32 -> logits [B, S, V] (compute dtype).

    pld_theta: optional progressive-layer-drop keep-base (traced scalar;
    ref: deepspeed/runtime/progressive_layer_drop.py + arXiv:2010.13369):
    layer l survives with prob 1 - (l/L)*(1-theta), deeper layers dropped
    more often. Training-only (pass None for eval).

    segment_ids/positions: packed-sequence support — [B, S] ids keep
    attention block-diagonal per document, [B, S] positions restart the
    learned positional embedding at each document start."""
    B, S = tokens.shape
    dtype = cfg.dtype
    if (cfg.sequence_parallel and cfg.sp_layout == "zigzag"
            and positions is None):
        raise ValueError(
            "sp_layout='zigzag' permutes the token order — pass "
            "positions (the zigzag_perm itself for unpacked batches) so "
            "positional encodings follow the tokens")
    with jax.named_scope("embed"):
        wte = params["wte"]["embedding"].astype(dtype)
        x = wte[tokens]
        if cfg.use_wpe:
            wpe = params["wpe"]["embedding"].astype(dtype)
            x = x + (wpe[positions] if positions is not None
                     else wpe[:S][None])

    block = params["block"]
    L = cfg.n_layers

    # pin the scan carry's layout: without this, XLA's sharding
    # propagation may pick conflicting activation shardings between the
    # forward and transpose scan bodies under fsdp x tp (an "involuntary
    # full rematerialization" reshard per layer); batch stays over the dp
    # axes, token/feature dims replicated
    # under sequence parallelism the token dim stays sharded over
    # 'sequence' — pinning it replicated would allgather the full
    # residual stream every layer and erase SP's memory win
    carry_spec = P(("data", "fsdp"),
                   "sequence" if cfg.sequence_parallel else None, None)

    def _pin(t):
        m = get_abstract_mesh()
        if m is None or m.empty or not {"data", "fsdp"} <= set(m.axis_names):
            return t  # no engine mesh in context (e.g. raw single-device)
        # inside a shard_map region (e.g. the compressed-collective wire
        # path maps the loss over 'data') manual axes are already local —
        # a constraint naming them is both meaningless and rejected
        manual = {n for n, ty in zip(m.axis_names, m.axis_types)
                  if ty == AxisType.Manual}
        if not manual:
            return jax.lax.with_sharding_constraint(t, carry_spec)

        def keep(entry):
            if entry is None:
                return None
            names = entry if isinstance(entry, tuple) else (entry,)
            left = tuple(n for n in names if n not in manual)
            return left if left else None
        spec = P(*(keep(e) for e in carry_spec))
        if all(e is None for e in spec):
            return t
        return jax.lax.with_sharding_constraint(t, spec)

    def body(carry, scanned):
        layer, lidx = scanned
        x, r = carry
        x = _pin(x)
        r, dr = jax.random.split(r) if r is not None else (None, None)
        y = _block(x, layer, cfg, dropout_rng=dr, deterministic=deterministic,
                   segment_ids=segment_ids, positions=positions)
        if pld_theta is not None and not deterministic:
            kr = jax.random.fold_in(dr, jnp.int32(7))
            keep_p = 1.0 - (lidx.astype(jnp.float32) / L) * \
                (1.0 - pld_theta.astype(jnp.float32))
            keep = jax.random.bernoulli(kr, keep_p)
            y = jnp.where(keep, y, x)
        return (_pin(y), r), None

    if cfg.remat:
        # the policy must match the attention path actually taken: when
        # flash is requested but ineligible for this S, the jnp path tags
        # "attn" and produces no flash residuals
        body = jax.checkpoint(
            body, policy=remat_policy(cfg.remat_policy,
                                      flash=_flash_eligible(cfg, S)))

    rng = rng if rng is not None else jax.random.PRNGKey(0)
    (x, _), _ = jax.lax.scan(body, (x, rng), (block, jnp.arange(L)))

    with jax.named_scope("logits"):
        x = _norm(x, params["ln_f"], cfg)
        if hidden_only:
            return x
        if cfg.tie_embeddings:
            logits = x @ wte.T
        else:
            head = params["lm_head"]
            logits = x @ head["kernel"].astype(dtype)
            if "bias" in head:   # e.g. GPT-J ships an lm_head bias
                logits = logits + head["bias"].astype(dtype)
        return logits


def _head_nll(other: Dict, y: jnp.ndarray, targets: jnp.ndarray,
              cfg: GPTConfig, loss_mask=None) -> jnp.ndarray:
    """Mean next-token NLL from post-ln_f hidden states (pipeline / layered
    heads). Honors cfg.loss_chunk (fused chunked CE, ops/cross_entropy.py)
    and an optional [.., S] loss mask (packed batches)."""
    w, b = _vocab_proj(other, cfg)
    if cfg.loss_chunk:
        from deepspeed_tpu.ops.cross_entropy import chunked_softmax_xent
        return chunked_softmax_xent(y, w, targets, bias=b,
                                    chunk=cfg.loss_chunk,
                                    loss_mask=loss_mask)
    logits = jax.lax.dot_general(
        y, w, (((y.ndim - 1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    if b is not None:
        logits = logits + b.astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, targets[..., None], axis=-1).squeeze(-1)
    if loss_mask is not None:
        return -(ll * loss_mask).sum() / jnp.maximum(loss_mask.sum(), 1.0)
    return -ll.mean()


def _vocab_proj(params: Dict, cfg: GPTConfig):
    """(w [V, H], bias [V] | None) for the chunked-loss path."""
    if cfg.tie_embeddings:
        return params["wte"]["embedding"].astype(cfg.dtype), None
    head = params["lm_head"]
    b = head.get("bias")
    return (head["kernel"].astype(cfg.dtype).T,
            None if b is None else b.astype(cfg.dtype))


def loss_fn(params: Dict, batch: Dict, rng: jax.Array, cfg: GPTConfig,
            deterministic: bool = False) -> jnp.ndarray:
    """Causal LM cross-entropy. batch: {"tokens": [B, S]} (next-token) or
    {"tokens", "targets"}. fp32 log-softmax for stability.

    Packed batches add "segment_ids"/"positions" [B, S]; pair them with a
    "loss_mask" zeroing each segment's last token (whose next-token
    target crosses into the following document)."""
    tokens = batch["tokens"]
    targets = batch.get("targets")
    segs = batch.get("segment_ids")
    poss = batch.get("positions")
    if targets is None:
        targets = tokens[:, 1:]
        tokens = tokens[:, :-1]
        segs = None if segs is None else segs[:, :-1]
        poss = None if poss is None else poss[:, :-1]
    mask = batch.get("loss_mask")
    if mask is not None and mask.shape[-1] != targets.shape[-1]:
        # pack_documents emits a (S-1)-wide mask aligned with the
        # implicit-targets slice above; pairing it with an explicit
        # seq-wide "targets" key would silently misalign mask/segments
        raise ValueError(
            f"loss_mask width {mask.shape[-1]} != target width "
            f"{targets.shape[-1]} — a pack_documents batch must either "
            f"keep implicit targets (no 'targets' key; loss_fn slices "
            f"next-token pairs) or be rewritten as a whole by "
            f"dataloader.zigzag_batch, which derives targets BEFORE "
            f"permuting so every per-token array stays aligned")
    if cfg.loss_chunk:
        # fused vocab-projection + loss: never materializes [B, S, V]
        # (ops/cross_entropy.py — frees ~3GB+ at GPT-2-1.5B scale); under
        # a mesh that splits the batch it scans each shard's own tokens
        # on a projection gathered once
        from deepspeed_tpu.ops.cross_entropy import chunked_softmax_xent
        x = forward(params, tokens, cfg, rng, deterministic=deterministic,
                    pld_theta=batch.get("pld_theta"), hidden_only=True,
                    segment_ids=segs, positions=poss)
        w, b = _vocab_proj(params, cfg)
        return chunked_softmax_xent(x, w, targets, bias=b,
                                    chunk=cfg.loss_chunk, loss_mask=mask)
    logits = forward(params, tokens, cfg, rng, deterministic=deterministic,
                     pld_theta=batch.get("pld_theta"),
                     segment_ids=segs, positions=poss)
    logits = logits.astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, targets[..., None], axis=-1).squeeze(-1)
    if mask is not None:
        return -(ll * mask).sum() / jnp.maximum(mask.sum(), 1.0)
    return -ll.mean()


def make_loss_fn(cfg: GPTConfig):
    """Engine-contract loss: (params, batch, rng) -> loss. ``describe``
    feeds the engine's "engine ready" line (called under the engine's
    mesh, which the chunked loss's layout follows)."""
    from deepspeed_tpu.ops.cross_entropy import loss_layout

    def _loss(params, batch, rng):
        return loss_fn(params, batch, rng, cfg)
    _loss.describe = lambda: {
        "attention": attention_impl(cfg),
        "loss": loss_layout(cfg.loss_chunk) if cfg.loss_chunk else "dense"}
    return _loss


# ---------------------------------------------------------------------------
# sharding
# ---------------------------------------------------------------------------

def gpt_partition_rules() -> list:
    """TP rules for the stacked-layer layout (dim 0 = layer).

    Megatron mapping (delegated to client mpu in the reference, SURVEY §2.2;
    owned here): qkv & mlp_in column-parallel, attn_out & mlp_out
    row-parallel, vocab-parallel embedding.
    """
    return [
        PartitionRule(r"block/qkv/kernel", P(None, None, "model")),
        PartitionRule(r"block/qkv/bias", P(None, "model")),
        PartitionRule(r"block/attn_out/kernel", P(None, "model", None)),
        PartitionRule(r"block/mlp_in/kernel", P(None, None, "model")),
        PartitionRule(r"block/mlp_in/bias", P(None, "model")),
        PartitionRule(r"block/mlp_gate/kernel", P(None, None, "model")),
        PartitionRule(r"block/mlp_gate/bias", P(None, "model")),
        PartitionRule(r"block/mlp_out/kernel", P(None, "model", None)),
        # NOTE: embeddings deliberately NOT model-sharded: a vocab-sharded
        # table makes XLA fully rematerialize the gather (SPMD warning) —
        # proper masked vocab-parallel lookup is a follow-up; fsdp sharding
        # still applies under ZeRO-3.
    ]


# ---------------------------------------------------------------------------
# pipeline parallelism integration
# ---------------------------------------------------------------------------

def gpt_pipeline_partition_rules(tp: bool = False) -> list:
    """Partition rules for pipeline mode: the stacked layer dim is sharded
    over 'pipe' (each stage owns n_layers/pp layers), optionally composed
    with Megatron TP on the inner dims."""
    model = "model" if tp else None
    return [
        PartitionRule(r"block/(ln1|ln2)/(scale|bias)", P("pipe", None)),
        PartitionRule(r"block/qkv/kernel", P("pipe", None, model)),
        PartitionRule(r"block/qkv/bias", P("pipe", model)),
        PartitionRule(r"block/attn_out/kernel", P("pipe", model, None)),
        PartitionRule(r"block/attn_out/bias", P("pipe", None)),
        PartitionRule(r"block/mlp_in/kernel", P("pipe", None, model)),
        PartitionRule(r"block/mlp_in/bias", P("pipe", model)),
        PartitionRule(r"block/mlp_gate/kernel", P("pipe", None, model)),
        PartitionRule(r"block/mlp_gate/bias", P("pipe", model)),
        PartitionRule(r"block/mlp_out/kernel", P("pipe", model, None)),
        PartitionRule(r"block/mlp_out/bias", P("pipe", None)),
    ]


def make_pipeline_loss_fn(cfg: GPTConfig, mesh, num_stages: int,
                          num_micro: int, schedule: str = "1f1b",
                          virtual_chunks: int = 1):
    """Engine-contract loss running the transformer stack as a shard_map
    pipeline over the 'pipe' mesh axis (1 stage = n_layers/pp layers).
    Embedding + LM head run replicated over pipe (tied-weight grads are
    psum'd across stages by shard_map's transpose — the ReduceTiedGrads
    capability, ref pipe/engine.py:240)."""
    from deepspeed_tpu.runtime.pipe.engine import make_pipelined_loss_fn

    assert cfg.n_layers % num_stages == 0, (cfg.n_layers, num_stages)

    def split_params(params):
        other = {k: v for k, v in params.items() if k != "block"}
        return params["block"], other

    def embed_fn(other, batch):
        tokens = batch["tokens"]
        targets = batch.get("targets")
        if targets is None:
            targets = tokens[:, 1:]
            tokens = tokens[:, :-1]
        S = tokens.shape[1]
        x = other["wte"]["embedding"].astype(cfg.dtype)[tokens]
        if cfg.use_wpe:
            x = x + other["wpe"]["embedding"].astype(cfg.dtype)[:S][None]
        return x, targets

    def stage_fn(block_local, x):
        def body(carry, layer):
            return _block(carry, layer, cfg, deterministic=True), None
        y, _ = jax.lax.scan(body, x, block_local)
        return y

    def head_loss_fn(other, y, targets):
        y = _norm(y, other["ln_f"], cfg)
        return _head_nll(other, y, targets, cfg)

    # block leaves: rank 2 -> P('pipe'), rank 3 -> P('pipe')
    def spec_of(leaf):
        return P(*(["pipe"] + [None] * (leaf.ndim - 1)))

    import dataclasses
    dummy = init_params(jax.random.PRNGKey(0), dataclasses.replace(
        cfg, vocab_size=8, n_layers=num_stages, n_heads=1,
        n_kv_heads=None, d_model=8, d_ff=None, max_seq_len=8,
        rotary_dim=None, mesh=None))
    specs = jax.tree_util.tree_map(spec_of, dummy["block"])

    if schedule == "interleaved":
        assert cfg.n_layers % (num_stages * virtual_chunks) == 0, \
            (cfg.n_layers, num_stages, virtual_chunks)
    return make_pipelined_loss_fn(
        embed_fn, stage_fn, head_loss_fn, split_params,
        num_stages, num_micro, mesh, specs, remat_stage=cfg.remat,
        schedule=schedule, virtual_chunks=virtual_chunks)


# ---------------------------------------------------------------------------
# ZeRO-Infinity parameter streaming integration
# ---------------------------------------------------------------------------

def layered_model(cfg: GPTConfig):
    """LayeredModel contract for the parameter-streaming engine
    (runtime/zero/param_offload.py) — trains GPTs larger than device HBM
    (ref capability: 13B params on one 32GB GPU, docs/_pages/features.md:116;
    ref machinery: runtime/swap_tensor/partitioned_param_swapper.py:37)."""
    from deepspeed_tpu.runtime.zero.param_offload import LayeredModel

    def split_params(params):
        other = {k: v for k, v in params.items() if k != "block"}
        return params["block"], other

    def embed_fn(other, batch):
        tokens = batch["tokens"]
        targets = batch.get("targets")
        if targets is None:
            targets = tokens[:, 1:]
            tokens = tokens[:, :-1]
        S = tokens.shape[1]
        x = other["wte"]["embedding"].astype(cfg.dtype)[tokens]
        if cfg.use_wpe:
            x = x + other["wpe"]["embedding"].astype(cfg.dtype)[:S][None]
        return x, targets

    def layer_fn(lp, x):
        return _block(x, lp, cfg, deterministic=True)

    def head_fn(other, y, targets):
        y = _norm(y, other["ln_f"], cfg)
        return _head_nll(other, y, targets, cfg)

    return LayeredModel(split_params=split_params, embed_fn=embed_fn,
                        layer_fn=layer_fn, head_fn=head_fn,
                        n_layers=cfg.n_layers,
                        layer_remat_policy=(remat_policy(cfg.remat_policy,
                                                         flash=cfg.use_flash_attention)
                                            if cfg.remat else None))


def host_param_factory(seed: int, cfg: GPTConfig):
    """Host-RAM parameter factory for models too large to materialize as
    one stacked tree: factory(i) -> layer i's fp32 numpy pytree (unstacked),
    factory("other") -> embeddings/final-norm tree. Feeds
    InfinityParamEngine without ever holding more than one layer twice."""
    d, ff = cfg.d_model, cfg.ffn_dim

    def norm_p():
        if cfg.norm == "rmsnorm":
            return {"scale": np.ones((d,), np.float32)}
        return {"scale": np.ones((d,), np.float32),
                "bias": np.zeros((d,), np.float32)}

    def dense_p(r, shape, std):
        entry = {"kernel": (r.standard_normal(shape, np.float32) * std)}
        if cfg.use_bias:
            entry["bias"] = np.zeros((shape[-1],), np.float32)
        return entry

    def factory(which):
        if which == "other":
            r = np.random.default_rng(seed)
            other = {
                "wte": {"embedding": (r.standard_normal(
                    (cfg.vocab_size, d), np.float32) * 0.02)},
                "ln_f": norm_p(),
            }
            if cfg.use_wpe:
                other["wpe"] = {"embedding": (r.standard_normal(
                    (cfg.max_seq_len, d), np.float32) * 0.02)}
            if not cfg.tie_embeddings:
                other["lm_head"] = {"kernel": (r.standard_normal(
                    (d, cfg.vocab_size), np.float32) * 0.02)}
            return other
        i = int(which)
        r = np.random.default_rng(seed + 1 + i)
        resid = 0.02 / np.sqrt(2.0 * cfg.n_layers)
        layer = {
            "ln1": norm_p(),
            "qkv": dense_p(r, (d, cfg.qkv_dim), 0.02),
            "attn_out": dense_p(r, (d, d), resid),
            "ln2": norm_p(),
            "mlp_in": dense_p(r, (d, ff), 0.02),
            "mlp_out": dense_p(r, (ff, d), resid),
        }
        if cfg.activation == "swiglu":
            layer["mlp_gate"] = dense_p(r, (d, ff), 0.02)
        return layer

    return factory


def kv_bytes_per_token(cfg: GPTConfig, dtype=jnp.bfloat16) -> int:
    """Bytes of K+V cache ONE token occupies across all layers — the
    paged-cache allocator's budget unit (inference/paged_cache.py). The
    static engine pays this for `max_batch x S_max` slots up front; the
    paged cache pays it per token actually in flight. The plain K and V
    pools' count: another cache dialect says its own beside it
    (inference/dialect.py ``bytes_per_token``, ``slot_bytes``)."""
    return int(2 * cfg.n_layers * cfg.kv_heads * cfg.head_dim
               * jnp.dtype(dtype).itemsize)


def decode_geometry(cfg: GPTConfig, block_size: int,
                    max_seq_len: Optional[int] = None) -> Tuple[int, int]:
    """(blocks_per_slot, tokens_per_slot) for a block-paged KV cache over
    this config: the per-request block table is sized to cover the model's
    maximum sequence, rounded up to whole blocks. Shared by the paged
    cache, the serving scheduler and the engine's slot programs so all
    three agree on the gathered cache's virtual length."""
    assert block_size >= 1
    s = max_seq_len or cfg.max_seq_len
    nb = -(-s // block_size)
    return nb, nb * block_size


def num_params(cfg: GPTConfig) -> int:
    d, L, ff, V = cfg.d_model, cfg.n_layers, cfg.ffn_dim, cfg.vocab_size
    qkv = cfg.qkv_dim                  # (H + 2*Hkv) * Dh — GQA-aware
    nb = 1 if cfg.use_bias else 0
    per_layer = (d * qkv + nb * qkv + d * d + nb * d
                 + 2 * d * ff + nb * (ff + d)
                 + (2 if cfg.norm == "layernorm" else 1) * 2 * d)
    if cfg.activation == "swiglu":
        per_layer += d * ff + nb * ff  # separate gate kernel
    n = V * d + L * per_layer + (2 if cfg.norm == "layernorm" else 1) * d
    if cfg.use_wpe:
        n += cfg.max_seq_len * d
    if not cfg.tie_embeddings:
        n += d * V
    return n


def train_flops_per_token(cfg: GPTConfig, seq_len: int,
                          include_head: bool = True) -> float:
    """Model flops per token, fwd+bwd — Megatron-LM-style accounting
    (the reference's own lineage): 6*N_matmul + attention, where N_matmul
    counts every matmul parameter including the logit projection (for tied
    embeddings the d*V head matmul is real compute even though the weight
    is shared with wte)."""
    N = num_params(cfg) - cfg.vocab_size * cfg.d_model  # drop wte lookup
    if cfg.tie_embeddings and include_head:
        N += cfg.d_model * cfg.vocab_size  # the tied logit matmul
    attn = 12 * cfg.n_layers * cfg.d_model * seq_len
    return 6.0 * N + attn
