"""Analytic HBM estimator + compile-memory guard.

Why analytic, not XLA cost analysis: the estimate must come before the
compile. A program estimated within ~1GB of a 16GB v5e spends its compile
in a long memory-fitting search and then runs out of memory anyway (seen
on an earlier machine; PERF.md's bring-up section records what the
current one does). So peak bytes are estimated from the model/config
shape alone, and a bench refuses to compile anything too close to device
HBM.

Reference analog: the autotuner prunes configs by an activation+state
memory model *before* launching them
(ref: deepspeed/autotuning/autotuner.py:396 mem-per-GPU pruning;
ref: deepspeed/runtime/zero/stage3.py memory estimators
``estimate_zero3_model_states_mem_needs``).

Calibration (measured on a 16GB v5e with an earlier toolchain, PERF.md):
- gpt2-1.5B b16 full-remat + chunked CE: compiles ~2min, runs (the
  headline). Estimate must stay SAFE.
- same + flash_only remat (saves ~2.6GB flash residuals), or b24/b32, or
  selective remat at b4+ (5.9GB saved acts at b4): compile grind / OOM.
  Estimates must be REFUSED.
- gpt2-medium selective b8/b16 + chunked CE: comfortable. SAFE.
"""

from dataclasses import dataclass, field
from typing import Any, Dict, Optional

GiB = 1024 ** 3

# default distance-to-HBM below which we refuse to compile (GiB). The
# known-good 1.5B headline estimates ~14.4GB on 16GB — refusing anything
# estimated past (HBM - 1.2GiB) keeps it runnable while rejecting every
# config that ground the compiler or ran out of memory.
DEFAULT_HEADROOM_GIB = 1.2

# allocator/fragmentation + small-buffer slack added to every estimate
FUDGE_BYTES = int(0.25 * GiB)

KNOWN_HBM = {  # by device_kind substring (lowercased)
    "v5 lite": 16 * GiB,
    "v5e": 16 * GiB,
    "v5p": 95 * GiB,
    "v4": 32 * GiB,
    "v6": 32 * GiB,
}


class MemoryGuardError(RuntimeError):
    """Raised when a config's estimated peak HBM is too close to device
    capacity to compile safely."""


@dataclass
class MemoryEstimate:
    contributions: Dict[str, int] = field(default_factory=dict)

    @property
    def total(self) -> int:
        return sum(self.contributions.values())

    def summary(self) -> str:
        parts = ", ".join(f"{k}={v / GiB:.2f}GiB"
                          for k, v in self.contributions.items())
        return f"{self.total / GiB:.2f}GiB ({parts})"


def _dtype_bytes(precision: str) -> int:
    return {"bf16": 2, "fp16": 2, "fp32": 4}[precision]


def state_bytes(n_params: int, precision: str = "bf16",
                memory_efficient: bool = False,
                optimizer: str = "adamw") -> Dict[str, int]:
    """Persistent training-state bytes: params + optimizer moments
    [+ fp32 masters]. Shared by the full estimator and the engine's
    HBM-headroom warning so the two can't drift."""
    pb = _dtype_bytes(precision)
    if precision == "fp32":
        opt = 8 * n_params                       # fp32 m+v
    elif memory_efficient:
        opt = 4 * n_params                       # bf16 m+v (SR updates)
    else:
        opt = 12 * n_params                      # fp32 master + m + v
    if optimizer == "adagrad":
        opt = opt * 2 // 3                       # single moment
    return {"params": n_params * pb, "optimizer": opt}


def estimate_train_bytes(
    *,
    n_params: int,
    n_layers: int,
    d_model: int,
    ffn_dim: int,
    qkv_dim: int,
    n_heads: int,
    vocab_size: int,
    batch: int,
    seq: int,
    precision: str = "bf16",
    memory_efficient: bool = False,
    remat: bool = True,
    remat_policy: str = "full",
    loss_chunk: int = 0,
    optimizer: str = "adamw",
) -> MemoryEstimate:
    """Peak training HBM for one data-parallel shard of a GPT-style model.

    Peak model: persistent state (params + optimizer moments [+ masters])
    plus max(gradients, live activations) — under reverse-mode scan the
    gradient buffer fills as the saved activations drain, so they mostly
    don't coexist at full size — plus the loss-path working set and an
    allocator fudge.

    Activation widths (units of d_model per token per layer, bf16) by
    remat policy, counted from what each policy saves for backward:
    - none:       ln1+ln2 (2) + qkv + flash o (1) + attn out (1) +
                  gelu in+out (2*ffn/d) + mlp out (1)
    - selective:  qkv + flash o (1) + gelu in (ffn/d) + mlp out (1)
                  [measured 9.38*d at 1.5B — PERF.md b4-selective 5.9GB]
    - full:       layer-boundary hidden only (1)
    - flash_only: boundary (1) + packed flash o residual (1)
                  [measured +2.6GB at 1.5B b16 — PERF.md]
    full/flash_only additionally pay ONE layer's un-rematted working set
    (transient, not *L) during the per-layer recompute.
    """
    est = MemoryEstimate()
    pb = _dtype_bytes(precision)

    # --- persistent training state -----------------------------------
    est.contributions.update(state_bytes(n_params, precision,
                                         memory_efficient, optimizer))

    grad_bytes = n_params * pb                   # accumulator or transient

    # --- activations --------------------------------------------------
    tokens = batch * seq
    ffn_w = ffn_dim / d_model
    qkv_w = qkv_dim / d_model
    none_width = 2 + qkv_w + 1 + 1 + 2 * ffn_w + 1
    if not remat:
        width, transient = none_width, 0.0
    elif remat_policy == "selective":
        width, transient = qkv_w + 1 + ffn_w + 1, 0.0
    elif remat_policy == "flash_only":
        width, transient = 2.0, none_width
    else:
        # 'full' — and 'offload_flash', whose saved residuals live in
        # pinned HOST memory, so device HBM matches full remat
        width, transient = 1.0, none_width
    act_bytes = int(tokens * n_layers * width * d_model * 2)
    act_bytes += int(tokens * transient * d_model * 2)   # one-layer recompute
    act_bytes += tokens * n_layers * n_heads * 4         # flash lse (fp32)
    # grads fill while saved activations drain: peak is the larger one
    est.contributions["grads_or_acts"] = max(grad_bytes, act_bytes)

    # --- loss path ----------------------------------------------------
    # one cost model for both paths: rows processed at once x fp32
    # (logits + softmax + bwd residual). Dense is simply chunk=inf —
    # using a SMALLER per-row factor for dense (as r3 did: 8 vs 12)
    # breaks the monotonicity the guard's safety rests on in the
    # clamped regime chunk >= tokens, where the two programs coincide
    # (hypothesis counterexample: b1/s256/chunk2048)
    rows = min(loss_chunk, tokens) if loss_chunk else tokens
    est.contributions["loss"] = rows * vocab_size * 12

    est.contributions["fudge"] = FUDGE_BYTES
    return est


def estimate_gpt_train_bytes(cfg, batch: int, seq: Optional[int] = None,
                             **kw) -> MemoryEstimate:
    """Convenience wrapper mapping a models.gpt.GPTConfig."""
    from deepspeed_tpu.models import gpt
    return estimate_train_bytes(
        n_params=gpt.num_params(cfg), n_layers=cfg.n_layers,
        d_model=cfg.d_model, ffn_dim=cfg.ffn_dim, qkv_dim=cfg.qkv_dim,
        n_heads=cfg.n_heads, vocab_size=cfg.vocab_size,
        batch=batch, seq=seq or cfg.max_seq_len,
        remat=cfg.remat, remat_policy=cfg.remat_policy,
        loss_chunk=cfg.loss_chunk, **kw)


def estimate_bert_train_bytes(cfg, batch: int, seq: Optional[int] = None,
                              **kw) -> MemoryEstimate:
    """Convenience wrapper mapping a models.bert.BertConfig. The encoder
    layer is the classic post/pre-LN transformer (ffn = 4d, fused qkv =
    3d); bidirectional attention changes flops, not live bytes, so the
    GPT activation-width model carries over unchanged."""
    from deepspeed_tpu.models import bert
    return estimate_train_bytes(
        n_params=bert.num_params(cfg), n_layers=cfg.n_layers,
        d_model=cfg.d_model, ffn_dim=4 * cfg.d_model,
        qkv_dim=3 * cfg.d_model, n_heads=cfg.n_heads,
        vocab_size=cfg.vocab_size, batch=batch,
        seq=seq or cfg.max_seq_len, remat=cfg.remat,
        remat_policy=cfg.remat_policy, loss_chunk=cfg.loss_chunk, **kw)


def estimate_moe_train_bytes(cfg, batch: int, seq: Optional[int] = None,
                             **kw) -> MemoryEstimate:
    """models.moe_gpt.MoEGPTConfig variant: the dense-GPT estimate (with
    the MoE param count — experts dominate) plus the gating/dispatch
    working set of ONE layer (transient under the moe remat policy):
    fp32 combine weights + dispatch mask [B, S, E, C] and the dispatched
    expert activations [E, C_total, d..ffn]."""
    from deepspeed_tpu.models import moe_gpt
    from deepspeed_tpu.moe.sharded_moe import _capacity
    seq = seq or cfg.max_seq_len
    est = estimate_train_bytes(
        n_params=moe_gpt.num_params(cfg), n_layers=cfg.n_layers,
        d_model=cfg.d_model, ffn_dim=cfg.ffn_dim, qkv_dim=cfg.qkv_dim,
        n_heads=cfg.n_heads, vocab_size=cfg.vocab_size, batch=batch,
        seq=seq, remat=cfg.remat, remat_policy=cfg.remat_policy,
        loss_chunk=cfg.loss_chunk, **kw)
    E = cfg.num_experts
    cf = cfg.capacity_factor * (2 if cfg.moe_k == 2 else 1)
    C = _capacity(seq, E, cf, cfg.min_capacity)
    dispatch = batch * seq * E * C * 5            # fp32 combine + bool mask
    expert_act = E * C * batch * (cfg.d_model + cfg.ffn_dim) * 2
    est.contributions["moe_dispatch"] = dispatch + expert_act
    return est


def estimate_infer_bytes(cfg, batch: int,
                         max_seq: Optional[int] = None) -> MemoryEstimate:
    """Inference working set for a models.gpt config: bf16 params, the
    preallocated [L, B, S_max, Hkv, Dh] KV cache pair, one fp32 logits
    row per sequence, and the prefill activation transient."""
    from deepspeed_tpu.models import gpt
    est = MemoryEstimate()
    max_seq = max_seq or cfg.max_seq_len
    pb = 2                                        # bf16 serving
    est.contributions["params"] = gpt.num_params(cfg) * pb
    est.contributions["kv_cache"] = (
        2 * cfg.n_layers * batch * max_seq * cfg.kv_heads
        * cfg.head_dim * pb)
    est.contributions["logits"] = batch * cfg.vocab_size * 4
    # prefill holds one layer's qkv/ffn working set across the prompt
    est.contributions["prefill"] = int(
        batch * max_seq * (cfg.qkv_dim + cfg.ffn_dim + 2 * cfg.d_model) * pb)
    est.contributions["fudge"] = FUDGE_BYTES
    return est


def device_hbm_bytes(device: Any = None) -> Optional[int]:
    """Device HBM capacity, via memory_stats when the backend exposes it,
    else the known-capacity table. None for CPU/unknown (no guard)."""
    if device is None:
        import jax
        devices = jax.devices()
        if not devices:
            return None
        device = devices[0]
    if device.platform == "cpu":
        return None
    try:
        stats = device.memory_stats() or {}
        if stats.get("bytes_limit"):
            return int(stats["bytes_limit"])
    except Exception:  # dslint: disable=DS006 — probe falls through to the known-HBM table
        pass
    kind = (device.device_kind or "").lower()
    for k, v in KNOWN_HBM.items():
        if k in kind:
            return v
    return None


def check_compile_safe(est: MemoryEstimate, hbm_bytes: Optional[int],
                       headroom_gib: float = DEFAULT_HEADROOM_GIB):
    """Returns (ok, message). ok=True when the estimate clears the
    headroom or HBM capacity is unknown (nothing to guard against)."""
    if hbm_bytes is None:
        return True, "device HBM unknown — guard inactive"
    limit = hbm_bytes - int(headroom_gib * GiB)
    msg = (f"estimated peak {est.total / GiB:.2f}GiB vs limit "
           f"{limit / GiB:.2f}GiB (HBM {hbm_bytes / GiB:.0f}GiB - "
           f"{headroom_gib}GiB compile headroom): {est.summary()}")
    return est.total <= limit, msg


def _guard(est: MemoryEstimate, device, headroom_gib) -> str:
    ok, msg = check_compile_safe(est, device_hbm_bytes(device), headroom_gib)
    if not ok:
        raise MemoryGuardError(
            f"refusing to compile: {msg}. Shrink batch/model or use "
            f"remat_policy='full' + loss_chunk.")
    return msg


def guard_gpt_config(cfg, batch: int, seq: Optional[int] = None,
                     device: Any = None,
                     headroom_gib: float = DEFAULT_HEADROOM_GIB,
                     **estimate_kw) -> str:
    """Raise MemoryGuardError if compiling this training config risks the
    borderline-HBM compile grind; returns the decision message otherwise."""
    return _guard(estimate_gpt_train_bytes(cfg, batch, seq, **estimate_kw),
                  device, headroom_gib)


def guard_bert_config(cfg, batch: int, seq: Optional[int] = None,
                      device: Any = None,
                      headroom_gib: float = DEFAULT_HEADROOM_GIB,
                      **estimate_kw) -> str:
    """Encoder (BERT) variant of :func:`guard_gpt_config`."""
    return _guard(estimate_bert_train_bytes(cfg, batch, seq, **estimate_kw),
                  device, headroom_gib)


def guard_moe_config(cfg, batch: int, seq: Optional[int] = None,
                     device: Any = None,
                     headroom_gib: float = DEFAULT_HEADROOM_GIB,
                     **estimate_kw) -> str:
    """MoE-GPT variant of :func:`guard_gpt_config` (adds the dispatch
    working set on top of the dense estimate)."""
    return _guard(estimate_moe_train_bytes(cfg, batch, seq, **estimate_kw),
                  device, headroom_gib)


def guard_infer_config(cfg, batch: int, max_seq: Optional[int] = None,
                       device: Any = None,
                       headroom_gib: float = DEFAULT_HEADROOM_GIB) -> str:
    """Inference variant: params + KV cache + logits + prefill transient."""
    return _guard(estimate_infer_bytes(cfg, batch, max_seq),
                  device, headroom_gib)
