"""The SmallThinker decoder (SmallThinker-21BA3B-Instruct): the
``exaone_moe`` dialect's block with other data, no dialect of its own.
Every layer alike: RMSNorm, GQA with an explicit head size and NO q/k norm,
a per-layer attention kind (``"sliding"`` window layers, which alone carry
rotary, 3 : 1 with position-free ``"full"`` layers, the period STARTING with
the full layer), no leading dense layer, and an expert layer whose router
reads the layer's INPUT, before attention (``router_reads``), chooses
``moe_k`` of ``num_experts`` by a softmax over the chosen ones, with no
bias, no scaling and no shared expert, and whose experts gate with a ReLU
(``expert_act``). Served through the paged engine only, by
inference/hybrid.py (``layer_kinds`` makes its dialect the owner): window
layers keep a bounded ring per slot, full layers the paged pool.

Parameters (stacked on axis 0 over the layers): ``wte.embedding [V, d]``,
``ln_f.scale``, ``lm_head.kernel [d, V]`` (untied); ``block`` with ``ln1``,
``qkv`` ``[d, (H + 2 Hkv) Dh]``, ``attn_out`` ``[H Dh, d]``, ``ln2`` and
``moe``: ``router.kernel [d, E]`` (no bias), ``experts.wg|wi|wo.kernel
[held, d, f] | [held, f, d]``. No ``dense_block``, no ``shared``."""

from dataclasses import dataclass
from typing import Dict, Sequence

import jax
import jax.numpy as jnp

from deepspeed_tpu.models.exaone_moe import ExaoneMoEConfig


@dataclass
class SmallThinkerConfig(ExaoneMoEConfig):
    norm_eps: float = 1e-6
    rope_theta: float = 1.5e6
    qk_norm: bool = False
    attn_window: int = 4096
    n_dense_layers: int = 0
    num_experts: int = 64
    moe_k: int = 6
    moe_d_ff: int = 768
    n_shared_experts: int = 0
    routed_scaling: float = 1.0
    # data of the expert layer (moe/expert_share.py reads each with
    # K-EXAONE's value as the default)
    router_scoring: str = "softmax"
    router_renorm: bool = True        # softmax over 64, renormalised over
    #                                   the six = softmax over the six
    router_reads: str = "layer_input"   # | "ffn_input" (after attention)
    expert_act: str = "relu"            # the gate's activation; | "silu"

    def __post_init__(self):
        super().__post_init__()
        assert self.router_reads in ("layer_input", "ffn_input")
        assert self.expert_act in ("relu", "silu")


def layer_kinds(sliding_window_layout: Sequence[int],
                rope_layout: Sequence[int], n_layers: int):
    """``layer_kinds`` of the first ``n_layers`` layers from the published
    lists. Rotary rides with the window (inference/hybrid.py ``_qkv``), so
    the two lists have to agree."""
    assert list(rope_layout) == list(sliding_window_layout), \
        "a layer carries rotary exactly where it has a window"
    return tuple("sliding" if w else "full"
                 for w in sliding_window_layout[:n_layers])


def num_params(cfg: SmallThinkerConfig) -> int:
    """Parameters of the whole model at ``cfg``'s sizes, every expert
    counted (21,506,562,560 at the published ones)."""
    d, H, Hkv, Dh = cfg.d_model, cfg.n_heads, cfg.kv_heads, cfg.head_dim
    attn = d * (H + 2 * Hkv) * Dh + H * Dh * d + 2 * d      # + two norms
    layer = attn + d * cfg.num_experts \
        + cfg.num_experts * 3 * d * cfg.moe_d_ff
    return cfg.n_layers * layer + 2 * cfg.vocab_size * d + d


def init_params(rng: jax.Array, cfg: SmallThinkerConfig,
                std: float = 0.02) -> Dict:
    """Random weights: every matrix normal(``std``), unit norm scales.
    float32; the engine casts to its dtype."""
    d, H, Hkv, Dh = cfg.d_model, cfg.n_heads, cfg.kv_heads, cfg.head_dim
    f, E, L = cfg.moe_d_ff, cfg.num_experts, cfg.n_layers
    held = cfg.held[1]
    keys = iter(jax.random.split(rng, 16))

    def normal(shape):
        return jax.random.normal(next(keys), shape, jnp.float32) * std

    block = {"ln1": {"scale": jnp.ones((L, d))},
             "qkv": {"kernel": normal((L, d, (H + 2 * Hkv) * Dh))},
             "attn_out": {"kernel": normal((L, H * Dh, d))},
             "ln2": {"scale": jnp.ones((L, d))},
             "moe": {"router": {"kernel": normal((L, d, E))},
                     "experts": {"wg": {"kernel": normal((L, held, d, f))},
                                 "wi": {"kernel": normal((L, held, d, f))},
                                 "wo": {"kernel": normal((L, held, f, d))}}}}
    return {"wte": {"embedding": normal((cfg.vocab_size, d))},
            "block": block, "ln_f": {"scale": jnp.ones((d,))},
            "lm_head": {"kernel": normal((d, cfg.vocab_size))}}
