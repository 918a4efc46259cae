"""The ``qwen3_next`` decoder dialect (Qwen3-Next): a pre-norm RMSNorm
block whose attention is one of TWO kinds, by a period in the config, each
followed by a sparse FFN with a gated shared expert.

- Layer ``l`` (from 0) with ``(l + 1) % full_attention_interval != 0`` is
  Gated DeltaNet LINEAR attention: ``linear_key_heads`` heads of q and k and
  ``linear_value_heads`` heads of v (value head ``h`` reads key head ``h //
  (value heads / key heads)``), all of ``linear_head_dim``, pass a depthwise
  causal convolution of ``conv_kernel`` taps and SiLU; q and k are
  L2-normalised per head; ONE decay a value head a token ``a = exp(-exp(
  A_log) softplus(alpha + dt_bias))`` and a write strength ``b = sigmoid(
  beta)`` (both from one projection ``in_ba``) drive the recurrence ``S =
  (I - b k k^T) a S + b k v^T`` on a float32 state of ``[keys, values]`` a
  value head, read by ``o = S^T q``; the output is RMS-normalised per head
  with a PLAIN scale, times ``silu(z)`` (``z`` a full-rank projection), and
  projected back. The rule is KDA's (models/kimi_linear.py) with the decay a
  scalar: the same per-slot state and tails, the same step kernel, another
  chunk form (ops/attention/kda.py ``gdn_chunk``).
- Every ``full_attention_interval``-th layer is softmax attention over K/V
  rows in the paged pools: ``n_heads`` query heads on ``n_kv_heads`` K/V
  heads of ``head_size``; the query projection is twice as wide, per head
  ``[q | gate]``, and ``sigmoid(gate)`` scales the attention's OUTPUT
  (``attn_output_gate``); RMSNorm on each head's q and k (``qk_norm``);
  rotate-half rotary on the first ``rotary_dim`` channels of every head
  (``rotary_half``). These are the engine's own two paged attention paths
  with those three as data (inference/engine.py ``_qkv_heads``).

Every norm but the linear layers' gated one stores its scale as an OFFSET
from one (``norm_offset``: ``y * (1 + scale)``, zeros at initialisation).
The FFN is moe/expert_share.py's: a softmax router over all experts, the k
chosen weights renormalised, no bias, and a shared expert times ``sigmoid(h
. w_sg)`` (``shared_expert_gate``). Left out: the multi-token-prediction
module.

Parameters: ``wte.embedding [V, d]``, ``ln_f.scale``, ``lm_head.kernel
[d, V]``; the attention sublayers stacked BY KIND, each in layer order:
``gdn`` (``ln1``, ``in_qkvz.kernel [d, 2 Hk Dh + 2 Hv Dh]`` (columns q, k,
v, z), ``in_ba.kernel [d, 2 Hv]`` (columns b, alpha), ``conv.kernel [taps,
2 Hk Dh + Hv Dh]`` (tap ``j`` meets the token ``taps - 1 - j`` steps back),
``A_log [Hv]``, ``dt_bias [Hv]``, ``o_norm.scale [Dh]``, ``attn_out.kernel
[Hv Dh, d]``) and ``attn`` (``ln1``, ``qkv.kernel [d, (2 H + 2 Hkv) Dh]``
(columns: per head ``[q | gate]``, then k, then v), ``q_norm`` / ``k_norm``
``.scale [Dh]``, ``attn_out.kernel [H Dh, d]``); the FFNs in one stack
``block`` (``ln2``, ``moe``: ``router.kernel [d, E]``, ``experts``,
``shared``, ``shared_gate.kernel [d, 1]``)."""

from dataclasses import dataclass
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.models.gpt import GPTConfig
from deepspeed_tpu.models.kimi_linear import trained_decay


@dataclass
class Qwen3NextConfig(GPTConfig):
    norm: str = "rmsnorm"
    norm_eps: float = 1e-6
    norm_offset: bool = True
    activation: str = "swiglu"
    use_bias: bool = False
    use_wpe: bool = False
    tie_embeddings: bool = False
    # which layers page K and V: every ``full_attention_interval``-th
    full_attention_interval: int = 4
    # the two halves of the linear dialect, chosen apart (inference/linear.py)
    recurrent_rule: str = "gdn"
    paged_kind: str = "kv"
    # linear attention
    linear_key_heads: int = 16
    linear_value_heads: int = 32
    linear_head_dim: int = 128
    conv_kernel: int = 4
    l2_eps: float = 1e-6
    # full attention
    n_heads: int = 16
    n_kv_heads: int = 2
    head_size: int = 256
    attn_output_gate: bool = True
    qk_norm: bool = True
    rotary_dim: int = 64
    rotary_half: bool = True
    rope_theta: float = 1e7
    # the expert layer (names as KimiLinearConfig's)
    n_dense_layers: int = 0
    num_experts: int = 512
    moe_k: int = 10
    moe_d_ff: int = 512
    n_shared_experts: int = 1
    shared_expert_gate: bool = True
    router_scoring: str = "softmax"
    router_renorm: bool = True
    routed_scaling: float = 1.0
    n_group: int = 1
    topk_group: int = 1
    # (first, count): the routed experts this chip holds; None = all
    experts_held: Optional[Tuple[int, int]] = None

    def __post_init__(self):
        assert self.n_dense_layers == 0
        assert self.linear_value_heads % self.linear_key_heads == 0
        assert self.rotary_dim % 2 == 0 and self.rotary_dim <= self.head_dim
        kinds = self.attn_kinds
        assert 0 < kinds.sum() < self.n_layers, \
            "both kinds of layer: the interval names some layers, not all"
        first, count = self.held
        assert 0 <= first and first + count <= self.num_experts

    @property
    def held(self) -> Tuple[int, int]:
        return self.experts_held or (0, self.num_experts)

    @property
    def n_sparse_layers(self) -> int:
        return self.n_layers

    @property
    def attn_kinds(self) -> np.ndarray:
        """Per layer (0-indexed): 0 linear attention, 1 full attention."""
        return ((np.arange(self.n_layers) + 1)
                % self.full_attention_interval == 0).astype(np.int32)

    @property
    def recurrent_stacks(self) -> Tuple[str, str]:
        """The parameter stacks of the two kinds (recurrent, paged)."""
        return "gdn", "attn"

    @property
    def n_full_layers(self) -> int:
        """The layers whose history is rows of the paged pools."""
        return int(self.attn_kinds.sum())

    @property
    def n_recurrent_layers(self) -> int:
        return self.n_layers - self.n_full_layers

    @property
    def linear_heads(self) -> int:
        """Heads of the rule and of its state: the value heads (a key head
        is repeated onto the value heads it serves)."""
        return self.linear_value_heads

    @property
    def gdn_channels(self) -> int:
        """Channels of [q | k | v], what the convolution mixes."""
        return (2 * self.linear_key_heads + self.linear_value_heads) \
            * self.linear_head_dim

    @property
    def recurrent_state_shape(self) -> Tuple[int, ...]:
        """One slot's state in one linear layer (ops/attention/kda.py keeps
        it transposed, the key channels on the lanes)."""
        return (self.linear_value_heads, self.linear_head_dim,
                self.linear_head_dim)

    @property
    def conv_tail_width(self) -> int:
        """One slot's tail in one linear layer: the last ``conv_kernel -
        1`` tokens' un-convolved rows side by side."""
        return (self.conv_kernel - 1) * self.gdn_channels

    @property
    def recurrent_state_values(self) -> int:
        """float32 values a slot keeps in all linear-attention layers."""
        return self.n_recurrent_layers * self.linear_value_heads \
            * self.linear_head_dim ** 2

    @property
    def conv_tail_values(self) -> int:
        return self.n_recurrent_layers * self.conv_tail_width


def init_params(rng: jax.Array, cfg: Qwen3NextConfig, std: float = 0.02,
                norm_std: float = 0.02) -> Dict:
    """Random weights: every matrix normal(``std``), the convolution's taps
    normal(0.5), the offset norms' scales normal(``norm_std``) about ZERO
    (so the offset shows) and the gated norm's plain scale about ONE, the
    decay's ``A_log`` / ``dt_bias`` as kimi_linear's :func:`trained_decay`,
    one a value head. float32; the engine casts to its dtype."""
    d, f, E, L = cfg.d_model, cfg.moe_d_ff, cfg.num_experts, cfg.n_layers
    Hv, Dl, C = cfg.linear_value_heads, cfg.linear_head_dim, cfg.gdn_channels
    H, Hkv, Dh = cfg.n_heads, cfg.kv_heads, cfg.head_dim
    Lg, La = cfg.n_recurrent_layers, cfg.n_full_layers
    held = cfg.held[1]
    keys = iter(jax.random.split(rng, 40))

    def normal(shape, s=std):
        return jax.random.normal(next(keys), shape, jnp.float32) * s

    def offset(*shape):
        return {"scale": normal(shape, norm_std)}

    a_log, dt_bias = trained_decay(next(keys), (Lg, Hv), (Lg, Hv))
    gdn = {"ln1": offset(Lg, d),
           "in_qkvz": {"kernel": normal((Lg, d, C + Hv * Dl))},
           "in_ba": {"kernel": normal((Lg, d, 2 * Hv))},
           "conv": {"kernel": normal((Lg, cfg.conv_kernel, C), 0.5)},
           "A_log": a_log, "dt_bias": dt_bias,
           "o_norm": {"scale": 1.0 + normal((Lg, Dl), norm_std)},
           "attn_out": {"kernel": normal((Lg, Hv * Dl, d))}}
    attn = {"ln1": offset(La, d),
            "qkv": {"kernel": normal((La, d, (2 * H + 2 * Hkv) * Dh))},
            "q_norm": offset(La, Dh), "k_norm": offset(La, Dh),
            "attn_out": {"kernel": normal((La, H * Dh, d))}}
    width = cfg.n_shared_experts * f
    block = {"ln2": offset(L, d), "moe": {
        "router": {"kernel": normal((L, d, E))},
        "experts": {"wg": {"kernel": normal((L, held, d, f))},
                    "wi": {"kernel": normal((L, held, d, f))},
                    "wo": {"kernel": normal((L, held, f, d))}},
        "shared": {"mlp_gate": {"kernel": normal((L, d, width))},
                   "mlp_in": {"kernel": normal((L, d, width))},
                   "mlp_out": {"kernel": normal((L, width, d))}},
        "shared_gate": {"kernel": normal((L, d, 1))}}}
    return {"wte": {"embedding": normal((cfg.vocab_size, d))},
            "gdn": gdn, "attn": attn, "block": block, "ln_f": offset(d),
            "lm_head": {"kernel": normal((d, cfg.vocab_size))}}


def num_params(cfg: Qwen3NextConfig) -> int:
    """Parameters of the model as ``cfg`` holds it: every layer, the HELD
    experts (all of them where ``experts_held`` is None), the embedding and
    the untied head."""
    d, f = cfg.d_model, cfg.moe_d_ff
    Hv, Dl, C = cfg.linear_value_heads, cfg.linear_head_dim, cfg.gdn_channels
    H, Hkv, Dh = cfg.n_heads, cfg.kv_heads, cfg.head_dim
    linear = d * (C + Hv * Dl) + d * 2 * Hv + cfg.conv_kernel * C \
        + 2 * Hv + Dl + Hv * Dl * d
    full = d * (2 * H + 2 * Hkv) * Dh + 2 * Dh + H * Dh * d
    # the layer's two norms, router, shared expert and its gate
    rest = 2 * d + d * cfg.num_experts + 3 * d * cfg.n_shared_experts * f + d
    experts = cfg.held[1] * 3 * d * f
    return cfg.n_recurrent_layers * linear + cfg.n_full_layers * full \
        + cfg.n_layers * (rest + experts) + d + 2 * cfg.vocab_size * d
