"""The ``kimi_linear`` decoder dialect (Kimi-Linear): a pre-norm RMSNorm
block whose attention is one of TWO kinds, given by a list in the config.

- ``kda_layers``: gated delta-rule LINEAR attention (KDA). Queries, keys
  and values of ``linear_heads`` heads of ``linear_head_dim`` pass a
  depthwise causal convolution of ``conv_kernel`` taps and SiLU; q and k are
  L2-normalised per head; a decay per head and KEY CHANNEL ``a = exp(-exp(
  A_log) softplus((x W_fa) W_fb + dt_bias))`` and a write strength ``b =
  sigmoid(x W_b)`` drive the recurrence ``S = (I - b k k^T) Diag(a) S + b k
  v^T`` on a float32 state of ``[keys, values]`` a head, read by ``o = S^T
  q``; the output is normalised per head, gated by ``sigmoid((x W_ga)
  W_gb)`` and projected back. A sequence's whole history is that state:
  megabytes a slot whatever its length, no rows to page
  (inference/linear.py, ops/attention/kda.py).
- ``full_attn_layers``: latent attention (MLA) as models/dots_vlm.py has
  it, with ONE query projection (``q_lora_rank`` None) and NO rotation
  (``mla_use_nope``): no positions enter the model anywhere, order is
  carried by the KDA layers. These layers' rows are the paged pool.

The two lists are 1-indexed, as published, and need follow no period (the
published model ends ``KDA, KDA, MLA``). The FFN is dots_vlm's: leading
dense SwiGLU layers, then sigmoid-routed experts (one group: no group
limit) plus a shared expert (moe/expert_share.py), under the names that
module reads.

Parameters: ``wte.embedding [V, d]``, ``ln_f.scale``, ``lm_head.kernel
[d, V]``; the attention sublayers stacked BY KIND, each in layer order:
``kda`` (``ln1``, ``qkv.kernel [d, 3 H Dh]`` (columns q, k, v), ``conv.kernel
[taps, 3 H Dh]`` (tap ``j`` meets the token ``taps - 1 - j`` steps back),
``f_a`` ``[d, Dh]`` / ``f_b`` ``[Dh, H Dh]``, ``A_log [H]``, ``dt_bias [H
Dh]``, ``b.kernel [d, H]``, ``g_a`` / ``g_b`` as ``f_*``, ``o_norm.scale
[Dh]``, ``attn_out.kernel [H Dh, d]``) and ``mla`` (``ln1``, ``q.kernel
[d, H (d_n + d_r)]``, ``kv_a``, ``kv_a_norm``, ``k_up``, ``v_up``,
``attn_out`` as dots_vlm's); the FFN sublayers stacked by SHAPE:
``dense_block`` (``ln2``, ``mlp_gate`` / ``mlp_in`` / ``mlp_out``) and
``block`` (``ln2``, ``moe``)."""

from dataclasses import dataclass
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.models.dots_vlm import LANES
from deepspeed_tpu.models.gpt import GPTConfig
# what the layer loop asks of the two lists (its tests' names)
from deepspeed_tpu.models.recurrent import layer_bases, layer_runs  # noqa: F401


@dataclass
class KimiLinearConfig(GPTConfig):
    norm: str = "rmsnorm"
    norm_eps: float = 1e-5
    activation: str = "swiglu"
    use_bias: bool = False
    use_wpe: bool = False
    tie_embeddings: bool = False
    # which layers are of which kind, 1-indexed as published
    kda_layers: Tuple[int, ...] = ()
    full_attn_layers: Tuple[int, ...] = ()
    # linear attention
    linear_heads: int = 32
    linear_head_dim: int = 128
    conv_kernel: int = 4
    l2_eps: float = 1e-6
    # latent attention: one query projection, nothing rotated
    q_lora_rank: Optional[int] = None
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    mla_use_nope: bool = True
    # the expert layer (names as ExaoneMoEConfig's and DotsVLMConfig's)
    n_dense_layers: int = 1
    num_experts: int = 256
    moe_k: int = 8
    moe_d_ff: int = 1024
    n_shared_experts: int = 1
    routed_scaling: float = 2.446
    n_group: int = 1
    topk_group: int = 1
    # (first, count): the routed experts this chip holds; None = all
    experts_held: Optional[Tuple[int, int]] = None

    def __post_init__(self):
        self.kda_layers = tuple(int(l) for l in self.kda_layers)
        self.full_attn_layers = tuple(int(l) for l in self.full_attn_layers)
        both = sorted(self.kda_layers + self.full_attn_layers)
        assert both == list(range(1, self.n_layers + 1)), \
            "kda_layers and full_attn_layers name every layer once, from 1"
        assert self.kda_layers and self.full_attn_layers
        assert 0 < self.n_dense_layers < self.n_layers
        assert all(self.attn_kinds[:self.n_dense_layers] == 0), \
            "the leading dense layers are linear-attention layers"
        assert self.q_lora_rank is None and self.mla_use_nope
        assert self.n_group == 1 and self.topk_group == 1
        first, count = self.held
        assert 0 <= first and first + count <= self.num_experts

    @property
    def held(self) -> Tuple[int, int]:
        return self.experts_held or (0, self.num_experts)

    @property
    def n_sparse_layers(self) -> int:
        return self.n_layers - self.n_dense_layers

    @property
    def attn_kinds(self) -> np.ndarray:
        """Per layer (0-indexed): 0 linear attention, 1 latent."""
        kinds = np.zeros(self.n_layers, np.int32)
        kinds[np.asarray(self.full_attn_layers) - 1] = 1
        return kinds

    @property
    def n_kda_layers(self) -> int:
        return len(self.kda_layers)

    @property
    def n_full_layers(self) -> int:
        """The layers whose history is rows of the paged pool."""
        return len(self.full_attn_layers)

    @property
    def recurrent_stacks(self) -> Tuple[str, str]:
        """The parameter stacks of the two kinds (recurrent, paged)."""
        return "kda", "mla"

    @property
    def n_recurrent_layers(self) -> int:
        return self.n_kda_layers

    @property
    def recurrent_state_shape(self) -> Tuple[int, ...]:
        """One slot's state in one linear layer (ops/attention/kda.py
        keeps it transposed, the key channels on the lanes)."""
        return (self.linear_heads, self.linear_head_dim,
                self.linear_head_dim)

    @property
    def conv_tail_width(self) -> int:
        """One slot's tail in one linear layer: the last ``conv_kernel -
        1`` tokens' un-convolved rows side by side."""
        return (self.conv_kernel - 1) * self.kda_channels

    @property
    def kda_channels(self) -> int:
        """Channels of [q | k | v], what the convolution mixes."""
        return 3 * self.linear_heads * self.linear_head_dim

    @property
    def latent_row(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def latent_lanes(self) -> int:
        return -(-self.latent_row // LANES) * LANES

    @property
    def softmax_scale(self) -> float:
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5

    @property
    def recurrent_state_values(self) -> int:
        """float32 values a slot keeps in all linear-attention layers."""
        return self.n_kda_layers * self.linear_heads \
            * self.linear_head_dim ** 2

    @property
    def conv_tail_values(self) -> int:
        """Values a slot keeps of the last ``conv_kernel - 1`` tokens'
        un-convolved [q | k | v] rows, all linear-attention layers."""
        return self.n_kda_layers * (self.conv_kernel - 1) * self.kda_channels


def trained_decay(rng, shape_a, shape_dt):
    """``A_log`` and ``dt_bias`` drawn as TRAINED ones: ``A = U(1, 16)``
    and ``dt_bias`` the inverse softplus of a step log-uniform in [1e-3,
    1e-1], so a decay sits near 1 and the state remembers."""
    ka, kd = jax.random.split(rng)
    a_log = jnp.log(jax.random.uniform(ka, shape_a, jnp.float32, 1.0, 16.0))
    dt = jnp.exp(jax.random.uniform(kd, shape_dt, jnp.float32,
                                    np.log(1e-3), np.log(1e-1)))
    return a_log, dt + jnp.log(-jnp.expm1(-dt))


def init_params(rng: jax.Array, cfg: KimiLinearConfig, std: float = 0.02,
                bias_std: float = 0.02) -> Dict:
    """Random weights: every matrix normal(``std``), the convolution's taps
    normal(0.5), unit norm scales, the router's selection bias
    normal(``bias_std``), the decay's ``A_log`` / ``dt_bias`` as
    :func:`trained_decay`. float32; the engine casts to its dtype."""
    d, f, E = cfg.d_model, cfg.moe_d_ff, cfg.num_experts
    H, Dh, C = cfg.linear_heads, cfg.linear_head_dim, cfg.kda_channels
    Hm, rkv = cfg.n_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    held = cfg.held[1]
    keys = iter(jax.random.split(rng, 48))

    def normal(shape, s=std):
        return jax.random.normal(next(keys), shape, jnp.float32) * s

    def swiglu(L, width):
        return {"mlp_gate": {"kernel": normal((L, d, width))},
                "mlp_in": {"kernel": normal((L, d, width))},
                "mlp_out": {"kernel": normal((L, width, d))}}

    Lk, Lm = cfg.n_kda_layers, cfg.n_full_layers
    Ld, Ls = cfg.n_dense_layers, cfg.n_sparse_layers
    a_log, dt_bias = trained_decay(next(keys), (Lk, H), (Lk, H * Dh))
    kda = {"ln1": {"scale": jnp.ones((Lk, d))},
           "qkv": {"kernel": normal((Lk, d, C))},
           "conv": {"kernel": normal((Lk, cfg.conv_kernel, C), 0.5)},
           "f_a": {"kernel": normal((Lk, d, Dh))},
           "f_b": {"kernel": normal((Lk, Dh, H * Dh))},
           "A_log": a_log, "dt_bias": dt_bias,
           "b": {"kernel": normal((Lk, d, H))},
           "g_a": {"kernel": normal((Lk, d, Dh))},
           "g_b": {"kernel": normal((Lk, Dh, H * Dh))},
           "o_norm": {"scale": jnp.ones((Lk, Dh))},
           "attn_out": {"kernel": normal((Lk, H * Dh, d))}}
    mla = {"ln1": {"scale": jnp.ones((Lm, d))},
           "q": {"kernel": normal((Lm, d, Hm * (dn + dr)))},
           "kv_a": {"kernel": normal((Lm, d, rkv + dr))},
           "kv_a_norm": {"scale": jnp.ones((Lm, rkv))},
           "k_up": {"kernel": normal((Lm, Hm, dn, rkv))},
           "v_up": {"kernel": normal((Lm, Hm, rkv, dv))},
           "attn_out": {"kernel": normal((Lm, Hm * dv, d))}}
    sparse = {"ln2": {"scale": jnp.ones((Ls, d))}, "moe": {
        "router": {"kernel": normal((Ls, d, E)),
                   "bias": normal((Ls, E), bias_std)},
        "experts": {"wg": {"kernel": normal((Ls, held, d, f))},
                    "wi": {"kernel": normal((Ls, held, d, f))},
                    "wo": {"kernel": normal((Ls, held, f, d))}},
        "shared": swiglu(Ls, cfg.n_shared_experts * f)}}
    return {"wte": {"embedding": normal((cfg.vocab_size, d))},
            "kda": kda, "mla": mla,
            "dense_block": dict({"ln2": {"scale": jnp.ones((Ld, d))}},
                                **swiglu(Ld, cfg.ffn_dim)),
            "block": sparse, "ln_f": {"scale": jnp.ones((d,))},
            "lm_head": {"kernel": normal((d, cfg.vocab_size))}}
