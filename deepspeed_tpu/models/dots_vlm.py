"""The ``dots_vlm`` language model's decoder dialect (dots.vlm1, whose keys
are DeepSeek-V3's one for one): a pre-norm RMSNorm block whose attention is
LATENT (MLA: queries through a low rank with its own norm; keys and values
re-expanded from one normalised latent of ``kv_lora_rank`` values a token,
beside ONE rotated key of ``qk_rope_head_dim`` values that all heads share;
YaRN rotary on that part of a head alone), leading dense SwiGLU layers, and
sparse layers of sigmoid-routed experts chosen inside the best
``topk_group`` of ``n_group`` groups, plus a shared expert
(moe/expert_share.py). Served through the paged engine only
(inference/latent.py): a token's cache row is ``(c_kv, k_r)``, one pool, no
V pool and no KV heads. The vision tower is not part of this dialect: its
output rows would be spliced into a prompt's embeddings, and text requests
never run it.

A config class of its own beside ``ExaoneMoEConfig``, not a generalisation
of it: that class IS its two attention kinds (``layer_kinds`` is required
and keys inference/hybrid.py), and of the attention here nothing is
shared with it (no KV heads, three head sizes, two low ranks). What the two
do share they share by NAME, so the expert layer and the layer loop read
either alike: ``n_dense_layers``, ``experts_held``, ``num_experts``,
``moe_k``, ``moe_d_ff``, ``n_shared_experts``, ``routed_scaling``, the
parameter tree's ``moe`` subtree, ``layer_bases``' sparse ``index``.

Parameters (stacked on axis 0 over the layers of one SHAPE):
``wte.embedding [V, d]``, ``ln_f.scale``, ``lm_head.kernel [d, V]``;
``dense_block`` and ``block``, each with ``ln1``, ``q_a`` ``[d, r_q]``,
``q_a_norm`` ``[r_q]``, ``q_b`` ``[r_q, H (d_n + d_r)]`` (a head's no-rope
part, then its rope part), ``kv_a`` ``[d, r_kv + d_r]``, ``kv_a_norm``
``[r_kv]``, the up-projection ``W_kvb`` split by what reads it: ``k_up``
``[H, d_n, r_kv]`` (head j's ``W^K_j`` transposed: the absorbed query is
``q_n_j @ k_up[j]``) and ``v_up`` ``[H, r_kv, d_v]``, ``attn_out``
``[H d_v, d]``, ``ln2`` and either ``mlp_gate`` / ``mlp_in`` / ``mlp_out``
or ``moe`` (as models/exaone_moe.py)."""

from dataclasses import dataclass
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.models.gpt import GPTConfig
from deepspeed_tpu.ops.attention.rotary import yarn_inv_freq, yarn_mscale

LANES = 128


@dataclass
class DotsVLMConfig(GPTConfig):
    norm: str = "rmsnorm"
    norm_eps: float = 1e-6
    activation: str = "swiglu"
    use_bias: bool = False
    use_wpe: bool = False
    tie_embeddings: bool = False
    # latent attention
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    # factors on the two NORMED low ranks (inference/latent.py _project)
    q_lora_scale: float = 1.0
    kv_lora_scale: float = 1.0
    # attention sublayers a layer, each with a cache row of its own
    attn_sublayers: int = 1
    # YaRN on the rope part (rope_factor 1 = plain rotary)
    rope_theta: float = 10000.0
    rope_factor: float = 40.0
    rope_original_max: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 1.0
    # the expert layer (names as ExaoneMoEConfig's)
    n_dense_layers: int = 1
    num_experts: int = 256            # the router's width, as published
    moe_k: int = 8
    moe_d_ff: int = 2048
    n_shared_experts: int = 1
    routed_scaling: float = 2.5
    n_group: int = 8                  # the router's groups of experts
    topk_group: int = 4               # groups a token may choose inside
    # (first, count): the routed experts this chip holds; None = all
    experts_held: Optional[Tuple[int, int]] = None

    def __post_init__(self):
        # leading "dense" layers, then "sparse" ones; or none leading and
        # every layer "both" (inference/hybrid.py ffn_kind states the three)
        assert 0 <= self.n_dense_layers < self.n_layers
        assert self.num_experts % self.n_group == 0
        assert 0 < self.topk_group <= self.n_group
        assert self.qk_rope_head_dim % 2 == 0
        first, count = self.held
        assert 0 <= first and first + count <= self.num_experts

    @property
    def held(self) -> Tuple[int, int]:
        return self.experts_held or (0, self.num_experts)

    @property
    def n_sparse_layers(self) -> int:
        return self.n_layers - self.n_dense_layers

    @property
    def n_full_layers(self) -> int:
        """The attention sublayers whose history is rows of the paged
        pool: every layer's."""
        return self.n_layers * self.attn_sublayers

    @property
    def latent_row(self) -> int:
        """Values of a token's cache row: the latent and the shared key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def latent_lanes(self) -> int:
        """The row as the pool stores it: padded to whole lane tiles (HBM
        tiles a minor dimension by 128 anyway, and the kernel's products
        then end on a tile's edge); the padding is zeros."""
        return -(-self.latent_row // LANES) * LANES

    @property
    def softmax_scale(self) -> float:
        m = yarn_mscale(self.rope_factor, self.rope_mscale_all_dim)
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5 * m * m

    @property
    def rope_inv_freq(self) -> np.ndarray:
        """YaRN's per-pair frequencies (factor 1: plain rotary's)."""
        return yarn_inv_freq(self.qk_rope_head_dim, self.rope_theta,
                             self.rope_factor, self.rope_original_max,
                             self.rope_beta_fast, self.rope_beta_slow)


def init_params(rng: jax.Array, cfg: DotsVLMConfig, std: float = 0.02,
                bias_std: float = 0.02) -> Dict:
    """Random weights: every matrix normal(``std``), unit norm scales, the
    router's selection bias normal(``bias_std``). float32; the engine casts
    to its dtype."""
    d, H, f, E = cfg.d_model, cfg.n_heads, cfg.moe_d_ff, cfg.num_experts
    rq, rkv = cfg.q_lora_rank, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    held = cfg.held[1]
    keys = iter(jax.random.split(rng, 40))

    def normal(shape, s=std):
        return jax.random.normal(next(keys), shape, jnp.float32) * s

    def attn(L):
        return {"ln1": {"scale": jnp.ones((L, d))},
                "q_a": {"kernel": normal((L, d, rq))},
                "q_a_norm": {"scale": jnp.ones((L, rq))},
                "q_b": {"kernel": normal((L, rq, H * (dn + dr)))},
                "kv_a": {"kernel": normal((L, d, rkv + dr))},
                "kv_a_norm": {"scale": jnp.ones((L, rkv))},
                "k_up": {"kernel": normal((L, H, dn, rkv))},
                "v_up": {"kernel": normal((L, H, rkv, dv))},
                "attn_out": {"kernel": normal((L, H * dv, d))},
                "ln2": {"scale": jnp.ones((L, d))}}

    def swiglu(L, width):
        return {"mlp_gate": {"kernel": normal((L, d, width))},
                "mlp_in": {"kernel": normal((L, d, width))},
                "mlp_out": {"kernel": normal((L, width, d))}}

    Ld, Ls = cfg.n_dense_layers, cfg.n_sparse_layers
    sparse = attn(Ls)
    sparse["moe"] = {
        "router": {"kernel": normal((Ls, d, E)),
                   "bias": normal((Ls, E), bias_std)},
        "experts": {"wg": {"kernel": normal((Ls, held, d, f))},
                    "wi": {"kernel": normal((Ls, held, d, f))},
                    "wo": {"kernel": normal((Ls, held, f, d))}},
        "shared": swiglu(Ls, cfg.n_shared_experts * f)}
    return {"wte": {"embedding": normal((cfg.vocab_size, d))},
            "dense_block": dict(attn(Ld), **swiglu(Ld, cfg.ffn_dim)),
            "block": sparse, "ln_f": {"scale": jnp.ones((d,))},
            "lm_head": {"kernel": normal((d, cfg.vocab_size))}}


def layer_bases(cfg: DotsVLMConfig, n_blocks: int):
    """Where each layer's rows start in the flat latent pool
    (engine._scan_layers; ``n_blocks`` blocks an attention sublayer) and a
    sparse layer's row ``index`` in the dispatch's routing record. Split
    (dense layers, sparse layers). With ``attn_sublayers`` S > 1 a layer's
    ``rows`` are S offsets, sublayer j of layer l at ``(l S + j)
    n_blocks``."""
    nd, S = cfg.n_dense_layers, cfg.attn_sublayers
    layers = np.arange(cfg.n_layers)
    rows = layers * S * n_blocks
    if S > 1:
        rows = rows[:, None] + np.arange(S) * n_blocks
    bases = {"rows": rows.astype(np.int32),
             "index": np.maximum(layers - nd, 0).astype(np.int32)}
    return ({k: jnp.asarray(v[:nd]) for k, v in bases.items()},
            {k: jnp.asarray(v[nd:]) for k, v in bases.items()})
