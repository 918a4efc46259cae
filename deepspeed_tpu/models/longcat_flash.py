"""The ``longcat_flash`` decoder dialect (LongCat-Flash-Chat): every layer
is a shortcut-connected DOUBLE layer. Two latent-attention sublayers and
two dense SwiGLUs in sequence, each with its own norm and residual, and ONE
expert layer that reads the normed stream after the FIRST attention and
whose result joins the stream at the END of the layer (in a deployment the
experts' exchange runs under the second attention and both dense FFNs; on
one chip the layer runs where it stands)::

    x1 = x  + MLA_a(ln1a(x))
    u  = ln2a(x1);  m = M(u)
    x2 = x1 + F_a(u)
    x3 = x2 + MLA_b(ln1b(x2))
    x4 = x3 + F_b(ln2b(x3))
    out = x4 + m

The latent attention is models/dots_vlm.py's (queries through a low rank
with its own norm, one normalised latent and one shared rotated key a
token) with plain rotary (``rope_factor`` 1) and BOTH normed low ranks
rescaled (``q_lora_scale`` = sqrt(d / r_q), ``kv_lora_scale`` = sqrt(d /
r_kv): on both parts of the query and on the latent as the cache row holds
it, not on the shared key). The expert layer's router is a softmax over
``num_experts`` experts AND ``n_zero_experts`` zero-compute identity
experts behind them (moe/expert_share.py: ``router_scoring``,
``router_renorm``, ``n_zero_experts``), ``moe_k`` choices a token by a
bias that only selects, the chosen probabilities times ``routed_scaling``,
not renormalised, no shared expert, no leading dense layer. Served through
the paged engine only, by inference/latent.py: TWO cache rows a token a
layer in the one latent pool.

The config is DotsVLMConfig with other data, not a dialect of its own:
everything that differs is a field the latent blocks and the expert layer
read (``attn_sublayers``, the two scales, the router's three).

Parameters (stacked on axis 0 over the double layers): ``wte.embedding
[V, d]``, ``ln_f.scale``, ``lm_head.kernel [d, V]``, and ``block`` with the
two sublayers UNDER TWO NAMES, ``a`` and ``b`` (not stacked on a second
axis: a sublayer's tree is then exactly what ``latent.attend_prefill`` /
``attend_decode`` and the dense FFN take of a dots_vlm layer: ``ln1``,
``q_a``, ``q_a_norm``, ``kv_a``, ``kv_a_norm``, ``k_up``, ``v_up``,
``attn_out``, ``ln2``, ``mlp_gate`` / ``mlp_in`` / ``mlp_out``; and the
layer loop slices one leaf a layer, never a sublayer out of a pair), and
``moe`` = ``router`` (``kernel [d, E + Z]``, ``bias [E + Z]``) and
``experts`` (``wg`` / ``wi`` / ``wo`` over the held experts). ONE kernel is
stored otherwise than dots_vlm's: the query's up-projection TRANSPOSED,
``q_b_t`` ``[H (d_n + d_r), r_q]`` in ``q_b``'s place. Both serving
programs want the heads' queries head-major (the absorb's batch dimension,
the prefill kernel's queries in the lanes), and the compiler got that by
slicing ``q_b`` ``[1536, 12288]`` out of its stack and re-laying it every
sublayer of every dispatch (38 MB, eight times a step:
benchmark/tools/size_longcat_flash.py ``relaid``; PERF.md 7(ah) found the
same in Kimi-Linear); stored with the low rank minor it is read where it
lies (inference/latent.py ``_project``)."""

import math
from dataclasses import dataclass
from typing import Dict

import jax
import jax.numpy as jnp

from deepspeed_tpu.models.dots_vlm import DotsVLMConfig, layer_bases  # noqa: F401  (the pool's offsets are dots_vlm's, by attn_sublayers)


@dataclass
class LongcatFlashConfig(DotsVLMConfig):
    norm_eps: float = 1e-5
    # plain rotary
    rope_theta: float = 1e7
    rope_factor: float = 1.0
    attn_sublayers: int = 2
    # the expert layer: no leading dense layer, every layer holds both
    n_dense_layers: int = 0
    num_experts: int = 512            # real experts of the router's width
    n_zero_experts: int = 256         # identity experts behind them
    moe_k: int = 12
    n_shared_experts: int = 0
    routed_scaling: float = 6.0
    n_group: int = 1
    topk_group: int = 1
    router_scoring: str = "softmax"
    router_renorm: bool = False

    def __post_init__(self):
        super().__post_init__()
        assert self.n_dense_layers == 0 and self.attn_sublayers == 2
        assert self.router_scoring in ("sigmoid", "softmax")


def lora_scale(d_model: int, rank: int) -> float:
    """The factor ``mla_scale_q_lora`` / ``mla_scale_kv_lora`` put on a
    normed low rank: sqrt(hidden / rank)."""
    return math.sqrt(d_model / rank)


def init_params(rng: jax.Array, cfg: LongcatFlashConfig, std: float = 0.02,
                bias_std: float = 0.02) -> Dict:
    """Random weights: every matrix normal(``std``), unit norm scales, the
    router's selection bias normal(``bias_std``). float32; the engine casts
    to its dtype."""
    d, H, f = cfg.d_model, cfg.n_heads, cfg.moe_d_ff
    rq, rkv = cfg.q_lora_rank, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    L, held = cfg.n_layers, cfg.held[1]
    width = cfg.num_experts + cfg.n_zero_experts
    keys = iter(jax.random.split(rng, 40))

    def normal(shape, s=std):
        return jax.random.normal(next(keys), shape, jnp.float32) * s

    def sublayer():
        return {"ln1": {"scale": jnp.ones((L, d))},
                "q_a": {"kernel": normal((L, d, rq))},
                "q_a_norm": {"scale": jnp.ones((L, rq))},
                "q_b_t": {"kernel": normal((L, H * (dn + dr), rq))},
                "kv_a": {"kernel": normal((L, d, rkv + dr))},
                "kv_a_norm": {"scale": jnp.ones((L, rkv))},
                "k_up": {"kernel": normal((L, H, dn, rkv))},
                "v_up": {"kernel": normal((L, H, rkv, dv))},
                "attn_out": {"kernel": normal((L, H * dv, d))},
                "ln2": {"scale": jnp.ones((L, d))},
                "mlp_gate": {"kernel": normal((L, d, cfg.ffn_dim))},
                "mlp_in": {"kernel": normal((L, d, cfg.ffn_dim))},
                "mlp_out": {"kernel": normal((L, cfg.ffn_dim, d))}}

    moe = {"router": {"kernel": normal((L, d, width)),
                      "bias": normal((L, width), bias_std)},
           "experts": {"wg": {"kernel": normal((L, held, d, f))},
                       "wi": {"kernel": normal((L, held, d, f))},
                       "wo": {"kernel": normal((L, held, f, d))}}}
    return {"wte": {"embedding": normal((cfg.vocab_size, d))},
            "block": {"a": sublayer(), "b": sublayer(), "moe": moe},
            "ln_f": {"scale": jnp.ones((d,))},
            "lm_head": {"kernel": normal((d, cfg.vocab_size))}}


def num_params(cfg: LongcatFlashConfig) -> int:
    """Parameters as ``cfg`` holds them: ``n_layers`` double layers, the
    HELD experts, the vocabulary's rows as given (the untied head beside
    the embedding). The published count is that of a config with every
    expert held, all layers and the whole vocabulary."""
    d, H = cfg.d_model, cfg.n_heads
    rq, rkv = cfg.q_lora_rank, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    attn = d * rq + rq + rq * H * (dn + dr) + d * (rkv + dr) + rkv \
        + rkv * H * (dn + dv) + H * dv * d
    dense = 3 * d * cfg.ffn_dim
    width = cfg.num_experts + cfg.n_zero_experts
    router = d * width + width                    # + the selection bias
    layer = 2 * (attn + dense) + router + 4 * d \
        + cfg.held[1] * 3 * d * cfg.moe_d_ff
    return cfg.n_layers * layer + 2 * cfg.vocab_size * d + d
