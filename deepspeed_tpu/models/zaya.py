"""The ``zaya`` decoder dialect (ZAYA1): every layer is an attention
sublayer and an expert sublayer, each merged into the stream by a learned
RESIDUAL SCALING ``(s_r x + b_r) + (s_o f(norm(x)) + b_o)``.

Attention is CCA (compressed convolutional attention) with grouped-query
heads: queries and keys are projected DOWN into a latent of ``n_heads`` /
``kv_heads`` heads of ``head_size`` (8 x 128 and 2 x 128 from a stream of
2,048), mixed there by two causal convolutions over time (a depthwise one
of ``cca_time0`` taps, then one of ``cca_time1`` taps grouped by head) plus
the mean of the raw query and key heads, L2-normalised per head, the keys
scaled by a learned temperature, and rotated on the first
``partial_rotary_factor`` of each head; the value is two halves of one KV
head each, the second taken from the PREVIOUS token. What comes out is
plain grouped-query attention inside the latent (inference/cca.py), and
``attn_out`` projects ``n_heads * head_size`` back UP to the stream.

The expert sublayer picks ONE of ``num_experts`` SwiGLU experts, or a skip
output that adds nothing, by an MLP router of width ``router_hidden`` whose
down-projected state is mixed into the next layer's (moe/expert_share.py
``route_mlp``); the gate is the chosen probability itself and there is no
shared expert and no leading dense layer.

A config class of its own beside ``ExaoneMoEConfig`` and ``DotsVLMConfig``;
the expert layer's field names are theirs (``n_dense_layers``,
``experts_held``, ``num_experts``, ``moe_k``, ``moe_d_ff``,
``n_shared_experts``, ``routed_scaling``, the ``moe`` subtree,
``layer_bases``' sparse ``index``).

Parameters (stacked on axis 0 over the layers; C = (n_heads + kv_heads) *
head_size channels of [q~ | k~]): ``wte.embedding [V, d]`` (tied head),
``ln_f.scale``; ``block``: ``ln1``, ``qkv.kernel [d, C + 2 head_size]``
(columns q~, k~, then the value halves ``W_v1``, ``W_v2``),
``conv0.kernel [t0, C]`` / ``.bias [C]`` (tap ``j`` meets the token
``t0 - 1 - j`` steps back), ``conv1.kernel [t1, heads, head_size,
head_size]`` (a head's channels in, out) / ``.bias [C]``, ``temp
[kv_heads]``, ``attn_out.kernel``, ``res1`` / ``res2`` (``s_r``, ``b_r``,
``s_o``, ``b_o``, each ``[d]``), ``ln2``, and ``moe``: ``router`` (``down``
kernel + bias, ``mix`` ``[R]``, ``norm.scale``, ``w1`` / ``w2`` kernel +
bias, ``w3.kernel [R, E + 1]``, the selection ``bias [E + 1]``) and
``experts`` (``wg`` / ``wi`` / ``wo`` over the held experts)."""

from dataclasses import dataclass
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.models.gpt import GPTConfig


@dataclass
class ZayaConfig(GPTConfig):
    norm: str = "rmsnorm"
    norm_eps: float = 1e-5
    activation: str = "swiglu"
    use_bias: bool = False
    use_wpe: bool = False
    tie_embeddings: bool = True
    # attention inside the latent: n_heads x head_size, not d_model
    head_size: Optional[int] = 128
    n_kv_heads: Optional[int] = 2
    cca_time0: int = 2                # taps of the depthwise convolution
    cca_time1: int = 2                # taps of the convolution by head
    partial_rotary_factor: float = 0.5
    rope_theta: float = 5e6
    # the expert layer (names as ExaoneMoEConfig's)
    n_dense_layers: int = 0
    num_experts: int = 16             # the router has one more output: skip
    moe_k: int = 1
    moe_d_ff: int = 2048
    n_shared_experts: int = 0
    routed_scaling: float = 1.0
    router_hidden: int = 256          # width of the router's carried state
    # (first, count): the routed experts this chip holds; None = all
    experts_held: Optional[Tuple[int, int]] = None

    def __post_init__(self):
        assert self.n_dense_layers == 0 and self.n_shared_experts == 0
        assert self.moe_k == 1 and self.router_hidden > 0
        assert self.cca_time0 == 2 and self.cca_time1 == 2, \
            "the per-slot tail holds ONE earlier token's row"
        assert self.rotary_channels % 2 == 0
        first, count = self.held
        assert 0 <= first and first + count <= self.num_experts

    @property
    def held(self) -> Tuple[int, int]:
        return self.experts_held or (0, self.num_experts)

    @property
    def n_sparse_layers(self) -> int:
        return self.n_layers

    @property
    def cca_channels(self) -> int:
        """Channels of [q~ | k~], what the convolutions mix."""
        return (self.n_heads + self.kv_heads) * self.head_dim

    @property
    def rotary_channels(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)

    @property
    def cca_tail_values(self) -> int:
        """Values a slot keeps per layer between dispatches: the previous
        token's [q~ | k~] row, its first convolution's output, and its
        half of the next token's value."""
        return 2 * self.cca_channels + self.head_dim


def init_params(rng: jax.Array, cfg: ZayaConfig, std: float = 0.02,
                bias_std: float = 0.02) -> Dict:
    """Random weights: every matrix normal(``std``); the residual scalings
    near their rest (scales 1 + 0.1 n, biases 0.02 n) and the
    convolutions' biases, the router's biases and mixing vector, the
    temperatures and the selection bias normal(``bias_std``), so that a
    dropped term shows. float32; the engine casts to its dtype."""
    d, H, Hkv, Dh = cfg.d_model, cfg.n_heads, cfg.kv_heads, cfg.head_dim
    f, E, R, C = cfg.moe_d_ff, cfg.num_experts, cfg.router_hidden, \
        cfg.cca_channels
    L, held = cfg.n_layers, cfg.held[1]
    keys = iter(jax.random.split(rng, 48))

    def normal(shape, s=std):
        return jax.random.normal(next(keys), shape, jnp.float32) * s

    def res():
        return {"s_r": 1.0 + normal((L, d), 0.1), "b_r": normal((L, d)),
                "s_o": 1.0 + normal((L, d), 0.1), "b_o": normal((L, d))}

    block = {
        "ln1": {"scale": jnp.ones((L, d))},
        "qkv": {"kernel": normal((L, d, C + 2 * Dh))},
        "conv0": {"kernel": normal((L, cfg.cca_time0, C), 0.5),
                  "bias": normal((L, C), bias_std)},
        "conv1": {"kernel": normal((L, cfg.cca_time1, H + Hkv, Dh, Dh),
                                   Dh ** -0.5),
                  "bias": normal((L, C), bias_std)},
        "temp": normal((L, Hkv), 0.1),
        "attn_out": {"kernel": normal((L, H * Dh, d))},
        "res1": res(), "ln2": {"scale": jnp.ones((L, d))}, "res2": res(),
        "moe": {
            "router": {
                "down": {"kernel": normal((L, d, R)),
                         "bias": normal((L, R), bias_std)},
                "mix": 0.5 + normal((L, R), 0.1),
                "norm": {"scale": jnp.ones((L, R))},
                "w1": {"kernel": normal((L, R, R), R ** -0.5),
                       "bias": normal((L, R), bias_std)},
                "w2": {"kernel": normal((L, R, R), R ** -0.5),
                       "bias": normal((L, R), bias_std)},
                "w3": {"kernel": normal((L, R, E + 1), R ** -0.5)},
                "bias": normal((L, E + 1), bias_std)},
            "experts": {"wg": {"kernel": normal((L, held, d, f))},
                        "wi": {"kernel": normal((L, held, d, f))},
                        "wo": {"kernel": normal((L, held, f, d))}}}}
    return {"wte": {"embedding": normal((cfg.vocab_size, d))},
            "block": block, "ln_f": {"scale": jnp.ones((d,))}}


def layer_bases(cfg: ZayaConfig, n_blocks: int, n_slots: int):
    """Where each layer's rows start in the flat K and V pools
    (engine._scan_layers; ``n_blocks`` blocks a layer) and in the flat
    per-slot tails (``n_slots`` rows a layer), and the layer's row
    ``index`` in the dispatch's routing record. Split (dense layers: none,
    sparse layers) as engine._dense_then_sparse takes them."""
    layers = np.arange(cfg.n_layers)
    return None, {"rows": jnp.asarray((layers * n_blocks).astype(np.int32)),
                  "tail": jnp.asarray((layers * n_slots).astype(np.int32)),
                  "index": jnp.asarray(layers.astype(np.int32))}
