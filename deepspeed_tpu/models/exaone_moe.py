"""The ``exaone_moe`` decoder dialect (K-EXAONE): a llama-style block
(RMSNorm, SwiGLU, GQA, untied head, no learned positions) with an explicit
head size, RMSNorm on each head's q and k, a per-layer attention kind
(``"sliding"`` window layers, which alone carry rotary, and ``"full"``
layers with no positional encoding), leading dense layers with their own
FFN width, and sparse layers of routed experts plus a shared expert
(moe/expert_share.py). Served through the paged engine only
(inference/hybrid.py): window layers keep a bounded ring per slot, full
layers the paged pool.

A config class of its own, beside ``GPTConfig``: the fields below mean
nothing to the dense trainer, the GShard ``MoEGPTConfig`` is a training
contract (capacity, aux loss), and every paged block function here reads
``cfg`` the way the GPT blocks do (``n_heads``, ``kv_heads``, ``head_dim``,
``norm``...), so one subclass keeps ``_norm`` / ``_dense`` / the engine's
checks working unchanged.

Parameters (stacked on axis 0 over the layers of one SHAPE):
``wte.embedding [V, d]``, ``ln_f.scale``, ``lm_head.kernel [d, V]``;
``dense_block`` (the ``n_dense_layers`` leading layers) and ``block`` (the
sparse layers), each with ``ln1``, ``qkv`` ``[d, (H + 2 Hkv) Dh]``,
``q_norm`` / ``k_norm`` ``[Dh]``, ``attn_out`` ``[H Dh, d]``, ``ln2`` and
either ``mlp_gate`` / ``mlp_in`` / ``mlp_out`` or ``moe``:
``router.kernel [d, E]``, ``router.bias [E]`` (selection only),
``experts.wg|wi|wo.kernel [held, d, f] | [held, f, d]``, ``shared`` (a
SwiGLU of width ``n_shared_experts * f``)."""

from dataclasses import dataclass
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.models.gpt import GPTConfig


@dataclass
class ExaoneMoEConfig(GPTConfig):
    norm: str = "rmsnorm"
    activation: str = "swiglu"
    use_bias: bool = False
    use_wpe: bool = False
    tie_embeddings: bool = False
    rope_theta: float = 1e6
    qk_norm: bool = True
    # one of "sliding" | "full" per layer; sliding layers attend
    # (t - attn_window, t] and are the only ones that carry rotary
    layer_kinds: Tuple[str, ...] = ()
    n_dense_layers: int = 1           # leading layers with a dense FFN (d_ff)
    num_experts: int = 128            # the router's width, as published
    moe_k: int = 8
    moe_d_ff: int = 2048
    n_shared_experts: int = 1
    routed_scaling: float = 2.5
    # (first, count): the routed experts this chip holds; None = all
    experts_held: Optional[Tuple[int, int]] = None

    def __post_init__(self):
        assert len(self.layer_kinds) == self.n_layers, \
            (len(self.layer_kinds), self.n_layers)
        assert set(self.layer_kinds) <= {"sliding", "full"}, self.layer_kinds
        if "sliding" in self.layer_kinds:
            assert self.attn_window, "sliding layers need attn_window"
        assert self.head_size, "the dialect states its head size"
        # 0: every layer sparse (models/smallthinker.py)
        assert 0 <= self.n_dense_layers < self.n_layers
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
        return sum(k == "full" for k in self.layer_kinds)

    @property
    def n_window_layers(self) -> int:
        return self.n_layers - self.n_full_layers


def window_blocks(cfg, block_size: int) -> int:
    """Blocks of one slot's ring in a window layer: the window's reach,
    ``attn_window - 1`` tokens back from the current one, touches at most
    this many blocks whatever the alignment."""
    return -(-(cfg.attn_window - 1) // block_size) + 1


def init_params(rng: jax.Array, cfg: ExaoneMoEConfig, std: float = 0.02,
                bias_std: float = 0.02) -> Dict:
    """Random weights in the family's initialisation: every matrix
    normal(``std``), unit norm scales, the router's selection bias
    normal(``bias_std``). float32; the engine casts to its dtype."""
    d, H, Hkv, Dh = cfg.d_model, cfg.n_heads, cfg.kv_heads, cfg.head_dim
    f, E = cfg.moe_d_ff, cfg.num_experts
    held = cfg.held[1]
    keys = iter(jax.random.split(rng, 32))

    def normal(shape, s=std):
        return jax.random.normal(next(keys), shape, jnp.float32) * s

    def attn(L):
        return {"ln1": {"scale": jnp.ones((L, d))},
                "qkv": {"kernel": normal((L, d, (H + 2 * Hkv) * Dh))},
                "q_norm": {"scale": jnp.ones((L, Dh))},
                "k_norm": {"scale": jnp.ones((L, Dh))},
                "attn_out": {"kernel": normal((L, H * Dh, d))},
                "ln2": {"scale": jnp.ones((L, d))}}

    def swiglu(L, width):
        return {"mlp_gate": {"kernel": normal((L, d, width))},
                "mlp_in": {"kernel": normal((L, d, width))},
                "mlp_out": {"kernel": normal((L, width, d))}}

    Ld, Ls = cfg.n_dense_layers, cfg.n_sparse_layers
    dense = dict(attn(Ld), **swiglu(Ld, cfg.ffn_dim))
    sparse = attn(Ls)
    sparse["moe"] = {
        "router": {"kernel": normal((Ls, d, E)),
                   "bias": normal((Ls, E), bias_std)},
        "experts": {"wg": {"kernel": normal((Ls, held, d, f))},
                    "wi": {"kernel": normal((Ls, held, d, f))},
                    "wo": {"kernel": normal((Ls, held, f, d))}},
        "shared": swiglu(Ls, cfg.n_shared_experts * f)}
    return {"wte": {"embedding": normal((cfg.vocab_size, d))},
            "dense_block": dense, "block": sparse,
            "ln_f": {"scale": jnp.ones((d,))},
            "lm_head": {"kernel": normal((d, cfg.vocab_size))}}


def layer_bases(cfg: ExaoneMoEConfig, n_full: int, n_win: int):
    """Where each layer's state starts in the two flat pools
    (engine._scan_layers): ``full [L]`` and ``win [L]`` block offsets
    (``n_full`` / ``n_win`` blocks a layer; a layer of the other kind
    points at block 0, the first layer's trash block, so its write lands
    nowhere), ``sliding [L]`` bool and ``index [L]``. Split (dense layers,
    sparse layers)."""
    sliding = np.array([k == "sliding" for k in cfg.layer_kinds])
    full = np.where(sliding, 0, (np.cumsum(~sliding) - 1) * n_full)
    win = np.where(sliding, (np.cumsum(sliding) - 1) * n_win, 0)
    nd = cfg.n_dense_layers
    bases = {"full": full.astype(np.int32), "win": win.astype(np.int32),
             "sliding": sliding,
             # a sparse layer's row in the dispatch's routing record
             "index": np.maximum(np.arange(cfg.n_layers) - nd, 0
                                 ).astype(np.int32)}
    return ({k: jnp.asarray(v[:nd]) for k, v in bases.items()},
            {k: jnp.asarray(v[nd:]) for k, v in bases.items()})
