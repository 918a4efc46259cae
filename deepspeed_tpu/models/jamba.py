"""The ``jamba`` decoder dialect (AI21 Jamba): a pre-norm RMSNorm block
whose mixer is one of TWO kinds, by a period in the config, each followed
by a dense SwiGLU.

- Layer ``i`` (from 0) with ``i % attn_layer_period == attn_layer_offset``
  is ATTENTION: ``n_heads`` query heads on ``n_kv_heads`` K/V heads, no
  rotation and no positions anywhere (order is carried by the other kind),
  causal softmax. Its K and V rows are the paged pools, exactly the GPT
  blocks' (inference/engine.py ``_attn_prefill_paged`` /
  ``_attn_decode_paged``).
- Every other layer is a MAMBA-1 selective state-space mixer on ``d_inner
  = mamba_expand * d_model`` channels: ``[x | z] = u W_in``; ``x`` passes a
  depthwise causal convolution of ``conv_kernel`` taps (with bias) and
  SiLU; ``[dt | B | C] = x W_x``, each RMS-normalised (the family's three
  inner norms); ``delta = softplus(dt W_dt + b_dt)``; the recurrence, per
  channel ``c`` and state index ``n``, on a float32 state,

      h_t[c, n] = exp(delta_t[c] A[c, n]) h_{t-1}[c, n]
                  + delta_t[c] B_t[n] x_t[c],      A = -exp(A_log)
      y_t[c]    = sum_n C_t[n] h_t[c, n] + D[c] x_t[c]

  and ``out = (y * silu(z)) W_out``. The transition is DIAGONAL and
  data-dependent: no matrix product in it. A sequence's whole history in
  such a layer is ``d_inner x d_state`` float32 values a slot whatever its
  length (inference/ssm.py, ops/attention/ssm.py), plus the last
  ``conv_kernel - 1`` tokens' un-convolved ``x`` rows.

Parameters: ``wte.embedding [V, d]`` (tied: the head is its transpose),
``ln_f.scale``; the mixers stacked BY KIND, each in layer order: ``ssm``
(``ln1``, ``in_proj.kernel [d, 2 Di]`` (columns x, z), ``conv.kernel
[taps, Di]`` (tap ``j`` meets the token ``taps - 1 - j`` steps back) and
``conv.bias [Di]``, ``x_proj.kernel [Di, R + 2 N]`` (columns dt, B, C),
``dt_norm`` / ``b_norm`` / ``c_norm`` ``.scale``, ``dt_proj.kernel [R,
Di]`` and ``.bias``, ``A_log [N, Di]`` (the published ``[Di, N]``
TRANSPOSED, as the state is kept: the channels on the lanes), ``D [Di]``,
``out_proj.kernel [Di, d]``) and ``attn`` (``ln1``, ``qkv.kernel [d, (H + 2
Hkv) Dh]``, ``attn_out.kernel [H Dh, d]``); the FFNs in one stack
``block`` (``ln2``, ``mlp_gate`` / ``mlp_in`` / ``mlp_out``)."""

from dataclasses import dataclass
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.models.gpt import GPTConfig


@dataclass
class JambaConfig(GPTConfig):
    norm: str = "rmsnorm"
    norm_eps: float = 1e-6
    activation: str = "swiglu"
    use_bias: bool = False
    use_wpe: bool = False
    tie_embeddings: bool = True
    n_kv_heads: int = 1
    # which layers are attention
    attn_layer_period: int = 14
    attn_layer_offset: int = 7
    # the state-space mixer
    mamba_expand: int = 2
    mamba_d_state: int = 16
    mamba_dt_rank: int = 160
    conv_kernel: int = 4
    # every FFN is one dense SwiGLU (num_experts 1: no router)
    n_dense_layers: int = 0

    def __post_init__(self):
        assert self.rotary_dim in (None, 0) and not self.use_wpe, \
            "no positions enter this model"
        assert self.n_dense_layers == 0
        kinds = self.attn_kinds
        assert 0 < kinds.sum() < self.n_layers, \
            "both kinds of layer: the period and offset name some, not all"

    @property
    def attn_kinds(self) -> np.ndarray:
        """Per layer (0-indexed): 0 state-space, 1 attention."""
        layers = np.arange(self.n_layers)
        return (layers % self.attn_layer_period
                == self.attn_layer_offset).astype(np.int32)

    @property
    def recurrent_stacks(self) -> Tuple[str, str]:
        """The parameter stacks of the two kinds (recurrent, paged)."""
        return "ssm", "attn"

    @property
    def n_full_layers(self) -> int:
        """The layers whose history is rows of the paged pools."""
        return int(self.attn_kinds.sum())

    @property
    def n_recurrent_layers(self) -> int:
        return self.n_layers - self.n_full_layers

    @property
    def d_inner(self) -> int:
        return self.mamba_expand * self.d_model

    @property
    def recurrent_state_shape(self) -> Tuple[int, ...]:
        """One slot's state in one state-space layer, as it is STORED:
        the published ``[d_inner, d_state]`` transposed, so that the
        channels lie on the lanes (16 state indices on the lanes would pad
        to 128: 8 times the bytes)."""
        return (self.mamba_d_state, self.d_inner)

    @property
    def conv_tail_width(self) -> int:
        """One slot's tail in one state-space layer: the last
        ``conv_kernel - 1`` tokens' un-convolved ``x`` rows side by
        side."""
        return (self.conv_kernel - 1) * self.d_inner

    @property
    def recurrent_state_values(self) -> int:
        """float32 values a slot keeps in all state-space layers."""
        return self.n_recurrent_layers * self.d_inner * self.mamba_d_state

    @property
    def conv_tail_values(self) -> int:
        return self.n_recurrent_layers * self.conv_tail_width


def trained_step(rng, shape_dt):
    """``dt_proj.bias`` drawn as a TRAINED one: the inverse softplus of a
    step log-uniform in [1e-3, 1e-1] (the published initialiser), so that
    with ``A[c, n] = -(n + 1)`` a channel's slowest state index keeps
    ``exp(-delta)`` = 0.90-0.999 of itself a token and the state really
    remembers."""
    dt = jnp.exp(jax.random.uniform(rng, shape_dt, jnp.float32,
                                    np.log(1e-3), np.log(1e-1)))
    return dt + jnp.log(-jnp.expm1(-dt))


def init_params(rng: jax.Array, cfg: JambaConfig, std: float = 0.02) -> Dict:
    """Random weights: every matrix normal(``std``) but ``dt_proj``
    (uniform in +-``dt_rank``^-0.5, the published initialiser), the
    convolution's taps normal(0.5) and its bias normal(``std``), unit norm
    scales, ``A_log[n, c] = log(n + 1)``, ``D`` = 1, ``dt_proj.bias`` as
    :func:`trained_step`. float32; the engine casts to its dtype."""
    d, f, V = cfg.d_model, cfg.ffn_dim, cfg.vocab_size
    Di, N, R = cfg.d_inner, cfg.mamba_d_state, cfg.mamba_dt_rank
    H, Hkv, Dh = cfg.n_heads, cfg.kv_heads, cfg.head_dim
    Ls, La, L = cfg.n_recurrent_layers, cfg.n_full_layers, cfg.n_layers
    keys = iter(jax.random.split(rng, 24))

    def normal(shape, s=std):
        return jax.random.normal(next(keys), shape, jnp.float32) * s

    ssm = {"ln1": {"scale": jnp.ones((Ls, d))},
           "in_proj": {"kernel": normal((Ls, d, 2 * Di))},
           "conv": {"kernel": normal((Ls, cfg.conv_kernel, Di), 0.5),
                    "bias": normal((Ls, Di))},
           "x_proj": {"kernel": normal((Ls, Di, R + 2 * N))},
           "dt_norm": {"scale": jnp.ones((Ls, R))},
           "b_norm": {"scale": jnp.ones((Ls, N))},
           "c_norm": {"scale": jnp.ones((Ls, N))},
           "dt_proj": {"kernel": jax.random.uniform(
               next(keys), (Ls, R, Di), jnp.float32, -R ** -0.5, R ** -0.5),
               "bias": trained_step(next(keys), (Ls, Di))},
           "A_log": jnp.broadcast_to(
               jnp.log(jnp.arange(1, N + 1, dtype=jnp.float32))[None, :,
                                                                 None],
               (Ls, N, Di)),
           "D": jnp.ones((Ls, Di)),
           "out_proj": {"kernel": normal((Ls, Di, d))}}
    attn = {"ln1": {"scale": jnp.ones((La, d))},
            "qkv": {"kernel": normal((La, d, (H + 2 * Hkv) * Dh))},
            "attn_out": {"kernel": normal((La, H * Dh, d))}}
    block = {"ln2": {"scale": jnp.ones((L, d))},
             "mlp_gate": {"kernel": normal((L, d, f))},
             "mlp_in": {"kernel": normal((L, d, f))},
             "mlp_out": {"kernel": normal((L, f, d))}}
    return {"wte": {"embedding": normal((V, d))}, "ssm": ssm, "attn": attn,
            "block": block, "ln_f": {"scale": jnp.ones((d,))}}


def num_params(cfg: JambaConfig) -> int:
    """Parameters of the whole model (the tied embedding once)."""
    d, f = cfg.d_model, cfg.ffn_dim
    Di, N, R = cfg.d_inner, cfg.mamba_d_state, cfg.mamba_dt_rank
    mixer = d * 2 * Di + (cfg.conv_kernel + 1) * Di + Di * (R + 2 * N) \
        + R + 2 * N + R * Di + Di + Di * N + Di + Di * d
    attn = d * (cfg.n_heads + 2 * cfg.kv_heads) * cfg.head_dim \
        + cfg.n_heads * cfg.head_dim * d
    ffn = 3 * d * f + 2 * d                       # + the layer's two norms
    return cfg.n_recurrent_layers * (mixer + ffn) \
        + cfg.n_full_layers * (attn + ffn) + cfg.vocab_size * d + d
