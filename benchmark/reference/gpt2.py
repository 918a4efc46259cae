"""Plain reference: GPT-2's forward pass and per-token loss in float32
``jax.numpy`` with ``default_matmul_precision("highest")``. No kernels, no
cache, no batching tricks, and no code shared with ``deepspeed_tpu``.

Follows "Language Models are Unsupervised Multitask Learners" (Radford et
al., 2019) and the released model: learned token and position embeddings,
pre-LayerNorm blocks (eps 1e-5) of causal multi-head attention scaled by
1/sqrt(head size) and a 4x MLP with the tanh-approximated GELU
(``gelu_new``), a final LayerNorm, and an output projection tied to the
token embedding. No departure from the published description.

Takes the weights in the stacked layout the benchmark generates (layer
weights stacked on axis 0); whatever their type, every layer is computed
in float32."""

import jax
import jax.numpy as jnp

EPS = 1e-5


def _f32(tree):
    return jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), tree)


def _layernorm(x, p):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + EPS) * p["scale"] + p["bias"]


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        0.7978845608028654 * (x + 0.044715 * x ** 3)))


def _layer(x, p, n_head: int):
    """One block on x [B, S, d] with this layer's weights ``p``."""
    p = _f32(p)
    B, S, d = x.shape
    dh = d // n_head
    h = _layernorm(x, p["ln1"])
    qkv = h @ p["qkv"]["kernel"] + p["qkv"]["bias"]
    q, k, v = (t.reshape(B, S, n_head, dh).transpose(0, 2, 1, 3)
               for t in jnp.split(qkv, 3, axis=-1))
    scores = q @ k.transpose(0, 1, 3, 2) / jnp.sqrt(jnp.float32(dh))
    causal = jnp.tril(jnp.ones((S, S), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    ctx = jax.nn.softmax(scores, axis=-1) @ v
    ctx = ctx.transpose(0, 2, 1, 3).reshape(B, S, d)
    x = x + ctx @ p["attn_out"]["kernel"] + p["attn_out"]["bias"]
    h = _layernorm(x, p["ln2"])
    m = _gelu_new(h @ p["mlp_in"]["kernel"] + p["mlp_in"]["bias"])
    return x + m @ p["mlp_out"]["kernel"] + p["mlp_out"]["bias"]


_layer_jit = jax.jit(_layer, static_argnums=(2,))


@jax.jit
def _embed(wte, wpe, tokens):
    S = tokens.shape[1]
    return wte.astype(jnp.float32)[tokens] + wpe.astype(jnp.float32)[:S][None]


@jax.jit
def _head(x, ln_f, wte):
    return _layernorm(x, _f32(ln_f)) @ wte.astype(jnp.float32).T


def logits(params, tokens, n_head: int):
    """float32 logits [B, S, V] for tokens [B, S]."""
    with jax.default_matmul_precision("highest"):
        x = _embed(params["wte"]["embedding"], params["wpe"]["embedding"],
                   tokens)
        n_layer = params["block"]["qkv"]["kernel"].shape[0]
        for i in range(n_layer):
            x = _layer_jit(x, jax.tree_util.tree_map(
                lambda w: w[i], params["block"]), n_head)
        return _head(x, params["ln_f"], params["wte"]["embedding"])


def token_losses(lg, targets):
    """Cross-entropy [B, S] of float32 logits [B, S, V] at targets [B, S]."""
    logp = jax.nn.log_softmax(lg.astype(jnp.float32), axis=-1)
    return -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]

