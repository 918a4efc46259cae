"""BERT model family — MLM (+NSP) pretraining, TPU-native.

Capability match for the reference's BERT stack: the fused transformer
layer it showcases (ref: deepspeed/ops/transformer/transformer.py:460,
tutorial docs/_tutorials/bert-pretraining.md) and the full BERT parity
models its kernel tests train (ref: tests/unit/modeling.py 1,597 LoC
post-LN, modelingpreln.py pre-LN). Layers are stacked on a leading axis
and run under ``lax.scan`` (one compiled block, L iterations — the XLA
analog of the reference reusing one CUDA layer object per depth);
blocks live under the ``"block"`` pytree key so MoQ/eigenvalue's
stacked-layer machinery applies unchanged.
"""

from dataclasses import dataclass, field
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.transformer.encoder_layer import (
    DeepSpeedTransformerConfig, _layernorm, init_layer_params, layer_forward)


@dataclass
class BertConfig:
    vocab_size: int = 30522
    n_layers: int = 12
    n_heads: int = 12
    d_model: int = 768
    max_seq_len: int = 512
    type_vocab_size: int = 2
    dropout: float = 0.1
    layer_norm_eps: float = 1e-12
    pre_layer_norm: bool = True
    dtype: jnp.dtype = jnp.bfloat16
    # per-layer activation checkpointing; off by default (small-model
    # fine-tuning fits HBM) — pretraining batch sizes need it (the
    # bert_bench/pretrain call sites enable it)
    remat: bool = False
    remat_policy: str = "selective"   # see models.gpt.remat_policy
    # fused chunked MLM cross-entropy (0 = dense log_softmax). At
    # seq512 x batch32 the dense path materializes a 2GB fp32 [B,S,V]
    # logits tensor; chunking caps it at ~chunk x V (ops/cross_entropy.py)
    loss_chunk: int = 0

    @property
    def layer_config(self) -> DeepSpeedTransformerConfig:
        return DeepSpeedTransformerConfig(
            hidden_size=self.d_model, heads=self.n_heads,
            attn_dropout_ratio=self.dropout,
            hidden_dropout_ratio=self.dropout,
            num_hidden_layers=self.n_layers,
            layer_norm_eps=self.layer_norm_eps,
            pre_layer_norm=self.pre_layer_norm)


PRESETS = {
    "bert-base": dict(n_layers=12, n_heads=12, d_model=768),
    "bert-large": dict(n_layers=24, n_heads=16, d_model=1024),
    "bert-tiny": dict(n_layers=2, n_heads=2, d_model=128),
}


def preset(name: str, **overrides) -> BertConfig:
    return BertConfig(**{**PRESETS[name], **overrides})


def init_params(rng: jax.Array, cfg: BertConfig) -> Dict:
    ks = jax.random.split(rng, 8)
    s = 0.02
    d = cfg.d_model

    layer_keys = jax.random.split(ks[0], cfg.n_layers)
    per_layer = [init_layer_params(k, cfg.layer_config) for k in layer_keys]
    block = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *per_layer)

    return {
        "embeddings": {
            "word": jax.random.normal(ks[1], (cfg.vocab_size, d)) * s,
            "position": jax.random.normal(ks[2], (cfg.max_seq_len, d)) * s,
            "token_type": jax.random.normal(ks[3], (cfg.type_vocab_size, d)) * s,
            "ln": {"scale": jnp.ones((d,)), "bias": jnp.zeros((d,))},
        },
        "block": block,
        "pooler": {"kernel": jax.random.normal(ks[4], (d, d)) * s,
                   "bias": jnp.zeros((d,))},
        "mlm": {  # transform + tied-embedding decoder bias
            "kernel": jax.random.normal(ks[5], (d, d)) * s,
            "bias": jnp.zeros((d,)),
            "ln": {"scale": jnp.ones((d,)), "bias": jnp.zeros((d,))},
            "decoder_bias": jnp.zeros((cfg.vocab_size,)),
        },
        "nsp": {"kernel": jax.random.normal(ks[6], (d, 2)) * s,
                "bias": jnp.zeros((2,))},
    }


def encode(params: Dict, tokens: jnp.ndarray, cfg: BertConfig,
           token_type_ids: Optional[jnp.ndarray] = None,
           attention_mask: Optional[jnp.ndarray] = None,
           rng: Optional[jax.Array] = None,
           deterministic: bool = True) -> jnp.ndarray:
    """tokens [B, S] -> hidden states [B, S, D] (compute dtype)."""
    B, S = tokens.shape
    dtype = cfg.dtype
    emb = params["embeddings"]
    x = emb["word"].astype(dtype)[tokens] + \
        emb["position"].astype(dtype)[:S][None]
    if token_type_ids is None:
        token_type_ids = jnp.zeros_like(tokens)
    x = x + emb["token_type"].astype(dtype)[token_type_ids]
    x = _layernorm(x, emb["ln"]["scale"].astype(dtype),
                   emb["ln"]["bias"].astype(dtype), cfg.layer_norm_eps)

    lcfg = cfg.layer_config
    assert deterministic or rng is not None, \
        "training mode (deterministic=False) needs an rng for dropout"
    rng = rng if rng is not None else jax.random.PRNGKey(0)

    def body(carry, layer):
        h, r = carry
        r, lr = jax.random.split(r)
        y = layer_forward(layer, h, lcfg, attn_mask=attention_mask,
                          rng=None if deterministic else lr,
                          deterministic=deterministic)
        return (y, r), None

    if cfg.remat:
        # per-layer activation checkpointing: without it the scan keeps
        # every layer's attention/MLP intermediates for the backward —
        # BERT-large at pretraining batch sizes does not fit HBM
        # (ref capability: activation_checkpointing/checkpointing.py).
        # Policy shared with the GPT family (encoder_layer tags
        # qkv/attn/mlp_pre and the flash kernel its packed residuals).
        # The flash flag must mirror _attention_core's gate so the
        # selective policy never saves the attention output twice
        # (packed flash_out + 'attn').
        from deepspeed_tpu.models.gpt import remat_policy
        from deepspeed_tpu.ops.transformer.encoder_layer import flash_block
        # masked batches take the flash path too (kv_mask support); the
        # gate must still mirror _attention_core's dropout condition
        flash_used = (flash_block(S, cfg.d_model // cfg.n_heads) is not None
                      and (deterministic or cfg.dropout == 0.0))
        body = jax.checkpoint(
            body, policy=remat_policy(cfg.remat_policy, flash=flash_used))

    (x, _), _ = jax.lax.scan(body, (x, rng), params["block"])
    return x


def _mlm_hidden(params: Dict, x: jnp.ndarray, cfg: BertConfig):
    """MLM head transform: encoder states -> pre-decode hidden [B,S,d]."""
    dtype = x.dtype
    h = x @ params["mlm"]["kernel"].astype(dtype) + \
        params["mlm"]["bias"].astype(dtype)
    h = jax.nn.gelu(h, approximate=True)
    return _layernorm(h, params["mlm"]["ln"]["scale"].astype(dtype),
                      params["mlm"]["ln"]["bias"].astype(dtype),
                      cfg.layer_norm_eps)


def _nsp_logits(params: Dict, x: jnp.ndarray):
    dtype = x.dtype
    pooled = jnp.tanh(x[:, 0] @ params["pooler"]["kernel"].astype(dtype) +
                      params["pooler"]["bias"].astype(dtype))
    return pooled @ params["nsp"]["kernel"].astype(dtype) + \
        params["nsp"]["bias"].astype(dtype)


def forward(params: Dict, tokens: jnp.ndarray, cfg: BertConfig,
            token_type_ids=None, attention_mask=None,
            rng: Optional[jax.Array] = None,
            deterministic: bool = True):
    """Returns (mlm_logits [B,S,V], nsp_logits [B,2])."""
    x = encode(params, tokens, cfg, token_type_ids, attention_mask,
               rng, deterministic)
    dtype = x.dtype
    # MLM head: transform -> LN -> tied-embedding decode
    h = _mlm_hidden(params, x, cfg)
    mlm_logits = h @ params["embeddings"]["word"].astype(dtype).T + \
        params["mlm"]["decoder_bias"].astype(dtype)
    return mlm_logits, _nsp_logits(params, x)


def loss_fn(params: Dict, batch: Dict, rng: jax.Array, cfg: BertConfig,
            deterministic: bool = False) -> jnp.ndarray:
    """MLM (+optional NSP) loss. batch:
    tokens [B,S]; mlm_labels [B,S] with -1 = not masked;
    optional token_type_ids, attention_mask, nsp_labels [B]."""
    labels = batch["mlm_labels"]
    mask = (labels >= 0).astype(jnp.float32)
    if cfg.loss_chunk:
        from deepspeed_tpu.ops.cross_entropy import chunked_softmax_xent
        x = encode(params, batch["tokens"], cfg,
                   batch.get("token_type_ids"), batch.get("attention_mask"),
                   rng, deterministic)
        h = _mlm_hidden(params, x, cfg)
        loss = chunked_softmax_xent(
            h, params["embeddings"]["word"].astype(h.dtype),
            jnp.maximum(labels, 0),
            bias=params["mlm"]["decoder_bias"].astype(h.dtype),
            chunk=cfg.loss_chunk, loss_mask=mask)
        nsp_logits = _nsp_logits(params, x)
    else:
        mlm_logits, nsp_logits = forward(
            params, batch["tokens"], cfg,
            token_type_ids=batch.get("token_type_ids"),
            attention_mask=batch.get("attention_mask"),
            rng=rng, deterministic=deterministic)
        logp = jax.nn.log_softmax(mlm_logits.astype(jnp.float32), axis=-1)
        picked = jnp.take_along_axis(
            logp, jnp.maximum(labels, 0)[..., None], axis=-1).squeeze(-1)
        loss = -(picked * mask).sum() / jnp.maximum(mask.sum(), 1.0)
    if "nsp_labels" in batch:
        nsp_logp = jax.nn.log_softmax(nsp_logits.astype(jnp.float32), -1)
        loss = loss - jnp.mean(jnp.take_along_axis(
            nsp_logp, batch["nsp_labels"][:, None], axis=-1))
    return loss


def make_loss_fn(cfg: BertConfig):
    """Engine-contract loss: (params, batch, rng) -> loss."""
    def _loss(params, batch, rng):
        return loss_fn(params, batch, rng, cfg)
    return _loss


# ---------------------------------------------------------------------------
# SQuAD fine-tuning head (the BingBertSquad workload,
# ref: tests/model/BingBertSquad + DeepSpeedExamples' nvidia/modeling
# BertForQuestionAnswering — a start/end span classifier on the encoder)
# ---------------------------------------------------------------------------

def init_squad_head(rng: jax.Array, cfg: BertConfig) -> Dict:
    """Span-prediction head params: add under params["qa"]."""
    return {"kernel": jax.random.normal(rng, (cfg.d_model, 2)) * 0.02,
            "bias": jnp.zeros((2,))}


def squad_logits(params: Dict, tokens: jnp.ndarray, cfg: BertConfig,
                 token_type_ids=None, attention_mask=None,
                 rng: Optional[jax.Array] = None,
                 deterministic: bool = True):
    """-> (start_logits [B, S], end_logits [B, S]) fp32."""
    x = encode(params, tokens, cfg, token_type_ids, attention_mask,
               rng, deterministic)
    qa = params["qa"]
    logits = x @ qa["kernel"].astype(x.dtype) + qa["bias"].astype(x.dtype)
    s, e = jnp.split(logits.astype(jnp.float32), 2, axis=-1)
    return s[..., 0], e[..., 0]


def squad_loss_fn(params: Dict, batch: Dict, rng: jax.Array,
                  cfg: BertConfig, deterministic: bool = False):
    """Mean of start/end-position cross-entropies. batch: tokens [B,S],
    start_positions [B], end_positions [B], optional token_type_ids /
    attention_mask."""
    s_logits, e_logits = squad_logits(
        params, batch["tokens"], cfg, batch.get("token_type_ids"),
        batch.get("attention_mask"), rng, deterministic)
    S = s_logits.shape[1]

    def xent(logits, pos):
        # out-of-range positions (e.g. unanswerable examples marked with
        # seq_len, the reference's ignored_index convention, or -1) are
        # excluded from the loss
        valid = ((pos >= 0) & (pos < S)).astype(jnp.float32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        picked = jnp.take_along_axis(
            logp, jnp.clip(pos, 0, S - 1)[:, None], axis=-1)[:, 0]
        return -(picked * valid).sum() / jnp.maximum(valid.sum(), 1.0)

    return 0.5 * (xent(s_logits, batch["start_positions"]) +
                  xent(e_logits, batch["end_positions"]))


def make_squad_loss_fn(cfg: BertConfig):
    def _loss(params, batch, rng):
        return squad_loss_fn(params, batch, rng, cfg)
    return _loss


def bert_partition_rules(vocab_parallel: bool = False):
    """TP rules: column-parallel qkv/mlp_in, row-parallel
    attn_out/mlp_out — the Megatron recipe the reference delegates to
    the client mpu (SURVEY.md §2.2 TP row). ``vocab_parallel`` also
    row-shards the word embedding (requires vocab_size % tp == 0)."""
    from deepspeed_tpu.parallel.sharding import PartitionRule
    from jax.sharding import PartitionSpec as P
    rules = [
        PartitionRule(r"block/qkv/kernel", P(None, None, "model")),
        PartitionRule(r"block/qkv/bias", P(None, "model")),
        PartitionRule(r"block/attn_out/kernel", P(None, "model", None)),
        PartitionRule(r"block/mlp_in/kernel", P(None, None, "model")),
        PartitionRule(r"block/mlp_in/bias", P(None, "model")),
        PartitionRule(r"block/mlp_out/kernel", P(None, "model", None)),
    ]
    if vocab_parallel:
        rules.append(PartitionRule(r"embeddings/word", P("model", None)))
    return rules


def num_params(cfg: BertConfig) -> int:
    d, L, V = cfg.d_model, cfg.n_layers, cfg.vocab_size
    per_layer = 12 * d * d + 13 * d
    emb = (V + cfg.max_seq_len + cfg.type_vocab_size) * d + 2 * d
    heads = 2 * d * d + 6 * d + V + 2  # pooler + mlm transform/ln + nsp
    return L * per_layer + emb + heads
