"""Chunked softmax cross-entropy — the vocabulary-projection + loss fused op.

Capability analog of the reference's fused logit/loss CUDA path (ref:
csrc/transformer/softmax_kernels.cu — fused scaled-masked softmax; the
reference never ships a vocab-parallel loss because Megatron owns it there,
ref tests/model/Megatron_GPT2 harness delegates to Megatron's
vocab_parallel_cross_entropy). TPU-first design:

At GPT-2 scale the logits tensor dominates loss-path memory: B=16, S=1024,
V=50k is a 3.3GB fp32 array, and the standard ``log_softmax`` path
materializes it (plus the log-prob tensor, plus a residual for the backward)
— several × 3.3GB of HBM for bytes that are consumed immediately. This op
scans over token chunks and computes, per chunk, only the row logsumexp and
the gold-token logit, so peak extra memory is O(chunk × V) instead of
O(N × V). The backward recomputes each chunk's logits (one extra logit
matmul — ~2% of a training step's FLOPs) and accumulates the vocab-weight
gradient in an fp32 scan carry.

The matmuls contract in the input dtype (bf16 on TPU) with fp32
accumulation on the MXU; softmax statistics and the dW accumulator are
fp32. dlogits is cast to the weight dtype for the two backward matmuls —
the same precision trade every other layer's gradients make.
"""

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import AxisType, PartitionSpec as P, get_abstract_mesh

from deepspeed_tpu.parallel.mesh import BATCH_AXES

__all__ = ["softmax_xent_ll", "chunked_softmax_xent", "loss_layout"]


def _token_axes(rows: Optional[int] = None) -> Tuple[str, ...]:
    """The batch axes of the mesh in context that the chunk scan is mapped
    over: those no enclosing shard_map holds yet and that are larger than
    1, provided ``rows`` (the tokens' leading dimension) splits over them.
    Empty means the scan runs unmapped, as on one device."""
    m = get_abstract_mesh()
    if m is None or m.empty:
        return ()
    axes = tuple(n for n, ty in zip(m.axis_names, m.axis_types)
                 if n in BATCH_AXES and ty != AxisType.Manual
                 and m.shape[n] > 1)
    n = math.prod(m.shape[a] for a in axes)
    return axes if rows is None or rows % n == 0 else ()


def loss_layout(chunk: int) -> str:
    """What the chunked loss does under the mesh in context, for the
    engine's "engine ready" line."""
    axes = _token_axes()
    if not axes:
        return f"chunked({chunk})"
    m = get_abstract_mesh()
    over = " x ".join(f"{a}={m.shape[a]}" for a in axes)
    return f"chunked({chunk})/shard over {over}, projection gathered"


def _per_shard(fn, axes, in_specs, out_specs):
    """``fn`` on each shard of tokens, the way ``gpt._flash_per_device``
    maps the flash kernel; with no axes to map, ``fn`` itself. Axes the
    map does not take ('model', 'sequence') stay with XLA."""
    if not axes:
        return fn
    return jax.shard_map(fn, in_specs=in_specs, out_specs=out_specs,
                         axis_names=set(axes), check_vma=False)


def _gathered(w, b, axes):
    """The projection whole on every shard of tokens: ONE all-gather over
    the mapped axes (ZeRO-3 cuts ``w`` over 'fsdp'), outside every loop
    and named for the trace."""
    if not axes:
        return w, b
    with jax.named_scope("loss_gather"):
        return _per_shard(lambda *wb: wb, axes, P(), P())(w, b)


def _chunk_logits(xc, w, b):
    """[C, H] @ [V, H]^T (+ b) -> fp32 [C, V] with fp32 MXU accumulation."""
    logits = jax.lax.dot_general(
        xc, w, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    if b is not None:
        logits = logits + b.astype(jnp.float32)
    return logits


def _chunk_of(N: int, chunk: int) -> int:
    """Tokens per scan step for a shard of ``N`` tokens."""
    c = int(min(chunk, N))
    # prefer an exact divisor of N near the requested chunk (same adaptive-
    # divisor approach as the flash block fallback) — a padded final chunk
    # wastes a full chunk of logit matmul when N is just over a multiple
    div = next((d for d in range(c, 0, -1) if N % d == 0), 1)
    return div if div >= c // 2 else c


def _pad_rows(a, rows: int):
    """``a`` zero-padded along dim 0 up to ``rows`` (padded rows get zero
    cotangent — they never contribute grads)."""
    pad = rows - a.shape[0]
    if not pad:
        return a
    return jnp.concatenate([a, jnp.zeros((pad,) + a.shape[1:], a.dtype)])


def _fwd_local(chunk, x, w, b, t):
    """One shard's scan. Returns ``ll`` shaped like ``t`` and the
    backward's residuals: the shard's tokens, targets and row logsumexp,
    flat and padded up to a chunk multiple."""
    x2 = x.reshape(-1, x.shape[-1])
    t2 = t.reshape(-1)
    N = x2.shape[0]
    c = _chunk_of(N, chunk)
    x2, t2 = (_pad_rows(a, N + (-N) % c) for a in (x2, t2))
    xs = x2.reshape(-1, c, x2.shape[-1])
    ts = t2.reshape(-1, c)

    def body(_, xt):
        xc, tc = xt
        logits = _chunk_logits(xc, w, b)
        m = jnp.max(logits, axis=-1)
        lse = m + jnp.log(jnp.sum(jnp.exp(logits - m[:, None]), axis=-1))
        gold = jnp.take_along_axis(logits, tc[:, None], axis=-1)[:, 0]
        return None, (gold - lse, lse)

    _, (ll, lse) = jax.lax.scan(body, None, (xs, ts))
    ll, lse = ll.reshape(-1), lse.reshape(-1)
    return ll[:N].reshape(t.shape), (x2, t2, lse)


def _bwd_local(chunk, axes, x2, w, b, t2, lse, g):
    """One shard's recomputing backward. ``dw`` and ``db`` stay local fp32
    carries through the scan and are summed across the shards ONCE."""
    N, H = g.size, x2.shape[-1]
    c = _chunk_of(N, chunk)
    g2 = _pad_rows(g.reshape(-1), x2.shape[0])
    xs = x2.reshape(-1, c, H)
    ts = t2.reshape(-1, c)
    gs = g2.reshape(-1, c).astype(jnp.float32)
    ls = lse.reshape(-1, c)

    def body(carry, xtgl):
        dw, db = carry
        xc, tc, gc, lc = xtgl
        logits = _chunk_logits(xc, w, b)
        p = jnp.exp(logits - lc[:, None])                  # softmax, fp32
        cols = jax.lax.broadcasted_iota(jnp.int32, p.shape, 1)
        onehot = (cols == tc[:, None]).astype(jnp.float32)
        dlog = gc[:, None] * (onehot - p)                  # d loss / d logits
        dlb = dlog.astype(w.dtype)
        dxc = jax.lax.dot_general(                         # [C,V] @ [V,H]
            dlb, w, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(x2.dtype)
        dw = dw + jax.lax.dot_general(                     # [V,C] @ [C,H]
            dlb, xc, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        if db is not None:
            db = db + jnp.sum(dlog, axis=0)
        return (dw, db), dxc

    dw0 = jnp.zeros(w.shape, jnp.float32)
    db0 = None if b is None else jnp.zeros(b.shape, jnp.float32)
    (dw, db), dx = jax.lax.scan(body, (dw0, db0), (xs, ts, gs, ls))
    if axes:
        dw, db = jax.lax.psum((dw, db), axes)
    dx, dw = dx.reshape(-1, H)[:N], dw.astype(w.dtype)
    return (dx.reshape(g.shape + (H,)), dw,
            None if b is None else db.astype(b.dtype))


def _fwd(x, w, b, t, chunk, axes):
    tok = P(axes)
    return _per_shard(functools.partial(_fwd_local, chunk), axes,
                      (tok, P(), P(), tok), (tok, tok))(
                          x, *_gathered(w, b, axes), t)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _xent_ll(x, w, b, t, chunk, axes):
    return _fwd(x, w, b, t, chunk, axes)[0]


def _xent_ll_fwd(x, w, b, t, chunk, axes):
    ll, (x2, t2, lse) = _fwd(x, w, b, t, chunk, axes)
    return ll, (x2, w, b, t2, lse)


def _xent_ll_bwd(chunk, axes, res, g):
    x2, w, b, t2, lse = res
    tok = P(axes)
    dx, dw, db = _per_shard(
        functools.partial(_bwd_local, chunk, axes), axes,
        (tok, P(), P(), tok, tok, tok), (tok, P(), P()))(
            x2, *_gathered(w, b, axes), t2, lse, g)
    return dx, dw, db, None


_xent_ll.defvjp(_xent_ll_fwd, _xent_ll_bwd)


def softmax_xent_ll(x: jnp.ndarray, w: jnp.ndarray, targets: jnp.ndarray,
                    bias: Optional[jnp.ndarray] = None,
                    chunk: int = 2048) -> jnp.ndarray:
    """Per-token log-likelihood without materializing the logits matrix.

    ``ll[i] = logits[i, targets[i]] - logsumexp(logits[i])`` where
    ``logits = x @ w.T (+ bias)``.

    Where the mesh in context splits the batch over axes of more than one
    device (``_token_axes``), the scan runs PER SHARD OF TOKENS on a
    projection gathered once: ZeRO-3 cuts ``w`` along the dimension the
    projection contracts, and a scan over the global token axis would
    all-reduce every chunk's logits. ``chunk`` is then per shard, and
    ``dw`` is summed across the shards once, in fp32. Without such axes
    the call is the single-device program.

    Args:
      x: ``[..., H]`` activations (compute dtype; leading dims flattened).
      w: ``[V, H]`` vocabulary projection (``wte`` layout — for an
        ``[H, V]`` lm-head kernel pass ``kernel.T``; XLA folds the
        transpose into the matmul).
      targets: ``[...]`` int32 gold token ids, same leading shape as x.
      bias: optional ``[V]`` logit bias (e.g. GPT-J lm_head).
      chunk: tokens per scan step. Peak extra memory is ~``chunk × V``
        fp32; 2048×50k ≈ 412MB. N is zero-padded up to a chunk multiple.

    Returns fp32 ``ll`` with the leading shape of ``targets``.
    """
    return _xent_ll(x, w, bias, targets.astype(jnp.int32), int(chunk),
                    _token_axes(x.shape[0]))


def chunked_softmax_xent(x, w, targets, bias=None, chunk: int = 2048,
                         loss_mask=None) -> jnp.ndarray:
    """Masked-mean negative log-likelihood over ``targets`` (scalar fp32).
    Under a mesh ``ll`` is one global array, so numerator and mask count
    are global sums: never a mean of per-shard means."""
    ll = softmax_xent_ll(x, w, targets, bias=bias, chunk=chunk)
    if loss_mask is not None:
        return -(ll * loss_mask).sum() / jnp.maximum(loss_mask.sum(), 1.0)
    return -ll.mean()
