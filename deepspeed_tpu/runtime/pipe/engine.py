"""Pipelined execution over the 'pipe' mesh axis.

Capability analog of the reference's PipelineEngine
(ref: deepspeed/runtime/pipe/engine.py:46 — instruction interpreter
_exec_schedule :1364, p2p sends :951/:1046, tied-grad reduction :240).
TPU-native design: instead of interpreting an instruction stream with
torch.distributed send/recv, the WHOLE pipeline (all microbatches, all
stages) is ONE jitted shard_map program:

- stage weights = layer-stacked params sharded over the 'pipe' axis;
- activation transfer = `lax.ppermute` to the next stage (rides ICI
  neighbor links, same wire pattern as the reference's p2p :48);
- the microbatch loop is a `lax.scan` over M + P - 1 "clock ticks";
- the backward pipeline comes from autodiff: ppermute's transpose is the
  reverse ppermute, so grad of the scan IS the reverse-order pipeline
  (cooldown bubble included);
- tied weights (e.g. embedding reused by the LM head) are passed
  replicated-over-pipe; shard_map's transpose psums their grads across
  stages — the reference's ReduceTiedGrads dissolves into autodiff.

Other mesh axes (data/fsdp/model/sequence) stay "auto": XLA keeps managing
ZeRO/TP sharding inside each stage.
"""

from functools import partial
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

PyTree = Any


def stage_index(axis: str = "pipe"):
    return jax.lax.axis_index(axis)


def pipeline_apply(stage_fn: Callable,
                   stage_params: PyTree,
                   x_micro: jnp.ndarray,
                   num_stages: int,
                   *,
                   axis: str = "pipe") -> jnp.ndarray:
    """Run the pipelined forward inside a shard_map context.

    stage_fn(stage_params, x) -> y applies this stage's layer slice.
    x_micro: [M, mb, ...] microbatched stage-0 input (replicated over pipe).
    Returns [M, mb, ...] outputs, valid on the LAST stage (other stages
    hold garbage — mask before use).

    Tick t: stage s computes microbatch (t - s); M + P - 1 ticks total.
    """
    M = x_micro.shape[0]
    num_ticks = M + num_stages - 1
    s = jax.lax.axis_index(axis)
    is_first = s == 0

    perm = [(i, (i + 1) % num_stages) for i in range(num_stages)]

    def tick(state, t):
        # stage 0 consumes microbatch t (clipped; out-of-range ticks are
        # bubble and produce masked garbage), others consume what arrived
        inp = jnp.where(is_first,
                        x_micro[jnp.clip(t, 0, M - 1)],
                        state)
        out = stage_fn(stage_params, inp)
        nxt = jax.lax.ppermute(out, axis, perm)
        return nxt, out

    state0 = jnp.zeros_like(x_micro[0])
    _, outs = jax.lax.scan(tick, state0, jnp.arange(num_ticks))
    # last stage's valid outputs live at ticks [P-1, P-1+M)
    return jax.lax.dynamic_slice_in_dim(outs, num_stages - 1, M, axis=0)


def pipeline_loss(stage_fn: Callable,
                  head_loss_fn: Callable,
                  stage_params: PyTree,
                  other_params: PyTree,
                  x_micro: jnp.ndarray,
                  target_micro: PyTree,
                  num_stages: int,
                  *,
                  axis: str = "pipe") -> jnp.ndarray:
    """Pipelined forward + last-stage loss, inside shard_map.

    head_loss_fn(other_params, y, target) -> scalar mean loss for one
    microbatch (runs on the last stage only; other stages' contribution is
    masked to zero and the scalar is psum'd — the analog of the reference's
    _aggregate_total_loss broadcast, ref pipe/engine.py:548).
    """
    y_micro = pipeline_apply(stage_fn, stage_params, x_micro, num_stages,
                             axis=axis)
    s = jax.lax.axis_index(axis)
    is_last = (s == num_stages - 1).astype(jnp.float32)

    def one(y, t):
        return head_loss_fn(other_params, y, t)

    losses = jax.vmap(one)(y_micro, target_micro)          # [M]
    local = jnp.mean(losses) * is_last
    return jax.lax.psum(local, axis)


# ---------------------------------------------------------------------------
# memory-bounded 1F1B execution
# ---------------------------------------------------------------------------

def _zeros_like_f32(tree):
    return jax.tree_util.tree_map(
        lambda x: jnp.zeros(x.shape, jnp.float32), tree)


def _ring_perms(P_):
    """(forward, backward) neighbor permutations on the pipe ring."""
    return ([(i, (i + 1) % P_) for i in range(P_)],
            [(i, (i - 1) % P_) for i in range(P_)])


def _head_closure(head_loss_fn, target_micro, M):
    """head_for(m): loss closure of the head for microbatch slot m
    (clipped — invalid slots are masked by the caller)."""
    def head_for(m):
        tgt = jax.tree_util.tree_map(
            lambda z: z[jnp.clip(m, 0, M - 1)], target_micro)
        return lambda op, y: head_loss_fn(op, y, tgt)
    return head_for

def _one_f_one_b_program(stage_fn: Callable,
                         head_loss_fn: Callable,
                         num_stages: int,
                         axis: str,
                         stage_params: PyTree,
                         other_params: PyTree,
                         x_micro: jnp.ndarray,
                         target_micro: PyTree):
    """1F1B pipelined forward+backward as ONE scan, inside shard_map.

    Memory-bounded analog of the reference's TrainSchedule
    (ref: deepspeed/runtime/pipe/schedule.py:189): each tick every stage
    runs one forward (microbatch f = t - s) and one backward
    (microbatch b = t - (2P - 2 - s)), so a stage holds at most
    2*(P-1-s) in-flight microbatch *inputs* — O(stages), not
    O(microbatches). Backward recomputes the stage forward from the saved
    input (activation checkpointing at stage granularity, like the
    reference's PipelineModule activation_checkpoint_interval).

    Returns (mean loss, dstage_params, dother_params, dx_micro) — gradients
    computed manually (the caller wraps this in a custom_vjp; autodiff never
    sees the scan, so no O(ticks) residuals are retained).
    """
    M = x_micro.shape[0]
    P_ = num_stages
    s = jax.lax.axis_index(axis)
    is_first = s == 0
    is_last = s == P_ - 1
    num_ticks = M + 2 * P_ - 2
    K = max(2 * P_ - 1, 1)              # input ring-buffer slots

    fwd_perm, bwd_perm = _ring_perms(P_)
    f32 = jnp.float32
    zeros_like_tree = _zeros_like_f32
    head_for = _head_closure(head_loss_fn, target_micro, M)

    def tick(carry, t):
        (fwd_in, bwd_in, buf, dstage, dother, dx_acc, loss_acc) = carry
        f = t - s                        # forward microbatch id
        b = t - (2 * P_ - 2 - s)         # backward microbatch id
        f_valid = (f >= 0) & (f < M)
        b_valid = (b >= 0) & (b < M)

        # ---- forward ----
        inp = jnp.where(is_first, x_micro[jnp.clip(f, 0, M - 1)], fwd_in)
        buf = jnp.where(f_valid,
                        jax.lax.dynamic_update_index_in_dim(
                            buf, inp, jnp.clip(f, 0, M - 1) % K, 0),
                        buf)
        out = stage_fn(stage_params, inp)

        # ---- last-stage head: loss + dy for the just-finished microbatch
        loss_m, head_vjp = jax.vjp(head_for(f), other_params, out)
        dother_m, dy_head = head_vjp(jnp.ones((), loss_m.dtype))
        mask_last = (is_last & f_valid).astype(f32)
        loss_acc = loss_acc + loss_m.astype(f32) * mask_last
        dother = jax.tree_util.tree_map(
            lambda a, g: a + g.astype(f32) * mask_last, dother, dother_m)

        # ---- backward (recompute from the saved stage input) ----
        # at the last stage b == f, and the input is the one stored this tick
        x_saved = jnp.where(is_last, inp, buf[jnp.clip(b, 0, M - 1) % K])
        cot_in = jnp.where(is_last, dy_head.astype(bwd_in.dtype), bwd_in)
        _, stage_vjp = jax.vjp(stage_fn, stage_params, x_saved)
        dstage_m, dx_m = stage_vjp(cot_in)
        mask_b = b_valid.astype(f32)
        dstage = jax.tree_util.tree_map(
            lambda a, g: a + g.astype(f32) * mask_b, dstage, dstage_m)
        # grads w.r.t. the pipeline input (stage 0's dx -> embedding)
        mask_first_b = (is_first & b_valid).astype(dx_m.dtype)
        dx_acc = jax.lax.dynamic_update_index_in_dim(
            dx_acc,
            dx_acc[jnp.clip(b, 0, M - 1)] + dx_m * mask_first_b,
            jnp.clip(b, 0, M - 1), 0)

        # ---- neighbor exchange ----
        fwd_out = jax.lax.ppermute(out, axis, fwd_perm)
        bwd_out = jax.lax.ppermute(dx_m, axis, bwd_perm)
        return (fwd_out, bwd_out, buf, dstage, dother, dx_acc, loss_acc), None

    x0 = jnp.zeros_like(x_micro[0])
    carry0 = (x0, jnp.zeros_like(x0),
              jnp.zeros((K,) + x0.shape, x0.dtype),
              zeros_like_tree(stage_params),
              zeros_like_tree(other_params),
              jnp.zeros_like(x_micro),
              jnp.zeros((), f32))
    (_, _, _, dstage, dother, dx_micro, loss_sum), _ = jax.lax.scan(
        tick, carry0, jnp.arange(num_ticks))

    # per-microbatch mean -> batch mean; scale grads accordingly
    inv_m = 1.0 / M
    loss = jax.lax.psum(loss_sum * inv_m, axis)
    dother = jax.tree_util.tree_map(
        lambda g: jax.lax.psum(g * inv_m, axis), dother)
    dx_micro = jax.lax.psum(dx_micro * inv_m, axis)
    dstage = jax.tree_util.tree_map(lambda g: g * inv_m, dstage)
    return loss, dstage, dother, dx_micro


def _make_stashed_grad_loss(run):
    """custom_vjp wrapper shared by the 1F1B and interleaved makers:
    the forward runs the manual fwd+bwd program and stashes the grads
    as residuals; bwd scales them by the incoming cotangent."""

    @jax.custom_vjp
    def loss_fn(stage_params, other_params, x_micro, target_micro):
        loss, _, _, _ = run(stage_params, other_params, x_micro,
                            target_micro)
        return loss

    def fwd(stage_params, other_params, x_micro, target_micro):
        loss, dstage, dother, dx = run(stage_params, other_params,
                                       x_micro, target_micro)
        return loss, (dstage, dother, dx, target_micro)

    def bwd(res, g):
        dstage, dother, dx, target_micro = res
        scale = lambda t: jax.tree_util.tree_map(lambda v_: v_ * g, t)
        dtarget = jax.tree_util.tree_map(
            lambda z: (jnp.zeros(z.shape, jax.dtypes.float0)
                       if not jnp.issubdtype(z.dtype, jnp.floating)
                       else jnp.zeros_like(z)),
            target_micro)
        return scale(dstage), scale(dother), dx * g, dtarget

    loss_fn.defvjp(fwd, bwd)
    return loss_fn


def make_1f1b_loss_fn(stage_fn: Callable,
                      head_loss_fn: Callable,
                      num_stages: int,
                      mesh: Mesh,
                      stage_params_specs: PyTree,
                      *,
                      axis: str = "pipe") -> Callable:
    """(stage_params, other_params, x_micro, target_micro) -> scalar loss,
    differentiable, executing the memory-bounded 1F1B schedule. Gradients
    are produced by the same single scan (custom_vjp; the forward pass
    runs fwd+bwd eagerly and stashes the grads as residuals — train-only,
    eval paths should use the plain pipeline)."""

    def run(stage_params, other_params, x_micro, target_micro):
        prog = partial(_one_f_one_b_program, stage_fn, head_loss_fn,
                       num_stages, axis)
        return jax.shard_map(
            prog, mesh=mesh,
            in_specs=(stage_params_specs, P(), P(), P()),
            out_specs=(P(), stage_params_specs, P(), P()),
            axis_names={axis}, check_vma=False)(
                stage_params, other_params, x_micro, target_micro)

    return _make_stashed_grad_loss(run)


def make_pipelined_loss_fn(embed_fn: Callable,
                           stage_fn: Callable,
                           head_loss_fn: Callable,
                           split_params: Callable,
                           num_stages: int,
                           num_micro: int,
                           mesh: Mesh,
                           stage_params_specs: PyTree,
                           *,
                           remat_stage: bool = True,
                           schedule: str = "1f1b",
                           virtual_chunks: int = 1,
                           axis: str = "pipe") -> Callable:
    """Build an engine-compatible loss fn (params, batch, rng) -> loss.

    - embed_fn(other_params, batch) -> (x [B, ...], targets pytree [B, ...])
      runs replicated on every stage (cheap: embedding lookup).
    - split_params(params) -> (stacked_stage_params, other_params); the
      stacked leaves have leading dim L == layers and are sharded P('pipe')
      on that dim by the caller's partition rules.
    - stage_params_specs: PartitionSpec pytree for the stacked params
      (leading 'pipe' axis); other axes stay auto.
    - schedule: '1f1b' (DEFAULT — memory-bounded, ref TrainSchedule
      pipe/schedule.py:189; activation memory O(stages), which is what
      matters at depth) or 'gpipe' (fill-drain via scan+autodiff;
      activation memory O(microbatches)).

    Under '1f1b' the returned loss_fn carries an ``eval_fn`` attribute
    running the GPipe forward — the 1F1B custom_vjp computes gradients
    eagerly inside its forward, which eval must not pay for; the engine
    picks ``eval_fn`` up automatically.

    schedule='interleaved' runs chunk-granular 1F1B over
    ``virtual_chunks`` virtual stages per device (megatron-style
    interleaving — beyond the reference's schedule set), cutting the
    pipeline bubble by up to ~virtual_chunks at small M/P. The caller
    must feed stage params in virtual-stage stacking order
    (interleave_layer_perm); num_micro must be a multiple of the stage
    count.
    """
    if schedule not in ("gpipe", "1f1b", "interleaved"):
        raise ValueError(f"unknown pipeline schedule {schedule!r}")
    if schedule == "interleaved" and virtual_chunks < 2:
        raise ValueError("schedule='interleaved' needs virtual_chunks >= 2"
                         " (with 1 chunk it IS plain 1f1b)")
    gpipe_stage_fn = stage_fn
    if remat_stage:
        # 1f1b checkpoints at stage granularity by construction; the
        # gpipe path (training or the eval companion) gets explicit remat
        gpipe_stage_fn = jax.checkpoint(
            stage_fn, policy=jax.checkpoint_policies.nothing_saveable)

    if schedule == "1f1b":
        loss_1f1b = make_1f1b_loss_fn(stage_fn, head_loss_fn, num_stages,
                                      mesh, stage_params_specs, axis=axis)
    elif schedule == "interleaved":
        loss_1f1b = make_interleaved_loss_fn(
            stage_fn, head_loss_fn, num_stages, virtual_chunks,
            num_micro, mesh, stage_params_specs, axis=axis)

    def _micro_split(params, batch):
        stage_params, other_params = split_params(params)
        x, targets = embed_fn(other_params, batch)
        B = x.shape[0]
        assert B % num_micro == 0, (B, num_micro)
        mb = B // num_micro
        x_micro = x.reshape((num_micro, mb) + x.shape[1:])
        target_micro = jax.tree_util.tree_map(
            lambda t: t.reshape((num_micro, mb) + t.shape[1:]), targets)
        return stage_params, other_params, x_micro, target_micro

    def _gpipe(params, batch):
        stage_params, other_params, x_micro, target_micro = \
            _micro_split(params, batch)
        inner = partial(pipeline_loss, gpipe_stage_fn, head_loss_fn,
                        num_stages=num_stages, axis=axis)
        sharded = jax.shard_map(
            inner,
            mesh=mesh,
            in_specs=(stage_params_specs,
                      P(),      # other params: replicated over pipe (auto elsewhere)
                      P(),      # x_micro
                      P()),     # targets
            out_specs=P(),
            axis_names={axis},
            check_vma=False)
        return sharded(stage_params, other_params, x_micro, target_micro)

    def loss_fn(params, batch, rng):
        del rng
        if schedule in ("1f1b", "interleaved"):
            stage_params, other_params, x_micro, target_micro = \
                _micro_split(params, batch)
            if schedule == "interleaved":
                # virtual-stage stacking order, applied INSIDE the traced
                # loss: a differentiable gather, so grads scatter back to
                # the natural layout and optimizer state/checkpoints/the
                # gpipe eval companion never see the permuted order
                leaves = jax.tree_util.tree_leaves(stage_params)
                L = leaves[0].shape[0]
                if L % (num_stages * virtual_chunks):
                    # a non-dividing L would silently TRUNCATE the model
                    # (the gather below keeps only the permuted rows)
                    raise ValueError(
                        f"interleaved schedule needs stacked layers "
                        f"({L}) divisible by stages*chunks "
                        f"({num_stages}*{virtual_chunks})")
                perm = jnp.asarray(interleave_layer_perm(
                    L, num_stages, virtual_chunks))
                stage_params = jax.tree_util.tree_map(
                    lambda p: p[perm], stage_params)
            return loss_1f1b(stage_params, other_params, x_micro,
                             target_micro)
        return _gpipe(params, batch)

    if schedule in ("1f1b", "interleaved"):
        def eval_fn(params, batch, rng):
            del rng
            return _gpipe(params, batch)
        loss_fn.eval_fn = eval_fn

    return loss_fn


# ---------------------------------------------------------------------------
# interleaved 1F1B (virtual pipeline stages)
# ---------------------------------------------------------------------------

def interleave_layer_perm(L: int, P_: int, v: int):
    """Row permutation putting a [L]-stacked layer pytree into virtual-
    stage order: device d's contiguous 'pipe' slab then holds its v
    chunks (virtual stages c*P+d) back to back. Applied INSIDE the
    traced loss (a differentiable gather — autodiff scatters grads back
    to the natural order), so optimizer state and checkpoints keep the
    natural layer layout."""
    import numpy as np
    Lv = L // (v * P_)
    rows = []
    for d in range(P_):
        for c in range(v):
            base = (c * P_ + d) * Lv
            rows.extend(range(base, base + Lv))
    return np.asarray(rows)


def _buffer_depths(tab, P_: int, v: int, M: int):
    """Max in-flight (received-not-yet-consumed) microbatches per (device,
    chunk), for the activation and cotangent ring buffers. Consumption
    order per chunk is increasing microbatch id, so slot = m %% K is
    collision-free for K = max window."""
    T = tab["fwd_c"].shape[1]
    V = v * P_
    k_act = 1
    k_cot = 1
    for d in range(P_):
        for c in range(v):
            vs = c * P_ + d
            # activation for F(c, m) arrives at the producer's F tick
            # (prev virtual stage) or is read straight from x_micro
            # (vs == 0); consumed by B(c, m)
            if vs > 0:
                pd, pc = (d - 1, c) if d > 0 else (P_ - 1, c - 1)
                recv = {tab["fwd_m"][pd, t]: t for t in range(T)
                        if tab["fwd_valid"][pd, t]
                        and tab["fwd_c"][pd, t] == pc}
            else:
                recv = {tab["fwd_m"][d, t]: t for t in range(T)
                        if tab["fwd_valid"][d, t]
                        and tab["fwd_c"][d, t] == c}
            cons = {tab["bwd_m"][d, t]: t for t in range(T)
                    if tab["bwd_valid"][d, t] and tab["bwd_c"][d, t] == c}
            for t in range(T):
                live = [m for m in recv
                        if recv[m] <= t and cons.get(m, T + 1) > t]
                if live:
                    k_act = max(k_act, max(live) - min(live) + 1)
            # cotangent for B(c, m): produced by the next virtual
            # stage's B (or the local head F when vs == V-1)
            if vs == V - 1:
                crecv = {tab["fwd_m"][d, t]: t for t in range(T)
                         if tab["fwd_valid"][d, t]
                         and tab["fwd_c"][d, t] == c}
            else:
                nd, nc = (d + 1, c) if d < P_ - 1 else (0, c + 1)
                crecv = {tab["bwd_m"][nd, t]: t for t in range(T)
                         if tab["bwd_valid"][nd, t]
                         and tab["bwd_c"][nd, t] == nc}
            for t in range(T):
                live = [m for m in crecv
                        if crecv[m] <= t and cons.get(m, T + 1) > t]
                if live:
                    k_cot = max(k_cot, max(live) - min(live) + 1)
    return k_act, k_cot


def _interleaved_program(stage_fn, head_loss_fn, num_stages, v, tables,
                         k_act, k_cot, axis,
                         stage_params, other_params, x_micro,
                         target_micro):
    """Interleaved 1F1B as ONE scan over the precomputed lockstep tick
    tables (runtime/pipe/schedule.py interleaved_1f1b_tables): each tick
    every device runs at most one chunk-forward and one chunk-backward,
    at (chunk, microbatch) coordinates read from the table — the
    schedule is data, not control flow. Activations/cotangents hop
    devices via ppermute; each device's stacked slab is [v, Lv, ...]
    with the chunk picked by dynamic index. Cuts the pipeline bubble by
    up to ~v at small M/P (see schedule.py; megatron-style virtual
    stages — beyond the reference's schedule set, ref deepspeed/runtime/
    pipe/schedule.py:182)."""
    M = x_micro.shape[0]
    P_ = num_stages
    V = v * P_
    d = jax.lax.axis_index(axis)
    T = tables["fwd_c"].shape[1]
    tab = {k: jnp.asarray(val) for k, val in tables.items()}

    fwd_perm, bwd_perm = _ring_perms(P_)
    f32 = jnp.float32
    zeros_like_tree = _zeros_like_f32
    head_for = _head_closure(head_loss_fn, target_micro, M)

    # local slab [v*Lv, ...] -> [v, Lv, ...]
    slab = jax.tree_util.tree_map(
        lambda p: p.reshape((v, p.shape[0] // v) + p.shape[1:]),
        stage_params)

    def chunk_params(c):
        return jax.tree_util.tree_map(lambda p: p[c], slab)

    x0 = jnp.zeros_like(x_micro[0])

    def tick(carry, t):
        (act_buf, cot_buf, dstage, dother, dx_acc, loss_acc) = carry

        # ---- forward: one chunk-F at the table's coordinates ----
        fc = tab["fwd_c"][d, t]
        fm = tab["fwd_m"][d, t]
        fv = tab["fwd_valid"][d, t] == 1
        vs_f = fc * P_ + d
        inp = jnp.where(vs_f == 0, x_micro[jnp.clip(fm, 0, M - 1)],
                        act_buf[fc, jnp.clip(fm, 0, M - 1) % k_act])
        out = stage_fn(chunk_params(fc), inp)

        # last virtual stage: head loss + cotangent, delivered locally
        loss_m, head_vjp = jax.vjp(head_for(fm), other_params, out)
        dother_m, dy_head = head_vjp(jnp.ones((), loss_m.dtype))
        m_head = ((vs_f == V - 1) & fv).astype(f32)
        loss_acc = loss_acc + loss_m.astype(f32) * m_head
        dother = jax.tree_util.tree_map(
            lambda a, g: a + g.astype(f32) * m_head, dother, dother_m)
        cot_buf = jnp.where(
            m_head > 0,
            cot_buf.at[fc, jnp.clip(fm, 0, M - 1) % k_cot].set(
                dy_head.astype(cot_buf.dtype)),
            cot_buf)

        # ship the activation to device d+1; store what arrives from d-1
        recv_act = jax.lax.ppermute(out, axis, fwd_perm)
        pd = (d - 1) % P_
        sfc = tab["fwd_c"][pd, t]
        sfm = tab["fwd_m"][pd, t]
        svs = sfc * P_ + pd
        rc = jnp.where(d == 0, sfc + 1, sfc)      # my chunk for that msg
        r_ok = ((tab["fwd_valid"][pd, t] == 1) & (svs < V - 1)
                & (rc < v))
        act_buf = jnp.where(
            r_ok,
            act_buf.at[jnp.clip(rc, 0, v - 1),
                       jnp.clip(sfm, 0, M - 1) % k_act].set(recv_act),
            act_buf)

        # ---- backward: one chunk-B at the table's coordinates ----
        bc = tab["bwd_c"][d, t]
        bm = tab["bwd_m"][d, t]
        bv = tab["bwd_valid"][d, t] == 1
        vs_b = bc * P_ + d
        x_saved = jnp.where(vs_b == 0, x_micro[jnp.clip(bm, 0, M - 1)],
                            act_buf[bc, jnp.clip(bm, 0, M - 1) % k_act])
        cot_in = cot_buf[bc, jnp.clip(bm, 0, M - 1) % k_cot]
        _, svjp = jax.vjp(stage_fn, chunk_params(bc), x_saved)
        dchunk, dx_m = svjp(cot_in.astype(x_saved.dtype))
        m_b = bv.astype(f32)
        dstage = jax.tree_util.tree_map(
            lambda acc, g: acc.at[bc].add(g.astype(f32) * m_b),
            dstage, dchunk)
        # embedding grads (virtual stage 0) accumulate per microbatch
        m_b0 = ((vs_b == 0) & bv).astype(dx_m.dtype)
        dx_acc = dx_acc.at[jnp.clip(bm, 0, M - 1)].add(dx_m * m_b0)

        # ship the cotangent to device d-1; store what arrives from d+1
        recv_cot = jax.lax.ppermute(dx_m, axis, bwd_perm)
        nd = (d + 1) % P_
        nbc = tab["bwd_c"][nd, t]
        nbm = tab["bwd_m"][nd, t]
        nvs = nbc * P_ + nd
        rcb = jnp.where(d == P_ - 1, nbc - 1, nbc)
        rb_ok = ((tab["bwd_valid"][nd, t] == 1) & (nvs > 0) & (rcb >= 0))
        cot_buf = jnp.where(
            rb_ok,
            cot_buf.at[jnp.clip(rcb, 0, v - 1),
                       jnp.clip(nbm, 0, M - 1) % k_cot].set(
                recv_cot.astype(cot_buf.dtype)),
            cot_buf)

        return (act_buf, cot_buf, dstage, dother, dx_acc, loss_acc), None

    carry0 = (jnp.zeros((v, k_act) + x0.shape, x0.dtype),
              jnp.zeros((v, k_cot) + x0.shape, f32),
              zeros_like_tree(slab),
              zeros_like_tree(other_params),
              jnp.zeros_like(x_micro),
              jnp.zeros((), f32))
    (_, _, dstage, dother, dx_micro, loss_sum), _ = jax.lax.scan(
        tick, carry0, jnp.arange(T))

    inv_m = 1.0 / M
    loss = jax.lax.psum(loss_sum * inv_m, axis)
    dother = jax.tree_util.tree_map(
        lambda g: jax.lax.psum(g * inv_m, axis), dother)
    dx_micro = jax.lax.psum(dx_micro * inv_m, axis)
    # [v, Lv, ...] grads -> the [v*Lv, ...] slab layout of the input
    dstage = jax.tree_util.tree_map(
        lambda g: g.reshape((g.shape[0] * g.shape[1],) + g.shape[2:]) *
        inv_m, dstage)
    return loss, dstage, dother, dx_micro


def make_interleaved_loss_fn(stage_fn, head_loss_fn, num_stages, v,
                             num_micro, mesh, stage_params_specs, *,
                             axis: str = "pipe"):
    """(stage_params_virtual, other_params, x_micro, target_micro) ->
    scalar loss under the interleaved 1F1B schedule; differentiable via
    the same stashed-grads custom_vjp shape as make_1f1b_loss_fn.
    stage_params_virtual must be stacked in VIRTUAL-STAGE order
    (interleave_layer_perm) so the 'pipe' sharding gives each device its
    v chunks."""
    from deepspeed_tpu.runtime.pipe.schedule import interleaved_1f1b_tables
    tables = interleaved_1f1b_tables(num_stages, v, num_micro)
    k_act, k_cot = _buffer_depths(tables, num_stages, v, num_micro)

    def run(stage_params, other_params, x_micro, target_micro):
        prog = partial(_interleaved_program, stage_fn, head_loss_fn,
                       num_stages, v, tables, k_act, k_cot, axis)
        return jax.shard_map(
            prog, mesh=mesh,
            in_specs=(stage_params_specs, P(), P(), P()),
            out_specs=(P(), stage_params_specs, P(), P()),
            axis_names={axis}, check_vma=False)(
                stage_params, other_params, x_micro, target_micro)

    return _make_stashed_grad_loss(run)
