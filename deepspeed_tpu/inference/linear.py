"""Paged serving blocks for a model whose layers are of two kinds of
cache STATE: a FIFTH dialect, and the first whose slot costs memory before
it holds a token.

- The RECURRENT layers keep, per slot and layer, a float32 state that
  summarises the slot's whole history, read AND rewritten by every token
  whatever the sequence's length and not recomputable from any block, and
  beside it the un-convolved rows of the last ``conv_kernel - 1`` tokens,
  the left context of a depthwise convolution. No block table reaches
  either. THREE RULES write such a state today, and the store, the layer
  loop and the resume rule below are the state's, not a rule's
  (:func:`rule_of`: the config's ``recurrent_rule``):
  "kda", gated delta-rule linear attention with a decay per key channel
  (models/kimi_linear.py; ``linear_heads`` matrices ``[Dv, Dk]`` a layer,
  its tail the ``[q | k | v]`` rows); "gdn", the same rule with ONE decay a
  head (Gated DeltaNet, models/qwen3_next.py; the same state, tail and step
  kernel, another chunk form): both are this file's ``delta_*`` blocks over
  ops/attention/kda.py, told apart by a :class:`DeltaRule` record; and
  "ssm", a Mamba-1 state-space mixer (models/jamba.py: inference/ssm.py
  over ops/attention/ssm.py; ``[d_state, d_inner]`` a layer, its tail the
  ``x`` rows).
- The PAGED layers keep rows behind the slot's block table, of one of TWO
  KINDS (:func:`_latent_rows`: the config's ``paged_kind``): "latent", ONE
  pool of latent rows (Kimi-Linear's MLA layers, latent.py's two attention
  paths called from here), or "kv", the GPT blocks' K and V pools (Jamba's
  attention layers and Qwen3-Next's gated ones: the engine's own two paths
  called from here).

The rule and the paged kind are chosen APART, each by its own datum of the
config (:func:`prefill_attends`, :func:`decode_attends`): Kimi-Linear is
(kda, latent), Jamba (ssm, kv), Qwen3-Next the cross pair (gdn, kv).

:class:`LinearState` rides in ``k_pool``'s place (``v_pool`` is None, or
the V pool where the paged layers keep two): ``rows`` ``[L_paged, N,
block, lanes]``, ``state`` ``[L_rec, slots, *cfg.recurrent_state_shape]``
float32, ``tail`` ``[L_rec, slots, cfg.conv_tail_width]`` (a slot's rows
side by side, oldest first: with ``taps - 1 = 3`` rows a dimension of
their own the device pads them to a tile of 8 or 16 and both programs
re-laid the buffer out on every dispatch). Each kind's
buffers are indexed by the kind's OWN layer counter
(models/recurrent.layer_bases): which layer is of which kind is the
config's to say (a list, a period) and the loop follows any order.

A prefill chunk carries its slot's state from chunk to chunk THROUGH the
state buffer: it starts from the slot's state and tail when ``start > 0``
and from zeros when ``start = 0`` (a reused slot starts clean without
anything being cleared), runs the rule's chunk form (``kda_chunk``,
``gdn_chunk``, ``ssm_scan``) and leaves the state after its last valid
token. A decode dispatch is one recurrent step (``kda_step``, ``ssm_step``)
over the ACTIVE slots, in place; the state of an idle slot, or of one still
in prefill, is not touched. A preempted request recomputes from position 0,
as any other: the replay rebuilds the state.

One compiled body per KIND of layer (:func:`run_layers`): the leading
dense layers inline (recurrent, by the config), then ONE scan over the
runs the kinds cut the other layers into, each run an inner loop over its
recurrent layers and the paged layer that ends it, every buffer in the
loops' carries.

Not served by this dialect, and refused at construction by name (no
program of theirs carries the state): prefix sharing and copy-on-write, the
host tier, int8 pools, speculation/verify, the fused horizon, LoRA, tensor
parallelism; nor the static-cache paths. docs/LINEAR_ATTENTION.md,
docs/STATE_SPACE.md."""

import contextlib
import functools
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference import dialect, latent, ssm
from deepspeed_tpu.inference.hybrid import _ffn, _heads, _rows, split_experts
from deepspeed_tpu.models.gpt import _dense, _norm
from deepspeed_tpu.models.recurrent import layer_bases, layer_runs
from deepspeed_tpu.ops.attention import kda


class LinearState(NamedTuple):
    """The device state of this dialect's cache (module docstring).
    ``stats`` / ``route``: the expert layers' counters and the last
    dispatch's selection, as hybrid.PagedState's."""
    rows: jnp.ndarray
    state: jnp.ndarray
    tail: jnp.ndarray
    stats: Optional[jnp.ndarray] = None
    route: Optional[jnp.ndarray] = None

    def delete(self):
        for a in self:
            if a is not None:
                a.delete()


def is_linear(cfg) -> bool:
    """A model that keeps a per-slot recurrent state, whichever rule
    writes it."""
    return bool(getattr(cfg, "recurrent_state_values", 0))


def _latent_rows(cfg) -> bool:
    """The paged layers keep ONE pool of latent rows (Kimi-Linear's MLA
    layers), not K and V pools (Jamba's and Qwen3-Next's attention layers):
    ``paged_kind`` ("latent" | "kv"), or, for a config from before that
    key, whether it has a ``kv_lora_rank``."""
    kind = getattr(cfg, "paged_kind", None)
    return latent.DIALECT.owns(cfg) if kind is None else kind == "latent"


def new_state(cfg, num_blocks: int, block_size: int, num_slots: int, dtype):
    """Zeroed (LinearState, what rides in ``v_pool``'s place) for
    ``num_blocks`` blocks a paged layer: ``rows`` one pool of latent rows
    and nothing beside it, or the K pool of the K/V heads' rows and a V
    pool."""
    Lr = cfg.n_recurrent_layers
    state = LinearState(
        jnp.zeros((cfg.n_full_layers, num_blocks, block_size,
                   cfg.latent_lanes if _latent_rows(cfg)
                   else cfg.kv_heads * cfg.head_dim), dtype),
        jnp.zeros((Lr, num_slots) + tuple(cfg.recurrent_state_shape),
                  jnp.float32),
        jnp.zeros((Lr, num_slots, cfg.conv_tail_width), dtype))
    return state, None if _latent_rows(cfg) else jnp.zeros_like(state.rows)


def step_plan(active):
    """(batch rows with the active ones first, how many are active): the
    work list of every layer's ``kda_step`` call, made once a dispatch."""
    order = jnp.argsort(jnp.logical_not(active), stable=True)
    return order.astype(jnp.int32), jnp.sum(active, dtype=jnp.int32)[None]


def _layer(stack, i):
    """Layer ``i`` (traced) of a parameter stack."""
    return jax.tree_util.tree_map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False),
        stack)


def _unit(x, eps):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def _conv(xs, left, p):
    """The depthwise causal convolution over a token's un-convolved row
    ``xs`` ``[T, C]`` and the ``taps - 1`` rows before it (``left``, the
    oldest first): tap j meets the token taps - 1 - j back. float32."""
    f32 = jnp.float32
    w = p["conv"]["kernel"].astype(f32)                        # [taps, C]
    return xs.astype(f32) * w[-1] + sum(
        rows.astype(f32) * w[j] for j, rows in enumerate(left))


def _decay(p, step, H):
    """``g = -exp(A_log) softplus(step + dt_bias)`` of ``H`` heads, float32:
    ``step`` ``[T, H * n]`` gives ``[T, H, n]`` (KDA: a decay a key
    channel), ``[T, H]`` gives ``[T, H]`` (Gated DeltaNet: one a head)."""
    f32 = jnp.float32
    rate = jnp.exp(p["A_log"].astype(f32))
    if step.shape[-1] == H:
        return -rate * jax.nn.softplus(step.astype(f32)
                                       + p["dt_bias"].astype(f32))
    return -rate[:, None] * jax.nn.softplus(
        _heads(step.astype(f32) + p["dt_bias"].astype(f32), H))


def _project(h, p):
    """h ``[T, d]`` (normed) -> the tokens' un-convolved ``[q | k | v]``
    rows ``[T, C]`` and what the decay, the output gate and the write
    strength are made from."""
    with jax.named_scope("kda_proj"):
        return (_dense(h, p["qkv"]), _dense(_dense(h, p["f_a"]), p["f_b"]),
                _dense(_dense(h, p["g_a"]), p["g_b"]), _dense(h, p["b"]))


def _mix(proj, left, p, cfg):
    """:func:`_project`'s rows with the ``taps - 1`` rows before each
    (``left``: that many ``[T, C]`` arrays, the oldest first: a token's own
    left context) -> what the rule takes, float32: (q, k, v, g ``[T, H,
    Dh]``, b ``[T, H]``), and the output gate ``[T, H, Dh]``."""
    H, Dh = cfg.linear_heads, cfg.linear_head_dim
    f32 = jnp.float32
    xs, decay, gate, write = proj
    with jax.named_scope("kda_mix"):
        y = _conv(xs, left, p)
        q, k, v = (_heads(a, H) for a in jnp.split(jax.nn.silu(y), 3, -1))
        q = _unit(q, cfg.l2_eps) * Dh ** -0.5
        k = _unit(k, cfg.l2_eps)
        g = _decay(p, decay, H)
        b = jax.nn.sigmoid(write.astype(f32))
        gate = jax.nn.sigmoid(_heads(gate.astype(f32), H))
    return (q, k, v, g, b), gate


def _project_gdn(h, p):
    """Gated DeltaNet's :func:`_project`: ``[q | k | v | z]`` from ONE
    projection and ``[b | alpha]`` from another; the same four results."""
    with jax.named_scope("gdn_proj"):
        C = p["conv"]["kernel"].shape[-1]
        qkvz, ba = _dense(h, p["in_qkvz"]), _dense(h, p["in_ba"])
        write, decay = jnp.split(ba, 2, axis=-1)
        return qkvz[:, :C], decay, qkvz[:, C:], write


def _mix_gdn(proj, left, p, cfg):
    """Gated DeltaNet's :func:`_mix`: ``linear_key_heads`` heads of q and k
    (``[T, Hk, Dh]``: each serves ``H / Hk`` consecutive value heads, and
    the rule's chunk form and :func:`delta_decode` repeat it where they
    must), ONE decay a value head (g ``[T, H]``), and the gate ``silu(z)``
    ``[T, H, Dh]``."""
    Hk, H, Dh = cfg.linear_key_heads, cfg.linear_heads, cfg.linear_head_dim
    f32 = jnp.float32
    xs, decay, z, write = proj
    with jax.named_scope("gdn_mix"):
        y = jax.nn.silu(_conv(xs, left, p))
        q, k, v = jnp.split(y, [Hk * Dh, 2 * Hk * Dh], axis=-1)
        q = _unit(_heads(q, Hk), cfg.l2_eps) * Dh ** -0.5
        k = _unit(_heads(k, Hk), cfg.l2_eps)
        g = _decay(p, decay, H)
        b = jax.nn.sigmoid(write.astype(f32))
        gate = jax.nn.silu(_heads(z.astype(f32), H))
    return (q, k, _heads(v, H), g, b), gate


class DeltaRule(NamedTuple):
    """What tells the two gated delta rules apart; the blocks below are
    what they share. ``name``: the scopes ``attn_<name>``, ``<name>_proj``
    / ``_mix`` / ``_out`` and the chunk form's; ``project`` / ``mix``: the
    layer's projections and what makes the rule's operands of them;
    ``chunk``: the rule's chunk form (ops/attention/kda.py). The decode
    step is ONE kernel for both: its packed rows carry the decay on the
    128 lanes of a row a head, and a head's scalar fills that row."""
    name: str
    project: Callable
    mix: Callable
    chunk: Callable


KDA = DeltaRule("kda", _project, _mix, kda.kda_chunk)
GDN = DeltaRule("gdn", _project_gdn, _mix_gdn, kda.gdn_chunk)


def _on_lanes(g, q):
    """The decay as the step kernel takes it: a value a key channel."""
    return g if g.ndim == q.ndim else jnp.broadcast_to(g[..., None], q.shape)


def _output(x, o, gate, p, cfg, name):
    """The rule's output ``[T, H, Dh]`` float32, normalised per head (a
    plain scale), gated and projected back onto the stream ``x``."""
    with jax.named_scope(name + "_out"):
        o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                              + cfg.norm_eps) \
            * p["o_norm"]["scale"].astype(jnp.float32)
        return x + _dense(_rows(o * gate).astype(x.dtype), p["attn_out"])


def delta_prefill(rule, x, state, tails, slot, positions, n_valid, p, cfg,
                  at, impl):
    """The linear-attention sublayer over a PROMPT CHUNK of slot ``slot``:
    ``x`` ``[C, d]`` -> (x + attention, state, tails); the slot's state and
    tail lie at ``at + slot`` of the flat buffers; ``impl``: the engine's,
    by which the rule's chunk form chooses its kernel (kda.chunk_form)."""
    C = x.shape[0]
    taps = cfg.conv_kernel
    at = at + slot
    resumed = positions[0] > 0
    valid = jnp.arange(C) < n_valid
    with jax.named_scope("paged_attn"), jax.named_scope("attn_" + rule.name):
        h = _norm(x, p["ln1"], cfg)
        # position 0 is left-padded with zeros and starts from a zero
        # state: a reused slot starts clean without anything being cleared
        t0 = jnp.where(resumed, tails[at], 0).reshape(taps - 1, -1)
        s0 = jnp.where(resumed, state[at], 0.0)
        proj = rule.project(h, p)
        rows = jnp.concatenate([t0, proj[0]], axis=0)          # [taps-1+C, C]
        left = [rows[j:j + C] for j in range(taps - 1)]
        (q, k, v, g, b), gate = rule.mix(proj, left, p, cfg)
        # a padding token leaves the state alone
        g = jnp.where(jnp.expand_dims(valid, tuple(range(1, g.ndim))), g,
                      0.0)
        b = jnp.where(valid[:, None], b, 0.0)
        o, s = rule.chunk(q, k, v, g, b, s0, impl=impl)
        state = state.at[at].set(s)
        # what the next chunk (or the first decode step) resumes from: the
        # rows of the last VALID tokens; with none, the tail as it was
        tails = tails.at[at].set(jax.lax.dynamic_slice_in_dim(
            rows, n_valid, taps - 1).reshape(-1))
        return _output(x, o, gate, p, cfg, rule.name), state, tails


def delta_decode(rule, x, state, tails, active, p, cfg, at, impl, plan):
    """The linear-attention sublayer for ONE new token per slot: ``x``
    ``[B, d]`` -> (x + attention, state, tails). Slot ``s``'s state and
    tail lie at ``at + s``; only the ACTIVE slots' are rewritten."""
    B = x.shape[0]
    with jax.named_scope("paged_attn"), jax.named_scope("attn_" + rule.name):
        h = _norm(x, p["ln1"], cfg)
        # [B, 3 C], read out BEFORE the update below is formed: fused into
        # it, the shifted read kept the update from running in place and
        # the whole buffer was copied in and out of the program
        t0 = jax.lax.optimization_barrier(
            jax.lax.dynamic_slice_in_dim(tails, at, B))
        proj = rule.project(h, p)
        C = proj[0].shape[-1]
        (q, k, v, g, b), gate = rule.mix(
            proj, jnp.split(t0, cfg.conv_kernel - 1, axis=1), p, cfg)
        own = jnp.concatenate([t0[:, C:], proj[0]], axis=1)
        tails = jax.lax.dynamic_update_slice_in_dim(
            tails, jnp.where(active[:, None], own, t0), at, 0)
        q, k = (kda.per_value_head(a, v.shape[-2]) for a in (q, k))
        g = _on_lanes(g, q)
        if impl == "pallas":
            order, count = plan
            state, o = kda.kda_step(state, kda.pack_step(q, k, g, v, b),
                                    at + order, order, count)
            # rows that did not decode hold whatever was in the buffer
            o = jnp.where(active[:, None, None], o, 0.0)
        else:
            with jax.named_scope("kda_step"):
                state, o = kda.kda_step_reference(state, q, k, v, g, b, at,
                                                  active)
        return _output(x, o, gate, p, cfg, rule.name), state, tails


kda_prefill = functools.partial(delta_prefill, KDA)
kda_decode = functools.partial(delta_decode, KDA)
gdn_prefill = functools.partial(delta_prefill, GDN)
gdn_decode = functools.partial(delta_decode, GDN)


def rule_of(cfg) -> str:
    """Which rule writes the config's recurrent state: ``recurrent_rule``
    ("kda" | "gdn" | "ssm"), or, for a config from before that key, the
    state-space one where it has a ``mamba_d_state`` and KDA else."""
    return getattr(cfg, "recurrent_rule", None) \
        or ("ssm" if ssm.is_ssm(cfg) else "kda")


@contextlib.contextmanager
def _kv_scope(cfg):
    """The scopes of a K/V attention layer with an output gate; a plain one
    (Jamba's) keeps the engine's own."""
    if not getattr(cfg, "attn_output_gate", False):
        yield
        return
    with jax.named_scope("paged_attn"), jax.named_scope("attn_gated"):
        yield


def prefill_attends(cfg, table_row, positions, n_valid, slot, impl):
    """The two attention sublayers of a PROMPT CHUNK of slot ``slot``, as
    :func:`run_layers` calls them, the recurrent kind's first:
    ``attend(x [C, d], flat, p, base) -> (x + attention, flat)``. ``flat``
    ends (state, tails) and starts with the paged kind's pool or pools:
    (rows,) of latents or (K pool, V pool). Each half by its OWN datum of
    the config: the rule (:func:`rule_of`) and the paged kind."""
    from deepspeed_tpu.inference.engine import _attn_prefill_paged
    block = {"kda": kda_prefill, "gdn": gdn_prefill,
             "ssm": ssm.ssm_prefill}[rule_of(cfg)]

    def recurrent_attn(x, flat, p, base):
        y, state, tails = block(x, flat[-2], flat[-1], slot, positions,
                                n_valid, p, cfg, base["state"], impl=impl)
        return y, flat[:-2] + (state, tails)

    def latent_attn(x, flat, p, base):
        y, rows = latent.attend_prefill(x, flat[0], table_row, positions,
                                        n_valid, p, cfg, base["rows"], impl)
        return y, (rows,) + flat[1:]

    def kv_attn(x, flat, p, base):
        with _kv_scope(cfg):
            _, attn, kv = _attn_prefill_paged(
                x[None], flat[:2], table_row, positions, n_valid, p, cfg,
                base=base["rows"])
        return x + attn[0], kv + flat[2:]
    return recurrent_attn, latent_attn if _latent_rows(cfg) else kv_attn


def decode_attends(cfg, tables, lengths, active, impl, paged_plan):
    """The same for ONE new token per slot (``x`` ``[B, d]``);
    ``paged_plan``: the paged layers' kernel's grid for these lengths."""
    from deepspeed_tpu.inference.engine import _attn_decode_paged
    plan = step_plan(active)
    block = {"kda": kda_decode, "gdn": gdn_decode,
             "ssm": ssm.ssm_decode}[rule_of(cfg)]

    def recurrent_attn(x, flat, p, base):
        y, state, tails = block(x, flat[-2], flat[-1], active, p, cfg,
                                base["state"], impl, plan)
        return y, flat[:-2] + (state, tails)

    def latent_attn(x, flat, p, base):
        y, rows = latent.attend_decode(x, flat[0], tables, lengths, active,
                                       p, cfg, base["rows"], impl, paged_plan)
        return y, (rows,) + flat[1:]

    def kv_attn(x, flat, p, base):
        with _kv_scope(cfg):
            _, attn, kv = _attn_decode_paged(
                x[:, None], flat[:2], tables, lengths, active, p, cfg,
                impl=impl, base=base["rows"], plan=paged_plan)
        return x + attn[:, 0], kv + flat[2:]
    return recurrent_attn, latent_attn if _latent_rows(cfg) else kv_attn


def run_layers(cfg, params, experts, carry, flat, bases, attends, valid,
               impl):
    """Every layer of one serving program. ``carry`` = (x ``[T, d]``, aux
    as engine._dense_then_sparse makes it); ``flat``: the cache's buffers
    as the ``attends`` take them, each flat over its OWN kind's layers;
    ``bases``: per layer, by layer index (models/recurrent.layer_bases);
    ``attends``: :func:`prefill_attends` or :func:`decode_attends`, the
    recurrent kind's sublayer and the paged kind's; ``params`` without the
    expert kernels, which are ``experts`` (hybrid.split_experts; None
    where every FFN is dense).

    The kinds follow the config, so the loop is cut where it says: the
    leading dense layers inline, then ONE scan over the runs of the other
    layers, each run ``n`` recurrent layers (an inner loop whose trip
    count is the run's own) and the paged layer that ends it, then the
    recurrent layers behind the last paged one, if any. One compiled body
    per kind: every buffer rides in the loops' carries and is updated in
    place. (A ``lax.cond`` on the kind inside one scan compiled a copy of
    the recurrent state, 1.7 GB, into the paged branch: PERF.md, PR 40.)"""
    nd = cfg.n_dense_layers
    recurrent_attn, paged_attn = attends
    recurrent, paged = cfg.recurrent_stacks

    def layer(l, stack, attend, loop):
        (x, aux), flat = loop
        base = {k: v[l] for k, v in bases.items()}
        x2, flat = attend(x, flat, _layer(params[stack], base["attn"]), base)
        # only the inline leading layers come with a Python index
        p = _layer(params["dense_block"], l) if isinstance(l, int) \
            else _layer(params["block"], l - nd)
        y, aux = _ffn(x2, p, cfg, impl, valid, aux, base["index"], experts)
        return (y, aux), flat

    def recurrent_run(start, n, loop):
        return jax.lax.fori_loop(
            0, n, lambda i, loop: layer(start + i, recurrent,
                                        recurrent_attn, loop), loop)

    loop = (carry, flat)
    for l in range(nd):
        loop = layer(l, recurrent, recurrent_attn, loop)
    starts, counts, behind = layer_runs(cfg)

    def run(loop, r):
        start, n = r
        loop = recurrent_run(start, n, loop)
        return layer(start + n, paged, paged_attn, loop), None

    loop, _ = jax.lax.scan(run, loop, (jnp.asarray(starts),
                                       jnp.asarray(counts)))
    if behind[1]:
        loop = recurrent_run(jnp.int32(behind[0]), jnp.int32(behind[1]),
                             loop)
    return loop


def serve_layers(eng, params, pools, attends, x, valid, impl: str,
                 phase: int):
    """The layers of both serving programs (:func:`run_layers`, whose loop
    this feeds): ``pools`` = (LinearState, the V pool or None), the paged
    layers' pool or pools, the recurrent layers' state and their
    convolution tails side by side in the carry, each stacked over its OWN
    kind's layers; ``attends``: the two attention sublayers; ``valid``
    ``[T]``: the rows that are tokens. ``x`` ``[1, C, d]`` or ``[B, 1,
    d]``; ``phase``: the counters' row (0 prefill, 1 decode)."""
    cfg = eng.cfg
    st, v = pools
    shapes = (st.rows,) + (() if v is None else (v,)) \
        + (st.state, st.tail)
    flat = tuple(p.reshape((-1,) + p.shape[2:]) for p in shapes)
    T = x.shape[0] * x.shape[1]
    experts, aux = None, {"stats": None, "route": None}
    if "moe" in params["block"]:
        params, experts = split_experts(params)
        aux = eng._dispatch_record(T, st.stats)
    (y, aux), flat = run_layers(
        cfg, params, experts, (x.reshape(T, -1), aux), flat,
        layer_bases(cfg, st.rows.shape[1], st.state.shape[1]), attends,
        valid, impl)
    stats = st.stats
    if stats is not None:
        stats = stats.at[phase].add(aux["stats"])
    flat = [f.reshape(p.shape) for f, p in zip(flat, shapes)]
    return y.reshape(x.shape), (LinearState(
        flat[0], flat[-2], flat[-1], stats, aux["route"]),
        None if v is None else flat[1])


def prefill_layers(eng, params, pools, x, table_row, positions, n_valid,
                   slot, lora):
    impl = eng.decode_impl
    return serve_layers(
        eng, params, pools, prefill_attends(
            eng.cfg, table_row, positions, n_valid, slot, impl), x,
        jnp.arange(x.shape[1]) < n_valid, impl, 0)


def decode_layers(eng, params, pools, x, tables, lengths, active, impl, lora):
    plan = dialect.rows_plan(eng.cfg, pools, tables, lengths, active)
    return serve_layers(
        eng, params, pools, decode_attends(
            eng.cfg, tables, lengths, active, impl, plan), x, active, impl, 1)


def kv_bytes_per_token(cfg, dtype=jnp.bfloat16) -> int:
    """Bytes ONE token occupies across the PAGED layers: a recurrent layer
    keeps a state per slot and adds nothing per token."""
    return (latent.kv_bytes_per_token if _latent_rows(cfg)
            else dialect.full_layers_kv_bytes)(cfg, dtype)


def slot_bytes(cfg, block_size: int, dtype=jnp.bfloat16):
    """Per recurrent layer the float32 state, and the last tokens'
    un-convolved rows in the pools' type."""
    return dialect.SlotBytes(
        recurrent_state=4 * int(cfg.recurrent_state_values),
        conv_tail=int(cfg.conv_tail_values) * jnp.dtype(dtype).itemsize)


def gauges(reg, cache):
    if _latent_rows(cache.cfg):
        latent.gauges(reg, cache)
    reg.gauge("kv_recurrent_state_bytes",
              "device bytes of the per-slot recurrent state: float32, per "
              "recurrent layer and slot one matrix of head_dim x head_dim a "
              "head (linear attention) or d_state x d_inner (a state-space "
              "mixer), read and rewritten by every token whatever the "
              "slot's length").set(cache.recurrent_state_bytes)
    reg.gauge("kv_conv_tail_bytes",
              "device bytes of the recurrent layers' per-slot convolution "
              "tails: per layer and slot the un-convolved rows ([q | k | "
              "v], or a state-space mixer's x) of the last conv_kernel - 1 "
              "tokens").set(cache.conv_tail_bytes)


def _refusal(cfg):
    rule, doc = ("state-space", "STATE_SPACE") if rule_of(cfg) == "ssm" \
        else ("linear-attention", "LINEAR_ATTENTION")
    return (f"a per-slot recurrent state (written by its {rule} layers: it "
            f"summarises the whole history and rides beside the paged "
            f"pool)", doc)


def prefill_reads(cfg, *a) -> int:
    """The latent layers beside a recurrent state read as latent.py's do,
    the attention layers beside a state-space state as the GPT blocks
    (reached when called, as inference/ssm.py reaches them)."""
    if _latent_rows(cfg):
        return dialect.occupied_reads(cfg, *a)
    from deepspeed_tpu.inference.engine import tile_reads
    return tile_reads(cfg, *a)


def _ready_note(cfg, impl):
    """Which chunk form a Gated DeltaNet prefill program is traced with."""
    if rule_of(cfg) != "gdn":
        return ""
    form = kda.chunk_form(impl, cfg.linear_heads, cfg.linear_head_dim,
                          cfg.linear_head_dim,
                          key_heads=cfg.linear_key_heads)
    return f", gdn_chunk={form}"


DIALECT = dialect.Dialect(
    owns=is_linear, new_state=new_state, pool=lambda k: k.rows,
    prefill_layers=prefill_layers, decode_layers=decode_layers,
    prefill_reads=prefill_reads,
    refusal=_refusal, state=LinearState,
    bytes_per_token=kv_bytes_per_token, slot_bytes=slot_bytes,
    flash_steps=lambda cfg, start, bs: latent.flash_steps(cfg, start, bs)
    if _latent_rows(cfg) else 0,
    tile_row_bytes=lambda cfg, pool: None if _latent_rows(cfg)
    else dialect.pool_row_bytes(pool),
    needs_slot=True, gauges=gauges, ready_note=_ready_note)
