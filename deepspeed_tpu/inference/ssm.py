"""The serving blocks of a Mamba-1 state-space mixer and of the plain
attention layers beside it (models/jamba.py), for the layer loop and the
per-slot store of inference/linear.py: the SECOND rule that writes that
dialect's recurrent state (the first is linear.py's own gated delta rule),
and the first that pairs it with the GPT blocks' two K/V pools instead of
a latent pool.

A state-space layer keeps, per slot, ``[d_state, d_inner]`` float32 (the
published ``[d_inner, d_state]`` transposed: ops/attention/ssm.py) and the
un-convolved ``x`` rows of the last ``conv_kernel - 1`` tokens. The rules
of the store are linear.py's: a prefill chunk starts from the slot's state
and tail when ``start > 0`` and from zeros when ``start = 0``, runs the
recurrence over its tokens (``ssm_scan``; padding rows come with ``delta =
0`` and leave the state alone) and leaves the state after its last valid
token; a decode dispatch is one step (``ssm_step``) over the ACTIVE slots,
in place. The attention layers are the engine's own: ``n_heads`` query
heads on the one K/V head, nothing rotated, through
``_attn_prefill_paged`` (``_attend_occupied``) and ``_attn_decode_paged``
(``paged_decode``), their pools in the loop's carry beside the state: the
K/V half of linear.py's ``prefill_attends`` / ``decode_attends``, which
pair it with this rule by the config's data.
docs/STATE_SPACE.md."""

import jax
import jax.numpy as jnp

from deepspeed_tpu.models.gpt import _dense, _kernel_of, _norm
from deepspeed_tpu.ops.attention import ssm

f32 = jnp.float32


def is_ssm(cfg) -> bool:
    return bool(getattr(cfg, "mamba_d_state", 0))


def _project32(h, p):
    """``h @ kernel (+ bias)`` accumulated AND returned in float32: what
    follows is a norm or the step that enters ``exp``."""
    y = jnp.dot(h, _kernel_of(p, h.dtype), preferred_element_type=f32)
    return y + p["bias"].astype(f32) if "bias" in p else y


def _mix(xs, left, p, cfg):
    """The un-convolved ``x`` rows ``[T, Di]`` with the ``taps - 1`` rows
    before each (``left``: that many ``[T, Di]`` arrays, the oldest first)
    -> what the recurrence takes, float32: x ``[T, Di]`` (convolved, SiLU),
    delta ``[T, Di]``, B, C ``[T, N]``."""
    N, R = cfg.mamba_d_state, cfg.mamba_dt_rank
    with jax.named_scope("ssm_mix"):
        # the depthwise convolution: tap j meets the token taps - 1 - j back
        w = p["conv"]["kernel"].astype(f32)                    # [taps, Di]
        x = jax.nn.silu(
            xs.astype(f32) * w[-1] + p["conv"]["bias"].astype(f32) + sum(
                rows.astype(f32) * w[j] for j, rows in enumerate(left)))
        dt, B, C = jnp.split(_project32(x.astype(xs.dtype), p["x_proj"]),
                             [R, R + N], axis=-1)
        dt, B, C = (_norm(a, p[n], cfg) for a, n in (
            (dt, "dt_norm"), (B, "b_norm"), (C, "c_norm")))
        delta = jax.nn.softplus(_project32(dt.astype(xs.dtype),
                                           p["dt_proj"]))
    return x, delta, B, C


def _transition(p):
    return -jnp.exp(p["A_log"].astype(f32))                    # [N, Di]


def _output(res, y, x, z, p):
    """The recurrence's ``y`` ``[T, Di]`` float32 with the skip, gated and
    projected back onto the stream ``res``."""
    with jax.named_scope("ssm_mix"):
        y = (y + p["D"].astype(f32) * x) * jax.nn.silu(z.astype(f32))
    with jax.named_scope("ssm_proj"):
        return res + _dense(y.astype(res.dtype), p["out_proj"])


def ssm_prefill(x, state, tails, slot, positions, n_valid, p, cfg, at,
                impl):
    """The state-space sublayer over a PROMPT CHUNK of slot ``slot``: ``x``
    ``[C, d]`` -> (x + mixer, state, tails); the slot's state and tail lie
    at ``at + slot`` of the flat buffers."""
    C = x.shape[0]
    taps, Di = cfg.conv_kernel, cfg.d_inner
    at = at + slot
    resumed = positions[0] > 0
    valid = jnp.arange(C) < n_valid
    with jax.named_scope("paged_attn"), jax.named_scope("attn_ssm"):
        h = _norm(x, p["ln1"], cfg)
        with jax.named_scope("ssm_proj"):
            xs, z = jnp.split(_dense(h, p["in_proj"]), 2, axis=-1)
        # position 0 is left-padded with zeros and starts from a zero
        # state: a reused slot starts clean without anything being cleared
        t0 = jnp.where(resumed, tails[at], 0).reshape(taps - 1, Di)
        s0 = jnp.where(resumed, state[at], 0.0)
        rows = jnp.concatenate([t0, xs], axis=0)              # [taps-1+C, Di]
        xc, delta, B, Cm = _mix(xs, [rows[j:j + C] for j in range(taps - 1)],
                                p, cfg)
        # a padding token leaves the state alone
        delta = jnp.where(valid[:, None], delta, 0.0)
        if impl == "pallas":
            y, s = ssm.ssm_scan(xc, delta, _transition(p), B, Cm, s0)
        else:
            with jax.named_scope("ssm_scan"):
                y, s = ssm.ssm_recurrence(xc, delta, _transition(p), B, Cm,
                                          s0)
        state = state.at[at].set(s)
        # what the next chunk (or the first decode step) resumes from: the
        # rows of the last VALID tokens; with none, the tail as it was
        tails = tails.at[at].set(jax.lax.dynamic_slice_in_dim(
            rows, n_valid, taps - 1).reshape(-1))
        return _output(x, y, xc, z, p), state, tails


def ssm_decode(x, state, tails, active, p, cfg, at, impl, plan):
    """The state-space sublayer for ONE new token per slot: ``x`` ``[B,
    d]`` -> (x + mixer, state, tails). Slot ``s``'s state and tail lie at
    ``at + s``; only the ACTIVE slots' are rewritten."""
    n = x.shape[0]
    Di = cfg.d_inner
    with jax.named_scope("paged_attn"), jax.named_scope("attn_ssm"):
        h = _norm(x, p["ln1"], cfg)
        # read out BEFORE the update below is formed (linear.kda_decode:
        # fused into it, the shifted read kept the update from running in
        # place)
        t0 = jax.lax.optimization_barrier(
            jax.lax.dynamic_slice_in_dim(tails, at, n))
        with jax.named_scope("ssm_proj"):
            xs, z = jnp.split(_dense(h, p["in_proj"]), 2, axis=-1)
        xc, delta, B, Cm = _mix(
            xs, jnp.split(t0, cfg.conv_kernel - 1, axis=1), p, cfg)
        own = jnp.concatenate([t0[:, Di:], xs], axis=1)
        tails = jax.lax.dynamic_update_slice_in_dim(
            tails, jnp.where(active[:, None], own, t0), at, 0)
        if impl == "pallas":
            order, count = plan
            state, y = ssm.ssm_step(state, _transition(p),
                                    *ssm.pack_step(xc, delta, B, Cm),
                                    at + order, order, count)
            # rows that did not decode hold whatever was in the buffer
            y = jnp.where(active[:, None], y, 0.0)
        else:
            with jax.named_scope("ssm_step"):
                state, y = ssm.ssm_step_reference(
                    state, _transition(p), xc, delta, B, Cm, at, active)
        return _output(x, y, xc, z, p), state, tails
