"""The selective state-space recurrence of a Mamba-1 mixer
(models/jamba.py): per channel ``c`` and state index ``n``, on a float32
state,

    h_t[n, c] = exp(delta_t[c] A[n, c]) h_{t-1}[n, c]
                + delta_t[c] B_t[n] x_t[c],
    y_t[c]    = sum_n C_t[n] h_t[n, c],          A < 0, delta >= 0.

The transition is DIAGONAL: elementwise over ``N x Ci`` values a token, no
matrix product anywhere (the skip ``D x`` and the gate are the caller's).
Everything here keeps the state TRANSPOSED from the published ``[Ci, N]``:
``[N, Ci]``, the channels on the lanes and the ``N`` = 16 state indices on
the sublanes, so that a slot's state costs the bytes it holds (16 on the
lanes would pad to 128) and ``x``, ``delta`` and ``y`` rows are lane
vectors. Three forms of the same numbers:

- :func:`ssm_recurrence`: one token at a time under ``lax.scan``; what the
  other two are held to, and the portable path.
- :func:`ssm_scan` (prefill): a Mosaic kernel over a chunk's tokens with
  the state held in VMEM from the first token to the last, blocked over
  ``CHANNELS`` channels: HBM sees ``x``, ``delta``, ``B``, ``C`` once in
  and ``y`` once out, and the state once in and once out. (Formed outside,
  ``exp(delta A)`` and the state after every token are ``[T, N, Ci]``
  float32 each: 168 MB a layer for 512 tokens of 5,120 channels.) A token
  that must leave the state alone (chunk padding) comes with ``delta = 0``.
  Its time goes to the vector unit: ~7 operations on ``N x Ci`` values a
  token, one after the other in time.
- :func:`ssm_step` (decode): one token for each ACTIVE slot, a Mosaic
  kernel that reads and rewrites those slots' state IN PLACE in the flat
  ``[layers * slots, N, Ci]`` buffer and never touches another slot's.
  Memory-bound: 2 x ``N Ci`` x 4 bytes a slot.

``B`` and ``C`` reach the kernels with each value on all 128 lanes of a
row (:func:`lanes`): a column ``[N, 1]`` to spread over the lanes inside
the kernel would be a relayout a token.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CHANNELS = 512      # a grid step's channels: the state is 8 vregs of them
TOKENS = 128        # a grid step's tokens
LANES = 128


def ssm_recurrence(x, delta, A, B, C, h0):
    """x, delta ``[T, Ci]``, A ``[N, Ci]``, B, C ``[T, N]``, h0 ``[N,
    Ci]``, all float32. Returns (y ``[T, Ci]``, the state after the last
    token)."""
    def step(h, t):
        xt, dt, bt, ct = t
        h = jnp.exp(dt[None, :] * A) * h + (dt * xt)[None, :] * bt[:, None]
        return h, jnp.sum(ct[:, None] * h, axis=0)
    h, y = jax.lax.scan(step, h0, (x, delta, B, C))
    return y, h


def lanes(v, width: int = LANES):
    """``[..., N]`` -> ``[..., N, width]`` float32, each value on every
    lane of its row."""
    return jnp.broadcast_to(v.astype(jnp.float32)[..., None],
                            v.shape + (width,))


def _spread(rows, reps: int):
    """``[N, lanes]`` -> ``[N, reps * lanes]``: whole tiles side by side."""
    return rows if reps == 1 else jnp.concatenate([rows] * reps, axis=1)


def _scan_kernel(a_ref, h0_ref, x_ref, d_ref, b_ref, c_ref, y_ref, h_ref,
                 s_ref):
    """Grid step (i, j): tokens ``[i tt, (i + 1) tt)`` of channel block
    ``j``. a_ref / h0_ref / h_ref ``[N, cb]``; x_ref / d_ref / y_ref ``[tt,
    cb]``; b_ref / c_ref ``[tt, N, lanes]``; s_ref ``[blocks, N, cb]``: every
    block's state between its token steps."""
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when(i == 0)
    def _first():
        s_ref[j] = h0_ref[...]

    A = a_ref[...]
    reps = A.shape[1] // b_ref.shape[-1]

    def rows(g, h):
        r = pl.multiple_of(g * 8, 8)
        x8, d8 = x_ref[pl.ds(r, 8), :], d_ref[pl.ds(r, 8), :]
        ys = []
        for k in range(8):
            d = d8[k:k + 1]
            h = jnp.exp(d * A) * h \
                + (d * x8[k:k + 1]) * _spread(b_ref[r + k], reps)
            ys.append(jnp.sum(h * _spread(c_ref[r + k], reps), axis=0,
                              keepdims=True))
        y_ref[pl.ds(r, 8), :] = jnp.concatenate(ys, axis=0)
        return h

    h = jax.lax.fori_loop(0, x_ref.shape[0] // 8, rows, s_ref[j])
    s_ref[j] = h
    # the last token step's write is the one that stays
    h_ref[...] = h


def ssm_scan(x, delta, A, B, C, h0, *, interpret: bool = False):
    """:func:`ssm_recurrence` over a chunk, same arguments and results,
    the state in VMEM throughout. ``T`` is padded here to whole groups of 8
    tokens (``delta = 0``: the state passes through)."""
    T, Ci = x.shape
    N = A.shape[0]
    cb = CHANNELS if Ci % CHANNELS == 0 else Ci
    tt = min(TOKENS, -(-T // 8) * 8)
    Tp = -(-T // tt) * tt
    w = min(LANES, cb)
    f32 = jnp.float32

    def pad(a):
        return jnp.pad(a.astype(f32), ((0, Tp - T),) + ((0, 0),) * (a.ndim - 1))
    token = pl.BlockSpec((tt, cb), lambda i, j: (i, j))
    block = pl.BlockSpec((N, cb), lambda i, j: (0, j))
    row = pl.BlockSpec((tt, N, w), lambda i, j: (i, 0, 0))
    y, h = pl.pallas_call(
        _scan_kernel, name="ssm_scan", grid=(Tp // tt, Ci // cb),
        in_specs=[block, block, token, token, row, row],
        out_specs=[token, block],
        out_shape=[jax.ShapeDtypeStruct((Tp, Ci), f32),
                   jax.ShapeDtypeStruct((N, Ci), f32)],
        scratch_shapes=[pltpu.VMEM((Ci // cb, N, cb), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        **({"interpret": True} if interpret else {}),
    )(A.astype(f32), h0.astype(f32), pad(x), pad(delta), lanes(pad(B), w),
      lanes(pad(C), w))
    return y[:T], h


def _step_kernel(state_ids, rows, count, a_ref, s_ref, xd_ref, bc_ref,
                 so_ref, y_ref, *, cb: int):
    """One grid step: the slot ``rows[i]``. s_ref / so_ref ``[1, N, Ci]``
    (the SAME buffer in HBM); a_ref ``[N, Ci]``; xd_ref ``[1, 2, Ci]``: rows
    x, delta; bc_ref ``[1, 2 N, lanes]``: B's rows, then C's; y_ref ``[1, 1,
    Ci]``."""
    i = pl.program_id(0)
    n = count[0]

    @pl.when(i < n)
    def _live():
        N, Ci = a_ref.shape
        reps = cb // bc_ref.shape[-1]
        b = _spread(bc_ref[0, 0:N, :], reps)
        c = _spread(bc_ref[0, N:2 * N, :], reps)
        for c0 in range(0, Ci, cb):
            at = slice(c0, c0 + cb)
            d = xd_ref[0, 1:2, at]
            h = jnp.exp(d * a_ref[:, at]) * s_ref[0, :, at] \
                + (d * xd_ref[0, 0:1, at]) * b
            so_ref[0, :, at] = h
            y_ref[0, :, at] = jnp.sum(h * c, axis=0, keepdims=True)

    @pl.when(n == 0)
    def _idle():
        # the one block every step then maps to goes back as it came
        so_ref[...] = s_ref[...]
        y_ref[...] = jnp.zeros_like(y_ref)


def ssm_step(state, A, xd, bc, state_ids, rows, count, *,
             interpret: bool = False):
    """One token for each active slot, the state rewritten in place.
    ``state`` ``[S, N, Ci]`` float32 (every layer's slots); ``A`` ``[N,
    Ci]``; ``xd`` ``[B, 2, Ci]`` and ``bc`` ``[B, 2 N, lanes]`` float32
    (:func:`pack_step`); ``rows`` ``[B]``: the batch rows, those that
    decode FIRST; ``state_ids`` ``[B]``: where row ``rows[i]``'s state lies
    in ``state``; ``count`` ``[1]``: how many decode. Returns (state, y
    ``[B, Ci]``: rows that do not decode hold whatever was there). Call it
    under ``jax.jit`` with ``state`` donated."""
    S, N, Ci = state.shape
    B = xd.shape[0]
    w = bc.shape[-1]
    assert xd.shape == (B, 2, Ci) and bc.shape == (B, 2 * N, w), \
        (state.shape, xd.shape, bc.shape)
    cb = CHANNELS if Ci % CHANNELS == 0 else Ci

    def live(i, count):
        # a step past the last live row stays on that row's block: nothing
        # is fetched for it and nothing written back
        return jnp.minimum(i, jnp.maximum(count[0] - 1, 0))

    def smap(i, state_ids, rows, count):
        return (state_ids[live(i, count)], 0, 0)

    def rmap(i, state_ids, rows, count):
        return (rows[live(i, count)], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3, grid=(B,),
        in_specs=[pl.BlockSpec((N, Ci), lambda i, *_: (0, 0)),
                  pl.BlockSpec((1, N, Ci), smap),
                  pl.BlockSpec((1, 2, Ci), rmap),
                  pl.BlockSpec((1, 2 * N, w), rmap)],
        out_specs=[pl.BlockSpec((1, N, Ci), smap),
                   pl.BlockSpec((1, 1, Ci), rmap)])
    state, y = pl.pallas_call(
        functools.partial(_step_kernel, cb=cb), name="ssm_step",
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((B, 1, Ci), jnp.float32)],
        # operand 4 (behind the three prefetched scalars and A) is the state
        input_output_aliases={4: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        **({"interpret": True} if interpret else {}),
    )(state_ids, rows, count, A.astype(jnp.float32), state, xd, bc)
    return state, y[:, 0]


def pack_step(x, delta, B, C):
    """The step kernel's operands: x, delta ``[B, Ci]`` and B, C ``[B, N]``
    -> (``[B, 2, Ci]``, ``[B, 2 N, lanes]``) float32."""
    f32 = jnp.float32
    w = min(LANES, x.shape[-1])
    return (jnp.stack([x.astype(f32), delta.astype(f32)], axis=1),
            jnp.concatenate([lanes(B, w), lanes(C, w)], axis=1))


def ssm_step_reference(state, A, x, delta, B, C, base, active):
    """The portable decode step: the same rewrite of ``state`` ``[S, N,
    Ci]`` at rows ``base .. base + B`` by one step of
    :func:`ssm_recurrence` a slot, the idle slots' rows put back as they
    were."""
    n = x.shape[0]
    s0 = jax.lax.dynamic_slice_in_dim(state, base, n)

    def one(s, x, d, b, c):
        y, s = ssm_recurrence(x[None], d[None], A, b[None], c[None], s)
        return y[0], s
    y, s = jax.vmap(one)(s0, x, delta, B, C)
    s = jnp.where(active[:, None, None], s, s0)
    return jax.lax.dynamic_update_slice_in_dim(state, s, base, 0), y
