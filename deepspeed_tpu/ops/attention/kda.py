"""Gated delta-rule linear attention with a decay per key channel (KDA;
models/kimi_linear.py): the recurrence, per head,

    S_t = (I - b_t k_t k_t^T) Diag(a_t) S_{t-1} + b_t k_t v_t^T,
    o_t = S_t^T q_t,           a_t = exp(g_t) in (0, 1]^Dk, b_t in (0, 1)

on a float32 state of ``[Dk keys, Dv values]`` a head. Everything here
keeps the state TRANSPOSED, ``[H, Dv, Dk]``: the key channels lie on the
lanes, so the decay, ``q`` and ``k`` are lane vectors and the decay scales
the state without a relayout. Three forms of the same numbers:

- :func:`kda_recurrence`: one token at a time under ``lax.scan``; what the
  other two are held to, and the portable decode step.
- :func:`kda_chunk` (prefill): the chunkwise-parallel form over sub-chunks
  of ``sub`` tokens. With ``G_i`` the decay's running log-sum inside a
  sub-chunk, ``w_i = v_i - S'_i^T k_i`` (what the delta rule writes) obeys
  ``(I + tril(A) Diag(b)) W = V - K~ S_0``, ``A_ij = sum_c k_ic k_jc
  exp(G_ic - G_jc)``, ``K~ = k exp(G)``: ONE unit-triangular solve per
  sub-chunk and head (:func:`_solve_unit_lower`: matrix products),
  independent of the state, so all sub-chunks solve at once and only ``W =
  U - M S_0``, the outputs ``Q~ S_0 + tril(P) Diag(b)
  W`` and ``S = Diag(exp G_c) S_0 + K-^T Diag(b) W`` run in sequence, all
  matmuls. ``exp(G_i - G_j)`` is formed per PAIR (never ``exp(-G_j)``,
  which overflows under a strong decay), every other factor is at most 1.
  Plain XLA under the scope name ``kda_chunk``, float32 at the highest
  matmul precision: the state is float32 and a one-pass product would
  round what enters it to bfloat16.
- :func:`kda_step` (decode): one token for each ACTIVE slot, a Mosaic
  kernel that reads and rewrites those slots' state IN PLACE in the flat
  ``[layers * slots, H, Dv, Dk]`` buffer and never touches another slot's.
  Memory-bound: 2 x 64 KiB a head a slot against ~0.1 MFLOP.

The same rule with ONE decay a head a token, ``a_t`` a scalar (Gated
DeltaNet; models/qwen3_next.py), shares the state's layout, the recurrence
(:func:`gdn_recurrence`) and the step kernel (the packed rows carry the
decay as a row of 128 lanes a head: the scalar fills it), and has a chunk
form of its own, :func:`gdn_chunk`: the pair decay ``exp(G_i - G_j)`` is
then ``[c, c]`` a head, so ``A = (K K^T) * D`` and ``P = (Q K^T) * D`` are
matrix products and a mask (:func:`_gdn_pairs`) where KDA's pairs are
formed a key channel at a time on the vector unit (:func:`_kda_pairs`);
everything behind ``A`` and ``P`` is one function, :func:`_chunk_form`.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

HIGHEST = jax.lax.Precision.HIGHEST
HEADS_PER_STEP = 16     # 1 MiB of state a grid step, in and out double-buffered


def kda_recurrence(q, k, v, g, b, s0):
    """q, k, g ``[T, H, Dk]``, v ``[T, H, Dv]``, b ``[T, H]``, all float32
    (q and k as they enter the rule: normalised, q scaled); ``s0`` ``[H,
    Dv, Dk]``. Returns (o ``[T, H, Dv]``, the state after the last
    token)."""
    def step(s, t):
        qt, kt, vt, gt, bt = t
        s = s * jnp.exp(gt)[:, None, :]
        w = vt - jnp.einsum("hvk,hk->hv", s, kt, precision=HIGHEST)
        s = s + (bt[:, None] * w)[:, :, None] * kt[:, None, :]
        return s, jnp.einsum("hvk,hk->hv", s, qt, precision=HIGHEST)
    s, o = jax.lax.scan(step, s0, (q, k, v, g, b))
    return o, s


def _kda_pairs(q, k, G, seen):
    """KDA's ``A`` (keys against keys) and ``P`` (queries against keys)
    ``[n, H, c, c]``: the decay between each pair is a VECTOR over the key
    channels, so each pair's product is formed on the vector unit, a
    sub-chunk at a time (``[H, c, c, K]`` alive at once)."""
    def pairs(x):
        qs, ks, Gs = x
        E = jnp.exp(jnp.where(seen[None, :, :, None],
                              Gs[:, :, None] - Gs[:, None], -jnp.inf))
        kd = ks[:, None] * E                           # [H, i, j, K]
        return (jnp.sum(ks[:, :, None] * kd, -1),
                jnp.sum(qs[:, :, None] * kd, -1))
    return jax.lax.map(pairs, (q, k, G))


def _gdn_pairs(q, k, G, seen):
    """The same with ONE decay a head a token (``G`` ``[n, H, c, 1]``): the
    pair decay ``exp(G_i - G_j)`` is a scalar, ``[n, H, c, c]``, so ``A =
    (K K^T) * D`` and ``P = (Q K^T) * D`` are two matrix products and a
    mask; no ``[H, c, c, K]`` temporary exists."""
    Gs = G[..., 0]
    D = jnp.exp(jnp.where(seen, Gs[..., :, None] - Gs[..., None, :],
                          -jnp.inf))
    return (jnp.einsum("nhik,nhjk->nhij", k, k, precision=HIGHEST) * D,
            jnp.einsum("nhik,nhjk->nhij", q, k, precision=HIGHEST) * D)


SOLVE_BLOCK = 8


def _solve_unit_lower(L, rhs):
    """``X`` with ``(I + L) X = rhs`` for STRICTLY lower-triangular ``L``
    ``[..., c, c]`` and ``rhs`` ``[..., c, w]``, as matrix products: blocks
    of ``SOLVE_BLOCK`` rows, each diagonal block inverted by its Neumann
    series (``L_ii`` is nilpotent: ``(I + L_ii)^-1 = prod_j (I +
    (-L_ii)^(2^j))``, two squarings for 8 rows) and the blocks below it by forward
    substitution. ``lax.linalg.triangular_solve`` walks the ``c`` rows one
    after the other in a custom call: 0.69 ms a layer a 512-token chunk on a
    v5e, 16% of a prefill-heavy window (PERF.md, PR 54). The series is kept
    to 8 rows because its terms can be large where the result is not: for a
    run of identical unit keys ``L`` is all ones, ``L^4`` reaches 35 at 8
    rows and ``L^8`` 6,435 at 16, where the outputs then lose three digits
    (tests/test_qwen3_next.py holds that case to the recurrence)."""
    c = L.shape[-1]
    bl = SOLVE_BLOCK if c % SOLVE_BLOCK == 0 else c
    nb = c // bl

    def mm(a, x):
        return jnp.einsum("...ij,...jk->...ik", a, x, precision=HIGHEST)

    def rows(x, i):
        return x[..., i * bl:(i + 1) * bl, :]
    neg = jnp.stack([-rows(L, i)[..., i * bl:(i + 1) * bl]
                     for i in range(nb)], axis=-3)         # [..., nb, bl, bl]
    inv, power, reach = jnp.eye(bl) + neg, neg, 2
    while reach < bl:
        power = mm(power, power)
        inv = inv + mm(inv, power)
        reach *= 2
    out = []
    for i in range(nb):
        r = rows(rhs, i)
        if i:
            r = r - mm(rows(L, i)[..., :i * bl], jnp.concatenate(out, -2))
        out.append(mm(inv[..., i, :, :], r))
    return jnp.concatenate(out, axis=-2)


def _chunk_form(q, k, v, g, b, s0, sub, pairs, scope):
    """What the two delta rules' chunk forms share (the module docstring's
    algebra): ``g`` ``[T, H, K]`` or ``[T, H, 1]``, and ``pairs`` the rule's
    way to ``A`` and ``P``; every other factor broadcasts."""
    T, H, K = q.shape
    V = v.shape[-1]
    n = -(-T // sub)
    with jax.named_scope(scope):
        def cut(x):
            """[T, H, ...] -> [n, H, sub, ...], zeros behind the last."""
            x = jnp.pad(x.astype(jnp.float32),
                        ((0, n * sub - T),) + ((0, 0),) * (x.ndim - 1))
            return jnp.moveaxis(x.reshape((n, sub) + x.shape[1:]), 1, 2)
        q, k, v, g, b = (cut(x) for x in (q, k, v, g, b[..., None]))
        G = jnp.cumsum(g, axis=2)                          # [n, H, c, K|1]
        i = jnp.arange(sub)
        seen = i[:, None] >= i[None, :]                    # key j <= query i
        A, P = pairs(q, k, G, seen)
        P = jnp.where(seen, P, 0.0)
        # (I + tril(A, -1) Diag(b)) [U | M] = [V | K~]
        low = jnp.where(i[:, None] > i[None, :],
                        A * jnp.swapaxes(b, -1, -2), 0.0)
        kt = k * jnp.exp(G)
        sol = _solve_unit_lower(low, jnp.concatenate([v, kt], -1))
        U, M = sol[..., :V], sol[..., V:]
        qt = q * jnp.exp(G)
        last = G[:, :, -1:]                                # [n, H, 1, K|1]
        kbar = k * jnp.exp(last - G)

        def step(s, x):
            U, M, P, qt, kbar, b, last = x
            wb = (U - jnp.einsum("hck,hvk->hcv", M, s, precision=HIGHEST)) * b
            o = jnp.einsum("hck,hvk->hcv", qt, s, precision=HIGHEST) \
                + jnp.einsum("hij,hjv->hiv", P, wb, precision=HIGHEST)
            s = s * jnp.exp(last) \
                + jnp.einsum("hjk,hjv->hvk", kbar, wb, precision=HIGHEST)
            return s, o
        s, o = jax.lax.scan(step, s0.astype(jnp.float32),
                            (U, M, P, qt, kbar, b, last))
        o = jnp.moveaxis(o, 1, 2).reshape(n * sub, H, V)[:T]
    return o, s


def kda_chunk(q, k, v, g, b, s0, sub: int = 64):
    """The chunkwise-parallel form of :func:`kda_recurrence`, same
    arguments and results. A token that must leave the state alone (chunk
    padding) comes with ``g = 0`` and ``b = 0``."""
    return _chunk_form(q, k, v, g, b, s0, sub, _kda_pairs, "kda_chunk")


def gdn_recurrence(q, k, v, g, b, s0):
    """:func:`kda_recurrence` with ONE decay a head a token, ``g`` ``[T,
    H]`` (Gated DeltaNet; models/qwen3_next.py): what :func:`gdn_chunk`
    and the step kernel fed that decay on every lane are held to."""
    return kda_recurrence(q, k, v, jnp.broadcast_to(g[..., None], q.shape),
                          b, s0)


def gdn_chunk(q, k, v, g, b, s0, sub: int = 64):
    """The chunkwise-parallel form of :func:`gdn_recurrence`: the algebra
    of :func:`kda_chunk` with the pair decays formed as ``[c, c]`` scalars
    (:func:`_gdn_pairs`), under the scope ``gdn_chunk``."""
    return _chunk_form(q, k, v, g[..., None], b, s0, sub, _gdn_pairs,
                       "gdn_chunk")


def _step_kernel(state_ids, rows, count, s_ref, x_ref, so_ref, o_ref, *,
                 heads: int):
    """One grid step: ``heads`` heads of the slot ``rows[i]``. s_ref /
    so_ref ``[1, heads, Dv, Dk]`` (the SAME buffer in HBM); x_ref ``[1,
    heads, 8, Dk]``: rows q, k, a, v, b (on every lane), 0, 0, 0; o_ref
    ``[1, heads, Dv]``."""
    i = pl.program_id(1)
    n = count[0]

    @pl.when(i < n)
    def _live():
        D = s_ref.shape[-1]
        eye = jax.lax.broadcasted_iota(jnp.int32, (D, D), 0) \
            == jax.lax.broadcasted_iota(jnp.int32, (D, D), 1)
        for h in range(heads):
            s = s_ref[0, h]                                # [Dv, Dk]
            x = x_ref[0, h]                                # [8, Dk]
            q, k, a, v, b = (x[r:r + 1] for r in range(5))
            # row 0: (q a) . S, the decayed state read by q; row 1: (k a)
            # . S, what the state holds for k
            r = jax.lax.dot_general(
                x * a, s, (((1,), (1,)), ((), ())), precision=HIGHEST,
                preferred_element_type=jnp.float32)        # [8, Dv]
            w = v - r[1:2]
            kb = k * b
            o_ref[0, pl.ds(h, 1), :] = r[0:1] + jnp.sum(
                q * kb, axis=-1, keepdims=True) * w
            # w as a column, to scale k's row by it
            w_col = jnp.sum(jnp.where(eye, w, 0.0), axis=1, keepdims=True)
            so_ref[0, h] = s * a + w_col * kb

    @pl.when(n == 0)
    def _idle():
        # the one block every step then maps to goes back as it came
        so_ref[...] = s_ref[...]
        o_ref[...] = jnp.zeros_like(o_ref)


def kda_step(state, x, state_ids, rows, count, *, interpret: bool = False):
    """One token for each active slot, the state rewritten in place.
    ``state`` ``[N, H, Dv, Dk]`` float32 (every layer's slots); ``x`` ``[B,
    H, 8, Dk]`` float32 (:func:`pack_step`); ``rows`` ``[B]``: the batch
    rows, those that decode FIRST; ``state_ids`` ``[B]``: where row
    ``rows[i]``'s state lies in ``state``; ``count`` ``[1]``: how many
    decode. Returns (state, o ``[B, H, Dv]``: rows that do not decode hold
    whatever was there). Call it under ``jax.jit`` with ``state``
    donated."""
    N, H, Dv, Dk = state.shape
    B = x.shape[0]
    assert Dv == Dk and x.shape == (B, H, 8, Dk), (state.shape, x.shape)
    hb = HEADS_PER_STEP if H % HEADS_PER_STEP == 0 else H

    def live(i, count):
        # a step past the last live row stays on that row's block: nothing
        # is fetched for it and nothing written back
        return jnp.minimum(i, jnp.maximum(count[0] - 1, 0))

    def smap(j, i, state_ids, rows, count):
        return (state_ids[live(i, count)], j, 0, 0)

    def xmap(j, i, state_ids, rows, count):
        return (rows[live(i, count)], j, 0, 0)

    def omap(j, i, state_ids, rows, count):
        return (rows[live(i, count)], j, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3, grid=(H // hb, B),
        in_specs=[pl.BlockSpec((1, hb, Dv, Dk), smap),
                  pl.BlockSpec((1, hb, 8, Dk), xmap)],
        out_specs=[pl.BlockSpec((1, hb, Dv, Dk), smap),
                   pl.BlockSpec((1, hb, Dv), omap)])
    return pl.pallas_call(
        functools.partial(_step_kernel, heads=hb), name="kda_step",
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((B, H, Dv), jnp.float32)],
        # operand 3 (behind the three prefetched scalars) is the state
        input_output_aliases={3: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        **({"interpret": True} if interpret else {}),
    )(state_ids, rows, count, state, x)


def pack_step(q, k, g, v, b):
    """The kernel's operand: q, k, g, v ``[B, H, D]`` and b ``[B, H]``
    float32 -> ``[B, H, 8, D]`` rows q, k, exp(g), v, b, zeros."""
    rows = jnp.stack([q, k, jnp.exp(g), v,
                      jnp.broadcast_to(b[..., None], q.shape)], axis=2)
    return jnp.pad(rows.astype(jnp.float32), ((0, 0), (0, 0), (0, 3), (0, 0)))


def kda_step_reference(state, q, k, v, g, b, base, active):
    """The portable decode step: the same rewrite of ``state`` ``[N, H,
    Dv, Dk]`` at rows ``base .. base + B`` by one step of
    :func:`kda_recurrence` a slot, the idle slots' rows put back as they
    were."""
    B = q.shape[0]
    s0 = jax.lax.dynamic_slice_in_dim(state, base, B)

    def one(s, q, k, v, g, b):
        o, s = kda_recurrence(q[None], k[None], v[None], g[None], b[None], s)
        return o[0], s
    o, s = jax.vmap(one)(s0, q, k, v, g, b)
    s = jnp.where(active[:, None, None, None], s, s0)
    return jax.lax.dynamic_update_slice_in_dim(state, s, base, 0), o
