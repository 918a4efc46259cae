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

A FOURTH form, of the scalar rule only: with ``impl`` "pallas" and heads of
128 :func:`gdn_chunk` is ONE Mosaic kernel a call
(:func:`_gdn_chunk_kernel`). Grid (groups of 16 value heads) x (the chunk's
sub-chunks of 64, in sequence); the group's float32 state is the kernel's
output block, loaded from ``s0`` at the first sub-chunk, resident in VMEM
across them and written back once; a grid step reads its sub-chunk's q, k
(per KEY head, unrepeated), v rows and its g and b, and writes its ``o``:
no ``[n, H, c, ...]`` intermediate exists in HBM. Two heads' ``[64, 64]``
matrices lie side by side on the 128 lanes and multiply the block-diagonal
of the other operand, so every product of the solve fills the matrix unit.
``G`` is a product with the lower-triangular ones; the unit-triangular
inverse is built by merging diagonal blocks pairwise from single rows up to
64 (:func:`_inverse_unit_lower`: NO Neumann series, so nothing grows on a
run of identical keys) and applied in one product, ``W = (I + L)^-1 (V - K~
S)``; every product keeps float32 operands at the highest precision.
:func:`kda_chunk` is still XLA: its pair stage is ``[64, 64, 128]`` work a
head on the vector unit, a kernel of its own that :func:`_pairs_tail` is
written to follow (the decay broadcasts from a column as from a row of
lanes a token).
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


def kda_chunk(q, k, v, g, b, s0, sub: int = 64, *, impl=None):
    """The chunkwise-parallel form of :func:`kda_recurrence`, same
    arguments and results. A token that must leave the state alone (chunk
    padding) comes with ``g = 0`` and ``b = 0``. Plain XLA under every
    ``impl``: the vector decay's pair stage has no kernel yet
    (:func:`gdn_chunk` has)."""
    del impl
    return _chunk_form(q, k, v, g, b, s0, sub, _kda_pairs, "kda_chunk")


def gdn_recurrence(q, k, v, g, b, s0):
    """:func:`kda_recurrence` with ONE decay a head a token, ``g`` ``[T,
    H]`` (Gated DeltaNet; models/qwen3_next.py): what :func:`gdn_chunk`
    and the step kernel fed that decay on every lane are held to."""
    return kda_recurrence(q, k, v, jnp.broadcast_to(g[..., None], q.shape),
                          b, s0)


# value heads a grid step of the chunk kernel: 8 pairs go through each stage
# together (the census' sweep: 0.50 / 0.34 / 0.29 / 0.27 ms at 2 / 4 / 8 / 16)
CHUNK_HEADS = 16
CHUNK_SUB = 64          # two heads' [64, 64] matrices fill a row of 128 lanes


def chunk_form(impl, H: int, K: int, V: int, sub: int = CHUNK_SUB,
               key_heads=None) -> str:
    """Which implementation :func:`gdn_chunk` takes for these shapes:
    "mosaic" (the kernel: ``impl`` "pallas", both head sizes the 128 lanes,
    sub-chunks of 64, the value heads in pairs that have a key head each or
    share one) or "xla" (:func:`_chunk_form`)."""
    rep = H // (key_heads or H)
    served = impl == "pallas" and K == V == 128 and sub == CHUNK_SUB \
        and H % 2 == 0 \
        and (rep == 1 or rep % 2 == 0 and _chunk_heads(H) % rep == 0)
    return "mosaic" if served else "xla"


def _chunk_heads(H: int) -> int:
    """Value heads a grid step: the most, up to ``CHUNK_HEADS``, that
    divide an even ``H``."""
    return next(hb for hb in (16, 8, 4, 2)
                if hb <= CHUNK_HEADS and H % hb == 0)


def gdn_chunk(q, k, v, g, b, s0, sub: int = CHUNK_SUB, *, impl=None,
              interpret: bool = False):
    """The chunkwise-parallel form of :func:`gdn_recurrence`: the algebra
    of :func:`kda_chunk` with the pair decays formed as ``[c, c]`` scalars,
    under the scope ``gdn_chunk``. q and k may come with FEWER heads than
    v, ``[T, Hk, K]``: value head ``h`` reads key head ``h // (H / Hk)``
    (Gated DeltaNet's grouped keys, unrepeated). With ``impl`` "pallas"
    and shapes the kernel takes (:func:`chunk_form`) ONE Mosaic kernel
    (:func:`_gdn_chunk_kernel`); else plain XLA (:func:`_gdn_pairs` and
    :func:`_chunk_form`), what the kernel is held to."""
    Hk, K = q.shape[1:]
    H, V = v.shape[1:]
    if chunk_form(impl, H, K, V, sub, Hk) == "xla":
        q, k = (per_value_head(a, H) for a in (q, k))
        return _chunk_form(q, k, v, g[..., None], b, s0, sub, _gdn_pairs,
                           "gdn_chunk")
    with jax.named_scope("gdn_chunk"):
        return _gdn_chunk_call(q, k, v, g, b, s0, interpret)


def per_value_head(a, H: int):
    """q or k ``[..., Hk, K]`` -> ``[..., H, K]``: a key head repeated onto
    the consecutive value heads it serves; with a head each, ``a``."""
    return a if a.shape[-2] == H else jnp.repeat(a, H // a.shape[-2], -2)


def _mm(a, x, dims=((1,), (0,))):
    return jax.lax.dot_general(a, x, (dims, ((), ())), precision=HIGHEST,
                               preferred_element_type=jnp.float32)


def _blocks(x, left):
    """``[c, 2 c]`` (two heads' ``[c, c]`` matrices side by side on the
    lanes) -> the ``[2 c, 2 c]`` block-diagonal matrix of the two."""
    return jnp.concatenate([jnp.where(left, x, 0.0),
                            jnp.where(left, 0.0, x)], axis=0)


def _inverse_unit_lower(lows, row, col, left):
    """``(I + L)^-1`` for STRICTLY lower-triangular ``L``, two heads' side
    by side (each of ``lows`` ``[c, 2 c]``; ``row`` / ``col``: an entry's
    row and its column inside its head), by merging diagonal blocks
    pairwise, ``inv([[A, 0], [C, B]]) = [[A^-1, 0], [-B^-1 C A^-1,
    B^-1]]``, from single rows (whose inverse is 1) up to ``c``: no Neumann
    series at all, so no term is larger than the inverses' own entries (the
    run of identical keys for which :func:`_solve_unit_lower` stops its
    series at 8 rows). Every product is ``[c, 2 c] x [2 c, 2 c]``: both
    heads in one pass of the matrix unit's 128 x 128. The pairs of heads
    go level by level together: a level's two products depend on each
    other, the pairs' do not."""
    c = lows[0].shape[0]
    eye = jnp.where(row == col, 1.0, 0.0)
    invs = [eye] * len(lows)
    shift = 0                                   # blocks of m = 2^shift rows
    while 1 << shift < c:
        # below the diagonal blocks of m rows, inside those of 2 m
        at = (row >> shift + 1 == col >> shift + 1) \
            & (row >> shift != col >> shift)
        below = [jnp.where(at, low, 0.0) for low in lows]
        if shift == 0:      # blocks of one row: A^-1 = B^-1 = 1, no product
            invs = [eye - x for x in below]
        else:
            # -B^-1 C A^-1 fills the lower rows of a block of 2 m alone:
            # where those are whole tiles of 8 sublanes, only they pass
            # through the matrix unit
            m = 1 << shift
            tiles = m % 8 == 0
            part = [jnp.concatenate([inv[i + m:i + 2 * m]
                                     for i in range(0, c, 2 * m)], axis=0)
                    for inv in invs] if tiles else invs
            half = [_mm(x, _blocks(y, left)) for x, y in zip(part, below)]
            part = [x - _mm(y, _blocks(inv, left))
                    for x, y, inv in zip(part, half, invs)]
            invs = [jnp.concatenate(
                [y for i in range(0, c, 2 * m)
                 for y in (inv[i:i + m], x[i // 2:i // 2 + m])], axis=0)
                for inv, x in zip(invs, part)] if tiles else part
        shift += 1
    return invs


def _gdn_pair_stage(x, shared: bool, row, col, left):
    """``A`` and ``P`` of two heads side by side, ``[c, 2 c]`` each, for
    every pair of ``x`` (:func:`_pairs_tail`'s): the pair decay ``exp(G_i -
    G_j)`` is formed per pair under the mask, as :func:`_gdn_pairs` forms
    it. ``shared``: the two heads read ONE key head (q and k hold its rows
    twice), so one head's rows against both copies of the keys give ``K
    K^T`` and ``Q K^T`` on both halves of the lanes; else the product of
    the stacked rows also holds the two heads' cross terms, which the
    matrix unit's 128 columns carry anyway, and they are dropped."""
    c = row.shape[0]
    # [K; Q] K^T: one latch of the keys for both
    kq = [_mm(jnp.concatenate([k[:c], q[:c]] if shared else [k, q], axis=0),
              k, ((1,), (1,))) for q, k, _, _, _ in x]
    A, P = [], []
    for (_, _, _, G, _), kq in zip(x, kq):
        if shared:
            kk, qk = kq[:c], kq[c:]
        else:
            kk = jnp.where(left, kq[:c], kq[c:2 * c])
            qk = jnp.where(left, kq[2 * c:3 * c], kq[3 * c:])
        # a token's running log-decay on its head's lanes, and as a row:
        # entry [j, lane of j] of each head, the same numbers
        G = jnp.where(left, G[:c], G[c:])
        Gj = jnp.sum(jnp.where(row == col, G, 0.0), axis=0, keepdims=True)
        D = jnp.exp(jnp.where(row >= col, G - Gj, -jnp.inf))
        A.append(kk * D)
        P.append(jnp.where(row >= col, qk * D, 0.0))
    return A, P


def _pairs_tail(x, A, P, s, row, col, left):
    """Everything behind ``A`` and ``P`` for a sub-chunk of several pairs
    of heads, their states resident; per pair: ``x`` = (q, k ``[2 c, Dk]``,
    v ``[2 c, Dv]`` the two heads' rows stacked, ``G`` ``[2 c, 1]`` the
    running log-decay (a decay a key channel would come ``[2 c, Dk]`` and
    broadcast the same way), ``b`` ``[2 c, 1]``), ``A``, ``P`` ``[c, 2 c]``
    side by side, ``s`` the pair's two states ``[Dv, Dk]``. Returns per
    pair (o ``[2 c, Dv]``, the two states after)."""
    c = A[0].shape[0]
    halves = (slice(0, c), slice(c, 2 * c))
    # b as a row, a column of A a token written
    lows = [jnp.where(row > col, A * jnp.sum(
        jnp.where(row == col, jnp.where(left, b[:c], b[c:]), 0.0), axis=0,
        keepdims=True), 0.0) for (_, _, _, _, b), A in zip(x, A)]
    invs = _inverse_unit_lower(lows, row, col, left)
    # stage by stage over the pairs, as the inverse: what follows one
    # product in the program's text does not wait for it
    eG = [jnp.exp(G) for _, _, _, G, _ in x]
    # [K~; Q~] S: what the state holds for the keys, and reads for q
    ks = [[_mm(jnp.concatenate([k[h] * e[h], q[h] * e[h]], axis=0), s[i],
               ((1,), (1,))) for i, h in enumerate(halves)]
          for (q, k, _, _, _), e, s in zip(x, eG, s)]
    # (I + tril(A) Diag(b)) W = V - K~ S
    wb = [_mm(_blocks(inv, left),
              v - jnp.concatenate([y[:c] for y in ks], axis=0)) * b
          for (_, _, v, _, b), inv, ks in zip(x, invs, ks)]
    o = [jnp.concatenate([y[c:] for y in ks], axis=0)
         + _mm(_blocks(P, left), wb) for ks, P, wb in zip(ks, P, wb)]
    after = []
    for (_, k, _, G, _), wb, s in zip(x, wb, s):
        pair = []
        for i, h in enumerate(halves):
            # on the lanes first: Mosaic broadcasts a [1, 1] along one axis
            Gk = G[h] + jnp.zeros_like(k[h])
            last = Gk[c - 1:c]                             # [1, Dk]
            pair.append(s[i] * jnp.exp(last) + _mm(
                wb[h], k[h] * jnp.exp(last - Gk), ((0,), (0,))))
        after.append(pair)
    return list(zip(o, after))


def _gdn_chunk_kernel(q_ref, k_ref, v_ref, gb_ref, s0_ref, o_ref, s_ref, *,
                      heads: int, rep: int):
    """One grid step: ``heads`` value heads' sub-chunk of ``c`` tokens.
    v_ref, o_ref ``[c, heads * D]`` (a head's rows are 128 lanes of the
    ``[T, H * D]`` array as it lies), q_ref, k_ref ``[c, heads / rep * D]``
    (``rep`` value heads read one key head); gb_ref ``[c, 128]``: g on
    lanes ``0 .. heads``, b on lanes ``64 .. 64 + heads``; s0_ref / s_ref
    ``[heads, Dv, Dk]``: the output block is the same for every sub-chunk
    of the head group, so the state stays in VMEM from the first (loaded
    from s0) to the last (written back once)."""
    @pl.when(pl.program_id(1) == 0)
    def _first():
        s_ref[...] = s0_ref[...]

    c = v_ref.shape[0]
    D = v_ref.shape[1] // heads
    row = jax.lax.broadcasted_iota(jnp.int32, (c, 2 * c), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (c, 2 * c), 1)
    left = lane < c
    col = lane & (c - 1)
    gb = gb_ref[...]
    # the running sum of g down the sub-chunk, every head at once: a product
    # with the lower-triangular ones (the ones are exact in every pass)
    Gs = _mm(jnp.where(jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
                       >= jax.lax.broadcasted_iota(jnp.int32, (c, c), 1),
                       1.0, 0.0), gb)

    def rows(ref, h, rep=1):
        return jnp.concatenate(
            [ref[:, (h + i) // rep * D:((h + i) // rep + 1) * D]
             .astype(jnp.float32) for i in range(2)], axis=0)

    def column(x, at):
        return jnp.concatenate([x[:, at + i:at + i + 1] for i in range(2)],
                               axis=0)
    pairs = range(0, heads, 2)
    x = [(rows(q_ref, h, rep), rows(k_ref, h, rep), rows(v_ref, h),
          column(Gs, h), column(gb, 64 + h)) for h in pairs]
    A, P = _gdn_pair_stage(x, rep % 2 == 0, row, col, left)
    out = _pairs_tail(x, A, P, [(s_ref[h], s_ref[h + 1]) for h in pairs],
                      row, col, left)
    for h, (o, s) in zip(pairs, out):
        for i in range(2):
            o_ref[:, (h + i) * D:(h + i + 1) * D] = o[i * c:(i + 1) * c]
            s_ref[h + i] = s[i]


def _gdn_chunk_call(q, k, v, g, b, s0, interpret):
    """q, k ``[T, Hk, 128]``, v ``[T, H, 128]``, g, b ``[T, H]``, s0 ``[H,
    128, 128]`` -> (o ``[T, H, 128]`` float32, the state after). Grid:
    (groups of heads) x (sub-chunks, in sequence); nothing but the operands
    and the results touches HBM."""
    T, H, D = v.shape
    c = CHUNK_SUB
    hb, rep = _chunk_heads(H), H // q.shape[1]
    n = -(-T // c)

    def flat(x):
        return jnp.pad(x.reshape(T, -1), ((0, n * c - T), (0, 0)))

    def lanes(x):
        """[T, H] -> [H / hb, n c, 64]: a group's heads on the first lanes,
        zeros behind them and behind the last token (a padding token: g =
        0, b = 0)."""
        x = jnp.pad(x.astype(jnp.float32), ((0, n * c - T), (0, 0)))
        return jnp.pad(jnp.moveaxis(x.reshape(n * c, H // hb, hb), 1, 0),
                       ((0, 0), (0, 0), (0, 64 - hb)))
    gb = jnp.concatenate([lanes(g), lanes(b)], axis=-1)
    wide = pl.BlockSpec((c, hb * D), lambda j, i: (i, j))
    keys = pl.BlockSpec((c, hb // rep * D), lambda j, i: (i, j))
    state = pl.BlockSpec((hb, D, D), lambda j, i: (j, 0, 0))
    o, s = pl.pallas_call(
        functools.partial(_gdn_chunk_kernel, heads=hb, rep=rep),
        name="gdn_chunk", grid=(H // hb, n),
        in_specs=[keys, keys, wide,
                  pl.BlockSpec((None, c, 128), lambda j, i: (j, i, 0)),
                  state],
        out_specs=[wide, state],
        out_shape=[jax.ShapeDtypeStruct((n * c, H * D), jnp.float32),
                   jax.ShapeDtypeStruct((H, D, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        **({"interpret": True} if interpret else {}),
    )(flat(q), flat(k), flat(v), gb, s0.astype(jnp.float32))
    return o[:T].reshape(T, H, D), s


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
