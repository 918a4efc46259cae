"""Paged-attention flash-decode kernel tests (tentpole:
ops/attention/paged.py): the kernel against its gather reference, its tile
walk and its work list. The impl switch through inference/engine.py and
inference/serving.py is tests/test_paged_serving.py.

The kernel runs in INTERPRET mode here (JAX_PLATFORMS=cpu, see
conftest.py) — same kernel body, Python-evaluated — so tier-1 exercises
the pallas path without a TPU. The gather path is the bit-reference:
kernel-level tests are allclose (the online softmax reassociates the
reduction)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.attention.paged import (STEP_BYTES, STEP_VIEWS,
                                               blocks_per_step, decode_plan,
                                               paged_decode_attention,
                                               paged_decode_reference,
                                               paged_verify_attention,
                                               paged_verify_reference,
                                               pool_row_bytes, tiles_run)

def _pool_problem(seed=0, B=3, Hkv=2, group=2, Dh=32, bs=8, NB=4):
    """Random pools ``[N, block, Hkv*Dh]`` (heads folded into the rows,
    as the paged cache stores them) + per-slot DISTINCT block tables
    (trash block 0 kept out of every table) + lengths hitting a partial
    block, a mid block and the last slot of the last block."""
    rng = np.random.default_rng(seed)
    N = B * NB + 1
    q = jnp.asarray(rng.normal(size=(B, Hkv, group, Dh)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(N, bs, Hkv * Dh)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(N, bs, Hkv * Dh)), jnp.float32)
    ids = rng.permutation(np.arange(1, N))
    tables = jnp.asarray(ids.reshape(B, NB), jnp.int32)
    lengths = jnp.asarray([bs // 2, bs * 2 + 1, bs * NB - 1], jnp.int32)
    return q, kp, vp, tables, lengths


# ---------------------------------------------------------------------------
# kernel unit tests (interpret mode — the tier-1 CPU smoke of the kernel)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("window", [None, 9])
def test_paged_kernel_matches_reference(devices, pallas_interpret, window):
    """Flash-decode through the block table == dense gathered softmax,
    at partial-block, mid-block and full-last-block lengths."""
    q, kp, vp, tables, lengths = _pool_problem()
    out = paged_decode_attention(q, kp, vp, tables, lengths,
                                 scale=q.shape[-1] ** -0.5, window=window)
    ref = paged_decode_reference(q, kp, vp, tables, lengths,
                                 scale=q.shape[-1] ** -0.5, window=window)
    assert out.shape == ref.shape == q.shape
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def test_paged_kernel_mha_single_group(devices, pallas_interpret):
    """group == H//Hkv == 1 (plain MHA) and group == H (MQA) both hit
    the packed-matmul path."""
    for Hkv, group in ((4, 1), (1, 4)):
        q, kp, vp, tables, lengths = _pool_problem(Hkv=Hkv, group=group)
        out = paged_decode_attention(q, kp, vp, tables, lengths, scale=0.25)
        ref = paged_decode_reference(q, kp, vp, tables, lengths, scale=0.25)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)


def test_paged_kernel_ignores_stale_blocks(devices, pallas_interpret):
    """Positions past lengths[b] never contribute: poisoning every pool
    slot beyond each slot's length (including whole table entries the
    clamped index_map re-reads) leaves the output bit-identical."""
    q, kp, vp, tables, lengths = _pool_problem()
    out = paged_decode_attention(q, kp, vp, tables, lengths, scale=0.25)
    bs = kp.shape[1]
    kp2, vp2 = np.asarray(kp).copy(), np.asarray(vp).copy()
    for b in range(tables.shape[0]):
        pos = int(lengths[b])
        for j in range(tables.shape[1]):
            bid = int(tables[b, j])
            for s in range(bs):
                if j * bs + s > pos:
                    kp2[bid, s] = 1e4
                    vp2[bid, s] = -1e4
    out2 = paged_decode_attention(q, jnp.asarray(kp2), jnp.asarray(vp2),
                                  tables, lengths, scale=0.25)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out2))


# the serving cells' head shapes (heads, kv heads, head size, table
# entries, window, block) with small pools, and a table whose length is
# prime
CELL_SHAPES = [
    pytest.param(25, 25, 64, 64, None, 16, id="gpt2-xl-table64"),
    pytest.param(64, 8, 128, 256, None, 16, id="kexaone-full-table256"),
    pytest.param(64, 8, 128, 9, 128, 16, id="kexaone-ring9-window128"),
    pytest.param(25, 25, 64, 13, None, 16, id="gpt2-xl-prime-table13"),
]
# large blocks, where the tile follows the row's bytes (float32 pools here:
# rows of 1,024 and of 256 bytes): 4 blocks of 128 and of 512 a step; a
# window ring and a table that do not divide by 4, a band that starts in
# the middle of a tile
BYTE_SHAPES = [
    pytest.param(8, 2, 128, 33, 4096, 128, id="block128-ring33-window4096"),
    pytest.param(8, 2, 128, 20, 700, 128, id="block128-table20-window700"),
    pytest.param(4, 1, 64, 6, None, 512, id="block512-table6-mqa"),
]
CELL_SHAPES += BYTE_SHAPES


def _row_bytes(Hkv, Dh):
    return Hkv * Dh * 4                 # float32 pools


def _edge_lengths(nb, bs, window, P):
    """Slot lengths at every edge of a block, a tile (``P`` blocks) and
    the table, and one whose band starts in the middle of a tile. A ring
    table's lengths are relative to its first block, so they stay within
    the ring and (the band being the caller's whole table) past nothing
    the window has dropped."""
    edges = [0, bs - 1, bs, P * bs - 1, P * bs, P * bs + 1, nb * bs - 1]
    if window is not None:
        edges += [window - 1, window, window + bs // 2,
                  window + (P // 2) * bs + bs // 2]
    return sorted({min(n, nb * bs - 1) for n in edges})


def _cell_problem(H, Hkv, Dh, nb, window, seed=0, bs=16):
    """A slot per edge length plus one whose unused table entries name
    the trash block 0 (as the paged cache leaves them); the other slots'
    entries past their length name blocks of their own, poisoned."""
    rng = np.random.default_rng(seed)
    lengths = _edge_lengths(
        nb, bs, window, blocks_per_step(nb, bs, _row_bytes(Hkv, Dh)))
    lengths.append(lengths[len(lengths) // 2])       # the trash-table slot
    B = len(lengths)
    N = B * nb + 1
    q = jnp.asarray(rng.normal(size=(B, Hkv, H // Hkv, Dh)), jnp.float32)
    kp = rng.normal(size=(N, bs, Hkv * Dh)).astype(np.float32)
    vp = rng.normal(size=(N, bs, Hkv * Dh)).astype(np.float32)
    tables = rng.permutation(np.arange(1, N)).reshape(B, nb).astype(np.int32)
    tables[-1, lengths[-1] // bs + 1:] = 0
    return q, kp, vp, tables, np.asarray(lengths, np.int32)


@pytest.mark.parametrize("H,Hkv,Dh,nb,window,bs", CELL_SHAPES)
def test_paged_kernel_matches_reference_at_cell_shapes(
        devices, pallas_interpret, H, Hkv, Dh, nb, window, bs):
    """The tile walk against the dense gathered softmax at the serving
    cells' head shapes, every slot at another edge: an empty cache, a
    block's last and first position, a tile's last, first and second, the
    table's last; unused entries naming the trash block."""
    q, kp, vp, tables, lengths = _cell_problem(H, Hkv, Dh, nb, window, bs=bs)
    args = (q, jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(tables),
            jnp.asarray(lengths))
    out = paged_decode_attention(*args, scale=Dh ** -0.5, window=window)
    ref = paged_decode_reference(*args, scale=Dh ** -0.5, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("H,Hkv,Dh,nb,window,bs", CELL_SHAPES)
def test_tile_walk_keeps_its_promises(devices, pallas_interpret, H, Hkv, Dh,
                                      nb, window, bs):
    """What the mechanism promises, from shapes alone: a grid step
    attends 128 to 256 tokens (the whole table where it is shorter) or,
    where a block is large, the fewest blocks whose K and V reach
    ``STEP_BYTES`` (at most ``STEP_VIEWS``, at most the table), the
    host's count of the steps that run is the kernel's own arithmetic,
    and a block past a slot's length or wholly below its band never
    reaches the output, NaN and all."""
    rb = _row_bytes(Hkv, Dh)
    P, floor = blocks_per_step(nb, bs, rb), blocks_per_step(nb, bs)
    if bs == 16:
        assert P == floor
        assert P * bs == min(nb * bs, 128) or 128 <= P * bs <= 256, P
    else:
        assert floor < P == min(nb, STEP_VIEWS,
                                -(-STEP_BYTES // (2 * bs * rb)))
    q, kp, vp, tables, lengths = _cell_problem(H, Hkv, Dh, nb, window, bs=bs)
    for b, n in enumerate(lengths):
        hi = n // bs
        lo = 0 if window is None else max(n - window + 1, 0) // bs
        assert tiles_run(int(n), nb, bs, window, row_bytes=rb) \
            == hi // P - lo // P + 1
        for e in range(nb):
            if (e > hi or e < lo) and tables[b, e] != 0:
                kp[tables[b, e]] = np.nan
                vp[tables[b, e]] = np.nan
    assert tiles_run(0, nb, bs, window, row_bytes=rb) == 1
    assert tiles_run(nb * bs - 1, nb, bs, row_bytes=rb) == -(-nb // P)
    out = paged_decode_attention(q, jnp.asarray(kp), jnp.asarray(vp),
                                 jnp.asarray(tables), jnp.asarray(lengths),
                                 scale=Dh ** -0.5, window=window)
    assert np.isfinite(np.asarray(out)).all()
    # and what it does attend is all it should: the poisoned blocks
    # zeroed, the reference agrees
    ref = paged_decode_reference(
        q, jnp.asarray(np.nan_to_num(kp)), jnp.asarray(np.nan_to_num(vp)),
        jnp.asarray(tables), jnp.asarray(lengths), scale=Dh ** -0.5,
        window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("H,Hkv,Dh,nb,window,bs", CELL_SHAPES)
def test_decode_plan_fetches_attended_blocks_once(devices, H, Hkv, Dh, nb,
                                                  window, bs):
    """The grid worked out from the lengths: as many steps as the slots'
    tiles that run, in slot order; every attended table entry named by
    the ref of its place in the tile at its own step; a ref's index
    changes only to an attended entry, so nothing else is fetched and
    nothing twice."""
    rb = _row_bytes(Hkv, Dh)
    P = blocks_per_step(nb, bs, rb)
    lengths = np.asarray(_edge_lengths(nb, bs, window, P), np.int32)
    B = len(lengths)
    plan = decode_plan(jnp.asarray(lengths), nb, bs, row_bytes=rb,
                       window=window)
    steps = int(plan.steps)
    slot, tile = np.asarray(plan.slot)[:steps], np.asarray(plan.tile)[:steps]
    held = np.asarray(plan.held)[:, :steps]
    assert plan.held.shape == (P, B * -(-nb // P)) and plan.cut[-1] == P
    assert steps == sum(tiles_run(int(n), nb, bs, window, row_bytes=rb)
                        for n in lengths)
    assert sorted(set(slot)) == list(range(B))
    assert (np.diff(slot) >= 0).all()
    attended = set()
    for b in range(B):
        hi = lengths[b] // bs
        lo = 0 if window is None else max(lengths[b] - window + 1, 0) // bs
        attended |= {b * nb + e for e in range(lo, hi + 1)}
        assert list(tile[slot == b]) == list(range(lo // P, hi // P + 1))
    named_at_own_step = set()
    for w in range(steps):
        for i in range(P):
            e = tile[w] * P + i
            if slot[w] * nb + e in attended and e < nb:
                assert held[i, w] == slot[w] * nb + e
                named_at_own_step.add(held[i, w])
    assert named_at_own_step == attended
    fetched = [held[i, w] for i in range(P) for w in range(steps)
               if w == 0 or held[i, w] != held[i, w - 1]]
    assert sorted(fetched) == sorted(attended)


# which slots decode, of B: the work list is cut from these
MASKS = {
    "first-idle": lambda B: np.arange(B) != 0,
    "last-idle": lambda B: np.arange(B) != B - 1,
    "every-other-idle": lambda B: np.arange(B) % 2 == 1,
    "single-live": lambda B: np.arange(B) == B // 2,
    "none-live": lambda B: np.zeros(B, bool),
    "all-live": lambda B: np.ones(B, bool),
}
# (table entries, window, q_len, block, a pool row's bytes, the tile): the
# full table, the window ring, a windowed table of several tiles, a verify
# chunk; then tiles by the bytes: a ring of 33 blocks of 128 in tiles of 4,
# the same blocks of an int8 pool (a quarter of the bytes) in tiles of 8, a
# verify chunk over a table of 24 blocks of 512 in tiles of 4
PLAN_CUTS = [
    pytest.param(64, None, 1, 16, None, 8, id="table64"),
    pytest.param(9, 128, 1, 16, None, 9, id="ring9-window128"),
    pytest.param(32, 100, 1, 16, None, 8, id="table32-window100"),
    pytest.param(64, None, 5, 16, None, 8, id="table64-verify5"),
    pytest.param(33, 4096, 1, 128, 1024, 4, id="block128-ring33-tile4"),
    pytest.param(33, 4096, 1, 128, 256, 8, id="block128-ring33-tile8"),
    pytest.param(24, None, 3, 512, 256, 4, id="block512-table24-verify3")]


@pytest.mark.parametrize("mask", MASKS)
@pytest.mark.parametrize("nb,window,q_len,bs,rb,P", PLAN_CUTS)
def test_decode_plan_of_the_active_slots(devices, nb, window, q_len, bs, rb,
                                         P, mask):
    """``decode_plan(active=)`` is the plan of the live slots alone, slot
    indices mapped back: a slot that does not decode has no step, first
    and last slot included, every attended block of a live slot is still
    fetched once and nothing else is; with every slot live, and with no
    mask, the arrays are the parent's number for number."""
    assert blocks_per_step(nb, bs, rb) == P
    lengths = np.asarray(_edge_lengths(nb, bs, window, P), np.int32)
    lengths = np.minimum(lengths, nb * bs - q_len)
    B = len(lengths)
    active = MASKS[mask](B)
    kw = dict(row_bytes=rb, window=window, q_len=q_len)
    plan = decode_plan(jnp.asarray(lengths), nb, bs, active=active, **kw)
    assert plan.cut == (nb, bs, window, q_len, P)
    np.testing.assert_array_equal(np.asarray(plan.live), active)
    steps = int(plan.steps)
    per_slot = [tiles_run(int(n), nb, bs, window, q_len, rb) if a else 0
                for n, a in zip(lengths, active)]
    assert steps == sum(per_slot)
    slot = np.asarray(plan.slot)
    assert [int((slot[:steps] == b).sum()) for b in range(B)] == per_slot
    held = np.asarray(plan.held)
    assert held.shape == (P, B * -(-nb // P))
    assert held.min() >= 0 and held.max() < B * nb     # padding too
    parent = decode_plan(jnp.asarray(lengths), nb, bs, **kw)
    assert parent.live is None
    if active.all():
        for a, b in zip(plan[:4], parent[:4]):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    if not active.any():
        return
    live = np.flatnonzero(active)
    alone = decode_plan(jnp.asarray(lengths[live]), nb, bs, **kw)
    assert int(alone.steps) == steps
    np.testing.assert_array_equal(slot[:steps],
                                  live[np.asarray(alone.slot)[:steps]])
    np.testing.assert_array_equal(np.asarray(plan.tile)[:steps],
                                  np.asarray(alone.tile)[:steps])
    attended = set()
    for b in live:
        hi = min((lengths[b] + q_len - 1) // bs, nb - 1)
        lo = 0 if window is None else max(lengths[b] - window + 1, 0) // bs
        attended |= {b * nb + e for e in range(lo, hi + 1)}
    h = np.asarray(alone.held)[:, :steps]
    for i in range(P):
        fetched = [held[i, w] for w in range(steps)
                   if w == 0 or held[i, w] != held[i, w - 1]]
        mine = sorted(a for a in attended if a % nb % P == i)
        if mine:
            assert sorted(fetched) == mine
            np.testing.assert_array_equal(held[i, :steps],
                                          live[h[i] // nb] * nb + h[i] % nb)
        else:
            # a ref no live slot's band reaches names one block all
            # through (the kernel reads it as zeros), as in the parent
            assert len(fetched) == 1


# ZAYA1's attention (8 query / 2 KV heads of 128, a table of 6 blocks)
# beside the older cells'; its block is 1,024 on the chip, 128 here; and
# the tiles by the bytes
MASKED_SHAPES = CELL_SHAPES[:3] + [
    pytest.param(8, 2, 128, 6, None, 128, id="zaya1-table6")] + BYTE_SHAPES


@functools.partial(jax.jit, static_argnames=("scale", "window"))
def _attend_live_slots(q, kp, vp, tables, lengths, active, *, scale, window):
    plan = decode_plan(lengths, tables.shape[1], kp.shape[1],
                       row_bytes=pool_row_bytes(kp), window=window,
                       active=active)
    return paged_decode_attention(q, kp, vp, tables, lengths, scale=scale,
                                  window=window, plan=plan)


@pytest.mark.parametrize("live", ["some-live", "none-live"])
@pytest.mark.parametrize("H,Hkv,Dh,nb,window,bs", MASKED_SHAPES)
def test_slots_that_do_not_decode_are_not_visited(devices, pallas_interpret,
                                                  H, Hkv, Dh, nb, window, bs,
                                                  live):
    """A slot with no request (length 0, its table the trash block) and a
    slot in mid-prefill (inactive, its progress as its length, blocks of
    its own) have no grid step: with NaN in the trash block and in every
    block of the prefilling slot the live slots read, to the bit, what
    they read without the poison, and the rows of the slots that do not
    decode are exactly zero. With no slot live the call returns zeros."""
    q, kp, vp, tables, lengths = _cell_problem(H, Hkv, Dh, nb, window, bs=bs)
    B = len(lengths)
    idle, prefilling = 0, B // 2
    lengths[idle] = 0
    tables[idle] = 0
    lengths[prefilling] = min(300, nb * bs - 5)
    active = np.ones(B, bool)
    active[[idle, prefilling]] = False
    if live == "none-live":
        active[:] = False
    kp[0] = vp[0] = 0.0

    def call(kp, vp):
        return _attend_live_slots(q, kp, vp, jnp.asarray(tables),
                                  jnp.asarray(lengths), jnp.asarray(active),
                                  scale=Dh ** -0.5, window=window)
    clean = np.asarray(call(jnp.asarray(kp), jnp.asarray(vp)))
    kp[0] = vp[0] = np.nan
    kp[tables[prefilling]] = vp[tables[prefilling]] = np.nan
    out = np.asarray(call(jnp.asarray(kp), jnp.asarray(vp)))
    np.testing.assert_array_equal(out, clean)
    assert (out[~active] == 0).all()
    if active.any():
        ref = np.asarray(paged_decode_reference(
            q, jnp.asarray(np.nan_to_num(kp)), jnp.asarray(np.nan_to_num(vp)),
            jnp.asarray(tables), jnp.asarray(lengths), scale=Dh ** -0.5,
            window=window))
        np.testing.assert_allclose(out[active], ref[active], atol=2e-5,
                                   rtol=2e-5)


@pytest.mark.parametrize("H,Hkv,Dh,nb,window,bs", CELL_SHAPES)
def test_a_slots_bad_block_stays_its_own(devices, pallas_interpret, H, Hkv,
                                         Dh, nb, window, bs):
    """Fault isolation between slots: a ref whose entry a step's slot
    does not attend still holds the block it fetched for the slot
    before, and a probability of 0 times that block's NaN would be a
    NaN. With every block that every second slot ATTENDS poisoned (K
    and V, infinities too), the slots between them read what the
    reference reads."""
    q, kp, vp, tables, lengths = _cell_problem(H, Hkv, Dh, nb, window, bs=bs)
    B = len(lengths)
    bad = np.arange(B) % 2 == 0
    clean = (q, jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(tables),
             jnp.asarray(lengths))
    for b in np.flatnonzero(bad):
        hi = lengths[b] // bs
        lo = 0 if window is None else max(lengths[b] - window + 1, 0) // bs
        kp[tables[b, lo:hi + 1]] = np.nan
        vp[tables[b, lo:hi + 1]] = np.where(b % 4 == 0, np.nan, np.inf)
    out = np.asarray(paged_decode_attention(
        q, jnp.asarray(kp), jnp.asarray(vp), *clean[3:], scale=Dh ** -0.5,
        window=window))
    ref = np.asarray(paged_decode_reference(*clean, scale=Dh ** -0.5,
                                            window=window))
    assert not np.isfinite(out[bad]).all(axis=(1, 2, 3)).any()
    assert np.isfinite(out[~bad]).all()
    np.testing.assert_allclose(out[~bad], ref[~bad], atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("other", [dict(window=64), dict(q_len=2),
                                   dict(nb=12), dict(row_bytes=None),
                                   dict(row_bytes=256)],
                         ids=["window", "q_len", "table", "token-tile",
                              "int8-tile"])
def test_a_plan_fits_its_call_or_the_call_refuses(devices, pallas_interpret,
                                                  other):
    """A plan worked out for another window, chunk, table or tile (the
    token rule's one block of 128 a step, or an int8 pool's eight, where
    the call's float32 pool takes four) would fire the kernel's first and
    last tile at the wrong steps without a word: the call checks what the
    plan was cut for."""
    shape = dict(nb=20, bs=128, rb=1024, heads=(8, 2, 128)) \
        if "row_bytes" in other else dict(nb=13, bs=16, rb=6400,
                                          heads=(25, 25, 64))
    nb, bs, rb = shape["nb"], shape["bs"], shape["rb"]
    q, kp, vp, tables, lengths = _cell_problem(*shape["heads"], nb, None,
                                               bs=bs)
    args = (q, jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(tables),
            jnp.asarray(lengths))
    good = decode_plan(args[4], nb, bs, row_bytes=rb)
    assert good.cut == (nb, bs, None, 1, blocks_per_step(nb, bs, rb))
    np.testing.assert_array_equal(
        np.asarray(paged_decode_attention(*args, scale=0.125, plan=good)),
        np.asarray(paged_decode_attention(*args, scale=0.125)))
    kw = {**dict(window=None, q_len=1, nb=nb, row_bytes=rb), **other}
    wrong = decode_plan(args[4], kw.pop("nb"), bs, **kw)
    assert wrong.cut != good.cut
    with pytest.raises(AssertionError):
        paged_decode_attention(*args, scale=0.125, plan=wrong)


@pytest.mark.parametrize("G", [2, 5])
@pytest.mark.parametrize("H,Hkv,Dh,nb,window,bs", BYTE_SHAPES)
def test_paged_verify_matches_reference_at_byte_tiles(
        devices, pallas_interpret, H, Hkv, Dh, nb, window, bs, G):
    """A verify chunk (``q_len`` > 1) over tiles cut by the bytes: chunk
    query i of a slot at every edge attends positions up to its own, the
    chunk's last query in the tile after its first where the chunk
    straddles a tile's edge."""
    q, kp, vp, tables, lengths = _cell_problem(H, Hkv, Dh, nb, window, bs=bs)
    lengths = np.minimum(lengths, nb * bs - G)
    rng = np.random.default_rng(3)
    qg = jnp.asarray(rng.normal(size=(len(lengths), G) + q.shape[1:]),
                     jnp.float32)
    args = (qg, jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(tables),
            jnp.asarray(lengths))
    out = paged_verify_attention(*args, scale=Dh ** -0.5, window=window)
    ref = paged_verify_reference(*args, scale=Dh ** -0.5, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_paged_kernel_no_dense_gather(devices):
    """Acceptance: the pallas path never materializes the virtual
    [B, NB*block, ...] cache — its jaxpr contains no gather the size of
    pool[tables] (the reference path's first op)."""
    q, kp, vp, tables, lengths = _pool_problem()
    B, NB = tables.shape
    bs, row = kp.shape[1], kp.shape[2]
    dense = (B, NB, bs, row)

    def gathers(fn):
        jaxpr = jax.make_jaxpr(fn)(q, kp, vp, tables, lengths)
        return [e for e in jaxpr.jaxpr.eqns
                if e.primitive.name == "gather"
                and tuple(e.outvars[0].aval.shape) == dense]

    assert gathers(lambda *a: paged_decode_reference(*a, scale=0.25))
    assert not gathers(lambda *a: paged_decode_attention(
        *a, scale=0.25, interpret=True))
