"""The paged-attention kernel's work list (``decode_plan``): which grid
steps run, what each fetches, and that a slot which does not decode is
never visited. The kernel runs in INTERPRET mode here, as in
tests/test_paged_attention.py, which holds it to its gather reference."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.attention.paged import (blocks_per_step, decode_plan,
                                               paged_decode_attention,
                                               paged_decode_reference,
                                               pool_row_bytes, tiles_run)

from paged_attention_util import (CELL_SHAPES, MASKED_SHAPES, cell_problem,
                                  edge_lengths, row_bytes)


@pytest.mark.parametrize("H,Hkv,Dh,nb,window,bs", CELL_SHAPES)
def test_decode_plan_fetches_attended_blocks_once(devices, H, Hkv, Dh, nb,
                                                  window, bs):
    """The grid worked out from the lengths: as many steps as the slots'
    tiles that run, in slot order; every attended table entry named by
    the ref of its place in the tile at its own step; a ref's index
    changes only to an attended entry, so nothing else is fetched and
    nothing twice."""
    rb = row_bytes(Hkv, Dh)
    P = blocks_per_step(nb, bs, rb)
    lengths = np.asarray(edge_lengths(nb, bs, window, P), np.int32)
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
    lengths = np.asarray(edge_lengths(nb, bs, window, P), np.int32)
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
    q, kp, vp, tables, lengths = cell_problem(H, Hkv, Dh, nb, window, bs=bs)
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
    q, kp, vp, tables, lengths = cell_problem(*shape["heads"], nb, None,
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
