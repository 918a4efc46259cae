"""Paged-attention flash-decode kernel tests (tentpole:
ops/attention/paged.py + the impl switch through inference/engine.py and
inference/serving.py).

The kernel runs in INTERPRET mode here (JAX_PLATFORMS=cpu, see
conftest.py) — same kernel body, Python-evaluated — so tier-1 exercises
the pallas path without a TPU. The gather path is the bit-reference:
kernel-level tests are allclose (the online softmax reassociates the
reduction), serving-level tests assert token-for-token EQUALITY of the
greedy stream, including across an eviction/requeue."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference import engine as engine_lib
from deepspeed_tpu.inference.engine import InferenceEngine
from deepspeed_tpu.inference.hybrid import _rows, causal_band
from deepspeed_tpu.inference.serving import ServeRequest, ServingEngine
from deepspeed_tpu.models import gpt
from deepspeed_tpu.ops.attention.paged import (blocks_per_step, decode_plan,
                                               gather_pool_blocks,
                                               paged_decode_attention,
                                               paged_decode_reference,
                                               resolve_decode_impl,
                                               tiles_run)


def tiny(**over):
    cfg = gpt.GPTConfig(vocab_size=128, n_layers=2, n_heads=4, d_model=32,
                        max_seq_len=64, use_flash_attention=False,
                        remat=False, dtype=jnp.float32, **over)
    params = gpt.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def prompts_of(lengths, seed=1):
    r = np.random.default_rng(seed)
    return [r.integers(1, 128, n).astype(np.int32) for n in lengths]


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
# entries, window) with small pools, and a table whose length is prime
CELL_SHAPES = [
    pytest.param(25, 25, 64, 64, None, id="gpt2-xl-table64"),
    pytest.param(64, 8, 128, 256, None, id="kexaone-full-table256"),
    pytest.param(64, 8, 128, 9, 128, id="kexaone-ring9-window128"),
    pytest.param(25, 25, 64, 13, None, id="gpt2-xl-prime-table13"),
]


def _edge_lengths(nb, bs, window):
    """Slot lengths at every edge of a block, a tile and the table. A
    ring table's lengths are relative to its first block, so they stay
    within the ring and (the band being the caller's whole table) past
    nothing the window has dropped."""
    P = blocks_per_step(nb, bs)
    edges = [0, bs - 1, bs, P * bs - 1, P * bs, P * bs + 1, nb * bs - 1]
    if window is not None:
        edges += [window - 1, window, window + bs // 2]
    return sorted({min(n, nb * bs - 1) for n in edges})


def _cell_problem(H, Hkv, Dh, nb, window, seed=0, bs=16):
    """A slot per edge length plus one whose unused table entries name
    the trash block 0 (as the paged cache leaves them); the other slots'
    entries past their length name blocks of their own, poisoned."""
    rng = np.random.default_rng(seed)
    lengths = _edge_lengths(nb, bs, window)
    lengths.append(lengths[len(lengths) // 2])       # the trash-table slot
    B = len(lengths)
    N = B * nb + 1
    q = jnp.asarray(rng.normal(size=(B, Hkv, H // Hkv, Dh)), jnp.float32)
    kp = rng.normal(size=(N, bs, Hkv * Dh)).astype(np.float32)
    vp = rng.normal(size=(N, bs, Hkv * Dh)).astype(np.float32)
    tables = rng.permutation(np.arange(1, N)).reshape(B, nb).astype(np.int32)
    tables[-1, lengths[-1] // bs + 1:] = 0
    return q, kp, vp, tables, np.asarray(lengths, np.int32)


@pytest.mark.parametrize("H,Hkv,Dh,nb,window", CELL_SHAPES)
def test_paged_kernel_matches_reference_at_cell_shapes(
        devices, pallas_interpret, H, Hkv, Dh, nb, window):
    """The tile walk against the dense gathered softmax at the serving
    cells' head shapes, every slot at another edge: an empty cache, a
    block's last and first position, a tile's last, first and second, the
    table's last; unused entries naming the trash block."""
    q, kp, vp, tables, lengths = _cell_problem(H, Hkv, Dh, nb, window)
    args = (q, jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(tables),
            jnp.asarray(lengths))
    out = paged_decode_attention(*args, scale=Dh ** -0.5, window=window)
    ref = paged_decode_reference(*args, scale=Dh ** -0.5, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("H,Hkv,Dh,nb,window", CELL_SHAPES)
def test_tile_walk_keeps_its_promises(devices, pallas_interpret, H, Hkv, Dh,
                                      nb, window):
    """What the mechanism promises, from shapes alone: a grid step
    attends 128 to 256 tokens (the whole table where it is shorter), the
    host's count of the steps that run is the kernel's own arithmetic,
    and a block past a slot's length or wholly below its band never
    reaches the output, NaN and all."""
    bs = 16
    P = blocks_per_step(nb, bs)
    assert P * bs == min(nb * bs, 128) or 128 <= P * bs <= 256, P
    q, kp, vp, tables, lengths = _cell_problem(H, Hkv, Dh, nb, window)
    for b, n in enumerate(lengths):
        hi = n // bs
        lo = 0 if window is None else max(n - window + 1, 0) // bs
        assert tiles_run(int(n), nb, bs, window) == hi // P - lo // P + 1
        for e in range(nb):
            if (e > hi or e < lo) and tables[b, e] != 0:
                kp[tables[b, e]] = np.nan
                vp[tables[b, e]] = np.nan
    assert tiles_run(0, nb, bs, window) == 1
    assert tiles_run(nb * bs - 1, nb, bs) == -(-nb // P)
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


@pytest.mark.parametrize("H,Hkv,Dh,nb,window", CELL_SHAPES)
def test_decode_plan_fetches_attended_blocks_once(devices, H, Hkv, Dh, nb,
                                                  window):
    """The grid worked out from the lengths: as many steps as the slots'
    tiles that run, in slot order; every attended table entry named by
    the ref of its place in the tile at its own step; a ref's index
    changes only to an attended entry, so nothing else is fetched and
    nothing twice."""
    bs = 16
    P = blocks_per_step(nb, bs)
    lengths = np.asarray(_edge_lengths(nb, bs, window), np.int32)
    B = len(lengths)
    plan = decode_plan(jnp.asarray(lengths), nb, bs, window=window)
    steps = int(plan.steps)
    slot, tile = np.asarray(plan.slot)[:steps], np.asarray(plan.tile)[:steps]
    held = np.asarray(plan.held)[:, :steps]
    assert plan.held.shape == (P, B * -(-nb // P))
    assert steps == sum(tiles_run(int(n), nb, bs, window) for n in lengths)
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
# (table entries, window, q_len): the full table, the window ring, a
# windowed table of several tiles, a verify chunk
PLAN_CUTS = [pytest.param(64, None, 1, id="table64"),
             pytest.param(9, 128, 1, id="ring9-window128"),
             pytest.param(32, 100, 1, id="table32-window100"),
             pytest.param(64, None, 5, id="table64-verify5")]


@pytest.mark.parametrize("mask", MASKS)
@pytest.mark.parametrize("nb,window,q_len", PLAN_CUTS)
def test_decode_plan_of_the_active_slots(devices, nb, window, q_len, mask):
    """``decode_plan(active=)`` is the plan of the live slots alone, slot
    indices mapped back: a slot that does not decode has no step, first
    and last slot included, every attended block of a live slot is still
    fetched once and nothing else is; with every slot live, and with no
    mask, the arrays are the parent's number for number."""
    bs = 16
    P = blocks_per_step(nb, bs)
    lengths = np.asarray(_edge_lengths(nb, bs, window), np.int32)
    lengths = np.minimum(lengths, nb * bs - q_len)
    B = len(lengths)
    active = MASKS[mask](B)
    kw = dict(window=window, q_len=q_len)
    plan = decode_plan(jnp.asarray(lengths), nb, bs, active=active, **kw)
    assert plan.cut == (nb, bs, window, q_len)
    np.testing.assert_array_equal(np.asarray(plan.live), active)
    steps = int(plan.steps)
    per_slot = [tiles_run(int(n), nb, bs, window, q_len) if a else 0
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
# beside the older cells'; its block is 1,024 on the chip, 128 here
MASKED_SHAPES = CELL_SHAPES[:3] + [
    pytest.param(8, 2, 128, 6, None, id="zaya1-table6")]


@functools.partial(jax.jit, static_argnames=("scale", "window"))
def _attend_live_slots(q, kp, vp, tables, lengths, active, *, scale, window):
    plan = decode_plan(lengths, tables.shape[1], kp.shape[1], window=window,
                       active=active)
    return paged_decode_attention(q, kp, vp, tables, lengths, scale=scale,
                                  window=window, plan=plan)


@pytest.mark.parametrize("live", ["some-live", "none-live"])
@pytest.mark.parametrize("H,Hkv,Dh,nb,window", MASKED_SHAPES)
def test_slots_that_do_not_decode_are_not_visited(devices, pallas_interpret,
                                                  H, Hkv, Dh, nb, window,
                                                  live):
    """A slot with no request (length 0, its table the trash block) and a
    slot in mid-prefill (inactive, its progress as its length, blocks of
    its own) have no grid step: with NaN in the trash block and in every
    block of the prefilling slot the live slots read, to the bit, what
    they read without the poison, and the rows of the slots that do not
    decode are exactly zero. With no slot live the call returns zeros."""
    bs = 128 if nb == 6 else 16
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


@pytest.mark.parametrize("H,Hkv,Dh,nb,window", CELL_SHAPES)
def test_a_slots_bad_block_stays_its_own(devices, pallas_interpret, H, Hkv,
                                         Dh, nb, window):
    """Fault isolation between slots: a ref whose entry a step's slot
    does not attend still holds the block it fetched for the slot
    before, and a probability of 0 times that block's NaN would be a
    NaN. With every block that every second slot ATTENDS poisoned (K
    and V, infinities too), the slots between them read what the
    reference reads."""
    q, kp, vp, tables, lengths = _cell_problem(H, Hkv, Dh, nb, window)
    bs, B = 16, len(lengths)
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
                                   dict(nb=12)],
                         ids=["window", "q_len", "table"])
def test_a_plan_fits_its_call_or_the_call_refuses(devices, pallas_interpret,
                                                  other):
    """A plan worked out for another window, chunk or table would fire
    the kernel's first and last tile at the wrong steps without a word:
    the call checks what the plan was cut for."""
    q, kp, vp, tables, lengths = _cell_problem(25, 25, 64, 13, None)
    args = (q, jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(tables),
            jnp.asarray(lengths))
    good = decode_plan(args[4], 13, 16)
    np.testing.assert_array_equal(
        np.asarray(paged_decode_attention(*args, scale=0.125, plan=good)),
        np.asarray(paged_decode_attention(*args, scale=0.125)))
    kw = {**dict(window=None, q_len=1, nb=13), **other}
    wrong = decode_plan(args[4], kw.pop("nb"), 16, **kw)
    with pytest.raises(AssertionError):
        paged_decode_attention(*args, scale=0.125, plan=wrong)


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


def test_resolve_decode_impl(devices, monkeypatch):
    assert resolve_decode_impl("gather") == "gather"
    assert resolve_decode_impl("pallas") == "pallas"
    monkeypatch.setenv("DS_PAGED_DECODE_IMPL", "pallas")
    assert resolve_decode_impl(None) == "pallas"
    monkeypatch.delenv("DS_PAGED_DECODE_IMPL")
    assert resolve_decode_impl(None) == "gather"    # CPU default
    with pytest.raises(ValueError, match="expected 'pallas' or 'gather'"):
        resolve_decode_impl("cuda")


# ---------------------------------------------------------------------------
# serving parity: pallas stream == gather stream, token for token
# ---------------------------------------------------------------------------

def _serve(impl, cfg, params, prompts, n_new, **srv_kw):
    eng = InferenceEngine(config=cfg, params=params, dtype=jnp.float32)
    srv = ServingEngine(eng, decode_impl=impl, **srv_kw)
    out = srv.run([ServeRequest(rid=i, prompt=p, max_new_tokens=n_new)
                   for i, p in enumerate(prompts)])
    return out, srv


def test_serving_parity_pallas_vs_gather(devices, pallas_interpret):
    """Greedy serving output is token-for-token identical under both
    impls — GQA + rotary + sliding window + chunked prefill all on, so
    the full feature stack flows through the kernel."""
    cfg, _ = tiny()
    cfg = dataclasses.replace(cfg, rotary_dim=4, use_wpe=False,
                              n_kv_heads=2, attn_window=10)
    params = gpt.init_params(jax.random.PRNGKey(0), cfg)
    prompts = prompts_of((4, 13, 7), seed=7)
    kw = dict(num_slots=2, block_size=4, num_blocks=30, prefill_chunk=4)
    ref, _ = _serve("gather", cfg, params, prompts, 8, **kw)
    out, srv = _serve("pallas", cfg, params, prompts, 8, **kw)
    assert srv.decode_impl == "pallas"
    for i in ref:
        np.testing.assert_array_equal(out[i], ref[i])
    assert srv.stats["peak_occupancy"] > 1    # batched decode really ran


@pytest.mark.parametrize("impl", ["gather", "pallas"])
def test_a_neighbour_in_prefill_changes_nothing(devices, pallas_interpret,
                                                impl):
    """A request decodes in a 4-slot engine while another is in
    mid-prefill (inactive in the decode dispatch, its progress as its
    length) and two slots hold nothing: its tokens and log-probabilities
    are those of the same request served alone, and the span says how
    many tiles the dispatch did not take."""
    from deepspeed_tpu.telemetry import Telemetry
    cfg, params = tiny()
    short, long_ = prompts_of((5, 41), seed=11)
    kw = dict(num_slots=4, block_size=4, num_blocks=40, prefill_chunk=4,
              decode_impl=impl)

    def run(prompts, telemetry=None):
        eng = InferenceEngine(config=cfg, params=params, dtype=jnp.float32)
        srv = ServingEngine(eng, telemetry=telemetry, **kw)
        reqs = [ServeRequest(rid=i, prompt=p, max_new_tokens=12,
                             logprobs=True) for i, p in enumerate(prompts)]
        out = srv.run(reqs)
        return out[0], list(reqs[0].out_logprobs)

    alone_tok, alone_lp = run([short])
    tel = Telemetry()
    tok, lp = run([short, long_], tel)
    np.testing.assert_array_equal(tok, alone_tok)
    assert lp == alone_lp and len(lp) == 12
    spans = [s[5] for s in tel.tracer.spans() if s[1] == "serve.decode"
             and s[5].get("live") == 1]
    # one live slot; 2 idle slots a tile each, and the prefilling slot's
    # progress (4 to 40 tokens of a 64-token table: one tile of 16 blocks)
    assert spans and all(a["kv_steps"] == 1 for a in spans)
    assert {a["idle_tiles"] for a in spans} == {3}


def test_serving_parity_pallas_across_eviction(devices, pallas_interpret):
    """The eviction/requeue recompute path (tight pool, zero watermark)
    stays parity-exact under the pallas kernel."""
    cfg, params = tiny()
    p1, p2 = prompts_of((10, 9), seed=9)

    def run(impl):
        eng = InferenceEngine(config=cfg, params=params, dtype=jnp.float32)
        srv = ServingEngine(eng, num_slots=2, block_size=4, num_blocks=7,
                            decode_impl=impl)
        srv.cache.watermark = 0
        out = srv.run([ServeRequest(rid="a", prompt=p1, max_new_tokens=12),
                       ServeRequest(rid="b", prompt=p2, max_new_tokens=10)])
        return out, srv.stats["evictions"]

    ref, ev_g = run("gather")
    out, ev_p = run("pallas")
    assert ev_g >= 1 and ev_p >= 1
    np.testing.assert_array_equal(out["a"], ref["a"])
    np.testing.assert_array_equal(out["b"], ref["b"])


def test_serving_engine_impl_defaults_to_engine(devices):
    """ServingEngine inherits the engine's resolved decode_impl (CPU
    default: gather) unless overridden."""
    cfg, params = tiny()
    eng = InferenceEngine(config=cfg, params=params, dtype=jnp.float32)
    assert eng.decode_impl == "gather"
    assert ServingEngine(eng, num_slots=2).decode_impl == "gather"
    assert ServingEngine(eng, num_slots=2,
                         decode_impl="pallas").decode_impl == "pallas"
    with pytest.raises(ValueError):
        ServingEngine(eng, num_slots=2, decode_impl="nope")


# ---------------------------------------------------------------------------
# slot-capacity overflow (satellite): finish, don't clobber
# ---------------------------------------------------------------------------

def test_full_budget_slot_finished_not_overwritten(devices):
    """A decoding slot whose cache length has reached the per-slot block
    budget is FINISHED before the decode kernel runs — not preempted
    (the resume prompt is as long, it would requeue forever) and never
    allowed to clamp-write into its own last live block."""
    cfg, params = tiny()
    eng = InferenceEngine(config=cfg, params=params, dtype=jnp.float32)
    srv = ServingEngine(eng, num_slots=2, block_size=4, num_blocks=40)
    req = ServeRequest(rid="full", prompt=prompts_of((8,))[0],
                       max_new_tokens=16)
    srv.submit(req)
    srv._admit()
    slot = srv.slots.index(req)
    # drive the slot to the edge of its block budget by hand
    srv.cache.ensure_capacity(slot, srv.cache.tokens_per_slot)
    srv.cache.lengths[slot] = srv.cache.tokens_per_slot
    req.state = "decode"
    req.out.append(1)
    used_before = srv.cache.used_blocks
    assert srv._decode_step(now=0.0) == 0     # nothing decoded
    assert req.state == "done" and req in srv.finished
    assert srv.slots[slot] is None
    assert srv.cache.used_blocks < used_before   # blocks back in the pool
    assert srv.stats["evictions"] == 0


@pytest.mark.parametrize("impl", ["gather", "pallas"])
def test_engine_masks_capacity_overflow_write(devices, pallas_interpret,
                                              impl):
    """Engine-side belt: decode_slots with lengths == NB*block routes
    the new token's K/V write to the trash block instead of clamping
    into the slot's last live block."""
    cfg, params = tiny()
    eng = InferenceEngine(config=cfg, params=params, dtype=jnp.float32)
    bs, NB = 4, 3
    N = 8
    L, Hkv, Dh = cfg.n_layers, cfg.kv_heads, cfg.head_dim
    rng = np.random.default_rng(0)
    kp = jnp.asarray(rng.normal(size=(L, N, bs, Hkv * Dh)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(L, N, bs, Hkv * Dh)), jnp.float32)
    tables = np.zeros((2, NB), np.int32)
    tables[0] = [1, 2, 3]
    tables[1] = [4, 5, 6]
    # slot 0 at FULL budget, slot 1 mid-block
    lengths = np.array([NB * bs, 5], np.int32)
    active = np.array([True, True])
    _, k2, v2 = eng.decode_slots(kp.copy(), vp.copy(), tables, lengths,
                                 np.array([3, 4], np.int32), active,
                                 impl=impl)
    # every block slot 0 owns is untouched (the overflow write went to
    # each layer's trash block 0, and to no other layer's blocks);
    # slot 1's current position DID get written
    np.testing.assert_array_equal(np.asarray(k2)[:, 1:4],
                                  np.asarray(kp)[:, 1:4])
    assert not np.array_equal(np.asarray(k2)[:, 0], np.asarray(kp)[:, 0])
    np.testing.assert_array_equal(np.asarray(k2)[:, 6:],
                                  np.asarray(kp)[:, 6:])
    assert not np.array_equal(np.asarray(k2)[:, 5, 1],
                              np.asarray(kp)[:, 5, 1])
    assert not np.array_equal(np.asarray(v2)[:, 5, 1],
                              np.asarray(vp)[:, 5, 1])


# ---------------------------------------------------------------------------
# the prefill chunk's read: the occupied part of the slot's row only
# ---------------------------------------------------------------------------

def _whole_row_block(x, pools, table_row, positions, n_valid, p, cfg, lora,
                     base):
    """The plain reference: one block over a prompt chunk that writes the
    chunk's K and V, gathers the slot's WHOLE row, whatever is occupied,
    and lets the causal band mask the rest (the engine's two-pool read
    until PR 41)."""
    B, C, D = x.shape
    H, Dh, Hkv = cfg.n_heads, cfg.head_dim, cfg.kv_heads
    k_pool, v_pool = pools
    bs, NB = k_pool.shape[1], table_row.shape[0]
    lr = (lambda t: None) if lora is None else lora.get
    h = gpt._norm(x, p["ln1"], cfg)
    qkv = gpt._dense(h, p["qkv"], lora=lr("qkv"))
    q, k, v = gpt._qkv_split_rotary(qkv, cfg, positions[None], B, C)
    valid = jnp.arange(C) < n_valid
    trow = table_row + base
    blk = table_row[jnp.clip(positions // bs, 0, NB - 1)]
    blk = jnp.where(valid, blk, 0) + base
    k_pool = k_pool.at[blk, positions % bs].set(_rows(k[0]))
    v_pool = v_pool.at[blk, positions % bs].set(_rows(v[0]))
    kc = gather_pool_blocks(k_pool, trow[None], Hkv)[0]   # [NB*bs, Hkv, Dh]
    vc = gather_pool_blocks(v_pool, trow[None], Hkv)[0]
    qg = q[0].reshape(C, Hkv, H // Hkv, Dh)
    scores = jnp.einsum("ckgd,skd->ckgs", qg, kc).astype(jnp.float32)
    scores *= cfg.attn_scale if cfg.attn_scale is not None \
        else 1.0 / np.sqrt(Dh)
    sidx = jax.lax.broadcasted_iota(jnp.int32, (1, 1, 1, NB * bs), 3)
    scores = causal_band(scores, sidx, positions[:, None, None, None],
                         cfg.attn_window)
    probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    attn = jnp.einsum("ckgs,skd->ckgd", probs, vc).reshape(1, C, D)
    x = x + gpt._dense(attn, p["attn_out"], lora=lr("attn_out"))
    h = gpt._norm(x, p["ln2"], cfg)
    return x + engine_lib._ffn(h, p, cfg, lora=lora), (k_pool, v_pool)


_CHUNK = 64
_READ_VARIANTS = {
    # GPT-2's own shape of attention, and everything the read must carry
    # at once: grouped KV heads, a window, a scale of its own, a LoRA row,
    # and a table whose first blocks another slot's table holds too
    "plain": dict(over={}, lora=False, shared=False),
    "gqa_window_lora_shared": dict(
        over=dict(n_kv_heads=2, attn_window=200, attn_scale=0.2),
        lora=True, shared=True),
}


@functools.lru_cache(maxsize=None)
def _read_problem(variant):
    spec = _READ_VARIANTS[variant]
    cfg = gpt.GPTConfig(vocab_size=128, n_layers=2, n_heads=4, d_model=64,
                        max_seq_len=1024, use_flash_attention=False,
                        remat=False, dtype=jnp.float32, **spec["over"])
    params = gpt.init_params(jax.random.PRNGKey(0), cfg)
    layer = 1                       # not the first: ``base`` is not 0
    p = jax.tree_util.tree_map(lambda a: a[layer], params["block"])
    bs, NB = 16, 64                 # the cells' block and table: 1,024
    N = 2 * NB + 1                  # two slots' blocks and the trash block
    r = np.random.default_rng(7)
    D, row = cfg.d_model, cfg.kv_heads * cfg.head_dim
    # stale floats in every lane no chunk has written
    pools = tuple(jnp.asarray(r.normal(size=(cfg.n_layers * N, bs, row)),
                              jnp.float32) for _ in range(2))
    ids = r.permutation(np.arange(1, N))
    other, own = ids[:NB], ids[NB:]
    lora = None
    if spec["lora"]:
        def factors(i, o, rb=4, nba=2):
            return (jnp.asarray(r.normal(size=(1, nba, i, rb)) * 0.1,
                                jnp.float32),
                    jnp.asarray(r.normal(size=(1, nba, rb, o)) * 0.1,
                                jnp.float32))
        lora = {"qkv": factors(D, cfg.qkv_dim), "attn_out": factors(D, D)}
    x = jnp.asarray(r.normal(size=(1, _CHUNK, D)), jnp.float32)

    def table_for(start):
        # the blocks below the matched boundary are the other slot's
        shared = start // bs if spec["shared"] else 0
        return jnp.asarray(np.concatenate([other[:shared], own[shared:]]),
                           jnp.int32)

    def run(block):
        def chunk(pools, table_row, start, n_valid):
            positions = start + jnp.arange(_CHUNK, dtype=jnp.int32)
            return block(x, pools, table_row, positions, n_valid, p, cfg,
                         lora=lora, base=layer * N)
        return jax.jit(chunk)
    return (pools, table_for, run(engine_lib._block_prefill_paged),
            run(_whole_row_block), other, layer * N)


@pytest.mark.parametrize("variant", sorted(_READ_VARIANTS))
@pytest.mark.parametrize("n_valid", [1, _CHUNK - 1, _CHUNK])
@pytest.mark.parametrize("start", [0, 16, 48, 64, 127, 128, 192, 512, 960])
def test_prefill_chunk_reads_the_occupied_part_of_its_row(devices, start,
                                                          n_valid, variant):
    """The dense pass over the shortest run of tiles that holds what the
    chunk's valid queries see is the whole-row softmax: the same output on
    every valid lane, and the same pools to the last bit (the write is
    what it was, and a shared block is read, never written)."""
    pools, table_for, new, ref, other, base = _read_problem(variant)
    table_row = table_for(start)
    y, got = new(pools, table_row, start, n_valid)
    y_ref, want = ref(pools, table_row, start, n_valid)
    np.testing.assert_allclose(np.asarray(y)[0, :n_valid],
                               np.asarray(y_ref)[0, :n_valid],
                               atol=2e-5, rtol=2e-5)
    assert np.isfinite(np.asarray(y)).all()
    for a, b, before in zip(got, want, pools):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        if _READ_VARIANTS[variant]["shared"]:
            np.testing.assert_array_equal(np.asarray(a)[other + base],
                                          np.asarray(before)[other + base])


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _traced_tiles(start, n, bs, nb, window):
    return engine_lib.attended_tiles(start, n, bs, nb, window)[:2]


@pytest.mark.parametrize("bs,nb,window", [(16, 64, None), (16, 64, 200),
                                          (4, 16, None), (512, 48, None),
                                          (16, 100, 1), (16, 512, 300)])
def test_attended_tiles_cover_what_a_chunk_sees_and_little_more(bs, nb,
                                                                window):
    """The length the program picks from ``(start, n)`` and the count the
    scheduler makes of it are ONE function: every key a valid query may
    see lies in ``[lo, hi)``, the run is no longer than that needs, the
    table is cut into at most ``PREFILL_READ_LENGTHS`` tiles, and a
    traced ``start`` gives what a Python one gives."""
    attended_tiles = engine_lib.attended_tiles
    for start in range(0, nb * bs - 64, max(1, nb * bs // 97)):
        for n in (1, 63, 64):
            lo, hi, P = attended_tiles(start, n, bs, nb, window)
            W = P * bs
            assert P % blocks_per_step(nb, bs) == 0
            assert -(-nb // P) <= engine_lib.PREFILL_READ_LENGTHS
            oldest = 0 if window is None else max(start - window + 1, 0)
            assert lo * W <= oldest < (lo + 1) * W
            assert (hi - 1) * W < start + n <= hi * W
            tlo, thi = _traced_tiles(jnp.int32(start), jnp.int32(n), bs, nb,
                                     window)
            assert (int(tlo), int(thi)) == (lo, hi)
