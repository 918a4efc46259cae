"""Paged-attention flash-decode kernel tests (tentpole:
ops/attention/paged.py): the kernel against its gather reference and its
tile walk; its work list is tests/test_paged_attention_plan.py, the verify
chunk tests/test_paged_attention_verify.py. The impl
switch through inference/engine.py and inference/serving.py is
tests/test_paged_serving.py.

The kernel runs in INTERPRET mode here (JAX_PLATFORMS=cpu, see
conftest.py) — same kernel body, Python-evaluated — so tier-1 exercises
the pallas path without a TPU. The gather path is the bit-reference:
kernel-level tests are allclose (the online softmax reassociates the
reduction)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.attention.paged import (STEP_BYTES, STEP_VIEWS,
                                               blocks_per_step,
                                               paged_decode_attention,
                                               paged_decode_reference,
                                               tiles_run)

from paged_attention_util import CELL_SHAPES, cell_problem, row_bytes


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


@pytest.mark.parametrize("H,Hkv,Dh,nb,window,bs", CELL_SHAPES)
def test_paged_kernel_matches_reference_at_cell_shapes(
        devices, pallas_interpret, H, Hkv, Dh, nb, window, bs):
    """The tile walk against the dense gathered softmax at the serving
    cells' head shapes, every slot at another edge: an empty cache, a
    block's last and first position, a tile's last, first and second, the
    table's last; unused entries naming the trash block."""
    q, kp, vp, tables, lengths = cell_problem(H, Hkv, Dh, nb, window, bs=bs)
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
    rb = row_bytes(Hkv, Dh)
    P, floor = blocks_per_step(nb, bs, rb), blocks_per_step(nb, bs)
    if bs == 16:
        assert P == floor
        assert P * bs == min(nb * bs, 128) or 128 <= P * bs <= 256, P
    else:
        assert floor < P == min(nb, STEP_VIEWS,
                                -(-STEP_BYTES // (2 * bs * rb)))
    q, kp, vp, tables, lengths = cell_problem(H, Hkv, Dh, nb, window, bs=bs)
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
def test_a_slots_bad_block_stays_its_own(devices, pallas_interpret, H, Hkv,
                                         Dh, nb, window, bs):
    """Fault isolation between slots: a ref whose entry a step's slot
    does not attend still holds the block it fetched for the slot
    before, and a probability of 0 times that block's NaN would be a
    NaN. With every block that every second slot ATTENDS poisoned (K
    and V, infinities too), the slots between them read what the
    reference reads."""
    q, kp, vp, tables, lengths = cell_problem(H, Hkv, Dh, nb, window, bs=bs)
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
