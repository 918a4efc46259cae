"""int8 paged KV-cache quantization unit tests (tentpole:
ops/quantizer.py KV helpers + the scale-aware pool layout in
inference/paged_cache.py + the dequantize-in-kernel paged attention in
ops/attention/paged.py).

The quantizer helpers are checked against a pure-numpy re-derivation
(round-trip error bound, exact re-round stability, live-mask zeroing);
the kernel tests run the pallas flash-decode in INTERPRET mode with
int8 pools + scales against the fp gather reference, bounding the
attention-output error by the per-block quantization step
(docs/KV_QUANT.md)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import gpt
from deepspeed_tpu.inference.paged_cache import PagedKVCache
from deepspeed_tpu.ops import quantizer
from deepspeed_tpu.ops.attention.paged import (paged_decode_attention,
                                               paged_decode_reference,
                                               paged_verify_attention,
                                               paged_verify_reference)
from deepspeed_tpu.ops.quantizer import (kv_block_scales,
                                         kv_dequantize_blocks,
                                         kv_quantize_blocks,
                                         kv_requantize_blocks,
                                         resolve_kv_quant)


def tiny(**over):
    return gpt.GPTConfig(vocab_size=128, n_layers=2, n_heads=4, d_model=32,
                         max_seq_len=64, use_flash_attention=False,
                         remat=False, dtype=jnp.float32, **over)


# ---------------------------------------------------------------------------
# knob resolution
# ---------------------------------------------------------------------------

def test_resolve_kv_quant(monkeypatch):
    monkeypatch.delenv("DS_KV_QUANT", raising=False)
    assert resolve_kv_quant(None) == "off"            # default: off
    assert resolve_kv_quant("int8") == "int8"
    assert resolve_kv_quant(True) == "int8"
    assert resolve_kv_quant(False) == "off"
    monkeypatch.setenv("DS_KV_QUANT", "int8")
    assert resolve_kv_quant(None) == "int8"
    assert resolve_kv_quant("off") == "off"           # explicit beats env
    monkeypatch.setenv("DS_KV_QUANT", "fp4")
    with pytest.raises(ValueError, match="DS_KV_QUANT"):
        resolve_kv_quant(None)


# ---------------------------------------------------------------------------
# numpy-reference round trips
# ---------------------------------------------------------------------------

def _np_roundtrip(x):
    """Independent numpy re-derivation of the block quant math."""
    absmax = np.max(np.abs(x), axis=(-3, -1))
    scale = absmax / 127.0
    safe = np.where(scale > 0, scale, 1.0)[..., None, :, None]
    q = np.clip(np.round(x / safe), -127, 127).astype(np.int8)
    return q, scale, q.astype(np.float32) * scale[..., None, :, None]


def test_kv_quant_matches_numpy_reference(rng):
    x = rng.normal(size=(5, 8, 2, 16)).astype(np.float32) * 3.0
    q_ref, s_ref, deq_ref = _np_roundtrip(x)
    s = kv_block_scales(jnp.asarray(x))
    q = kv_quantize_blocks(jnp.asarray(x), s)
    deq = kv_dequantize_blocks(q, s)
    np.testing.assert_allclose(np.asarray(s), s_ref, rtol=1e-6)
    # round(x/s) at exact .5 boundaries may tie-break differently
    # between numpy and XLA; bound by one quantization level instead
    assert int(np.sum(np.asarray(q).astype(np.int32)
                      != q_ref.astype(np.int32))) == 0 or \
        np.max(np.abs(np.asarray(q).astype(np.int32)
                      - q_ref.astype(np.int32))) <= 1
    np.testing.assert_allclose(np.asarray(deq), deq_ref,
                               atol=float(s_ref.max()), rtol=0)


def test_kv_quant_roundtrip_error_bound(rng):
    """|dequant - original| <= scale/2 elementwise — the tolerance
    model every downstream parity bound builds on."""
    x = rng.normal(size=(7, 8, 4, 8)).astype(np.float32) * 10.0
    q, s = kv_requantize_blocks(jnp.asarray(x))
    deq = np.asarray(kv_dequantize_blocks(q, s))
    err = np.abs(deq - x)
    bound = (np.asarray(s) / 2.0 + 1e-7)[..., None, :, None]
    assert (err <= bound).all(), float((err - bound).max())


def test_kv_quant_exact_requant_stability():
    """Re-quantizing a dequantized block with the SAME scale is exact:
    the read-modify-requantize write path replays untouched lanes
    bit-identically as long as the block absmax doesn't move."""
    rng = np.random.default_rng(3)
    x = rng.normal(size=(4, 8, 2, 8)).astype(np.float32)
    q1, s1 = kv_requantize_blocks(jnp.asarray(x))
    deq = kv_dequantize_blocks(q1, s1)
    q2 = kv_quantize_blocks(deq, s1)
    np.testing.assert_array_equal(np.asarray(q1), np.asarray(q2))


def test_kv_quant_live_mask_drops_stale_lanes():
    """A freed block's garbage (huge stale values) must not inflate the
    new owner's scale: requantize with a live mask zeroes dead token
    rows BEFORE the absmax."""
    x = np.ones((1, 8, 2, 4), np.float32)
    x[0, 5:] = 1e6                                    # stale garbage
    live = jnp.asarray(np.arange(8) < 5)[None]
    q, s = kv_requantize_blocks(jnp.asarray(x), live)
    assert float(jnp.max(s)) == pytest.approx(1.0 / 127.0)
    deq = np.asarray(kv_dequantize_blocks(q, s))
    np.testing.assert_allclose(deq[0, :5], 1.0, atol=1e-2)
    np.testing.assert_array_equal(deq[0, 5:], 0.0)    # zeroed, not 1e6


def test_kv_quant_zero_block_is_safe():
    """The all-zero trash block yields scale 0 and finite round trips
    (the guarded divide) — no NaN/inf ever enters the pool."""
    z = jnp.zeros((2, 8, 2, 4), jnp.float32)
    q, s = kv_requantize_blocks(z)
    assert float(jnp.max(jnp.abs(s))) == 0.0
    assert np.isfinite(np.asarray(q)).all()
    np.testing.assert_array_equal(
        np.asarray(kv_dequantize_blocks(q, s)), 0.0)


# ---------------------------------------------------------------------------
# int8 pool layout + budget accounting
# ---------------------------------------------------------------------------

def test_paged_cache_int8_pool_layout(devices):
    cfg = tiny()
    c = PagedKVCache(cfg, num_slots=2, block_size=4, num_blocks=6,
                     kv_quant="int8")
    assert c.pool_dtype == jnp.int8
    assert c.k.dtype == jnp.int8 and c.v.dtype == jnp.int8
    assert c.k_scale.shape == (cfg.n_layers, c.num_blocks, cfg.kv_heads)
    assert c.k_scale.dtype == jnp.float32
    assert c.bytes_per_token == gpt.kv_bytes_per_token(cfg, jnp.int8)
    assert c.scale_bytes_per_block == 2 * cfg.n_layers * cfg.kv_heads * 4
    # off mode: no scale pools, fp pool dtype, zero scale overhead
    c0 = PagedKVCache(cfg, num_slots=2, block_size=4, num_blocks=6,
                      kv_quant="off")
    assert c0.k_scale is None and c0.scale_bytes_per_block == 0
    assert c0.pool_dtype == c0.dtype


def test_paged_cache_int8_budget_doubles_blocks(devices):
    """At the same HBM budget the int8 pool admits ~4x the fp32 blocks
    (2x vs a bf16 pool), minus the fp32 scale sidecar — the capacity
    headline, derived from the allocator's own arithmetic."""
    cfg = tiny()
    per_tok_fp = gpt.kv_bytes_per_token(cfg, jnp.float32)
    budget = per_tok_fp * 4 * 10          # exactly 10 fp32 4-token blocks
    fp = PagedKVCache(cfg, num_slots=2, block_size=4,
                      hbm_budget_bytes=budget, dtype=jnp.float32,
                      kv_quant="off")
    q = PagedKVCache(cfg, num_slots=2, block_size=4,
                     hbm_budget_bytes=budget, dtype=jnp.float32,
                     kv_quant="int8")
    assert fp.free_blocks == 10          # budget // per_block (+trash)
    per_block_q = (gpt.kv_bytes_per_token(cfg, jnp.int8) * 4
                   + q.scale_bytes_per_block)
    assert q.free_blocks == budget // per_block_q
    assert q.free_blocks >= int(1.8 * fp.free_blocks)
    # usage accounting includes the scale sidecar
    q.allocate(0, 6)
    assert q.used_block_bytes() == 2 * per_block_q


def test_cache_bytes_per_token_dtype_aware(devices):
    cfg = tiny()
    fp, i8 = (PagedKVCache(cfg, num_slots=2, block_size=8, num_blocks=6,
                           dtype=jnp.float32, kv_quant=mode)
              for mode in ("off", "int8"))
    assert fp.bytes_per_token == 4 * i8.bytes_per_token   # no scales
    fp.allocate(0, 8)
    i8.allocate(0, 8)
    # the scale sidecar amortised over a block's tokens
    assert i8.bytes_per_token < i8.used_block_bytes() / 8 \
        < fp.used_block_bytes() / 8 == fp.bytes_per_token


# ---------------------------------------------------------------------------
# kernel parity: int8 pools through the pallas flash-decode (interpret)
# ---------------------------------------------------------------------------

def _quant_pool_problem(seed=0, B=3, Hkv=2, group=2, Dh=32, bs=8, NB=4):
    """fp pools + their int8 twins with per-(block, kv_head) scales;
    same distinct-table/trash-block-0 geometry as test_paged_attention's
    _pool_problem. Blocks are quantized per head and then folded to the
    stored ``[N, block, Hkv*Dh]`` rows."""
    rng = np.random.default_rng(seed)
    N = B * NB + 1
    q = jnp.asarray(rng.normal(size=(B, Hkv, group, Dh)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(N, bs, Hkv, Dh)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(N, bs, Hkv, Dh)), jnp.float32)
    ids = rng.permutation(np.arange(1, N))
    tables = jnp.asarray(ids.reshape(B, NB), jnp.int32)
    lengths = jnp.asarray([bs // 2, bs * 2 + 1, bs * NB - 1], jnp.int32)
    kq, ks = kv_requantize_blocks(kp)
    vq, vs = kv_requantize_blocks(vp)
    kp, vp, kq, vq = (a.reshape(N, bs, Hkv * Dh) for a in (kp, vp, kq, vq))
    return q, kp, vp, kq, ks, vq, vs, tables, lengths


# a block of 128 rows of 2 heads of 128: four a step in float32 (1 KiB a
# row), eight in int8 (a quarter of the bytes, and the most views a step)
LARGE_BLOCK = dict(Hkv=2, group=2, Dh=128, bs=128, NB=20)


@pytest.mark.parametrize("problem", [{}, LARGE_BLOCK],
                         ids=["block8", "block128-tile8"])
def test_paged_kernel_int8_matches_quant_reference(devices,
                                                   pallas_interpret,
                                                   problem):
    """The kernel's in-register dequantize == the gather reference over
    the SAME int8 pools: only softmax reassociation apart (allclose at
    the fp parity tolerance, not the quant tolerance). An int8 pool's
    tile follows ITS bytes: other entries a step than the float32 pool
    of the same blocks, per-tile scales and all."""
    from deepspeed_tpu.ops.attention.paged import (blocks_per_step,
                                                   pool_row_bytes)
    q, kp, _, kq, ks, vq, vs, tables, lengths = _quant_pool_problem(**problem)
    NB, bs = tables.shape[1], kq.shape[1]
    if problem:
        assert (blocks_per_step(NB, bs, pool_row_bytes(kq)),
                blocks_per_step(NB, bs, pool_row_bytes(kp))) == (8, 4)
    out = paged_decode_attention(q, kq, vq, tables, lengths, scale=0.25,
                                 k_scale=ks, v_scale=vs)
    ref = paged_decode_reference(q, kq, vq, tables, lengths, scale=0.25,
                                 k_scale=ks, v_scale=vs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def test_paged_kernel_int8_error_vs_fp_is_bounded(devices,
                                                  pallas_interpret):
    """int8 attention output vs the unquantized fp reference: the error
    is bounded by a small multiple of the largest quantization step
    (attention outputs are convex combinations of dequantized V rows,
    perturbed by the K-step through the softmax; docs/KV_QUANT.md)."""
    q, kp, vp, kq, ks, vq, vs, tables, lengths = _quant_pool_problem()
    out_q = paged_decode_attention(q, kq, vq, tables, lengths, scale=0.25,
                                   k_scale=ks, v_scale=vs)
    out_fp = paged_decode_reference(q, kp, vp, tables, lengths, scale=0.25)
    step = float(jnp.maximum(jnp.max(ks), jnp.max(vs)))
    err = float(np.max(np.abs(np.asarray(out_q) - np.asarray(out_fp))))
    assert err <= 8.0 * step, (err, step)


@pytest.mark.parametrize("problem", [{}, LARGE_BLOCK],
                         ids=["block8", "block128-tile8"])
@pytest.mark.parametrize("G", [2, 3])
def test_paged_verify_int8_matches_quant_reference(devices,
                                                   pallas_interpret, G,
                                                   problem):
    q, _, _, kq, ks, vq, vs, tables, lengths = _quant_pool_problem(**problem)
    B, Hkv, group, Dh = q.shape
    rng = np.random.default_rng(7)
    qg = jnp.asarray(rng.normal(size=(B, G, Hkv, group, Dh)), jnp.float32)
    lengths = jnp.maximum(lengths - G, 0)
    out = paged_verify_attention(qg, kq, vq, tables, lengths, scale=0.25,
                                 k_scale=ks, v_scale=vs)
    ref = paged_verify_reference(qg, kq, vq, tables, lengths, scale=0.25,
                                 k_scale=ks, v_scale=vs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)
