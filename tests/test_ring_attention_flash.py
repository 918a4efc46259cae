"""Ring attention whose every step runs the Pallas flash kernel (interpret
mode on the CPU), and the kernel's static ``q_off`` that the ring's steps
stand on. The ring on the jnp path is tests/test_ring_attention.py."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.attention.flash import mha_reference
from deepspeed_tpu.ops.attention.ring import ring_attention
from deepspeed_tpu.parallel.mesh import MeshSpec, make_mesh


def test_ring_flash_kernel_matches_dense(devices, pallas_interpret):
    """use_flash=True routes every ring step through the Pallas flash
    kernel (interpret mode on CPU): parity incl. grads, GQA, packing."""
    mesh = make_mesh(MeshSpec(data=2, sequence=4))
    B, S, H, Hkv, D = 1, 256, 4, 2, 8
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (B, S, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, Hkv, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, Hkv, D), jnp.float32)
    segs = jnp.asarray(
        np.repeat(np.arange(4), 64)[None].astype(np.int32))
    out = ring_attention(q, k, v, mesh, causal=True, use_flash=True,
                         block_q=32, block_kv=32, segment_ids=segs)
    ref = mha_reference(q, k, v, causal=True, segment_ids=segs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)

    g_r = jax.grad(lambda q, k, v: jnp.sum(ring_attention(
        q, k, v, mesh, causal=True, use_flash=True, block_q=32,
        block_kv=32, segment_ids=segs) ** 2), argnums=(0, 1, 2))(q, k, v)
    g_d = jax.grad(lambda q, k, v: jnp.sum(mha_reference(
        q, k, v, causal=True, segment_ids=segs) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    for a, b, nm in zip(g_r, g_d, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-3, err_msg=nm)


@pytest.mark.parametrize("ring,S,blk,window", [
    pytest.param(4, 256, 32, 96, id="ring4-S256-blk32-W96"),
    # each device holds ONE 512 block: the diagonal step's block straddles
    # the causal diagonal and the next step's the window's lower edge, and
    # both hold several sub-tiles (flash.SUB_TILE) for the backward to walk
    pytest.param(2, 1024, 512, 640, id="ring2-S1024-blk512-W640")])
def test_ring_flash_window_matches_dense(devices, pallas_interpret, ring, S,
                                         blk, window):
    """Flash-kernel ring steps with a sliding window: the banded partial
    block (static q_off) goes through the kernel's offset index maps."""
    mesh = make_mesh(MeshSpec(data=8 // ring, sequence=ring))
    ks = jax.random.split(jax.random.PRNGKey(8), 3)
    q, k, v = (jax.random.normal(kk, (1, S, 2, 8), jnp.float32)
               for kk in ks)
    out = ring_attention(q, k, v, mesh, causal=True, use_flash=True,
                         block_q=blk, block_kv=blk, window=window)
    ref = mha_reference(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)
    # grads too: the q_off-shifted windowed BACKWARD index maps (the
    # clip-based first/last q-block computation in _flash_bwd) are
    # otherwise uncovered
    g_r = jax.grad(lambda q, k, v: jnp.sum(ring_attention(
        q, k, v, mesh, causal=True, use_flash=True, block_q=blk,
        block_kv=blk, window=window) ** 2), argnums=(0, 1, 2))(q, k, v)
    g_d = jax.grad(lambda q, k, v: jnp.sum(mha_reference(
        q, k, v, causal=True, window=window) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    for a, b, nm in zip(g_r, g_d, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-3, err_msg=nm)


def test_flash_block_q_off_primitive(devices, pallas_interpret):
    """flash_block_fwd with a static q_off equals the corresponding
    off-diagonal tile of a dense full-sequence attention: q rows sit
    q_off tokens after the block's first key."""
    from deepspeed_tpu.ops.attention.flash import flash_block_fwd
    S_loc, off = 64, 64          # q rows are tokens [64, 128), keys [0, 64)
    ks = jax.random.split(jax.random.PRNGKey(9), 3)
    q, k, v = (jax.random.normal(kk, (1, 2 * S_loc, 2, 8), jnp.float32)
               for kk in ks)
    o, lse = flash_block_fwd(q[:, S_loc:], k[:, :S_loc], v[:, :S_loc],
                             causal=True, block_q=32, block_kv=32,
                             window=96, q_off=off)
    # dense tile: full-seq windowed-causal attention restricted to
    # q-rows [64,128) x keys [0,64), renormalized over those keys only
    D = q.shape[-1]
    logits = jnp.einsum("bqhd,bkhd->bhqk", q[:, S_loc:],
                        k[:, :S_loc]) / np.sqrt(D)
    rows = off + np.arange(S_loc)[:, None]
    cols = np.arange(S_loc)[None, :]
    band = (rows >= cols) & (rows - cols < 96)
    logits = jnp.where(jnp.asarray(band)[None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    ref = jnp.einsum("bhqk,bkhd->bqhd", probs, v[:, :S_loc])
    valid = band.any(axis=1)                 # rows inside the band
    np.testing.assert_allclose(np.asarray(o)[0, valid],
                               np.asarray(ref)[0, valid],
                               rtol=2e-5, atol=2e-5)
    # lse is the banded logsumexp for in-band rows: both [H, S] slices
    ref_lse = np.asarray(jax.scipy.special.logsumexp(logits, axis=-1))[0]
    got_lse = np.asarray(lse)[0]
    np.testing.assert_allclose(got_lse[:, valid], ref_lse[:, valid],
                               rtol=2e-5, atol=2e-5)
