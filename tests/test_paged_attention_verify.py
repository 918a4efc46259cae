"""The paged-attention kernel on a verify chunk (``q_len`` > 1) against its
gather reference, over tiles cut by the bytes. Interpret mode, as
tests/test_paged_attention.py."""

import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.attention.paged import (paged_verify_attention,
                                               paged_verify_reference)

from paged_attention_util import BYTE_SHAPES, cell_problem


@pytest.mark.parametrize("G", [2, 5])
@pytest.mark.parametrize("H,Hkv,Dh,nb,window,bs", BYTE_SHAPES)
def test_paged_verify_matches_reference_at_byte_tiles(
        devices, pallas_interpret, H, Hkv, Dh, nb, window, bs, G):
    """A verify chunk (``q_len`` > 1) over tiles cut by the bytes: chunk
    query i of a slot at every edge attends positions up to its own, the
    chunk's last query in the tile after its first where the chunk
    straddles a tile's edge."""
    q, kp, vp, tables, lengths = cell_problem(H, Hkv, Dh, nb, window, bs=bs)
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
