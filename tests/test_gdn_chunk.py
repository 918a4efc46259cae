"""Gated DeltaNet's chunk form (``kda.gdn_chunk``: the portable XLA form
and the Mosaic kernel, interpreted here) against the token recurrence,
across chunk borders and under strong decays. The rest of the rule and
the configuration are tests/test_qwen3_next.py."""

import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.attention import kda

from test_qwen3_next import _chunk, _rule_inputs


# a chunk shorter than a sub-chunk, whole sub-chunks and a part of one under
# decays strong enough to overflow exp(-G), and sub-chunks of 4 with the
# chunk's border at every offset of one; the kernel at the serving cell's
# kind of shapes: heads of 128, sub-chunks of 64, a chunk of 512 whose state
# goes on to the next at a sub-chunk's border and inside one
@pytest.mark.parametrize("form,T,H,D,sub,strong,cuts", [
    ("xla", 5, 3, 8, 64, False, (2,)), ("xla", 150, 3, 8, 64, True, (75,)),
    ("xla", 13, 3, 8, 4, True, (4, 5, 6, 7)),
    ("mosaic", 5, 2, 128, 64, False, (2,)),
    ("mosaic", 150, 2, 128, 64, True, (75,)),
    ("mosaic", 512, 4, 128, 64, True, (256, 203))])
def test_gdn_chunk_is_the_token_recurrence(form, T, H, D, sub, strong, cuts):
    """1e-5: float32 at the highest matmul precision on both sides; the
    chunkwise form sums a sub-chunk's writes in another order."""
    args = _rule_inputs(T, H, D, T, strong)
    chunk = _chunk(form, H, D, sub)
    o, s = kda.gdn_recurrence(*args)
    o2, s2 = chunk(*args)
    assert float(jnp.abs(o).max()) > 0.05
    np.testing.assert_allclose(np.asarray(o2), np.asarray(o), atol=1e-5)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(s), atol=1e-5)
    # two chunks, the state handed from one to the next, are the one
    for cut in cuts:
        first = tuple(a[:cut] for a in args[:5])
        rest = tuple(a[cut:] for a in args[:5])
        o3, s3 = chunk(*first, args[5])
        o4, s4 = chunk(*rest, s3)
        np.testing.assert_allclose(np.asarray(jnp.concatenate([o3, o4])),
                                   np.asarray(o), atol=1e-5)
        np.testing.assert_allclose(np.asarray(s4), np.asarray(s), atol=1e-5)
    # a padding token (g = 0, b = 0) leaves the state alone
    pad = tuple(jnp.concatenate([a, jnp.zeros_like(a[:3])])
                for a in args[:5])
    _, s5 = chunk(*pad, args[5])
    np.testing.assert_allclose(np.asarray(s5), np.asarray(s), atol=1e-5)
