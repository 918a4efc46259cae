"""Every Pallas kernel through the Pallas->Mosaic lowering, from the CPU.

Interpret mode (how the parity suites run the kernels) checks neither block
tiling nor layouts. ``lower(lowering_platforms=("tpu",))`` runs the TPU
lowering without a chip and refuses, for one, a block whose last two
dimensions neither divide by (8, 128) nor equal the array's: the int8 paged
kernel's ``(1, Hkv)`` scale blocks were refused here at every shape while
every CPU test passed. What libtpu's Mosaic compiler then makes of a kernel
that lowers is tools/kernel_census.py's business, on the chip.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.attention import flash, paged
from deepspeed_tpu.ops.int8_matmul import fit_blocks, int8_matmul
from deepspeed_tpu.ops.sparse_attention import blocksparse
from deepspeed_tpu.ops.sparse_attention.sparsity_config import \
    FixedSparsityConfig

# (heads, kv_heads, head_dim, flash block): gpt2-medium, gpt2-1.5b, GQA
SHAPES = [pytest.param(16, 16, 64, 1024, id="gpt2-medium"),
          pytest.param(25, 25, 64, 1024, id="gpt2-1.5b"),
          pytest.param(32, 8, 128, 512, id="gqa-dh128")]


def S(shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype)


def lower_tpu(fn, *args) -> str:
    """StableHLO of ``fn`` lowered for the TPU platform; raises where the
    Mosaic lowering refuses the kernel."""
    return jax.jit(fn).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()


@pytest.mark.parametrize("H,Hkv,D,blk", SHAPES)
def test_flash_forward_and_backward_lower(H, Hkv, D, blk):
    q, kv = S((2, 1024, H, D)), S((2, 1024, Hkv, D))

    def fl(q, k, v):
        return flash.flash_attention(q, k, v, causal=True, block_q=blk,
                                     block_kv=blk)

    def grads(q, k, v):
        return jax.grad(lambda *a: fl(*a).astype(jnp.float32).sum(),
                        argnums=(0, 1, 2))(q, k, v)
    assert lower_tpu(fl, q, kv, kv).count("tpu_custom_call") == 1
    # forward (for the residuals), dq, dk/dv: under the names the
    # benchmark's trace readers find them by (harness/readers.py), with
    # the backward's sub-tile walk in their bodies or without
    text = lower_tpu(grads, q, kv, kv)
    assert text.count("tpu_custom_call") == 3
    for name in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        assert text.count(f'kernel_name = "{name}"') == 1, name
    # one walked block a head, or two walked and one whole below them
    assert flash.tile_census(1024, 1024, blk, blk) == (10, 16)


def test_flash_masks_segments_and_windows_lower():
    q, m, sg = S((2, 1024, 8, 64)), S((2, 1024), jnp.float32), \
        S((2, 1024), jnp.int32)

    def fl(q, k, v, m, sg):
        return flash.flash_attention(
            q, k, v, causal=True, block_q=256, block_kv=256, kv_mask=m,
            segment_ids=sg, window=300)
    assert "tpu_custom_call" in lower_tpu(fl, q, q, q, m, sg)


# the K-EXAONE cell's two tables: (slots, table entries, window)
KEXAONE_TABLES = {"kexaone-full-table256": (48, 256, None),
                  "kexaone-ring9-window128": (48, 9, 128)}


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("q_len", [1, 5], ids=["decode", "verify5"])
@pytest.mark.parametrize(
    "H,Hkv,D,blk", SHAPES + [pytest.param(64, 8, 128, name, id=name)
                             for name in KEXAONE_TABLES])
def test_paged_attention_lowers(H, Hkv, D, blk, q_len, quant):
    B, nb, window = KEXAONE_TABLES.get(blk, (8, 32, None))
    bs, N = 16, B * nb + 1
    pool = S((N, bs, Hkv * D), jnp.int8 if quant else jnp.bfloat16)
    scales = {"k_scale": S((N, Hkv), jnp.float32),
              "v_scale": S((N, Hkv), jnp.float32)} if quant else {}
    tables, lengths = S((B, nb), jnp.int32), S((B,), jnp.int32)
    if q_len == 1:
        fn, q = paged.paged_decode_attention, S((B, Hkv, H // Hkv, D))
    else:
        fn, q = paged.paged_verify_attention, \
            S((B, q_len, Hkv, H // Hkv, D))

    def call(q, k, v, t, ln, *sc):
        return fn(q, k, v, t, ln, scale=0.125, window=window,
                  **dict(zip(scales, sc)))
    assert "tpu_custom_call" in lower_tpu(call, q, pool, pool, tables,
                                          lengths, *scales.values())


# the serving cells' decode calls: (heads, kv heads, head size, slots,
# table entries, block, window)
MASKED_CALLS = [
    pytest.param(25, 25, 64, 17, 64, 16, None, id="gpt2-xl-chat"),
    pytest.param(64, 8, 128, 48, 256, 16, None, id="kexaone-full-table256"),
    pytest.param(64, 8, 128, 48, 9, 16, 128, id="kexaone-ring9-window128"),
    pytest.param(8, 2, 128, 40, 6, 1024, None, id="zaya1-block1024"),
    pytest.param(28, 4, 128, 24, 33, 128, 4096,
                 id="smallthinker-ring33-window4096"),
    pytest.param(28, 4, 128, 24, 128, 128, None,
                 id="smallthinker-full-table128"),
    pytest.param(20, 1, 128, 192, 24, 512, None, id="jamba2-block512"),
]


@pytest.mark.parametrize("H,Hkv,D,B,nb,bs,window", MASKED_CALLS)
def test_paged_attention_lowers_with_a_plan_of_the_active_slots(
        H, Hkv, D, B, nb, bs, window):
    """The work list cut from the slots that decode (a traced mask), and
    the zeroing of the rows it leaves out, through the TPU lowering at
    the cells' shapes, in the tile their pool's row gives (blocks of 128
    and of 512: four a step)."""
    pool = S((B * nb + 1, bs, Hkv * D))
    assert paged.blocks_per_step(nb, bs, paged.pool_row_bytes(pool)) == {
        16: 9 if nb == 9 else 8, 128: 4, 512: 4, 1024: 1}[bs]
    args = (S((B, Hkv, H // Hkv, D)), pool, pool, S((B, nb), jnp.int32),
            S((B,), jnp.int32))

    def call(q, k, v, t, ln, active=None):
        plan = paged.decode_plan(ln, nb, bs, row_bytes=paged.pool_row_bytes(k),
                                 window=window, active=active)
        return paged.paged_decode_attention(q, k, v, t, ln, scale=0.125,
                                            window=window, plan=plan)
    masked = lower_tpu(call, *args, S((B,), jnp.bool_))
    assert masked.count("tpu_custom_call") == 1
    # the mask costs the plan one select and the output one
    assert masked.count("stablehlo.select") \
        == lower_tpu(call, *args).count("stablehlo.select") + 2


def test_mla_decode_lowers_with_a_plan_of_the_active_slots():
    """The same for the latent kernel at the dots.vlm1 cell's shape: 16
    slots, 128 heads over rows of 640 lanes, 48 blocks of 512."""
    from deepspeed_tpu.ops.attention import mla
    B, H, row, nb, bs = 16, 128, 640, 48, 512
    args = (S((B, H, row)), S((B * nb + 1, bs, row)), S((B, nb), jnp.int32),
            S((B,), jnp.int32), S((B,), jnp.bool_))

    def call(q, pool, t, ln, active):
        plan = paged.decode_plan(ln, nb, bs, active=active)
        return mla.mla_decode_attention(q, pool, t, ln, value_width=512,
                                        scale=0.07, plan=plan)
    assert lower_tpu(call, *args).count("tpu_custom_call") == 1


@pytest.mark.parametrize("H,keys", [(128, 512), (128, 1024), (32, 2048)])
def test_mla_prefill_step_lowers(H, keys):
    """The expanded prefill's flash step at both latent cells' shapes (a
    chunk of 512 queries in the lanes, 1, 2 and 4 key blocks of 512, 128 +
    64 score dimensions): one Mosaic call of ONE grid step a head, its
    carry updated in place."""
    from deepspeed_tpu.ops.attention import mla
    C, f32 = 512, jnp.float32
    carry = (S((H, 1, C), f32), S((H, 1, C), f32), S((H, 128, C), f32))
    args = (S((H, 128, C)), S((H, 64, C)), S((H, keys, 128)), S((keys, 64)),
            S((H, 128, keys)), S((keys,), jnp.int32), S((C,), jnp.int32))

    def call(carry, *a):
        return mla.mla_prefill_step(carry, *a, 0.11)
    text = lower_tpu(call, carry, *args)
    assert text.count("tpu_custom_call") == 1
    for out, operand in ((0, 7), (1, 8), (2, 9)):
        assert f"output_tuple_indices = [{out}], operand_index = {operand}" \
            in text
    grids = [e.params["grid_mapping"].grid
             for e in jax.make_jaxpr(call)(carry, *args).eqns
             if e.primitive.name == "pallas_call"]
    assert grids == [(H, 1)]


@pytest.mark.parametrize("M,K,N", [(8, 1024, 3072), (256, 4096, 4096)])
def test_int8_matmul_lowers(M, K, N):
    bk, bn = fit_blocks(K, N)

    def mm(x, q, s):
        return int8_matmul(x, q, s, block_k=bk, block_n=bn)
    assert "tpu_custom_call" in lower_tpu(
        mm, S((M, K)), S((K, N), jnp.int8), S((1, N), jnp.float32))


@pytest.mark.parametrize("H,D", [(16, 64), (25, 64), (8, 128)])
def test_blocksparse_lowers(H, D):
    layout = np.asarray(FixedSparsityConfig(num_heads=H, block=128)
                        .make_layout(1024))
    q = S((2, 1024, H, D))

    def bsa(q, k, v):
        return blocksparse.blocksparse_attention(q, k, v, layout,
                                                 causal=True,
                                                 use_kernel=True)
    assert "tpu_custom_call" in lower_tpu(bsa, q, q, q)
