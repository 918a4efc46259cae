"""The cache-dialect seam (inference/dialect.py): one record a dialect,
looked up once. Every number below is what the tree BEFORE the seam held
(PR 44: PagedKVCache's state and byte attributes, the four per-module
``refuse`` messages, InferenceEngine.prefill_attended /
mla_prefill_tiles), for the five configurations of ``tests/*_util.py`` and
GPT-2, so that the record is shown to say what the predicate chains said."""

import jax
import jax.numpy as jnp
import pytest

import dots_vlm_util
import exaone_moe_util
import jamba_util
import kimi_linear_util
import zaya_util
from deepspeed_tpu.inference import (cca, dialect, engine, hybrid, latent,
                                     linear)
from deepspeed_tpu.inference.engine import InferenceEngine
from deepspeed_tpu.inference.paged_cache import PagedKVCache
from deepspeed_tpu.models import gpt

F32 = "float32"
OCCUPIED = [0, 0, 112, 112, 512, 512, 912, 912]
TILES = [128, 128, 128, 256, 640, 640, 1024, 1024]

# name -> (config, owner, (k, v) leaves' shapes at 48 blocks of 4 and 2
# slots, the cache's attributes, table columns, the refusal's words,
# prefill_reads over GRID at blocks of 16 in a row of 64, flash steps at
# starts 0, 3, 37 and blocks of 4)
CASES = {
    "gpt2": (
        lambda: gpt.GPTConfig(vocab_size=96, n_layers=2, n_heads=4,
                              d_model=32, max_seq_len=96, dtype=jnp.float32),
        lambda: engine.DIALECT, [(2, 49, 4, 32), (2, 49, 4, 32)],
        dict(bytes_per_token=512), 24, None, TILES, [0, 0, 0]),
    "exaone": (
        exaone_moe_util.tiny_config, lambda: hybrid.DIALECT,
        [(2, 49, 4, 32), (6, 7, 4, 32)] * 2,
        dict(bytes_per_token=512, window_bytes=36864, ring_blocks=3), 27,
        # the full layers' tiles whatever the window (the ring's) says
        ("sliding-window layers (bounded per-slot window state)",
         "EXPERT_SHARE"), TILES, [0, 0, 0]),
    "dots": (
        dots_vlm_util.tiny_config, lambda: latent.DIALECT, [(4, 49, 4, 128)],
        dict(bytes_per_token=2048), 24,
        ("a latent (MLA) cache row (one pool of latents, no K/V heads)",
         "LATENT_ATTENTION"), OCCUPIED, [4, 8, 44]),
    "zaya": (
        zaya_util.tiny_config, lambda: cca.DIALECT,
        [(4, 49, 4, 16), (4, 2, 2, 48), (4, 2, 8), (4, 49, 4, 16)],
        dict(bytes_per_token=512, cca_tail_bytes=3328), 24,
        ("convolutional (CCA) attention (a per-slot tail of the previous "
         "token rides beside the K and V pools)", "CCA_ATTENTION"),
        OCCUPIED, [0, 0, 0]),
    "kimi": (
        kimi_linear_util.tiny_config, lambda: linear.DIALECT,
        [(2, 49, 4, 128), (5, 2, 4, 8, 8), (5, 2, 288)],
        dict(bytes_per_token=1024, recurrent_state_bytes=10240,
             conv_tail_bytes=11520), 24,
        ("a per-slot recurrent state (written by its linear-attention "
         "layers: it summarises the whole history and rides beside the "
         "paged pool)", "LINEAR_ATTENTION"), OCCUPIED, [2, 4, 22]),
    "jamba": (
        jamba_util.tiny_config, lambda: linear.DIALECT,
        [(2, 49, 4, 8), (6, 2, 8, 64), (6, 2, 192), (2, 49, 4, 8)],
        dict(bytes_per_token=128, recurrent_state_bytes=24576,
             conv_tail_bytes=9216), 24,
        ("a per-slot recurrent state (written by its state-space layers: "
         "it summarises the whole history and rides beside the paged "
         "pool)", "STATE_SPACE"), TILES, [0, 0, 0]),
}
GRID = [(s, n) for s in (0, 100, 512, 900) for n in (1, 128)]
BYTES = ("window_bytes", "cca_tail_bytes", "recurrent_state_bytes",
         "conv_tail_bytes", "ring_blocks")


@pytest.fixture(params=sorted(CASES))
def case(request):
    make, owner, *rest = CASES[request.param]
    return (make(), owner(), *rest)


def test_exactly_one_dialect_owns_a_config(case):
    cfg, owner = case[:2]
    own = [d for d in (linear.DIALECT, hybrid.DIALECT, latent.DIALECT,
                       cca.DIALECT) if d.owns(cfg)]
    assert own[:1] == ([] if owner is engine.DIALECT else [owner])
    # the one config two predicates answer: latent layers beside a
    # recurrent state, which is why the recurrent state is asked first
    assert own[1:] in ([], [latent.DIALECT]) \
        and (len(own) == 2) == hasattr(cfg, "kda_layers")
    assert dialect.of(cfg) is owner
    assert dialect.of(gpt.GPTConfig()) is engine.DIALECT
    eng = InferenceEngine.__new__(InferenceEngine)      # as the size tools
    eng.cfg = cfg
    assert eng.dialect is owner and "dialect" in vars(eng)


def test_new_state_holds_what_the_cache_held(case):
    cfg, d, shapes = case[:3]
    k, v = d.new_state(cfg, 49, 4, 2, jnp.float32)
    leaves = jax.tree_util.tree_leaves((k, v))
    assert [a.shape for a in leaves] == shapes
    assert {str(a.dtype) for a in leaves} == {F32}
    assert d.pool(k).shape == shapes[0]
    assert (d.state is None) == (d is engine.DIALECT)
    assert d.state is None or isinstance(k, d.state)
    # a recurrent state is float32 whatever the pools hold
    kb, _ = d.new_state(cfg, 49, 4, 2, jnp.bfloat16)
    assert str(d.pool(kb).dtype) == "bfloat16"
    assert {str(a.dtype) for a in jax.tree_util.tree_leaves(kb)} == (
        {"bfloat16", F32} if d is linear.DIALECT else {"bfloat16"})


def test_cache_attributes_are_the_parents(case):
    cfg, d, shapes, want, columns = case[:5]
    cache = PagedKVCache(cfg, num_slots=2, block_size=4, dtype=jnp.float32)
    assert cache.dialect is d and cache.num_blocks == 49
    assert cache.bytes_per_token == want["bytes_per_token"]
    assert {a: getattr(cache, a) for a in BYTES} \
        == {a: want.get(a, 0) for a in BYTES}
    assert cache.slot_state_bytes == cache.window_bytes \
        + cache.recurrent_state_bytes + cache.conv_tail_bytes
    assert cache.tables.shape == (2, columns)
    assert [a.shape for a in jax.tree_util.tree_leaves(cache.pools)] == shapes
    # the budget buys what the slots hold first, then blocks
    per_block = 4 * cache.bytes_per_token
    budget = cache.slot_state_bytes + 10 * per_block
    assert PagedKVCache(cfg, num_slots=2, block_size=4, dtype=jnp.float32,
                        hbm_budget_bytes=budget).num_blocks == 11


def test_the_dialect_says_which_decode_kernel_cuts_the_tile(case):
    """``paged_decode``'s tile follows a pool row's bytes and ``mla_decode``
    keeps the 128-token rule: the record says which attends its rows (a
    recurrent state's paged layers are latents in one config and K/V heads
    in the other), the cache keeps the answer for ``kv_steps``, and
    ``rows_plan`` cuts the grid by it."""
    from deepspeed_tpu.ops.attention.paged import blocks_per_step
    cfg, d, shapes = case[:3]
    cache = PagedKVCache(cfg, num_slots=2, block_size=4, dtype=jnp.float32)
    latents = d is latent.DIALECT or hasattr(cfg, "kda_layers")
    want = None if latents else 4 * shapes[0][-1]
    assert cache.tile_row_bytes == want
    assert d.tile_row_bytes(cfg, d.pool(cache.k)) == want
    if d is engine.DIALECT or d is hybrid.DIALECT:
        return                                  # plans of their own
    plan = dialect.rows_plan(cfg, (cache.k, cache.v), cache.tables,
                             cache.lengths, None)
    assert plan.cut == (24, 4, None, 1, blocks_per_step(24, 4, want))


def test_the_one_refusal_says_what_each_module_said(case):
    cfg, d, words = case[0], case[1], case[5]
    if words is None:
        assert d.refusal is None
        dialect.refuse(cfg, "prefix sharing (prefix_cache)")    # nothing
        return
    rule, doc = words
    with pytest.raises(ValueError) as e:
        dialect.refuse(cfg, "prefix sharing (prefix_cache)")
    assert str(e.value) == (
        f"prefix sharing (prefix_cache) is not supported for a model with "
        f"{rule}: see docs/{doc}.md")
    with pytest.raises(ValueError, match="int8 KV pools"):
        PagedKVCache(cfg, num_slots=2, block_size=4, kv_quant="int8")


def test_prefill_reads_and_flash_steps_are_the_parents(case):
    cfg, d, reads, steps = case[0], case[1], case[6], case[7]
    eng = InferenceEngine.__new__(InferenceEngine)
    eng.cfg = cfg
    assert [eng.prefill_attended(s, n, 16, 64) for s, n in GRID] == reads
    assert [eng.mla_prefill_tiles(s, 4) for s in (0, 3, 37)] == steps
    assert d.needs_slot == (d in (cca.DIALECT, linear.DIALECT))
    # the int8 pools' requantising write reads the whole row
    assert {eng.prefill_attended(s, n, 16, 64, True)
            for s, n in GRID} == {1024}
    if d is engine.DIALECT:
        # a window starts the read at its oldest key's tile
        eng.cfg = gpt.GPTConfig(attn_window=128)
        assert [eng.prefill_attended(s, n, 16, 64) for s, n in GRID] \
            == [128, 128, 128, 256, 256, 256, 256, 384]
