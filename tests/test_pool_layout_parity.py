"""The paged pool's HBM layout changed (heads folded into the minor
dimension, pools carried through the layer loop and addressed at
``[layer, block]``); what is stored did not. Every paged program family
(plain, int8, LoRA, speculative verify, fused horizon, GQA + rotary +
window) is served here on the gather path and on the Pallas kernel
(interpret mode) and held, token for token and byte for byte (one
rotary case, and the kernel's floats since its tile walk: to one unit in
the last place), to what the tree BEFORE
the change produced: ``tests/data/pool_layout_parity.npz``
was recorded from commit 60f8ab9 with ``python
tests/test_pool_layout_parity.py --record``. Pools are compared through a
``[L, N, block, Hkv, Dh]`` view, stale lanes included, so a write that
lands in another layer's block or another head's lanes shows even where
no token moves.

Every block but each layer's TRASH block (block 0 of the layer, ``l*N``
of the stack) is held to the record as before. The trash blocks are held
only to "finite" since PR 36: the kernel's work list is cut from the
slots that decode, so an inactive slot's row enters the next layer from
a zero attention output and not from attention over the trash block, and
what such a row then writes (always to a trash block, which no grid step
of any slot reads any more) differs from the record's. Nothing a request
owns or emits may move for that.
"""

import functools
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.engine import InferenceEngine
from deepspeed_tpu.inference.serving import ServeRequest, ServingEngine
from deepspeed_tpu.models import gpt
from deepspeed_tpu.runtime.lora import add_lora, adapter_state_dict

DATA = pathlib.Path(__file__).parent / "data" / "pool_layout_parity.npz"

VARIANTS = ("plain", "gqa_rotary_window", "int8", "lora", "int8_lora",
            "verify", "verify_int8", "horizon3", "horizon3_int8",
            "prefix_cow")
IMPLS = ("gather", "pallas")


def _model(variant):
    over = {}
    if variant == "gqa_rotary_window":
        over = dict(rotary_dim=4, use_wpe=False, n_kv_heads=2,
                    attn_window=6)
    cfg = gpt.GPTConfig(vocab_size=128, n_layers=3, n_heads=4, d_model=32,
                        max_seq_len=64, use_flash_attention=False,
                        remat=False, dtype=jnp.float32, **over)
    return cfg, gpt.init_params(jax.random.PRNGKey(0), cfg)


def _adapter(params, seed=3, rank=4):
    lp = add_lora(params, rng=jax.random.PRNGKey(seed), rank=rank,
                  alpha=2.0 * rank)
    r = np.random.default_rng(seed)
    blk = {}
    for t, e in lp["block"].items():
        e = dict(e)
        if "lora_b" in e:
            e["lora_b"] = jnp.asarray(
                r.standard_normal(e["lora_b"].shape) * 0.05, jnp.float32)
        blk[t] = e
    return adapter_state_dict(dict(lp, block=blk))


def serve_case(variant, impl):
    """Tokens of every request and the pools as the run leaves them."""
    cfg, params = _model(variant)
    eng = InferenceEngine(config=cfg, params=params, dtype=jnp.float32)
    kw = dict(num_slots=2, block_size=4, num_blocks=22, prefill_chunk=4,
              decode_impl=impl)
    if "int8" in variant:
        kw["kv_quant"] = "int8"
    if "lora" in variant:
        kw.update(lora_serve=True, lora_pool_blocks=2, lora_max_rank=4,
                  lora_rank_block=4)
    if variant.startswith("verify"):
        kw.update(spec_decode=True, spec_k=3)
    if variant.startswith("horizon3"):
        kw["decode_horizon"] = 3
    if variant == "prefix_cow":
        kw["prefix_cache"] = True
    srv = ServingEngine(eng, **kw)
    r = np.random.default_rng(5)
    # three requests over two slots: the third reuses a freed slot's
    # blocks, so stale lanes of an earlier owner stay in the pool
    prompts = [r.integers(1, 128, n).astype(np.int32) for n in (5, 11, 7)]
    if variant.startswith("verify"):
        # repetitive prompts: the n-gram drafter then gets acceptances
        prompts = [np.tile(p[:3], 4)[:len(p) + 2] for p in prompts]
    if variant == "prefix_cow":
        # a shared 6-token prefix that ends mid-block: copy-on-write
        prompts = [np.concatenate([prompts[1][:6], p[:4]]) for p in prompts]
    reqs = []
    for i, p in enumerate(prompts):
        extra = {}
        if "lora" in variant and i != 1:      # request 1 stays base-only
            extra["adapter_id"] = "t1"
        reqs.append(ServeRequest(rid=i, prompt=p, max_new_tokens=7, **extra))
    if "lora" in variant:
        srv.register_adapter("t1", _adapter(params))
    out = srv.run(reqs)
    L, Hkv, Dh = cfg.n_layers, cfg.kv_heads, cfg.head_dim
    c = srv.cache
    got = {"tokens": np.concatenate([np.asarray(out[i], np.int32)
                                     for i in range(len(reqs))])}
    for name in ("k", "v"):
        got[name] = np.asarray(getattr(c, name)).reshape(
            L, c.num_blocks, c.block_size, Hkv, Dh)
    if c.quantized:
        got["k_scale"] = np.asarray(c.k_scale)
        got["v_scale"] = np.asarray(c.v_scale)
    return got


@pytest.fixture(scope="module")
def recorded():
    with np.load(DATA) as z:
        return {k: z[k] for k in z.files}


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("variant", VARIANTS)
def test_tokens_and_pools_equal_the_parent(devices, pallas_interpret,
                                           recorded, variant, impl):
    got = serve_case(variant, impl)
    keys = sorted(k.split("/", 2)[2] for k in recorded
                  if k.startswith(f"{variant}/{impl}/"))
    assert keys == sorted(got), (keys, sorted(got))
    for name in keys:
        want = recorded[f"{variant}/{impl}/{name}"]
        assert got[name].dtype == want.dtype, name
        if name != "tokens":
            # [L, N, ...]: block 0 of a layer is its trash block
            assert got[name].shape == want.shape, name
            assert np.isfinite(got[name][:, 0]).all(), name
            got[name], want = got[name][:, 1:], want[:, 1:]
        if impl == "pallas" and want.dtype == np.float32:
            # since PR 29 the kernel walks 128-token tiles with all heads
            # of a tile in one product (the record's walked a block a
            # step, a head at a time): its sums associate otherwise, and
            # through the next layer's input a tenth of the pools' floats
            # move by a unit in the last place (at most 6e-7 of values up
            # to 2.2), the trash blocks' with them. Tokens and the int8
            # payload stay equal to the last bit
            np.testing.assert_allclose(got[name], want, rtol=0, atol=1e-6)
            continue
        if variant == "gqa_rotary_window" and name in ("k", "v"):
            # the one case not equal to the last bit: rotary's
            # multiply-adds feed the pool write directly, and the CPU
            # compiler contracts them differently now that the write
            # takes a folded row. 2-4% of K's elements move by one unit
            # in the last place (at most 4.5e-8), and through the next
            # layer's input some of V's; every token is equal
            np.testing.assert_allclose(got[name], want, rtol=0, atol=1e-6)
            continue
        np.testing.assert_array_equal(
            got[name], want, err_msg=f"{variant}/{impl}: {name} differs "
            f"from what the parent wrote")


def test_recorded_runs_exercise_what_they_name(recorded):
    """The record is of live traffic: every pool holds written blocks
    beyond the trash block, int8 pools are int8 with scales, and the
    variants do not all emit the same stream."""
    streams = set()
    for variant in VARIANTS:
        for impl in IMPLS:
            k = recorded[f"{variant}/{impl}/k"]
            assert np.abs(k[:, 1:].astype(np.float32)).sum() > 0
            assert (k.dtype == np.int8) == ("int8" in variant)
            assert (f"{variant}/{impl}/k_scale" in recorded) \
                == ("int8" in variant)
            streams.add(recorded[f"{variant}/{impl}/tokens"].tobytes())
    assert len(streams) >= 4


if __name__ == "__main__":
    assert sys.argv[1:] == ["--record"], "usage: --record (on the parent)"
    import jax.experimental.pallas as pl
    pl.pallas_call = functools.partial(pl.pallas_call, interpret=True)
    rec = {}
    for variant in VARIANTS:
        for impl in IMPLS:
            for name, a in serve_case(variant, impl).items():
                rec[f"{variant}/{impl}/{name}"] = a
            print(variant, impl, rec[f"{variant}/{impl}/tokens"].tolist())
    DATA.parent.mkdir(exist_ok=True)
    np.savez_compressed(DATA, **rec)
    print("wrote", DATA, DATA.stat().st_size, "bytes")
