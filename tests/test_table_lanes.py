"""The engine's looked-up tables with whole rows of 128 lanes.

A ``[V, d]`` bf16 table with ``d % 128 != 0`` is stored column-major on a
v5e, and every program that looked a token up copied it whole first
(tests/test_pool_layout_aot.py reads that off the compiled programs). The
engine pads such a table once, at construction, and cuts the padding off
wherever it reads the table: here that is held to be the SAME mathematics,
to the bit, in every program family, and no change at all for a model
whose ``d`` already is a multiple of 128.
"""

import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference import engine as engine_mod
from deepspeed_tpu.inference.engine import (InferenceEngine, table_lanes,
                                            tied_logits, whole_lane_tables)
from deepspeed_tpu.inference.serving import ServeRequest, ServingEngine
from deepspeed_tpu.inference.speculative import generate_speculative
from deepspeed_tpu.models import gpt

V, S_MAX = 160, 64
# (d_model, heads): 192 is 1.5 x 128 lanes, 128 is whole
WIDTHS = {192: 3, 128: 4}
MODELS = list(itertools.product(WIDTHS, (True, False), (True, False)))
IDS = [f"d{d}-{'tied' if tie else 'untied'}-{'wpe' if wpe else 'rotary'}"
       for d, tie, wpe in MODELS]


def tiny(d, tie=True, wpe=True, heads=None, dtype=jnp.float32):
    cfg = gpt.GPTConfig(vocab_size=V, n_layers=2, n_heads=heads or WIDTHS[d],
                        d_model=d, max_seq_len=S_MAX, use_wpe=wpe,
                        tie_embeddings=tie, rotary_dim=None if wpe else 16,
                        use_flash_attention=False, remat=False, dtype=dtype)
    return cfg, gpt.init_params(jax.random.PRNGKey(0), cfg)


def engines(monkeypatch, cfg, params, dtype=jnp.float32, **kw):
    """(the engine as it is, one that stores the tables as it is handed
    them: what every engine was before)."""
    eng = InferenceEngine(config=cfg, params=params, dtype=dtype, **kw)
    with monkeypatch.context() as mp:
        mp.setattr(engine_mod, "whole_lane_tables", lambda p, specs: (p, ""))
        plain = InferenceEngine(config=cfg, params=params, dtype=dtype, **kw)
    assert plain.params["wte"]["embedding"].shape == (V, cfg.d_model)
    return eng, plain


def same(a, b):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("d", WIDTHS)
def test_stored_tables_have_whole_lanes(devices, d):
    cfg, params = tiny(d)
    eng = InferenceEngine(config=cfg, params=params, dtype=jnp.float32)
    lanes = -(-d // 128) * 128
    for name, rows in (("wte", V), ("wpe", S_MAX)):
        stored = eng.params[name]["embedding"]
        assert stored.shape == (rows, lanes)
        same(stored[:, :d], params[name]["embedding"])
        assert not np.asarray(stored[:, d:]).any()
    # nothing else is touched
    assert eng.params["block"]["qkv"]["kernel"].shape == (2, d, 3 * d)


def test_helpers_trace_nothing_for_whole_lanes():
    """For ``d % 128 == 0`` the stored table is the table and the traced
    lookup and head are text for text the plain formulas'."""
    table = jnp.ones((V, 256), jnp.bfloat16)
    assert table_lanes(table, 256) is table
    params = {"wte": {"embedding": table}, "lm_head": {"kernel": table.T}}
    stored, note = whole_lane_tables(params)
    assert stored is params and note == ""
    eng = InferenceEngine.__new__(InferenceEngine)
    eng.cfg = gpt.GPTConfig(vocab_size=V, n_layers=1, n_heads=4, d_model=256)
    idx = jnp.zeros((3, 5), jnp.int32)
    x = jnp.ones((3, 1, 256), jnp.bfloat16)
    assert str(jax.make_jaxpr(eng._wte)(params, idx)) == str(jax.make_jaxpr(
        lambda p, i: p["wte"]["embedding"][i])(params, idx))
    assert str(jax.make_jaxpr(tied_logits)(x, table)) \
        == str(jax.make_jaxpr(lambda x, t: x @ t.T)(x, table))


def test_partitioned_lanes_are_left_alone():
    """A table whose lanes a partition rule cuts over the mesh keeps its
    shape (padding would move the shards' boundaries), and the log line's
    note says so."""
    from jax.sharding import PartitionSpec as P
    params = {"wte": {"embedding": jnp.ones((V, 192))},
              "wpe": {"embedding": jnp.ones((S_MAX, 192))}}
    specs = {"wte": {"embedding": P(None, "model")},
             "wpe": {"embedding": P(None, None)}}
    out, note = whole_lane_tables(params, specs)
    assert out["wte"]["embedding"].shape == (V, 192)
    assert out["wpe"]["embedding"].shape == (S_MAX, 256)
    assert "wte lanes 192 left as they are" in note
    assert "wpe lanes 192->256" in note


@pytest.mark.parametrize("d,tie,wpe", MODELS, ids=IDS)
def test_lookup_and_logits_equal_the_unpadded_formula(devices, monkeypatch,
                                                      d, tie, wpe):
    cfg, params = tiny(d, tie, wpe)
    eng, plain = engines(monkeypatch, cfg, params)
    r = np.random.default_rng(d)
    tokens = jnp.asarray(r.integers(0, V, (2, 9)), jnp.int32)
    x = eng._embed(eng.params, tokens)
    ref = params["wte"]["embedding"][tokens]
    if wpe:
        ref = ref + params["wpe"]["embedding"][:9][None]
    same(x, ref)
    same(x, plain._embed(plain.params, tokens))
    h = jnp.asarray(r.standard_normal((2, 9, d)), jnp.float32)
    same(eng._logits(eng.params, h), plain._logits(plain.params, h))
    assert eng._logits(eng.params, h).shape == (2, 9, V)
    same(eng.forward(np.asarray(tokens)), plain.forward(np.asarray(tokens)))


@pytest.mark.parametrize("d,tie,wpe", MODELS, ids=IDS)
def test_generate_emits_the_tokens_it_emitted_before(devices, monkeypatch,
                                                     d, tie, wpe):
    """The static-cache programs: prefill and decode, plain and with the
    per-row positions of a left-padded batch."""
    cfg, params = tiny(d, tie, wpe)
    eng, plain = engines(monkeypatch, cfg, params)
    r = np.random.default_rng(d + 1)
    tokens = r.integers(1, V, (2, 10)).astype(np.int32)
    same(eng.generate(tokens, max_new_tokens=6),
         plain.generate(tokens, max_new_tokens=6))
    mask = np.ones_like(tokens)
    mask[1, :4] = 0
    same(eng.generate(tokens, max_new_tokens=6, attention_mask=mask),
         plain.generate(tokens, max_new_tokens=6, attention_mask=mask))
    same(eng.generate(tokens, max_new_tokens=6, temperature=0.9, top_k=7,
                      seed=5),
         plain.generate(tokens, max_new_tokens=6, temperature=0.9, top_k=7,
                        seed=5))


def _serve(eng, prompts, **kw):
    srv = ServingEngine(eng, num_slots=2, block_size=4, num_blocks=30,
                        prefill_chunk=8, **kw)
    out = srv.run([ServeRequest(rid=i, prompt=p, max_new_tokens=6)
                   for i, p in enumerate(prompts)])
    return [np.asarray(out[i]) for i in range(len(prompts))]


@pytest.mark.parametrize("d,tie,wpe", MODELS, ids=IDS)
def test_serving_emits_the_tokens_it_emitted_before(devices, monkeypatch,
                                                    d, tie, wpe):
    """The two paged programs of every run (chunked prefill, slot decode)."""
    cfg, params = tiny(d, tie, wpe)
    eng, plain = engines(monkeypatch, cfg, params)
    r = np.random.default_rng(d + 2)
    prompts = [r.integers(1, V, n).astype(np.int32) for n in (5, 11, 3)]
    for got, ref in zip(_serve(eng, prompts), _serve(plain, prompts)):
        same(got, ref)


@pytest.mark.parametrize("family", ["horizon", "verify", "extend"])
def test_other_program_families_read_the_padded_table(devices, monkeypatch,
                                                      family):
    """The fused decode horizon, the paged speculative verify and the
    static-cache chunk verify look tokens up too."""
    cfg, params = tiny(192)
    eng, plain = engines(monkeypatch, cfg, params)
    r = np.random.default_rng(7)
    prompts = [r.integers(1, V, n).astype(np.int32) for n in (6, 9)]
    if family == "extend":
        dcfg, dparams = tiny(192, heads=6)
        draft, dplain = engines(monkeypatch, dcfg, dparams)
        same(generate_speculative(eng, draft, prompts[0][None], 8, gamma=3),
             generate_speculative(plain, dplain, prompts[0][None], 8, gamma=3))
        return
    kw, program = ({"decode_horizon": 3}, eng._decode_horizon) \
        if family == "horizon" \
        else ({"spec_decode": True, "spec_k": 3}, eng._verify_slots)
    for got, ref in zip(_serve(eng, prompts, **kw),
                        _serve(plain, prompts, **kw)):
        same(got, ref)
    assert program._cache_size() == 1


def test_tensor_parallel_engine_constructs_and_agrees(devices, monkeypatch):
    """The tables are not cut over the model axis (gpt_partition_rules), so
    every shard holds the padded table whole."""
    cfg, params = tiny(192, heads=6)
    eng, _ = engines(monkeypatch, cfg, params)
    tp, tp_plain = engines(monkeypatch, cfg, params, mp_size=2)
    wte = tp.params["wte"]["embedding"]
    assert wte.shape == (V, 256)
    assert wte.sharding.shard_shape(wte.shape) == (V, 256)
    qkv = tp.params["block"]["qkv"]["kernel"]
    assert qkv.sharding.shard_shape(qkv.shape)[2] == qkv.shape[2] // 2
    tokens = np.random.default_rng(3).integers(1, V, (1, 8)).astype(np.int32)
    ref = eng.generate(tokens, max_new_tokens=5)
    same(tp.generate(tokens, max_new_tokens=5), ref)
    same(tp_plain.generate(tokens, max_new_tokens=5), ref)


def test_bf16_and_int8_engines_pad_the_table_too(devices, monkeypatch):
    """The engine's own precisions: the table is cast first and padded
    after, and weight-only int8 leaves it a float table."""
    cfg, params = tiny(192)
    tokens = np.random.default_rng(4).integers(1, V, (1, 8)).astype(np.int32)
    for dtype in (jnp.bfloat16, jnp.int8):
        eng, plain = engines(monkeypatch, cfg, params, dtype=dtype)
        wte = eng.params["wte"]["embedding"]
        assert wte.shape == (V, 256) and wte.dtype == eng.dtype
        same(eng.forward(tokens), plain.forward(tokens))
