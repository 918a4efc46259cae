"""A serving dispatch crosses the host link once each way (PERF.md, PR 31).

The paged serving programs are jitted behind ONE packed host operand
(``engine.pack_operands`` / ``engine._packed``), the sampler's ``seen``
mask lives on the device, and the sampled tokens come back in one pull.
Checked here: (a) every dispatch of a serving run is bit-equal to
``_prefill_slot_fn`` / ``_decode_slots_fn`` jitted by position and fed
operand by operand from the scheduler's HOST state, greedy and sampled
with a repetition penalty, across eviction and resume; (b) the spans'
``h2d`` / ``d2h`` and the ``sampler_mask_uploads`` counter; (c) nothing
compiles in steady state. That the programs AS THE ENGINE JITS THEM,
compiled ahead of time for a v5e at the GPT-2 XL cell's sizes, hold no
copy of the pool is read in tests/test_pool_layout_aot.py, off the one
compile that file's readers share.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference import hybrid
from deepspeed_tpu.inference.engine import (InferenceEngine, _packed,
                                            pack_operands)
from deepspeed_tpu.inference.serving import ServeRequest, ServingEngine
from deepspeed_tpu.models import gpt
from deepspeed_tpu.telemetry import Telemetry
from deepspeed_tpu.utils.compile_guard import CompileWatch

from exaone_moe_util import tiny_config, tiny_params


def _engine(model):
    if model == "exaone_moe":
        cfg = tiny_config()
        params = tiny_params(cfg)
    else:
        cfg = gpt.GPTConfig(vocab_size=128, n_layers=2, n_heads=4,
                            d_model=32, max_seq_len=64,
                            use_flash_attention=False, remat=False,
                            dtype=jnp.float32)
        params = gpt.init_params(jax.random.PRNGKey(0), cfg)
    return InferenceEngine(
        config=cfg, params=jax.tree_util.tree_map(np.asarray, params),
        dtype=jnp.float32)


def _requests(vocab, lengths, new_tokens, seed=3, **knobs):
    r = np.random.default_rng(seed)
    return [ServeRequest(rid=i, prompt=r.integers(1, vocab, n)
                         .astype(np.int32), max_new_tokens=new_tokens,
                         **({"seed": 11 + i, **knobs} if knobs else {}))
            for i, n in enumerate(lengths)]


def _same(got, ref):
    got, ref = (jax.tree_util.tree_leaves(x) for x in (got, ref))
    assert len(got) == len(ref)
    for a, b in zip(got, ref):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _shadow(eng, srv):
    """Run ``_prefill_slot_fn`` / ``_decode_slots_fn`` beside every
    dispatch, jitted by position with one device operand each, as the
    wrappers did before the packed buffer, and the mask's rows from the
    sampler's HOST mirror; compare everything the programs return.
    Returns the count of dispatches compared."""
    plain_p = jax.jit(eng._prefill_slot_fn)
    plain_d = jax.jit(eng._decode_slots_fn, static_argnums=(7,))
    packed_p, packed_d = eng.prefill_into_slot, eng.decode_slots
    i32, f32, u32 = jnp.int32, jnp.float32, jnp.uint32
    lane_types = (u32, i32, f32, i32, f32, f32)
    count = {"prefill": 0, "decode": 0}

    def pool(k):
        return k._replace(route=None) if isinstance(k, hybrid.PagedState) \
            else k

    def prefill(k, v, table_row, tokens, start, n_valid, scales=None,
                sample_state=None, lora=None):
        *lane, slot, _ = sample_state
        ref = plain_p(eng.params, pool(k), v, *(
            jnp.asarray(x, i32) for x in (table_row, tokens, start, n_valid)),
            *(jnp.asarray(x, t) for x, t in zip(lane, lane_types)),
            jnp.asarray(srv.sampler.seen[slot], bool))
        out = packed_p(k, v, table_row, tokens, start, n_valid,
                       scales=scales, sample_state=sample_state, lora=lora)
        _same(out, ref)
        count["prefill"] += 1
        return out

    def decode(k, v, tables, lengths, tokens, active, impl=None,
               scales=None, sample_state=None, lora=None):
        *lanes, _ = sample_state
        ref = plain_d(eng.params, pool(k), v, jnp.asarray(tables, i32),
                      jnp.asarray(lengths, i32), jnp.asarray(tokens, i32),
                      jnp.asarray(active, bool), impl or eng.decode_impl, *(
                          jnp.asarray(x, t)
                          for x, t in zip(lanes, lane_types)),
                      jnp.asarray(srv.sampler.seen, bool))
        out = packed_d(k, v, tables, lengths, tokens, active, impl,
                       scales=scales, sample_state=sample_state, lora=lora)
        _same(out, ref)
        count["decode"] += 1
        return out

    eng.prefill_into_slot, eng.decode_slots = prefill, decode
    return count


@pytest.mark.parametrize("model", ["gpt2", "exaone_moe"])
@pytest.mark.parametrize("lanes", ["greedy", "sampled_penalized"])
def test_packed_dispatch_is_bit_equal_to_the_plain_programs(devices, model,
                                                            lanes):
    eng = _engine(model)
    knobs = {} if lanes == "greedy" else dict(
        temperature=0.9, top_k=20, top_p=0.9, repetition_penalty=1.3)
    # a pool too small for three requests' answers: decode growth evicts
    # one, which resumes from its prompt plus what it had emitted, so a
    # penalized row is rebuilt at admission and grows token by token
    srv = ServingEngine(eng, num_slots=3, block_size=4, num_blocks=14,
                        prefill_chunk=8)
    srv.cache.watermark = 0
    count = _shadow(eng, srv)
    reqs = _requests(eng.cfg.vocab_size, (10, 9, 13, 6), 12, **knobs)
    srv.run(reqs)
    assert all(r.state == "done" and len(r.out) == 12 for r in reqs)
    assert srv.stats["evictions"] >= 1
    assert count["prefill"] >= 6 and count["decode"] >= 12
    if knobs:
        assert srv.stats["sampled_tokens"] == 4 * 12


def test_pack_operands_round_trip():
    """Every kind of section comes back as the value it went in as, bit
    for bit, and in the program's order."""
    r = np.random.default_rng(0)
    seen = r.random((3, 40)) < 0.3
    parts = (("i", r.integers(-5, 99, (3, 4))),
             ("b", np.array([1, 0, 1], bool)), ("static", "gather"),
             ("u", np.array([[0, 2**32 - 1]], np.uint32)),
             ("f", np.float32(0.1)), ("seen", None), ("row", 2),
             ("f", np.array([-0.0, np.inf, 1e-40], np.float32)))
    packed, layout = pack_operands(
        *parts, ("lora", np.arange(6).reshape(3, 2)))
    assert packed.dtype == np.int32 and packed.ndim == 1
    assert pack_operands(*parts)[1] == layout[:-1]

    def fn(params, k_pool, v_pool, *operands, scales=None, lora=None):
        assert operands[2] == "gather"
        return operands[:2] + operands[3:], lora

    got, lora = jax.jit(_packed(fn, "round_trip"),
                        static_argnames=("layout",))(
        None, None, None, packed, layout, seen, None,
        (np.float32(1), np.float32(2)))
    want = (parts[0][1], parts[1][1], parts[3][1], parts[4][1], seen,
            seen[2], parts[7][1])
    for a, b in zip(got, want):
        assert a.dtype == np.asarray(b).dtype or a.dtype == jnp.int32
        np.testing.assert_array_equal(
            np.atleast_1d(np.asarray(a)).view(np.uint8),
            np.atleast_1d(np.asarray(b, a.dtype)).view(np.uint8))
    assert [float(x) for x in lora[:2]] == [1.0, 2.0]
    np.testing.assert_array_equal(lora[2], np.arange(6).reshape(3, 2))


def test_spans_count_one_transfer_each_way_and_mask_uploads(devices):
    eng = _engine("gpt2")
    tel = Telemetry()
    srv = ServingEngine(eng, num_slots=2, block_size=4, num_blocks=24,
                        prefill_chunk=8, telemetry=tel)
    uploads = srv.metrics.counter("sampler_mask_uploads")
    srv.run(_requests(128, (12,), 4))     # warm-up: the first upload
    base = uploads.value
    assert base == 1
    tel.tracer.reset()
    # greedy, and sampled without a penalty: rows stay all-False, so
    # admission and release leave the device's mask alone
    srv.run(_requests(128, (9, 17, 5), 6))
    srv.run(_requests(128, (9, 5), 6, temperature=0.8, top_k=10))
    assert uploads.value == base
    enqueue = tel.tracer.spans("serve.dispatch.enqueue")
    pulls = tel.tracer.spans("serve.pull")
    assert len(enqueue) >= 20 and len(pulls) >= 15
    for rec in enqueue:
        assert rec[5]["h2d"] == 1 and 0 < rec[5]["h2d_bytes"] < 4096
    assert all(rec[5]["d2h"] == 1 for rec in pulls)
    # a penalized request marks its row when it is admitted: one upload
    # by its first dispatch; every NEW token it emits is one more, and
    # its release (the row clears) shows at the next dispatch
    tel.tracer.reset()
    req = _requests(128, (9,), 1, temperature=0.8, repetition_penalty=1.3)[0]
    srv.submit(req)
    srv.step()
    assert tel.tracer.spans("serve.dispatch.enqueue") \
        and uploads.value == base + 1
    while srv.busy:
        srv.step()
    srv.run(_requests(128, (5,), 2))
    assert uploads.value == base + 2
    srv.run(_requests(128, (5,), 2))
    assert uploads.value == base + 2


def test_steady_state_compiles_nothing(devices):
    eng = _engine("gpt2")
    srv = ServingEngine(eng, num_slots=2, block_size=4, num_blocks=24,
                        prefill_chunk=8)
    srv.run(_requests(128, (12, 7), 4))
    for r in _requests(128, (9, 17, 5, 11), 10, seed=5):
        srv.submit(r)
    watch = CompileWatch(max_compiles=0, label="packed dispatch")
    watch.wrap(eng._prefill_slot)
    watch.wrap(eng._decode_slots)
    steps = 0
    with watch:
        while srv.busy:
            srv.step()
            steps += 1
    assert steps >= 20
    assert eng._prefill_slot._cache_size() == 1
    assert eng._decode_slots._cache_size() == 1
