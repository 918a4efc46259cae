"""int8 KV-cache × serving-feature integration tests (tentpole:
DS_KV_QUANT plumbing through inference/engine.py slot programs +
inference/serving.py dispatch + inference/paged_cache.py scale pools).

The contract under test (docs/KV_QUANT.md): kv_quant="off" is BIT-
IDENTICAL to a ServingEngine that never heard of the knob; int8 keeps
greedy streams argmax-stable on the smoke configs (>= 99% token match
vs the unquantized static engine) while composing with every serving
feature — shared-prefix COW, speculative rollback across block edges,
eviction/requeue, chaos faults — at the SAME compiled-program count and
zero steady-state recompiles."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.engine import InferenceEngine
from deepspeed_tpu.inference.serving import ServeRequest, ServingEngine
from deepspeed_tpu.models import gpt
from deepspeed_tpu.telemetry import Telemetry
from deepspeed_tpu.utils.faults import Fault, injected


def tiny(**over):
    cfg = gpt.GPTConfig(vocab_size=128, n_layers=2, n_heads=4, d_model=32,
                        max_seq_len=64, use_flash_attention=False,
                        remat=False, dtype=jnp.float32, **over)
    params = gpt.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def prompts_of(lengths, seed=1):
    r = np.random.default_rng(seed)
    return [r.integers(1, 128, n).astype(np.int32) for n in lengths]


@pytest.fixture(scope="module")
def eng(devices):
    cfg, params = tiny()
    return InferenceEngine(config=cfg, params=params, dtype=jnp.float32)


def serve(eng, prompts, n_new=8, **kw):
    kw.setdefault("num_slots", 2)
    kw.setdefault("block_size", 4)
    kw.setdefault("num_blocks", 24)
    kw.setdefault("prefill_chunk", 8)
    srv = ServingEngine(eng, **kw)
    out = srv.run([ServeRequest(rid=i, prompt=p, max_new_tokens=n_new)
                   for i, p in enumerate(prompts)])
    return srv, out


def _match_rate(out, refs):
    tot = match = 0
    for i, ref in enumerate(refs):
        got = np.asarray(out[i])
        ref = np.asarray(ref)
        n = min(len(got), len(ref))
        match += int((got[:n] == ref[:n]).sum())
        tot += max(len(got), len(ref))
    return match / max(tot, 1)


# ---------------------------------------------------------------------------
# off mode is bit-identical to today's serving
# ---------------------------------------------------------------------------

def test_kv_quant_off_is_bit_identical(eng):
    prompts = prompts_of((5, 9, 12, 3))
    _, base = serve(eng, prompts)                     # knob never passed
    _, off = serve(eng, prompts, kv_quant="off")
    for i in range(len(prompts)):
        np.testing.assert_array_equal(off[i], base[i])


def test_kv_quant_env_resolution(eng, monkeypatch):
    monkeypatch.setenv("DS_KV_QUANT", "int8")
    srv = ServingEngine(eng, num_slots=2, block_size=4, num_blocks=8)
    assert srv.kv_quant == "int8" and srv.cache.quantized
    # explicit off beats the env var
    srv = ServingEngine(eng, num_slots=2, block_size=4, num_blocks=8,
                        kv_quant="off")
    assert srv.kv_quant == "off" and srv.cache.k_scale is None


# ---------------------------------------------------------------------------
# int8 greedy parity vs the unquantized static engine
# ---------------------------------------------------------------------------

def test_kv_quant_int8_greedy_match(eng):
    """>= 99% greedy token match vs the unquantized static-engine
    streams on the CPU smoke config (docs/KV_QUANT.md tolerance)."""
    prompts = prompts_of((5, 9, 12, 3))
    refs = [eng.generate(p[None], max_new_tokens=8)[0] for p in prompts]
    srv, out = serve(eng, prompts, kv_quant="int8")
    assert srv.stats["completed"] == len(prompts)
    assert srv.stats["peak_occupancy"] > 1            # really batched
    assert _match_rate(out, refs) >= 0.99


def test_kv_quant_int8_rotary_gqa_window(devices):
    """int8 composes with rotary positions, grouped KV heads and
    sliding-window masking — the full feature stack the fp path
    serves."""
    import dataclasses
    cfg, _ = tiny()
    cfg = dataclasses.replace(cfg, rotary_dim=4, use_wpe=False,
                              n_kv_heads=2, attn_window=6)
    params = gpt.init_params(jax.random.PRNGKey(0), cfg)
    e = InferenceEngine(config=cfg, params=params, dtype=jnp.float32)
    prompts = prompts_of((7, 11), seed=2)
    refs = [e.generate(p[None], max_new_tokens=6)[0] for p in prompts]
    _, out = serve(e, prompts, n_new=6, kv_quant="int8")
    assert _match_rate(out, refs) >= 0.99


# ---------------------------------------------------------------------------
# x shared-prefix cache: sharing + COW on the int8 layout
# ---------------------------------------------------------------------------

def test_kv_quant_warm_prefix_matches_cold(eng):
    """Warm (prefix hits) int8 serving == cold int8 serving token-for-
    token: shared full blocks are reused with their scales, and the
    read-modify-requantize write path never touches a published
    block."""
    sys_prompt = np.arange(1, 25, dtype=np.int32)
    r = np.random.default_rng(0)
    prompts = [np.concatenate([sys_prompt,
                               r.integers(1, 128, 6).astype(np.int32)])
               for _ in range(4)]
    cold_srv, cold = serve(eng, prompts, block_size=8, prefill_chunk=16,
                           prefix_cache=False, kv_quant="int8")
    warm_srv, warm = serve(eng, prompts, block_size=8, prefill_chunk=16,
                           prefix_cache=True, kv_quant="int8")
    assert warm_srv.stats["prefix_hits"] > 0
    assert warm_srv.stats["prefill_chunks"] < cold_srv.stats[
        "prefill_chunks"]
    for i in range(len(prompts)):
        np.testing.assert_array_equal(warm[i], cold[i])


def test_kv_quant_cow_divergence_mid_block(eng):
    """Mid-block divergence under int8: the COW copy carries BOTH the
    int8 block bytes and the per-block scales, so the diverging request
    still matches its cold int8 stream exactly."""
    base = np.arange(1, 31, dtype=np.int32)
    div = base.copy()
    div[21] = 99                                      # inside block 2
    srv = ServingEngine(eng, num_slots=2, block_size=8, num_blocks=24,
                        prefill_chunk=16, prefix_cache=True,
                        kv_quant="int8")
    out1 = srv.run([ServeRequest(rid="a", prompt=base, max_new_tokens=8)])
    out2 = srv.run([ServeRequest(rid="b", prompt=div, max_new_tokens=8)])
    assert srv.cache.cow_copies == 1
    assert srv.stats["prefix_hits"] == 1
    for p, got in ((base, out1["a"]), (div, out2["b"])):
        cold = ServingEngine(eng, num_slots=2, block_size=8,
                             num_blocks=24, prefill_chunk=16,
                             prefix_cache=False, kv_quant="int8")
        ref = cold.run([ServeRequest(rid="x", prompt=p,
                                     max_new_tokens=8)])["x"]
        np.testing.assert_array_equal(got, ref)


# ---------------------------------------------------------------------------
# x speculative decoding: rollback across block edges with scales
# ---------------------------------------------------------------------------

def test_kv_quant_spec_rollback_block_boundary(eng):
    """Speculative int8 serving with a draft chunk size that forces
    rejects to straddle block edges: rollback trims the tail block and
    the next owner's write live-masks the stale int8 lanes, so the
    spec-on int8 stream equals the spec-off int8 stream's match rate
    against itself — here they must be token-identical since acceptance
    is target-argmax equality ON THE SAME quantized cache state only
    when histories coincide; we assert completion + near-total match."""
    prompts = prompts_of((5, 9, 12), seed=1)
    s_srv, s_out = serve(eng, prompts, n_new=10, spec_decode=True,
                         kv_quant="int8")
    p_srv, p_out = serve(eng, prompts, n_new=10, spec_decode=False,
                         kv_quant="int8")
    assert s_srv.stats["completed"] == 3
    assert s_srv.stats["spec_accepted"] > 0           # really speculated
    assert _match_rate(s_out, [p_out[i] for i in range(3)]) >= 0.99


def test_kv_quant_spec_eviction_requeue(eng):
    """Tiny pool + speculation + int8: decode growth exhausts the free
    list mid-stream, the evicted request requeues and completes; the
    rollback/requeue bookkeeping never corrupts the scale pools
    (completion + finite pools is the assert)."""
    prompts = prompts_of((12, 12, 12), seed=3)
    srv, out = serve(eng, prompts, n_new=12, num_blocks=10,
                     spec_decode=True, kv_quant="int8")
    assert srv.stats["completed"] == 3
    assert srv.stats["evictions"] >= 1
    assert np.isfinite(np.asarray(srv.cache.k_scale)).all()
    assert np.isfinite(np.asarray(srv.cache.v_scale)).all()


# ---------------------------------------------------------------------------
# compile contract: same program count, the fp variant stays cold
# ---------------------------------------------------------------------------

def test_kv_quant_compile_count_contract(devices):
    """DS_KV_QUANT=int8 keeps the serving compile contract: exactly one
    prefill + one decode executable (the int8 entries of the two
    callables), the fp variant stays COLD (quant never compiles both
    sets: each callable holds ONE entry), and a second identical workload
    compiles NOTHING."""
    from deepspeed_tpu.utils.compile_guard import CompileWatch, cache_size
    cfg, params = tiny()
    e = InferenceEngine(config=cfg, params=params, dtype=jnp.float32)
    p1, p2 = prompts_of((10, 9), seed=9)

    def run_workload():
        srv = ServingEngine(e, num_slots=2, block_size=4, num_blocks=7,
                            prefill_chunk=8, spec_decode=False,
                            kv_quant="int8")
        srv.cache.watermark = 0
        out = srv.run([ServeRequest(rid="a", prompt=p1, max_new_tokens=12),
                       ServeRequest(rid="b", prompt=p2, max_new_tokens=10)])
        return srv, out

    srv, warm_out = run_workload()
    assert srv.stats["evictions"] >= 1
    n_prefill = cache_size(e._prefill_slot)
    if n_prefill is not None:
        # the unquantized variant never compiled: same program COUNT,
        # not 2x — quant swaps the entry, it doesn't add one
        assert n_prefill == 1
        assert cache_size(e._decode_slots) == 1

    watch = CompileWatch(max_compiles=0, label="int8 serving steady state")
    watch.wrap(e._prefill_slot)
    watch.wrap(e._decode_slots)
    with watch:                            # raises RecompileError on exit
        srv2, out = run_workload()
    assert srv2.stats["evictions"] >= 1
    for rid in ("a", "b"):
        np.testing.assert_array_equal(out[rid], warm_out[rid])


# ---------------------------------------------------------------------------
# chaos: cache.quantize fault degrades the step, never the pool
# ---------------------------------------------------------------------------

def test_kv_quant_chaos_transient_fault_retries_clean(eng):
    """A transient device error at the cache.quantize site (fires
    BEFORE dispatch, donated pools untouched) is retried by the serving
    backoff and the final streams are identical to a fault-free int8
    run — the retry replays against uncorrupted int8 pools + scales."""
    prompts = prompts_of((5, 9, 12), seed=1)
    _, clean = serve(eng, prompts, n_new=6, kv_quant="int8",
                     retry_backoff_s=0.0)
    with injected(Fault("cache.quantize", "device_error", step=1),
                  seed=0) as inj:
        srv, out = serve(eng, prompts, n_new=6, kv_quant="int8",
                         retry_backoff_s=0.0)
    assert ("cache.quantize", "device_error", 1) in inj.fired
    assert srv.stats["retries"] >= 1
    for i in range(3):
        np.testing.assert_array_equal(out[i], clean[i])
    assert np.isfinite(np.asarray(srv.cache.k_scale)).all()


# ---------------------------------------------------------------------------
# telemetry: capacity gauges + sampled quant-error histogram
# ---------------------------------------------------------------------------

def test_kv_quant_telemetry_gauges_and_error_histogram(eng):
    prompts = prompts_of((5, 9), seed=1)
    srv, _ = serve(eng, prompts, kv_quant="int8", telemetry=Telemetry())
    reg = srv.metrics
    bpt = reg.gauge("kv_cache_bytes_per_token").value
    assert bpt == pytest.approx(
        srv.cache.bytes_per_token
        + srv.cache.scale_bytes_per_block / srv.cache.block_size)
    assert reg.gauge("kv_pool_dtype").value == 8      # int8 = 8 bits
    h = reg.histogram("serving_kv_quant_error")
    assert h.count > 0                                # sampled at least once
    # the observed upper bound is half a quantization step: tiny
    assert h.sum / h.count < 1.0
    text = reg.to_prometheus()
    assert "kv_cache_bytes_per_token" in text
    assert "serving_kv_quant_error" in text
    # off mode: gauges report the fp layout, no error histogram samples
    srv0, _ = serve(eng, prompts, kv_quant="off", telemetry=Telemetry())
    assert srv0.metrics.gauge("kv_cache_bytes_per_token").value == \
        srv0.cache.bytes_per_token
    assert srv0.metrics.histogram("serving_kv_quant_error").count == 0


def test_kv_quant_telemetry_off_noop(eng):
    """Default-off telemetry stays a no-op under quant — no registry,
    no sampled device pulls beyond the step sync."""
    prompts = prompts_of((5,), seed=1)
    srv, out = serve(eng, prompts, kv_quant="int8")
    assert srv._h_kv_err is None
    assert len(out[0]) == 5 + 8           # prompt + generated stream
