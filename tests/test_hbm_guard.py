"""Compile-memory guard: analytic estimator calibration + refusal.

The guard refuses a configuration whose estimate sits too close to device
HBM before it is compiled (utils/hbm.py). These tests pin the estimator to
the measured ground truth: every config that ran fine on the 16GB v5e
must be SAFE, every config that OOM'd or ground the compiler must be
REFUSED. Reference analog: the autotuner prunes by memory model before
launching configs (ref: deepspeed/autotuning/autotuner.py:396).
"""

import jax.numpy as jnp
import pytest

from deepspeed_tpu.models import gpt
from deepspeed_tpu.utils import hbm

V5E = 16 * hbm.GiB


def _safe(preset, batch, remat, pol, lc, me, precision="bf16"):
    cfg = gpt.preset(preset, max_seq_len=1024, dtype=jnp.bfloat16,
                     remat=remat, remat_policy=pol, loss_chunk=lc)
    est = hbm.estimate_gpt_train_bytes(cfg, batch, 1024,
                                       precision=precision,
                                       memory_efficient=me)
    ok, msg = hbm.check_compile_safe(est, V5E)
    return ok, est, msg


# (name, preset, batch, remat, policy, loss_chunk, memory_efficient,
#  ran_on_chip) — ground truth from PERF.md round-2 measurements
CALIBRATION = [
    ("b16-full-ce", "gpt2-1.5b", 16, True, "full", 2048, True, True),
    ("b4-full", "gpt2-1.5b", 4, True, "full", 0, True, True),
    ("b16-flashonly", "gpt2-1.5b", 16, True, "flash_only", 2048, True,
     False),  # compile grind, never ran
    ("b24-full-ce", "gpt2-1.5b", 24, True, "full", 2048, True, False),
    ("b32-full-ce", "gpt2-1.5b", 32, True, "full", 2048, True, False),
    ("b16-sel-ce", "gpt2-1.5b", 16, True, "selective", 2048, True, False),
    ("b4-sel", "gpt2-1.5b", 4, True, "selective", 0, True,
     False),  # OOM: 5.9GB saved activations
    ("med-b8-sel", "gpt2-medium", 8, True, "selective", 0, False, True),
    ("med-b16-ce", "gpt2-medium", 16, True, "selective", 2048, False, True),
    ("med-b8-noremat", "gpt2-medium", 8, False, "selective", 2048, False,
     True),
    ("med-b16-noremat", "gpt2-medium", 16, False, "selective", 2048, False,
     False),  # 12GB activations alone — cannot fit 16GB
]


@pytest.mark.parametrize("name,preset,batch,remat,pol,lc,me,ran",
                         CALIBRATION, ids=[c[0] for c in CALIBRATION])
def test_calibration(name, preset, batch, remat, pol, lc, me, ran):
    ok, est, msg = _safe(preset, batch, remat, pol, lc, me)
    assert ok == ran, f"{name}: guard={ok}, ground truth ran={ran} — {msg}"


def test_selective_width_matches_measured():
    # PERF.md: 1.5B batch-4 selective saved 5.9GB of activations
    cfg = gpt.preset("gpt2-1.5b", max_seq_len=1024,
                     remat_policy="selective")
    est = hbm.estimate_gpt_train_bytes(cfg, 4, 1024,
                                       memory_efficient=True)
    acts = est.contributions["grads_or_acts"]
    assert 4.7 * hbm.GiB < acts < 6.5 * hbm.GiB


def test_flashonly_residual_matches_measured():
    # PERF.md: flash_only saves ~2.6GB of flash residuals beyond full
    cfg_f = gpt.preset("gpt2-1.5b", max_seq_len=1024, remat_policy="full",
                       loss_chunk=2048)
    cfg_o = gpt.preset("gpt2-1.5b", max_seq_len=1024,
                       remat_policy="flash_only", loss_chunk=2048)
    kw = dict(memory_efficient=True)
    delta = (hbm.estimate_gpt_train_bytes(cfg_o, 16, 1024, **kw).total -
             hbm.estimate_gpt_train_bytes(cfg_f, 16, 1024, **kw).total)
    assert 2.0 * hbm.GiB < delta < 3.2 * hbm.GiB


def test_guard_raises_with_context():
    cfg = gpt.preset("gpt2-1.5b", max_seq_len=1024,
                     remat_policy="flash_only", loss_chunk=2048)

    class FakeDev:
        platform, device_kind = "tpu", "TPU v5 lite"

        def memory_stats(self):
            return {}

    with pytest.raises(hbm.MemoryGuardError) as e:
        hbm.guard_gpt_config(cfg, 16, 1024, device=FakeDev(),
                             memory_efficient=True)
    assert "refusing to compile" in str(e.value)
    assert "GiB" in str(e.value)


def test_guard_inactive_off_accelerator():
    cfg = gpt.preset("gpt2-1.5b", max_seq_len=1024,
                     remat_policy="selective")

    class CpuDev:
        platform, device_kind = "cpu", "cpu"

    # unknown/absent HBM -> no refusal (nothing to guard)
    msg = hbm.guard_gpt_config(cfg, 64, 1024, device=CpuDev(),
                               memory_efficient=True)
    assert "guard inactive" in msg


def test_gqa_shrinks_estimate():
    base = gpt.preset("gpt2-medium", max_seq_len=1024)
    gqa = gpt.preset("gpt2-medium", max_seq_len=1024, n_kv_heads=4)
    b = hbm.estimate_gpt_train_bytes(base, 8, 1024).total
    g = hbm.estimate_gpt_train_bytes(gqa, 8, 1024).total
    assert g < b


def test_bert_estimator_calibration():
    """bert-large seq128 b256 and seq512 b64 (the bench grid's upper
    rows) must be SAFE on 16GiB with full remat + chunked CE; an absurd
    batch must be REFUSED — so bert_bench's guard keeps the real grid
    runnable while stopping the compiles that never fit."""
    from deepspeed_tpu.models import bert
    cfg = bert.preset("bert-large", max_seq_len=512, dropout=0.0,
                      dtype=jnp.bfloat16, remat=True, remat_policy="full",
                      loss_chunk=2048)
    for seq, batch in [(128, 256), (128, 512), (512, 32), (512, 64)]:
        est = hbm.estimate_bert_train_bytes(cfg, batch, seq)
        ok, msg = hbm.check_compile_safe(est, V5E)
        assert ok, f"seq{seq} b{batch} must be safe: {msg}"
    est = hbm.estimate_bert_train_bytes(cfg, 4096, 512)
    ok, msg = hbm.check_compile_safe(est, V5E)
    assert not ok, f"b4096 seq512 must be refused: {msg}"


def test_moe_estimator_calibration():
    """The moe_bench grid (12L/768d, E=8/16, b8 seq1024) is SAFE; the
    dispatch working set grows the estimate over dense; a huge
    expert-count config at big batch is REFUSED."""
    from deepspeed_tpu.models import moe_gpt
    cfg = moe_gpt.MoEGPTConfig(n_layers=12, n_heads=12, d_model=768,
                               max_seq_len=1024, dtype=jnp.bfloat16,
                               remat=True, num_experts=8, moe_k=2,
                               capacity_factor=1.25)
    est = hbm.estimate_moe_train_bytes(cfg, 8, 1024)
    ok, msg = hbm.check_compile_safe(est, V5E)
    assert ok, msg
    assert est.contributions["moe_dispatch"] > 0
    dense_like = hbm.estimate_train_bytes(
        n_params=moe_gpt.num_params(cfg), n_layers=cfg.n_layers,
        d_model=cfg.d_model, ffn_dim=cfg.ffn_dim, qkv_dim=cfg.qkv_dim,
        n_heads=cfg.n_heads, vocab_size=cfg.vocab_size, batch=8, seq=1024,
        remat=cfg.remat, remat_policy=cfg.remat_policy,
        loss_chunk=cfg.loss_chunk)
    assert est.total > dense_like.total
    big = moe_gpt.MoEGPTConfig(n_layers=24, n_heads=16, d_model=2048,
                               max_seq_len=2048, dtype=jnp.bfloat16,
                               remat=True, num_experts=64, moe_k=2)
    est = hbm.estimate_moe_train_bytes(big, 32, 2048)
    ok, msg = hbm.check_compile_safe(est, V5E)
    assert not ok, f"64-expert 1.3B-ish at b32 must be refused: {msg}"


def test_moe_num_params_matches_init():
    from deepspeed_tpu.models import moe_gpt
    import jax
    cfg = moe_gpt.MoEGPTConfig(vocab_size=128, n_layers=2, n_heads=2,
                               d_model=32, max_seq_len=64,
                               dtype=jnp.float32, num_experts=4)
    params = moe_gpt.init_params(jax.random.PRNGKey(0), cfg)
    n = sum(x.size for x in jax.tree.leaves(params))
    assert moe_gpt.num_params(cfg) == n


def test_infer_estimator_calibration():
    """A static-cache grid (gpt2-medium/large, b8-32, 584-token cache)
    is SAFE; a 32k-cache x 256-batch config is REFUSED (KV cache alone
    exceeds HBM)."""
    cfg = gpt.preset("gpt2-large", max_seq_len=584, dtype=jnp.bfloat16)
    est = hbm.estimate_infer_bytes(cfg, 32, 584)
    ok, msg = hbm.check_compile_safe(est, V5E)
    assert ok, msg
    cfg = gpt.preset("gpt2-large", max_seq_len=32768, dtype=jnp.bfloat16)
    est = hbm.estimate_infer_bytes(cfg, 256, 32768)
    ok, msg = hbm.check_compile_safe(est, V5E)
    assert not ok, msg
    assert est.contributions["kv_cache"] > est.contributions["params"]


class _FakeV5e:
    platform = "tpu"
    device_kind = "TPU v5e"

    def memory_stats(self):
        return {}


def test_guard_wrappers_raise():
    """guard_bert/moe/infer_config raise MemoryGuardError on a v5e-sized
    device for configs past the headroom, and return the decision message
    for safe ones."""
    from deepspeed_tpu.models import bert, moe_gpt
    dev = _FakeV5e()
    bcfg = bert.preset("bert-large", max_seq_len=512, dtype=jnp.bfloat16,
                       remat=True, remat_policy="full", loss_chunk=2048)
    assert "estimated peak" in hbm.guard_bert_config(bcfg, 64, 512,
                                                     device=dev)
    with pytest.raises(hbm.MemoryGuardError):
        hbm.guard_bert_config(bcfg, 4096, 512, device=dev)
    mcfg = moe_gpt.MoEGPTConfig(n_layers=12, n_heads=12, d_model=768,
                                max_seq_len=1024, dtype=jnp.bfloat16,
                                remat=True, num_experts=8)
    assert "estimated peak" in hbm.guard_moe_config(mcfg, 8, 1024,
                                                    device=dev)
    icfg = gpt.preset("gpt2-large", max_seq_len=584, dtype=jnp.bfloat16)
    assert "estimated peak" in hbm.guard_infer_config(icfg, 32, 584,
                                                      device=dev)
    big = gpt.preset("gpt2-large", max_seq_len=32768, dtype=jnp.bfloat16)
    with pytest.raises(hbm.MemoryGuardError):
        hbm.guard_infer_config(big, 256, 32768, device=dev)


# ---------------------------------------------------------------------------
# property-based estimator invariants (hypothesis)
# ---------------------------------------------------------------------------

try:
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:  # environment without hypothesis: collect the
    # rest of the module and skip just the property tests
    import pytest as _pytest

    def given(*a, **k):
        return _pytest.mark.skip(reason="hypothesis not installed")

    def settings(*a, **k):
        return lambda f: f

    class _NoStrategies:
        def __getattr__(self, name):
            return lambda *a, **k: None

    st = _NoStrategies()


@settings(max_examples=30, deadline=None)
@given(st.integers(min_value=1, max_value=64),       # batch
       st.sampled_from([256, 1024, 4096]),           # seq
       st.sampled_from(["full", "selective", "flash_only"]),
       st.booleans())                                 # loss chunked?
def test_estimator_monotonicity(batch, seq, pol, chunked):
    """The guard's safety rests on these order relations: more batch/seq
    never estimates SMALLER; 'full' remat never estimates above
    'selective' or no-remat; chunked CE never estimates above dense.
    A violation would let a strictly-bigger program through a guard the
    smaller one failed."""
    cfg = gpt.preset("gpt2-medium", max_seq_len=seq,
                     dtype=jnp.bfloat16, remat=True, remat_policy=pol,
                     loss_chunk=2048 if chunked else 0)
    base = hbm.estimate_gpt_train_bytes(cfg, batch, seq).total
    assert hbm.estimate_gpt_train_bytes(cfg, batch + 1, seq).total >= base
    if seq >= 512:
        assert hbm.estimate_gpt_train_bytes(cfg, batch, seq * 2).total \
            >= base
    import dataclasses
    if pol != "full":
        full = dataclasses.replace(cfg, remat_policy="full")
        assert hbm.estimate_gpt_train_bytes(full, batch, seq).total <= base
    norem = dataclasses.replace(cfg, remat=False)
    assert hbm.estimate_gpt_train_bytes(norem, batch, seq).total >= \
        hbm.estimate_gpt_train_bytes(
            dataclasses.replace(cfg, remat_policy="full"), batch, seq).total
    if chunked:
        dense = dataclasses.replace(cfg, loss_chunk=0)
        assert hbm.estimate_gpt_train_bytes(dense, batch, seq).total >= base
