"""What keeps a run that did not use the chip from looking like one that
did: chip_smoke.py has no CPU mode, the device gates propagate errors and
raise where a kernel was asked for and cannot run, the compile cache goes
where the rule says, and no process that holds the chip starts a child."""

import gc
import logging
import os
import subprocess
import sys
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu import utils
from deepspeed_tpu.inference.serving import ServeRequest, ServingEngine
from deepspeed_tpu.models import gpt
from deepspeed_tpu.utils.logging import logger

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def tiny(**over):
    cfg = gpt.GPTConfig(vocab_size=128, n_layers=2, n_heads=4, d_model=32,
                        max_seq_len=64, remat=False, dtype=jnp.float32,
                        **over)
    return cfg, gpt.init_params(jax.random.PRNGKey(0), cfg)


@pytest.fixture
def ready_lines():
    """The "engine ready" lines logged while the test runs."""
    lines = []

    class Grab(logging.Handler):
        def emit(self, record):
            if "ready" in record.getMessage():
                lines.append(record.getMessage())

    h = Grab()
    logger.addHandler(h)
    yield lines
    logger.removeHandler(h)


def test_chip_smoke_has_no_cpu_mode():
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"),
                       capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert "no TPU" in r.stderr and "platform='cpu'" in r.stderr
    assert '"ok"' not in r.stdout


@pytest.mark.parametrize("script", ["bench.py"])
def test_bench_scripts_fail_without_a_tpu(script):
    r = subprocess.run([sys.executable, script], cwd=REPO,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"),
                       capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert "found none" in r.stderr
    assert not r.stdout.strip()


def test_on_tpu_propagates_a_backend_error(monkeypatch):
    def broken():
        raise RuntimeError("Unable to initialize backend 'tpu'")
    monkeypatch.setattr(jax, "devices", broken)
    with pytest.raises(RuntimeError, match="Unable to initialize"):
        utils.on_tpu()


def test_compile_cache_left_to_jax_when_the_variable_is_set(monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")

    def no_update(*a, **kw):
        raise AssertionError(f"jax.config.update{a} with the variable set")
    monkeypatch.setattr(jax.config, "update", no_update)
    assert utils.setup_compile_cache() == "/some/dir"


def test_compile_cache_fixed_in_the_checkout_when_unset(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        got = utils.setup_compile_cache()
        assert got == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
        assert utils.setup_compile_cache() == got      # same path every time
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_training_default_off_tpu_is_dense_and_says_so(devices, ready_lines):
    cfg, params = tiny()                    # use_flash_attention defaults on
    assert gpt.attention_impl(cfg) == "dense"
    deepspeed_tpu.initialize(
        model=gpt.make_loss_fn(cfg), model_parameters=params,
        config={"train_batch_size": 8,
                "optimizer": {"type": "adamw", "params": {"lr": 1e-3}}})
    assert any("platform=cpu" in ln and "attention=dense" in ln
               for ln in ready_lines), ready_lines


def test_flash_asked_for_on_a_tpu_and_unusable_raises(monkeypatch):
    monkeypatch.setattr("deepspeed_tpu.utils.on_tpu", lambda: True)
    cfg, _ = tiny()
    # a 256 block holds no second sub-tile: the backward walks nothing
    assert gpt.attention_impl(cfg, 256) == \
        "flash(256x256, backward sub-tiles 1/1)"
    # GPT-2 XL's training geometry: one 1,024 block a head, 10 of 16
    assert gpt.attention_impl(cfg, 1024) == \
        "flash(1024x1024, backward sub-tiles 10/16)"
    with pytest.raises(ValueError, match="no flash block"):
        gpt.attention_impl(cfg, 100)
    assert gpt.attention_impl(
        gpt.GPTConfig(use_flash_attention=False), 100) == "dense"


def test_serving_default_off_tpu_is_gather_on_one_device(devices,
                                                         ready_lines):
    cfg, params = tiny(use_flash_attention=False)
    eng = deepspeed_tpu.init_inference((cfg, params), dtype=jnp.float32)
    assert eng.decode_impl == "gather"
    assert any("platform=cpu" in ln and "decode_impl=gather" in ln
               and "devices=1" in ln for ln in ready_lines), ready_lines
    # eight devices visible, one engine: its parameters sit on one of them
    where = {d for x in jax.tree_util.tree_leaves(eng.params)
             for d in x.devices()}
    assert len(where) == 1


def test_pallas_decode_off_tpu_raises_instead_of_interpreting(devices):
    """decode_impl="pallas" without the tests' interpret fixture: the
    kernel is not quietly run in the Pallas interpreter."""
    cfg, params = tiny(use_flash_attention=False)
    eng = deepspeed_tpu.init_inference((cfg, params), dtype=jnp.float32)
    srv = ServingEngine(eng, num_slots=2, block_size=4, num_blocks=24,
                        decode_impl="pallas")
    with pytest.raises(Exception, match="[Ii]nterpret"):
        srv.run([ServeRequest(rid=0, prompt=np.arange(1, 6, dtype=np.int32),
                              max_new_tokens=2)])


def test_no_child_process_once_the_chip_is_held(monkeypatch):
    from deepspeed_tpu.autotuning import SubprocessRunner
    from tools._subproc import run_json
    assert not utils.holds_chip()           # a CPU backend holds no chip
    monkeypatch.setattr("deepspeed_tpu.utils.holds_chip", lambda: True)
    monkeypatch.setattr("tools._subproc.holds_chip", lambda: True)
    with pytest.raises(RuntimeError, match="holds the chip"):
        SubprocessRunner([sys.executable, "-c", "print(1)"])({})
    with pytest.raises(RuntimeError, match="holds the chip"):
        run_json([sys.executable, "-c", "print(1)"], 10, {})


def test_flash_under_a_mesh_runs_per_device(devices, pallas_interpret,
                                            monkeypatch):
    """Under a sharded step the flash kernel is mapped by hand (XLA cannot
    partition a Mosaic call): fsdp=4 x tp=2 with the kernel on gives the
    single-device dense losses."""
    monkeypatch.setattr("deepspeed_tpu.utils.on_tpu", lambda: True)
    from deepspeed_tpu.parallel.mesh import MeshSpec, make_mesh
    cfg = gpt.GPTConfig(vocab_size=128, n_layers=2, n_heads=4, d_model=32,
                        max_seq_len=128, remat=True, remat_policy="full",
                        dtype=jnp.float32, flash_block_q=128,
                        flash_block_kv=128)
    dense = gpt.GPTConfig(**{**cfg.__dict__, "use_flash_attention": False})
    # host copy: each engine donates the device state it is given
    params = jax.tree_util.tree_map(
        np.asarray, gpt.init_params(jax.random.PRNGKey(0), cfg))
    data = {"tokens": np.random.default_rng(0).integers(
        0, 128, (8, 129)).astype(np.int32)}
    ds = {"train_batch_size": 8, "zero_optimization": {"stage": 3},
          "optimizer": {"type": "adamw", "params": {"lr": 1e-3}}}

    def losses(c, mesh, rules):
        eng, *_ = deepspeed_tpu.initialize(
            model=gpt.make_loss_fn(c), model_parameters=params, config=ds,
            mesh=mesh, partition_rules=rules)
        return [float(eng.train_batch(data)["loss"]) for _ in range(2)]

    got = losses(cfg, make_mesh(MeshSpec(data=1, fsdp=4, model=2), devices),
                 gpt.gpt_partition_rules())
    want = losses(dense, make_mesh(MeshSpec(data=1), devices[:1]), None)
    np.testing.assert_allclose(got, want, rtol=2e-4)


def test_serving_engine_with_telemetry_is_freed(devices):
    """One process runs every configuration of a bench, so an engine that
    is dropped must let go of its parameters and KV pools: the process-wide
    fault injector used to pin every telemetry-enabled ServingEngine
    through its listener (found on the chip: 1-2 GiB kept per serving
    configuration, the device full after nine)."""
    cfg, params = tiny(use_flash_attention=False)

    def drive():
        eng = deepspeed_tpu.init_inference((cfg, params), dtype=jnp.float32)
        srv = ServingEngine(eng, num_slots=2, block_size=4, num_blocks=24,
                            telemetry=True)
        srv.run([ServeRequest(rid=0, prompt=np.arange(1, 6, dtype=np.int32),
                              max_new_tokens=2)])
        return weakref.ref(srv), weakref.ref(srv.cache)

    srv, cache = drive()
    gc.collect()
    assert srv() is None and cache() is None
