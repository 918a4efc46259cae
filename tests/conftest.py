"""Test harness: virtual 8-device CPU mesh.

TPU analog of the reference's multi-process fixture
(ref: tests/unit/common.py:66 @distributed_test forking N local processes).
On TPU/JAX we emulate a multi-chip host inside ONE process with
``xla_force_host_platform_device_count`` — every sharding/collective code
path compiles and runs exactly as on an 8-chip slice.

Must set env before jax is imported anywhere.
"""

import os
import shutil
import tempfile

os.environ["JAX_PLATFORMS"] = "cpu"  # force: the session env may point at a real TPU  # dslint: disable=DS005 — must pin the platform BEFORE jax imports
flags = os.environ.get("XLA_FLAGS", "")  # dslint: disable=DS005 — bootstrap: XLA flags only apply pre-import
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = flags + " --xla_force_host_platform_device_count=8"  # dslint: disable=DS005 — bootstrap: XLA flags only apply pre-import


def _run_cache_dir(run) -> str:
    return os.path.join(tempfile.gettempdir(), f"ds_tpu_tests_jax_cache_{run}")


# One persistent compile cache a RUN: a fresh directory that the run's
# workers share (xdist hands every worker the run's id before it imports
# this file; without xdist, this process) and that goes when the session
# ends, so a tiny program is compiled once a run and no test passes on
# what an earlier run compiled. Set through the environment, before jax
# is imported: utils.setup_compile_cache then leaves it alone, and the
# checkout's .jax_cache is never written. Compile COUNTS do not move:
# jax fires the event CompileWatch counts on a hit as on a miss, and
# _cache_size() is the jit's own cache.
os.environ["JAX_COMPILATION_CACHE_DIR"] = _run_cache_dir(  # dslint: disable=DS005 — bootstrap: jax reads it as it is imported
    os.environ.get("PYTEST_XDIST_TESTRUNUID") or os.getpid())  # dslint: disable=DS005 — xdist's own variable
os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"  # dslint: disable=DS005 — bootstrap
os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"  # dslint: disable=DS005 — bootstrap

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_configure(config):
    # tier-1 CI deselects with `-m 'not slow'`; the gate's full mode
    # runs everything
    config.addinivalue_line(
        "markers", "slow: heavy end-to-end test, excluded from tier-1")


@pytest.hookimpl(trylast=True)
def pytest_sessionfinish(session):
    """Remove the run's compile cache: the controller once its workers
    are down (xdist tears them down in its own sessionfinish, before this
    one), a run without xdist when it ends."""
    config = session.config
    if hasattr(config, "workerinput"):
        return
    manager = getattr(config.pluginmanager.getplugin("dsession"),
                      "nodemanager", None)
    shutil.rmtree(_run_cache_dir(manager.testrunuid if manager
                                 else os.getpid()), ignore_errors=True)


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual devices, got {len(devs)}"
    return devs


@pytest.fixture
def rng():
    return np.random.default_rng(0)


@pytest.fixture
def pallas_interpret(monkeypatch):
    """Force pallas interpret mode on CPU (shared by the kernel parity
    suites)."""
    import functools

    import jax.experimental.pallas as pl

    monkeypatch.setattr(pl, "pallas_call",
                        functools.partial(pl.pallas_call, interpret=True))
    yield
