"""Test harness: virtual 8-device CPU mesh.

TPU analog of the reference's multi-process fixture
(ref: tests/unit/common.py:66 @distributed_test forking N local processes).
On TPU/JAX we emulate a multi-chip host inside ONE process with
``xla_force_host_platform_device_count`` — every sharding/collective code
path compiles and runs exactly as on an 8-chip slice.

Must set env before jax is imported anywhere.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"  # force: the session env may point at a real TPU  # dslint: disable=DS005 — must pin the platform BEFORE jax imports
flags = os.environ.get("XLA_FLAGS", "")  # dslint: disable=DS005 — bootstrap: XLA flags only apply pre-import
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = flags + " --xla_force_host_platform_device_count=8"  # dslint: disable=DS005 — bootstrap: XLA flags only apply pre-import

import jax  # noqa: E402

# tests compile on the CPU and count compilations: keep them off the
# persistent compile cache that initialize()/init_inference() place in the
# checkout (deepspeed_tpu/utils setup_compile_cache)
jax.config.update("jax_enable_compilation_cache", False)
import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_configure(config):
    # tier-1 CI deselects with `-m 'not slow'`; the gate's full mode
    # runs everything
    config.addinivalue_line(
        "markers", "slow: heavy end-to-end test, excluded from tier-1")


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
