import os

# the persistent compile cache's home when JAX_COMPILATION_CACHE_DIR is
# unset: one fixed directory at the root of the checkout. The directory is
# part of the cache key, so it never holds a pid, a timestamp or a tempdir
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_COMPILE_CACHE_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def on_tpu() -> bool:
    """Whether device 0 is a TPU — the single source of truth for flash
    eligibility and the other hardware gates. A backend that fails to
    initialise raises here; it never reads as "not a TPU"."""
    import jax
    return jax.devices()[0].platform == "tpu"


def require_tpu(who: str):
    """The first device, which must be a TPU, else ``SystemExit``: a
    measurement path that finds no chip fails, it does not fall back to
    the CPU (bench.py, the tools that measure, tools/kernel_census.py)."""
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"{who} measures a TPU and found none: JAX reports "
            f"platform={dev.platform!r} ({dev.device_kind}). "
            f"There is no CPU mode.")
    return dev


def holds_chip() -> bool:
    """Whether this process has initialised the TPU backend. A chip
    belongs to one process at a time: once this is true, a child process
    that needs the chip fails or hangs, so nothing here starts one."""
    import jax
    from jax._src import xla_bridge
    return (xla_bridge.backends_are_initialized()
            and jax.default_backend() == "tpu")


def setup_compile_cache() -> str:
    """Place JAX's persistent compilation cache and return its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX's own handling of the
    variable stands and nothing is configured in code. Unset, the cache
    goes to :data:`DEFAULT_COMPILE_CACHE_DIR`. Called from ``initialize``,
    ``init_inference``, ``chip_smoke.py`` and the bench scripts."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")  # dslint: disable=DS005 — mirrors jax's own env contract
    if env_dir:
        return env_dir
    import jax
    if jax.config.jax_compilation_cache_dir != DEFAULT_COMPILE_CACHE_DIR:
        jax.config.update("jax_compilation_cache_dir",
                          DEFAULT_COMPILE_CACHE_DIR)
    return DEFAULT_COMPILE_CACHE_DIR
