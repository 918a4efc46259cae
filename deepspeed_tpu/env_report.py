"""Environment report — ``ds_report`` analog.

Capability match for the reference's env report
(ref: deepspeed/env_report.py + bin/ds_report): prints framework
versions, platform/device inventory, HBM capacity, and a feature
compatibility table (which optional subsystems are usable in this
environment) instead of the reference's CUDA-op build matrix.
"""

import importlib
import os
import platform
import sys

GREEN_OK = "\033[92m[OKAY]\033[0m"
RED_NO = "\033[91m[NO]\033[0m"


def _version(mod_name: str) -> str:
    try:
        mod = importlib.import_module(mod_name)
        return getattr(mod, "__version__", "unknown")
    except ImportError:
        return "not installed"


def _feature_rows():
    """(name, available, note) for every optional subsystem."""
    rows = []
    import jax
    platform_name = jax.default_backend()
    on_tpu = platform_name == "tpu"
    rows.append(("tpu backend", on_tpu, f"backend={platform_name}"))

    from deepspeed_tpu.ops.op_builder import AsyncIOBuilder, CPUAdamBuilder
    for label, builder in (("async_io (C++ aio pool)", AsyncIOBuilder),
                           ("cpu_adam (host offload)", CPUAdamBuilder)):
        try:
            b = builder()
            ok = b.is_compatible()
            note = "builds on demand" if ok else "toolchain/libaio missing"
            if ok:
                b.load()
                note = "built"
        except Exception as e:
            ok, note = False, f"{type(e).__name__}: {e}"
        rows.append((label, ok, note))

    try:
        import jax.experimental.pallas  # noqa: F401
        rows.append(("pallas kernels", True, "flash/block-sparse attention"))
    except ImportError:
        rows.append(("pallas kernels", False, "pallas unavailable"))

    multi = False
    try:
        multi = jax.process_count() > 1
    except Exception:  # dslint: disable=DS006 — best-effort report probe
        pass
    rows.append(("multi-host runtime", multi,
                 f"{jax.process_count() if multi else 1} process(es)"))
    return rows


def main():
    import jax
    import deepspeed_tpu

    lines = ["-" * 70, "DeepSpeed-TPU environment report", "-" * 70]
    lines.append(f"deepspeed_tpu ........ {deepspeed_tpu.__version__}")
    lines.append(f"python ............... {sys.version.split()[0]} "
                 f"({platform.platform()})")
    for mod in ("jax", "jaxlib", "flax", "optax", "orbax.checkpoint", "numpy"):
        lines.append(f"{mod:<21}{'':.<1} {_version(mod)}")
    lines.append("-" * 70)

    devs = jax.devices()
    lines.append(f"devices: {len(devs)} x {devs[0].device_kind} "
                 f"(process {jax.process_index()}/{jax.process_count()})")
    try:
        stats = jax.local_devices()[0].memory_stats()
        if stats and "bytes_limit" in stats:
            lines.append(f"HBM per device: {stats['bytes_limit'] / 1e9:.1f} GB")
    except Exception:  # dslint: disable=DS006 — best-effort report probe
        pass
    from deepspeed_tpu.utils import setup_compile_cache
    via = "JAX_COMPILATION_CACHE_DIR" \
        if os.environ.get("JAX_COMPILATION_CACHE_DIR") else "default"
    lines.append(f"compilation cache: {setup_compile_cache()} ({via})")
    lines.append("-" * 70)

    for name, ok, note in _feature_rows():
        status = GREEN_OK if ok else RED_NO
        lines.append(f"{name:<28} {status}  {note}")
    lines.append("-" * 70)
    print("\n".join(lines))
    return lines


if __name__ == "__main__":
    main()
