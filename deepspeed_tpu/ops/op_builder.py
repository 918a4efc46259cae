"""JIT builder for native (C++) host ops.

TPU-native analog of the reference's op build system
(ref: op_builder/builder.py:107 OpBuilder.load / :524 CUDAOpBuilder):
the reference JIT-compiles CUDA/C++ pybind11 extensions on first use; here
the native surface is host-only (async file I/O, AVX optimizer steps), so we
compile a plain shared library with ``g++`` and bind it with ``ctypes`` —
no pybind11 in the image, and ctypes avoids a Python ABI dependency.

Build artifacts are cached under ``<repo>/build/`` keyed by a hash of the
sources, the flags and, under ``-march=native``, the host CPU, so repeat
imports are instant and a library built on one machine is never loaded on
another.
"""

import ctypes
import hashlib
import os
import platform
import subprocess
import threading
from typing import List, Optional

from deepspeed_tpu.utils.logging import logger

_PKG_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_CSRC = os.path.join(_PKG_DIR, "csrc")
_BUILD_DIR = os.environ.get(  # dslint: disable=DS005,DS013 — build-dir path for the op compiler, read once at import on purpose; a path, not a feature flag, so it stays outside the FLAGS registry
    "DS_TPU_BUILD_DIR",
    os.path.join(os.path.dirname(_PKG_DIR), "build"))

_lock = threading.Lock()
_loaded = {}


def _host_cpu_id() -> str:
    """What ``-march=native`` resolves to here: the model and feature
    flags of the first CPU."""
    try:
        with open("/proc/cpuinfo") as f:
            seen = {}
            for line in f:
                key = line.split(":", 1)[0].strip()
                if key in ("model name", "flags", "Features") \
                        and key not in seen:
                    seen[key] = line.strip()
            if seen:
                return "|".join(seen.values())
    except OSError:
        pass
    return f"{platform.machine()}|{platform.processor()}"


class OpBuilder:
    """Compile a list of C++ sources into a shared lib, return a CDLL.

    Mirrors the reference builder's contract: ``load()`` either returns the
    cached library or compiles it (ref: op_builder/builder.py:107).
    """

    name: str = ""
    sources: List[str] = []
    extra_flags: List[str] = []

    def __init__(self):
        self._lib: Optional[ctypes.CDLL] = None

    def abs_sources(self) -> List[str]:
        return [os.path.join(_CSRC, s) for s in self.sources]

    def cxx_flags(self) -> List[str]:
        march = [] if os.environ.get("DS_TPU_NO_NATIVE_ARCH") else ["-march=native"]  # dslint: disable=DS005,DS013 — compiler-flag escape hatch for the native build, truthiness on purpose (any value disables)
        return (["-O3", "-std=c++17", "-shared", "-fPIC", "-fopenmp",
                 "-Wall"] + march + list(self.extra_flags))

    def _hash(self) -> str:
        h = hashlib.sha256()
        for src in self.abs_sources():
            with open(src, "rb") as f:
                h.update(f.read())
        flags = self.cxx_flags()
        h.update(" ".join(flags).encode())
        if "-march=native" in flags:
            h.update(_host_cpu_id().encode())
        return h.hexdigest()[:16]

    def lib_path(self) -> str:
        return os.path.join(_BUILD_DIR, f"lib{self.name}_{self._hash()}.so")

    def is_compatible(self) -> bool:
        """Host ops need only a C++ toolchain (cf. ds_report compat matrix)."""
        try:
            subprocess.run(["g++", "--version"], capture_output=True, check=True)
            return True
        except (OSError, subprocess.CalledProcessError):
            return False

    def build(self) -> str:
        path = self.lib_path()
        if os.path.exists(path):
            return path
        os.makedirs(_BUILD_DIR, exist_ok=True)
        # compile to a process-private temp path, then atomically rename so a
        # concurrent process never dlopens a half-written library
        tmp = f"{path}.tmp.{os.getpid()}"
        cmd = ["g++"] + self.cxx_flags() + self.abs_sources() + [
            "-o", tmp, "-lpthread"]
        logger.info("building native op %s: %s", self.name, " ".join(cmd))
        proc = subprocess.run(cmd, capture_output=True, text=True)
        if proc.returncode != 0:
            raise RuntimeError(
                f"failed to build op '{self.name}':\n{proc.stderr}")
        os.replace(tmp, path)
        return path

    def load(self) -> ctypes.CDLL:
        with _lock:
            if self.name in _loaded:
                return _loaded[self.name]
            lib = ctypes.CDLL(self.build())
            self._decorate(lib)
            _loaded[self.name] = lib
            return lib

    def _decorate(self, lib: ctypes.CDLL) -> None:
        """Attach argtypes/restype signatures. Override per op."""


class AsyncIOBuilder(OpBuilder):
    """Async file I/O thread pool (ref: op_builder/async_io.py:10,
    csrc/aio/py_lib/deepspeed_aio_thread.cpp)."""

    name = "ds_aio"
    sources = ["aio/ds_aio.cpp"]

    def _decorate(self, lib):
        c = ctypes
        lib.ds_aio_create.argtypes = [c.c_int, c.c_int, c.c_long, c.c_int]
        lib.ds_aio_create.restype = c.c_void_p
        lib.ds_aio_destroy.argtypes = [c.c_void_p]
        for fn in (lib.ds_aio_pread, lib.ds_aio_submit_read):
            fn.argtypes = [c.c_void_p, c.c_void_p, c.c_long, c.c_char_p,
                           c.c_long]
            fn.restype = c.c_long
        for fn in (lib.ds_aio_pwrite, lib.ds_aio_submit_write):
            fn.argtypes = [c.c_void_p, c.c_void_p, c.c_long, c.c_char_p,
                           c.c_long]
            fn.restype = c.c_long
        lib.ds_aio_wait.argtypes = [c.c_void_p]
        lib.ds_aio_wait.restype = c.c_long
        lib.ds_aio_inflight.argtypes = [c.c_void_p]
        lib.ds_aio_inflight.restype = c.c_long
        lib.ds_aligned_alloc.argtypes = [c.c_long, c.c_long]
        lib.ds_aligned_alloc.restype = c.c_void_p
        lib.ds_aligned_free.argtypes = [c.c_void_p]


class CPUAdamBuilder(OpBuilder):
    """Vectorized host Adam/Adagrad/LAMB-trust step for offloaded optimizer
    state (ref: op_builder/cpu_adam.py, csrc/adam/cpu_adam.cpp:284,
    csrc/includes/cpu_adam.h:55 Step_AVX)."""

    name = "ds_cpu_adam"
    sources = ["adam/cpu_adam.cpp"]

    def _decorate(self, lib):
        c = ctypes
        fp = c.POINTER(c.c_float)
        u16 = c.POINTER(c.c_uint16)
        lib.ds_adam_update.argtypes = [
            c.c_long, fp, fp, fp, fp,
            c.c_float, c.c_float, c.c_float, c.c_float, c.c_float,
            c.c_float, c.c_float, c.c_int]
        lib.ds_adam_update_copy_bf16.argtypes = [
            c.c_long, fp, fp, fp, fp,
            c.c_float, c.c_float, c.c_float, c.c_float, c.c_float,
            c.c_float, c.c_float, c.c_int, u16]
        lib.ds_adagrad_update.argtypes = [
            c.c_long, fp, fp, fp, c.c_float, c.c_float, c.c_float]
        lib.ds_lamb_norms.argtypes = [c.c_long, fp, fp, fp]


ALL_OPS = {b.name: b for b in (AsyncIOBuilder(), CPUAdamBuilder())}
