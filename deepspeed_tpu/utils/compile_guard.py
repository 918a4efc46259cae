"""Compile-count regression guard.

The serving engine's perf story rests on a compile contract: steady
state is exactly TWO compiled programs (`_prefill_slot`, `_decode_slots`)
and ZERO recompiles across admission, eviction and requeue.  Nothing in
the code *structurally* prevents a refactor from silently breaking that
— a dynamic shape, a fresh lambda, a python int leaking into a traced
position all recompile quietly and only show up as a latency cliff on
the chip.  ``CompileWatch`` turns the contract into an executable assert.

Counting strategy, in preference order:

1. ``jax.monitoring`` duration events.  Every XLA compilation fires
   ``/jax/core/compile/backend_compile_duration`` exactly once, so a
   registered listener counts real backend compiles — including eager-op
   programs that no jit cache ever sees (the failure mode PR 2's
   per-slot ``logits[i:i+1]`` slice would have been).
2. For jax builds without ``jax.monitoring`` (or with the event renamed)
   a jit-wrapper fallback: ``CompileWatch.wrap(fn)`` snapshots
   ``fn._cache_size()`` deltas for explicitly registered jitted
   callables.  Narrower — it only sees tracing-cache growth of wrapped
   functions — but it keeps the guard meaningful on old jax.

Usage::

    with CompileWatch(max_compiles=0) as w:
        engine.step(); engine.step()
    # raises RecompileError on exit if anything compiled

    w = CompileWatch()
    with w:
        run_workload()
    assert w.compiles <= 2

The watch only *asserts on clean exit* — an exception inside the body
propagates untouched (masking the original failure with a compile-count
complaint would be strictly worse).
"""

import threading
from typing import List, Optional

import jax

# what counts as a jit entry point (wrapper chains, compile-event stem)
# is shared with tools/dslint via jit_registry so the runtime watch and
# the static lint police the same callable set
from deepspeed_tpu.utils.jit_registry import (COMPILE_EVENT_STEM,
                                              is_compile_event)
from deepspeed_tpu.utils.jit_registry import cache_size as _registry_cache_size

_COMPILE_EVENT_STEM = COMPILE_EVENT_STEM  # back-compat alias


class RecompileError(AssertionError):
    """Raised when a CompileWatch block compiled more than allowed."""


def _monitoring_api():
    """(register, unregister) for duration listeners, or None."""
    mon = getattr(jax, "monitoring", None)
    reg = getattr(mon, "register_event_duration_secs_listener", None)
    if reg is None:
        return None
    try:
        from jax._src import monitoring as _mon_impl
        unreg = getattr(
            _mon_impl, "_unregister_event_duration_listener_by_callback",
            None)
    except Exception:  # dslint: disable=DS006 — private API probe; fallback below
        unreg = None
    return reg, unreg


class CompileWatch:
    """Count XLA compilations inside a ``with`` block and (optionally)
    assert a ceiling.

    Args:
      max_compiles: raise :class:`RecompileError` on clean exit when
        more than this many compilations happened inside the block.
        ``None`` (default) means count only, never raise.
      label: prefix for the error message — name the contract being
        enforced (e.g. ``"serving steady state"``).
    """

    def __init__(self, max_compiles: Optional[int] = None,
                 label: str = "CompileWatch"):
        self.max_compiles = max_compiles
        self.label = label
        self.compiles = 0
        self.events: List[str] = []
        self._lock = threading.Lock()
        self._armed = False
        self._listener = None
        self._unreg = None
        self._wrapped = []  # (jitted_fn, cache_size_at_enter)

    # -- jit-wrapper fallback -------------------------------------------

    def wrap(self, jitted_fn):
        """Register a jitted callable for the cache-size fallback and
        return it unchanged.

        Harmless (and free) when event monitoring is active; on jax
        builds without ``jax.monitoring`` the watch counts
        ``_cache_size()`` growth of every wrapped function instead.
        """
        if hasattr(jitted_fn, "_cache_size"):
            self._wrapped.append(jitted_fn)
        return jitted_fn

    @property
    def monitored(self) -> bool:
        """True when real event-based counting is active."""
        return self._listener is not None

    # -- context manager ------------------------------------------------

    def __enter__(self):
        self.compiles = 0
        self.events = []
        self._armed = True
        api = _monitoring_api()
        if api is not None:
            reg, self._unreg = api

            def _on_event(event, duration=None, **kw):
                if not is_compile_event(event):
                    return
                with self._lock:
                    if self._armed:
                        self.compiles += 1
                        self.events.append(event)

            self._listener = _on_event
            reg(_on_event)
        self._wrap_base = [(f, f._cache_size()) for f in self._wrapped]
        return self

    def __exit__(self, exc_type, exc, tb):
        with self._lock:
            self._armed = False
        if self._listener is not None and self._unreg is not None:
            try:
                self._unreg(self._listener)
            except Exception:  # dslint: disable=DS006 — private unregister API; the disarm flag above already silences the listener
                pass
        if self._listener is None:
            # fallback: tracing-cache growth of registered callables
            self.compiles = sum(
                max(0, f._cache_size() - base) for f, base in self._wrap_base)
        if exc_type is not None:
            return False  # never mask the body's own failure
        if self.max_compiles is not None and self.compiles > self.max_compiles:
            raise RecompileError(
                f"{self.label}: {self.compiles} compilation(s) inside the "
                f"watched block (allowed {self.max_compiles}). Events: "
                f"{self.events or '(cache-size fallback)'} — a traced shape, "
                f"python value in a traced position, or fresh callable is "
                f"defeating the compile cache.")
        return False


_process_compiles = [0]
_process_listener = [False]


def compile_count() -> int:
    """Backend compilations in this process since the first call (which
    registers one listener for good: jax has no public way to remove
    one). A caller attributes compilations to a call by taking the
    difference round it: jax compiles on the calling thread, inside the
    call. Without ``jax.monitoring`` the count stays 0."""
    if not _process_listener[0]:
        _process_listener[0] = True
        api = _monitoring_api()
        if api is not None:
            def _on_event(event, duration=None, **kw):
                if is_compile_event(event):
                    _process_compiles[0] += 1
            api[0](_on_event)
    return _process_compiles[0]


def cache_size(jitted_fn) -> Optional[int]:
    """Number of compiled programs held by a jitted callable, or None
    when the jax build doesn't expose it.  Use to pin 'exactly N
    programs' (cache sizes) alongside CompileWatch's 'zero new
    compiles' (cache deltas).  (Implementation lives in
    :mod:`~deepspeed_tpu.utils.jit_registry`, the shared jit-entry-point
    definition; this re-export keeps the historical import path.)"""
    return _registry_cache_size(jitted_fn)
