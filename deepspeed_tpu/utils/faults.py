"""Deterministic fault injection — the chaos substrate for the serving
and checkpoint robustness layers.

Production serving dies in ways unit tests never exercise: a cache-
exhaustion storm mid-decode, a device that throws once and recovers, a
decode step that silently takes 100x its budget, a host that crashes
between writing checkpoint state and updating the ``latest`` pointer.
This module makes every one of those failure modes a *scheduled,
reproducible event*: a :class:`FaultInjector` carries an ordered set of
:class:`Fault` specs, each bound to a named **site** (a point in the
code that calls :func:`FaultInjector.fire`) and a **visit index** at
which it triggers. Same spec + same seed → the identical failure
sequence, so chaos tests assert exact outcomes (token parity, which tag
``load_checkpoint`` lands on) instead of "it didn't crash".

Sites currently instrumented:

====================== =====================================================
``serving.decode``     before each batched decode-slots dispatch
``serving.prefill``    before each prefill-chunk dispatch
``cache.ensure``       inside ``PagedKVCache.ensure_capacity`` (growth);
                       while any fault is armed the scheduler visits it
                       once a decoding slot a step, else only for the
                       slots whose table grows (docs/ROBUSTNESS.md)
``cache.allocate``     inside ``PagedKVCache.allocate`` (admission)
``cache.match``        before the prefix-index lookup in ``allocate``;
                       ``cache_exhausted`` degrades the request to a
                       cold miss (served correctly, no sharing)
``cache.cow``          before the copy-on-write block copy (and before
                       ANY bookkeeping mutates); ``cache_exhausted``
                       raises CacheExhausted — the admission retries
``cache.quantize``     inside the engine's paged public wrappers when
                       ``kv_quant=int8``, after the ``engine.*`` site
                       and still BEFORE the device dispatch — donated
                       pool/scale buffers are untouched, so the
                       serving retry replays the step safely
``cache.spill``        before a spill batch's gather dispatch in the
                       host-tier spill daemon (``spill_tick``);
                       ``cache_exhausted`` skips the batch — blocks
                       stay device-resident behind exponential backoff
``cache.restore``      before a host→device block restore on a prefix
                       match; ``cache_exhausted`` truncates the match
                       there (the tail re-prefills; the host entry
                       survives for a later retry)
``cache.host_corrupt`` at restore time, AFTER ``cache.restore``
                       passed; ``cache_exhausted`` flips a real byte of
                       the stored block so the CRC32 check itself
                       drives the degrade path (chain discarded,
                       cold-miss re-prefill — never wrong tokens)
``cache.adapter_load`` before a LoRA adapter's pool load at admission
                       (``AdapterPool.acquire``), BEFORE any pool
                       state moves; ``device_error``/``cache_exhausted``
                       degrade that request to a structured ``error``
                       terminal state — the batch keeps serving, never
                       wrong tokens — while ``crash`` kills the replica
                       (the router drains it) (docs/ADAPTERS.md)
``engine.decode``      ``InferenceEngine.decode_slots`` public wrapper
``engine.verify``      ``InferenceEngine.verify_slots`` public wrapper
                       (speculative verify); the scheduler degrades the
                       step to plain one-token decode, never retries
``serving.spec_draft`` before the per-slot draft proposals each
                       speculative step; same degrade-to-plain contract
``serving.horizon``    before the fused multi-step decode dispatch each
                       horizon step, BEFORE any capacity or slot state
                       moves; the scheduler degrades the step to N=1
                       single-step decode — never retried, never a
                       dropped token (docs/MULTISTEP.md)
``checkpoint.pre_commit``  after state write, BEFORE the tag dir commit
``checkpoint.commit``  after the tag dir commit, BEFORE ``latest`` update
``router.dispatch``    after the router picks a target replica, BEFORE
                       the request is submitted to it — a retry re-picks
                       against untouched replicas
``router.step``        before each per-replica step in the router's
                       round-robin loop; ``crash`` kills that replica
                       (its in-flight work drains onto survivors)
``router.drain``       at the start of a dead replica's drain, BEFORE
                       any snapshot/redistribution state moves
``router.migrate_gather``  before the source replica gathers a finished
                       prefill's KV blocks into host DRAM for a
                       replica-to-replica migration; any failure falls
                       back to cold re-prefill on the decode side
``router.migrate_scatter``  before the destination replica lands the
                       migrated blocks free-list-only into its own
                       pool; failure (including capacity refusal)
                       discards the partial landing and falls back cold
``router.migrate_corrupt``  after the gather passed, before the landing
                       fetch; ``cache_exhausted`` flips a real stored
                       byte so the genuine per-array CRC32 verify
                       drives the fallback — never wrong tokens
====================== =====================================================

Fault kinds and what firing does:

- ``device_error`` — raises :class:`TransientDeviceError` (the serving
  engine retries with exponential backoff + deterministic jitter);
- ``crash`` — raises :class:`InjectedCrash` (simulated process death:
  the exception unwinds past the save path exactly where ``kill -9``
  would cut it);
- ``slow`` — sleeps ``param`` seconds inside the caller's timed region
  (drives the step watchdog); a hung step is a ``slow`` fault whose
  param exceeds the step budget;
- ``cache_exhausted`` — returned to the site, which raises its own
  domain exception (:class:`~deepspeed_tpu.inference.paged_cache.
  CacheExhausted`) so the scheduler's eviction path runs for real.

The ambient injector is either :func:`install`-ed programmatically
(tests use the :func:`injected` context manager) or parsed once from
``DS_FAULTS`` / ``DS_FAULT_SEED``::

    DS_FAULTS="serving.decode:device_error@3;checkpoint.commit:crash@0"
    DS_FAULT_SEED=0

Entry grammar: ``site:kind@step[*count][~param]`` joined by ``;`` —
fire ``kind`` at ``site`` on visits ``[step, step+count)`` with float
``param`` (sleep seconds for ``slow``).
"""

import time
import warnings
import weakref
from contextlib import contextmanager
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np


class FaultError(Exception):
    """Base class for every injected failure."""


class UnknownFaultSiteWarning(UserWarning):
    """A fault spec names a site no code path ever fires — almost
    always a typo (``serving.prefil``): the chaos config would silently
    inject nothing. Tests running with warnings-as-errors fail loudly."""


class TransientDeviceError(FaultError):
    """A device dispatch failed in a retryable way (injected analog of a
    one-off XLA/runtime error; the serving engine's backoff handles it)."""


class InjectedCrash(FaultError):
    """Simulated process death: raised where the process would die, so
    everything after the site (e.g. the ``latest`` pointer update) never
    happens — the crash-consistency scenario checkpoint tests drive."""


@dataclass(frozen=True)
class Fault:
    """One scheduled failure: fire ``kind`` at ``site`` on visit
    indices ``[step, step + count)``. ``param`` is kind-specific
    (sleep seconds for ``slow``)."""
    site: str
    kind: str
    step: int = 0
    count: int = 1
    param: float = 0.0

    def matches(self, visit: int) -> bool:
        return self.step <= visit < self.step + self.count


KINDS = ("device_error", "crash", "slow", "cache_exhausted")

# every site some shipped code path fires (the module-docstring table);
# subsystems adding sites register them so parse_spec can flag typos
KNOWN_SITES = {
    "serving.decode", "serving.prefill", "serving.spec_draft",
    "serving.horizon",
    "engine.prefill", "engine.decode", "engine.verify",
    "cache.allocate", "cache.ensure", "cache.match", "cache.cow",
    "cache.quantize", "cache.spill", "cache.restore", "cache.host_corrupt",
    "cache.adapter_load",
    "checkpoint.pre_commit", "checkpoint.commit",
    "router.dispatch", "router.step", "router.drain",
    "router.migrate_gather", "router.migrate_scatter",
    "router.migrate_corrupt",
}

_warned_sites: set = set()


def register_site(site: str) -> None:
    """Declare ``site`` as a real fire point (plugins/tests adding
    their own sites keep :func:`parse_spec` quiet about them)."""
    KNOWN_SITES.add(site)


def parse_spec(spec: str) -> List[Fault]:
    """Parse the ``DS_FAULTS`` grammar (see module docstring). A spec
    naming a site nothing ever fires warns ONCE per site
    (:class:`UnknownFaultSiteWarning`) — a typo'd chaos config should
    fail loudly in tests, not silently inject nothing."""
    faults: List[Fault] = []
    for entry in spec.replace(",", ";").split(";"):
        entry = entry.strip()
        if not entry:
            continue
        try:
            site, rest = entry.split(":", 1)
            kind, rest = rest.split("@", 1)
            param = 0.0
            count = 1
            if "~" in rest:
                rest, p = rest.split("~", 1)
                param = float(p)
            if "*" in rest:
                rest, c = rest.split("*", 1)
                count = int(c)
            step = int(rest)
        except ValueError as e:
            raise ValueError(
                f"bad fault spec entry {entry!r} (want "
                f"site:kind@step[*count][~param]): {e}") from e
        if kind not in KINDS:
            raise ValueError(f"unknown fault kind {kind!r} in {entry!r} "
                             f"(known: {', '.join(KINDS)})")
        site = site.strip()
        if site not in KNOWN_SITES and site not in _warned_sites:
            _warned_sites.add(site)
            warnings.warn(
                f"fault spec names unknown site {site!r} — no "
                f"instrumented code path fires it, so this entry "
                f"injects nothing (known sites: "
                f"{', '.join(sorted(KNOWN_SITES))})",
                UnknownFaultSiteWarning, stacklevel=2)
        faults.append(Fault(site=site, kind=kind.strip(),
                            step=step, count=count, param=param))
    return faults


class FaultInjector:
    """Deterministic, seedable fault scheduler.

    ``visit(site)`` increments the site's visit counter and returns the
    matching :class:`Fault` (or None); ``fire(site)`` additionally acts
    on the generic kinds (raise / sleep) and returns domain-specific
    kinds (``cache_exhausted``) for the site to interpret. ``fired``
    logs every triggered fault as ``(site, kind, visit)`` so tests can
    assert the chaos actually happened.

    ``rng`` is a seeded generator shared with the serving engine's
    retry jitter: one seed pins the whole failure-and-recovery timeline.
    """

    def __init__(self, faults: Sequence[Fault] = (), seed: int = 0):
        self.faults: List[Fault] = list(faults)
        self.seed = int(seed)
        self.rng = np.random.default_rng(self.seed)
        self.visits: Dict[str, int] = {}
        self.fired: List[Tuple[str, str, int]] = []
        # observers notified on every fired fault (before the kind
        # acts, so a raise still reaches them): telemetry tracers tag
        # chaos events into the request-lifecycle timeline here. Held
        # WEAKLY: the ambient injector lives as long as the process, and a
        # strong reference to a serving engine's callback would pin the
        # engine, its parameters and its KV pools on the device for good
        self._listeners: List[weakref.ref] = []

    def add_listener(self, cb) -> None:
        """Register ``cb(site, kind, visit)``, called on every fired
        fault (including ones that then raise). The subscriber keeps
        ``cb`` alive; the injector drops it when the subscriber dies."""
        self._listeners.append(weakref.ref(cb))

    @classmethod
    def from_env(cls, env=None) -> "FaultInjector":
        # ambient chaos config; tests pin it via install()/injected().
        # resolve_flag carries the declared defaults ("" / seed 0) and
        # honors the explicit env mapping chaos tests pass in
        from deepspeed_tpu.utils.env import resolve_flag
        spec = resolve_flag("DS_FAULTS", env=env)
        seed = resolve_flag("DS_FAULT_SEED", env=env)
        return cls(parse_spec(spec), seed=seed)

    # -- scheduling ----------------------------------------------------
    def visit(self, site: str) -> Optional[Fault]:
        n = self.visits.get(site, 0)
        self.visits[site] = n + 1
        if not self.faults:
            return None
        for f in self.faults:
            if f.site == site and f.matches(n):
                self.fired.append((site, f.kind, n))
                live = [(ref, ref()) for ref in self._listeners]
                self._listeners = [ref for ref, cb in live if cb is not None]
                for _, cb in live:
                    if cb is not None:
                        cb(site, f.kind, n)
                return f
        return None

    def fire(self, site: str) -> Optional[Fault]:
        """Visit ``site`` and act on the matched fault: raise the
        generic kinds, sleep for ``slow``, return the rest."""
        f = self.visit(site)
        if f is None:
            return None
        n = self.visits[site] - 1
        if f.kind == "device_error":
            raise TransientDeviceError(
                f"injected device error at {site} (visit {n})")
        if f.kind == "crash":
            raise InjectedCrash(f"injected crash at {site} (visit {n})")
        if f.kind == "slow":
            time.sleep(f.param)
        return f

    def jitter(self, scale: float) -> float:
        """Deterministic backoff jitter in ``[0, scale)``."""
        return float(self.rng.uniform(0.0, scale))

    def reset(self) -> None:
        """Rewind visit counters and the rng — same timeline replays."""
        self.visits.clear()
        self.fired.clear()
        self.rng = np.random.default_rng(self.seed)


# -- ambient injector --------------------------------------------------
_active: Optional[FaultInjector] = None


def active() -> FaultInjector:
    """The ambient injector: installed one, else env-derived (parsed
    once; an empty ``DS_FAULTS`` yields a no-op injector)."""
    global _active
    if _active is None:
        _active = FaultInjector.from_env()
    return _active


def install(injector: Optional[FaultInjector]) -> Optional[FaultInjector]:
    """Install ``injector`` as the ambient one (None re-derives from the
    env on next use). Returns the previous injector for restore."""
    global _active
    prev = _active
    _active = injector
    return prev


def maybe_fire(site: str) -> Optional[Fault]:
    """Module-level site hook: fire against the ambient injector. The
    no-fault fast path is one dict get + compare."""
    return active().fire(site)


@contextmanager
def injected(*faults: Fault, seed: int = 0):
    """Install a fresh injector for the block (tests)::

        with faults.injected(Fault("serving.decode", "device_error",
                                   step=3)) as inj:
            srv.run(reqs)
        assert inj.fired
    """
    inj = FaultInjector(faults, seed=seed)
    prev = install(inj)
    try:
        yield inj
    finally:
        install(prev)
