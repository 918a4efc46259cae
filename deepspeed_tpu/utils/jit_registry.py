"""The shared definition of "what counts as a jit entry point".

Two independent guards police the compile contract and used to disagree
about the set of callables it covers:

- ``utils/compile_guard.py`` (CompileWatch) counts *runtime* compiles by
  listening for the ``backend_compile`` monitoring event, falling back
  to ``_cache_size()`` deltas of explicitly registered jitted callables;
- ``tools/dslint`` (DS002/DS003, and the v2 interprocedural DS011/DS012)
  pattern-matches jit wrapper *syntax* in the AST.

When one side learns a new spelling (``pjit``, ``functools.partial(
jax.jit, ...)``) and the other doesn't, a callable is watched at runtime
but invisible to the lint — or vice versa. This module is the single
source of truth both import: the wrapper name-chains, the donation/
static keyword names, and the monitoring-event stem. It is deliberately
**pure stdlib** (no jax import): dslint loads it straight from the file
path (``tools/dslint/symbols.py``) so linting never imports the code
under analysis.
"""

from typing import Optional, Sequence, Tuple

# Dotted-name chains that wrap a python callable into an XLA-compiled
# entry point. Matched against ``ast`` attribute chains by dslint and
# usable for runtime predicates. ("jit",)/( "pjit",) cover
# ``from jax import jit`` style imports used in older layers.
JIT_WRAPPER_CHAINS: Tuple[Tuple[str, ...], ...] = (
    ("jax", "jit"), ("jit",),
    ("jax", "pjit"), ("pjit",),
    ("jax", "experimental", "pjit", "pjit"),
)

# Keyword names on the wrapper call that change the entry point's
# aliasing/caching contract. DS003/DS011 read DONATE_KWARGS; DS002/DS004
# read STATIC_KWARGS; CompileWatch doesn't care but the names live here
# so a future spelling lands in both tools at once.
DONATE_KWARGS: Tuple[str, ...] = ("donate_argnums", "donate_argnames")
STATIC_KWARGS: Tuple[str, ...] = ("static_argnums", "static_argnames")

# Substring (not equality) of the jax.monitoring duration event every
# XLA compilation fires: jax has moved the event between
# /jax/core/compile/backend_compile_duration and sibling names across
# releases; every variant keeps this stem.
COMPILE_EVENT_STEM = "backend_compile"


def is_jit_chain(chain: Sequence[str]) -> bool:
    """True when ``chain`` (a dotted-name list like ``["jax", "jit"]``)
    spells a jit wrapper."""
    return tuple(chain) in JIT_WRAPPER_CHAINS


def is_compile_event(event_name: str) -> bool:
    """True when a jax.monitoring duration event records a backend
    compilation (the thing CompileWatch counts)."""
    return COMPILE_EVENT_STEM in event_name


def cache_size(jitted_fn) -> Optional[int]:
    """Number of compiled programs held by a jitted callable, or None
    when the jax build doesn't expose it. Use to pin 'exactly N
    programs' (cache sizes) alongside CompileWatch's 'zero new
    compiles' (cache deltas)."""
    probe = getattr(jitted_fn, "_cache_size", None)
    if probe is None:
        return None
    return int(probe())


# Serving-side program catalog. The paged engine holds ONE jitted
# callable per family (``InferenceEngine._<stem>``). The int8 scale pools
# and the adapter operands reach it as optional pytree operands, so a
# family's variants are cache entries of that one callable, told apart
# by jax from the structure of what it is handed. The cost registry
# (telemetry/costs.py), the accountant and the ``program_*_<pid>`` gauges
# still name a variant by its own id: a cost card differs by variant, and
# the ids are a documented contract (tools/dslint/telemetry_schema.json,
# docs/OBSERVABILITY.md). ``program_id`` is the one place an id is made.
# Rows: (family stem, dispatch class, takes adapter operands); the block
# copies move cache bytes, not weights, and have no adapter variant.
DISPATCH_CLASSES: Tuple[str, ...] = (
    "prefill", "decode", "verify", "cow", "spill")
ENGINE_PROGRAM_FAMILIES: Tuple[Tuple[str, str, bool], ...] = (
    ("prefill_slot", "prefill", True),
    ("decode_slots", "decode", True),
    ("decode_horizon", "decode", True),
    ("verify_slots", "verify", True),
    ("cow_blocks", "cow", False),
    ("gather_blocks", "spill", False),
    ("scatter_block", "spill", False),
)


def program_id(stem: str, scales: bool = False, adapter: bool = False) -> str:
    """The id of one variant of family ``stem``: the stem itself for the
    fp program, ``_q`` with int8 scale pools, ``_l`` with adapter
    operands, ``_ql`` with both."""
    mark = ("q" if scales else "") + ("l" if adapter else "")
    return f"{stem}_{mark}" if mark else stem


def engine_programs() -> Tuple[Tuple[str, str, str], ...]:
    """``(program_id, engine_attr, dispatch_class)`` for every variant of
    every family, a family's variants all naming its one engine
    attribute: ``("decode_slots_ql", "_decode_slots", "decode")``."""
    return tuple(
        (program_id(stem, scales, adapter), "_" + stem, cls)
        for stem, cls, adapters in ENGINE_PROGRAM_FAMILIES
        for adapter in ((False, True) if adapters else (False,))
        for scales in (False, True))
