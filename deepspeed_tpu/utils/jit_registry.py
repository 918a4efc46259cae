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


# Serving-side program catalog: every jitted entry point the paged
# engine dispatches in steady state, by family stem and precision/LoRA
# twin suffix ("" fp, "_q" int8 KV, "_l" LoRA, "_ql" both). The cost
# registry (telemetry/costs.py) walks this table to probe
# ``cost_analysis()``/``memory_analysis()`` per program, and the
# per-dispatch accountant keys its charges on the same program ids —
# one table so the two planes can never disagree about what exists.
# ``cow_blocks`` and the host-tier transfer programs have no LoRA
# variant (they move cache bytes, not weights).
ENGINE_PROGRAM_FAMILIES: Tuple[Tuple[str, Tuple[str, ...]], ...] = (
    ("prefill_slot", ("", "_q", "_l", "_ql")),
    ("decode_slots", ("", "_q", "_l", "_ql")),
    ("decode_horizon", ("", "_q", "_l", "_ql")),
    ("verify_slots", ("", "_q", "_l", "_ql")),
    ("cow_blocks", ("", "_q")),
    ("gather_blocks", ("", "_q")),
    ("scatter_block", ("", "_q")),
)

# Declared per-feature twin deltas: what a feature suffix is ALLOWED to
# change relative to the base program. dslint's DS015 normalizes each
# twin's AST modulo this spec and flags any other divergence, so an edit
# to ``_decode_slots_fn`` that misses ``_decode_slots_q_fn`` is a lint
# error instead of a silent parity bug. Suffix characters compose:
# ``_ql`` owns the union of the "q" and "l" deltas.
#
#   params : extra positional parameters the twin's signature may add
#   names  : local/parameter names the feature owns — any statement or
#            tuple/call element mentioning ONLY these is feature-owned
#            and stripped before comparison (q: the scale pools beside
#            the int8 pools; l: the adapter pools and table rows)
#   kwargs : call keywords the twin may thread through (``k_scale=``,
#            ``lora_ops=``) that the base never passes
TWIN_DELTAS = {
    "q": {
        "params": ("k_scale", "v_scale", "ks_blk", "vs_blk"),
        "names": ("k_scale", "v_scale", "ks_blk", "vs_blk"),
        "kwargs": ("k_scale", "v_scale"),
    },
    "l": {
        "params": ("lora_a", "lora_b", "ablocks", "ablock_row"),
        "names": ("lora_a", "lora_b", "ablocks", "ablock_row",
                  "lora", "lora_ops"),
        "kwargs": ("lora", "lora_ops"),
    },
}


# program family stem -> dispatch class the accountant rolls it into
DISPATCH_CLASSES: Tuple[str, ...] = (
    "prefill", "decode", "verify", "cow", "spill")
_FAMILY_CLASS = {
    "prefill_slot": "prefill",
    "decode_slots": "decode",
    "decode_horizon": "decode",
    "verify_slots": "verify",
    "cow_blocks": "cow",
    "gather_blocks": "spill",
    "scatter_block": "spill",
}


def engine_programs() -> Tuple[Tuple[str, str, str], ...]:
    """``(program_id, engine_attr, dispatch_class)`` for every serving
    program: ``("decode_slots_ql", "_decode_slots_ql", "decode")``."""
    out = []
    for stem, suffixes in ENGINE_PROGRAM_FAMILIES:
        for suf in suffixes:
            out.append((stem + suf, "_" + stem + suf, _FAMILY_CLASS[stem]))
    return tuple(out)


def dispatch_class(program_id: str) -> str:
    """Dispatch class for a program id (``decode_horizon_q`` →
    ``decode``); raises ``KeyError`` on an unknown id."""
    for stem, suffixes in ENGINE_PROGRAM_FAMILIES:
        for suf in suffixes:
            if program_id == stem + suf:
                return _FAMILY_CLASS[stem]
    raise KeyError(f"unknown engine program id: {program_id!r}")
