# dslint: disable-file=DS005 — this IS the sanctioned env layer: every
# DS_* knob resolves here (DS013), so the ambient read is the point
"""Central registry + resolver for every ``DS_*`` environment switch.

Before this module each subsystem carried its own copy of the same
resolve-a-knob ritual — read ``os.environ``, strip/lower, accept the
same five spellings of off and four of on, raise ``ValueError`` on
garbage — a dozen near-identical blocks whose inevitable drift was
invisible (``resolve_telemetry`` silently coerced garbage to off while
its siblings raised). Now there is ONE parser and ONE table:

- :data:`FLAGS` declares every knob: name, type, default, choices and
  a one-line help string. The declared default IS the bit-reference
  off-state — the serving stack's contract that every feature switch
  defaults to the behavior the parity tests pin (dslint DS013 checks
  this mechanically by parsing this table).
- :func:`resolve_flag` is the only place environment state is read.
  Subsystem ``resolve_*`` helpers stay as the public API (explicit
  argument wins, then env, then default) but delegate parsing here.

dslint's DS013 rule flags any literal ``DS_*`` env read elsewhere under
``deepspeed_tpu/`` and any ``resolve_flag`` call naming a flag this
table doesn't declare, so adding a knob without declaring it — or
declaring it default-on — fails the lint, not a code review.
"""

import os
from dataclasses import dataclass, field
from typing import Dict, Mapping, Optional, Tuple

__all__ = ["Flag", "FLAGS", "resolve_flag", "flag_names",
           "MAX_DECODE_HORIZON", "resolve_decode_horizon"]

# the shared bool grammar every DS_* switch accepts; "" (unset) is off
TRUE_WORDS = ("on", "1", "true", "yes")
FALSE_WORDS = ("", "off", "0", "false", "no")


@dataclass(frozen=True)
class Flag:
    """One declared environment knob.

    ``kind`` selects the parser: ``bool`` (the on/off grammar above),
    ``int``, ``float``, ``str`` (returned verbatim, stripped), or
    ``choice`` (normalized via ``aliases`` then validated against
    ``choices``). ``default`` is returned when the variable is unset or
    empty — by contract the bit-reference off-state for feature
    switches. ``aliases`` maps accepted spellings onto canonical choice
    values (``"on" -> "int8"`` for DS_KV_QUANT).
    """
    name: str
    kind: str
    default: object
    help: str
    choices: Tuple[str, ...] = ()
    aliases: Mapping[str, str] = field(default_factory=dict)


def _mk(name, kind, default, help, **kw) -> Tuple[str, Flag]:
    return name, Flag(name=name, kind=kind, default=default, help=help, **kw)


# The registry. Feature switches (kind=bool) MUST default False — the
# off-state is the behavioral bit-reference (docs/LINT.md DS013).
FLAGS: Dict[str, Flag] = dict([
    _mk("DS_TELEMETRY", "bool", False,
        "metrics/tracer/spans plane on the serving engine; off is "
        "the no-op bit-reference (docs/OBSERVABILITY.md)"),
    _mk("DS_PREFIX_CACHE", "bool", False,
        "shared-prefix KV cache with refcounted blocks + COW; off is "
        "the refcount-free allocator bit-reference (docs/PREFIX_CACHE.md)"),
    _mk("DS_SPEC_DECODE", "bool", False,
        "speculative serving (draft + k+1 verify per slot); off is the "
        "plain one-token-decode bit-reference (docs/SPECULATIVE.md)"),
    _mk("DS_SPEC_DRAFT", "str", "ngram",
        "named drafter for speculative serving; 'ngram' (prompt-lookup) "
        "is the only named one — model drafters pass an object"),
    _mk("DS_SPEC_K", "int", 4,
        "draft chunk length per speculative step (docs/SPECULATIVE.md)"),
    _mk("DS_KV_QUANT", "choice", "off",
        "paged KV-cache block quantization; off is the bf16/fp32 pool "
        "bit-reference (docs/KV_QUANT.md)",
        choices=("off", "int8"),
        aliases={"0": "off", "false": "off", "no": "off", "none": "off",
                 "on": "int8", "1": "int8", "true": "int8", "yes": "int8"}),
    _mk("DS_KV_HOST_TIER", "bool", False,
        "host-DRAM second tier for spilled KV blocks; off is the "
        "device-only cache bit-reference (docs/KV_TIERING.md)"),
    _mk("DS_KV_HOST_BUDGET_MB", "float", 256.0,
        "host-tier byte budget in MiB (bounded so leaks surface)"),
    _mk("DS_PAGED_DECODE_IMPL", "str", None,
        "paged-decode kernel override ('pallas'/'gather'); unset picks "
        "the platform default (pallas on TPU, gather elsewhere)"),
    _mk("DS_INT8_FUSED", "bool", False,
        "route int8 dense entries through the Pallas fused "
        "dequant-matmul kernel (TPU-only experiment; models/gpt.py)"),
    _mk("DS_LORA_SERVE", "bool", False,
        "multi-tenant LoRA adapter serving (paged adapter pool + "
        "heterogeneous-adapter batched decode); off is the base-only "
        "bit-reference (docs/ADAPTERS.md)"),
    _mk("DS_LORA_POOL_MB", "float", 16.0,
        "device adapter-pool byte budget in MiB (sizes the paged "
        "rank-block pool; docs/ADAPTERS.md)"),
    _mk("DS_LORA_MAX_RANK", "int", 16,
        "largest adapter rank the pool accepts; fixes the static "
        "per-slot adapter-table width ceil(max_rank/rank_block)"),
    _mk("DS_LORA_RANK_BLOCK", "int", 8,
        "rank granularity of one adapter-pool block (an adapter "
        "occupies ceil(rank/rank_block) blocks)"),
    _mk("DS_DECODE_HORIZON", "int", 1,
        "decode iterations fused into one compiled program per dispatch "
        "(the serving horizon N); 1 is the one-token-per-step "
        "bit-reference, capped at 32 (docs/MULTISTEP.md)"),
    _mk("DS_FAULTS", "str", "",
        "ambient chaos spec 'site:kind@step[*count][~param];...' "
        "(docs/ROBUSTNESS.md); empty injects nothing"),
    _mk("DS_FAULT_SEED", "int", 0,
        "seed for the ambient FaultInjector's backoff-jitter rng"),
    _mk("DS_COST_ACCOUNTING", "bool", False,
        "per-dispatch analytic cost accounting (FLOPs/HBM bytes/KV "
        "block-seconds per request and tenant) without full telemetry; "
        "DS_TELEMETRY=on implies it (docs/OBSERVABILITY.md)"),
    _mk("DS_FLIGHT_RECORDER", "bool", False,
        "bounded flight recorder: on DegradedError/watchdog/breaker "
        "trips write a CRC-stamped postmortem JSON artifact "
        "(tools/postmortem.py reads it; docs/OBSERVABILITY.md)"),
    _mk("DS_FLIGHT_DIR", "str", "",
        "directory for flight-recorder postmortem artifacts; empty "
        "means the platform tempdir under ds_flight/"),
])


# ceiling on the fused-decode horizon: the scan body is cheap to grow,
# but every distinct N is its own compiled program and the serving
# harvest buffers N tokens per slot — cap it where the host-amortization
# curve has long flattened (docs/MULTISTEP.md)
MAX_DECODE_HORIZON = 32


def resolve_decode_horizon(value=None) -> int:
    """Resolve the fused-decode horizon N: explicit ``value`` wins, then
    ``DS_DECODE_HORIZON``, then 1 (the one-token-per-dispatch
    bit-reference). Validates 1 <= N <= :data:`MAX_DECODE_HORIZON`."""
    n = resolve_flag("DS_DECODE_HORIZON", value)
    if not 1 <= int(n) <= MAX_DECODE_HORIZON:
        raise ValueError(
            f"DS_DECODE_HORIZON={n!r}: expected an integer in "
            f"[1, {MAX_DECODE_HORIZON}]")
    return int(n)


def flag_names() -> Tuple[str, ...]:
    """Every declared DS_* knob, sorted (env_report / docs use this)."""
    return tuple(sorted(FLAGS))


def _parse(flag: Flag, raw: str):
    v = raw.strip()
    if flag.kind != "str":
        v = v.lower()
    if v == "":
        return flag.default
    if flag.kind == "bool":
        if v in FALSE_WORDS:
            return False
        if v in TRUE_WORDS:
            return True
        # ValueError, not assert: validates user env input, survives -O
        raise ValueError(f"{flag.name}={raw!r}: expected 'on' or 'off'")
    if flag.kind == "int":
        try:
            return int(v)
        except ValueError:
            raise ValueError(f"{flag.name}={raw!r}: expected an integer")
    if flag.kind == "float":
        try:
            return float(v)
        except ValueError:
            raise ValueError(f"{flag.name}={raw!r}: expected a number")
    if flag.kind == "choice":
        v = flag.aliases.get(v, v)
        if v not in flag.choices:
            raise ValueError(f"{flag.name}={raw!r}: expected "
                             + " or ".join(f"'{c}'"
                                           for c in reversed(flag.choices)))
        return v
    return v  # kind == "str": verbatim (stripped)


def resolve_flag(name: str, override=None, env: Optional[Mapping] = None):
    """Resolve the declared knob ``name``: explicit ``override`` wins,
    else the environment (``env`` mapping, default ``os.environ``),
    else the declared default.

    Overrides go through the same normalization as env strings when
    they are strings; non-string overrides pass through the kind's
    coercion (``bool``/``int``/``float``; ``True``/``False`` map onto a
    choice flag's on/off aliases so ``resolve_kv_quant(True)`` keeps
    meaning int8). Unknown names raise ``KeyError`` — declare the flag
    in :data:`FLAGS` first (dslint DS013 enforces the same statically).
    """
    flag = FLAGS.get(name)
    if flag is None:
        raise KeyError(f"undeclared env flag {name!r} — add it to "
                       f"deepspeed_tpu.utils.env.FLAGS")
    if override is not None:
        if isinstance(override, str):
            return _parse(flag, override)
        if flag.kind == "bool":
            return bool(override)
        if flag.kind == "int":
            return int(override)
        if flag.kind == "float":
            return float(override)
        if flag.kind == "choice" and isinstance(override, bool):
            return _parse(flag, "on" if override else "off")
        return override
    env = os.environ if env is None else env
    return _parse(flag, env.get(name, ""))
