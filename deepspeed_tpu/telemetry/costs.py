"""Cost-accounting plane: analytic cost model, XLA program cost
registry, and per-dispatch attribution.

The reproduction's serving-side answer to the reference's
``deepspeed/profiling/`` flops profiler: the telemetry plane (metrics/
tracer and its spans) can say how *long* a request took, this module says
what it *cost* — FLOPs, HBM bytes, and KV block-seconds — per program,
per request, and per tenant. Three pieces:

- **analytic model** — integer FLOPs/bytes formulas derived from the
  one source of truth in ``models/gpt.py`` (``num_params``,
  ``kv_bytes_per_token``); the training-side flops profiler
  (``profiling/flops_profiler``) imports its per-token constants from
  here so the two sides can never disagree;
- :class:`ProgramCostRegistry` — walks the shared
  ``utils/jit_registry.py`` engine program catalog and records, per
  program id, XLA's own ``cost_analysis()``/``memory_analysis()``
  numbers when a lowered executable is available, falling back to the
  analytic formulas at a reference shape when XLA declines (so the
  registry is always populated, CPU included); with telemetry on it
  also keeps each program's PROVENANCE table, parsed from the compiled
  module's text (:func:`parse_provenance`): instruction name -> opcode,
  result shape and layout, ``named_scope`` path, source file:line — so
  a profile's ``copy.63`` is a lookup, not a hunt;
- :class:`CostAccountant` — exact integer per-dispatch charges rolled
  into global ``serving_flops_total``/``serving_hbm_bytes_total``/
  ``serving_kv_block_seconds`` counters AND per-request footprints,
  with tenant rollup keyed by ``adapter_id``. A batched dispatch is
  charged as array operations over its live slots into per-slot
  ``int64`` accumulators, and the globals take the sums of the *same*
  integers, so conservation (sum of footprints == global counters, per
  dispatch class) holds exactly by construction; a slot's accumulators
  fold into its request where the slot is vacated and at every view
  (:meth:`CostAccountant.flush`).

Everything here is host-side integer arithmetic (python ints and numpy
``int64``) — no jax calls on the charge path, no device sync, zero new
compiled programs (``CompileWatch(0)`` holds with the plane on).
"""

import json
import math
import re
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from deepspeed_tpu.utils.jit_registry import (DISPATCH_CLASSES,
                                              engine_programs)

__all__ = ["PEAK_FLOPS", "PEAK_HBM_BYTES_PER_S", "device_peak_flops",
           "device_peak_hbm_bytes_per_s", "parse_provenance",
           "pool_copy_bytes", "param_copy_bytes", "shape_dims",
           "matmul_params",
           "model_flops_per_token", "attn_flops", "infer_flops",
           "infer_hbm_bytes", "weight_bytes", "split_even",
           "new_footprint", "merge_footprints", "ProgramCostRegistry",
           "CostAccountant", "NoopCostAccountant", "NOOP_COSTS"]

# dense peak flops per chip (bf16 MXU throughput) by device_kind
# prefix — the roofline denominator for MFU estimates. Extend as new
# generations appear in jax's device_kind strings. (Moved here from
# profiling/flops_profiler so serving and training share one table.)
PEAK_FLOPS = {
    "TPU v2": 45e12,
    "TPU v3": 123e12,
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,
    "TPU v5": 459e12,
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,
    "TPU v6e": 918e12,
}


# HBM bytes/s per chip by device_kind prefix — the roofline's other
# denominator. Only what a source states: "TPU v5 lite" is Google
# Cloud's documentation, "TPU v5e": 819 GB/s per chip (the figure
# benchmark/harness/peaks.py carries). An unknown device stays None.
PEAK_HBM_BYTES_PER_S = {
    "TPU v5 lite": 819e9,
}


def _device_peak(table: Dict[str, float], device) -> Optional[float]:
    """``table``'s entry for ``device`` (default: first local device) by
    the longest matching ``device_kind`` prefix; None when unknown."""
    if device is None:
        import jax
        devices = jax.local_devices()
        if not devices:
            return None
        device = devices[0]
    kind = getattr(device, "device_kind", "") or ""
    best = None
    best_len = -1
    for prefix, peak in table.items():
        if kind.startswith(prefix) and len(prefix) > best_len:
            best, best_len = peak, len(prefix)
    return best


def device_peak_flops(device=None) -> Optional[float]:
    """Peak dense FLOP/s for ``device`` (default: first local device),
    longest-prefix matched against :data:`PEAK_FLOPS`; None when the
    platform is unknown (CPU, new TPU generations)."""
    return _device_peak(PEAK_FLOPS, device)


def device_peak_hbm_bytes_per_s(device=None) -> Optional[float]:
    """Peak HBM bytes/s for ``device`` from
    :data:`PEAK_HBM_BYTES_PER_S`; None when no source is on record —
    never a default."""
    return _device_peak(PEAK_HBM_BYTES_PER_S, device)


# --------------------------------------------------------------------------
# analytic model — integer formulas over models/gpt.py's param counts
# --------------------------------------------------------------------------

def matmul_params(cfg, include_head: bool = True) -> int:
    """Parameters that participate in a matmul per token — ``num_params``
    minus the wte lookup, with the logit projection counted when
    ``include_head`` (for tied embeddings the d*V head matmul is real
    compute even though the weight is shared with wte). The same N the
    training-side ``train_flops_per_token`` uses, so fwd = 2N and
    fwd+bwd = 6N agree."""
    from deepspeed_tpu.models.gpt import num_params
    n = num_params(cfg) - cfg.vocab_size * cfg.d_model
    if include_head and cfg.tie_embeddings:
        n += cfg.d_model * cfg.vocab_size
    return int(n)


def model_flops_per_token(cfg, include_head: bool = True) -> int:
    """Forward matmul FLOPs per token, attention excluded: 2 FLOPs per
    matmul parameter. One third of the training-side ``6N``."""
    return 2 * matmul_params(cfg, include_head)


def _ctx_sum(n, s):
    """Keys attended by ``n`` consecutive tokens from position ``s``:
    the sum of ``s + i + 1`` over them. Ints, or integer arrays of one
    entry a slot (the accountant's batched charge)."""
    return n * s + (n * (n + 1)) // 2


def _flops(cfg, n, ctx, flops_tok: int):
    """Forward FLOPs of ``n`` tokens that attend ``ctx`` keys in all
    (:func:`_ctx_sum`): linear in both, so it holds for one charge and
    for the sums of many."""
    return n * flops_tok + 4 * cfg.n_layers * cfg.d_model * ctx


def _kv_bytes(kv_bytes_tok: int, n, ctx):
    """KV-cache bytes of the same tokens: each streams the cache up to
    its position (``ctx`` rows read) and writes its own row."""
    return kv_bytes_tok * (ctx + n)


def attn_flops(cfg, n_tokens: int, start_pos: int) -> int:
    """Forward attention-score FLOPs for ``n_tokens`` consecutive
    tokens starting at absolute position ``start_pos``: the token at
    position p attends over p+1 keys, QK^T and PV are each
    ``2 * d_model`` FLOPs per (query, key) pair per layer — the
    inference-shape refinement of the training formula's
    ``12 * L * d * s`` (which is 3x fwd at full context)."""
    return _flops(cfg, 0, _ctx_sum(int(n_tokens), int(start_pos)), 0)


def infer_flops(cfg, n_tokens: int, start_pos: int,
                include_head: bool = True) -> int:
    """Total forward FLOPs to process ``n_tokens`` new tokens of one
    sequence whose cache already holds ``start_pos`` tokens — linear
    (weight matmul) plus causal attention. Exact integer."""
    n = int(n_tokens)
    return _flops(cfg, n, _ctx_sum(n, int(start_pos)),
                  model_flops_per_token(cfg, include_head))


def weight_bytes(cfg, param_itemsize: int = 2) -> int:
    """Bytes of model weights one dispatch streams from HBM (every
    program reads the full parameter set once per dispatch)."""
    from deepspeed_tpu.models.gpt import num_params
    return int(num_params(cfg)) * int(param_itemsize)


def infer_hbm_bytes(cfg, n_tokens: int, start_pos: int,
                    kv_bytes_tok: int, param_itemsize: int = 2,
                    include_weights: bool = True) -> int:
    """Analytic HBM traffic for one sequence's share of a dispatch:
    KV-cache reads (each new token streams the cache up to its
    position) plus KV writes for the new tokens, plus optionally one
    full weight read (callers split the weight read across the live
    slots of a batched dispatch — see :func:`split_even`)."""
    n = int(n_tokens)
    kv = _kv_bytes(int(kv_bytes_tok), n, _ctx_sum(n, int(start_pos)))
    return kv + (weight_bytes(cfg, param_itemsize) if include_weights
                 else 0)


def split_even(total: int, n: int) -> List[int]:
    """Split integer ``total`` into ``n`` integer shares that sum to
    ``total`` exactly — ``total // n`` each, remainder distributed one
    unit at a time to the first ``total % n`` shares. The primitive
    that keeps per-request attribution conservative to the FLOP."""
    if n <= 0:
        return []
    q, r = divmod(int(total), n)
    return [q + 1 if i < r else q for i in range(n)]


# --------------------------------------------------------------------------
# per-request footprint
# --------------------------------------------------------------------------

def new_footprint() -> Dict:
    """Empty per-request cost footprint: per dispatch class a
    (dispatches, flops, hbm_bytes) triple, plus KV block-seconds
    integrated at horizon boundaries. Plain data — it rides request
    snapshots across router drains unchanged."""
    fp = {cls: {"dispatches": 0, "flops": 0, "hbm_bytes": 0}
          for cls in DISPATCH_CLASSES}
    fp["block_seconds"] = 0
    return fp


def merge_footprints(fps: Sequence[Dict]) -> Dict:
    """Sum footprints (tenant/fleet rollup)."""
    out = new_footprint()
    for fp in fps:
        if not fp:
            continue
        for cls in DISPATCH_CLASSES:
            for k in ("dispatches", "flops", "hbm_bytes"):
                out[cls][k] += fp.get(cls, {}).get(k, 0)
        out["block_seconds"] += fp.get("block_seconds", 0)
    return out


def footprint_totals(fp: Dict) -> Dict[str, int]:
    """Collapse a footprint to its cross-class totals."""
    return {
        "flops": sum(fp[c]["flops"] for c in DISPATCH_CLASSES),
        "hbm_bytes": sum(fp[c]["hbm_bytes"] for c in DISPATCH_CLASSES),
        "dispatches": sum(fp[c]["dispatches"] for c in DISPATCH_CLASSES),
        "block_seconds": fp["block_seconds"],
    }


# --------------------------------------------------------------------------
# program cost registry
# --------------------------------------------------------------------------
# provenance — which scope and source line a compiled instruction comes from
# --------------------------------------------------------------------------

_INSTR = re.compile(r"^\s*(ROOT\s+)?%?([\w.\-]+)\s+=\s+(.*)$")
_COMPUTATION = re.compile(r"^\s*(ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_TABLE_ROW = re.compile(r"^(\d+)\s+(.*)$")
_CALLED = re.compile(r"\b(calls|to_apply)=%?([\w.\-]+)")
# never device operations of their own; left out to keep the table small
_SKIPPED_OPCODES = ("parameter", "constant", "get-tuple-element", "tuple")


def _balanced(text: str, start: int) -> int:
    """Index just past the bracket group that opens at ``text[start]``."""
    depth = 0
    for i in range(start, len(text)):
        c = text[i]
        if c in "([{":
            depth += 1
        elif c in ")]}":
            depth -= 1
            if depth == 0:
                return i + 1
    return len(text)


def _attr(text: str, key: str) -> Optional[str]:
    m = re.search(key + r'=("([^"]*)"|[^\s}]+)', text)
    if m is None:
        return None
    return m.group(2) if m.group(2) is not None else m.group(1)


def _scope_of(op_name: str) -> Tuple[str, str]:
    """``jit(f)/while/body/kv_write/scatter`` -> (``while/body/kv_write``,
    ``scatter``): the named-scope path without the program's own
    ``jit(...)`` and the primitive that ends it."""
    parts = op_name.split("/")
    if parts and parts[0].startswith(("jit(", "pjit(")):
        parts = parts[1:]
    return "/".join(parts[:-1]), parts[-1] if parts else ""


def _nearest_named(name: str, users: Dict[str, List[str]],
                   operand: Dict[str, List[str]], named,
                   depth: int = 4) -> Optional[str]:
    """The nearest instruction of a computation that has metadata:
    breadth first over users, then the first operand's producer (through
    tuples and get-tuple-elements), at most ``depth`` steps away."""
    seen, frontier = {name}, [name]
    for _ in range(depth):
        nxt = []
        for n in frontier:
            for m in users.get(n, []) + operand.get(n, []):
                if m in named:
                    return m
                if m not in seen and m in operand:
                    seen.add(m)
                    nxt.append(m)
        frontier = nxt
    return None


def parse_provenance(hlo_text: str) -> Dict[str, Dict]:
    """instruction name -> ``{"opcode", "shape", "scope", "op",
    "source", "param"?, "program"?, "inferred"?}`` from a compiled module's
    text (``compiled.as_text()``).

    ``shape`` is the result shape with its layout as printed; ``scope``
    the ``jax.named_scope`` path out of ``metadata={op_name=...}``
    (transform wrappers such as ``jvp(...)``, ``transpose(...)``,
    ``checkpoint`` and ``rematted_computation`` are part of it);
    ``source`` is ``file:line`` of the innermost frame, from inline
    ``source_file``/``source_line`` or from the module's stack-frame
    tables. A fusion takes the metadata of its fused computation's root
    when it has none of its own. ``param``, on an instruction whose first
    operand is a parameter of the entry computation, is that argument's
    path as jax names it (``params['wte']['embedding']``). An instruction
    the compiler inserted
    itself (a layout-changing ``copy``) may carry no metadata: it takes
    the scope of the nearest instruction that has some (users first,
    then its operand's producer) and is marked ``"inferred": true``.
    Instructions
    inside fused computations and reducers are left out: they are not
    operations of their own on the device."""
    files: Dict[int, str] = {}
    locations: Dict[int, Tuple[int, int]] = {}     # id -> (file id, line)
    frames: Dict[int, int] = {}                    # id -> location id
    table = None
    computations: Dict[str, List[Dict]] = {}
    current = None
    called = set()
    for line in hlo_text.splitlines():
        stripped = line.strip()
        if current is None:
            if stripped in ("FileNames", "FunctionNames", "FileLocations",
                            "StackFrames"):
                table = stripped
                continue
            row = _TABLE_ROW.match(stripped) if table else None
            if row is not None:
                key, rest = int(row.group(1)), row.group(2)
                if table == "FileNames":
                    files[key] = rest.strip('"')
                elif table == "FileLocations":
                    locations[key] = (int(_attr(rest, "file_name_id") or 0),
                                      int(_attr(rest, "line") or 0))
                elif table == "StackFrames":
                    frames[key] = int(_attr(rest, "file_location_id") or 0)
                continue
            m = _COMPUTATION.match(line)
            if m is not None and "=" not in line.split("(", 1)[0]:
                table = None
                current = m.group(2)
                computations[current] = []
            continue
        if stripped == "}":
            current = None
            continue
        m = _INSTR.match(line)
        if m is None:
            continue
        name, rest = m.group(2), m.group(3)
        end = _balanced(rest, 0) if rest.startswith("(") \
            else (rest.find(" ") if " " in rest else len(rest))
        shape, rest = rest[:end], rest[end:].lstrip()
        paren = rest.find("(")
        if paren < 0:
            continue
        opcode = rest[:paren]
        close = _balanced(rest, paren)
        operands = re.findall(r"%([\w.\-]+)", rest[paren:close])
        attrs = rest[close:]
        meta = re.search(r"(?:^|[\s,])metadata=\{([^}]*)\}", attrs)
        meta = meta.group(1) if meta else ""
        for _, target in _CALLED.findall(attrs):
            called.add(target)
        fused = re.search(r"\bcalls=%?([\w.\-]+)", attrs)
        computations[current].append({
            "name": name, "root": bool(m.group(1)), "opcode": opcode,
            "shape": shape, "operands": operands, "meta": meta,
            "calls": fused.group(1) if fused and opcode == "fusion"
            else None})

    def source_of(meta: str) -> str:
        f, ln = _attr(meta, "source_file"), _attr(meta, "source_line")
        if f:
            return f"{f}:{ln}" if ln else f
        frame = _attr(meta, "stack_frame_id")
        if frame is None:
            return ""
        fid, ln = locations.get(frames.get(int(frame), 0), (0, 0))
        return f"{files[fid]}:{ln}" if fid in files else ""

    out: Dict[str, Dict] = {}
    for comp, instrs in computations.items():
        if comp in called:
            continue
        users: Dict[str, List[str]] = {}
        for ins in instrs:
            for o in ins["operands"]:
                users.setdefault(o, []).append(ins["name"])
        local: Dict[str, Dict] = {}
        for ins in instrs:
            meta = ins["meta"]
            if ins["calls"] in computations:
                root = [r["meta"] for r in computations[ins["calls"]]
                        if r["root"] and _attr(r["meta"], "op_name")]
                meta = root[0] if root else meta
            scope, op = _scope_of(_attr(meta, "op_name") or "")
            local[ins["name"]] = {"opcode": ins["opcode"],
                                  "shape": ins["shape"], "scope": scope,
                                  "op": op, "source": source_of(meta)}
        operand = {ins["name"]: ins["operands"][:1] for ins in instrs}
        args = {ins["name"]: _attr(ins["meta"], "op_name") for ins in instrs
                if ins["opcode"] == "parameter"}
        for name, first in operand.items():
            if first and args.get(first[0]):
                local[name]["param"] = args[first[0]].replace("\\", "")
        named = {n for n, e in local.items() if e["op"] or e["scope"]}
        for ins in instrs:
            if ins["name"] in named:
                continue
            donor = _nearest_named(ins["name"], users, operand, named)
            if donor is not None:
                local[ins["name"]].update(scope=local[donor]["scope"],
                                          source=local[donor]["source"],
                                          inferred=True)
        out.update((n, e) for n, e in local.items()
                   if e["opcode"] not in _SKIPPED_OPCODES)
    return out


def shape_dims(shape: str) -> Tuple[str, Tuple[int, ...]]:
    """``bf16[48,1089,16,1600]{3,2,1,0:T(8,128)(2,1)}`` -> ``("bf16",
    (48, 1089, 16, 1600))``; ``("", ())`` for a tuple or a token."""
    m = re.match(r"(\w+)\[([\d,]*)\]", shape)
    if m is None:
        return "", ()
    return m.group(1), tuple(int(d) for d in m.group(2).split(",") if d)


def _shape_bytes(shape: str) -> int:
    dtype, dims = shape_dims(shape)
    bits = re.search(r"\d+", dtype)                 # pred has none: a byte
    return math.prod(dims) * (int(bits.group()) // 8 if bits else 1)


def pool_copy_bytes(instructions: Dict[str, Dict],
                    pool_blocks: Sequence[int]) -> int:
    """Bytes of the ``copy`` instructions of a compiled serving program
    whose result has the paged KV pool's block count as a dimension
    (``pool_blocks``: N of one layer and L*N of the stacked layers):
    each is a re-laying-out of a pool-shaped value that the program
    runs on EVERY dispatch. 0 is the healthy value: the pool then has
    one layout from the entry parameter through the layer loop to the
    kernel (inference/paged_cache.py). ``instructions`` is
    :func:`parse_provenance`'s table."""
    blocks = {int(n) for n in pool_blocks}
    return sum(_shape_bytes(ins["shape"]) for ins in instructions.values()
               if ins["opcode"] == "copy"
               and blocks.intersection(shape_dims(ins["shape"])[1]))


def param_copy_bytes(instructions: Dict[str, Dict]) -> int:
    """Bytes of the ``copy`` instructions of a compiled serving program
    that re-lay a weight: the operand is an entry parameter under the
    program's argument ``params`` (``param``), or the copy carries such
    a parameter's name as its own (the compiler hands it on when the
    parameter reaches the copy through a prefetch: ``op``). Like a pool's
    copy it runs on EVERY dispatch. 0 is the healthy value: every weight
    is then read in the layout it is stored in. What read 164 MB a
    program until PR 39 was GPT-2 XL's two embedding tables, stored
    column-major because 1,600 lanes are not whole tiles of 128 and copied
    for the row gather (inference/engine.py ``whole_lane_tables``).
    ``instructions`` is :func:`parse_provenance`'s table."""
    return sum(_shape_bytes(ins["shape"]) for ins in instructions.values()
               if ins["opcode"] == "copy" and (
                   ins.get("param") or ins.get("op", "")).startswith("params["))


def scatter_windows(hlo_text: str, scope: str) -> List[int]:
    """Update windows of every ``scatter`` of a compiled module
    (``compiled.as_text()``) whose ``op_name`` holds ``scope``, fused ones
    included (so the text and not :func:`parse_provenance`'s table): the
    dimensions of its updates that are not ``update_window_dims``,
    multiplied. The device moves a scatter's windows one after the other
    and pays by the window, not by the byte: a prefill chunk's write read
    64 one-row windows here until it moved its 5 whole blocks
    (inference/paged_cache.py ``write_chunk``)."""
    shapes: Dict[str, str] = {}
    found = []
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m is None or m.group(3).startswith("("):
            continue
        shape, _, rest = m.group(3).partition(" ")
        shapes[m.group(2)] = shape
        if rest.startswith("scatter(") and scope in (
                _attr(rest, "op_name") or ""):
            found.append(rest)
    counts = []
    for rest in found:
        operands = re.findall(r"%([\w.\-]+)", rest[:_balanced(rest, 7)])
        window = _attr(rest, "update_window_dims").strip("{}")
        window = {int(d) for d in window.split(",") if d}
        dims = shape_dims(shapes[operands[-1]])[1]
        counts.append(math.prod(d for i, d in enumerate(dims)
                                if i not in window))
    return counts


def provenance_module_name(hlo_text: str) -> str:
    """``jit_serve_decode_slots`` out of ``HloModule jit_serve_...``."""
    m = re.match(r"\s*HloModule\s+([\w.\-]+)", hlo_text)
    return m.group(1) if m else ""


# --------------------------------------------------------------------------

class ProgramCostRegistry:
    """Static per-program cost card for every serving executable in the
    shared ``utils/jit_registry.py`` catalog.

    :meth:`populate` walks ``engine_programs()`` against a live engine:
    when the caller supplies compiled executables (or asks for an AOT
    probe) each entry records XLA's own ``cost_analysis()`` FLOPs /
    bytes-accessed and ``memory_analysis()`` peak/argument/output
    bytes; when XLA declines — the CPU backend reports neither — the
    entry falls back to the analytic formulas above at a reference
    shape, so the registry is populated either way. Entries are plain
    dicts; ``to_json()`` is the flight-recorder section."""

    def __init__(self):
        self.entries: Dict[str, Dict] = {}
        # program id -> {"module", "instructions"}: filled by
        # add_provenance on a program's first dispatch under telemetry
        self.provenance: Dict[str, Dict] = {}
        self.metrics = None      # the metrics registry of export_gauges

    # .. population .....................................................

    def populate(self, engine, cache=None, compiled=None) -> None:
        """Fill one entry per program id whose callable ``engine`` has.

        ``compiled`` optionally maps program id -> an object exposing
        ``cost_analysis()``/``memory_analysis()`` (an AOT
        ``jfn.lower(...).compile()`` result); entries without one get
        the analytic fallback. ``cache`` (a PagedKVCache) refines the
        KV byte constants; without it the fp32/bf16 defaults from the
        config dtype are used."""
        from deepspeed_tpu.models.gpt import kv_bytes_per_token
        cfg = engine.cfg
        try:
            import numpy as _np
            param_itemsize = int(_np.dtype(engine.dtype).itemsize)
        except Exception:
            param_itemsize = 2
        if cache is not None:
            kv_tok = int(cache.bytes_per_token)
        else:
            kv_tok = int(kv_bytes_per_token(cfg, engine.dtype))
        block = int(getattr(cache, "block_size", 16) or 16)
        block_bytes = kv_tok * block
        ref_ctx = max(1, int(cfg.max_seq_len) // 2)

        for pid, attr, cls in engine_programs():
            if getattr(engine, attr, None) is None:
                continue
            entry = {"program": pid, "attr": attr,
                     "dispatch_class": cls, "source": "analytic"}
            entry.update(self._analytic(cfg, cls, kv_tok, block_bytes,
                                        param_itemsize, ref_ctx))
            exe = (compiled or {}).get(pid)
            if exe is not None:
                xla = probe_compiled(exe)
                if xla:
                    entry["source"] = "xla"
                    entry.update(xla)
            self.entries[pid] = entry

    @staticmethod
    def _analytic(cfg, cls: str, kv_tok: int, block_bytes: int,
                  param_itemsize: int, ref_ctx: int) -> Dict:
        """Reference-shape cost card: one token (prefill/decode/verify)
        at half the model's max context, one block (cow/spill)."""
        if cls in ("prefill", "decode", "verify"):
            return {
                "flops": infer_flops(cfg, 1, ref_ctx),
                "bytes_accessed": infer_hbm_bytes(
                    cfg, 1, ref_ctx, kv_tok, param_itemsize),
                "flops_per_token": model_flops_per_token(cfg),
                "attn_flops_per_ctx_token": 4 * cfg.n_layers * cfg.d_model,
                "kv_bytes_per_token": kv_tok,
                "weight_bytes": weight_bytes(cfg, param_itemsize),
                "ref_context": ref_ctx,
            }
        # cow copies a block (read + write); spill moves one block one
        # way across the host interconnect
        moved = 2 * block_bytes if cls == "cow" else block_bytes
        return {"flops": 0, "bytes_accessed": moved,
                "block_bytes": block_bytes}

    # .. views ..........................................................

    def __len__(self) -> int:
        return len(self.entries)

    def get(self, pid: str) -> Optional[Dict]:
        return self.entries.get(pid)

    def export_gauges(self, registry) -> None:
        """Mirror each entry's headline numbers as gauges on a metrics
        registry (``program_flops_<pid>`` / ``program_hbm_bytes_<pid>``
        — declared as wildcard families in the telemetry schema)."""
        self.metrics = registry
        for pid, e in sorted(self.entries.items()):
            registry.gauge(f"program_flops_{pid}").set(e.get("flops", 0))
            registry.gauge(f"program_hbm_bytes_{pid}").set(
                e.get("bytes_accessed", 0))

    def add_provenance(self, pid: str, hlo_text: str,
                       pool_blocks: Sequence[int] = (),
                       paged_grid: Sequence[int] = ()) -> int:
        """Keep program ``pid``'s provenance table, parsed from the text
        of the executable that is actually loaded (a program restored
        from jax's persistent cache carries the metadata it was first
        compiled with: the cache does not key on it). The program's
        entry gains ``param_copy_bytes`` (:func:`param_copy_bytes`; the
        gauge ``program_param_copy_bytes_<pid>``), and with the paged
        pool's block counts also
        ``pool_copy_bytes`` (:func:`pool_copy_bytes`; also the gauge
        ``program_pool_copy_bytes_<pid>`` once :meth:`export_gauges`
        has been given a registry), which is returned. ``paged_grid``
        (table entries one grid step attends, steps a call at most),
        from the caller of a program that attends through the
        ``paged_decode`` kernel, adds how that kernel's grid is cut:
        ``paged_blocks_per_step`` and ``paged_grid_steps``, gauges
        ``program_paged_blocks_per_step_<pid>`` /
        ``program_paged_grid_steps_<pid>``."""
        instructions = parse_provenance(hlo_text)
        self.provenance[pid] = {
            "module": provenance_module_name(hlo_text),
            "instructions": instructions}
        copied = pool_copy_bytes(instructions, pool_blocks)
        entry = self.entries.setdefault(pid, {"program": pid})
        entry["param_copy_bytes"] = param_copy_bytes(instructions)
        if self.metrics is not None:
            self.metrics.gauge(f"program_param_copy_bytes_{pid}").set(
                entry["param_copy_bytes"])
        if pool_blocks:
            entry["pool_copy_bytes"] = copied
            if self.metrics is not None:
                self.metrics.gauge(
                    f"program_pool_copy_bytes_{pid}").set(copied)
        if paged_grid:
            per_step, steps = paged_grid
            entry.update(paged_blocks_per_step=per_step,
                         paged_grid_steps=steps)
            if self.metrics is not None:
                self.metrics.gauge(
                    f"program_paged_blocks_per_step_{pid}").set(per_step)
                self.metrics.gauge(
                    f"program_paged_grid_steps_{pid}").set(steps)
        return copied

    def roofline(self, pid: str, device=None) -> Optional[Dict]:
        """The least seconds the chip could take for program ``pid``'s
        FLOPs and bytes, and which of the two binds; None when either
        peak of the device is unknown."""
        e = self.entries.get(pid)
        peak_f = device_peak_flops(device)
        peak_b = device_peak_hbm_bytes_per_s(device)
        if e is None or not peak_f or not peak_b:
            return None
        tc = e.get("flops", 0) / peak_f
        tm = e.get("bytes_accessed", 0) / peak_b
        return {"peak_flops": peak_f, "peak_hbm_bytes_per_s": peak_b,
                "min_seconds": max(tc, tm),
                "bound": "compute" if tc >= tm else "memory"}

    def to_json(self) -> Dict:
        programs = {}
        for pid, e in sorted(self.entries.items()):
            programs[pid] = dict(e)
            roof = self.roofline(pid)
            if roof is not None:
                programs[pid]["roofline"] = roof
        out = {"programs": programs}
        if self.provenance:
            out["provenance"] = {pid: dict(t) for pid, t
                                 in sorted(self.provenance.items())}
        return out

    def dumps(self) -> str:
        return json.dumps(self.to_json(), sort_keys=True)


def probe_compiled(compiled) -> Dict:
    """Extract XLA's cost/memory analysis from a compiled executable,
    tolerating every historical shape of the API (dict, list-of-dict,
    absent, raising). Returns {} when XLA declines — the caller keeps
    its analytic numbers."""
    out: Dict = {}
    try:
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else {}
        if cost:
            if "flops" in cost:
                out["flops"] = int(cost["flops"])
            if "bytes accessed" in cost:
                out["bytes_accessed"] = int(cost["bytes accessed"])
    except (AttributeError, TypeError, ValueError, KeyError,
            IndexError, RuntimeError):
        pass        # XLA declined; the caller keeps analytic numbers
    try:
        mem = compiled.memory_analysis()
        for attr, key in (("temp_size_in_bytes", "peak_bytes"),
                          ("argument_size_in_bytes", "argument_bytes"),
                          ("output_size_in_bytes", "output_bytes")):
            v = getattr(mem, attr, None)
            if v is not None:
                out[key] = int(v)
    except (AttributeError, TypeError, ValueError, RuntimeError):
        pass        # memory analysis is backend-optional
    return out


# --------------------------------------------------------------------------
# per-dispatch accountant
# --------------------------------------------------------------------------

# the dispatch classes that charge_batched charges: a slot's share of
# them accrues in the per-slot accumulators; the others (prefill, cow,
# spill) are one call a dispatch and land on the request at once
BATCHED_CLASSES: Tuple[str, ...] = ("decode", "verify")
# what a slot accrues of a batched class: tokens processed, keys they
# attended (_ctx_sum), dispatches, and its shares of the weight reads.
# FLOPs and bytes are linear in them (_flops, _kv_bytes), so a slot's
# footprint is computed where it is folded, not once a dispatch
_ACCRUED = ("tokens", "ctx", "dispatches", "weight_bytes")


class CostAccountant:
    """Exact integer attribution of dispatch costs.

    One instance per :class:`ServingEngine`. Every charge computes the
    cost per live slot (each slot's own token count and cache context)
    and adds the same integers to the global per-class totals — so the
    conservation invariant

        sum(per-request footprints) + system footprint == globals

    holds exactly per dispatch class, with no float rounding and no
    remainder leakage (:func:`split_even`'s rule handles shared costs
    such as the per-dispatch weight read). Costs with no owning request
    (spill of refcount-zero blocks) land in ``self.system``. When a
    metrics registry is supplied the cross-class totals also feed the
    ``serving_flops_total``/``serving_hbm_bytes_total``/
    ``serving_kv_block_seconds`` counters.

    A batched dispatch and the block-seconds of a step are charged as a
    few array operations, whatever the number of slots, into per-slot
    ``int64`` accumulators beside ``cache.lengths`` (``slots`` is the
    engine's own list of seated requests, one entry a slot). FLOPs and
    bytes are linear in what a slot accrues (its tokens, the keys they
    attended, its dispatches and weight-read shares), so the totals
    take the dispatch's sums at once and a slot's own FLOPs and bytes
    are computed where its accumulators are folded into its request's
    ``cost`` and its tenant's footprint: where the slot is vacated
    (:meth:`fold`) and by :meth:`flush`, which every view calls first
    (:meth:`snapshot`, :attr:`tenants`; the engine's
    ``pending_snapshot`` and flight rows): ``req.cost`` of a request
    STILL SEATED is current after a view and not between two. The
    totals and the registry's counters are current after every charge.
    An accumulator holds one request's stay in one slot: at most
    ``max_seq_len`` tokens, ``max_seq_len ** 2`` keys, and a dispatch a
    token of at most the whole weight read each (1e6 dispatches of
    2e11 bytes are 2e17), all far under 2**63; the totals and the
    footprints are python ints and cannot overflow.

    ``self_s`` is the host seconds spent inside the charges and the
    folds since it was last taken, on ``clock``: the ``accountant`` part
    of a dispatch's ``self_us`` (inference/serving.py ``_account_gap``)."""

    enabled = True

    def __init__(self, cfg, kv_bytes_tok: int, block_bytes: int,
                 param_itemsize: int = 2, registry=None,
                 slots: Optional[List] = None,
                 clock: Callable[[], float] = time.perf_counter):
        self.cfg = cfg
        self.kv_bytes_tok = int(kv_bytes_tok)
        self.block_bytes = int(block_bytes)
        self.param_itemsize = int(param_itemsize)
        self._weight_bytes = weight_bytes(cfg, param_itemsize)
        self._flops_tok = model_flops_per_token(cfg)
        self.totals = {cls: {"dispatches": 0, "flops": 0, "hbm_bytes": 0}
                       for cls in DISPATCH_CLASSES}
        self.block_seconds_total = 0
        self.system = new_footprint()
        self._tenants: Dict[str, Dict] = {}
        self._slots = slots if slots is not None else []
        n = len(self._slots)
        self._acc = {cls: {k: np.zeros((n,), np.int64) for k in _ACCRUED}
                     for cls in BATCHED_CLASSES}
        self._acc_bs = np.zeros((n,), np.int64)
        self._clock = clock
        self.self_s = 0.0
        self._c_flops = self._c_bytes = self._c_blocks = None
        if registry is not None:
            self._c_flops = registry.counter(
                "serving_flops_total",
                "analytic model FLOPs dispatched, all classes")
            self._c_bytes = registry.counter(
                "serving_hbm_bytes_total",
                "analytic HBM bytes moved, all classes")
            self._c_blocks = registry.counter(
                "serving_kv_block_seconds",
                "KV block residency integrated at horizon boundaries "
                "(scheduler-clock units)")

    # .. internals ......................................................

    def _tenant(self, req) -> Dict:
        key = getattr(req, "adapter_id", None) or "base"
        t = self._tenants.get(key)
        if t is None:
            t = self._tenants[key] = new_footprint()
        return t

    def _add(self, cls: str, req, flops: int, nbytes: int,
             dispatches: int = 0) -> None:
        # system charges roll up under a reserved tenant
        tenant = self._tenant(req) if req is not None \
            else self._tenants.setdefault("system", new_footprint())
        for fp in ((req.cost if req is not None else self.system),
                   self.totals, tenant):
            slot = fp[cls]
            slot["flops"] += flops
            slot["hbm_bytes"] += nbytes
            slot["dispatches"] += dispatches
        if self._c_flops is not None:
            self._c_flops.inc(flops)
            self._c_bytes.inc(nbytes)

    # .. charge API (serving hot loop — host integers only) .............

    def charge_prefill(self, req, n_tokens: int, start_pos: int) -> None:
        """One prefill-chunk dispatch: single slot owns the whole cost,
        weight read included."""
        t0 = self._clock()
        n = int(n_tokens)
        ctx = _ctx_sum(n, int(start_pos))
        self._add("prefill", req, _flops(self.cfg, n, ctx, self._flops_tok),
                  _kv_bytes(self.kv_bytes_tok, n, ctx) + self._weight_bytes,
                  dispatches=1)
        self.self_s += self._clock() - t0

    def charge_batched(self, cls: str, slots, n_tokens, start_pos) -> None:
        """One batched dispatch (decode/horizon/verify) over the live
        ``slots`` (an index array): slot ``slots[j]`` processed
        ``n_tokens[j]`` tokens (or the one int, all of them) over the
        ``start_pos[j]`` its cache held (an integer array). Each slot
        is charged its own KV/attention cost; the single weight read
        is split exactly across the live slots as :func:`split_even`
        does, the first ``weights % live`` of them a byte more."""
        k = len(slots)
        if not k:
            return
        t0 = self._clock()
        acc = self._acc[cls]
        ctx = _ctx_sum(n_tokens, start_pos)
        acc["ctx"][slots] += ctx
        acc["tokens"][slots] += n_tokens
        acc["dispatches"][slots] += 1
        base, rem = divmod(self._weight_bytes, k)
        acc["weight_bytes"][slots] += base
        if rem:
            acc["weight_bytes"][slots[:rem]] += 1
        # the dispatch's sums, python ints from here on
        n = int(n_tokens.sum()) if isinstance(n_tokens, np.ndarray) \
            else int(n_tokens) * k
        ctx = int(ctx.sum())
        flops = _flops(self.cfg, n, ctx, self._flops_tok)
        nbytes = _kv_bytes(self.kv_bytes_tok, n, ctx) + self._weight_bytes
        tot = self.totals[cls]
        tot["flops"] += flops
        tot["hbm_bytes"] += nbytes
        tot["dispatches"] += k
        if self._c_flops is not None:
            self._c_flops.inc(flops)
            self._c_bytes.inc(nbytes)
        self.self_s += self._clock() - t0

    def charge_cow(self, req, n_blocks: int) -> None:
        """Copy-on-write block copies triggered by ``req``: read+write
        per block, no FLOPs."""
        if n_blocks <= 0:
            return
        self._add("cow", req, 0, 2 * self.block_bytes * int(n_blocks),
                  dispatches=int(n_blocks))

    def charge_spill(self, n_blocks: int, req=None,
                     restore: bool = False) -> None:
        """Host-tier block transfers (spill or restore): one-way block
        bytes each. Refcount-zero spills have no owner and land in the
        system footprint."""
        if n_blocks <= 0:
            return
        self._add("spill", req, 0, self.block_bytes * int(n_blocks),
                  dispatches=int(n_blocks))

    def charge_block_seconds(self, held, lengths, block_size: int,
                             ticks: int) -> None:
        """KV residency integrated at a horizon boundary, one call a
        step: every slot of the mask ``held`` is billed the blocks its
        entry of ``lengths`` (the cache's, an array over all slots)
        takes, for ``ticks`` scheduler-clock units."""
        t0 = self._clock()
        bs = (lengths + (block_size - 1)) // block_size * held
        if ticks != 1:
            bs *= int(ticks)
        total = int(bs.sum())
        if total > 0:
            self._acc_bs += bs
            self.block_seconds_total += total
            if self._c_blocks is not None:
                self._c_blocks.inc(total)
        self.self_s += self._clock() - t0

    # .. folding ........................................................

    def fold(self, slot: int, req) -> None:
        """Move what ``slot`` accrued onto ``req`` (its footprint and
        its tenant's) and zero it: where the slot is vacated, and for
        every seated slot by :meth:`flush`."""
        t0 = self._clock()
        for cls, acc in self._acc.items():
            d = int(acc["dispatches"][slot])
            if d:
                n, ctx = int(acc["tokens"][slot]), int(acc["ctx"][slot])
                flops = _flops(self.cfg, n, ctx, self._flops_tok)
                nbytes = _kv_bytes(self.kv_bytes_tok, n, ctx) \
                    + int(acc["weight_bytes"][slot])
                for fp in (req.cost[cls], self._tenant(req)[cls]):
                    fp["flops"] += flops
                    fp["hbm_bytes"] += nbytes
                    fp["dispatches"] += d
                for k in _ACCRUED:
                    acc[k][slot] = 0
        bs = int(self._acc_bs[slot])
        if bs:
            req.cost["block_seconds"] += bs
            self._tenant(req)["block_seconds"] += bs
            self._acc_bs[slot] = 0
        self.self_s += self._clock() - t0

    def flush(self) -> None:
        """Fold every seated slot's accumulators onto its request, so
        that ``req.cost`` and the tenants read what has been charged:
        every view calls it first."""
        for slot, req in enumerate(self._slots):
            if req is not None:
                self.fold(slot, req)

    # .. views ..........................................................

    @property
    def tenants(self) -> Dict[str, Dict]:
        """Footprint per tenant (``adapter_id``, ``"base"`` without
        one, ``"system"`` for unowned charges), current: a view."""
        self.flush()
        return self._tenants

    def snapshot(self) -> Dict:
        """Plain-data dump for flight recorder / bench rows."""
        tenants = self.tenants
        return {
            "totals": {cls: dict(v) for cls, v in self.totals.items()},
            "flops_total": sum(v["flops"] for v in self.totals.values()),
            "hbm_bytes_total": sum(v["hbm_bytes"]
                                   for v in self.totals.values()),
            "block_seconds_total": self.block_seconds_total,
            "system": {cls: dict(self.system[cls])
                       for cls in DISPATCH_CLASSES}
            | {"block_seconds": self.system["block_seconds"]},
            "tenants": {k: merge_footprints([v])
                        for k, v in sorted(tenants.items())},
        }


class NoopCostAccountant:
    """Off-mode twin: every charge is a constant-time no-op, so the
    accounting-off hot loop is bit-identical to pre-plane behavior."""

    enabled = False
    totals: Dict = {}
    tenants: Dict = {}
    block_seconds_total = 0

    def charge_prefill(self, req, n_tokens, start_pos):
        pass

    def charge_batched(self, cls, slots, n_tokens, start_pos):
        pass

    def charge_cow(self, req, n_blocks):
        pass

    def charge_spill(self, n_blocks, req=None, restore=False):
        pass

    def charge_block_seconds(self, held, lengths, block_size, ticks):
        pass

    def flush(self):
        pass

    def snapshot(self) -> Dict:
        return {}


NOOP_COSTS = NoopCostAccountant()
