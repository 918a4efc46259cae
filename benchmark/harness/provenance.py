"""Which program, named scope and source line a device operation of a
profiler trace comes from, and which of the program's own spans covers an
idle gap: the lookup behind the per-layer metrics ``kv_relayout_share``
and ``remat_time_share`` and behind the ``provenance_breakdown`` line.

Everything is read from the trace itself (``.xplane.pb``), so it says what
was actually loaded and works on a program that has no scopes yet:

- the ``/host:metadata`` plane holds the HLO module of every program that
  ran, with each instruction's ``op_name`` (the ``jax.named_scope`` path)
  and stack frame (file:line). ``jax.profiler.ProfileData`` does not show
  event metadata, so that plane is read from the protobuf's wire format;
- a device plane's ``XLA Modules`` line holds one event per execution of a
  program (``jit_<fn>(<fingerprint>)``): an operation of the ``XLA Ops``
  line belongs to the execution that contains its start, so ``copy.63`` of
  the prefill program and ``copy.63`` of the decode program are two rows;
- the host plane holds the program's spans (``serve.*`` with telemetry on,
  ``train.*`` always) as ``TraceAnnotation`` events on the device's clock.

The parser of the module text is the benchmark's own (no code shared with
``deepspeed_tpu/telemetry/costs.py``): a change to the program's parser
cannot move a benchmark number."""

import bisect
import glob
import os
import re
from collections import defaultdict

from harness import tracereduce as tr

MODULES_LINE = "XLA Modules"
METADATA_PLANE = "/host:metadata"
PROGRAM_SPAN_PREFIXES = ("serve.", "train.")
KV_SCOPES = ("kv_write", "kv_gather", "paged_attn")
REMAT_SCOPE = "rematted_computation"       # jax.checkpoint's recompute
XLA_REMAT_NAME = re.compile(r"\.remat\d*$")    # XLA's own rematerialisation


# ---- the protobuf wire format, as far as the metadata plane needs it --------

def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        shift += 7
        if not b & 0x80:
            return out, i


def _fields(buf):
    """(field number, wire type, value) of one serialized message."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 1:
            value, i = buf[i:i + 8], i + 8
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire == 5:
            value, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"wire type {wire}")
        yield field, wire, value


def _hlo_text(module_proto: bytes):
    """Text of a serialized HloModuleProto, or None."""
    from jax._src.lib import xla_client as xc
    try:
        return xc._xla.HloModule.from_serialized_hlo_module_proto(
            module_proto).to_string()
    except Exception:       # noqa: BLE001 - a jaxlib without that entry
        try:
            return xc.XlaComputation(module_proto).as_hlo_text()
        except Exception:   # noqa: BLE001 - no provenance, no metric
            return None


def hlo_texts(path: str) -> dict:
    """{"jit_fn(fingerprint)": module text} for every program whose HLO the
    trace carries (XSpace.planes=1; XPlane.name=2, .event_metadata=4, a map
    entry's value=2; XEventMetadata.name=2, .stats=5; XStat.bytes_value=6
    holds an HloProto, whose hlo_module=1)."""
    with open(path, "rb") as f:
        space = f.read()
    out = {}
    for field, _, plane in _fields(space):
        if field != 1:
            continue
        parts = list(_fields(plane))
        if not any(f == 2 and v == METADATA_PLANE.encode()
                   for f, _, v in parts):
            continue
        for f, _, entry in parts:
            if f != 4:
                continue
            meta = next((v for k, _, v in _fields(entry) if k == 2), None)
            if meta is None:
                continue
            name, proto = None, None
            for k, _, v in _fields(meta):
                if k == 2:
                    name = v.decode()
                elif k == 5:
                    proto = next((x for j, w, x in _fields(v)
                                  if j == 6 and w == 2), proto)
            if not name or proto is None:
                continue
            module = next((x for j, w, x in _fields(proto)
                           if j == 1 and w == 2), None)
            text = _hlo_text(module) if module is not None else None
            if text:
                out[name] = text
    return out


# ---- module text -> {instruction: opcode, shape, scope, source} -------------

_INSTR = re.compile(r"^\s*(ROOT\s+)?%?([\w.\-]+)\s+=\s+(.*)$")
_COMPUTATION = re.compile(r"^\s*(ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_ROW = re.compile(r"^(\d+)\s+(.*)$")


def _past_group(text, start):
    depth = 0
    for i in range(start, len(text)):
        if text[i] in "([{":
            depth += 1
        elif text[i] in ")]}":
            depth -= 1
            if depth == 0:
                return i + 1
    return len(text)


def _attr(text, key):
    m = re.search(key + r'=("([^"]*)"|[^\s}]+)', text)
    if m is None:
        return None
    return m.group(2) if m.group(2) is not None else m.group(1)


def parse_hlo(text: str) -> dict:
    """instruction name -> {"opcode", "shape", "scope", "op", "source",
    "inferred"?}. ``scope`` is ``op_name`` without the leading
    ``jit(...)`` and the closing primitive; ``source`` the innermost
    frame's file:line. A fusion takes the metadata of its fused
    computation's root; an instruction without metadata (a copy the
    compiler inserted) takes the scope of the nearest instruction that has
    some (users first, then its operand's producer), marked ``inferred``.
    Instructions of fused computations and reducers are left out."""
    files, locations, frames = {}, {}, {}
    table, current = None, None
    comps, called = {}, set()
    for line in text.splitlines():
        s = line.strip()
        if current is None:
            if s in ("FileNames", "FunctionNames", "FileLocations",
                     "StackFrames"):
                table = s
                continue
            row = _ROW.match(s) if table else None
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
                table, current = None, m.group(2)
                comps[current] = []
            continue
        if s == "}":
            current = None
            continue
        m = _INSTR.match(line)
        if m is None:
            continue
        rest = m.group(3)
        end = _past_group(rest, 0) if rest.startswith("(") \
            else (rest.find(" ") if " " in rest else len(rest))
        shape, rest = rest[:end], rest[end:].lstrip()
        paren = rest.find("(")
        if paren < 0:
            continue
        close = _past_group(rest, paren)
        attrs = rest[close:]
        meta = re.search(r"(?:^|[\s,])metadata=\{([^}]*)\}", attrs)
        called.update(t for _, t in re.findall(
            r"\b(calls|to_apply)=%?([\w.\-]+)", attrs))
        fused = re.search(r"\bcalls=%?([\w.\-]+)", attrs)
        opcode = rest[:paren]
        comps[current].append({
            "name": m.group(2), "root": bool(m.group(1)), "opcode": opcode,
            "shape": shape, "meta": meta.group(1) if meta else "",
            "operands": re.findall(r"%([\w.\-]+)", rest[paren:close]),
            "calls": fused.group(1) if fused and opcode == "fusion"
            else None})

    def source_of(meta):
        f, ln = _attr(meta, "source_file"), _attr(meta, "source_line")
        if f:
            return f"{f}:{ln}" if ln else f
        frame = _attr(meta, "stack_frame_id")
        if frame is None:
            return ""
        fid, ln = locations.get(frames.get(int(frame), 0), (0, 0))
        return f"{files[fid]}:{ln}" if fid in files else ""

    out = {}
    for comp, instrs in comps.items():
        if comp in called:
            continue
        users = defaultdict(list)
        for ins in instrs:
            for o in ins["operands"]:
                users[o].append(ins["name"])
        local = {}
        for ins in instrs:
            meta = ins["meta"]
            if ins["calls"] in comps:
                root = [r["meta"] for r in comps[ins["calls"]]
                        if r["root"] and _attr(r["meta"], "op_name")]
                meta = root[0] if root else meta
            parts = (_attr(meta, "op_name") or "").split("/")
            if parts[0].startswith(("jit(", "pjit(")):
                parts = parts[1:]
            local[ins["name"]] = {
                "opcode": ins["opcode"], "shape": ins["shape"],
                "scope": "/".join(parts[:-1]), "op": parts[-1] if parts
                else "", "source": source_of(meta)}
        operand = {ins["name"]: ins["operands"][:1] for ins in instrs}
        named = {n for n, e in local.items() if e["op"] or e["scope"]}
        for ins in instrs:
            if ins["name"] in named:
                continue
            donor = _nearest(ins["name"], users, operand, named)
            if donor is not None:
                local[ins["name"]].update(scope=local[donor]["scope"],
                                          source=local[donor]["source"],
                                          inferred=True)
        out.update(local)
    return out


def _nearest(name, users, operand, named, depth=4):
    """The nearest instruction with metadata: breadth first over users,
    then the first operand's producer, through tuples and
    get-tuple-elements, at most ``depth`` steps away."""
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


def dims_of(shape: str):
    """``bf16[1,1089,16,25,64]{...}`` -> (1, 1089, 16, 25, 64); () for a
    tuple or a scalar."""
    m = re.match(r"^\w+\[([\d,]*)\]", shape)
    if m is None or not m.group(1):
        return ()
    return tuple(int(x) for x in m.group(1).split(","))


# ---- the join: device operations by program, host spans of the program ------

def short_program(name: str) -> str:
    """``jit_serve_decode_slots(123)`` -> ``jit_serve_decode_slots``."""
    return name.split("(", 1)[0]


def span_name(event_name: str) -> str:
    """A ``TraceAnnotation`` with counts is named ``name#k=v,...#``."""
    return event_name.split("#", 1)[0]


class ProgramTrace:
    """``ops``: per device, [((program, op), start, end, self seconds)]
    inside [t0, t1); ``tables``: program -> parse_hlo table;
    ``spans``: the program's own host spans [(name, start, end)]."""

    def __init__(self, ops, tables, spans, t0, t1):
        self.ops, self.tables, self.spans = ops, tables, spans
        self.t0, self.t1 = t0, t1

    def entry(self, program, op):
        return self.tables.get(program, {}).get(op)

    def seconds(self, pick) -> float:
        """Mean over the devices of the self seconds of the operations
        ``pick(program, op, entry)`` accepts."""
        total = 0.0
        for dev in self.ops:
            total += sum(x for (p, o), _, _, x in dev
                         if pick(p, o, self.entry(p, o)))
        return total / len(self.ops) if self.ops else 0.0

    def top_ops(self, n=10):
        """[[label, seconds]]: ``<op>@<program>/<scope> <file>:<line>``
        (the scope path ends in the jax primitive, as ``op_name`` does),
        by self time, mean over the devices."""
        tot = defaultdict(float)
        for dev in self.ops:
            for key, _, _, x in dev:
                tot[key] += x / len(self.ops)
        rows = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
        return [[self.label(p, o), x] for (p, o), x in rows]

    def label(self, program, op) -> str:
        e = self.entry(program, op) or {}
        scope = "/".join(x for x in (e.get("scope"), e.get("op")) if x)
        if e.get("inferred"):
            scope += "(inferred)"
        src = e.get("source", "")
        for top in ("/deepspeed_tpu/", "/benchmark/"):
            if top in src:
                src = src[src.rfind(top) + 1:]
                break
        return f"{op}@{short_program(program)}/{scope} {src}".rstrip()

    def idle_gaps(self, fallback_spans=()):
        """(sums by span name, single gaps) of the first device's idle
        gaps, each named by the innermost program span covering its
        middle; where none does, by the innermost of ``fallback_spans``
        (the benchmark's outside spans), else ``no_span``."""
        dev = self.ops[0]
        busy = tr.merge([(s, e) for _, s, e, _ in dev])
        edges = [self.t0] + [x for iv in busy for x in iv] + [self.t1]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        sums, single = defaultdict(float), []
        for s, e in gaps:
            mid = 0.5 * (s + e)
            name = innermost(self.spans, mid) \
                or innermost(fallback_spans, mid) or "no_span"
            sums[name] += e - s
            single.append((name, e - s))
        return dict(sums), single


def innermost(spans, t):
    best = None
    for name, s, e in spans:
        if s <= t < e and (best is None or e - s < best[1]):
            best = (name, e - s)
    return best[0] if best else None


def load(path: str, t0: float, t1: float) -> ProgramTrace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    tables = {name: parse_hlo(text) for name, text in hlo_texts(path).items()}
    ops, spans = [], []
    for plane in sorted(pd.planes, key=lambda p: p.name):
        if plane.name.startswith(tr.DEVICE_PLANE_PREFIX):
            runs, events = [], []
            for ln in plane.lines:
                if ln.name == MODULES_LINE:
                    runs = sorted((ev.start_ns * 1e-9,
                                   (ev.start_ns + ev.duration_ns) * 1e-9,
                                   ev.name) for ev in ln.events)
                elif ln.name == tr.OPS_LINE:
                    events = [(ev.name, ev.start_ns * 1e-9,
                               (ev.start_ns + ev.duration_ns) * 1e-9)
                              for ev in ln.events]
            if not events:
                continue
            starts = [r[0] for r in runs]
            keyed = []
            for name, s, e in events:
                if e <= t0 or s >= t1:
                    continue
                i = bisect.bisect_right(starts, s) - 1
                program = runs[i][2] if i >= 0 and s < runs[i][1] \
                    else "no_program"
                keyed.append(((program, tr.op_name(name)),
                              max(s, t0), min(e, t1)))
            ops.append(tr.self_times(keyed))
        elif plane.name == tr.HOST_PLANE:
            for ln in plane.lines:
                for ev in ln.events:
                    name = span_name(ev.name)
                    if name.startswith(PROGRAM_SPAN_PREFIXES):
                        s = ev.start_ns * 1e-9
                        e = s + ev.duration_ns * 1e-9
                        if e > t0 and s < t1:
                            spans.append((name, s, e))
    return ProgramTrace(ops, tables, sorted(spans, key=lambda x: x[1]),
                        t0, t1)


# ---- what the readers ask ---------------------------------------------------

def trace_path(run):
    """The trace ``run.py`` wrote for this cell (its ``Context.trace_dir``)."""
    cell = run.get("cell")
    if cell is None:
        return None
    found = sorted(glob.glob(os.path.join(
        cell.root, ".bench_out", "trace", cell.name, "plugins", "profile",
        "*", "*.xplane.pb")))
    return found[-1] if found else None


def of_run(run):
    """The run's ProgramTrace (loaded once; says the provenance breakdown
    on an earlier line), or None when there is no device trace."""
    if "program_trace" in run:
        return run["program_trace"]
    run["program_trace"] = pt = None
    base, path = run.get("trace"), trace_path(run)
    if base is not None and path is not None:
        run["program_trace"] = pt = load(path, base.t0, base.t1)
        say_breakdown(run, pt)
    return pt


def say_breakdown(run, pt: ProgramTrace):
    """The ``breakdown`` of the contract line again, with each device
    operation's program, scope and source line, and the idle gaps by the
    program's innermost covering span."""
    sums, single = pt.idle_gaps(run["trace"].host_spans)
    head = sorted(sums.items(), key=lambda kv: -kv[1])[:8]
    tail = sorted(single, key=lambda kv: -kv[1])[:6]
    run["say"](info="provenance_breakdown",
               programs={short_program(p): len(t)
                         for p, t in pt.tables.items()},
               device_ops=pt.top_ops(10),
               idle_gaps=[["sum:" + k, v] for k, v in head]
               + [[k, v] for k, v in tail])
    dispatch = program_span_summary(pt)
    if dispatch:
        run["say"](info="program_spans", **dispatch)


RELAYOUT_OPCODES = ("copy", "reshape")


def is_kv_relayout(entry, pool_dims) -> bool:
    """A ``copy`` or a ``reshape`` (one that runs on the device moves every
    byte, as a copy does: a free one is a bitcast and has no event) in, or
    inferred into, the paged cache's write, gather or attention scope, or
    one whose result is one layer's whole pool (or the layers' pools
    stacked)."""
    if entry is None or entry["opcode"] not in RELAYOUT_OPCODES:
        return False
    if any(part in KV_SCOPES for part in entry["scope"].split("/")):
        return True
    return dims_of(entry["shape"])[-4:] == tuple(pool_dims)


def kv_relayout_share(run):
    """Device seconds of the KV pool's relayout copies and reshapes over
    busy seconds, %."""
    pt = of_run(run)
    if pt is None or run.get("kind") != "serve" or not pt.tables:
        return None
    busy = run["trace"].busy_s
    if busy <= 0:
        return None
    pool = (run["pool_blocks"] + 1, run["block_size"], run["kv_heads"],
            run["head_dim"])
    by_scope = defaultdict(float)
    for dev in pt.ops:
        for (p, o), _, _, x in dev:
            e = pt.entry(p, o)
            if is_kv_relayout(e, pool):
                by_scope[f"{short_program(p)}/{e['scope'] or 'no_scope'}"] \
                    += x / len(pt.ops)
    run["say"](info="kv_relayout", pool_dims=list(pool),
               seconds_by_program_scope=dict(by_scope), busy_s=busy)
    return 100.0 * sum(by_scope.values()) / busy


def is_remat(op, entry) -> bool:
    if XLA_REMAT_NAME.search(op):
        return True
    return entry is not None and REMAT_SCOPE in entry["scope"].split("/")


def remat_time_share(run):
    """Device seconds of operations that recompute (jax.checkpoint's
    ``rematted_computation``, or an instruction XLA rematerialised itself)
    over busy seconds, %."""
    pt = of_run(run)
    if pt is None or run.get("kind") != "train" or not pt.tables:
        return None
    busy = run["trace"].busy_s
    if busy <= 0:
        return None
    jax_s = pt.seconds(lambda p, o, e: e is not None
                       and REMAT_SCOPE in e["scope"].split("/"))
    all_s = pt.seconds(lambda p, o, e: is_remat(o, e))
    run["say"](info="remat", checkpoint_recompute_s=jax_s,
               xla_rematerialised_s=all_s - jax_s, busy_s=busy)
    return 100.0 * all_s / busy


def dispatch_idle_ms(run):
    """Device idle time inside the program's ``serve.dispatch`` spans, per
    dispatch seen whole in the traced tail, ms: what the host costs the
    device at every launch. None without a device trace or the spans."""
    pt = of_run(run)
    if pt is None or run.get("kind") != "serve":
        return None
    return program_span_summary(pt).get("dispatch_idle_ms")


def program_span_summary(pt: ProgramTrace):
    """From the program's own serving spans, when the run had telemetry
    on: median ``serve.dispatch.enqueue`` / ``.wait`` / ``serve.pull`` in
    ms, and the device's idle seconds inside ``serve.dispatch`` spans over
    the dispatches seen whole, ms. {} without such spans."""
    whole = [(s, e) for n, s, e in pt.spans if n == "serve.dispatch"
             and s >= pt.t0 and e <= pt.t1]
    if not whole or not pt.ops:
        return {}
    busy = tr.merge([(s, e) for _, s, e, _ in pt.ops[0]])
    starts = [b[0] for b in busy]

    def busy_inside(s, e):
        total = 0.0
        i = max(0, bisect.bisect_right(starts, s) - 1)
        while i < len(busy) and busy[i][0] < e:
            total += max(0.0, min(e, busy[i][1]) - max(s, busy[i][0]))
            i += 1
        return total

    idle = sum((e - s) - busy_inside(s, e) for s, e in whole)
    out = {"dispatches_seen_whole": len(whole),
           "dispatch_idle_ms": 1e3 * idle / len(whole)}
    for name, key in (("serve.dispatch.enqueue", "dispatch_enqueue_ms"),
                      ("serve.dispatch.wait", "dispatch_wait_ms"),
                      ("serve.pull", "pull_ms")):
        d = sorted((e - s) * 1e3 for n, s, e in pt.spans if n == name)
        if d:
            out[key + "_median"] = d[len(d) // 2]
    return out
