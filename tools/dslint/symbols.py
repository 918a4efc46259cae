"""dslint phase 1: the package-wide symbol table and call graph.

Everything the interprocedural rules (DS011–DS014, :mod:`interproc`)
need to see *across* files is collected here in one pass per module:

- function/method definitions with their parameter lists;
- jit-wrapped callables (``x = jax.jit(fn, donate_argnums=...)``,
  ``@partial(jax.jit, ...)`` decorations) with their donated/static
  positions, keyed the same way call sites spell them — ``("name", x)``
  module-scoped, ``("attr", x)`` package-wide for ``self.x``/``cls.x``;
- fault-site activity: ``fire("site")``/``maybe_fire("site")`` string
  literals, *fire-forwarding* helpers (a function that passes one of
  its own parameters into a fire call — ``serving._device_call``,
  ``paged_cache._fire``), ``KNOWN_SITES`` set literals and
  ``register_site("...")`` calls;
- env-flag activity: literal ``DS_*`` reads (``os.environ[...]``,
  ``os.environ.get``, ``os.getenv``, ``<mapping>.get("DS_...")``),
  ``resolve_flag("DS_...")`` calls, and the declared ``FLAGS`` table
  (name, kind, default) parsed from its AST literal;
- telemetry registrations: ``<metrics>.counter/gauge/histogram(name)``
  and ``<tracer>.event(name)`` / ``<tracer>.span(name)`` calls, with f-string names resolved by
  expanding module-level constant tables (the ``for key, ... in
  _STAT_FIELDS`` / ``for ph in PHASES`` idioms) and degraded to ``*``
  wildcard patterns when a piece stays dynamic;
- a file-level import graph (who imports whom inside the analyzed
  roots), which ``--closure`` uses to lint a changed file plus its
  direct callers.

The jit wrapper spellings come from
``deepspeed_tpu/utils/jit_registry.py`` — loaded straight from the file
path so dslint keeps its never-imports-the-code-under-analysis property
(the module is pure stdlib by contract).
"""

import ast
import json
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Set, Tuple

from tools.dslint.core import REPO_ROOT, link_parents

FUNC_TYPES = (ast.FunctionDef, ast.AsyncFunctionDef)

# -- shared jit-entry-point definition ----------------------------------

_FALLBACK_JIT_CHAINS = (("jax", "jit"), ("jit",), ("jax", "pjit"), ("pjit",))


def _load_jit_chains() -> Tuple[Tuple[str, ...], ...]:
    """The wrapper name-chains from utils/jit_registry.py, loaded from
    the FILE (never via the deepspeed_tpu package, which imports jax).
    Falls back to the built-in list when the file is absent (fixture
    trees)."""
    path = REPO_ROOT / "deepspeed_tpu" / "utils" / "jit_registry.py"
    try:
        import importlib.util
        spec = importlib.util.spec_from_file_location("_ds_jit_registry",
                                                      path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return tuple(tuple(c) for c in mod.JIT_WRAPPER_CHAINS)
    except Exception:
        return _FALLBACK_JIT_CHAINS


JIT_CHAINS = _load_jit_chains()


def _dotted(func: ast.AST) -> List[str]:
    parts: List[str] = []
    while isinstance(func, ast.Attribute):
        parts.append(func.attr)
        func = func.value
    if isinstance(func, ast.Name):
        parts.append(func.id)
        return list(reversed(parts))
    return []


def _is_jit(func: ast.AST) -> bool:
    return tuple(_dotted(func)) in JIT_CHAINS


def _int_items(value: ast.AST) -> List[int]:
    items = value.elts if isinstance(value, (ast.Tuple, ast.List)) \
        else [value]
    return [i.value for i in items
            if isinstance(i, ast.Constant) and isinstance(i.value, int)]


def _callee_key(node: ast.AST) -> Optional[Tuple[str, str]]:
    """("name", x) for a bare call target, ("attr", x) for self.x/cls.x."""
    if isinstance(node, ast.Name):
        return ("name", node.id)
    if isinstance(node, ast.Attribute) and isinstance(node.value, ast.Name) \
            and node.value.id in ("self", "cls"):
        return ("attr", node.attr)
    return None


# -- collected records --------------------------------------------------

@dataclass
class JitEntry:
    """One donating/static-carrying jit registration."""
    key: Tuple[str, str]        # how call sites spell it
    path: str
    line: int
    donate: List[int]           # donated positions AS SEEN AT CALL SITES
    static: List[int]
    helper_of: Optional[Tuple[str, str]] = None   # set for propagated entries


@dataclass
class FireSite:
    site: str                   # the literal (or "<dynamic>")
    path: str
    line: int
    fn: Optional[str]           # enclosing function name


@dataclass
class EnvRead:
    var: str
    path: str
    line: int
    how: str                    # "environ" | "getenv" | "get" | "resolve_flag"


@dataclass
class MetricReg:
    name: str                   # concrete name, or wildcard pattern with '*'
    kind: str                   # counter|gauge|histogram|event
    path: str
    line: int
    pattern: bool = False


@dataclass
class FuncInfo:
    name: str
    path: str
    line: int
    params: List[str]
    is_method: bool
    node: ast.AST = field(repr=False, default=None)


@dataclass
class SymbolTable:
    files: List[Tuple[str, ast.AST, Sequence[str]]] = field(
        default_factory=list)
    functions: List[FuncInfo] = field(default_factory=list)
    jit_entries: List[JitEntry] = field(default_factory=list)
    fire_sites: List[FireSite] = field(default_factory=list)
    # (path, fn-name) -> index of the forwarded site parameter (call-site
    # positions: `self` already dropped for methods)
    fire_forwarders: Dict[Tuple[str, str], int] = field(default_factory=dict)
    known_sites: Set[str] = field(default_factory=set)
    known_sites_loc: Optional[Tuple[str, int]] = None
    registered_sites: Dict[str, Tuple[str, int]] = field(default_factory=dict)
    env_reads: List[EnvRead] = field(default_factory=list)
    flags_declared: Dict[str, Tuple[str, object, str, int]] = field(
        default_factory=dict)       # name -> (kind, default, path, line)
    flags_path: Optional[str] = None
    metric_regs: List[MetricReg] = field(default_factory=list)
    imports: Dict[str, Set[str]] = field(default_factory=dict)  # path->paths


# -- per-module collection ----------------------------------------------

_REGISTRY_RECV = ("metrics", "registry", "reg")
_METRIC_METHODS = ("counter", "gauge", "histogram")


class _ModuleCollector:
    """One pass over one module's AST, appending into the SymbolTable."""

    def __init__(self, table: SymbolTable, path: str, tree: ast.AST,
                 lines: Sequence[str]):
        self.t = table
        self.path = path
        self.tree = tree
        self.lines = lines
        # one walk, shared by every collector below — ast.walk per
        # collector dominated the whole lint's runtime before this
        self.nodes: List[ast.AST] = list(ast.walk(tree))
        self.calls: List[ast.Call] = [n for n in self.nodes
                                      if isinstance(n, ast.Call)]
        self.assigns: List[ast.Assign] = [n for n in self.nodes
                                          if isinstance(n, ast.Assign)]
        # name -> registry-method kind, for the `make = metrics.counter
        # if ... else metrics.gauge; make(f"...")` idiom (resolved once
        # per module instead of re-walking the scope per call)
        self.name_reg_kinds: Dict[str, str] = {}
        for a in self.assigns:
            tnames = [t.id for t in a.targets if isinstance(t, ast.Name)]
            if not tnames:
                continue
            attrs = {sub.attr for sub in ast.walk(a.value)
                     if isinstance(sub, ast.Attribute)}
            hit = attrs & set(_METRIC_METHODS)
            if hit:
                for tn in tnames:
                    self.name_reg_kinds[tn] = sorted(hit)[0]
        # module-level constant tables for f-string loop resolution:
        # NAME -> set of strings (tuple-of-str, tuple-of-tuples first
        # elements, dict keys)
        self.const_tables: Dict[str, Set[str]] = {}
        # NAME -> str for simple module-level string constants
        self.str_consts: Dict[str, str] = {}

    # .. module constants ..............................................

    def _collect_consts(self) -> None:
        for node in self.tree.body if hasattr(self.tree, "body") else []:
            if not isinstance(node, ast.Assign):
                continue
            for tgt in node.targets:
                if not isinstance(tgt, ast.Name):
                    continue
                v = node.value
                if isinstance(v, ast.Constant) and isinstance(v.value, str):
                    self.str_consts[tgt.id] = v.value
        # second pass so dict keys can reference str constants above
        for node in self.tree.body if hasattr(self.tree, "body") else []:
            if not isinstance(node, ast.Assign):
                continue
            for tgt in node.targets:
                if not isinstance(tgt, ast.Name):
                    continue
                strs = self._string_set(node.value)
                if strs:
                    self.const_tables[tgt.id] = strs

    def _string_set(self, v: ast.AST) -> Set[str]:
        """The strings a module-level table yields when iterated: a
        tuple/list/set of strings, a tuple of tuples (first elements),
        or a dict (its keys) — covering ``for ph in PHASES``,
        ``for key, ... in _STAT_FIELDS`` and ``for s in HEALTH_CODES``."""
        out: Set[str] = set()
        if isinstance(v, (ast.Tuple, ast.List, ast.Set)):
            for e in v.elts:
                if isinstance(e, ast.Constant) and isinstance(e.value, str):
                    out.add(e.value)
                elif isinstance(e, (ast.Tuple, ast.List)) and e.elts:
                    first = e.elts[0]
                    if isinstance(first, ast.Constant) \
                            and isinstance(first.value, str):
                        out.add(first.value)
        elif isinstance(v, ast.Dict):
            for k in v.keys:
                if isinstance(k, ast.Constant) and isinstance(k.value, str):
                    out.add(k.value)
                elif isinstance(k, ast.Name) and k.id in self.str_consts:
                    out.add(self.str_consts[k.id])
        return out

    # .. driver ........................................................

    def run(self) -> None:
        self._collect_consts()
        self._collect_functions()
        self._collect_jit_entries()
        self._collect_fault_symbols()
        self._collect_env_reads()
        self._collect_flags_table()
        self._collect_metric_regs()

    # .. functions ......................................................

    def _collect_functions(self) -> None:
        for node in self.nodes:
            if not isinstance(node, FUNC_TYPES):
                continue
            params = [a.arg for a in (list(node.args.posonlyargs)
                                      + list(node.args.args))]
            is_method = bool(params) and params[0] in ("self", "cls")
            self.t.functions.append(FuncInfo(
                name=node.name, path=self.path, line=node.lineno,
                params=params, is_method=is_method, node=node))

    # .. jit entries ....................................................

    def _jit_decorator(self, dec: ast.AST) -> Optional[ast.Call]:
        if isinstance(dec, ast.Call):
            if _is_jit(dec.func):
                return dec
            chain = _dotted(dec.func)
            if chain[-1:] == ["partial"] and dec.args \
                    and _is_jit(dec.args[0]):
                return dec
        return None

    def _collect_jit_entries(self) -> None:
        for node in self.nodes:
            if isinstance(node, ast.Assign) and isinstance(node.value,
                                                           ast.Call):
                call = node.value
                if not _is_jit(call.func):
                    continue
                donate, static = self._donate_static(call)
                if not donate:
                    continue
                for tgt in node.targets:
                    key = _callee_key(tgt)
                    if key is None and isinstance(tgt, ast.Attribute):
                        # module-attr targets (rare) — track by attr name
                        key = ("attr", tgt.attr)
                    if key is not None:
                        # jitting a bound method (jax.jit(self._fn)) drops
                        # `self`, so the positions apply at call sites as-is
                        self.t.jit_entries.append(JitEntry(
                            key=key, path=self.path, line=node.lineno,
                            donate=donate, static=static))
            elif isinstance(node, FUNC_TYPES):
                for dec in node.decorator_list:
                    jd = self._jit_decorator(dec)
                    if jd is None:
                        continue
                    donate, static = self._donate_static(jd)
                    if not donate:
                        continue
                    params = [a.arg for a in (list(node.args.posonlyargs)
                                              + list(node.args.args))]
                    is_method = bool(params) and params[0] in ("self", "cls")
                    # a decorated method's donate positions count `self`;
                    # self.x call sites don't pass it — shift by one
                    off = 1 if is_method else 0
                    key = ("attr" if is_method else "name", node.name)
                    self.t.jit_entries.append(JitEntry(
                        key=key, path=self.path, line=node.lineno,
                        donate=[p - off for p in donate if p - off >= 0],
                        static=[p - off for p in static if p - off >= 0]))
                    break

    @staticmethod
    def _donate_static(call: ast.Call) -> Tuple[List[int], List[int]]:
        donate: List[int] = []
        static: List[int] = []
        for kw in call.keywords:
            if kw.arg == "donate_argnums":
                donate = _int_items(kw.value)
            elif kw.arg == "static_argnums":
                static = _int_items(kw.value)
        return donate, static

    # .. fault sites ....................................................

    def _fire_call_site_arg(self, call: ast.Call) -> Optional[ast.AST]:
        """The site argument when ``call`` is a fire: ``fire(x)`` /
        ``maybe_fire(x)`` / ``<anything>.fire(x)`` / ``<anything>.
        maybe_fire(x)``."""
        chain = _dotted(call.func)
        if chain and chain[-1] in ("fire", "maybe_fire") and call.args:
            return call.args[0]
        return None

    def _collect_fault_symbols(self) -> None:
        # KNOWN_SITES / register_site literals
        for node in self.assigns:
            for tgt in node.targets:
                if isinstance(tgt, ast.Name) \
                        and tgt.id == "KNOWN_SITES" \
                        and isinstance(node.value, (ast.Set, ast.Tuple,
                                                    ast.List)):
                    for e in node.value.elts:
                        if isinstance(e, ast.Constant) \
                                and isinstance(e.value, str):
                            self.t.known_sites.add(e.value)
                    self.t.known_sites_loc = (self.path, node.lineno)
        for node in self.calls:
            chain = _dotted(node.func)
            if chain[-1:] == ["register_site"] and node.args \
                    and isinstance(node.args[0], ast.Constant) \
                    and isinstance(node.args[0].value, str):
                self.t.registered_sites[node.args[0].value] = (
                    self.path, node.lineno)
            # fired literals + fire-forwarding helpers: a fire literal is
            # attributed to EVERY enclosing function (a nested closure's
            # fire still covers its public host for DS012)
            arg = self._fire_call_site_arg(node)
            if arg is None:
                continue
            if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
                fns = self._enclosing_funcs(node)
                for fn in fns or [None]:
                    self.t.fire_sites.append(FireSite(
                        site=arg.value, path=self.path, line=node.lineno,
                        fn=fn.name if fn is not None else None))
            elif isinstance(arg, ast.Name):
                fn = self._enclosing_func(node)
                if fn is None:
                    continue
                params = [a.arg for a in (list(fn.args.posonlyargs)
                                          + list(fn.args.args))]
                is_method = bool(params) and params[0] in ("self", "cls")
                if arg.id in params:
                    # helper forwards its own param into the fire —
                    # record the call-site position (minus self)
                    idx = params.index(arg.id) - (1 if is_method else 0)
                    if idx >= 0:
                        self.t.fire_forwarders[(self.path, fn.name)] = idx

    def _enclosing_funcs(self, node: ast.AST) -> List[ast.AST]:
        out: List[ast.AST] = []
        p = getattr(node, "_ds_parent", None)
        while p is not None:
            if isinstance(p, FUNC_TYPES):
                out.append(p)
            p = getattr(p, "_ds_parent", None)
        return out

    def _enclosing_func(self, node: ast.AST) -> Optional[ast.AST]:
        p = getattr(node, "_ds_parent", None)
        while p is not None:
            if isinstance(p, FUNC_TYPES):
                return p
            p = getattr(p, "_ds_parent", None)
        return None

    # .. env reads ......................................................

    def _collect_env_reads(self) -> None:
        for node in self.nodes:
            if isinstance(node, ast.Subscript):
                chain = _dotted(node.value)
                if chain == ["os", "environ"] \
                        and isinstance(node.slice, ast.Constant) \
                        and isinstance(node.slice.value, str):
                    self.t.env_reads.append(EnvRead(
                        node.slice.value, self.path, node.lineno, "environ"))
            if not isinstance(node, ast.Call) or not node.args:
                continue
            first = node.args[0]
            if not (isinstance(first, ast.Constant)
                    and isinstance(first.value, str)):
                continue
            chain = _dotted(node.func)
            if chain == ["os", "getenv"]:
                self.t.env_reads.append(EnvRead(
                    first.value, self.path, node.lineno, "getenv"))
            elif chain[-1:] == ["resolve_flag"]:
                self.t.env_reads.append(EnvRead(
                    first.value, self.path, node.lineno, "resolve_flag"))
            elif chain[-1:] == ["get"] and first.value.startswith("DS_"):
                # os.environ.get / env.get(<mapping param>) / dict get of
                # a DS_* key — all count as env-flag reads for DS013
                self.t.env_reads.append(EnvRead(
                    first.value, self.path, node.lineno, "get"))

    # .. FLAGS table ....................................................

    def _collect_flags_table(self) -> None:
        for node in self.assigns:
            names = [t.id for t in node.targets if isinstance(t, ast.Name)]
            if "FLAGS" not in names:
                continue
            self.t.flags_path = self.path
            # every Call inside the literal whose first arg is a DS_*
            # string declares a flag: covers Flag("DS_X", kind, default)
            # and the _mk("DS_X", kind, default, help) helper alike
            for call in ast.walk(node.value):
                if not (isinstance(call, ast.Call) and call.args):
                    continue
                a = call.args
                if not (isinstance(a[0], ast.Constant)
                        and isinstance(a[0].value, str)
                        and a[0].value.startswith("DS_")):
                    continue
                kind = a[1].value if len(a) > 1 \
                    and isinstance(a[1], ast.Constant) else "?"
                default = a[2].value if len(a) > 2 \
                    and isinstance(a[2], ast.Constant) else None
                self.t.flags_declared[a[0].value] = (
                    kind, default, self.path, call.lineno)

    # .. telemetry registrations .......................................

    def _collect_metric_regs(self) -> None:
        for node in self.calls:
            if not node.args:
                continue
            kind = self._reg_kind(node)
            if kind is None:
                continue
            name = self._name_of(node.args[0], node)
            if name is None:
                continue
            concrete, pattern = name
            self.t.metric_regs.append(MetricReg(
                name=concrete, kind=kind, path=self.path,
                line=node.lineno, pattern=pattern))

    def _reg_kind(self, call: ast.Call) -> Optional[str]:
        """counter/gauge/histogram/event when ``call`` registers a
        telemetry name; None otherwise (including bare Counter/Gauge/
        Histogram constructors, which never reach a registry)."""
        func = call.func
        if isinstance(func, ast.Attribute):
            if func.attr in _METRIC_METHODS:
                recv = _dotted(func.value)
                if recv and (recv[-1] in _REGISTRY_RECV
                             or any(r in _REGISTRY_RECV for r in recv)):
                    return func.attr
                return None
            if func.attr in ("event", "span"):
                recv = _dotted(func.value)
                if recv and ("tracer" in [r.lower() for r in recv]
                             or recv[-1].lower().endswith("tracer")):
                    return func.attr
                return None
            return None
        if isinstance(func, ast.Name):
            if func.id in ("Counter", "Gauge", "Histogram"):
                return None      # constructor, not a registry entry
            # the `make = metrics.counter if ... else metrics.gauge;
            # make(f"...")` idiom: the name was assigned somewhere in
            # this module from an expression mentioning a registry
            # method (precomputed map; conditional counter-or-gauge
            # resolves to the first kind — the schema doesn't key on
            # kind for existence checks)
            return self.name_reg_kinds.get(func.id)
        return None

    def _name_of(self, arg: ast.AST,
                 call: ast.Call) -> Optional[Tuple[str, bool]]:
        """(name, is_pattern) for the registration's name argument:
        literal → concrete; f-string → expanded against loop constant
        tables where possible, else a ``*`` wildcard pattern. Returns a
        '|'-joined set marker via multiple appends instead? No — the
        caller gets ONE entry; expansion appends extra records here."""
        if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
            return (arg.value, False)
        if not isinstance(arg, ast.JoinedStr):
            return None
        # try to expand each formatted value via loop constant tables
        parts: List[List[str]] = []
        dynamic = False
        for piece in arg.values:
            if isinstance(piece, ast.Constant):
                parts.append([str(piece.value)])
            elif isinstance(piece, ast.FormattedValue) \
                    and isinstance(piece.value, ast.Name):
                vals = self._loop_values(piece.value.id, call)
                if vals:
                    parts.append(sorted(vals))
                else:
                    parts.append(["*"])
                    dynamic = True
            else:
                parts.append(["*"])
                dynamic = True
        if dynamic:
            pat = "".join(p[0] if len(p) == 1 and p[0] != "*" else "*"
                          for p in parts)
            # collapse runs of *
            while "**" in pat:
                pat = pat.replace("**", "*")
            return (pat, True)
        # cartesian expansion (in practice one dynamic piece)
        names = [""]
        for p in parts:
            names = [n + v for n in names for v in p]
        kind = self._reg_kind(call)
        for extra in names[1:]:
            self.t.metric_regs.append(MetricReg(
                name=extra, kind=kind or "counter", path=self.path,
                line=call.lineno, pattern=False))
        return (names[0], False)

    def _loop_values(self, var: str, call: ast.Call) -> Set[str]:
        """Strings ``var`` ranges over, when it is the target (or first
        tuple element) of a for/comprehension iterating a module-level
        constant table — the f-string-in-loop registration idiom."""
        node: ast.AST = call
        p = getattr(node, "_ds_parent", None)
        while p is not None:
            targets_iters: List[Tuple[ast.AST, ast.AST]] = []
            if isinstance(p, (ast.For, ast.AsyncFor)):
                targets_iters.append((p.target, p.iter))
            for gen in getattr(p, "generators", []) or []:
                targets_iters.append((gen.target, gen.iter))
            for tgt, it in targets_iters:
                bound = None
                if isinstance(tgt, ast.Name) and tgt.id == var:
                    bound = True
                elif isinstance(tgt, ast.Tuple) and tgt.elts \
                        and isinstance(tgt.elts[0], ast.Name) \
                        and tgt.elts[0].id == var:
                    bound = True     # `for key, kind, help_ in TABLE`
                if bound:
                    if isinstance(it, ast.Name):
                        vals = self.const_tables.get(it.id, set())
                        if vals:
                            return vals
                    return set()
            p = getattr(p, "_ds_parent", None)
        return set()

    # .. imports (file-level call graph) ................................

    def collect_imports(self, module_index: Dict[str, str]) -> None:
        """Record which analyzed files this module imports.
        ``module_index`` maps dotted module names (``deepspeed_tpu.
        inference.serving``) to analyzed file paths."""
        deps: Set[str] = set()
        for node in self.nodes:
            mods: List[str] = []
            if isinstance(node, ast.Import):
                mods = [a.name for a in node.names]
            elif isinstance(node, ast.ImportFrom) and node.module \
                    and node.level == 0:
                mods = [node.module] + [f"{node.module}.{a.name}"
                                        for a in node.names]
            for m in mods:
                if m in module_index and module_index[m] != self.path:
                    deps.add(module_index[m])
        self.t.imports[self.path] = deps


# -- table construction -------------------------------------------------

def module_name_of(path: str) -> Optional[str]:
    """Dotted module name for a repo-relative posix path
    (``deepspeed_tpu/inference/serving.py`` →
    ``deepspeed_tpu.inference.serving``; ``__init__.py`` maps to its
    package)."""
    if not path.endswith(".py"):
        return None
    parts = path[:-3].split("/")
    if parts[-1] == "__init__":
        parts = parts[:-1]
    if not parts:
        return None
    return ".".join(parts)


def build_symbol_table(
        files: Sequence[Tuple[str, ast.AST, Sequence[str]]]) -> SymbolTable:
    """Phase 1: one SymbolTable over every parsed module."""
    table = SymbolTable(files=list(files))
    collectors = []
    module_index: Dict[str, str] = {}
    for path, tree, lines in files:
        link_parents(tree)      # idempotent; collectors walk upward
        mod = module_name_of(path)
        if mod:
            module_index[mod] = path
    for path, tree, lines in files:
        c = _ModuleCollector(table, path, tree, lines)
        c.run()
        c.collect_imports(module_index)
        collectors.append(c)
    # per-function keyed-call lists, computed once and shared by the
    # propagation passes below (re-walking per fixpoint round was the
    # hot spot of the whole lint)
    fn_calls: List[Tuple[FuncInfo, List[Tuple[Tuple[str, str],
                                              ast.Call]]]] = []
    for fn in table.functions:
        if fn.node is None:
            continue
        pairs = []
        for call in ast.walk(fn.node):
            if isinstance(call, ast.Call):
                key = _callee_key(call.func)
                if key is not None:
                    pairs.append((key, call))
        fn_calls.append((fn, pairs))
    _propagate_helper_donation(table, fn_calls)
    _collect_forwarded_fires(table, fn_calls)
    return table


def _collect_forwarded_fires(table: SymbolTable, fn_calls) -> None:
    """A literal passed into a fire-forwarder's site parameter counts as
    fired: ``self._device_call("serving.dispatch", fn, tok)`` fires
    ``serving.dispatch`` even though the ``fire(...)`` call itself only
    sees a variable. Forwarding is transitive — ``_maybe_inject`` passes
    its site into ``_fire`` which passes it into ``faults.fire`` — so
    the forwarder set is closed to a fixpoint first."""
    by_name: Dict[str, int] = {fn: idx for (_, fn), idx
                               in table.fire_forwarders.items()}
    if not by_name:
        return
    changed = True
    while changed:
        changed = False
        for fn, pairs in fn_calls:
            if (fn.path, fn.name) in table.fire_forwarders:
                continue
            off = 1 if fn.is_method else 0
            for key, call in pairs:
                if key[1] not in by_name:
                    continue
                idx = by_name[key[1]]
                if idx < len(call.args) \
                        and isinstance(call.args[idx], ast.Name) \
                        and call.args[idx].id in fn.params:
                    pos = fn.params.index(call.args[idx].id) - off
                    if pos >= 0:
                        table.fire_forwarders[(fn.path, fn.name)] = pos
                        by_name[fn.name] = pos
                        changed = True
                    break
    for path, tree, lines in table.files:
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            key = _callee_key(node.func)
            if key is None or key[1] not in by_name:
                continue
            idx = by_name[key[1]]
            if idx < len(node.args) \
                    and isinstance(node.args[idx], ast.Constant) \
                    and isinstance(node.args[idx].value, str):
                fn = None
                p = getattr(node, "_ds_parent", None)
                while p is not None:
                    if isinstance(p, FUNC_TYPES):
                        fn = p.name
                        break
                    p = getattr(p, "_ds_parent", None)
                table.fire_sites.append(FireSite(
                    site=node.args[idx].value, path=path,
                    line=node.lineno, fn=fn))


def _propagate_helper_donation(table: SymbolTable, fn_calls) -> None:
    """One level of helper inlining for DS011: a function that passes
    one of its own parameters into a donated position of a jit entry
    itself donates that parameter — callers of the helper get the same
    use-after check."""
    by_key: Dict[Tuple[str, str], List[JitEntry]] = {}
    for e in table.jit_entries:
        by_key.setdefault(e.key, []).append(e)
    new_entries: List[JitEntry] = []
    for fn, pairs in fn_calls:
        params = fn.params
        is_method = fn.is_method
        donated_params: Set[int] = set()
        for key, call in pairs:
            entries = by_key.get(key)
            if not entries:
                continue
            for entry in entries:
                # name-keyed entries only bind within their own module
                if entry.key[0] == "name" and entry.path != fn.path:
                    continue
                for pos in entry.donate:
                    if pos < len(call.args) \
                            and isinstance(call.args[pos], ast.Name) \
                            and call.args[pos].id in params:
                        donated_params.add(params.index(call.args[pos].id))
        if not donated_params:
            continue
        off = 1 if is_method else 0
        donate = sorted(p - off for p in donated_params if p - off >= 0)
        if not donate:
            continue
        key = ("attr" if is_method else "name", fn.name)
        if any(e.key == key for e in table.jit_entries):
            continue    # already a jit entry under this name
        new_entries.append(JitEntry(
            key=key, path=fn.path, line=fn.line, donate=donate,
            static=[], helper_of=key))
    table.jit_entries.extend(new_entries)


# -- import-graph cache (gate.sh quick / --closure) ---------------------

CALLGRAPH_CACHE = REPO_ROOT / "build" / "dslint_callgraph.json"

# Shared analysis INPUTS whose content changes rule behaviour without
# changing any analyzed .py file's import graph: the jit-wrapper
# spec and the telemetry schema. Their hashes ride the cache so a
# `--closure` run after editing one of them misses the cache and falls
# back to a full pass (a stale cache here means DS002/DS011/DS014
# silently lint against yesterday's contract).
CACHE_INPUT_FILES: Tuple[Tuple[str, Path], ...] = (
    ("jit_registry", REPO_ROOT / "deepspeed_tpu" / "utils"
     / "jit_registry.py"),
    ("telemetry_schema", REPO_ROOT / "tools" / "dslint"
     / "telemetry_schema.json"),
)


def cache_input_hashes(files: Optional[Sequence[Tuple[str, Path]]] = None
                       ) -> Dict[str, str]:
    """sha256 per shared analysis input; absent files hash to ''."""
    import hashlib
    out: Dict[str, str] = {}
    for key, p in (CACHE_INPUT_FILES if files is None else files):
        try:
            out[key] = hashlib.sha256(Path(p).read_bytes()).hexdigest()
        except OSError:
            out[key] = ""
    return out


def write_callgraph_cache(table: SymbolTable,
                          path: Optional[Path] = None,
                          inputs: Optional[Dict[str, str]] = None) -> Path:
    path = Path(path or CALLGRAPH_CACHE)
    path.parent.mkdir(parents=True, exist_ok=True)
    data = {p: sorted(deps) for p, deps in sorted(table.imports.items())}
    path.write_text(json.dumps({
        "version": 2,
        "inputs": cache_input_hashes() if inputs is None else inputs,
        "imports": data}, indent=1) + "\n", encoding="utf-8")
    return path


def load_callgraph_cache(path: Optional[Path] = None,
                         inputs: Optional[Dict[str, str]] = None
                         ) -> Dict[str, Set[str]]:
    """The cached import graph, or {} when the cache is missing,
    unreadable, from another cache version, or was written against
    different shared-input content (jit_registry / telemetry_schema) —
    {} makes --closure fall back to a full re-analysis."""
    path = Path(path or CALLGRAPH_CACHE)
    if not path.exists():
        return {}
    try:
        data = json.loads(path.read_text(encoding="utf-8"))
    except (OSError, ValueError):
        return {}
    if data.get("version") != 2:
        return {}
    current = cache_input_hashes() if inputs is None else inputs
    if data.get("inputs") != current:
        return {}
    return {p: set(deps) for p, deps in data.get("imports", {}).items()}


def closure_of(changed: Sequence[str],
               imports: Dict[str, Set[str]]) -> List[str]:
    """Changed files plus their DIRECT callers (files importing them),
    repo-relative paths in, repo-relative paths out."""
    changed_set = set(changed)
    out = set(changed_set)
    for path, deps in imports.items():
        if deps & changed_set:
            out.add(path)
    return sorted(out)
