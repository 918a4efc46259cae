"""dslint phase 2: interprocedural rules (DS011–DS014).

These consume the package-wide :class:`~tools.dslint.symbols.SymbolTable`
built in phase 1 — they see *across* modules, which the per-file rules
(DS001–DS010) deliberately don't:

DS011  donated buffer read after dispatch through a jit entry defined in
       ANOTHER module (or through one level of helper inlining) — the
       cross-module complement of DS003
DS012  fault-site integrity: every fired site literal is declared
       (KNOWN_SITES / register_site), every declared site is actually
       fired somewhere, every site is documented in docs/ROBUSTNESS.md,
       and public inference entries that dispatch a donated jit fire
       their site before the dispatch
DS013  env-flag registry: literal ``DS_*`` reads under ``deepspeed_tpu/``
       must route through ``utils/env.py::resolve_flag`` against a
       declared flag, and every declared bool flag defaults off (the
       off-state is the bit-reference)
DS014  telemetry schema drift: code-registered metric/trace names, the
       checked-in ``tools/dslint/telemetry_schema.json``, and
       docs/OBSERVABILITY.md must agree in both directions

Each rule implements ``check_package(table, docs_root=..., partial=...)``.
``partial=True`` (the ``--closure`` quick mode, where only a changed-file
closure was parsed) disables the completeness directions — "declared but
never fired", "in schema but not in code" — that are only meaningful
over the whole tree.
"""

import ast
import fnmatch
import json
import re
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Set, Tuple

from tools.dslint.core import REPO_ROOT, Finding
from tools.dslint.rules import (FUNC_TYPES, DonationHazard, _parents,
                                _stmt_of, _store_names)
from tools.dslint.symbols import (JitEntry, SymbolTable, _callee_key,
                                  _dotted)

DEFAULT_SCHEMA = Path(__file__).resolve().parent / "telemetry_schema.json"


class InterprocRule:
    id = "DS0XX"
    name = "base"
    autofixable = False
    rationale = ""

    def check_package(self, table: SymbolTable,
                      docs_root: Optional[Path] = None,
                      schema_path: Optional[Path] = None,
                      partial: bool = False) -> List[Finding]:
        raise NotImplementedError

    def _f(self, path: str, line: int, message: str,
           col: int = 0) -> Finding:
        return Finding(self.id, path, line, col, message)


# --------------------------------------------------------------------------
class DonationFlowHazard(InterprocRule):
    id = "DS011"
    name = "donated-buffer-use-after-dispatch"
    autofixable = False
    rationale = ("DS003 only sees jit registrations in the same file; a "
                 "buffer donated through an entry point defined in another "
                 "module — or passed through a helper that forwards it into "
                 "a donated position — is just as dead after the call, and "
                 "reading it returns garbage on TPU")

    def check_package(self, table, docs_root=None, schema_path=None,
                      partial=False):
        by_key: Dict[Tuple[str, str], List[JitEntry]] = {}
        for e in table.jit_entries:
            by_key.setdefault(e.key, []).append(e)
        if not by_key:
            return []
        out: List[Finding] = []
        ds003 = DonationHazard()
        for path, tree, lines in table.files:
            local = set(ds003._collect_donating(tree))
            for call in ast.walk(tree):
                if not isinstance(call, ast.Call):
                    continue
                key = _callee_key(call.func)
                if key is None or key in local \
                        or key not in by_key:
                    continue              # same-file entries are DS003's
                fn = None
                for p in _parents(call):
                    if isinstance(p, FUNC_TYPES):
                        fn = p
                        break
                if fn is None:
                    continue
                for entry in by_key[key]:
                    if entry.key[0] == "name" and entry.path != path:
                        continue          # bare names bind module-locally
                    for pos in entry.donate:
                        if pos < len(call.args) and isinstance(
                                call.args[pos], ast.Name):
                            out.extend(self._use_after(
                                fn, call, call.args[pos].id,
                                entry, path))
        return _dedupe(out)

    def _use_after(self, fn, call, name: str, entry: JitEntry,
                   path: str) -> List[Finding]:
        stmt = _stmt_of(call)
        if isinstance(stmt, ast.Assign) and any(
                name in _store_names(t) for t in stmt.targets):
            return []
        if isinstance(stmt, (ast.AugAssign, ast.AnnAssign)) and \
                name in _store_names(stmt.target):
            return []
        call_pos = (call.end_lineno or call.lineno,
                    call.end_col_offset or call.col_offset)
        events = []
        for n in ast.walk(fn):
            if isinstance(n, ast.Name) and n.id == name:
                if any(p is call for p in _parents(n)) or n is call:
                    continue
                events.append(((n.lineno, n.col_offset),
                               isinstance(n.ctx, ast.Store), n))
        events.sort(key=lambda e: e[0])
        via = (" (donates through a helper)" if entry.helper_of
               else f" (jit entry at {entry.path}:{entry.line})")
        for pos, is_store, n in events:
            if pos <= call_pos:
                continue
            if is_store:
                return []
            return [self._f(
                path, n.lineno,
                f"`{name}` was donated to `{entry.key[1]}`{via} but is "
                f"read afterwards — the buffer may have been aliased into "
                f"the output; rebind or copy before donating",
                col=n.col_offset)]
        return []


# --------------------------------------------------------------------------
class FaultSiteIntegrity(InterprocRule):
    id = "DS012"
    name = "fault-site-integrity"
    autofixable = False
    rationale = ("the chaos harness can only exercise sites that exist: a "
                 "fired literal nobody declared is untestable, a declared "
                 "site nobody fires is dead coverage, an undocumented site "
                 "is invisible to operators, and a public entry that "
                 "dispatches a donated jit without firing its site first "
                 "can't be fault-injected at the moment that matters")

    _ENTRY_PATHS = re.compile(r"(^|/)inference/")

    def check_package(self, table, docs_root=None, schema_path=None,
                      partial=False):
        out: List[Finding] = []
        declared = set(table.known_sites) | set(table.registered_sites)
        fired = {fs.site for fs in table.fire_sites}

        # (1) fired literal nobody declared — production code only; tests
        # fire synthetic sites at FaultInjector directly on purpose
        if declared:
            for fs in table.fire_sites:
                if fs.path.startswith("deepspeed_tpu/") \
                        and fs.site not in declared:
                    out.append(self._f(
                        fs.path, fs.line,
                        f"fault site '{fs.site}' is fired but not declared "
                        f"in KNOWN_SITES (or via register_site) — the chaos "
                        f"harness can't target it"))

        if not partial:
            # (2) declared site nobody fires
            for site in sorted(table.known_sites - fired):
                path, line = table.known_sites_loc or ("", 0)
                out.append(self._f(
                    path, line,
                    f"fault site '{site}' is declared in KNOWN_SITES but "
                    f"never fired anywhere — stale registration (remove it "
                    f"or wire the fire)"))
            for site, (path, line) in sorted(table.registered_sites.items()):
                if site not in fired:
                    out.append(self._f(
                        path, line,
                        f"fault site '{site}' is registered via "
                        f"register_site but never fired — stale "
                        f"registration"))
            # (3) declared site missing from the robustness doc
            out.extend(self._check_docs(table, declared, docs_root))

        # (4) public inference entries must fire before donated dispatch
        out.extend(self._check_fire_before_dispatch(table))
        return _dedupe(out)

    def _check_docs(self, table, declared: Set[str],
                    docs_root: Optional[Path]) -> List[Finding]:
        root = Path(docs_root) if docs_root is not None else REPO_ROOT / "docs"
        doc = root / "ROBUSTNESS.md"
        if not doc.exists() or not declared:
            return []
        text = doc.read_text(encoding="utf-8")
        out = []
        for site in sorted(declared):
            if site not in text:
                path, line = (table.known_sites_loc
                              or next(iter(table.registered_sites.values()),
                                      ("", 0)))
                if site in table.registered_sites:
                    path, line = table.registered_sites[site]
                out.append(self._f(
                    path, line,
                    f"fault site '{site}' is not documented in "
                    f"docs/ROBUSTNESS.md — add it to the site table"))
        return out

    def _check_fire_before_dispatch(self, table) -> List[Finding]:
        by_key: Dict[Tuple[str, str], List[JitEntry]] = {}
        for e in table.jit_entries:
            by_key.setdefault(e.key, []).append(e)
        if not by_key:
            return []
        # functions known to fire (directly or by forwarding)
        firing_fns: Set[Tuple[str, str]] = {
            (fs.path, fs.fn) for fs in table.fire_sites if fs.fn}
        firing_fns |= set(table.fire_forwarders)
        fires_by_fn: Dict[Tuple[str, str], List[int]] = {}
        for fs in table.fire_sites:
            if fs.fn:
                fires_by_fn.setdefault((fs.path, fs.fn), []).append(fs.line)
        forwarder_names = {fn for (_, fn) in table.fire_forwarders}
        out: List[Finding] = []
        for path, tree, lines in table.files:
            if not self._ENTRY_PATHS.search(path):
                continue
            for fn in ast.walk(tree):
                if not isinstance(fn, FUNC_TYPES) \
                        or fn.name.startswith("_"):
                    continue
                fire_lines = list(fires_by_fn.get((path, fn.name), []))
                for call in ast.walk(fn):
                    if not isinstance(call, ast.Call):
                        continue
                    key = _callee_key(call.func)
                    if key is not None and key[1] in forwarder_names:
                        fire_lines.append(call.lineno)
                for call in ast.walk(fn):
                    if not isinstance(call, ast.Call):
                        continue
                    key = _callee_key(call.func)
                    if key is None:
                        continue
                    entries = [e for e in by_key.get(key, ())
                               if e.key[0] == "attr" or e.path == path]
                    if not entries:
                        continue
                    entry = entries[0]
                    if entry.helper_of and (entry.path, entry.key[1]) \
                            in firing_fns:
                        continue      # the helper fires its own site
                    if any(fl <= call.lineno for fl in fire_lines):
                        continue
                    out.append(self._f(
                        path, call.lineno,
                        f"public entry `{fn.name}` dispatches donated jit "
                        f"`{key[1]}` without firing its fault site first — "
                        f"chaos tests can't inject at this dispatch; call "
                        f"maybe_fire(<site>) (or a fire-forwarding helper) "
                        f"before the dispatch"))
                    break             # one finding per public entry
        return out


# --------------------------------------------------------------------------
class EnvFlagRegistry(InterprocRule):
    id = "DS013"
    name = "env-flag-registry"
    autofixable = False
    rationale = ("every DS_* knob must be declared once in utils/env.py "
                 "FLAGS (name, type, default) and read via resolve_flag() "
                 "— scattered os.environ reads drift in parsing and "
                 "default, and a bool flag that defaults ON has no "
                 "bit-reference off-state")

    _EXEMPT = re.compile(r"(^|/)(tools|tests)/|conftest|(^|/)launcher/")

    def check_package(self, table, docs_root=None, schema_path=None,
                      partial=False):
        out: List[Finding] = []
        flags_path = table.flags_path

        for r in table.env_reads:
            if not r.var.startswith("DS_"):
                continue
            if r.how == "resolve_flag":
                if flags_path is not None \
                        and r.var not in table.flags_declared:
                    out.append(self._f(
                        r.path, r.line,
                        f"resolve_flag('{r.var}') reads an undeclared "
                        f"flag — add it to utils/env.py FLAGS with a "
                        f"typed default"))
                continue
            # raw read (os.environ / os.getenv / mapping.get)
            if not r.path.startswith("deepspeed_tpu/"):
                continue
            if r.path == flags_path or self._EXEMPT.search(r.path):
                continue
            out.append(self._f(
                r.path, r.line,
                f"direct env read of '{r.var}' bypasses the FLAGS "
                f"registry — declare it in utils/env.py and read it via "
                f"resolve_flag('{r.var}')"))

        if not partial:
            for name, (kind, default, path, line) in sorted(
                    table.flags_declared.items()):
                if kind == "bool" and default is True:
                    out.append(self._f(
                        path, line,
                        f"bool flag {name} defaults ON — the unset "
                        f"environment must be the bit-exact reference "
                        f"path; default it off and opt in explicitly"))
        return _dedupe(out)


# --------------------------------------------------------------------------
class TelemetrySchemaDrift(InterprocRule):
    id = "DS014"
    name = "telemetry-schema-drift"
    autofixable = False
    rationale = ("dashboards and alerts key on metric/trace names; a name "
                 "registered in code but absent from the schema (or "
                 "docs/OBSERVABILITY.md) is invisible to operators, and a "
                 "schema entry no code registers is a dead panel — the "
                 "checked-in telemetry_schema.json is the contract both "
                 "sides are held to")

    def check_package(self, table, docs_root=None, schema_path=None,
                      partial=False):
        spath = Path(schema_path) if schema_path is not None \
            else DEFAULT_SCHEMA
        if not spath.exists():
            return []        # no contract to enforce (fixture trees)
        try:
            schema = json.loads(spath.read_text(encoding="utf-8"))
        except (OSError, ValueError) as e:
            return [self._f(_rel(spath), 1,
                            f"unreadable telemetry schema: {e}")]
        metrics = set(schema.get("metrics", ()))
        events = set(schema.get("events", ()))
        spans = set(schema.get("spans", ()))
        patterns = list(schema.get("metric_patterns", ()))
        known = metrics | events | spans
        out: List[Finding] = []

        code_names: Set[str] = set()
        code_patterns: Set[str] = set()
        for reg in table.metric_regs:
            if self._TEST_PATHS.search(reg.path):
                continue      # unit tests register throwaway names
            target = {"event": events, "span": spans}.get(reg.kind, metrics)
            if reg.pattern:
                code_patterns.add(reg.name)
                if reg.name not in patterns:
                    out.append(self._f(
                        reg.path, reg.line,
                        f"dynamic telemetry name pattern '{reg.name}' is "
                        f"not in telemetry_schema.json metric_patterns — "
                        f"declare the family"))
                continue
            code_names.add(reg.name)
            if reg.name not in target \
                    and not _matches_any(reg.name, patterns):
                out.append(self._f(
                    reg.path, reg.line,
                    f"telemetry name '{reg.name}' ({reg.kind}) is "
                    f"registered in code but missing from "
                    f"telemetry_schema.json — add it (and a row in "
                    f"docs/OBSERVABILITY.md)"))

        if not partial:
            for name in sorted(known - code_names):
                out.append(self._f(
                    _rel(spath), 1,
                    f"schema entry '{name}' is registered by no code "
                    f"path — stale; remove it from telemetry_schema.json "
                    f"and docs/OBSERVABILITY.md"))
            for pat in patterns:
                if pat not in code_patterns:
                    out.append(self._f(
                        _rel(spath), 1,
                        f"schema pattern '{pat}' matches no dynamic "
                        f"registration in code — stale"))
            out.extend(self._check_docs(known, patterns, docs_root))
        return _dedupe(out)

    # .. docs/OBSERVABILITY.md two-way check ............................

    _TOKEN = re.compile(r"`([a-z0-9_{}|,<>*]+)`")
    _TEST_PATHS = re.compile(r"(^|/)tests/")

    def _check_docs(self, known: Set[str], patterns: Sequence[str],
                    docs_root: Optional[Path]) -> List[Finding]:
        root = Path(docs_root) if docs_root is not None else REPO_ROOT / "docs"
        doc = root / "OBSERVABILITY.md"
        if not doc.exists():
            return []
        text = doc.read_text(encoding="utf-8")
        out: List[Finding] = []
        rel = _rel(doc)
        # every backticked token in the doc, with {a|b}/{a,b} brace
        # notation expanded — so `serving_step_{admission,decode}_s`
        # documents both concrete names
        doc_names: Set[str] = set()
        for tok in self._TOKEN.findall(text):
            doc_names.update(_expand_doc_token(tok))
        # schema -> docs: every contract name appears somewhere in the doc
        for name in sorted(known):
            if name not in text and name not in doc_names \
                    and not any(fnmatch.fnmatch(name, d)
                                for d in doc_names if "*" in d):
                out.append(self._f(
                    rel, 1,
                    f"telemetry name '{name}' is in the schema but not "
                    f"mentioned in docs/OBSERVABILITY.md — document it"))
        # docs -> schema: metric-looking tokens in table first cells must
        # be real contract names (catches doc rows for renamed metrics)
        for i, ln in enumerate(text.splitlines(), 1):
            s = ln.strip()
            if not s.startswith("|"):
                continue
            first = s.split("|")[1] if s.count("|") >= 2 else ""
            for tok in self._TOKEN.findall(first):
                for cand in _expand_doc_token(tok):
                    if "_" not in cand:
                        continue      # prose words, not telemetry names
                    if cand in known or _matches_any(cand, patterns) \
                            or any(fnmatch.fnmatch(k, cand)
                                   for k in known):
                        continue
                    out.append(self._f(
                        rel, i,
                        f"docs/OBSERVABILITY.md names '{cand}' which is "
                        f"not in telemetry_schema.json — stale doc row "
                        f"or missing schema entry"))
        return out


def _expand_doc_token(tok: str) -> List[str]:
    """``serving_{ttft|tbt}_s`` → both concrete names; ``<x>``-style
    placeholders become ``*`` globs."""
    tok = re.sub(r"<[^>]*>", "*", tok)
    m = re.search(r"\{([^}]*)\}", tok)
    if not m:
        return [tok]
    out: List[str] = []
    for alt in re.split(r"[|,]", m.group(1)):
        out.extend(_expand_doc_token(
            tok[:m.start()] + alt.strip() + tok[m.end():]))
    return out


def _matches_any(name: str, patterns: Sequence[str]) -> bool:
    return any(fnmatch.fnmatch(name, p) for p in patterns)


def _rel(p: Path) -> str:
    try:
        return p.resolve().relative_to(REPO_ROOT).as_posix()
    except ValueError:
        return p.as_posix()


def _dedupe(findings: List[Finding]) -> List[Finding]:
    seen: Set[Tuple[str, str, int, str]] = set()
    out = []
    for f in findings:
        k = (f.rule, f.path, f.line, f.message)
        if k not in seen:
            seen.add(k)
            out.append(f)
    return out


# --------------------------------------------------------------------------

def interproc_rules() -> List[InterprocRule]:
    # dataflow (v3) imports InterprocRule from this module, so its
    # import must stay inside the function body
    from tools.dslint.dataflow import dataflow_rules
    return [DonationFlowHazard(), FaultSiteIntegrity(),
            EnvFlagRegistry(), TelemetrySchemaDrift()] + dataflow_rules()


def interproc_catalog() -> List[Dict[str, str]]:
    return [{"id": r.id, "name": r.name,
             "autofixable": r.autofixable, "rationale": r.rationale}
            for r in interproc_rules()]
