"""dslint v3: per-function control-flow graphs, forward dataflow, and
the flow-sensitive rules DS016–DS018.

The v2 interprocedural layer (:mod:`interproc`) sees *across* modules
but not *through* control flow — it cannot tell "released on every
path" from "released on the happy path". This module adds the missing
layer:

- :func:`build_cfg` — a per-function CFG with branch, loop (incl.
  for-else/while-else), try/except/finally, break/continue, raise and
  early-return edges. Statements inside a ``try`` body get one block
  each so exception edges are per-statement.
- :class:`ForwardAnalysis` / :class:`GenKill` + :func:`solve_forward` —
  a generic forward worklist solver over set-valued facts (union join,
  monotone transfer ⇒ the fixpoint terminates).
- :func:`build_pair_summaries` — interprocedural acquire/release
  summaries riding the PR-14 symbol table, so lifecycle-split helpers
  (``spill_tick`` acquires, ``_harvest_spill`` releases) are checked as
  a package, not per function.

The rules on top:

DS016  resource pairing: path-sensitive acquire/release balance for
       the repo's paired APIs (block allocate/free, adapter
       acquire/release, ``_in_transfer`` add/discard, host-tier
       pin/abort) — paths (including exception edges) that leak a
       local handle or double-release flag, plus a package-wide
       "acquired somewhere but released nowhere" summary direction.
DS017  traced-value escape: dataflow taint from traced jit arguments
       through assignment chains into Python control flow, host-sync
       calls, or dict keys — the flow-sensitive superset of the purely
       syntactic DS004 (DS017 only reports what DS004 cannot see, so
       the two never double-report one site).
DS018  snapshot round-trip completeness: every dataclass field of a
       snapshot-bearing request type (``ServeRequest``) must be
       serialized by ``snapshot_entry`` AND restored by
       ``from_snapshot`` — or be declared ephemeral in the module's
       ``SNAPSHOT_EPHEMERAL`` allowlist (adapter_id, seed chains and
       cost footprints each had to be retrofitted in separate PRs;
       this makes the next field a lint error instead).

Like every dslint rule, these never import the code under analysis.
"""

import ast
from collections import deque
from dataclasses import dataclass, field
from typing import (Dict, FrozenSet, Iterable, List, Optional, Sequence,
                    Set, Tuple)

from tools.dslint.core import Finding
from tools.dslint.interproc import InterprocRule, _dedupe
from tools.dslint.rules import FUNC_TYPES, TracedPythonBranch, _dotted
from tools.dslint.symbols import FuncInfo, SymbolTable

# ==========================================================================
# control-flow graph
# ==========================================================================

NORMAL = "normal"
EXC = "exc"            # exception edge (try-body stmt -> handler/finally)


class Block:
    """A straight-line run of statements. ``succ`` maps successor block
    -> edge kind (``normal`` | ``exc``)."""

    __slots__ = ("id", "label", "stmts", "succ", "pred")

    def __init__(self, bid: int, label: str = ""):
        self.id = bid
        self.label = label
        self.stmts: List[ast.stmt] = []
        self.succ: Dict["Block", str] = {}
        self.pred: Dict["Block", str] = {}

    def __repr__(self):
        return f"B{self.id}({self.label or len(self.stmts)})"

    def __hash__(self):
        return self.id


class CFG:
    """Control-flow graph of one function body: unique ``entry`` and
    ``exit`` blocks; ``exit`` doubles as the exceptional exit (an
    uncaught raise flows there too)."""

    def __init__(self, fn: ast.AST):
        self.fn = fn
        self.blocks: List[Block] = []
        self.entry = self.new("entry")
        self.exit = self.new("exit")

    def new(self, label: str = "") -> Block:
        b = Block(len(self.blocks), label)
        self.blocks.append(b)
        return b

    def edge(self, src: Optional[Block], dst: Block,
             kind: str = NORMAL) -> None:
        if src is None:
            return
        src.succ.setdefault(dst, kind)
        dst.pred.setdefault(src, kind)


class _Builder:
    def __init__(self, fn: ast.AST):
        self.cfg = CFG(fn)
        # (continue_target, break_target) innermost-last
        self.loops: List[Tuple[Block, Block]] = []
        # innermost-last list of exception targets: the blocks an
        # exception raised "here" may reach (handler entries + finally)
        self.exc: List[List[Block]] = []
        # innermost-last finally entries (return/break route through)
        self.finals: List[Block] = []

    def build(self) -> CFG:
        end = self._stmts(self.cfg.fn.body, self.cfg.entry)
        self.cfg.edge(end, self.cfg.exit)
        return self.cfg

    # -- statement dispatch ---------------------------------------------

    def _stmts(self, body: Sequence[ast.stmt],
               cur: Optional[Block]) -> Optional[Block]:
        """Process a statement list starting in ``cur``; returns the
        block control falls out of, or None when the end is
        unreachable (every path returned/raised/broke)."""
        for stmt in body:
            if cur is None:
                # dead code after return/raise: give it its own island
                # so analyses stay total, but nothing flows in
                cur = self.cfg.new("dead")
            cur = self._stmt(stmt, cur)
        return cur

    def _stmt(self, stmt: ast.stmt, cur: Block) -> Optional[Block]:
        if isinstance(stmt, ast.If):
            return self._if(stmt, cur)
        if isinstance(stmt, (ast.While, ast.For, ast.AsyncFor)):
            return self._loop(stmt, cur)
        if isinstance(stmt, ast.Try):
            return self._try(stmt, cur)
        if isinstance(stmt, (ast.With, ast.AsyncWith)):
            cur.stmts.append(stmt)
            return self._stmts(stmt.body, cur)
        if isinstance(stmt, ast.Return):
            cur.stmts.append(stmt)
            self.cfg.edge(cur, self.finals[-1] if self.finals
                          else self.cfg.exit)
            return None
        if isinstance(stmt, ast.Raise):
            cur.stmts.append(stmt)
            targets = self.exc[-1] if self.exc else [self.cfg.exit]
            for t in targets:
                self.cfg.edge(cur, t, EXC)
            return None
        if isinstance(stmt, ast.Break):
            cur.stmts.append(stmt)
            if self.loops:
                self.cfg.edge(cur, self.loops[-1][1])
            return None
        if isinstance(stmt, ast.Continue):
            cur.stmts.append(stmt)
            if self.loops:
                self.cfg.edge(cur, self.loops[-1][0])
            return None
        # plain statement (incl. nested defs, which are opaque here)
        cur.stmts.append(stmt)
        if self.exc:
            # inside a try body: per-statement exception edges — end the
            # block so the edge is as precise as the statement
            for t in self.exc[-1]:
                self.cfg.edge(cur, t, EXC)
            nxt = self.cfg.new()
            self.cfg.edge(cur, nxt)
            return nxt
        return cur

    def _if(self, stmt: ast.If, cur: Block) -> Optional[Block]:
        cur.stmts.append(stmt)      # the test evaluates in cur
        after = self.cfg.new("endif")
        then_b = self.cfg.new("then")
        self.cfg.edge(cur, then_b)
        then_end = self._stmts(stmt.body, then_b)
        self.cfg.edge(then_end, after)
        if stmt.orelse:
            else_b = self.cfg.new("else")
            self.cfg.edge(cur, else_b)
            else_end = self._stmts(stmt.orelse, else_b)
            self.cfg.edge(else_end, after)
        else:
            self.cfg.edge(cur, after)
        return after if after.pred else None

    def _loop(self, stmt, cur: Block) -> Optional[Block]:
        header = self.cfg.new("loop")
        header.stmts.append(stmt)   # test / iter evaluates per entry
        self.cfg.edge(cur, header)
        after = self.cfg.new("endloop")
        body_b = self.cfg.new("body")
        self.cfg.edge(header, body_b)
        self.loops.append((header, after))
        body_end = self._stmts(stmt.body, body_b)
        self.cfg.edge(body_end, header)     # back edge
        self.loops.pop()
        if stmt.orelse:
            # else runs on NORMAL loop exit (no break)
            else_b = self.cfg.new("loopelse")
            self.cfg.edge(header, else_b)
            else_end = self._stmts(stmt.orelse, else_b)
            self.cfg.edge(else_end, after)
        else:
            self.cfg.edge(header, after)
        return after if after.pred else None

    def _try(self, stmt: ast.Try, cur: Block) -> Optional[Block]:
        after = self.cfg.new("endtry")
        fin_entry = self.cfg.new("finally") if stmt.finalbody else None
        handler_entries = [self.cfg.new("except") for _ in stmt.handlers]
        # exception targets while inside the try body: every handler may
        # match; with no handlers the finally is the only catcher
        targets = list(handler_entries) or \
            ([fin_entry] if fin_entry else [])
        if stmt.handlers and fin_entry is not None:
            # an exception no handler matches still runs the finally
            targets = targets + [fin_entry]
        self.exc.append(targets or [self.cfg.exit])
        if fin_entry is not None:
            self.finals.append(fin_entry)
        body_b = self.cfg.new("try")
        self.cfg.edge(cur, body_b)
        body_end = self._stmts(stmt.body, body_b)
        self.exc.pop()
        else_end = self._stmts(stmt.orelse, body_end) \
            if stmt.orelse else body_end
        normal_join = fin_entry if fin_entry is not None else after
        self.cfg.edge(else_end, normal_join)
        for hb, handler in zip(handler_entries, stmt.handlers):
            h_end = self._stmts(handler.body, hb)
            self.cfg.edge(h_end, normal_join)
        if fin_entry is not None:
            self.finals.pop()
            fin_end = self._stmts(stmt.finalbody, fin_entry)
            if fin_end is not None:
                self.cfg.edge(fin_end, after)
                # the finally also forwards in-flight returns/raises
                outer = self.exc[-1] if self.exc else [self.cfg.exit]
                for t in outer:
                    self.cfg.edge(fin_end, t, EXC)
        return after if after.pred else None


def build_cfg(fn: ast.AST) -> CFG:
    """CFG for one function/method body (``fn`` is a FunctionDef)."""
    return _Builder(fn).build()


# ==========================================================================
# forward dataflow
# ==========================================================================

class ForwardAnalysis:
    """Forward may-analysis over frozenset facts: union join. Subclass
    and override :meth:`transfer_stmt` (or use :class:`GenKill`)."""

    def boundary(self) -> FrozenSet:
        return frozenset()

    def join(self, facts: Iterable[FrozenSet]) -> FrozenSet:
        out: FrozenSet = frozenset()
        for f in facts:
            out = out | f
        return out

    def transfer_stmt(self, stmt: ast.stmt, fact: FrozenSet) -> FrozenSet:
        return fact

    def transfer_block(self, block: Block, fact: FrozenSet) -> FrozenSet:
        for s in block.stmts:
            fact = self.transfer_stmt(s, fact)
        return fact


class GenKill(ForwardAnalysis):
    """gen/kill convenience: ``out = (in - kill(stmt)) | gen(stmt)``."""

    def gen(self, stmt: ast.stmt, fact: FrozenSet) -> Iterable:
        return ()

    def kill(self, stmt: ast.stmt, fact: FrozenSet) -> Iterable:
        return ()

    def transfer_stmt(self, stmt, fact):
        return (fact - frozenset(self.kill(stmt, fact))) \
            | frozenset(self.gen(stmt, fact))


def solve_forward(cfg: CFG, analysis: ForwardAnalysis
                  ) -> Tuple[Dict[Block, FrozenSet], Dict[Block, FrozenSet]]:
    """Worklist fixpoint; returns (in_facts, out_facts) per block.
    Monotone transfers over a finite fact lattice converge (loops
    included — the back edge just re-queues the header until stable)."""
    in_facts: Dict[Block, FrozenSet] = {}
    out_facts: Dict[Block, FrozenSet] = {}
    work = deque(cfg.blocks)
    while work:
        b = work.popleft()
        preds = [out_facts.get(p, frozenset()) for p in b.pred]
        inf = analysis.join(preds)
        if b is cfg.entry:
            inf = inf | analysis.boundary()
        out = analysis.transfer_block(b, inf)
        in_facts[b] = inf
        if out != out_facts.get(b):
            out_facts[b] = out
            for s in b.succ:
                if s not in work:
                    work.append(s)
    return in_facts, out_facts


# ==========================================================================
# shared AST helpers
# ==========================================================================

def _names_in(node: ast.AST) -> Set[str]:
    return {n.id for n in ast.walk(node) if isinstance(n, ast.Name)}


def shallow_walk(stmt: ast.stmt):
    """Walk a CFG-block statement's HEADER only. Compound statements
    land in a block alongside their test/iter/items, but their nested
    bodies live in their own blocks — a transfer function that walked
    the whole subtree would count every nested call twice (once in the
    header block, once in the body block). Nested function bodies
    don't execute here at all, so defs are opaque."""
    if isinstance(stmt, (ast.If, ast.While)):
        yield from ast.walk(stmt.test)
    elif isinstance(stmt, (ast.For, ast.AsyncFor)):
        yield from ast.walk(stmt.target)
        yield from ast.walk(stmt.iter)
    elif isinstance(stmt, (ast.With, ast.AsyncWith)):
        for item in stmt.items:
            yield from ast.walk(item.context_expr)
            if item.optional_vars is not None:
                yield from ast.walk(item.optional_vars)
    elif isinstance(stmt, (ast.Try, *FUNC_TYPES, ast.ClassDef)):
        yield stmt
    else:
        yield from ast.walk(stmt)


def _call_chain(call: ast.Call) -> List[str]:
    return _dotted(call.func)


def _fn_params(fn: ast.AST) -> List[str]:
    return [a.arg for a in (list(fn.args.posonlyargs) + list(fn.args.args)
                            + list(fn.args.kwonlyargs))]


# ==========================================================================
# DS016 — resource pairing
# ==========================================================================

@dataclass(frozen=True)
class PairSpec:
    """One paired acquire/release API. ``handle=True`` pairs return a
    trackable handle from the acquire (``bid = cache.allocate(...)``);
    set-style pairs (``handle=False``) mutate a named container attr
    (``self._in_transfer.update(ids)``) and are checked by package-wide
    summary balance instead of per-path handles."""
    kind: str
    acquire: Tuple[str, ...]
    release: Tuple[str, ...]
    handle: bool = True
    attr_suffix: Optional[str] = None    # receiver constraint (set-style)


DEFAULT_PAIRS: Tuple[PairSpec, ...] = (
    PairSpec("cache-block", ("allocate",), ("free", "_release")),
    PairSpec("adapter", ("acquire",), ("release",)),
    PairSpec("in-transfer", ("add", "update"), ("discard", "remove"),
             handle=False, attr_suffix="_in_transfer"),
    PairSpec("host-pin", ("pin",), ("unpin", "abort")),
)


def _calls_in(fn_node: ast.AST) -> List[Tuple[List[str], ast.Call]]:
    """All (dotted chain, Call) pairs under ``fn_node``, computed once
    per node — DS016 consults this list once per pair spec and again
    per check direction, so the walk itself must not repeat."""
    cached = getattr(fn_node, "_ds_calls", None)
    if cached is None:
        cached = [(_call_chain(n), n) for n in ast.walk(fn_node)
                  if isinstance(n, ast.Call)]
        cached = [(c, n) for c, n in cached if c]
        fn_node._ds_calls = cached
    return cached


def _pair_calls(fn_node: ast.AST, spec: PairSpec
                ) -> Tuple[List[ast.Call], List[ast.Call]]:
    """(acquire calls, release calls) of one pair inside ``fn_node``."""
    acq: List[ast.Call] = []
    rel: List[ast.Call] = []
    for chain, n in _calls_in(fn_node):
        if spec.attr_suffix is not None:
            # set-style: <...>._in_transfer.<op>(...)
            if len(chain) < 2 or not chain[-2].endswith(spec.attr_suffix):
                continue
        if chain[-1] in spec.acquire:
            acq.append(n)
        elif chain[-1] in spec.release:
            rel.append(n)
    return acq, rel


@dataclass
class PairSummary:
    """Interprocedural summary of one function's net pair activity:
    how many acquire and release sites of each kind it contains
    (transitively local — helpers are their own summaries)."""
    acquires: Dict[str, int] = field(default_factory=dict)
    releases: Dict[str, int] = field(default_factory=dict)


def summarize_pairs(fn_node: ast.AST,
                    pairs: Sequence[PairSpec] = DEFAULT_PAIRS
                    ) -> PairSummary:
    s = PairSummary()
    for spec in pairs:
        acq, rel = _pair_calls(fn_node, spec)
        if acq:
            s.acquires[spec.kind] = len(acq)
        if rel:
            s.releases[spec.kind] = len(rel)
    return s


def build_pair_summaries(table: SymbolTable,
                         pairs: Sequence[PairSpec] = DEFAULT_PAIRS
                         ) -> Dict[Tuple[str, str], PairSummary]:
    """(path, function name) -> :class:`PairSummary` for every function
    in the symbol table — the package-wide acquire/release ledger the
    completeness direction of DS016 reads."""
    out: Dict[Tuple[str, str], PairSummary] = {}
    for fn in table.functions:
        if fn.node is None:
            continue
        s = summarize_pairs(fn.node, pairs)
        if s.acquires or s.releases:
            out[(fn.path, fn.name)] = s
    return out


class _ReleasedNames(GenKill):
    """Forward may-analysis: handles released (by pair kind) since
    their last (re)binding — a release while already in the fact is a
    double release on some path."""

    def __init__(self, spec: PairSpec):
        self.spec = spec

    def _released_here(self, stmt: ast.stmt) -> Set[str]:
        out: Set[str] = set()
        for call in shallow_walk(stmt):
            if isinstance(call, ast.Call):
                h = _release_target(call, self.spec)
                if h:
                    out.add(h)
        return out

    def gen(self, stmt, fact):
        return self._released_here(stmt)

    def kill(self, stmt, fact):
        return _rebound_names(stmt)


def _rebound_names(stmt: ast.stmt) -> Set[str]:
    """Names this statement (header) rebinds: assignment targets,
    for-loop targets, with-as targets."""
    out: Set[str] = set()
    targets: List[ast.AST] = []
    if isinstance(stmt, ast.Assign):
        targets = list(stmt.targets)
    elif isinstance(stmt, (ast.For, ast.AsyncFor)):
        targets = [stmt.target]
    elif isinstance(stmt, (ast.With, ast.AsyncWith)):
        targets = [i.optional_vars for i in stmt.items
                   if i.optional_vars is not None]
    for t in targets:
        out |= {n.id for n in ast.walk(t)
                if isinstance(n, ast.Name) and isinstance(n.ctx, ast.Store)}
    return out


def _release_target(call: ast.Call, spec: PairSpec) -> Optional[str]:
    """The handle name a release call settles: ``free(h)`` /
    ``pool.release(h)`` → ``h``; ``h.release()`` → ``h``. None when
    ``call`` is not a release of this pair (or the handle isn't a
    simple name)."""
    chain = _call_chain(call)
    if not chain or chain[-1] not in spec.release:
        return None
    if call.args and isinstance(call.args[0], ast.Name):
        return call.args[0].id
    if isinstance(call.func, ast.Attribute) \
            and isinstance(call.func.value, ast.Name) \
            and not call.args:
        return call.func.value.id        # h.release()
    return None


class ResourcePairing(InterprocRule):
    id = "DS016"
    name = "resource-pairing"
    autofixable = False
    rationale = ("the paged cache, adapter pool and host tier all live "
                 "on paired acquire/release discipline (block refcounts, "
                 "adapter pins, in-transfer exclusion); a path — "
                 "including an exception edge — that leaks a handle or "
                 "releases twice corrupts the pool long after the call "
                 "that did it")

    def __init__(self, pairs: Sequence[PairSpec] = DEFAULT_PAIRS):
        self.pairs = tuple(pairs)

    def check_package(self, table, docs_root=None, schema_path=None,
                      partial=False):
        out: List[Finding] = []
        handle_pairs = [p for p in self.pairs if p.handle]
        for fn in table.functions:
            if fn.node is None:
                continue
            relevant = [p for p in handle_pairs
                        if _pair_calls(fn.node, p) != ([], [])]
            if not relevant:
                continue
            cfg = None
            for spec in relevant:
                acq, rel = _pair_calls(fn.node, spec)
                if not acq:
                    continue
                if cfg is None:
                    cfg = build_cfg(fn.node)
                out.extend(self._check_leaks(fn, cfg, spec, acq))
                out.extend(self._check_double_release(fn, cfg, spec))
        if not partial:
            out.extend(self._check_summary_balance(table))
        return _dedupe(out)

    # -- (a) handle leak: some path from acquire to exit w/o release ----

    def _check_leaks(self, fn: FuncInfo, cfg: CFG, spec: PairSpec,
                     acquires: List[ast.Call]) -> List[Finding]:
        out: List[Finding] = []
        for call in acquires:
            handle = self._handle_of(call, fn.node)
            if handle is None:
                continue
            if self._escapes(fn.node, handle, spec):
                continue
            leak = self._leak_path(cfg, call, handle, spec)
            if leak is not None:
                via = " (via an exception edge)" if leak == EXC else ""
                out.append(self._f(
                    fn.path, call.lineno,
                    f"`{handle}` acquired from `{_call_chain(call)[-1]}` "
                    f"({spec.kind}) is not released on every path to "
                    f"exit{via} — release it on all paths (try/finally) "
                    f"or hand it off explicitly"))
        return out

    @staticmethod
    def _handle_of(call: ast.Call, fn_node: ast.AST) -> Optional[str]:
        """The local name an acquire binds: ``h = pool.acquire(x)``."""
        for n in ast.walk(fn_node):
            if isinstance(n, ast.Assign) and n.value is call \
                    and len(n.targets) == 1 \
                    and isinstance(n.targets[0], ast.Name):
                return n.targets[0].id
        return None

    def _escapes(self, fn_node: ast.AST, handle: str,
                 spec: PairSpec) -> bool:
        """True when the handle's lifetime leaves this function: any
        Load use other than being released (returned, stored, passed
        on). Conservative — an escaped handle is someone else's
        balance to keep."""
        for n in ast.walk(fn_node):
            if not (isinstance(n, ast.Name) and n.id == handle
                    and isinstance(n.ctx, ast.Load)):
                continue
            p = getattr(n, "_ds_parent", None)
            if isinstance(p, ast.Call) and (
                    _release_target(p, spec) == handle):
                continue
            if isinstance(p, ast.Attribute) and isinstance(
                    getattr(p, "_ds_parent", None), ast.Call) \
                    and p._ds_parent.func is p \
                    and p.attr in spec.release:
                continue        # h.release()
            return True
        return False

    def _leak_path(self, cfg: CFG, call: ast.Call, handle: str,
                   spec: PairSpec) -> Optional[str]:
        """NORMAL/EXC when a path from the acquire reaches exit without
        releasing/rebinding ``handle``; None when every path settles it.
        Returns EXC when only exception paths leak."""
        start = None
        idx = 0
        for b in cfg.blocks:
            for i, s in enumerate(b.stmts):
                if any(n is call for n in shallow_walk(s)):
                    start, idx = b, i + 1
                    break
            if start is not None:
                break
        if start is None:
            return None

        def settles(stmt: ast.stmt) -> bool:
            for c in shallow_walk(stmt):
                if isinstance(c, ast.Call) \
                        and _release_target(c, spec) == handle:
                    return True
            return handle in _rebound_names(stmt)

        leak_kind: Optional[str] = None
        # DFS over (block, first-stmt-index); track whether the path so
        # far crossed an exception edge
        seen: Set[Tuple[int, int, bool]] = set()
        stack: List[Tuple[Block, int, bool]] = [(start, idx, False)]
        while stack:
            b, i, exc_path = stack.pop()
            key = (b.id, i, exc_path)
            if key in seen:
                continue
            seen.add(key)
            blocked = False
            for s in b.stmts[i:]:
                if settles(s):
                    blocked = True
                    break
            if blocked:
                continue
            if b is cfg.exit:
                if exc_path:
                    leak_kind = leak_kind or EXC
                else:
                    return NORMAL      # a plain path leaks: report that
                continue
            for succ, kind in b.succ.items():
                stack.append((succ, 0, exc_path or kind == EXC))
        return leak_kind

    # -- (b) double release ---------------------------------------------

    def _check_double_release(self, fn: FuncInfo, cfg: CFG,
                              spec: PairSpec) -> List[Finding]:
        analysis = _ReleasedNames(spec)
        in_facts, _ = solve_forward(cfg, analysis)
        out: List[Finding] = []
        for b in cfg.blocks:
            fact = in_facts.get(b, frozenset())
            for s in b.stmts:
                for call in shallow_walk(s):
                    if isinstance(call, ast.Call):
                        h = _release_target(call, spec)
                        if h and h in fact:
                            out.append(self._f(
                                fn.path, call.lineno,
                                f"`{h}` ({spec.kind}) may already be "
                                f"released when this "
                                f"`{_call_chain(call)[-1]}` runs — "
                                f"double release on some path"))
                fact = analysis.transfer_stmt(s, fact)
        return out

    # -- (c) package-wide summary balance -------------------------------

    def _check_summary_balance(self, table) -> List[Finding]:
        summaries = build_pair_summaries(table, self.pairs)
        out: List[Finding] = []
        for spec in self.pairs:
            acq_sites = [(path, name) for (path, name), s
                         in summaries.items()
                         if spec.kind in s.acquires
                         and path.startswith("deepspeed_tpu/")]
            rel_sites = [(path, name) for (path, name), s
                         in summaries.items()
                         if spec.kind in s.releases
                         and path.startswith("deepspeed_tpu/")]
            if acq_sites and not rel_sites:
                path, name = sorted(acq_sites)[0]
                fn = next(f for f in table.functions
                          if (f.path, f.name) == (path, name))
                out.append(self._f(
                    path, fn.line,
                    f"`{name}` acquires a {spec.kind} resource but "
                    f"nothing under deepspeed_tpu/ ever releases one "
                    f"({'/'.join(spec.release)}) — package-wide leak"))
        return out


# ==========================================================================
# DS017 — traced-value escape
# ==========================================================================

_HOST_SYNC_CALLS = {"float", "int", "bool"}
_HOST_SYNC_CHAINS = (["np", "asarray"], ["numpy", "asarray"],
                     ["jax", "device_get"], ["onp", "asarray"])


class _Taint(GenKill):
    """Forward taint over local names: a name is tainted when its value
    derives from a traced jit argument by data flow (metadata reads —
    .shape/.dtype/len()/isinstance() — launder the taint: they are
    static under trace)."""

    def __init__(self, sources: Set[str]):
        self.sources = sources

    def boundary(self):
        return frozenset(self.sources)

    # .. expression taint ..............................................

    def tainted(self, expr: ast.AST, fact: FrozenSet) -> bool:
        if isinstance(expr, ast.Name):
            return expr.id in fact
        if isinstance(expr, ast.Attribute):
            if expr.attr in TracedPythonBranch._OK_ATTRS:
                return False
            return self.tainted(expr.value, fact)
        if isinstance(expr, ast.Call):
            chain = _dotted(expr.func)
            if chain and chain[-1] in TracedPythonBranch._OK_CALLS:
                return False
            if chain and (chain[-1] in _HOST_SYNC_CALLS
                          or chain in _HOST_SYNC_CHAINS
                          or chain[-1] == "item"):
                return False       # host sync RESULT is a host value
            return any(self.tainted(a, fact) for a in expr.args) \
                or any(self.tainted(k.value, fact)
                       for k in expr.keywords) \
                or (isinstance(expr.func, ast.Attribute)
                    and self.tainted(expr.func.value, fact))
        if isinstance(expr, ast.Compare):
            if all(isinstance(op, (ast.Is, ast.IsNot, ast.In, ast.NotIn))
                   for op in expr.ops):
                return False       # structure test: static under trace
            return self.tainted(expr.left, fact) \
                or any(self.tainted(c, fact) for c in expr.comparators)
        if isinstance(expr, (ast.Tuple, ast.List, ast.Set)):
            return any(self.tainted(e, fact) for e in expr.elts)
        if isinstance(expr, ast.AST):
            return any(self.tainted(v, fact)
                       for _, v in ast.iter_fields(expr)
                       if isinstance(v, ast.AST)) \
                or any(self.tainted(e, fact)
                       for _, vs in ast.iter_fields(expr)
                       if isinstance(vs, list)
                       for e in vs if isinstance(e, ast.AST))
        return False

    # .. transfer ......................................................

    def gen(self, stmt, fact):
        out: Set[str] = set()
        if isinstance(stmt, ast.Assign) \
                and self.tainted(stmt.value, fact):
            for t in stmt.targets:
                out |= {n.id for n in ast.walk(t)
                        if isinstance(n, ast.Name)
                        and isinstance(n.ctx, ast.Store)}
        elif isinstance(stmt, ast.AugAssign) \
                and isinstance(stmt.target, ast.Name) \
                and (stmt.target.id in fact
                     or self.tainted(stmt.value, fact)):
            out.add(stmt.target.id)
        elif isinstance(stmt, (ast.For, ast.AsyncFor)) \
                and self.tainted(stmt.iter, fact):
            out |= {n.id for n in ast.walk(stmt.target)
                    if isinstance(n, ast.Name)}
        return out

    def kill(self, stmt, fact):
        if isinstance(stmt, ast.Assign) \
                and not self.tainted(stmt.value, fact):
            killed: Set[str] = set()
            for t in stmt.targets:
                killed |= {n.id for n in ast.walk(t)
                           if isinstance(n, ast.Name)
                           and isinstance(n.ctx, ast.Store)}
            return killed - self.sources
        return ()


class TracedValueEscape(InterprocRule):
    id = "DS017"
    name = "traced-value-escape"
    autofixable = False
    rationale = ("DS004 only sees a traced parameter used DIRECTLY in a "
                 "python branch; a traced value that flows through an "
                 "assignment chain into control flow, a host call "
                 "(float/int/bool/.item()/device_get) or a dict key "
                 "fails at trace time — or silently forces a host "
                 "round-trip per call — just the same")

    def check_package(self, table, docs_root=None, schema_path=None,
                      partial=False):
        ds004 = TracedPythonBranch()
        out: List[Finding] = []
        for path, tree, lines in table.files:
            # a file with no "jit" text has no jit wrapper to find
            if not any("jit" in l for l in lines):
                continue
            for fn, statics in ds004._jitted_defs(tree):
                params = set(_fn_params(fn)) - {"self", "cls"}
                sources = params - statics
                if not sources:
                    continue
                out.extend(self._check_fn(path, fn, sources))
        return _dedupe(out)

    def _check_fn(self, path: str, fn: ast.AST,
                  sources: Set[str]) -> List[Finding]:
        cfg = build_cfg(fn)
        analysis = _Taint(sources)
        in_facts, _ = solve_forward(cfg, analysis)
        out: List[Finding] = []
        for b in cfg.blocks:
            fact = in_facts.get(b, frozenset())
            if b is cfg.entry:
                fact = fact | analysis.boundary()
            for s in b.stmts:
                out.extend(self._sinks(path, s, fact, analysis, sources))
                fact = analysis.transfer_stmt(s, fact)
        # nested defs (scan bodies): inherit the taint of captured names
        for b in cfg.blocks:
            fact = in_facts.get(b, frozenset())
            for s in b.stmts:
                if isinstance(s, FUNC_TYPES):
                    captured = (fact | frozenset(sources)) \
                        - set(_fn_params(s))
                    if captured:
                        out.extend(self._check_fn(path, s, set(captured)))
                fact = analysis.transfer_stmt(s, fact)
        return out

    def _sinks(self, path: str, stmt: ast.stmt, fact: FrozenSet,
               analysis: _Taint, sources: Set[str]) -> List[Finding]:
        out: List[Finding] = []
        derived = fact - frozenset(sources)

        def _derived_only(expr: ast.AST) -> bool:
            """DS004 already flags DIRECT traced-param uses; DS017 owns
            the assignment-chain cases it cannot see."""
            used = _names_in(expr)
            return bool(used & derived) and not (used & sources)

        if isinstance(stmt, (ast.If, ast.While)):
            test = stmt.test
            if analysis.tainted(test, fact) and _derived_only(test):
                out.append(self._f(
                    path, stmt.lineno,
                    f"python {'if' if isinstance(stmt, ast.If) else 'while'}"
                    f" branches on a value derived from a traced argument "
                    f"(assignment chain) — under jit this fails at trace "
                    f"time; use lax.cond/where or mark the argument "
                    f"static"))
        for call in shallow_walk(stmt):
            if not isinstance(call, ast.Call):
                continue
            chain = _dotted(call.func)
            if not chain:
                continue
            is_sync = (chain[-1] in _HOST_SYNC_CALLS and len(chain) == 1) \
                or chain in _HOST_SYNC_CHAINS
            if is_sync and call.args \
                    and analysis.tainted(call.args[0], fact):
                out.append(self._f(
                    path, call.lineno,
                    f"`{'.'.join(chain)}()` forces a host sync on a "
                    f"traced value inside a jitted function — this "
                    f"fails at trace time (ConcretizationTypeError); "
                    f"keep the value on device"))
            elif chain[-1] == "item" and len(chain) >= 2 \
                    and not call.args:
                recv = call.func.value
                if analysis.tainted(recv, fact):
                    out.append(self._f(
                        path, call.lineno,
                        f"`.item()` on a traced value inside a jitted "
                        f"function — fails at trace time; keep the "
                        f"value on device"))
        for node in shallow_walk(stmt):
            if isinstance(node, ast.Dict):
                for k in node.keys:
                    if k is not None and analysis.tainted(k, fact):
                        out.append(self._f(
                            path, k.lineno,
                            f"a traced value is used as a dict key — "
                            f"tracers are not stable hash keys; key on "
                            f"a static instead"))
            elif isinstance(node, ast.Subscript) \
                    and isinstance(node.ctx, ast.Store) \
                    and isinstance(getattr(node, "_ds_parent", None),
                                   (ast.Assign, ast.AugAssign)) \
                    and isinstance(node.value, ast.Name) \
                    and node.value.id not in fact \
                    and analysis.tainted(node.slice, fact) \
                    and isinstance(node.slice, ast.Name):
                out.append(self._f(
                    path, node.lineno,
                    f"a traced value indexes a host container store — "
                    f"tracers are not stable hash keys; key on a "
                    f"static instead"))
        return out


# ==========================================================================
# DS018 — snapshot round-trip completeness
# ==========================================================================

class SnapshotRoundTrip(InterprocRule):
    id = "DS018"
    name = "snapshot-roundtrip-completeness"
    autofixable = False
    rationale = ("the drain/resume contract is only as complete as the "
                 "snapshot: a request field the scheduler writes but "
                 "pending_snapshot/from_snapshot don't round-trip is "
                 "silently lost on a replica death (adapter_id, seed "
                 "chains and cost footprints were each retrofitted in "
                 "separate PRs) — every field must round-trip or be "
                 "declared ephemeral in SNAPSHOT_EPHEMERAL")

    ALLOWLIST_NAME = "SNAPSHOT_EPHEMERAL"

    def check_package(self, table, docs_root=None, schema_path=None,
                      partial=False):
        # cheap pre-filter off the symbol table: a module without BOTH
        # halves of the round trip has no contract to check
        snap_paths = {f.path for f in table.functions
                      if f.name == "snapshot_entry"}
        restore_paths = {f.path for f in table.functions
                         if f.name == "from_snapshot"}
        out: List[Finding] = []
        for path, tree, lines in table.files:
            if path in snap_paths and path in restore_paths:
                out.extend(self._check_module(path, tree, partial))
        return _dedupe(out)

    def _check_module(self, path: str, tree: ast.AST,
                      partial: bool) -> List[Finding]:
        snap_fn = None
        cls = None
        restore_fn = None
        for node in ast.walk(tree):
            if isinstance(node, FUNC_TYPES) \
                    and node.name == "snapshot_entry":
                snap_fn = node
            elif isinstance(node, ast.ClassDef):
                for item in node.body:
                    if isinstance(item, FUNC_TYPES) \
                            and item.name == "from_snapshot":
                        cls, restore_fn = node, item
        if snap_fn is None or cls is None:
            return []

        fields = self._dataclass_fields(cls)
        if not fields:
            return []
        snap_keys = self._string_keys(snap_fn)
        restored = self._restored_kwargs(restore_fn)
        ephemeral, eph_line = self._allowlist(tree)

        out: List[Finding] = []
        for name, line in fields:
            if name in ephemeral:
                continue
            if name not in snap_keys:
                out.append(self._f(
                    path, line,
                    f"request field `{name}` is never serialized by "
                    f"snapshot_entry — a drained request silently loses "
                    f"it; add it to the snapshot or declare it in "
                    f"{self.ALLOWLIST_NAME} with a reason"))
            elif name not in restored:
                out.append(self._f(
                    path, line,
                    f"request field `{name}` is serialized by "
                    f"snapshot_entry but never restored by "
                    f"from_snapshot — the round trip drops it; restore "
                    f"it or declare it in {self.ALLOWLIST_NAME}"))
        if not partial:
            field_names = {n for n, _ in fields}
            for name in sorted(ephemeral - field_names):
                out.append(self._f(
                    path, eph_line,
                    f"{self.ALLOWLIST_NAME} declares `{name}` which is "
                    f"not a field of `{cls.name}` — stale allowlist "
                    f"entry"))
        return out

    @staticmethod
    def _dataclass_fields(cls: ast.ClassDef) -> List[Tuple[str, int]]:
        out: List[Tuple[str, int]] = []
        for item in cls.body:
            if isinstance(item, ast.AnnAssign) \
                    and isinstance(item.target, ast.Name):
                out.append((item.target.id, item.lineno))
        return out

    @staticmethod
    def _string_keys(fn: ast.AST) -> Set[str]:
        """String keys the snapshot writer emits: dict-literal keys plus
        ``entry["k"] = ...`` stores."""
        out: Set[str] = set()
        for node in ast.walk(fn):
            if isinstance(node, ast.Dict):
                for k in node.keys:
                    if isinstance(k, ast.Constant) \
                            and isinstance(k.value, str):
                        out.add(k.value)
            elif isinstance(node, ast.Subscript) \
                    and isinstance(node.ctx, ast.Store) \
                    and isinstance(node.slice, ast.Constant) \
                    and isinstance(node.slice.value, str):
                out.add(node.slice.value)
        return out

    @staticmethod
    def _restored_kwargs(fn: ast.AST) -> Set[str]:
        """Constructor keywords from_snapshot fills FROM THE ENTRY
        (``n=1`` counts as pinned, not restored)."""
        params = _fn_params(fn)
        entry_name = params[1] if len(params) > 1 else "entry"
        out: Set[str] = set()
        for node in ast.walk(fn):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Name)
                    and node.func.id in ("cls",)):
                continue
            for kw in node.keywords:
                if kw.arg and entry_name in _names_in(kw.value):
                    out.add(kw.arg)
        return out

    def _allowlist(self, tree: ast.AST) -> Tuple[Set[str], int]:
        for node in getattr(tree, "body", []):
            if isinstance(node, ast.Assign):
                names = [t.id for t in node.targets
                         if isinstance(t, ast.Name)]
                if self.ALLOWLIST_NAME in names:
                    vals: Set[str] = set()
                    for c in ast.walk(node.value):
                        if isinstance(c, ast.Constant) \
                                and isinstance(c.value, str):
                            vals.add(c.value)
                    return vals, node.lineno
        return set(), 0


# ==========================================================================

def dataflow_rules() -> List[InterprocRule]:
    return [ResourcePairing(), TracedValueEscape(),
            SnapshotRoundTrip()]


def dataflow_catalog() -> List[Dict[str, str]]:
    return [{"id": r.id, "name": r.name,
             "autofixable": r.autofixable, "rationale": r.rationale}
            for r in dataflow_rules()]
