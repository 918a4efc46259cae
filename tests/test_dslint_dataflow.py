"""dslint v3 tests: the CFG + dataflow core and the flow-sensitive
rules DS016–DS018.

Same three-layer shape as tests/test_dslint_interproc.py:
  1. dataflow machinery — CFG construction units (if/else, while,
     for-else, try/except/finally, early return), gen/kill fixpoint
     convergence on loops, interprocedural pair summaries, and the
     hash-keyed import-graph cache invalidation;
  2. per-rule fixtures — for each of DS016–DS018 at least one
     true-positive package that MUST flag and one clean twin that MUST
     NOT;
  3. regressions + self-scan — the real findings the rules' PR fixed
     stay fixed, and the whole tree lints clean under DS016–DS018 in
     under 15s.
"""

import ast
import json
import subprocess
import sys
import textwrap

from tools.dslint import build_symbol_table
from tools.dslint.core import REPO_ROOT, analyze_package, link_parents
from tools.dslint.dataflow import (DEFAULT_PAIRS, EXC, GenKill,
                                   ResourcePairing,
                                   SnapshotRoundTrip, TracedValueEscape,
                                   build_cfg, build_pair_summaries,
                                   dataflow_rules, solve_forward,
                                   summarize_pairs)
from tools.dslint.symbols import (cache_input_hashes, closure_of,
                                  load_callgraph_cache,
                                  write_callgraph_cache)


def fn_cfg(src):
    tree = ast.parse(textwrap.dedent(src))
    return build_cfg(tree.body[0])


def block_of(cfg, lineno):
    """The block whose statement list carries the stmt at ``lineno``."""
    for b in cfg.blocks:
        for s in b.stmts:
            if getattr(s, "lineno", None) == lineno:
                return b
    raise AssertionError(f"no block holds line {lineno}")


def table_of(files):
    parsed = []
    for path, src in files.items():
        tree = ast.parse(textwrap.dedent(src))
        link_parents(tree)
        parsed.append((path, tree, src.splitlines()))
    return build_symbol_table(parsed)


def rule_hits(rule, files, **kw):
    return rule.check_package(table_of(files), **kw)


# ---------------------------------------------------------------------------
# CFG construction units
# ---------------------------------------------------------------------------

def test_cfg_if_else_branches_and_merge():
    cfg = fn_cfg("""\
        def f(a):
            if a:
                x = 1
            else:
                x = 2
            return x
    """)
    header = block_of(cfg, 2)
    then_b, else_b = block_of(cfg, 3), block_of(cfg, 5)
    assert then_b in header.succ and else_b in header.succ
    merge = block_of(cfg, 6)
    assert merge in then_b.succ and merge in else_b.succ
    # the return reaches the exit
    assert cfg.exit in merge.succ


def test_cfg_if_without_else_falls_through():
    cfg = fn_cfg("""\
        def f(a):
            if a:
                x = 1
            return a
    """)
    header = block_of(cfg, 2)
    after = block_of(cfg, 4)
    # both the taken and the skipped branch reach the merge
    assert after in header.succ
    assert after in block_of(cfg, 3).succ


def test_cfg_while_has_back_edge_and_exit():
    cfg = fn_cfg("""\
        def f(a):
            while a:
                a = a - 1
            return a
    """)
    header = block_of(cfg, 2)
    body = block_of(cfg, 3)
    assert body in header.succ
    assert header in body.succ          # back edge
    assert block_of(cfg, 4) in header.succ


def test_cfg_for_else_runs_on_normal_exit_break_skips_it():
    cfg = fn_cfg("""\
        def f(items):
            for i in items:
                if i:
                    break
            else:
                x = 1
            return 0
    """)
    header = block_of(cfg, 2)
    else_b = block_of(cfg, 6)
    brk = block_of(cfg, 4)
    after = block_of(cfg, 7)
    assert else_b in header.succ        # normal loop exit -> else
    assert after not in header.succ     # ...and ONLY via the else
    assert after in brk.succ            # break jumps past the else
    assert after in else_b.succ


def test_cfg_try_except_finally_edges():
    cfg = fn_cfg("""\
        def f(a):
            try:
                risky(a)
            except ValueError:
                handled(a)
            finally:
                cleanup(a)
            return a
    """)
    body = block_of(cfg, 3)
    handler = block_of(cfg, 5)
    fin = block_of(cfg, 7)
    after = block_of(cfg, 8)
    # the try-body statement may jump to the handler — exceptionally
    assert handler in body.succ and body.succ[handler] == EXC
    # both the normal path and the handler drain through the finally
    assert fin in handler.succ
    assert any(fin in b.succ for b in cfg.blocks
               if b not in (handler, fin))
    assert after in fin.succ
    # an in-flight exception continues past the finally to the exit
    assert cfg.exit in fin.succ and fin.succ[cfg.exit] == EXC


def test_cfg_return_routes_through_finally():
    cfg = fn_cfg("""\
        def f(a):
            try:
                return 1
            finally:
                cleanup(a)
    """)
    ret = block_of(cfg, 3)
    fin = block_of(cfg, 5)
    assert fin in ret.succ              # return runs the finally first


def test_cfg_early_return_leaves_dead_code_unreachable():
    cfg = fn_cfg("""\
        def f(a):
            return a
            x = 1
    """)
    assert cfg.exit in block_of(cfg, 2).succ
    dead = block_of(cfg, 3)
    assert not dead.pred                # island: nothing flows in


def test_cfg_raise_targets_enclosing_handler():
    cfg = fn_cfg("""\
        def f(a):
            try:
                raise ValueError(a)
            except ValueError:
                return 0
    """)
    rais = block_of(cfg, 3)
    handler = block_of(cfg, 5)
    assert handler in rais.succ and rais.succ[handler] == EXC


# ---------------------------------------------------------------------------
# forward solver: gen/kill convergence on loops
# ---------------------------------------------------------------------------

class _Defined(GenKill):
    """Toy may-analysis: names assigned so far."""

    def gen(self, stmt, fact):
        if isinstance(stmt, ast.Assign):
            return {t.id for t in stmt.targets if isinstance(t, ast.Name)}
        return ()


def test_genkill_fixpoint_converges_on_loop():
    cfg = fn_cfg("""\
        def f(a):
            x = 1
            while a:
                y = x
                x = y + 1
            return x
    """)
    in_facts, out_facts = solve_forward(cfg, _Defined())
    # the loop body's facts include its own contribution via the back
    # edge — the fixpoint, not the first pass
    header = block_of(cfg, 3)
    assert {"x", "y"} <= in_facts[header]
    assert {"x", "y"} <= out_facts[cfg.exit] or \
        {"x", "y"} <= in_facts[cfg.exit]


def test_genkill_kill_removes_fact():
    class Tracked(GenKill):
        def gen(self, stmt, fact):
            if isinstance(stmt, ast.Assign) and isinstance(
                    stmt.value, ast.Constant):
                return {t.id for t in stmt.targets
                        if isinstance(t, ast.Name)}
            return ()

        def kill(self, stmt, fact):
            if isinstance(stmt, ast.Delete):
                return {t.id for t in stmt.targets
                        if isinstance(t, ast.Name)}
            return ()

    cfg = fn_cfg("""\
        def f():
            x = 1
            del x
            return 0
    """)
    _, out_facts = solve_forward(cfg, Tracked())
    assert "x" not in out_facts[cfg.exit]


# ---------------------------------------------------------------------------
# interprocedural pair summaries
# ---------------------------------------------------------------------------

def test_summarize_pairs_counts_sites():
    fn = ast.parse(textwrap.dedent("""\
        def admit(self, rid):
            a = self.cache.allocate(rid, 1)
            b = self.cache.allocate(rid, 2)
            self.cache.free(a)
            row = self.pool.acquire(rid)
            return b, row
    """)).body[0]
    s = summarize_pairs(fn, DEFAULT_PAIRS)
    assert s.acquires["cache-block"] == 2
    assert s.releases["cache-block"] == 1
    assert s.acquires["adapter"] == 1
    assert "adapter" not in s.releases


def test_build_pair_summaries_indexes_by_path_and_name():
    table = table_of({"deepspeed_tpu/a.py": """\
        def take(pool, x):
            h = pool.acquire(x)
            return h


        def give(pool, h):
            pool.release(h)
    """})
    summaries = build_pair_summaries(table)
    assert summaries[("deepspeed_tpu/a.py", "take")].acquires == \
        {"adapter": 1}
    assert summaries[("deepspeed_tpu/a.py", "give")].releases == \
        {"adapter": 1}


# ---------------------------------------------------------------------------
# import-graph cache: content-hash invalidation (satellite)
# ---------------------------------------------------------------------------

_FAKE_INPUTS = {"jit_registry": "aaa", "telemetry_schema": "bbb"}


def _cache_table():
    return table_of({
        "deepspeed_tpu/a.py": "from deepspeed_tpu import b\n",
        "deepspeed_tpu/b.py": "x = 1\n"})


def test_callgraph_cache_round_trips_with_matching_inputs(tmp_path):
    p = tmp_path / "cache.json"
    write_callgraph_cache(_cache_table(), path=p, inputs=_FAKE_INPUTS)
    imports = load_callgraph_cache(p, inputs=_FAKE_INPUTS)
    assert imports                      # hit
    assert closure_of(["deepspeed_tpu/b.py"], imports) == [
        "deepspeed_tpu/a.py", "deepspeed_tpu/b.py"]


def test_callgraph_cache_misses_when_inputs_change(tmp_path):
    p = tmp_path / "cache.json"
    write_callgraph_cache(_cache_table(), path=p, inputs=_FAKE_INPUTS)
    edited = dict(_FAKE_INPUTS, jit_registry="DIFFERENT")
    assert load_callgraph_cache(p, inputs=edited) == {}


def test_callgraph_cache_v1_format_is_stale(tmp_path):
    p = tmp_path / "cache.json"
    p.write_text(json.dumps(
        {"version": 1, "imports": {"a.py": ["b.py"]}}))
    assert load_callgraph_cache(p, inputs=_FAKE_INPUTS) == {}


def test_editing_the_registry_changes_the_cache_key(tmp_path):
    """The satellite's contract end to end: edit jit_registry.py →
    the input hash changes → a cache written before the edit misses."""
    reg = tmp_path / "jit_registry.py"
    reg.write_text((REPO_ROOT / "deepspeed_tpu" / "utils"
                    / "jit_registry.py").read_text())
    files = (("jit_registry", reg),)
    before = cache_input_hashes(files)
    p = tmp_path / "cache.json"
    write_callgraph_cache(_cache_table(), path=p, inputs=before)
    assert load_callgraph_cache(p, inputs=cache_input_hashes(files))

    reg.write_text(reg.read_text()
                   + "\nJIT_WRAPPER_CHAINS += ((\"xjit\",),)\n")
    after = cache_input_hashes(files)
    assert after != before
    assert load_callgraph_cache(p, inputs=after) == {}


# ---------------------------------------------------------------------------
# DS016: resource pairing
# ---------------------------------------------------------------------------

def test_ds016_early_return_leak_flags():
    files = {"deepspeed_tpu/inference/serving.py": """\
        class S:
            def admit(self, rid):
                slot = self.cache.allocate(rid)
                if self.full:
                    return None
                self.cache.free(slot)
                return rid
    """}
    hits = rule_hits(ResourcePairing(), files, partial=True)
    assert len(hits) == 1
    assert hits[0].rule == "DS016"
    assert "`slot`" in hits[0].message and "every path" in hits[0].message


def test_ds016_exception_edge_leak_flags():
    files = {"deepspeed_tpu/inference/serving.py": """\
        class S:
            def admit(self, rid):
                slot = self.cache.allocate(rid)
                try:
                    self.do_setup(rid)
                except ValueError:
                    raise
                self.cache.free(slot)
                return rid
    """}
    hits = rule_hits(ResourcePairing(), files, partial=True)
    assert len(hits) == 1
    assert "exception edge" in hits[0].message


def test_ds016_try_finally_release_is_clean():
    files = {"deepspeed_tpu/inference/serving.py": """\
        class S:
            def admit(self, rid):
                slot = self.cache.allocate(rid)
                try:
                    self.do_setup(rid)
                finally:
                    self.cache.free(slot)
                return rid
    """}
    assert rule_hits(ResourcePairing(), files, partial=True) == []


def test_ds016_escaped_handle_is_someone_elses_balance():
    files = {"deepspeed_tpu/inference/serving.py": """\
        class S:
            def admit(self, rid):
                slot = self.cache.allocate(rid)
                self.slots[rid] = slot
                return rid

            def retire(self, rid):
                self.cache.free(self.slots.pop(rid))
    """}
    assert rule_hits(ResourcePairing(), files, partial=True) == []


def test_ds016_double_release_on_some_path_flags():
    files = {"deepspeed_tpu/inference/serving.py": """\
        class S:
            def drop(self, rid):
                slot = self.cache.allocate(rid)
                if self.fancy:
                    self.cache.free(slot)
                self.cache.free(slot)
    """}
    hits = rule_hits(ResourcePairing(), files, partial=True)
    assert len(hits) == 1
    assert "double release" in hits[0].message


def test_ds016_branch_exclusive_release_is_clean():
    files = {"deepspeed_tpu/inference/serving.py": """\
        class S:
            def drop(self, rid):
                slot = self.cache.allocate(rid)
                if self.fancy:
                    self.cache.free(slot)
                else:
                    self.cache.free(slot)
    """}
    assert rule_hits(ResourcePairing(), files, partial=True) == []


def test_ds016_package_wide_unbalanced_kind_flags_only_full_tree():
    files = {"deepspeed_tpu/inference/serving.py": """\
        class S:
            def admit(self, rid):
                row = self.pool.acquire(rid)
                self.rows[rid] = row
                return rid
    """}
    full = rule_hits(ResourcePairing(), files)
    assert len(full) == 1
    assert "nothing under deepspeed_tpu/ ever releases" in full[0].message
    assert rule_hits(ResourcePairing(), files, partial=True) == []


# ---------------------------------------------------------------------------
# DS017: traced-value escape
# ---------------------------------------------------------------------------

def test_ds017_branch_on_derived_value_flags():
    files = {"deepspeed_tpu/ops/f.py": """\
        import jax
        from functools import partial


        @partial(jax.jit)
        def f(x):
            y = x * 2
            flag = y.sum()
            if flag > 0:
                return y
            return -y
    """}
    hits = rule_hits(TracedValueEscape(), files)
    assert len(hits) == 1
    assert hits[0].rule == "DS017"
    assert "assignment chain" in hits[0].message


def test_ds017_direct_param_branch_is_ds004s_finding_not_ours():
    files = {"deepspeed_tpu/ops/f.py": """\
        import jax
        from functools import partial


        @partial(jax.jit)
        def f(x):
            if x > 0:
                return x
            return -x
    """}
    assert rule_hits(TracedValueEscape(), files) == []


def test_ds017_metadata_chain_launders_taint():
    files = {"deepspeed_tpu/ops/f.py": """\
        import jax
        from functools import partial


        @partial(jax.jit)
        def f(x):
            s = x.shape
            if s[0] > 4:
                return x * 2
            return x
    """}
    assert rule_hits(TracedValueEscape(), files) == []


def test_ds017_host_sync_on_derived_value_flags():
    files = {"deepspeed_tpu/ops/f.py": """\
        import jax
        from functools import partial


        @partial(jax.jit)
        def f(x):
            acc = 0
            for i in range(3):
                acc = acc + x
            v = float(acc)
            return v
    """}
    hits = rule_hits(TracedValueEscape(), files)
    assert len(hits) == 1
    assert "host sync" in hits[0].message


def test_ds017_dict_key_from_traced_value_flags():
    files = {"deepspeed_tpu/ops/f.py": """\
        import jax
        from functools import partial


        @partial(jax.jit)
        def f(x):
            k = x + 1
            d = {k: 1}
            return d
    """}
    hits = rule_hits(TracedValueEscape(), files)
    assert len(hits) == 1
    assert "dict key" in hits[0].message


def test_ds017_static_args_stay_host_values():
    files = {"deepspeed_tpu/ops/f.py": """\
        import jax

        def _f(x, mode):
            m = mode + "x"
            if m == "ax":
                return x * 2
            return x

        f = jax.jit(_f, static_argnames=("mode",))
    """}
    assert rule_hits(TracedValueEscape(), files) == []


# ---------------------------------------------------------------------------
# DS018: snapshot round-trip completeness
# ---------------------------------------------------------------------------

_REQ_MOD = """\
    from dataclasses import dataclass

    {allow}

    @dataclass
    class Req:
        rid: str
        out: list = None
        retries: int = 0

        @classmethod
        def from_snapshot(cls, entry):
            return cls(rid=entry["rid"], out=list(entry["out"]))


    def snapshot_entry(req):
        return {{"rid": req.rid, "out": list(req.out)}}
"""


def test_ds018_unserialized_field_flags():
    files = {"deepspeed_tpu/inference/serving.py":
             _REQ_MOD.format(allow="")}
    hits = rule_hits(SnapshotRoundTrip(), files, partial=True)
    assert len(hits) == 1
    assert "`retries`" in hits[0].message
    assert "never serialized" in hits[0].message


def test_ds018_ephemeral_allowlist_silences():
    files = {"deepspeed_tpu/inference/serving.py": _REQ_MOD.format(
        allow='SNAPSHOT_EPHEMERAL = frozenset({"retries"})')}
    assert rule_hits(SnapshotRoundTrip(), files, partial=True) == []


def test_ds018_serialized_but_not_restored_flags():
    files = {"deepspeed_tpu/inference/serving.py": """\
        from dataclasses import dataclass

        @dataclass
        class Req:
            rid: str
            state: str = "queued"

            @classmethod
            def from_snapshot(cls, entry):
                return cls(rid=entry["rid"], state="queued")


        def snapshot_entry(req):
            return {"rid": req.rid, "state": req.state}
    """}
    hits = rule_hits(SnapshotRoundTrip(), files, partial=True)
    assert len(hits) == 1
    assert "never restored" in hits[0].message


def test_ds018_stale_allowlist_entry_flags_on_full_tree_only():
    files = {"deepspeed_tpu/inference/serving.py": _REQ_MOD.format(
        allow='SNAPSHOT_EPHEMERAL = frozenset({"retries", "ghost"})')}
    full = rule_hits(SnapshotRoundTrip(), files)
    assert len(full) == 1 and "`ghost`" in full[0].message
    assert rule_hits(SnapshotRoundTrip(), files, partial=True) == []


def test_ds018_module_without_snapshot_contract_is_ignored():
    files = {"deepspeed_tpu/inference/other.py": """\
        from dataclasses import dataclass

        @dataclass
        class Plain:
            a: int = 0
    """}
    assert rule_hits(SnapshotRoundTrip(), files) == []


# ---------------------------------------------------------------------------
# regressions: the real findings the rules' PR fixed stay fixed
# ---------------------------------------------------------------------------

def test_serving_snapshot_ephemeral_matches_request_fields():
    """The DS018 allowlist only names real ServeRequest fields (the
    stale-entry direction of the rule, pinned as a plain test too)."""
    from deepspeed_tpu.inference.serving import (SNAPSHOT_EPHEMERAL,
                                                 ServeRequest)
    fields = set(ServeRequest.__dataclass_fields__)
    assert SNAPSHOT_EPHEMERAL <= fields
    # and every non-ephemeral field is in the snapshot dict's keys
    import inspect
    from deepspeed_tpu.inference import serving
    src = inspect.getsource(serving.snapshot_entry)
    for name in fields - SNAPSHOT_EPHEMERAL:
        assert f'"{name}"' in src, name


# ---------------------------------------------------------------------------
# CLI / SARIF integration
# ---------------------------------------------------------------------------

def test_cli_explain_prints_doc_and_example():
    r = subprocess.run(
        [sys.executable, "-m", "tools.dslint", "--explain", "DS016"],
        capture_output=True, text=True, cwd=REPO_ROOT)
    assert r.returncode == 0
    assert "DS016" in r.stdout and "resource-pairing" in r.stdout
    assert "minimal true positive" in r.stdout
    bad = subprocess.run(
        [sys.executable, "-m", "tools.dslint", "--explain", "DS099"],
        capture_output=True, text=True, cwd=REPO_ROOT)
    assert bad.returncode == 2


def test_explain_covers_every_rule():
    from tools.dslint.explain import EXAMPLES, explain
    from tools.dslint.interproc import interproc_catalog
    from tools.dslint.rules import rule_catalog
    for r in rule_catalog() + interproc_catalog():
        assert r["id"] in EXAMPLES
        assert explain(r["id"])


def test_sarif_rules_carry_lintmd_help_anchors():
    from tools.dslint.sarif import to_sarif
    log = to_sarif([], [])
    rules = log["runs"][0]["tool"]["driver"]["rules"]
    by_id = {r["id"]: r for r in rules}
    assert by_id["DS016"]["helpUri"].endswith(
        "#the-flow-sensitive-rules-phase-3")
    assert by_id["DS011"]["helpUri"].endswith(
        "#the-interprocedural-rules-phase-2")
    assert by_id["DS001"]["helpUri"].endswith("#the-rules")
    assert {"DS016", "DS017", "DS018"} <= set(by_id)


# ---------------------------------------------------------------------------
# self-scan: the whole tree lints clean under DS016–DS018, fast
# ---------------------------------------------------------------------------

def test_v3_self_scan_clean_and_under_budget():
    stats = {}
    findings = analyze_package(
        [str(REPO_ROOT / "deepspeed_tpu"), str(REPO_ROOT / "tools"),
         str(REPO_ROOT / "tests")],
        rules=[], interproc=dataflow_rules(), stats=stats)
    assert findings == [], "\n".join(f.format() for f in findings)
    assert stats["total_s"] < 15.0, stats
