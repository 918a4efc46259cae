"""``python -m tools.dslint [paths...]`` — run the JAX-aware lint.

Exit code 0 when every finding is fixed, suppressed inline, or in the
baseline; 1 otherwise. ``--update-baseline`` rewrites the checked-in
baseline from the current tree (visible debt, non-blocking).

Two phases: the per-file rules (DS001–DS010) and the package-wide
rules over a shared symbol table — interprocedural (DS011–DS014) and
flow-sensitive dataflow (DS016–DS018). ``--closure`` switches to quick
mode: the positional paths are treated as *changed files* and the lint
runs over them plus their direct importers (from the cached import
graph), with the whole-tree completeness checks disabled; the cache
key includes the content hashes of jit_registry.py and
telemetry_schema.json, so editing either forces a full re-analysis.
``--sarif PATH`` additionally writes a SARIF 2.1.0 log.
``--explain DS0NN`` prints one rule's doc + a minimal true positive.
"""

import argparse
import sys

from tools.dslint.core import (DEFAULT_BASELINE, analyze_package,
                               apply_baseline, findings_to_json,
                               load_baseline, write_baseline)
from tools.dslint.interproc import interproc_catalog, interproc_rules
from tools.dslint.rules import default_rules, rule_catalog


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m tools.dslint",
        description="JAX/TPU-aware static analysis (rules DS001-DS018; "
                    "see docs/LINT.md)")
    ap.add_argument("paths", nargs="*", default=["deepspeed_tpu", "tools"],
                    help="files or directories (default: deepspeed_tpu "
                         "tools); with --closure: the changed files")
    ap.add_argument("--format", choices=("text", "json"), default="text")
    ap.add_argument("--baseline", default=str(DEFAULT_BASELINE),
                    help="baseline file (default: tools/dslint/"
                         "baseline.json)")
    ap.add_argument("--no-baseline", action="store_true",
                    help="report baselined findings as failures too")
    ap.add_argument("--update-baseline", action="store_true",
                    help="rewrite the baseline from the current findings "
                         "and exit 0")
    ap.add_argument("--rules", default=None,
                    help="comma-separated rule IDs to run (default: all)")
    ap.add_argument("--list-rules", action="store_true")
    ap.add_argument("--explain", metavar="DS0NN", default=None,
                    help="print one rule's doc + a minimal true-positive "
                         "example, then exit")
    ap.add_argument("--show-baselined", action="store_true",
                    help="also print baselined findings in text mode")
    ap.add_argument("--sarif", metavar="PATH", default=None,
                    help="also write a SARIF 2.1.0 log to PATH")
    ap.add_argument("--stats", action="store_true",
                    help="print per-phase timing to stderr")
    ap.add_argument("--closure", action="store_true",
                    help="quick mode: lint the given changed files plus "
                         "their direct importers (cached import graph); "
                         "whole-tree completeness checks are skipped")
    args = ap.parse_args(argv)

    if args.explain:
        from tools.dslint.explain import explain
        text = explain(args.explain)
        if text is None:
            print(f"no such rule: {args.explain}", file=sys.stderr)
            return 2
        print(text)
        return 0

    if args.list_rules:
        for r in rule_catalog() + interproc_catalog():
            fix = " [autofixable]" if r["autofixable"] else ""
            print(f"{r['id']} {r['name']}{fix}\n    {r['rationale']}")
        return 0

    rules = default_rules()
    inter = interproc_rules()
    if args.rules:
        wanted = {r.strip().upper() for r in args.rules.split(",")}
        rules = [r for r in rules if r.id in wanted]
        inter = [r for r in inter if r.id in wanted]
        if not rules and not inter:
            print(f"no such rules: {args.rules}", file=sys.stderr)
            return 2

    paths = args.paths or ["deepspeed_tpu", "tools"]
    if args.closure:
        from tools.dslint.symbols import closure_of, load_callgraph_cache
        from tools.dslint.core import REPO_ROOT, _norm_path
        changed = [_norm_path(p) for p in paths if p.endswith(".py")]
        imports = load_callgraph_cache()
        if not imports:
            # no cache yet (first run): fall back to a full-tree pass,
            # which also writes the cache for next time
            args.closure = False
            paths = ["deepspeed_tpu", "tools", "tests"]
        else:
            paths = [str(REPO_ROOT / p)
                     for p in closure_of(changed, imports)]
            if not paths:
                print("dslint: no python files in closure")
                return 0

    # the completeness directions ("declared but never fired", "in the
    # schema but registered by no code") only hold over the whole tree:
    # run them when the package root is in scope, not on a targeted
    # file/subdir lint (where absence just means "not analyzed")
    from pathlib import Path as _P
    from tools.dslint.core import REPO_ROOT as _ROOT
    pkg_root = (_ROOT / "deepspeed_tpu").resolve()
    partial = args.closure or not any(
        _P(p).resolve() == pkg_root for p in paths)

    stats = {}
    symtab_out = []
    findings = analyze_package(
        paths, rules=rules, interproc=inter, partial=partial,
        stats=stats, symtab_out=symtab_out)

    if not partial and symtab_out:
        # full-tree pass: refresh the import-graph cache quick mode uses
        from tools.dslint.symbols import write_callgraph_cache
        try:
            write_callgraph_cache(symtab_out[0])
        except OSError:
            pass

    if args.stats:
        print("dslint: {files:.0f} files, parse {parse_s:.2f}s, "
              "intraproc {intraproc_s:.2f}s, interproc {interproc_s:.2f}s,"
              " total {total_s:.2f}s".format(**stats), file=sys.stderr)

    if args.update_baseline:
        out = write_baseline(findings, args.baseline)
        print(f"dslint: baseline written to {out} "
              f"({len(findings)} entries)")
        return 0

    baseline = load_baseline(args.baseline) if not args.no_baseline else {}
    new, baselined = apply_baseline(findings, baseline)

    if args.sarif:
        from tools.dslint.sarif import write_sarif
        write_sarif(args.sarif, new, baselined,
                    rules=rule_catalog() + interproc_catalog())

    if args.format == "json":
        print(findings_to_json(new, baselined))
    else:
        for f in new:
            print(f.format())
            if f.snippet:
                print(f"    {f.snippet}")
        if args.show_baselined:
            for f in baselined:
                print(f.format())
        n_files = len({f.path for f in new})
        print(f"dslint: {len(new)} finding(s) in {n_files} file(s), "
              f"{len(baselined)} baselined")
    return 1 if new else 0


if __name__ == "__main__":
    sys.exit(main())
