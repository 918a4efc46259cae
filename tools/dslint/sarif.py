"""SARIF 2.1.0 emitter for dslint findings.

Emits the minimal valid static-analysis log CI viewers (GitHub code
scanning, VS Code SARIF viewer) consume: one run, one ``tool.driver``
carrying the rule catalog, one ``result`` per finding. New findings are
``error`` level; baselined ones are ``note`` (visible debt, non-
blocking). Paths are repo-root-relative with an ``originalUriBaseIds``
anchor so the log is portable across checkouts.
"""

import json
from typing import Dict, List, Optional, Sequence

from tools.dslint.core import REPO_ROOT, Finding

SARIF_VERSION = "2.1.0"
SARIF_SCHEMA = ("https://raw.githubusercontent.com/oasis-tcs/sarif-spec/"
                "master/Schemata/sarif-schema-2.1.0.json")


def _help_anchor(rule_id: str) -> str:
    """LINT.md section anchor for a rule id — SARIF viewers surface it
    as the rule's documentation link."""
    n = int(rule_id[2:])
    if n >= 16:
        return "#the-flow-sensitive-rules-phase-3"
    if n >= 11:
        return "#the-interprocedural-rules-phase-2"
    return "#the-rules"


def _rule_entry(rule: Dict[str, str]) -> Dict:
    return {
        "id": rule["id"],
        "name": rule["name"],
        "shortDescription": {"text": rule["name"]},
        "fullDescription": {"text": rule["rationale"]},
        "helpUri": ((REPO_ROOT / "docs" / "LINT.md").as_uri()
                    + _help_anchor(rule["id"])),
        "defaultConfiguration": {"level": "error"},
    }


def _result(f: Finding, rule_index: Dict[str, int]) -> Dict:
    res = {
        "ruleId": f.rule,
        "level": "note" if f.baselined else "error",
        "message": {"text": f.message},
        "locations": [{
            "physicalLocation": {
                "artifactLocation": {
                    "uri": f.path,
                    "uriBaseId": "REPO_ROOT",
                },
                "region": {
                    "startLine": max(1, int(f.line)),
                    "startColumn": max(1, int(f.col) + 1),
                },
            },
        }],
    }
    if f.rule in rule_index:
        res["ruleIndex"] = rule_index[f.rule]
    if f.snippet:
        loc = res["locations"][0]["physicalLocation"]
        loc["region"]["snippet"] = {"text": f.snippet}
    return res


def to_sarif(new: Sequence[Finding], baselined: Sequence[Finding] = (),
             rules: Optional[Sequence[Dict[str, str]]] = None) -> Dict:
    """The SARIF log as a plain dict; ``rules`` is the combined catalog
    (per-file + interprocedural) as produced by ``rule_catalog()`` /
    ``interproc_catalog()``."""
    if rules is None:
        from tools.dslint.interproc import interproc_catalog
        from tools.dslint.rules import rule_catalog
        rules = rule_catalog() + interproc_catalog()
    rule_index = {r["id"]: i for i, r in enumerate(rules)}
    return {
        "$schema": SARIF_SCHEMA,
        "version": SARIF_VERSION,
        "runs": [{
            "tool": {
                "driver": {
                    "name": "dslint",
                    "informationUri":
                        (REPO_ROOT / "docs" / "LINT.md").as_uri(),
                    "rules": [_rule_entry(r) for r in rules],
                },
            },
            "originalUriBaseIds": {
                "REPO_ROOT": {"uri": REPO_ROOT.as_uri() + "/"},
            },
            "results": ([_result(f, rule_index) for f in new]
                        + [_result(f, rule_index) for f in baselined]),
        }],
    }


def write_sarif(path, new: Sequence[Finding],
                baselined: Sequence[Finding] = (),
                rules: Optional[Sequence[Dict[str, str]]] = None) -> None:
    log = to_sarif(new, baselined, rules)
    with open(path, "w", encoding="utf-8") as fh:
        json.dump(log, fh, indent=1)
        fh.write("\n")
