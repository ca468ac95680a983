"""`colearn check` orchestration: run all three static analyzers on the
repo and fold their findings into one violations report (exit 1 names
each violation; ``--json`` for tooling). Pure host — validate() is a
plain function call; nothing initializes a jax backend or builds an
engine.
"""

from __future__ import annotations

import os
from typing import Any, Dict, List, Optional

# bump when an analyzer's rules or the matrix schema change
ANALYZER_VERSION = 2


def detect_root(root: Optional[str] = None) -> str:
    """Repo root = the directory holding the package directory (where
    capability_matrix.json and the docs live)."""
    if root:
        return os.path.abspath(root)
    import colearn_federated_learning_tpu as pkg

    return os.path.dirname(os.path.dirname(os.path.abspath(pkg.__file__)))


def run_check(root: Optional[str] = None,
              update_matrix: bool = False) -> Dict[str, Any]:
    """Run capability + seed-purity + schema analysis. Returns a report
    dict; ``report["violations"]`` empty means the repo is clean."""
    from colearn_federated_learning_tpu.analysis import (
        capability,
        schema,
        seed_purity,
    )

    root = detect_root(root)
    violations: List[Dict[str, Any]] = []

    if update_matrix:
        capability.write_matrix(root)
    cap = capability.check_capability(root)
    for v in cap["violations"]:
        violations.append(dict(v, analyzer="capability"))

    lint = seed_purity.lint_repo(root)
    for f in lint["violations"]:
        violations.append({
            "analyzer": "seed_purity",
            "kind": f["rule"],
            "where": f"{f['file']}:{f['line']}",
            "message": f"{f['symbol']} in {f['qualname']}: {f['detail']}",
        })
    for p in lint["allowlist_problems"]:
        e = p["entry"]
        violations.append({
            "analyzer": "seed_purity",
            "kind": p["kind"],
            "where": f"{e.get('file', '?')} ({e.get('qualname', '?')})",
            "message": f"allowlist entry {e.get('symbol', e.get('rule'))!r}: "
                       f"{p['kind'].replace('_', ' ')}",
        })

    sch = schema.check_schema(root)
    for v in sch["violations"]:
        violations.append(dict(v, analyzer="schema"))

    return {
        "analyzer_version": ANALYZER_VERSION,
        "root": root,
        "clean": not violations,
        "violations": violations,
        "capability": cap["counts"],
        "seed_purity": {
            "files_scanned": lint["files_scanned"],
            "findings": lint["findings"],
            "suppressed": lint["suppressed"],
        },
        "schema": {
            "registered_types": sch["registered_types"],
            "emit_sites": sch["emit_sites"],
            "emit_sites_resolved": sch["emit_sites_resolved"],
            "consumed_types": sch["consumed_types"],
            "consumed_fields": len(sch["consumed_fields"]),
        },
    }


def format_report(report: Dict[str, Any]) -> str:
    lines = [
        f"colearn check v{report['analyzer_version']} @ {report['root']}",
        f"capability: {report['capability']['features']} features, "
        f"{report['capability']['pairs']} pairings "
        f"({report['capability']['supported']} supported / "
        f"{report['capability']['rejected']} rejected)",
        f"seed purity: {report['seed_purity']['files_scanned']} files, "
        f"{report['seed_purity']['findings']} findings, "
        f"{report['seed_purity']['suppressed']} allowlisted",
        f"schema: {len(report['schema']['registered_types'])} record types, "
        f"{report['schema']['emit_sites']} emit sites "
        f"({report['schema']['emit_sites_resolved']} resolved), "
        f"{len(report['schema']['consumed_types'])} consumed types",
    ]
    if report["clean"]:
        lines.append("OK — no violations")
    else:
        lines.append(f"FAIL — {len(report['violations'])} violation(s):")
        for v in report["violations"]:
            lines.append(
                f"  [{v['analyzer']}/{v['kind']}] {v['where']}: "
                f"{v['message']}"
            )
    return "\n".join(lines)

