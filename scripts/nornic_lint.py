"""nornic-lint CLI: the AST-driven invariant suite gating tier-1.

Five passes over the whole package (see nornicdb_tpu/lint/ and
docs/static_analysis.md): jit-hygiene, lock-discipline,
degrade-contract, env-knob-catalog, metrics-catalog. Grandfathered
findings live in the committed baseline
(scripts/nornic_lint_baseline.json); anything not baselined fails the
run — and the default pytest suite (tests/test_lint.py) runs this
tool, so a PR introducing a violation fails tier-1.

Usage:
    python scripts/nornic_lint.py                    # human output, exit 1 on fresh findings
    python scripts/nornic_lint.py --json             # one JSON verdict line
    python scripts/nornic_lint.py --list-passes      # pass catalog
    python scripts/nornic_lint.py --passes lock-discipline,jit-hygiene
    python scripts/nornic_lint.py --update-baseline  # regenerate the baseline
    python scripts/nornic_lint.py --write-env-catalog  # regenerate docs/configuration.md block
"""

from __future__ import annotations

import argparse
import json
import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

from nornicdb_tpu import lint  # noqa: E402
from nornicdb_tpu.lint import astutil, env_catalog  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__.splitlines()[0])
    ap.add_argument("--root", default=_REPO,
                    help="repo root (default: this checkout)")
    ap.add_argument("--passes", default=None,
                    help="comma-separated subset of passes to run")
    ap.add_argument("--baseline", default=None,
                    help="baseline path (default: "
                         "scripts/nornic_lint_baseline.json)")
    ap.add_argument("--json", action="store_true",
                    help="one JSON verdict line")
    ap.add_argument("--list-passes", action="store_true",
                    help="print the pass catalog and exit")
    ap.add_argument("--update-baseline", action="store_true",
                    help="write the current findings as the baseline")
    ap.add_argument("--write-env-catalog", action="store_true",
                    help="regenerate the generated env-knob block in "
                         "docs/configuration.md and exit")
    ap.add_argument("--no-baseline", action="store_true",
                    help="report every finding, baseline ignored")
    args = ap.parse_args(argv)

    if args.list_passes:
        table = lint.pass_descriptions()
        if args.json:
            print(json.dumps(table))
        else:
            for name, desc in table.items():
                print(f"{name:18s} {desc}")
        return 0

    root = os.path.abspath(args.root)
    tree = astutil.load_package(root)

    if args.write_env_catalog:
        doc_path = os.path.join(root, env_catalog.DOC_REL)
        env_catalog.write_catalog(tree, doc_path)
        print(f"wrote env-knob catalog block to "
              f"{os.path.relpath(doc_path, root)}")
        return 0

    passes = [p.strip() for p in args.passes.split(",")] \
        if args.passes else None
    findings = lint.run_passes(root, passes=passes, tree=tree)

    baseline_path = args.baseline or os.path.join(
        root, lint.DEFAULT_BASELINE)
    if args.update_baseline:
        keep = {}
        if passes is not None and set(passes) != set(lint.pass_names()):
            # subset run: rewrite only the selected passes' entries —
            # dropping the others' grandfathered fingerprints here
            # would make the next full run fail on them as fresh
            keep = {fp: n for fp, n
                    in lint.load_baseline(baseline_path).items()
                    if fp.split("|", 1)[0] not in set(passes)}
        data = lint.save_baseline(baseline_path, findings, extra=keep)
        print(f"baseline: {len(findings)} findings "
              f"({len(data['findings'])} fingerprints, "
              f"{len(keep)} kept from other passes) -> "
              f"{os.path.relpath(baseline_path, root)}")
        return 0

    baseline = {} if args.no_baseline \
        else lint.load_baseline(baseline_path)
    fresh = lint.apply_baseline(findings, baseline)

    per_pass = {}
    run_names = passes or lint.pass_names()
    for name in run_names:
        total = sum(1 for f in findings if f.pass_name == name)
        fr = sum(1 for f in fresh if f.pass_name == name)
        per_pass[name] = {"findings": total, "baselined": total - fr,
                          "fresh": fr}

    verdict = {
        "nornic_lint": True,
        "verdict": "violations" if fresh else "pass",
        "files": len(tree.modules),
        "baseline": os.path.relpath(baseline_path, root),
        "passes": per_pass,
        "total": len(findings),
        "fresh_total": len(fresh),
        "fresh": [f.to_dict() for f in fresh],
    }
    if args.json:
        print(json.dumps(verdict))
    else:
        for f in fresh:
            print(f.render())
        base_n = len(findings) - len(fresh)
        print(f"nornic-lint: {len(tree.modules)} files, "
              f"{len(findings)} findings "
              f"({base_n} baselined, {len(fresh)} fresh) -> "
              f"{verdict['verdict']}")
    return 1 if fresh else 0


if __name__ == "__main__":
    sys.exit(main())
