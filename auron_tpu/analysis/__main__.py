"""CLI: ``python -m auron_tpu.analysis``.

Exit codes: 0 = clean (no unbaselined violations, no parse errors),
1 = violations, 2 = usage/environment error (missing/garbage baseline).

    # the CI gate (what tests/test_zz_lint_gate.py runs)
    python -m auron_tpu.analysis --baseline tools/lint_baseline.json

    # freeze the current violation set (shrinking it is always safe;
    # growing it is a review conversation)
    python -m auron_tpu.analysis --update-baseline

    # machine-readable report (tools/lint_report.py input)
    python -m auron_tpu.analysis --baseline tools/lint_baseline.json --json
"""

from __future__ import annotations

import argparse
import json
import os
import sys

from auron_tpu.analysis import core


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m auron_tpu.analysis", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("paths", nargs="*",
                    help="files/directories to analyze (default: the "
                         "repo tree — auron_tpu/, tools/, bench.py, "
                         "chip_smoke.py)")
    ap.add_argument("--baseline", default=None,
                    help="baseline JSON of grandfathered violations; "
                         "only NEW violations fail the run")
    ap.add_argument("--update-baseline", action="store_true",
                    help="write the current violation set to the "
                         "baseline path (default tools/lint_baseline."
                         "json) and exit 0")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="emit the machine-readable report to stdout")
    ap.add_argument("--rules", default=None,
                    help="comma-separated rule-id subset (debugging)")
    ap.add_argument("--root", default=None,
                    help="repo root for relative paths / directory-"
                         "scoped rules (default: this checkout)")
    args = ap.parse_args(argv)

    root = os.path.abspath(args.root) if args.root else core.repo_root()
    targets = args.paths or None
    rule_ids = (args.rules.split(",") if args.rules else None)

    if args.update_baseline and (args.rules
                                 or (args.paths and not args.root)):
        # a subset run must never overwrite the whole-tree baseline:
        # freezing only GL007's (or one directory's) violations would
        # silently discard every other rule's frozen entries and the
        # next full gate run would report them all as NEW
        print("graftlint: refusing --update-baseline with --rules or "
              "explicit paths — the baseline freezes the WHOLE tree; "
              "run without a subset filter (paths are allowed together "
              "with --root for a self-contained tree)",
              file=sys.stderr)
        return 2

    result = core.analyze(targets, root=root, rule_ids=rule_ids)

    if args.update_baseline:
        path = args.baseline or core.default_baseline_path(root)
        data = core.save_baseline(path, result.violations)
        print(f"graftlint: baseline updated — {len(data['entries'])} "
              f"entries ({len(result.violations)} violations, "
              f"{result.suppressed} suppressed) -> {path}")
        return 0

    report = result.to_json()
    stale: list = []
    new = result.violations
    if args.baseline:
        try:
            baseline = core.load_baseline(args.baseline)
        except (OSError, ValueError, json.JSONDecodeError) as e:
            print(f"graftlint: cannot load baseline: {e}",
                  file=sys.stderr)
            return 2
        new, old, stale = core.apply_baseline(result.violations, baseline)
        report["violations"] = [v.to_json() for v in new]
        report["grandfathered"] = len(old)
        report["stale_baseline_entries"] = stale
    report["new_violations"] = len(new)
    report["ok"] = not new and not result.parse_errors

    if args.as_json:
        print(json.dumps(report, indent=1))
    else:
        for v in new:
            print(v.render())
        for rel, msg in result.parse_errors:
            print(f"{rel}:0: parse error: {msg}")
        counts = ", ".join(f"{k}={n}" for k, n in result.by_rule().items())
        print(f"graftlint: {result.files_scanned} files, "
              f"{len(result.violations)} violations"
              + (f" ({counts})" if counts else "")
              + f", {result.suppressed} suppressed"
              + (f", {report.get('grandfathered', 0)} baselined, "
                 f"{len(new)} NEW" if args.baseline else ""))
        if stale:
            print(f"graftlint: {len(stale)} stale baseline entries "
                  f"(fixed code — prune with --update-baseline)")
    return 0 if report["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
