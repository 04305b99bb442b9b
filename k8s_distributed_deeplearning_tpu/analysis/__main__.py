"""graftlint CLI.

Usage::

    python -m k8s_distributed_deeplearning_tpu.analysis [paths...]
    graftlint [paths...] [--select=id,id] [--json] [--show-suppressed]
    graftlint --changed[=REF]      # only files touched vs REF (def. HEAD)
    graftlint --explain PASS       # a pass's checks/exemptions/token
    graftlint --list-passes

Exit codes (the contract ``tests/test_analysis.py`` pins):

- 0  no unsuppressed findings (suppressed ones are reported as a count)
- 1  at least one unsuppressed finding (each printed as
     ``path:line: [pass-id] severity: message (hint: ...)``)
- 2  usage error (unknown flag, unknown pass id, missing path)
"""
from __future__ import annotations

import argparse
import inspect
import json
import sys

from k8s_distributed_deeplearning_tpu import analysis


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(
        prog="graftlint",
        description="JAX-aware static analysis: recompile, collective-"
                    "mismatch, and cross-rank-divergence hazards.")
    parser.add_argument("paths", nargs="*",
                        help="files/directories to lint (default: the "
                             "package tree + examples/)")
    parser.add_argument("--select", default="",
                        help="comma-separated pass ids to run "
                             f"(default: all of {', '.join(analysis.PASS_IDS)})")
    parser.add_argument("--json", action="store_true", dest="as_json",
                        help="machine-readable output")
    parser.add_argument("--show-suppressed", action="store_true",
                        help="also print suppressed findings")
    parser.add_argument("--list-passes", action="store_true",
                        help="list pass ids and what they catch")
    parser.add_argument("--changed", nargs="?", const="HEAD", default=None,
                        metavar="REF",
                        help="lint only files changed vs git REF (default "
                             "HEAD: working tree + untracked), intersected "
                             "with the scan set; exit codes as in a full "
                             "run")
    parser.add_argument("--explain", default=None, metavar="PASS",
                        help="print one pass's checks, exemption rules, "
                             "and suppression token (from its docstring)")
    try:
        args = parser.parse_args(argv)
    except SystemExit as e:
        # argparse exits 2 on usage errors and 0 on --help; preserve both.
        return int(e.code or 0)

    if args.list_passes:
        for spec in analysis.PASSES:
            print(f"{spec.id:18s} {spec.doc}")
        return 0

    if args.explain is not None:
        spec = next((s for s in analysis.PASSES if s.id == args.explain),
                    None)
        if spec is None:
            print(f"graftlint: unknown pass {args.explain!r} "
                  f"(known: {', '.join(analysis.PASS_IDS)})",
                  file=sys.stderr)
            return 2
        print(f"{spec.id} — {spec.doc}")
        print()
        print(inspect.getdoc(spec.fn) or "(no docstring)")
        print()
        print(f"suppress with: # graftlint: disable={spec.id}")
        return 0

    select = tuple(s.strip() for s in args.select.split(",") if s.strip())
    import os
    for p in args.paths:
        if not os.path.exists(p):
            print(f"graftlint: no such path: {p}", file=sys.stderr)
            return 2
    run_paths = args.paths or None
    changed = None
    if args.changed is not None:
        try:
            changed = set(analysis.changed_paths(args.changed, run_paths))
        except RuntimeError as e:
            print(f"graftlint: {e}", file=sys.stderr)
            return 2
    try:
        if changed is not None and not changed:
            # Nothing in the scan set changed — trivially clean, same
            # output/exit contract as an empty full run.
            report = analysis.Report(findings=(), suppressed=())
        else:
            report = analysis.run(run_paths, select=select or None)
    except ValueError as e:
        print(f"graftlint: {e}", file=sys.stderr)
        return 2
    if changed:
        # The whole scan set is read (the registry passes hold contracts
        # that span files: a changed events.py is judged against every
        # emit site, not only the changed ones); findings are reported for
        # the changed files alone.
        report = analysis.Report(
            findings=tuple(f for f in report.findings
                           if os.path.abspath(f.path) in changed),
            suppressed=tuple(f for f in report.suppressed
                             if os.path.abspath(f.path) in changed))

    if args.as_json:
        print(json.dumps({
            "findings": [f.to_dict() for f in report.findings],
            "suppressed": [f.to_dict() for f in report.suppressed],
        }, indent=2))
    else:
        for f in report.findings:
            print(f.format())
        if args.show_suppressed:
            for f in report.suppressed:
                print(f"[suppressed] {f.format()}")
        n, s = len(report.findings), len(report.suppressed)
        print(f"graftlint: {n} finding{'s' if n != 1 else ''} "
              f"({s} suppressed)")
    return 1 if report.findings else 0


if __name__ == "__main__":
    sys.exit(main())
