"""`ray-tpu analyze` driver: the concurrency & contract static gate.

Runs the ``ray_tpu.util.analyze`` passes over the package (or explicit
paths), applies the committed ``ANALYZE_BASELINE.json`` allowlist, and
exits non-zero on any NEW finding: drift fails loud, at review time,
not at 3am in a chaos soak.

Usage:
    python -m ray_tpu.scripts.analyze [paths...]
        [--rule lock-order|blocking|finalizer|async-lock|contracts
               |retry|daemon-loop|timeout-order|jax-hotpath|lifecycle]...
        [--no-baseline] [--baseline-file F] [--json]
        [--diff REV]           # only findings on lines changed since REV
        [--write-baseline]     # re-emit the baseline from current findings

Baseline workflow: a justified finding is allowlisted by adding its
stable key (printed with --json, or by --write-baseline) to
ANALYZE_BASELINE.json with a one-line justification as the value.
Stale keys (matching nothing) are reported so the allowlist only ever
shrinks.
"""

from __future__ import annotations

import argparse
import json
import sys

from ray_tpu.util import analyze
from ray_tpu.util.analyze import core as _core


def _write_baseline(result: dict, path: str,
                    existing: dict) -> None:
    entries = {}
    for f in result["findings"]:
        entries[f.key] = existing.get(
            f.key, "TODO: one-line justification")
    with open(path, "w") as fh:
        json.dump({
            "_comment": (
                "ray-tpu analyze allowlist: finding key -> one-line "
                "justification. Only findings ABSENT from this file "
                "fail the run; stale keys are reported so the list "
                "only shrinks. Justify every entry."),
            "entries": dict(sorted(entries.items())),
        }, fh, indent=1)
        fh.write("\n")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="ray-tpu analyze",
        description="concurrency & contract static analysis")
    ap.add_argument("paths", nargs="*",
                    help="files to analyze (default: the ray_tpu "
                         "package)")
    ap.add_argument("--rule", action="append", dest="rules",
                    metavar="NAME",
                    help="run only this pass (repeatable); one of: "
                         + ", ".join(sorted(analyze.PASSES)))
    ap.add_argument("--no-baseline", action="store_true",
                    help="ignore ANALYZE_BASELINE.json (show "
                         "everything)")
    ap.add_argument("--baseline", action="store_true",
                    help="(default) apply the committed baseline "
                         "allowlist — kept as an explicit flag for "
                         "scripts")
    ap.add_argument("--baseline-file", default=None)
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="machine-readable findings (with stable "
                         "baseline keys)")
    ap.add_argument("--diff", metavar="REV", default=None,
                    help="only findings on lines changed since REV "
                         "(git diff -U0 parse)")
    ap.add_argument("--write-baseline", action="store_true",
                    help="write ANALYZE_BASELINE.json from current "
                         "findings (preserves existing justifications)")
    args = ap.parse_args(argv)

    if args.write_baseline and (args.paths or args.diff or args.rules):
        # A restricted run only sees a slice of the findings; writing
        # the baseline from it would silently DROP every allowlist
        # entry (and hand-written justification) outside the slice.
        print("analyze: --write-baseline requires a full repo-wide run "
              "(no explicit paths, no --diff, no --rule)",
              file=sys.stderr)
        return 2

    try:
        result = analyze.run(
            paths=args.paths or None,
            rules=args.rules,
            use_baseline=not args.no_baseline,
            baseline_file=args.baseline_file,
            diff_rev=args.diff,
        )
    except (ValueError, RuntimeError) as e:
        print(f"analyze: {e}", file=sys.stderr)
        return 2

    if args.write_baseline:
        path = args.baseline_file or _core.baseline_path()
        existing = _core.load_baseline(path)
        _write_baseline(result, path, existing)
        print(f"analyze: wrote {len(result['findings'])} entries to "
              f"{path}")
        return 0

    if args.as_json:
        print(json.dumps({
            "ok": result["ok"],
            "files_scanned": result["n_files"],
            "rule_counts": result["rule_counts"],
            "new": [f.to_dict() for f in result["new"]],
            "baselined": [f.to_dict() for f in result["allowed"]],
            "stale_baseline": result["stale_baseline"],
        }, indent=1))
    else:
        for f in result["new"]:
            print(f.format())
        for key in result["stale_baseline"]:
            print(f"stale baseline entry (matches nothing — remove "
                  f"it): {key}")
        n_new = len(result["new"])
        n_base = len(result["allowed"])
        scanned = "diff-restricted" if args.diff else "repo"
        verdict = "OK" if result["ok"] else "FAIL"
        print(f"analyze: {verdict} ({scanned}: {n_new} new finding(s), "
              f"{n_base} baselined, "
              f"{len(result['stale_baseline'])} stale baseline "
              f"key(s))")
    return 0 if result["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
