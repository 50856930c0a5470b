#!/usr/bin/env python
"""Run the repo's invariant lint suite (lighthouse_tpu/analysis).

Exit status:
  0  — zero unwaived findings and a healthy waiver ledger
  1  — unwaived findings, stale waivers, or waivers missing their
       mandatory justification

Usage:
  python tools/lint.py                # human output, all rules
  python tools/lint.py --json        # machine-readable findings
  python tools/lint.py --rule lock-discipline --rule jit-discipline
  python tools/lint.py --list-rules
  python tools/lint.py --root path/to/pkg --waivers path/to/waivers.json
  python tools/lint.py --prune-waivers          # report stale entries
  python tools/lint.py --prune-waivers --apply  # and delete them

Wired into tier-1 (tests/test_analysis.py runs this over the repo).
"""

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from lighthouse_tpu import analysis  # noqa: E402
from lighthouse_tpu.analysis.core import PACKAGE_ROOT  # noqa: E402


def prune_waivers(root=None, waivers_path=None, apply=False):
    """Stale-waiver burn-down as a command: a ledger entry whose
    ``match`` substring no longer appears on any source line of its
    ``path`` (or whose file is gone) waives nothing — the violation was
    fixed and the waiver must shed.  Returns the report dict; with
    ``apply`` the stale entries are deleted from the ledger in place."""
    root = Path(root) if root else PACKAGE_ROOT
    wpath = (Path(waivers_path) if waivers_path
             else analysis.default_waivers_path())
    entries = json.loads(wpath.read_text()) if wpath.exists() else []
    kept, stale = [], []
    for w in entries:
        target = root / str(w.get("path", ""))
        reason = None
        if not target.exists():
            reason = "file gone"
        else:
            lines = target.read_text().splitlines()
            if not any(str(w.get("match", "")) in ln for ln in lines):
                reason = "match substring on no source line"
        if reason is None:
            kept.append(w)
        else:
            stale.append({**w, "stale_reason": reason})
    if apply and stale:
        wpath.write_text(json.dumps(kept, indent=2) + "\n")
    return {
        "waivers_path": str(wpath),
        "checked": len(entries),
        "kept": len(kept),
        "stale": stale,
        "applied": bool(apply and stale),
    }


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--json", action="store_true",
                    help="emit machine-readable findings JSON")
    ap.add_argument("--rule", action="append", default=None,
                    help="run only this rule (repeatable)")
    ap.add_argument("--list-rules", action="store_true",
                    help="list registered rules and exit")
    ap.add_argument("--root", default=None,
                    help="package root to analyze (default: the "
                         "installed lighthouse_tpu)")
    ap.add_argument("--waivers", default=None,
                    help="waiver ledger path (default: the package's "
                         "analysis/waivers.json)")
    ap.add_argument("--prune-waivers", action="store_true",
                    help="report ledger entries whose match substring "
                         "no longer appears in their file")
    ap.add_argument("--apply", action="store_true",
                    help="with --prune-waivers: delete the stale "
                         "entries from the ledger")
    args = ap.parse_args(argv)

    if args.list_rules:
        for name, rule in sorted(analysis.all_rules().items()):
            print(f"{name:24s} {rule.description}")
        return 0

    if args.prune_waivers:
        rep = prune_waivers(root=args.root, waivers_path=args.waivers,
                            apply=args.apply)
        if args.json:
            print(json.dumps(rep, indent=2))
        else:
            for w in rep["stale"]:
                print(f"stale: {w['rule']}:{w['path']}:{w['match']!r} "
                      f"({w['stale_reason']})")
            print(f"{rep['checked']} waiver(s) checked, "
                  f"{len(rep['stale'])} stale"
                  + (", deleted" if rep["applied"] else ""))
        return 1 if rep["stale"] and not args.apply else 0

    report = analysis.run_analysis(
        root=args.root, rules=args.rule, waivers_path=args.waivers
    )
    if args.json:
        print(json.dumps({
            "clean": report["clean"],
            "findings": [f.to_dict() for f in report["findings"]],
            "waived": [f.to_dict() for f in report["waived"]],
            "waiver_errors": [f.to_dict()
                              for f in report["waiver_errors"]],
        }, indent=2))
    else:
        print(analysis.format_report(report))
    return 0 if report["clean"] else 1


if __name__ == "__main__":
    sys.exit(main())
