"""Driver: ``python -m tools.analyze [options] [paths...]``.

Exit codes:
    0   clean — no findings beyond the baseline
    1   new findings (or --write-baseline wrote nothing because of an error)
    2   internal error in the analyzer itself

The default baseline is tools/analyze/baseline.json; pass ``--baseline
none`` to compare against nothing (every finding is then "new").
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import traceback

from .core import (Project, filter_noqa, load_baseline, run_rules,
                   split_findings, write_baseline)
from .rules import ALL_RULES, rules_by_code

DEFAULT_BASELINE = os.path.join("tools", "analyze", "baseline.json")


def _repo_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        prog="python -m tools.analyze",
        description="paddle-tpu-analyze: AST-based tracer-safety, "
                    "host-sync and API-surface analyzer")
    ap.add_argument("paths", nargs="*", default=None,
                    help="files/dirs to analyze (default: paddle_tpu)")
    ap.add_argument("--root", default=None,
                    help="repo root for relative paths and the baseline "
                         "(default: autodetected from this file)")
    ap.add_argument("--only", "--rule", action="append", default=None,
                    dest="only", metavar="PTA###[,PTA###]",
                    help="run only these rules (repeatable or "
                         "comma-separated). The slow trace tier "
                         "(PTA009/PTA010/PTA012/PTA014, compiles code) "
                         "ONLY runs when selected here.")
    ap.add_argument("--changed-only", nargs="?", const="HEAD",
                    default=None, metavar="BASE",
                    help="analyze only .py files changed vs BASE "
                         "(git diff --name-only BASE, plus untracked "
                         "files; default BASE: HEAD) that fall under the "
                         "given paths — the fast pre-commit lane. No "
                         "changed files is a clean exit. Also scopes the "
                         "trace tier: only entrypoints whose import "
                         "closure touches a changed file are re-traced.")
    ap.add_argument("--skip", action="append", default=[],
                    metavar="PTA###[,PTA###]", help="disable these rules "
                    "(repeatable or comma-separated)")
    ap.add_argument("--baseline", default=None, metavar="FILE",
                    help=f"baseline file relative to root (default: "
                         f"{DEFAULT_BASELINE}; 'none' disables)")
    ap.add_argument("--write-baseline", "--regen-baseline",
                    action="store_true", dest="write_baseline",
                    help="record all current findings as the new baseline "
                         "and exit 0")
    ap.add_argument("--json", action="store_const", const="json",
                    dest="format", help="shorthand for --format json")
    ap.add_argument("--format", choices=("text", "json", "sarif"),
                    default="text", dest="format",
                    help="output format (default: text)")
    ap.add_argument("--output", "-o", default=None, metavar="FILE",
                    help="write the json/sarif payload to FILE (a text "
                         "summary still goes to stdout)")
    ap.add_argument("--strict", action="store_true",
                    help="warnings gate the exit code too (default: only "
                         "error-severity findings do)")
    ap.add_argument("--trace-report", default=None, metavar="FILE",
                    help="write the trace tier's per-entrypoint audit "
                         "stats (trace counts, transfers, fusion stats, "
                         "collective schedules) to FILE as json — "
                         "requires selecting PTA009/PTA010/PTA012/PTA014 "
                         "via --only")
    ap.add_argument("--fusion-report", nargs="?", const="fusion_audit.json",
                    default=None, metavar="FILE",
                    help="write the PTA014 ranked fusion-miss table to "
                         "FILE as json (default FILE: fusion_audit.json, "
                         "gitignored). Written automatically whenever "
                         "PTA014 is selected, so `--only PTA014 --format "
                         "json` emits the standalone artifact.")
    ap.add_argument("--list-rules", action="store_true")
    return ap


def _split_codes(specs) -> list:
    out = []
    for spec in specs or []:
        out.extend(c.strip() for c in spec.split(",") if c.strip())
    return out


def select_rules(args) -> list:
    by_code = rules_by_code()
    only = _split_codes(args.only)
    if only:
        unknown = [c for c in only if c.upper() not in by_code]
        if unknown:
            raise SystemExit(f"unknown rule(s): {', '.join(unknown)} "
                             f"(known: {', '.join(sorted(by_code))})")
        rules = [by_code[c.upper()] for c in only]
    else:
        # default run = fast AST tier only; the trace tier compiles every
        # registered entrypoint and must be opted into explicitly
        rules = [r for r in ALL_RULES if r.tier == "ast"]
    skip = {c.upper() for c in _split_codes(args.skip)}
    return [r for r in rules if r.code not in skip]


def _changed_paths(root: str, base: str, scope: list) -> list:
    """Changed-vs-``base`` plus untracked .py files that fall under the
    requested analysis paths (the --changed-only pre-commit lane)."""
    def _git(*argv):
        res = subprocess.run(["git", *argv], cwd=root,
                             capture_output=True, text=True)
        if res.returncode != 0:
            raise SystemExit(f"--changed-only: git {' '.join(argv)} "
                             f"failed: {res.stderr.strip()}")
        return [ln.strip() for ln in res.stdout.splitlines() if ln.strip()]

    changed = _git("diff", "--name-only", base)
    changed += _git("ls-files", "--others", "--exclude-standard")
    prefixes = []
    for p in scope:
        rel = os.path.relpath(os.path.abspath(p), root) \
            if os.path.isabs(p) else p
        prefixes.append(rel.rstrip("/"))
    scoped = []
    for rel in dict.fromkeys(changed):
        if not rel.endswith(".py"):
            continue
        if not os.path.exists(os.path.join(root, rel)):
            continue  # deleted by the change
        if not any(rel == p or rel.startswith(p + "/") or p == "."
                   for p in prefixes):
            continue
        scoped.append(rel)
    return scoped


def _salvage_output(args, root, rules, tb: str) -> None:
    """Exit-2 path: never leave a stale payload file behind. Overwrite
    the requested --output with a valid empty-results document carrying
    the internal error (SARIF: as a tool-execution notification)."""
    if not args.output or args.format not in ("sarif", "json"):
        return
    try:
        if args.format == "sarif":
            from .sarif import to_sarif
            payload = to_sarif([], rules, set(), error=tb)
        else:
            payload = {"version": 1, "root": root, "error": tb,
                       "rules": [r.code for r in rules],
                       "counts": {}, "findings": []}
        out_path = (args.output if os.path.isabs(args.output)
                    else os.path.join(root, args.output))
        with open(out_path, "w") as fh:
            json.dump(payload, fh, indent=1)
            fh.write("\n")
        print(f"internal error recorded in {args.format} output "
              f"{os.path.relpath(out_path, root)}", file=sys.stderr)
    except Exception:
        traceback.print_exc()


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    if args.list_rules:
        for r in ALL_RULES:
            tier = "" if r.tier == "ast" else f" [{r.tier} tier]"
            print(f"{r.code}  {r.name}{tier}: {r.description}")
        return 0

    root = os.path.abspath(args.root) if args.root else _repo_root()
    rules = select_rules(args)
    try:
        return _run(args, root, rules)
    except SystemExit:
        raise
    except Exception:
        traceback.print_exc()
        _salvage_output(args, root, rules, traceback.format_exc())
        return 2


def _run(args, root: str, rules: list) -> int:
    paths = args.paths or ["paddle_tpu"]
    if args.changed_only is not None:
        paths = _changed_paths(root, args.changed_only, paths)
        if not paths:
            print("--changed-only: no changed .py files under the "
                  "analyzed paths; clean")
            return 0
        if any(r.tier == "trace" for r in rules):
            # scope the trace tier too: only entrypoints whose static
            # import closure touches a changed file get re-traced
            from . import trace as trace_mod
            try:
                scope = trace_mod.scope_entrypoints(root, paths)
            except Exception:
                scope = None  # registry unimportable: run_audit records it
            trace_mod.set_audit_scope(scope)
            if scope is not None:
                print(f"--changed-only: trace tier scoped to "
                      f"{len(scope)} entrypoint(s)"
                      + (f": {', '.join(scope)}" if scope else ""))

    baseline_arg = args.baseline or DEFAULT_BASELINE
    baseline_path = (None if baseline_arg.lower() == "none"
                     else os.path.join(root, baseline_arg)
                     if not os.path.isabs(baseline_arg) else baseline_arg)

    project = Project(root, paths)
    findings = run_rules(project, rules)
    findings, suppressed = filter_noqa(project, findings)

    if args.trace_report:
        from .trace import last_report
        report = last_report()
        if report is None:
            print("--trace-report: no trace-tier rule ran (select PTA009/"
                  "PTA010 via --only)", file=sys.stderr)
        else:
            tr_path = (args.trace_report if os.path.isabs(args.trace_report)
                       else os.path.join(root, args.trace_report))
            with open(tr_path, "w") as fh:
                json.dump(report.stats_payload(), fh, indent=1,
                          sort_keys=True)
                fh.write("\n")
            print(f"wrote trace audit ({len(report.entrypoint_stats)} "
                  f"entrypoint(s)) to {os.path.relpath(tr_path, root)}")

    fusion_report = args.fusion_report
    if fusion_report is None and any(r.code == "PTA014" for r in rules):
        fusion_report = "fusion_audit.json"  # the standalone CI artifact
    if fusion_report:
        from .trace import last_report
        report = last_report()
        if report is None:
            print("--fusion-report: no trace-tier rule ran (select "
                  "PTA014 via --only)", file=sys.stderr)
        else:
            ranked = sorted(
                (st for st in report.entrypoint_stats.values()
                 if not st.error),
                key=lambda s: -s.unfused_boundary_bytes)
            fr_payload = {
                "version": 1,
                "platform": report.platform,
                "ranking": [st.name for st in ranked],
                "entrypoints": {
                    st.name: {
                        "fusion_regions": st.fusion_regions,
                        "unfused_boundary_bytes":
                            st.unfused_boundary_bytes,
                        "top_fusion_misses": st.top_fusion_misses,
                    } for st in ranked},
            }
            fr_path = (fusion_report if os.path.isabs(fusion_report)
                       else os.path.join(root, fusion_report))
            with open(fr_path, "w") as fh:
                json.dump(fr_payload, fh, indent=1, sort_keys=True)
                fh.write("\n")
            print(f"wrote fusion-miss audit ({len(ranked)} "
                  f"entrypoint(s)) to {os.path.relpath(fr_path, root)}")

    if args.write_baseline:
        if baseline_path is None:
            print("--write-baseline requires a baseline file", file=sys.stderr)
            return 1
        write_baseline(baseline_path, findings)
        print(f"wrote {len(findings)} finding(s) "
              f"({len({f.fingerprint for f in findings})} fingerprints) "
              f"to {os.path.relpath(baseline_path, root)}")
        return 0

    baseline = load_baseline(baseline_path) if baseline_path else {}
    # under --only/--skip, entries from unselected rules are invisible,
    # not expired — don't report them as stale
    selected_codes = {r.code for r in rules}
    baseline = {fp: e for fp, e in baseline.items()
                if e.get("rule") in selected_codes}
    new, baselined, expired = split_findings(findings, baseline)
    new_ids = {id(x) for x in new}
    # warnings only gate under --strict; errors always do
    gating = [f for f in new if args.strict or f.severity == "error"]

    payload = None
    if args.format == "json":
        payload = {
            "version": 1,
            "root": root,
            "rules": [r.code for r in rules],
            "counts": {"total": len(findings), "new": len(new),
                       "gating": len(gating),
                       "baselined": len(baselined),
                       "suppressed": len(suppressed),
                       "expired_baseline_entries": len(expired)},
            "findings": [
                {"rule": f.rule, "path": f.path, "line": f.line,
                 "col": f.col, "message": f.message,
                 "severity": f.severity,
                 "fingerprint": f.fingerprint,
                 "status": "new" if id(f) in new_ids else "baselined"}
                for f in findings],
        }
    elif args.format == "sarif":
        from .sarif import to_sarif
        payload = to_sarif(findings, rules, new_ids)

    if payload is not None and args.output:
        out_path = (args.output if os.path.isabs(args.output)
                    else os.path.join(root, args.output))
        with open(out_path, "w") as fh:
            json.dump(payload, fh, indent=1)
            fh.write("\n")
        print(f"wrote {args.format} ({len(findings)} finding(s), "
              f"{len(new)} new) to {os.path.relpath(out_path, root)}")
    elif payload is not None:
        print(json.dumps(payload, indent=1))

    if payload is None or args.output:
        for f in new:
            sev = "" if f.severity == "error" else f" ({f.severity})"
            print(f.render() + sev)
        if baselined:
            print(f"[{len(baselined)} pre-existing finding(s) suppressed "
                  f"by baseline]")
        if suppressed:
            print(f"[{len(suppressed)} finding(s) suppressed by inline "
                  f"noqa]")
        if expired:
            print(f"[{len(expired)} baseline entr(ies) no longer match — "
                  f"run --regen-baseline to prune]")
        if new:
            gate_note = ("" if len(gating) == len(new) else
                         f" ({len(new) - len(gating)} warning(s) not "
                         f"gating; use --strict)")
            print(f"{len(new)} new finding(s){gate_note}; fix them, add "
                  f"`# noqa: PTA### -- reason`, or run --regen-baseline "
                  f"(docs/static_analysis.md)")
        else:
            print(f"clean: 0 new findings "
                  f"({len(baselined)} baselined, {len(suppressed)} noqa)")
    return 1 if gating else 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SystemExit:
        raise
    except Exception:
        traceback.print_exc()
        sys.exit(2)
