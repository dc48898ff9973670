"""``dslint`` — static-analysis CLI + CI regression gate (ISSUE 6, 8, 9).

    python -m deepspeed_tpu.tools.dslint deepspeed_tpu/            # full lint
    python -m deepspeed_tpu.tools.dslint --changed                 # CI gate
    python -m deepspeed_tpu.tools.dslint pkg/ --update-baseline    # re-record
    python -m deepspeed_tpu.tools.dslint pkg/ --engines b,c        # subset
    python -m deepspeed_tpu.tools.dslint dumps/ --engines e,f      # memory

Runs the source engines — B (AST JAX-footgun rules) and C (AST concurrency
sanitizer, ISSUE 8) — over ``*.py`` under the given paths, and the program
engines — A (HLO declarations), D (collective consistency) and E (static
HBM liveness vs the committed ``.dsmem-budgets.json`` ledger, ISSUE 9) —
over any ``*.hlo`` post-optimization text dumps, then gates the result on
the committed baseline (``.dslint-baseline.json``): findings already in the
baseline are reported but do not fail; NEW findings exit 1.
``--update-baseline`` rewrites the ledger from the current findings —
entries whose finding disappeared expire, so the debt only shrinks.
``--engines a..g`` selects engines (default: all seven; Engine F needs a
live param tree — it runs via ``engine.verify_program()`` and the dsmem
tests, the CLI only lists its catalog). Engine G (ISSUE 15) adds the
serving-protocol plane: the page-ownership dataflow lint runs over every
``*.py`` scanned, and a scan covering ``serving/`` also runs the bounded
protocol model checker (violations carry ``model://`` pseudo-paths with
minimal counterexample traces). ``--sarif OUT.sarif`` additionally writes
a SARIF 2.1.0 document — one run per engine — for CI inline annotations.

``--changed`` lints just the files git reports as modified/staged/untracked
— the cheap per-PR gate; the committed baseline makes the full run
equivalent, so either works in CI. New engines ride the same fingerprints:
old Engine B findings keep their baseline entries untouched.

Engines A/D also run where live compiled programs exist:
``DeepSpeedEngine.verify_program()``, ``ServingEngine.verify()``, the
and the ``lint``/``dsan``-marked tier-1 tests.

Exit codes: 0 clean (or baseline-known only), 1 new findings, 2 usage /
unparseable file / corrupt baseline.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
from collections import Counter
from typing import List, Optional

from ..analysis import (
    ALL_ENGINES,
    DEFAULT_BASELINE_NAME,
    ENGINE_RULES,
    HLO_SUFFIXES,
    Baseline,
    all_rules,
    lint_paths,
)

EXIT_CLEAN, EXIT_FINDINGS, EXIT_USAGE = 0, 1, 2


def _git_changed_files() -> List[str]:
    """Python files git sees as modified / staged / untracked.

    git prints paths relative to the REPO ROOT regardless of cwd — resolve
    against `git rev-parse --show-toplevel`, or a `--changed` run from a
    subdirectory would filter every path out and pass the gate vacuously."""
    top = subprocess.run(
        ["git", "rev-parse", "--show-toplevel"],
        capture_output=True, text=True, timeout=30, check=True,
    ).stdout.strip()
    out = set()
    for args in (
        ["git", "diff", "--name-only", "HEAD"],
        ["git", "diff", "--name-only", "--cached"],
        ["git", "ls-files", "--others", "--exclude-standard", "--full-name"],
    ):
        res = subprocess.run(
            args, capture_output=True, text=True, timeout=30, check=True,
            cwd=top,
        )
        out.update(l.strip() for l in res.stdout.splitlines() if l.strip())
    return sorted(
        path for f in out
        if f.endswith(".py") or f.endswith(HLO_SUFFIXES)
        for path in [os.path.join(top, f)] if os.path.exists(path)
    )


def _find_baseline(paths: List[str]) -> Optional[str]:
    """Nearest committed baseline: CWD, then upward from the first path."""
    if os.path.exists(DEFAULT_BASELINE_NAME):
        return DEFAULT_BASELINE_NAME
    probe = os.path.abspath(paths[0]) if paths else os.getcwd()
    if os.path.isfile(probe):
        probe = os.path.dirname(probe)
    for _ in range(6):
        cand = os.path.join(probe, DEFAULT_BASELINE_NAME)
        if os.path.exists(cand):
            return cand
        parent = os.path.dirname(probe)
        if parent == probe:
            break
        probe = parent
    return None


def collect(
    paths: List[str],
    baseline_path: Optional[str] = None,
    hot_patterns=None,
    donate_patterns=None,
    engines=None,
) -> dict:
    """Run the selected engines + baseline split; the dict the CLI
    consumes. Raises SyntaxError / ValueError upward."""
    findings, suppressed, files = lint_paths(
        paths, hot_patterns=hot_patterns, donate_patterns=donate_patterns,
        engines=engines,
    )
    # fingerprints embed the path: normalize relative to the baseline's
    # directory so absolute-path callers and repo-root CLI runs
    # agree on what "the same finding" is
    anchor = os.path.realpath(
        os.path.dirname(os.path.abspath(baseline_path))
        if baseline_path else os.getcwd()
    )

    def _norm(path: str) -> str:
        try:
            rel = os.path.relpath(os.path.realpath(path), anchor)
        except ValueError:  # different drive (windows)
            return path
        return rel.replace(os.sep, "/") if not rel.startswith("..") else path

    for f in findings:
        f.path = _norm(f.path)
    scanned = {_norm(f) for f in files}
    baseline = Baseline.load(baseline_path or "")
    new, known, stale = baseline.split(findings)
    # an entry is only provably stale when its file was actually scanned
    # this run (a --changed subset must not declare the rest of the ledger
    # dead)
    stale = [
        fp for fp in stale
        if baseline.entries[fp].get("path") in scanned
    ]
    return {
        "files_scanned": len(files),
        "findings_total": len(findings),
        "new": new,
        "known": known,
        "stale_baseline_entries": stale,
        "suppressed": suppressed,
        "per_rule": dict(Counter(f.rule for f in findings)),
        "baseline_path": baseline.path or None,
        "baseline_size": len(baseline),
        "_baseline": baseline,
        "_findings": findings,
        "_scanned": scanned,
    }


def main(argv: Optional[List[str]] = None) -> int:
    p = argparse.ArgumentParser(
        prog="python -m deepspeed_tpu.tools.dslint",
        description="JAX/TPU static analyzer: AST rules + baseline CI gate "
        "(HLO program rules run via engine.verify_program / "
        "ServingEngine.verify and the lint-marked tests)",
    )
    p.add_argument("paths", nargs="*", help="files or directories to lint")
    p.add_argument("--changed", action="store_true",
                   help="lint the files git reports as changed instead of PATHS")
    p.add_argument("--engines", default=",".join(sorted(ALL_ENGINES)),
                   help="comma-separated engine letters to run: a (HLO "
                   "declarations over *.hlo dumps), b (AST JAX footguns), "
                   "c (AST concurrency sanitizer), d (HLO collective "
                   "consistency), e (static HBM liveness + budgets over "
                   "*.hlo dumps), f (sharding-spec tables — live trees "
                   "only, catalog via --list-rules), g (serving-protocol "
                   "ownership lint + bounded model checker). Default: all")
    p.add_argument("--baseline", default=None,
                   help=f"baseline file (default: nearest {DEFAULT_BASELINE_NAME})")
    p.add_argument("--config", default=None,
                   help="ds_config JSON whose `analysis` section supplies "
                   "hot_function_patterns / donate_name_patterns / baseline "
                   "([] = built-in defaults) and can disable the lint")
    p.add_argument("--update-baseline", action="store_true",
                   help="re-record the baseline from the current findings "
                   "(adds new, expires stale) and exit 0")
    p.add_argument("--no-baseline", action="store_true",
                   help="ignore any baseline: every finding fails")
    p.add_argument("--json", action="store_true", help="machine-readable report")
    p.add_argument("--sarif", default=None, metavar="OUT",
                   help="also write a SARIF 2.1.0 report (one run per "
                   "engine) to OUT for CI inline annotations")
    p.add_argument("--list-rules", action="store_true",
                   help="print the rule catalog and exit")
    args = p.parse_args(argv)

    engines = frozenset(
        e.strip().lower() for e in args.engines.split(",") if e.strip()
    )
    bad = engines - ALL_ENGINES
    if bad or not engines:
        print(
            f"dslint: unknown --engines {sorted(bad)} "
            f"(know {sorted(ALL_ENGINES)})",
            file=sys.stderr,
        )
        return EXIT_USAGE

    if args.list_rules:
        for letter in sorted(engines):
            for rule, desc in sorted(ENGINE_RULES[letter].items()):
                print(f"{letter}  {rule:<28} {desc}")
        return EXIT_CLEAN

    paths = list(args.paths)
    if args.changed:
        try:
            paths = _git_changed_files()
        except (OSError, subprocess.SubprocessError) as e:
            print(f"dslint: --changed needs a git checkout: {e}", file=sys.stderr)
            return EXIT_USAGE
        if not paths:
            print("dslint: no changed python files")
            return EXIT_CLEAN
    if not paths:
        p.print_usage(sys.stderr)
        print("dslint: give PATHS or --changed", file=sys.stderr)
        return EXIT_USAGE

    hot_patterns = donate_patterns = cfg_baseline = None
    if args.config:
        from ..runtime.config import AnalysisConfig, DeepSpeedConfigError

        try:
            with open(args.config, encoding="utf-8") as fh:
                doc = json.load(fh)
            acfg = AnalysisConfig.from_dict(
                doc.get("analysis", {}) if isinstance(doc, dict) else {}
            )
        except (OSError, json.JSONDecodeError, DeepSpeedConfigError,
                TypeError) as e:
            print(f"dslint: cannot read --config {args.config!r}: {e}",
                  file=sys.stderr)
            return EXIT_USAGE
        if not acfg.enabled:
            print("dslint: analysis.enabled=false in --config — skipping")
            return EXIT_CLEAN
        hot_patterns = acfg.hot_function_patterns or None
        donate_patterns = acfg.donate_name_patterns or None
        cfg_baseline = acfg.baseline or None

    baseline_path = args.baseline
    if baseline_path is None and cfg_baseline and not args.no_baseline:
        baseline_path = cfg_baseline
    if baseline_path is None and not args.no_baseline:
        baseline_path = _find_baseline(paths)
    if args.no_baseline:
        baseline_path = None

    try:
        report = collect(paths, baseline_path=baseline_path,
                         hot_patterns=hot_patterns,
                         donate_patterns=donate_patterns,
                         engines=engines)
    except SyntaxError as e:
        print(f"dslint: cannot parse {e.filename}:{e.lineno}: {e.msg}",
              file=sys.stderr)
        return EXIT_USAGE
    except ValueError as e:  # corrupt baseline
        print(f"dslint: {e}", file=sys.stderr)
        return EXIT_USAGE

    baseline: Baseline = report.pop("_baseline")
    findings = report.pop("_findings")
    scanned = report.pop("_scanned")

    if args.sarif:
        from ..analysis.sarif import sarif_report

        known_fps = {f.fingerprint() for f in report["known"]}
        doc = sarif_report(findings, known_fingerprints=known_fps,
                           engines=engines)
        try:
            with open(args.sarif, "w", encoding="utf-8") as fh:
                json.dump(doc, fh, indent=1)
                fh.write("\n")
        except OSError as e:
            print(f"dslint: cannot write --sarif {args.sarif!r}: {e}",
                  file=sys.stderr)
            return EXIT_USAGE
        print(f"dslint: SARIF report ({len(doc['runs'])} runs) -> {args.sarif}")

    if args.update_baseline:
        if engines != ALL_ENGINES:
            # a subset run sees a subset of findings; recording it would
            # expire every other engine's entries for the scanned files
            print(
                "dslint: --update-baseline requires the full engine set "
                "(drop --engines)",
                file=sys.stderr,
            )
            return EXIT_USAGE
        baseline.path = baseline.path or args.baseline or DEFAULT_BASELINE_NAME
        baseline.update(findings, scanned_paths=scanned)
        baseline.save()
        print(
            f"dslint: baseline {baseline.path} updated — "
            f"{len(baseline)} finding(s) recorded, "
            f"{len(report['stale_baseline_entries'])} expired"
        )
        return EXIT_CLEAN

    if args.json:
        doc = dict(report)
        doc["new"] = [f.to_dict() for f in report["new"]]
        doc["known"] = [f.to_dict() for f in report["known"]]
        print(json.dumps(doc, indent=1))
    else:
        for f in report["new"]:
            print(f"NEW  {f.render()}")
        for f in report["known"]:
            print(f"     {f.render()}  (baselined)")
        stale = len(report["stale_baseline_entries"])
        print(
            f"dslint: {report['findings_total']} finding(s) "
            f"({len(report['new'])} new, {len(report['known'])} baselined, "
            f"{report['suppressed']} suppressed) in "
            f"{report['files_scanned']} file(s)"
            + (f"; {stale} stale baseline entries — rerun with "
               "--update-baseline to expire" if stale else "")
        )
    return EXIT_FINDINGS if report["new"] else EXIT_CLEAN


if __name__ == "__main__":
    raise SystemExit(main())
