"""``trace_diff`` — machine-checkable run comparison over step traces.

    python -m deepspeed_tpu.tools.trace_diff A.jsonl B.jsonl \
        [--threshold-pct 10] [--min-ms 0.05] [--kind train] [--json]

Aligns the ``*_step`` records of two StepTracer JSONL files (by step number
where both runs sampled the same steps, by sample order otherwise), then
compares per-run MEDIANS of:

- end-to-end step latency (``dur_ms``),
- every host span (``spans.children.*``),
- per-axis collective bytes (``comm_bytes.*``).

A span/metric whose B-median exceeds its A-median by more than
``--threshold-pct`` (and by more than ``--min-ms`` for time-valued rows —
sub-noise spans can't flag) is a REGRESSION. Exit code: 0 when no
regression, 1 when any, 2 on usage/parse errors — so CI can gate on
``trace_diff baseline.jsonl candidate.jsonl``.
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import Any, Dict, List, Optional, Tuple


class TraceFormatError(Exception):
    """A trace file that cannot be diffed: wrong schema or truncated beyond
    use. Carries a human-readable message — the CLI exits 2 with it instead
    of a raw traceback (rotation can hand this tool a partial ``.1`` file)."""


def _check_schema(rec: Any, path: str, lineno: int) -> Dict[str, Any]:
    """A step record must be a flat object with the expected field types —
    anything else is another tool's JSONL, not a StepTracer trace."""
    if not isinstance(rec, dict):
        raise TraceFormatError(
            f"{path}:{lineno}: JSON line is {type(rec).__name__}, not an "
            "object — this is not a StepTracer trace"
        )
    for key, want in (("spans", dict), ("comm_bytes", dict)):
        if key in rec and rec[key] is not None and not isinstance(rec[key], want):
            raise TraceFormatError(
                f"{path}:{lineno}: field {key!r} is "
                f"{type(rec[key]).__name__}, expected {want.__name__} — "
                "schema mismatch (trace written by an incompatible version?)"
            )
    dur = rec.get("dur_ms")
    if dur is not None and not isinstance(dur, (int, float)):
        raise TraceFormatError(
            f"{path}:{lineno}: field 'dur_ms' is {type(dur).__name__}, "
            "expected a number — schema mismatch"
        )
    return rec


def load_step_records(path: str, kind: Optional[str] = None) -> List[Dict[str, Any]]:
    """The ``*_step`` records of one JSONL trace, in file order.

    One torn TAIL line (a killed or mid-rotation run) is tolerated; torn
    lines elsewhere, undecodable bytes, or records of the wrong shape raise
    :class:`TraceFormatError` with the offending location."""
    out: List[Dict[str, Any]] = []
    torn: List[int] = []
    try:
        with open(path, encoding="utf-8") as fh:
            lines = fh.readlines()
    except UnicodeDecodeError as e:
        raise TraceFormatError(
            f"{path}: not a text JSONL trace ({e.reason} at byte {e.start})"
        ) from e
    last = len(lines)
    for lineno, line in enumerate(lines, start=1):
        line = line.strip()
        if not line:
            continue
        try:
            rec = json.loads(line)
        except json.JSONDecodeError:
            torn.append(lineno)
            continue
        k = str(_check_schema(rec, path, lineno).get("kind", ""))
        if not k.endswith("_step"):
            continue
        if kind is not None and k != f"{kind}_step":
            continue
        out.append(rec)
    if torn and torn != [last]:
        raise TraceFormatError(
            f"{path}: {len(torn)} undecodable line(s) (first at line "
            f"{torn[0]} of {last}) — the file is truncated or corrupt, not "
            "just missing its tail; re-capture the trace"
        )
    return out


def align(a: List[Dict], b: List[Dict]) -> List[Tuple[Dict, Dict]]:
    """Pair records by step number when the runs sampled overlapping steps,
    else zip by sample order (different sample_every → order is the only
    common axis)."""
    a_by = {r.get("step"): r for r in a if r.get("step") is not None}
    b_by = {r.get("step"): r for r in b if r.get("step") is not None}
    common = sorted(set(a_by) & set(b_by))
    if common:
        return [(a_by[s], b_by[s]) for s in common]
    return list(zip(a, b))


def _median(xs: List[float]) -> Optional[float]:
    xs = sorted(x for x in xs if x is not None)
    if not xs:
        return None
    n = len(xs)
    return xs[n // 2] if n % 2 else 0.5 * (xs[n // 2 - 1] + xs[n // 2])


def _series(recs: List[Dict]) -> Dict[str, List[float]]:
    """metric name → per-record values. Time-valued names end in ``_ms``."""
    out: Dict[str, List[float]] = {}

    def put(name, v):
        if isinstance(v, (int, float)):
            out.setdefault(name, []).append(float(v))

    for r in recs:
        put("dur_ms", r.get("dur_ms"))
        for name, ms in (r.get("spans", {}).get("children") or {}).items():
            put(f"span:{name}_ms", ms)
        for axis, nbytes in (r.get("comm_bytes") or {}).items():
            put(f"comm_bytes:{axis}", nbytes)
    return out


def diff(
    a: List[Dict],
    b: List[Dict],
    threshold_pct: float = 10.0,
    min_ms: float = 0.05,
) -> Dict[str, Any]:
    pairs = align(a, b)
    if not pairs:
        return {"aligned_steps": 0, "rows": [], "regressions": []}
    sa = _series([p[0] for p in pairs])
    sb = _series([p[1] for p in pairs])
    rows, regressions = [], []
    for name in sorted(set(sa) | set(sb)):
        ma, mb = _median(sa.get(name, [])), _median(sb.get(name, []))
        if ma is None or mb is None:
            continue
        delta = mb - ma
        pct = (delta / abs(ma) * 100.0) if ma else (0.0 if not delta else float("inf"))
        is_time = name.endswith("_ms")
        regressed = pct > threshold_pct and (not is_time or abs(delta) > min_ms)
        row = {
            "metric": name,
            "a_median": ma,
            "b_median": mb,
            "delta": delta,
            "delta_pct": None if pct == float("inf") else round(pct, 2),
            "regressed": regressed,
        }
        rows.append(row)
        if regressed:
            regressions.append(row)
    return {
        "aligned_steps": len(pairs),
        "threshold_pct": threshold_pct,
        "rows": rows,
        "regressions": regressions,
    }


def _format_table(report: Dict[str, Any]) -> str:
    lines = [
        f"aligned steps: {report['aligned_steps']}",
        f"{'metric':<28} {'A median':>14} {'B median':>14} {'delta %':>9}  flag",
        "-" * 74,
    ]
    for row in report["rows"]:
        pct = row["delta_pct"]
        lines.append(
            f"{row['metric']:<28} {row['a_median']:>14.4g} {row['b_median']:>14.4g} "
            f"{(f'{pct:+.1f}' if pct is not None else 'new'):>9}  "
            f"{'REGRESSED' if row['regressed'] else ''}"
        )
    n = len(report["regressions"])
    lines.append("-" * 74)
    lines.append(
        f"{n} regression(s) above {report['threshold_pct']:.1f}%"
        if n else "no regressions"
    )
    return "\n".join(lines)


def main(argv: Optional[List[str]] = None) -> int:
    p = argparse.ArgumentParser(
        prog="python -m deepspeed_tpu.tools.trace_diff",
        description="diff two step-trace JSONL runs; exit 1 on regression",
    )
    p.add_argument("trace_a", help="baseline trace (JSONL)")
    p.add_argument("trace_b", help="candidate trace (JSONL)")
    p.add_argument("--threshold-pct", type=float, default=10.0,
                   help="regression threshold (%% worse than baseline median)")
    p.add_argument("--min-ms", type=float, default=0.05,
                   help="ignore time regressions smaller than this (noise floor)")
    p.add_argument("--kind", default=None,
                   help="only this step family (train | inference | ...)")
    p.add_argument("--json", action="store_true", help="emit the report as JSON")
    args = p.parse_args(argv)
    try:
        a = load_step_records(args.trace_a, kind=args.kind)
        b = load_step_records(args.trace_b, kind=args.kind)
    except (OSError, TraceFormatError) as e:
        print(f"trace_diff: {e}", file=sys.stderr)
        return 2
    if not a or not b:
        print(
            f"trace_diff: no step records ({args.trace_a}: {len(a)}, "
            f"{args.trace_b}: {len(b)})",
            file=sys.stderr,
        )
        return 2
    try:
        report = diff(a, b, threshold_pct=args.threshold_pct, min_ms=args.min_ms)
    except (TypeError, ValueError, KeyError, AttributeError) as e:
        # records that passed the shape check but still defeat the metric
        # extraction: a clear one-liner, never a traceback, always exit 2
        print(
            f"trace_diff: traces are not comparable "
            f"({type(e).__name__}: {e}) — schema mismatch between "
            f"{args.trace_a} and {args.trace_b}?",
            file=sys.stderr,
        )
        return 2
    print(json.dumps(report, indent=1) if args.json else _format_table(report))
    return 1 if report["regressions"] else 0


if __name__ == "__main__":
    raise SystemExit(main())
