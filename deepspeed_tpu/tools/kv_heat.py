"""``kv_heat`` — page-lifetime / session-heat reporting over KV heat traces.

    python -m deepspeed_tpu.tools.kv_heat KV_HEAT.jsonl \
        [--pool NAME] [--page N] [--heatmap] [--bins N] \
        [--what-if] [--policy NAME] [--resident-fraction F] \
        [--min-cold-fraction PCT] [--threshold S] \
        [--max-overhead-pct PCT --bench BENCH.json] \
        [--diff B.jsonl --threshold-pct 10] [--json]

Consumes the schema-versioned JSONL the KVHeatTracer emits
(telemetry/kv_heat.py; per-pool lifecycle events + columnar per-step
touches) and renders:

- the **aggregate report** (default): per-pool event counts, end-of-trace
  occupancy split (active/prefix/shared/other/free), cold-page fractions at
  the recorded idle thresholds, free-list fragmentation, page-lifetime
  quantiles (the same bucket interpolation the registry histogram exports,
  so the numbers cross-check against the live gauges);
- a per-page **lifetime timeline** (``--page``): the page's lease history
  as a time-scaled bar — ``.`` free, ``#`` held, ``=`` shared (refcount
  > 1), ``P`` prefix-index-held, ``*`` touched in that window;
- a pool **heatmap** (``--heatmap``): page-id buckets x time bins, cell
  intensity = touches, the visual working-set-vs-resident-set answer;
- the **what-if spill evaluator** (``--what-if``): the recorded stream
  replayed against a ``--resident-fraction`` x capacity resident set under
  each candidate eviction policy (idle-age LRU / prefix-aware /
  slot-priority), reporting hypothetical spills, restore stalls and host
  traffic — what ROADMAP item 2 picks its policy from;
- the **policy cross-check** (``--policy``, ISSUE 17 satellite): the same
  recorded stream replayed against the LIVE tier implementation
  (``serving.tiering.replay_live_tier`` — real ``HostPageStore``, CRC
  verified) under one named policy, and diffed field-by-field against the
  what-if simulator's prediction; any divergence (victim order, residency
  accounting, restore stalls) exits 1;
- a **diff** (``--diff``): two runs' heat metrics compared, worse-than-
  threshold deltas flagged.

Exit codes (CI-gateable): 0 clean, 1 a gate tripped (``--min-cold-fraction``
floor not met, ``--max-overhead-pct`` exceeded, or any ``--diff``
regression), 2 usage/parse errors.
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import Any, Dict, List, Optional, Tuple

from ..telemetry.kv_heat import (
    KVHeatError,
    evaluate_spill_policies,
    heat_report,
    iter_pool_events,
    load_heat_records,
    pools_in,
)

# heat metrics --diff compares: (name, higher_is_better). Cold fraction is
# "better" higher FOR TIERING (more spillable headroom), but as a serving
# regression axis a hotter-running pool that suddenly goes cold means the
# resident set outgrew the working set — flag increases.
_DIFF_METRICS = (
    ("cold_fraction", False),
    ("fragmentation", False),
    ("page_lifetime_p99_s", False),
    ("pages_in_use_end", False),
)

_SHADES = " .:-=+*#%@"


def _fmt_s(v: Optional[float]) -> str:
    if v is None:
        return "-"
    return f"{v * 1e3:.2f}ms" if v < 1.0 else f"{v:.3f}s"


def _first_cold(occ: Dict[str, Any]) -> Optional[float]:
    for _th, frac in sorted(
        occ["cold_fraction"].items(), key=lambda kv: float(kv[0])
    ):
        return frac
    return None


def _overall_metrics(report: Dict[str, Any], pool: str) -> Dict[str, Any]:
    """One flat dict of a pool's heat metrics (the --diff comparison axis)."""
    pl = report["pools"][pool]
    occ = pl["occupancy"]
    return {
        "allocs": pl["allocs"],
        "pages_in_use_end": occ["pages_in_use"],
        "cold_fraction": _first_cold(occ),
        "fragmentation": occ["fragmentation"],
        "page_lifetime_p50_s": pl["page_lifetime_s"]["p50"],
        "page_lifetime_p99_s": pl["page_lifetime_s"]["p99"],
        "prefix_hits": pl["prefix_hits"],
        "touch_steps": pl["touch_steps"],
    }


# ---------------------------------------------------------------------------
# rendering
# ---------------------------------------------------------------------------

def _format_report(report: Dict[str, Any]) -> str:
    lines: List[str] = []
    for pool, pl in report["pools"].items():
        occ = pl["occupancy"]
        pg = occ["pages"]
        lines += [
            f"pool {pool}: capacity {pl['capacity']} pages"
            + (f" x {pl['page_bytes']} B" if pl["page_bytes"] else "")
            + f"   span {pl['span_s']:.3f}s   touch steps {pl['touch_steps']}",
            f"  events: {pl['allocs']} alloc / {pl['retains']} retain / "
            f"{pl['frees']} free   prefix: {pl['prefix_registered']} reg / "
            f"{pl['prefix_hits']} hit / {pl['prefix_evictions']} evict   "
            f"sessions: {pl['sessions_started']} start / {pl['sessions_ended']} end",
            f"  occupancy (end): {occ['pages_in_use']}/{occ['capacity']} in use "
            f"[active {pg['active']} | prefix {pg['prefix']} | shared "
            f"{pg['shared']} | other {pg['other']} | free {pg['free']}]   "
            f"fragmentation {occ['fragmentation']:.3f}",
            "  cold fraction: " + "   ".join(
                f">{th}s: " + (f"{100.0 * f:.1f}%" if f is not None else "-")
                for th, f in sorted(
                    occ["cold_fraction"].items(), key=lambda kv: float(kv[0])
                )
            ),
            f"  page lifetime: n={pl['page_lifetime_s']['count']} "
            f"mean {_fmt_s(pl['page_lifetime_s']['mean'])} "
            f"p50 {_fmt_s(pl['page_lifetime_s']['p50'])} "
            f"p99 {_fmt_s(pl['page_lifetime_s']['p99'])}"
            + (
                f"   session idle p50 {_fmt_s(pl['session_idle_age_p50_s'])}"
                if pl["session_idle_age_p50_s"] is not None else ""
            ),
            "",
        ]
    return "\n".join(lines).rstrip()


def _pool_span(records, pool: str) -> Tuple[float, float]:
    times = [float(ev[1]) for ev in iter_pool_events(records, pool)]
    if not times:
        raise KVHeatError(f"pool {pool!r}: no events in trace")
    return min(times), max(times)


def _page_timeline(records, pool: str, page: int, width: int = 64) -> str:
    """One page's lease history, time-scaled: ``.`` free ``#`` held ``=``
    shared ``P`` prefix-held; a window the page was touched in shows ``*``
    over a held state."""
    t0, t1 = _pool_span(records, pool)
    span = max(t1 - t0, 1e-12)
    # per-window state resolved from the event walk: (refs, prefix, touched)
    refs = 0
    in_prefix = False
    cells = [{"state": None, "touched": False} for _ in range(width)]

    def win(t: float) -> int:
        return min(width - 1, int((float(t) - t0) / span * width))

    def paint(t: float) -> None:
        c = cells[win(t)]
        c["state"] = (
            "P" if in_prefix and refs > 0
            else ("=" if refs > 1 else ("#" if refs == 1 else "."))
        )

    seen = False
    for ev in iter_pool_events(records, pool):
        op = ev[0]
        if op == "touch":
            for slot_wp in ev[3]:
                if int(slot_wp[1]) == page:
                    cells[win(ev[1])]["touched"] = True
                    seen = True
            continue
        if op == "B":
            for p, c in ev[2]:
                if int(p) == page:
                    refs = int(c)
                    in_prefix = page in {int(x) for x in ev[3]}
                    paint(ev[1])
                    seen = True
            continue
        if op == "E":
            if int(ev[2]) == page:
                in_prefix = False
                paint(ev[1])
                seen = True
            continue
        pages = ev[2] if isinstance(ev[2], (list, tuple)) else []
        hits = sum(1 for p in pages if int(p) == page)
        if not hits:
            continue
        seen = True
        if op == "A":
            refs = 1
        elif op == "R":
            refs += hits
        elif op == "F":
            refs = max(0, refs - hits)
            if refs == 0:
                in_prefix = False
        elif op == "G":
            in_prefix = True
        elif op == "H":
            cells[win(ev[1])]["touched"] = True
        elif op == "S":
            pass  # ownership, not a refcount change
        paint(ev[1])
    if not seen:
        raise KVHeatError(f"pool {pool!r}: page {page} never appears in trace")
    # forward-fill states between events; free until first event
    bar = []
    state = "."
    for c in cells:
        if c["state"] is not None:
            state = c["state"]
        bar.append("*" if c["touched"] and state != "." else state)
    return (
        f"pool {pool} page {page}  [{t0:.3f}s .. {t1:.3f}s]\n"
        f"|{''.join(bar)}|\n"
        "legend: . free  # held  = shared  P prefix-held  * touched"
    )


def _heatmap(records, pool: str, capacity: int, bins: int = 24,
             rows: int = 16) -> str:
    """Page-id buckets x time bins; cell intensity = touches + lifecycle
    activity landing in that (bucket, window)."""
    t0, t1 = _pool_span(records, pool)
    span = max(t1 - t0, 1e-12)
    rows = max(1, min(rows, capacity))
    grid = [[0] * bins for _ in range(rows)]

    def bucket(p: int) -> int:
        return min(rows - 1, (int(p) - 1) * rows // max(1, capacity))

    def win(t: float) -> int:
        return min(bins - 1, int((float(t) - t0) / span * bins))

    for ev in iter_pool_events(records, pool):
        op = ev[0]
        w = win(ev[1])
        if op == "touch":
            for slot_wp in ev[3]:
                grid[bucket(slot_wp[1])][w] += 1
        elif op in ("A", "R", "F", "G", "H"):
            for p in ev[2]:
                grid[bucket(p)][w] += 1
        elif op == "E":
            grid[bucket(ev[2])][w] += 1
    peak = max((v for row in grid for v in row), default=0)
    lines = [
        f"pool {pool} heatmap: {rows} page buckets (cap {capacity}) x "
        f"{bins} windows of {span / bins:.3f}s, peak {peak} touches/cell"
    ]
    per = max(1, capacity // rows)
    for r, row in enumerate(grid):
        lo = r * per + 1
        hi = capacity if r == rows - 1 else (r + 1) * per
        cells = "".join(
            _SHADES[min(len(_SHADES) - 1, v * (len(_SHADES) - 1) // peak)]
            if peak else " "
            for v in row
        )
        lines.append(f"  pages {lo:>4}-{hi:<4} |{cells}|")
    return "\n".join(lines)


def _format_whatif(wi: Dict[str, Any]) -> str:
    lines = [
        f"what-if spill: pool {wi['pool']}  resident "
        f"{wi['resident_cap']}/{wi['capacity']} pages "
        f"({100.0 * wi['resident_fraction']:.0f}%)"
        + (f"  page {wi['page_bytes']} B" if wi["page_bytes"] else ""),
        f"{'policy':<16} {'spills':>8} {'spilled':>12} {'stalls':>8} "
        f"{'restored':>12}",
        "-" * 60,
    ]
    for name, r in wi["policies"].items():
        lines.append(
            f"{name:<16} {r['spills']:>8} {r['spilled_bytes']:>11}B "
            f"{r['restore_stalls']:>8} {r['restored_bytes']:>11}B"
        )
    best = min(
        wi["policies"].items(),
        key=lambda kv: (kv[1]["restore_stalls"], kv[1]["spills"], kv[0]),
    )[0]
    lines.append("-" * 60)
    lines.append(f"fewest restore stalls: {best}")
    return "\n".join(lines)


def _policy_crosscheck(
    records, pool: str, policy: str, resident_fraction: float,
    as_json: bool = False,
) -> int:
    """``--policy``: the what-if simulator's prediction vs the LIVE tier
    implementation replaying the same stream (ISSUE 17 satellite). The two
    must agree field-by-field — a delta means the simulator no longer
    models the engine's victim order or residency accounting. Exit 0 in
    agreement, 1 on any mismatch, 2 on an unknown policy."""
    from ..serving.tiering import TIERING_POLICIES, replay_live_tier

    if policy not in TIERING_POLICIES:
        print(
            f"kv_heat: unknown policy {policy!r} "
            f"(have {list(TIERING_POLICIES)})", file=sys.stderr,
        )
        return 2
    sim = evaluate_spill_policies(
        records, pool, resident_fraction=resident_fraction,
        policies=(policy,),
    )["policies"][policy]
    live = replay_live_tier(
        records, pool, policy, resident_fraction=resident_fraction,
    )
    fields = sorted(set(sim) | set(live))
    rows = [
        {
            "field": f,
            "predicted": sim.get(f),
            "live": live.get(f),
            "match": sim.get(f) == live.get(f),
        }
        for f in fields
    ]
    mismatches = [r for r in rows if not r["match"]]
    out = {
        "pool": pool, "policy": policy,
        "resident_fraction": resident_fraction,
        "rows": rows, "mismatches": len(mismatches),
    }
    if as_json:
        print(json.dumps(out, indent=1))
    else:
        lines = [
            f"policy cross-check: pool {pool}  policy {policy}  resident "
            f"{100.0 * resident_fraction:.0f}%",
            f"{'field':<18} {'predicted':>12} {'live':>12}  flag",
            "-" * 52,
        ]
        for r in rows:
            lines.append(
                f"{r['field']:<18} {r['predicted']:>12} {r['live']:>12}  "
                f"{'' if r['match'] else 'MISMATCH'}"
            )
        lines.append("-" * 52)
        lines.append(
            f"{len(mismatches)} mismatch(es)" if mismatches
            else "simulator and live tier agree"
        )
        print("\n".join(lines))
    return 1 if mismatches else 0


# ---------------------------------------------------------------------------
# diff + gates
# ---------------------------------------------------------------------------

def diff_reports(
    a: Dict[str, Any], b: Dict[str, Any], threshold_pct: float = 10.0
) -> Dict[str, Any]:
    """Compare two runs' pool heat metrics; B worse than A by more than
    ``threshold_pct`` on any axis is a regression."""
    rows, regressions = [], []
    for name, higher_better in _DIFF_METRICS:
        ma, mb = a.get(name), b.get(name)
        if ma is None or mb is None:
            continue
        delta = mb - ma
        pct = (delta / abs(ma) * 100.0) if ma else (0.0 if not delta else float("inf"))
        worse = -pct if higher_better else pct
        regressed = worse > threshold_pct
        row = {
            "metric": name, "a": ma, "b": mb,
            "delta_pct": None if pct == float("inf") else round(pct, 2),
            "regressed": regressed,
        }
        rows.append(row)
        if regressed:
            regressions.append(row)
    return {"threshold_pct": threshold_pct, "rows": rows, "regressions": regressions}


def _format_diff(report: Dict[str, Any]) -> str:
    lines = [
        f"{'metric':<26} {'A':>12} {'B':>12} {'delta %':>9}  flag",
        "-" * 68,
    ]
    for row in report["rows"]:
        pct = row["delta_pct"]
        lines.append(
            f"{row['metric']:<26} {row['a']:>12.5g} {row['b']:>12.5g} "
            f"{(f'{pct:+.1f}' if pct is not None else 'new'):>9}  "
            f"{'REGRESSED' if row['regressed'] else ''}"
        )
    n = len(report["regressions"])
    lines.append("-" * 68)
    lines.append(
        f"{n} regression(s) above {report['threshold_pct']:.1f}%"
        if n else "no regressions"
    )
    return "\n".join(lines)


def _cold_gate(report: Dict[str, Any], pool: str, min_pct: float,
               threshold_s: Optional[float]) -> int:
    """``--min-cold-fraction``: the tiering viability floor — exit 1 when
    the pool's measured cold fraction (at ``--threshold``, default the
    smallest recorded one) is BELOW ``min_pct`` (not enough cold pages for
    a spill tier to pay for itself)."""
    occ = report["pools"][pool]["occupancy"]
    cf = occ["cold_fraction"]
    if threshold_s is not None:
        frac = cf.get(str(float(threshold_s)))
        if frac is None:
            print(
                f"kv_heat: threshold {threshold_s}s not recorded "
                f"(have {sorted(cf)})", file=sys.stderr,
            )
            return 2
    else:
        frac = _first_cold(occ)
    if frac is None:
        print(
            f"kv_heat: pool {pool}: no in-use pages at end of trace — cold "
            "fraction undefined", file=sys.stderr,
        )
        return 1
    if frac * 100.0 < min_pct:
        print(
            f"kv_heat: cold fraction {100.0 * frac:.1f}% below the "
            f"{min_pct:.1f}% floor", file=sys.stderr,
        )
        return 1
    return 0


def _overhead_gate(bench_path: str, max_pct: float) -> int:
    """``--max-overhead-pct``: pin the hook overhead a JSON record holds
    (``overhead.heat_overhead_pct``) under ``max_pct``."""
    try:
        with open(bench_path, encoding="utf-8") as fh:
            bench = json.load(fh)
    except (OSError, json.JSONDecodeError) as e:
        print(f"kv_heat: {bench_path}: {e}", file=sys.stderr)
        return 2
    pct = bench.get("overhead", {}).get("heat_overhead_pct")
    if pct is None:
        print(
            f"kv_heat: {bench_path}: no overhead.heat_overhead_pct",
            file=sys.stderr,
        )
        return 2
    if float(pct) > max_pct:
        print(
            f"kv_heat: hook overhead {float(pct):.3f}% exceeds the "
            f"{max_pct:.1f}% pin", file=sys.stderr,
        )
        return 1
    return 0


# ---------------------------------------------------------------------------

def main(argv: Optional[List[str]] = None) -> int:
    p = argparse.ArgumentParser(
        prog="python -m deepspeed_tpu.tools.kv_heat",
        description="page-lifetime / session-heat reports over KV heat "
                    "JSONL; exit 1 on a tripped gate",
    )
    p.add_argument("trace", help="heat trace (JSONL from KVHeatTracer)")
    p.add_argument("--pool", default=None,
                   help="pool to render (default: first in trace)")
    p.add_argument("--page", type=int, default=None, metavar="N",
                   help="render one page's lifetime timeline")
    p.add_argument("--heatmap", action="store_true",
                   help="render the pool's page x time touch heatmap")
    p.add_argument("--bins", type=int, default=24,
                   help="time windows for --heatmap / timeline width scale")
    p.add_argument("--what-if", action="store_true",
                   help="replay the trace through candidate spill policies")
    p.add_argument("--policy", default=None, metavar="NAME",
                   help="cross-check NAME's what-if prediction against the "
                   "live tier implementation; mismatches exit 1")
    p.add_argument("--resident-fraction", type=float, default=0.5,
                   metavar="F", help="--what-if/--policy resident set, "
                   "fraction of capacity (default 0.5)")
    p.add_argument("--min-cold-fraction", type=float, default=None,
                   metavar="PCT", help="gate: exit 1 if the pool's cold "
                   "fraction is below PCT%% (tiering viability floor)")
    p.add_argument("--threshold", type=float, default=None, metavar="S",
                   help="idle threshold (seconds) for --min-cold-fraction "
                   "(default: smallest recorded)")
    p.add_argument("--max-overhead-pct", type=float, default=None,
                   metavar="PCT", help="gate: exit 1 if --bench records "
                   "hook overhead above PCT%%")
    p.add_argument("--bench", default=None, metavar="BENCH_JSON",
                   help="JSON record holding overhead.heat_overhead_pct, "
                   "for --max-overhead-pct")
    p.add_argument("--diff", default=None, metavar="B_JSONL",
                   help="compare against a second trace; regressions exit 1")
    p.add_argument("--threshold-pct", type=float, default=10.0,
                   help="--diff regression threshold (%% worse than A)")
    p.add_argument("--json", action="store_true", help="emit JSON")
    args = p.parse_args(argv)
    if args.max_overhead_pct is not None and not args.bench:
        print("kv_heat: --max-overhead-pct requires --bench", file=sys.stderr)
        return 2
    try:
        records = load_heat_records(args.trace)
        if not records:
            print(f"kv_heat: {args.trace}: no kv_heat records", file=sys.stderr)
            return 2
        pools = pools_in(records)
        pool = args.pool or pools[0]
        if pool not in pools:
            print(
                f"kv_heat: pool {pool!r} not in trace (have {pools})",
                file=sys.stderr,
            )
            return 2
        report = heat_report(records)

        gates = 0
        if args.min_cold_fraction is not None:
            rc = _cold_gate(report, pool, args.min_cold_fraction, args.threshold)
            if rc == 2:
                return 2
            gates |= rc
        if args.max_overhead_pct is not None:
            rc = _overhead_gate(args.bench, args.max_overhead_pct)
            if rc == 2:
                return 2
            gates |= rc

        if args.page is not None:
            print(_page_timeline(records, pool, args.page))
            return gates
        if args.heatmap:
            print(_heatmap(
                records, pool, report["pools"][pool]["capacity"],
                bins=max(1, args.bins),
            ))
            return gates
        if args.diff is not None:
            records_b = load_heat_records(args.diff)
            pools_b = pools_in(records_b)
            if pool not in pools_b:
                print(
                    f"kv_heat: pool {pool!r} not in {args.diff} "
                    f"(have {pools_b})", file=sys.stderr,
                )
                return 2
            dr = diff_reports(
                _overall_metrics(report, pool),
                _overall_metrics(heat_report(records_b), pool),
                threshold_pct=args.threshold_pct,
            )
            print(json.dumps(dr, indent=1) if args.json else _format_diff(dr))
            return 1 if (dr["regressions"] or gates) else 0
        if args.policy is not None:
            rc = _policy_crosscheck(
                records, pool, args.policy, args.resident_fraction,
                as_json=args.json,
            )
            if rc == 2:
                return 2
            return 1 if (rc or gates) else 0
        if args.what_if:
            wi = evaluate_spill_policies(
                records, pool, resident_fraction=args.resident_fraction,
            )
            print(json.dumps(wi, indent=1) if args.json else _format_whatif(wi))
            return gates

        print(json.dumps(report, indent=1) if args.json
              else _format_report(report))
        return gates
    except (OSError, KVHeatError) as e:
        print(f"kv_heat: {e}", file=sys.stderr)
        return 2


if __name__ == "__main__":
    raise SystemExit(main())
