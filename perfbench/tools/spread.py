"""Takes the spread of a set of runs of one served cell apart, from the runs'
``--dump`` files. For the builder; the driver never runs it.

    python3 perfbench/tools/spread.py chiprun_out/set1 [more dirs or files] [--json out.json]

One row a run: the reading (tokens credited in the window over its length: the
document cell's ``serve_tok_s``; taken from the result line where that reports
it, else counted again from the dump), generated tokens a second, steps in the
window, the median ``srv.step`` of steps that only decode and of steps that
hold a prefill or chunk call, requests finished, prompt tokens credited, the
place in the backlog cycle of the first request admitted inside the window,
and what was in flight, uncredited, when the window closed.

Then the set's spread (quartile distance over the median, all runs and with
the run farthest from the median left out) split into three terms. In a
backlog the work of step k is the same in every run (no clock reaches the
schedule), so with m the run nearest the set's median, over the steps
[s_m, e_m] that m's window holds and the N_m tokens it was credited:

    speed_i = N_m / (time run i took over those same steps)
    phase_i = reading_i - speed_i         which tokens i's own window held
    mode_i  = N_m / (m's step times, each scaled by the median of i's time
              over m's for steps of its kind) - reading_m   a shift of every step alike
    rest_i  = speed_i - reading_m - mode_i       stalls, jitter inside a run

so reading_i - reading_m = mode_i + phase_i + rest_i exactly, and because the
quartile distance is a fixed weighted sum of the sorted readings, the three
weighted sums add up to the set's spread. Steps that run i never reached (a
slow run stops earlier) are filled with m's, scaled the same way. A *mode run*
is one whose decode-only median is 5% or more over the fastest of the others'.
An open loop's steps do not line up by number, so such a set gets the rows
only.
"""

from __future__ import annotations

import argparse
import bisect
import glob
import json
import os
import statistics
import sys
from dataclasses import dataclass, field
from typing import List, Optional

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

from perfbench import arith  # noqa: E402

MODE_RATIO = 1.05
STALL_S = 0.02


@dataclass
class Run:
    name: str
    cell: str
    seed: int
    W: float
    reported: Optional[float]
    t0: List[float]               # start of every srv.step, window opens at 0
    tau: List[float]              # step k lasts until step k+1 starts (the last: its own length)
    gen: List[int]                # tokens the step emitted
    recs: List[dict]
    kind: List[int] = field(default_factory=list)   # prefills in flight in step k: 0, 1, 2 (or more)

    def index(self, t: float) -> int:
        return max(0, bisect.bisect_right(self.t0, t) - 1)

    def pos(self, t: float) -> float:
        """Time -> place on the step axis (step number plus the share of it done)."""
        k = self.index(t)
        return k + min(1.0, (t - self.t0[k]) / self.tau[k])


def load(path: str, workload: str = "") -> Optional[Run]:
    with open(path) as f:
        return from_doc(json.load(f), os.path.basename(path), workload)


def from_doc(doc: dict, name: str, workload: str = "") -> Optional[Run]:
    """None for a dump of another cell, or of a cell that is not served."""
    res = doc["result"]
    steps = [s for s in doc["steps"] if s[0] == "srv.step"]
    if not steps or (workload and res["cell"] != workload):
        return None
    t0 = [s[1] for s in steps]
    tau = [b - a for a, b in zip(t0, t0[1:])] + [steps[-1][2]]
    m = res["metrics"].get("serve_tok_s")
    run = Run(name, res["cell"], int(res["seed"]), float(res["seconds"]), m and float(m["value"]), t0, tau,
              [int(s[3]["decode_tokens"]) + int(s[3]["first_tokens"]) for s in steps], doc["recs"])
    inflight = [0] * len(t0)
    for r in run.recs:
        if r["admit"] is None:
            continue
        ka = run.index(r["admit"])
        kf = len(t0) - 1 if r["first"] is None else run.index(r["first"])
        for k in range(ka, kf + 1):
            inflight[k] += 1
    run.kind = [min(n, 2) for n in inflight]
    return run


def collect(paths: List[str], workload: str = "") -> List[Run]:
    files = []
    for p in paths:
        files += sorted(glob.glob(os.path.join(p, "*.json"))) if os.path.isdir(p) else [p]
    return [r for r in (load(f, workload) for f in files) if r is not None]


def credited(run: Run, a: float, b: float):
    """(generated, prompt) tokens the window [a, b) is credited: a step's
    tokens stamped where the step ends, the prompts by the metric's own rule
    (``arith.tokens_in_window``: in proportion once a prefill has its
    first-token stamp, nothing before)."""
    gen = sum(g for t, d, g in zip(run.t0, run.tau, run.gen) if a <= t + d < b)
    prompts = [arith.Rec(due=0.0, prompt_len=r["plen"], new_tokens=0, t_submit=r["submit"], t_admit=r["admit"],
                         t_first_token=r["first"]) for r in run.recs]
    return gen, arith.tokens_in_window(prompts, a, b)


def _median(v):
    return statistics.median(v) if v else None


def row(run: Run) -> dict:
    W = run.W
    gen, prompt = credited(run, 0.0, W)
    inside = [k for k, t in enumerate(run.t0) if t >= 0 and t + run.tau[k] <= W]
    by_submit = sorted(range(len(run.recs)), key=lambda j: run.recs[j]["submit"])
    first_in = min((j for j in by_submit if run.recs[j]["admit"] is not None and run.recs[j]["admit"] >= 0),
                   key=lambda j: run.recs[j]["admit"], default=None)
    open_flight = [r for r in run.recs if r["admit"] is not None and r["admit"] < W and r["first"] is None]
    per_step = _median([r["plen"] / (run.index(r["first"]) - run.index(r["admit"]) + 1)
                        for r in run.recs if r["admit"] is not None and r["first"] is not None and r["admit"] >= 0])
    return {
        "run": run.name, "seed": run.seed,
        "reading": run.reported if run.reported is not None else (gen + prompt) / W,
        "recounted": (gen + prompt) / W, "gen_tok_s": gen / W, "steps": len(inside),
        "decode_step_p50_s": _median([run.tau[k] for k in inside if run.kind[k] == 0 and run.gen[k] > 0]),
        "chunk_step_p50_s": _median([run.tau[k] for k in inside if run.kind[k] == 1]),
        "finished": sum(1 for r in run.recs if r["counted"]), "prompt_tokens": prompt,
        "first_admitted": None if first_in is None else by_submit.index(first_in),
        "open_pos": run.pos(0.0), "close_pos": run.pos(W),
        # what the close left uncredited: prompts admitted before it that had no first token yet
        "in_flight_at_close": [[r["plen"], W - r["admit"]] for r in open_flight],
        "uncredited": sum(min(r["plen"], (per_step or 0) * (run.index(W) - run.index(r["admit"]) + 1))
                          for r in open_flight),
    }


def quartile_weights(n: int) -> List[float]:
    """w with sum(w[j] * sorted(v)[j]) == Q3 - Q1 of ``statistics.quantiles(v, n=4)``."""
    w = [0.0] * n
    for q, sign in ((1, -1.0), (3, 1.0)):
        p = q * (n + 1) / 4
        j = min(max(int(p), 1), n - 1)
        frac = p - j
        w[j - 1] += sign * (1 - frac)
        w[j] += sign * frac
    return w


def spread(values) -> float:
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def aligned(runs: List[Run]) -> bool:
    """The same work at the same step number in every run."""
    n = min(len(r.gen) for r in runs)
    return all(r.gen[:n] == runs[0].gen[:n] for r in runs)


def terms(runs: List[Run], rows: List[dict]):
    """Per run (mode, phase, rest) in the reading's unit, against the run
    nearest the set's median, and that run's name."""
    R = [r["reading"] for r in rows]
    med = statistics.median(R)
    m = min(range(len(runs)), key=lambda i: (abs(R[i] - med), R[i]))
    ref = runs[m]
    s, e = ref.pos(0.0), ref.pos(ref.W)
    ks = range(int(s), min(int(e), len(ref.tau) - 1) + 1)

    def share(k):     # how much of step k lies inside [s, e]
        return min(e, k + 1) - max(s, k)

    def step_ratios(run):
        """By kind of step, the median over the steps both windows hold of
        run's time over m's for the same step (the same work)."""
        lo, hi = max(run.index(0.0), ref.index(0.0)), min(run.index(run.W), ref.index(ref.W))
        return [_median([run.tau[k] / ref.tau[k] for k in range(lo, hi) if ref.kind[k] == c]) or 1.0 for c in (0, 1, 2)]

    out = []
    for i, run in enumerate(runs):
        ratio = step_ratios(run)
        scaled = sum(ref.tau[k] * ratio[ref.kind[k]] * share(k) for k in ks)
        own = sum((run.tau[k] if k < len(run.tau) - 1 else ref.tau[k] * ratio[ref.kind[k]]) * share(k) for k in ks)
        N = R[m] * ref.W
        mode = N / scaled - R[m]
        speed = N / own
        lo, hi = run.index(0.0), min(run.index(run.W), len(ref.tau) - 1)
        out.append({"mode": mode, "phase": R[i] - speed, "rest": speed - R[m] - mode,
                    "filled_steps": max(0, ks[-1] - (len(run.tau) - 2)), "decode_ratio": ratio[0],
                    # single steps that took STALL_S longer than the same step of the median run
                    "stalls": [[k, run.tau[k] - ref.tau[k]] for k in range(lo, hi) if run.tau[k] - ref.tau[k] > STALL_S]})
    return out, runs[m].name


def mode_runs(rows: List[dict]) -> List[str]:
    """Runs whose decode-only step median is MODE_RATIO or more over the fastest of the others'
    (not their median: on some machines most runs of a set are mode runs)."""
    d = [r["decode_step_p50_s"] for r in rows]
    return [r["run"] for i, r in enumerate(rows) if d[i] and len([x for x in d if x]) > 1
            and d[i] >= MODE_RATIO * min(x for j, x in enumerate(d) if j != i and x)]


def split(readings: List[float], per_run: List[dict]) -> dict:
    """The set's spread and its three terms (shares of the median; they add up)."""
    order = sorted(range(len(readings)), key=lambda i: readings[i])
    w = quartile_weights(len(readings))
    med = statistics.median(readings)
    out = {"spread": spread(readings), "median": med}
    for key in ("mode", "phase", "rest"):
        out[key] = sum(wj * per_run[i][key] for wj, i in zip(w, order)) / med
    return out


def trimmed(readings: List[float]) -> List[int]:
    """Indices kept when the run farthest from the median is left out, where
    that narrows the spread (the driver's tightness rule)."""
    if len(readings) < 4:
        return list(range(len(readings)))
    med = statistics.median(readings)
    far = max(range(len(readings)), key=lambda i: abs(readings[i] - med))
    keep = [i for i in range(len(readings)) if i != far]
    return keep if spread([readings[i] for i in keep]) < spread(readings) else list(range(len(readings)))


def analyse(runs: List[Run]) -> dict:
    rows = [row(r) for r in runs]
    R = [r["reading"] for r in rows]
    out = {"rows": rows, "spread": spread(R) if len(R) > 1 else None, "median": statistics.median(R)}
    keep = trimmed(R)
    out["trimmed_spread"] = spread([R[i] for i in keep]) if len(keep) > 1 else None
    out["left_out"] = [runs[i].name for i in range(len(runs)) if i not in keep]
    out["mode_runs"] = mode_runs(rows)
    if len(runs) < 2 or not aligned(runs):
        out["terms"] = None
        return out
    per_run, ref = terms(runs, rows)
    for r, t in zip(rows, per_run):
        r.update(t)
    out.update({"median_run": ref, "terms": split(R, per_run),
                "terms_trimmed": split([R[i] for i in keep], [per_run[i] for i in keep])})
    sound = [i for i in range(len(runs)) if runs[i].name not in out["mode_runs"]]
    if len(sound) > 1:
        out["terms_outside_mode"] = split([R[i] for i in sound], [per_run[i] for i in sound])
    return out


def _fmt(res: dict) -> str:
    pct = lambda x: "-" if x is None else f"{100 * x:+.3f}%"
    lines = ["run | reading | recounted | gen tok/s | steps | decode-only p50 ms | chunk p50 ms | finished | prompt tokens |"
             " first admitted (nth submitted) | open at step | close at step | in flight at close (prompt, s since admit)"
             " | about uncredited | mode | phase | rest | steps filled | stalls (step +s)"]
    for r in res["rows"]:
        fa = r["first_admitted"]
        lines.append(" | ".join(str(x) for x in (
            r["run"], f"{r['reading']:.2f}", f"{r['recounted']:.2f}", f"{r['gen_tok_s']:.2f}", r["steps"],
            "-" if r["decode_step_p50_s"] is None else f"{1e3 * r['decode_step_p50_s']:.3f}",
            "-" if r["chunk_step_p50_s"] is None else f"{1e3 * r['chunk_step_p50_s']:.3f}",
            r["finished"], f"{r['prompt_tokens']:.0f}", "-" if fa is None else fa,
            f"{r['open_pos']:.2f}", f"{r['close_pos']:.2f}",
            ", ".join(f"{p} {t:.3f}" for p, t in r["in_flight_at_close"]) or "none", f"{r['uncredited']:.0f}",
            *(f"{r[k]:+.2f}" if k in r else "-" for k in ("mode", "phase", "rest")), r.get("filled_steps", "-"),
            ", ".join(f"{k} +{x:.3f}" for k, x in r.get("stalls", [])) or "none")))
    lines.append(f"median {res['median']:.2f}; spread {pct(res['spread'])}; trimmed {pct(res['trimmed_spread'])}"
                 f" (left out: {', '.join(res['left_out']) or 'none'})")
    lines.append(f"mode runs: {', '.join(res['mode_runs']) or 'none'}")
    if res["terms"] is None:
        lines.append("no terms: the runs' steps do not line up by number (an open loop, or a single run)")
        return "\n".join(lines)
    lines.append(f"median run {res['median_run']}")
    for label, key in (("all runs", "terms"), ("trimmed", "terms_trimmed"), ("outside the mode", "terms_outside_mode")):
        t = res.get(key)
        if t:
            lines.append(f"{label}: spread {pct(t['spread'])} = mode {pct(t['mode'])} + phase {pct(t['phase'])}"
                         f" + rest {pct(t['rest'])}")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("dumps", nargs="+", help="--dump directories or files of ONE set")
    ap.add_argument("--workload", default="", help="the cell, where a directory holds dumps of several")
    ap.add_argument("--json", default="", help="write the rows and terms here as well")
    args = ap.parse_args(argv)
    runs = collect(args.dumps, args.workload)
    cells = sorted({r.cell for r in runs})
    if len(cells) != 1:
        print(f"spread: one set of one served cell at a time; found {cells or 'no served dump'} (--workload picks one)", file=sys.stderr)
        return 2
    res = analyse(runs)
    print(_fmt(res))
    if args.json:
        with open(args.json, "w") as f:
            json.dump(res, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
