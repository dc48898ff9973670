"""Writes what a run saw, for the builder who has to find where a spread comes
from: per-request stamps, per-step records, and the names in the trace. Used
by ``run.py --dump`` and ``tools/sweep.py``; the driver never asks for it."""

from __future__ import annotations

import json
import os


def summary(ctx) -> dict:
    from perfbench import arith

    t0, t1 = ctx.window
    gaps = arith.pooled_gaps(ctx.recs, t0, t1)
    hist = {}
    for g in gaps:
        k = round(g, 2)
        hist[k] = hist.get(k, 0) + 1
    return {
        "requests": len(ctx.recs), "counted": sum(1 for r in ctx.recs if r.counted),
        "steps": len(ctx.steps), "gaps": len(gaps),
        "gap_hist_10ms": {f"{k:.2f}": v for k, v in sorted(hist.items())},
        "moe": moe_counts(ctx),
    }


def moe_counts(ctx) -> dict:
    """What the expert layers did over the whole window, from the program's
    ``ds.serve.emit`` counters (PERF.md section 3): the mean, a decode step, of
    the held experts a token reached (summed over layers), of the pairs
    computed here and of the fullest expert's tokens. The routing follows the
    weights and the token ids, so it is the one part of a backlog cell's work
    that ``--seed`` reaches. Empty where the program reports no such counter."""
    from perfbench import program_spans

    emits = [r[3] for r in program_spans.records_in(ctx.window) or ()
             if r[0] == "ds.serve.emit" and r[3].get("moe_pairs_held")]
    keys = ("moe_experts_hit", "moe_experts_streamed", "moe_pairs_held", "moe_load_max")
    return {k: sum(a[k] for a in emits) / len(emits) for k in keys if emits and all(k in a for a in emits)}


def write(out_dir: str, out: dict, ctx) -> None:
    os.makedirs(out_dir, exist_ok=True)
    tag = f"{out['cell']}.s{out['seed']}.t{int('breakdown' in out)}"
    t0 = ctx.window[0]
    doc = {
        "result": out, "summary": summary(ctx),
        "recs": [
            {"due": r.due - t0, "plen": r.prompt_len, "new": r.new_tokens, "submit": r.t_submit - t0,
             "admit": None if r.t_admit is None else r.t_admit - t0,
             "first": None if r.t_first_token is None else r.t_first_token - t0,
             "last": (r.t_emissions[-1] - t0) if r.t_emissions else None,
             "n": r.n_tokens, "status": r.status, "counted": r.counted}
            for r in ctx.recs
        ],
        "steps": [[s.kind, s.t0 - t0, s.t1 - s.t0, s.info] for s in ctx.steps],
    }
    if ctx.trace is not None:
        tr = ctx.trace
        doc["trace"] = {
            "window_s": tr.window_s, "busy_s": tr.busy_s, "n_devices": tr.n_devices, "lines": tr.line_names,
            "ops": sorted(tr.op_seconds.items(), key=lambda kv: -kv[1])[:80],
            "modules": {k: [len(v), sum(v) / len(v)] for k, v in tr.module_durations.items()},
            "idle_by_span": tr.idle_by_span,
        }
    with open(os.path.join(out_dir, tag + ".json"), "w") as f:
        json.dump(doc, f)
