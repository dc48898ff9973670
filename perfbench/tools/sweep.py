"""Several windows of one served cell in ONE process, for the builder: the
knee sweep (one window per rate) and the spread diagnosis (windows that differ
only in seed, or in a key of the mix such as ``order_seed``, or not at all). The server is built and warmed once, so a window
costs its own length and not a set-up. Not what the driver runs; a bound is
never set from this tool alone, because it shares one process's weights and
set-up among its windows.

    python3 perfbench/tools/sweep.py --workload serve-xl-chat-open --seconds 30 \
        --plan "rate_rps=0.8,seed=1;rate_rps=1.1,seed=1" --out chiprun_out/sweep.json

A plan entry overrides top-level keys of the cell's traffic file (numbers) and
gives the window's seed (token ids; the weights stay those of the first seed).
"""

from __future__ import annotations

import argparse
import copy
import json
import os
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, _ROOT)


def main(argv=None) -> int:
    from perfbench import arith, dump, run
    from perfbench.context import Context
    from perfbench.manifest import Manifest

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--plan", required=True)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)

    m = Manifest(_ROOT)
    cell = m.cell(args.workload)
    cfg = m.config(cell["config"])
    base = m.traffic(cell["traffic"])
    run.setup_jax_cache()
    devs, peak = run.check_device(int(cell["chips"]), require_tpu=True)
    plan = []
    for item in args.plan.split(";"):
        kv = dict(p.split("=") for p in item.split(",") if p)
        plan.append({k: (int(v) if k == "seed" else float(v)) for k, v in kv.items()})
    ctx = Context(cell=cell, config=cfg, traffic=base, chips=1, peak=peak)
    runner = m.runner(cfg["runner"]).Runner(ctx, plan[0].get("seed", 0), devs[:1], run._span_factory(False), run.log)
    runner.setup()
    tracer = run.TraceCtl(False, 0.0, "")
    rows = []
    for p in plan:
        tr = copy.deepcopy(base)
        tr.update({k: v for k, v in p.items() if k != "seed"})
        ctx = Context(cell=cell, config=cfg, traffic=tr, chips=1, peak=peak)
        runner.ctx, runner.seed = ctx, int(p.get("seed", 0))
        runner.live, runner.done, runner.counted = [], [], {}
        runner.srv._draining = False   # drain() is terminal for a server; this tool reopens it between windows
        runner.measure(args.seconds, tracer)
        correct, attempted, failed, notes = runner.finish_counts()
        row = dict(p)
        row.update({"attempted": attempted, "failed": failed, "leak": runner.leak})
        for grp in ("end_to_end", "per_layer"):
            for met in m.metrics_for(args.workload, grp):
                if met["name"] == "setup_s" or met["source"] == "device_trace":
                    continue
                spec = m.metric_spec(met["name"])
                row[met["name"]] = m.reader(spec["reader"]).read(ctx, **spec.get("args", {}))
        t0, t1 = ctx.window
        row["tokens_in_window_per_s"] = arith.tokens_in_window(ctx.recs, t0, t1) / (t1 - t0)
        late = [s for s in ctx.steps if s.t0 >= t1 - 2.0 and s.t1 <= t1]
        row["queue_at_end"] = late[-1].info["queue"] if late else None
        row["ttft_p95"] = arith.quantile([r.t_first_token - r.due for r in ctx.recs if r.counted and r.t_first_token], 0.95)
        row["summary"] = dump.summary(ctx)
        row["recs"] = [
            {"due": r.due - t0, "plen": r.prompt_len, "admit": None if r.t_admit is None else r.t_admit - t0,
             "em": [round(t - t0, 5) for t in r.t_emissions], "status": r.status}
            for r in ctx.recs
        ]
        rows.append(row)
        print(json.dumps({k: v for k, v in row.items() if k not in ("summary", "recs")}), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(rows, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
