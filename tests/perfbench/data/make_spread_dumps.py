"""How ``tests/perfbench/data/spread/*.json`` were made (kept so that they can
be made again; no test runs this). Five dumps of ``serve-xl-doc-batch``
recorded on the chip (PR 28's second set on the parent's runner, ``run.py
--dump``; 140 KB each) are
cut to a 20 s window and to the keys ``perfbench/tools/spread.py`` reads:
three as they were, one with every step from the start of the ramp made 8%
longer (what a whole-run mode does; its window then opens earlier in the
cycle, as a real one's does), one with its window opened 1.3 s late (a phase
shift and nothing else). The reading of each is counted again over its cut
window by the metric's rule.

    python3 tests/perfbench/data/make_spread_dumps.py <dir of dumps> tests/perfbench/data/spread
"""

import glob
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))))

from perfbench.tools import spread  # noqa: E402

W = 20.0
FIRST = 120   # the same first step in every file (about 4 s before the opening), so that step numbers still line up


def cut(doc, name, open_at=0.0, stretch=1.0):
    steps = [s for s in doc["steps"] if s[0] == "srv.step"]
    origin = steps[0][1]

    def t(x):   # stretched about the start of the ramp, then counted from the new opening
        return None if x is None else round(origin + stretch * (x - origin) - open_at, 6)

    kept = [["srv.step", t(s[1]), round(stretch * s[2], 6),
             {"decode_tokens": s[3]["decode_tokens"], "first_tokens": s[3]["first_tokens"]}] for s in steps]
    last = next(k for k, s in enumerate(kept) if s[1] + s[2] >= W)   # the step that straddles the close ends the run
    end = kept[last][1] + kept[last][2]
    recs = []
    for r in doc["recs"]:
        if t(r["submit"]) > end:
            continue
        q = {"plen": r["plen"], "new": r["new"], "n": r["n"], "submit": t(r["submit"])}
        for key in ("admit", "first", "last"):
            v = t(r[key])
            q[key] = v if v is not None and v <= end else None
        q["counted"] = q["last"] is not None and r["n"] == r["new"] and 0 <= q["last"] < W
        recs.append(q)
    out = {"result": {"cell": doc["result"]["cell"], "seed": doc["result"]["seed"], "seconds": W, "metrics": {}},
           "first_step": FIRST, "recs": recs, "steps": kept[FIRST:last + 1]}
    gen, prompt = spread.credited(spread.from_doc(out, name), 0.0, W)
    out["result"]["metrics"]["serve_tok_s"] = {"value": (gen + prompt) / W, "unit": "tokens/s"}
    path = os.path.join(sys.argv[2], name + ".json")
    with open(path, "w") as f:
        json.dump(out, f, separators=(",", ":"))
    print(name, out["result"]["metrics"]["serve_tok_s"]["value"], len(out["steps"]), len(recs), os.path.getsize(path))


def main():
    files = sorted(glob.glob(os.path.join(sys.argv[1], "serve-xl-doc-batch.*.t0.json")))
    docs = [json.load(open(f)) for f in files[:5]]
    os.makedirs(sys.argv[2], exist_ok=True)
    cut(docs[0], "sound-a")
    cut(docs[1], "sound-b")
    cut(docs[2], "sound-c")
    cut(docs[3], "mode", stretch=1.08)
    cut(docs[4], "phase", open_at=1.3)


if __name__ == "__main__":
    main()
