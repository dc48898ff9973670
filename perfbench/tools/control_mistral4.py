"""The controls of the ``mistral4`` configuration's ``correct`` limits. Each
has to come out as NOT correct; the benchmark's own runs never run them.

    python3 perfbench/tools/control_mistral4.py --config mistral-small-4-119b-ep8-serve-1chip --seeds 1 2 3

For every seed, the cell's own set-up (the weights ``init_inference`` makes
from the seed, the server, the two warm-up requests) and then:

- the program's served tokens read by the float32 reference as the cell reads
  them (``served``: this one is correct), and by a reference with one thing
  changed: the rotary part of the score left out (``rope_score``), plain
  rotary in yarn's place (``yarn``), the query's position scale 1
  (``qscale``), the latent's norm skipped (``latent_norm``), one layer's
  routed experts left out (``experts:<l>``), the cached rows rounded to 8 bits
  (``fp8_rows``: ``float8_e4m3fn``). A program that differed so would be as
  far from the full reference as the full program is from the changed one.
  ``int8_rows`` (int8 codes, a scale a row) is read beside them and is NOT
  held to read incorrect: the gaps cannot tell it from bf16 (PERF.md, PR 34);
- ``int8``: the SHORT warm-up prompt continued greedily (``--int8-tokens``)
  by a copy of the reference in which every matrix product takes both
  operands rounded to int8 (``tools/control.dot8``: the nearest precision
  below the configuration's bf16), read by the float32 reference. (The long
  prompt is left out: a token costs a forward over 9k positions.)

One line of JSON a seed: each reading's largest and mean gap beside the two
limits (``runners/serve_mistral4.Runner.reference_check``), and the controls
that read correct, which has to be none.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from perfbench import reference_mistral4 as reference  # noqa: E402
from perfbench.tools.control import dot8  # noqa: E402


@functools.partial(jax.jit, static_argnames=("arch",))
def next_token_int8(params, ids, n, *, arch):
    """Greedy next token after the first ``n`` of the padded ``ids``, by the
    reference with every product in int8."""
    return jnp.argmax(reference.logits(params, ids, arch, dot=dot8)[n - 1])


def int8_gap(params, prompt, new_tokens: int, arch):
    n_prompt = len(prompt)
    ids = np.zeros((-(-(n_prompt + new_tokens) // 128) * 128,), np.int32)
    ids[:n_prompt] = prompt
    for n in range(n_prompt, n_prompt + new_tokens):
        ids[n] = int(next_token_int8(params, jnp.asarray(ids), n, arch=arch))
    gap, _ = reference.served_gaps(params, jnp.asarray(ids), n_prompt, n_prompt + new_tokens, arch=arch)
    return np.asarray(gap)[n_prompt - 1: n_prompt + new_tokens - 1]


def main(argv=None) -> int:
    from perfbench import run
    from perfbench.context import Context
    from perfbench.manifest import Manifest

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--experts-layer", type=int, default=3, help="the layer whose routed experts the control leaves out")
    ap.add_argument("--dump-gaps", default="", help="write every reading's gaps, position by position, here (a .jsonl)")
    ap.add_argument("--int8-tokens", type=int, default=64,
                    help="tokens the int8 control continues the short warm-up prompt by (0: leave it out)")
    args = ap.parse_args(argv)
    m = Manifest(_ROOT)
    cfg = m.config(args.config)
    run.setup_jax_cache()
    _, peak = run.check_device(1, require_tpu=True)
    arch = reference.Arch.from_config(cfg)
    skips = ["rope_score", "yarn", "qscale", "latent_norm", f"experts:{args.experts_layer}", "fp8_rows", "int8_rows"]
    for seed in args.seeds:
        ctx = Context(cell={}, config=cfg, traffic={}, chips=1, peak=peak)
        r = m.runner(cfg["runner"]).Runner(ctx, seed, jax.devices()[:1], lambda name: None,
                                          lambda msg: print(f"[control] {msg}", file=sys.stderr, flush=True))
        r.setup()
        ok, served = r.reference_check()
        keep = ("max_logit_gap", "mean_logit_gap", "uncapped_mean_logit_gap", "off_argmax", "positions")
        out = {"seed": seed, "margin": served["margin"], "mean_gap_limit": served["mean_gap_limit"],
               "served": {k: served[k] for k in keep}, "served_correct": ok, "logit_std": served["logit_std"]}
        correct = {}
        raw = {"seed": seed, "served": [float(g) for g in r.served_gaps()[0]]}
        for skip in skips:
            correct[skip], notes = r.reference_check(skip=skip)
            out[skip] = {k: notes[k] for k in keep}
            if args.dump_gaps:
                raw[skip] = [float(g) for g in r.served_gaps(skip)[0]]
        if args.int8_tokens:
            short = min(r.warm, key=lambda w: len(w.prompt))
            gaps = int8_gap(r.engine.params, np.asarray(short.prompt, np.int32), args.int8_tokens, arch)
            out["int8"] = {"max_logit_gap": float(gaps.max()),
                           "mean_logit_gap": float(np.minimum(gaps, served["gap_cap"]).mean()),
                           "uncapped_mean_logit_gap": float(gaps.mean()),
                           "off_argmax": int((gaps > 0).sum()), "positions": int(len(gaps))}
            correct["int8"] = out["int8"]["max_logit_gap"] <= out["margin"] and out["int8"]["mean_logit_gap"] <= out["mean_gap_limit"]
        out["controls_read_correct"] = sorted(k for k, v in correct.items() if v and k != "int8_rows")   # has to be empty
        out["int8_rows_reads_correct"] = bool(correct["int8_rows"])   # a reading, either way
        print(json.dumps(out), flush=True)
        if args.dump_gaps:
            if args.int8_tokens:
                raw["int8"] = [float(g) for g in gaps]
            with open(args.dump_gaps, "a") as f:
                f.write(json.dumps(raw) + "\n")
        r.srv.drain(0.0)
        del r, ctx
    return 0


if __name__ == "__main__":
    sys.exit(main())
