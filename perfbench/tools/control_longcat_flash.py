"""The controls of the ``longcat_flash`` configuration's ``correct`` limits.
Each has to come out as NOT correct; the benchmark's own runs never run them.

    python3 perfbench/tools/control_longcat_flash.py --config longcat-flash-560b-ep32-serve-1chip --seeds 1 2 3

For every seed, the cell's own set-up (the weights ``init_inference`` makes
from the seed, the server, the two warm-up requests) and then:

- the program's served tokens read by the float32 reference as the cell reads
  them (``served``: this one is correct), and by a reference with one thing
  changed (``reference_longcat_flash.SKIPS``): the shortcut's ``m`` left out
  (``shortcut``), ``m`` taken from ``u2`` instead of ``u1`` (``moe_from_u2``),
  the identity experts' term left out (``identity``), sigmoid in softmax's
  place (``sigmoid``), the weights renormalised over the picks (``renorm``),
  ``routed_scaling_factor`` 1 (``scale1``), the query's or the latent's scale
  1 (``s_q``, ``s_kv``), the second attention skipped (``attn2``), the rotary
  part of the score left out (``rope_score``). A program that differed so
  would be as far from the full reference as the full program is from the
  changed one. ``experts:<l>`` (one double layer's HELD experts left out: one
  selected pair in 48) is read beside them and is NOT held to read incorrect;
- ``int8``: the SHORT warm-up prompt continued greedily (``--int8-tokens``)
  by a copy of the reference in which every matrix product takes both
  operands rounded to int8 (``tools/control.dot8``: the nearest precision
  below the configuration's bf16), read by the float32 reference.

One line of JSON a seed: each reading's largest and mean gap beside the two
limits (``runners/serve_longcat_flash.Runner.reference_check``), and the
controls that read correct, which has to be none.
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

from perfbench import reference_longcat_flash as reference  # noqa: E402
from perfbench.tools.control import dot8  # noqa: E402

KEEP = ("max_logit_gap", "mean_logit_gap", "uncapped_mean_logit_gap", "off_argmax", "positions")


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


def readings(r, arch, skips, int8_tokens: int) -> dict:
    """One seed's line from a set-up runner ``r``."""
    ok, served = r.reference_check()
    out = {"margin": served["margin"], "mean_gap_limit": served["mean_gap_limit"],
           "served": {k: served[k] for k in KEEP}, "served_correct": ok, "logit_std": served["logit_std"]}
    correct = {}
    for skip in skips:
        correct[skip], notes = r.reference_check(skip=skip)
        out[skip] = {k: notes[k] for k in KEEP}
    if int8_tokens:
        short = min(r.warm, key=lambda w: len(w.prompt))
        gaps = int8_gap(r.engine.params, np.asarray(short.prompt, np.int32), int8_tokens, arch)
        out["int8"] = {"max_logit_gap": float(gaps.max()),
                       "mean_logit_gap": float(np.minimum(gaps, served["gap_cap"]).mean()),
                       "uncapped_mean_logit_gap": float(gaps.mean()),
                       "off_argmax": int((gaps > 0).sum()), "positions": int(len(gaps))}
        correct["int8"] = out["int8"]["max_logit_gap"] <= out["margin"] and out["int8"]["mean_logit_gap"] <= out["mean_gap_limit"]
    out["controls_read_correct"] = sorted(k for k, v in correct.items() if v and not k.startswith("experts:"))  # has to be empty
    out["held_experts_left_out_reads_correct"] = {k: bool(v) for k, v in correct.items() if k.startswith("experts:")}
    return out


def main(argv=None) -> int:
    from perfbench import run
    from perfbench.context import Context
    from perfbench.manifest import Manifest

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--experts-layer", type=int, default=2, help="the double layer whose held experts the reading leaves out")
    ap.add_argument("--int8-tokens", type=int, default=64,
                    help="tokens the int8 control continues the short warm-up prompt by (0: leave it out)")
    args = ap.parse_args(argv)
    m = Manifest(_ROOT)
    cfg = m.config(args.config)
    run.setup_jax_cache()
    _, peak = run.check_device(1, require_tpu=True)
    arch = reference.Arch.from_config(cfg)
    skips = list(reference.SKIPS) + [f"experts:{args.experts_layer}"]
    for seed in args.seeds:
        ctx = Context(cell={}, config=cfg, traffic={}, chips=1, peak=peak)
        r = m.runner(cfg["runner"]).Runner(ctx, seed, jax.devices()[:1], lambda name: None,
                                          lambda msg: print(f"[control] {msg}", file=sys.stderr, flush=True))
        r.setup()
        print(json.dumps({"seed": seed, **readings(r, arch, skips, args.int8_tokens)}), flush=True)
        r.srv.drain(0.0)
        del r, ctx
    return 0


if __name__ == "__main__":
    sys.exit(main())
