"""The controls of the ``phi4flash`` configuration's ``correct`` limits. Each
has to come out as NOT correct; the benchmark's own runs never run them.

    python3 perfbench/tools/control_phi4flash.py --config phi-4-mini-flash-serve-1chip --seeds 1 2 3

For every seed, the cell's own set-up (the weights ``init_inference`` makes
from the seed, the server, the two warm-up requests) and then:

- the program's served tokens read by the float32 reference as the cell reads
  them (``served``: this one is correct), and by a reference with one thing
  changed (``reference_phi4flash.SKIPS``): the recurrence left out, ``s_t = D
  . c_t`` (``no_state``: what the limits see of the scan state at all), the
  scan state kept in bfloat16 (``state_bf16``), every Mamba layer started from the state and convolution
  rows the sequence itself ends with, as a slot would be that was not zeroed
  at admission (``state_dirty``), the lambda term left out (``lambda``), the
  pair norm left out (``subln``), the memory taken after the gate
  (``mem_gated``), ``D`` left out (``no_d``), a window layer reading every key
  (``window``), the convolution's carried rows dropped at a chunk edge
  (``conv_edge``: the 1 024-token prompt has three), a cross layer attending
  layer 17's projections of its own input (``cross_own``). A program that
  differed so would be as far from the full reference as the full program is
  from the changed one;
- ``reused_slot``: the two warm-up requests served AGAIN, by the same server,
  in the slots the first pass left (the state-not-zeroed control's other
  half: this one has to read CORRECT, the program zeroes at admission);
- ``int8``: the SHORT warm-up prompt continued greedily (``--int8-tokens``)
  by a copy of the reference in which every matrix product takes both
  operands rounded to int8 (``tools/control.dot8``: the nearest precision
  below the configuration's bf16), read by the float32 reference.

One line of JSON a seed: each reading's largest and mean gap beside the two
limits (``runners/serve_phi4flash.Runner.reference_check``), and the controls
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

from perfbench import reference_phi4flash as reference  # noqa: E402
from perfbench.tools.control import dot8  # noqa: E402

KEEP = ("max_logit_gap", "mean_logit_gap", "off_argmax", "positions")


@functools.partial(jax.jit, static_argnames=("arch",))
def next_token_int8(params, ids, n, *, arch):
    """Greedy next token after the first ``n`` of the padded ``ids``, by the
    reference with every product in int8; the vocabulary a block at a time."""
    h = jax.lax.dynamic_slice_in_dim(reference.hidden(params, ids, arch, dot=dot8), n - 1, 1, 0)
    blocks = np.gcd(arch.vocab, reference.VOCAB_BLOCKS)
    rows = arch.vocab // blocks

    def block(best, b):
        lg = dot8(h, jax.lax.dynamic_slice_in_dim(params["embed"], b * rows, rows, 0).astype(jnp.float32).T)[0]
        i = jnp.argmax(lg)
        return jax.lax.cond(lg[i] > best[0], lambda: (lg[i], b * rows + i), lambda: best), None

    return jax.lax.scan(block, (jnp.float32(-jnp.inf), jnp.int32(0)), jnp.arange(blocks, dtype=jnp.int32))[0][1]


def int8_gap(params, prompt, new_tokens: int, arch):
    n_prompt = len(prompt)
    ids = np.zeros((-(-(n_prompt + new_tokens) // 256) * 256,), np.int32)
    ids[:n_prompt] = prompt
    for n in range(n_prompt, n_prompt + new_tokens):
        ids[n] = int(next_token_int8(params, jnp.asarray(ids), n, arch=arch))
    gap, _ = reference.served_gaps(params, jnp.asarray(ids), n_prompt, n_prompt + new_tokens, arch=arch)
    return np.asarray(gap)[n_prompt - 1: n_prompt + new_tokens - 1]


def _line(gaps) -> dict:
    return {"max_logit_gap": float(gaps.max()), "mean_logit_gap": float(gaps.mean()),
            "off_argmax": int((gaps > 0).sum()), "positions": int(len(gaps))}


def readings(r, arch, skips, int8_tokens: int, reuse: bool = True) -> dict:
    """One seed's line from a set-up runner ``r``."""
    ok, served = r.reference_check()
    margin, limit = served["margin"], served["mean_gap_limit"]
    out = {"margin": margin, "mean_gap_limit": limit,
           "served": {k: served[k] for k in KEEP}, "served_correct": ok, "logit_std": served["logit_std"]}
    correct = {}
    for skip in skips:
        correct[skip], notes = r.reference_check(skip=skip)
        out[skip] = {k: notes[k] for k in KEEP}
    if int8_tokens:
        short = min(r.warm, key=lambda w: len(w.prompt))
        out["int8"] = _line(int8_gap(r.engine.params, np.asarray(short.prompt, np.int32), int8_tokens, arch))
        correct["int8"] = out["int8"]["max_logit_gap"] <= margin and out["int8"]["mean_logit_gap"] <= limit
    if reuse:
        # the same two requests again, in slots that now hold the first pass's leavings
        first = r.warm
        r.warm = [r.srv.submit(np.asarray(w.prompt, np.int32), max_new_tokens=len(w.tokens), seed=i)
                  for i, w in enumerate(first)]
        r.srv.run()
        again, notes = r.reference_check()
        out["reused_slot"] = {k: notes[k] for k in KEEP}
        out["reused_slot_reads_correct"] = bool(again)          # has to be true
        out["reused_slot_same_tokens"] = all(list(a.tokens) == list(b.tokens) for a, b in zip(first, r.warm))
        r.warm = first
    out["controls_read_correct"] = sorted(k for k, v in correct.items() if v)  # has to be empty
    return out


def main(argv=None) -> int:
    from perfbench import run
    from perfbench.context import Context
    from perfbench.manifest import Manifest

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--skips", nargs="*", default=list(reference.SKIPS))
    ap.add_argument("--int8-tokens", type=int, default=64,
                    help="tokens the int8 control continues the short warm-up prompt by (0: leave it out)")
    args = ap.parse_args(argv)
    m = Manifest(_ROOT)
    cfg = m.config(args.config)
    run.setup_jax_cache()
    _, peak = run.check_device(1, require_tpu=True)
    arch = reference.Arch.from_config(cfg)
    for seed in args.seeds:
        ctx = Context(cell={}, config=cfg, traffic={}, chips=1, peak=peak)
        r = m.runner(cfg["runner"]).Runner(ctx, seed, jax.devices()[:1], lambda name: None,
                                          lambda msg: print(f"[control] {msg}", file=sys.stderr, flush=True))
        r.setup()
        print(json.dumps({"seed": seed, **readings(r, arch, args.skips, args.int8_tokens)}), flush=True)
        r.srv.drain(0.0)
        del r, ctx
    return 0


if __name__ == "__main__":
    sys.exit(main())
