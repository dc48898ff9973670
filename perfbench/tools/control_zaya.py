"""The controls of the ``zaya`` configuration's ``correct`` limits. Each has to
come out as NOT correct; the benchmark's own runs never run them.

    python3 perfbench/tools/control_zaya.py --config zaya1-8b-l14-serve-1chip --seeds 1 2 3

For every seed, the cell's own set-up (the weights ``init_inference`` makes
from the seed, the server, the warm-up requests) and then:

- the program's served tokens read by the float32 reference as the cell reads
  them (``served``: this one is correct), and by a reference with one thing
  changed (``reference_zaya.SKIPS``): what the convolutions and the value shift
  take from the rows before dropped wherever one call hands them to another of
  a different kind (``carry_edge``: every 256th position, and the first row a
  decode step computes; what a program that lost or misplaced its carried rows
  there reads), the value shift left out (``no_shift``),
  the q-k mean (``no_mean``), the second convolution (``no_conv1``), the
  router's depth state (``no_depth``), the experts' part (``no_experts``), the
  residual vectors (``no_res``). A program that differed so would be as far
  from the full reference as the full program is from the changed one;
- ``reused_slot``: the warm-up requests served AGAIN, by the same server,
  in the slots the first pass left: this one has to read CORRECT (the programs
  start a request's carried rows from zeros);
- ``int8``: the shortest and the longest warm-up prompt continued greedily
  (``--int8-tokens``) by a copy of the reference in which every matrix product
  takes both operands rounded to int8 (``tools/control.dot8``: the nearest
  precision below the configuration's bf16), read by the float32 reference.

One line of JSON a seed: each reading's numbers beside the three limits
(``runners/serve_zaya.Runner.reference_check``), and the controls that read
correct, which has to be none. ``--dump DIR`` keeps every reading's arrays.
"""

from __future__ import annotations

import argparse
import functools
import gc
import json
import os
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from perfbench import reference_zaya as reference  # noqa: E402
from perfbench.tools.control import dot8  # noqa: E402

KEEP = ("max_logit_gap", "mean_logit_gap", "handover_gap", "handover_largest", "left_out", "off_argmax", "positions")


@functools.partial(jax.jit, static_argnames=("arch",))
def next_token_int8(params, ids, n, *, arch):
    """Greedy next token after the first ``n`` of the padded ``ids``, by the
    reference with every product in int8; the vocabulary a block at a time."""
    h = jax.lax.dynamic_slice_in_dim(reference.hidden(params, ids, arch, dot=dot8)[0], n - 1, 1, 0)
    blocks = np.gcd(arch.vocab, reference.VOCAB_BLOCKS)
    rows = arch.vocab // blocks

    def block(best, b):
        lg = dot8(h, jax.lax.dynamic_slice_in_dim(params["embed"], b * rows, rows, 0).astype(jnp.float32).T)[0]
        i = jnp.argmax(lg)
        return jax.lax.cond(lg[i] > best[0], lambda: (lg[i], b * rows + i), lambda: best), None

    return jax.lax.scan(block, (jnp.float32(-jnp.inf), jnp.int32(0)), jnp.arange(blocks, dtype=jnp.int32))[0][1]


def int8_read(params, prompt, new_tokens: int, arch) -> dict:
    """One prompt continued greedily by the int8 reference, read by the
    float32 one: what ``Runner.served_gaps`` gives of a served request."""
    n_prompt = len(prompt)
    ids = np.zeros((-(-(n_prompt + new_tokens) // 128) * 128,), np.int32)
    ids[:n_prompt] = prompt
    for n in range(n_prompt, n_prompt + new_tokens):
        ids[n] = int(next_token_int8(params, jnp.asarray(ids), n, arch=arch))
    n_valid = n_prompt + new_tokens
    gap, std, ties = reference.served_gaps(params, jnp.asarray(ids), jnp.int32(n_prompt), jnp.int32(n_valid), arch=arch, rows=new_tokens)
    return {"gap": np.asarray(gap), "tie": np.asarray(ties).min(axis=0)[:n_valid], "n_prompt": n_prompt,
            "std": float(np.asarray(std).mean())}


def readings(r, arch, skips, int8_tokens, reuse: bool = True, dump: str = "") -> dict:
    """One seed's line from a set-up runner ``r``. ``int8_tokens``: how many
    tokens the int8 control continues the shortest and the longest warm-up
    prompt by (a pair; 0 leaves a prompt out). ``dump``: a file the readings'
    arrays go to, a position each (what the limits are set from)."""
    real, kept = r.served_gaps, {}

    def check(name, reads):
        """``reference_check`` over ``reads()``, which are kept under ``name``."""
        def once(skip=""):
            kept[name] = reads()
            return kept[name]
        r.served_gaps = once
        try:
            return r.reference_check()
        finally:
            del r.served_gaps      # the class's own again (an attribute here would keep the runner alive in a cycle)

    ok, served = check("served", real)
    out = {**{k: served[k] for k in ("margin", "mean_gap_limit", "gap_cap", "handover_margin", "tie_margin")},
           "served": {k: served[k] for k in KEEP}, "served_correct": ok, "logit_std": served["logit_std"]}
    correct = {}
    for skip in skips:
        correct[skip], notes = check(skip, lambda: real(skip))
        out[skip] = {k: notes[k] for k in KEEP}
    if any(int8_tokens):
        ends = (min(r.warm, key=lambda w: len(w.prompt)), max(r.warm, key=lambda w: len(w.prompt)))
        correct["int8"], notes = check("int8", lambda: [
            int8_read(r.engine.params, np.asarray(w.prompt, np.int32), n, arch) for w, n in zip(ends, int8_tokens) if n])
        out["int8"] = {k: notes[k] for k in KEEP}
    if reuse:
        # the same requests again, in slots that now hold the first pass's leavings
        first = r.warm
        r.warm = [r.srv.submit(np.asarray(w.prompt, np.int32), max_new_tokens=len(w.tokens), seed=i)
                  for i, w in enumerate(first)]
        r.srv.run()
        again, notes = r.reference_check()
        out["reused_slot"] = {k: notes[k] for k in KEEP}
        out["reused_slot_reads_correct"] = bool(again)          # has to be true
        out["reused_slot_same_tokens"] = all(list(a.tokens) == list(b.tokens) for a, b in zip(first, r.warm))
        r.warm = first
    if dump:
        np.savez_compressed(dump, **{f"{name}.{i}.{k}": np.asarray(v) for name, reads in kept.items()
                                     for i, x in enumerate(reads) for k, v in x.items()})
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
    ap.add_argument("--int8-tokens", type=int, nargs=2, default=(128, 64),
                    help="tokens the int8 control continues the shortest and the longest warm-up prompt by (0: leave it out)")
    ap.add_argument("--dump", default="", help="a directory for every reading's arrays, a file a seed")
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
        dump = os.path.join(args.dump, f"seed_{seed}.npz") if args.dump else ""
        print(json.dumps({"seed": seed, **readings(r, arch, args.skips, args.int8_tokens, dump=dump)}), flush=True)
        r.srv.drain(0.0)
        del r, ctx
        gc.collect()      # the next seed's model needs this one's memory
    return 0


if __name__ == "__main__":
    sys.exit(main())
