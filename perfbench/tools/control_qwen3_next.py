"""The controls of the ``qwen3_next`` configuration's ``correct`` limits. Each
has to come out as NOT correct; the benchmark's own runs never run them.

    python3 perfbench/tools/control_qwen3_next.py --config qwen3-next-80b-ep8-l12-serve-1chip --seeds 1 2 3

For every seed, the cell's own set-up (the weights ``init_inference`` makes
from the seed, the server, the warm-up requests) and then:

- the program's served tokens read by the float32 reference as the cell reads
  them (``served``: this one is correct), and by a reference with one thing
  changed (``reference_qwen3_next.SKIPS``): the state kept in bfloat16
  (``state_bf16``: the nearest precision below), the delta term left out
  (``no_delta``), the decay ignored (``no_decay``), the state or the
  convolution's rows dropped wherever one call hands them to another
  (``state_edge``, ``conv_edge``: every 256th position, and the first row a
  decode step computes), an output gate left out (``no_out_gate``,
  ``no_attn_gate``), the shared expert's gate (``no_shared_gate``). A program
  that differed so would be as far from the full reference as the full program
  is from the changed one;
- ``reused_slot``: the warm-up requests served AGAIN, by the same server, in
  the slots the first pass left: this one has to read CORRECT (the programs
  start a request's state from zeros).

One line of JSON a seed: each reading's numbers beside the three limits
(``runners/serve_qwen3_next.Runner.reference_check``), the expert loads of the
warm-up's decode steps (``moe_load_max_over_mean``: whether the seeded routers
have favourites), and the controls that read correct, which has to be none.
``--dump DIR`` keeps every reading's arrays.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

import jax  # noqa: E402
import numpy as np  # noqa: E402

from perfbench import reference_qwen3_next as reference  # noqa: E402
from perfbench.tools import control_zaya  # noqa: E402

def readings(r, skips, reuse: bool = True, dump: str = "") -> dict:
    """One seed's line from a set-up runner ``r``: ``control_zaya.readings`` as
    it is (the served reading, each skip's, the used slots', the arrays to
    ``dump``), its int8 continuation left out: this configuration's precision
    control is the reference's own ``state_bf16``."""
    return control_zaya.readings(r, None, skips, (0, 0), reuse, dump)


def expert_loads(r) -> dict:
    """Over the warm-up's decode steps so far: the fullest held expert of a
    layer over the mean (``ds.serve.emit``'s ``moe_load_max`` against
    ``moe_pairs_held``), and the share of the routed pairs that were held."""
    from deepspeed_tpu.telemetry import spans

    emits = [a for n, _, _, a in spans.snapshot() if n == "ds.serve.emit" and a.get("moe_pairs_held")]
    per_step = r.mcfg.num_experts * r.mcfg.n_layer
    if not emits:
        return {}
    return {"moe_load_max_over_mean": float(np.mean([a["moe_load_max"] * per_step / a["moe_pairs_held"] for a in emits])),
            "held_share": float(sum(a["moe_pairs_held"] for a in emits) / sum(a["moe_pairs_routed"] for a in emits))}


def main(argv=None) -> int:
    from perfbench import run
    from perfbench.context import Context
    from perfbench.manifest import Manifest

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--skips", nargs="*", default=list(reference.SKIPS))
    ap.add_argument("--dump", default="", help="a directory for every reading's arrays, a file a seed")
    args = ap.parse_args(argv)
    m = Manifest(_ROOT)
    cfg = m.config(args.config)
    run.setup_jax_cache()
    _, peak = run.check_device(1, require_tpu=True)
    if args.dump:
        os.makedirs(args.dump, exist_ok=True)
    for seed in args.seeds:
        ctx = Context(cell={}, config=cfg, traffic={}, chips=1, peak=peak)
        r = m.runner(cfg["runner"]).Runner(ctx, seed, jax.devices()[:1], lambda name: None,
                                          lambda msg: print(f"[control] {msg}", file=sys.stderr, flush=True))
        r.setup()
        loads = expert_loads(r)
        dump = os.path.join(args.dump, f"seed_{seed}.npz") if args.dump else ""
        print(json.dumps({"seed": seed, **loads, **readings(r, args.skips, dump=dump)}), flush=True)
        r.srv.drain(0.0)
        del r, ctx
        gc.collect()      # the next seed's model needs this one's memory
    return 0


if __name__ == "__main__":
    sys.exit(main())
