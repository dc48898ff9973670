"""The controls of the ``xing4_0`` configuration's ``correct`` limits. Each has
to come out as NOT correct; the benchmark's own runs never run them.

    python3 perfbench/tools/control_xing4.py --config xing4.0-29b-a4b-ep8-l20-serve-1chip --seeds 1 2 3

For every seed, the cell's own set-up (the weights ``init_inference`` makes
from the seed, the server, the two warm-up requests) and then:

- the program's served tokens read by the float32 reference as the cell reads
  them (``served``: this one is correct), and by a reference with one thing
  changed (``reference_xing4``'s ``skip``): ``H_res`` the identity
  (``hres_identity``), ``H_post`` without its 2 (``hpost_one``), the maps'
  dynamic part dropped (``static_maps``: the gains 0), one Sinkhorn round in
  place of 20 (``sinkhorn_1``), the statistic over ``E`` in place of ``n E``
  (``stat_E``), the maps computed in bfloat16 (``maps_bf16``), the rotary part
  of the score left out (``rope_score``), one layer's routed experts left out
  (``experts:<l>``), ``routed_scaling_factor`` 1 (``scale_1``). A program that
  differed so would be as far from the full reference as the full program is
  from the changed one;
- ``int8``: the SHORT warm-up prompt continued greedily (``--int8-tokens``)
  by a copy of the reference in which every matrix product takes both
  operands rounded to int8 (``tools/control.dot8``: the nearest precision
  below the configuration's bf16), read by the float32 reference.

One line of JSON a seed: each reading's largest and mean gap beside the two
limits (``runners/serve_mistral4.Runner.reference_check``), and the controls
that read correct (``controls_read_correct``): what is listed there is a
READING, to be written down as one, or a limit that is too wide.
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

from perfbench import reference_xing4 as reference  # noqa: E402
from perfbench.tools.control import dot8  # noqa: E402

SKIPS = ("hres_identity", "hpost_one", "static_maps", "sinkhorn_1", "stat_E", "maps_bf16", "rope_score", "scale_1")


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


CAPS = (0.25, 0.5)   # the mean gap under these caps beside the plain one: what a capped limit would read


def _stats(gaps, cap) -> dict:
    out = {"max_logit_gap": float(gaps.max()), "mean_logit_gap": float(np.minimum(gaps, cap).mean()),
           "uncapped_mean_logit_gap": float(gaps.mean()), "off_argmax": int((gaps > 0).sum()), "positions": int(len(gaps))}
    out.update({f"mean_capped_{c}": float(np.minimum(gaps, c).mean()) for c in CAPS})
    return out


def readings(r, skips, int8_tokens: int = 0) -> dict:
    """The set-up runner ``r``'s served tokens read by the reference and by
    each control → the line's dict: each reading's gaps' statistics, and
    which read correct by the configuration's two limits
    (``runners/serve_mistral4.Runner.reference_check``)."""
    ref = r.cfg["reference"]
    margin, limit = float(ref["logit_margin"]), float(ref.get("mean_gap_limit", "inf"))
    cap = float(ref.get("gap_cap", "inf"))
    inside = lambda st: st["max_logit_gap"] <= margin and st["mean_logit_gap"] <= limit  # noqa: E731
    gaps, stds = r.served_gaps()
    out = {"seed": r.seed, "margin": margin, "mean_gap_limit": limit, "gap_cap": cap, "served": _stats(gaps, cap),
           "logit_std": stds}
    out["served_correct"] = inside(out["served"])
    for skip in skips:
        out[skip] = _stats(r.served_gaps(skip)[0], cap)
    if int8_tokens:
        short = min(r.warm, key=lambda w: len(w.prompt))
        out["int8"] = _stats(int8_gap(r.engine.params, np.asarray(short.prompt, np.int32), int8_tokens,
                                      reference.Arch.from_config(r.cfg)), cap)
    out["controls_read_correct"] = sorted(k for k in (*skips, *(["int8"] if int8_tokens else [])) if inside(out[k]))
    return out


def main(argv=None) -> int:
    from perfbench import run
    from perfbench.context import Context
    from perfbench.manifest import Manifest

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--experts-layer", type=int, default=7, help="the layer whose routed experts the control leaves out")
    ap.add_argument("--int8-tokens", type=int, default=64,
                    help="tokens the int8 control continues the short warm-up prompt by (0: leave it out)")
    args = ap.parse_args(argv)
    m = Manifest(_ROOT)
    cfg = m.config(args.config)
    run.setup_jax_cache()
    _, peak = run.check_device(1, require_tpu=True)
    for seed in args.seeds:
        ctx = Context(cell={}, config=cfg, traffic={}, chips=1, peak=peak)
        r = m.runner(cfg["runner"]).Runner(ctx, seed, jax.devices()[:1], lambda name: None,
                                          lambda msg: print(f"[control] {msg}", file=sys.stderr, flush=True))
        r.setup()
        print(json.dumps(readings(r, [*SKIPS, f"experts:{args.experts_layer}"], args.int8_tokens)), flush=True)
        r.srv.drain(0.0)
        del r, ctx
    return 0


if __name__ == "__main__":
    sys.exit(main())
