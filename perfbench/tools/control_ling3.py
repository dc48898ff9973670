"""The controls of the ``bailing_hybrid`` configuration's (Ling-3.0-flash)
``correct`` limits. Each has to come out as NOT correct, or is written down as
a reading; the benchmark's own runs never run them.

    python3 perfbench/tools/control_ling3.py --config ling-3.0-flash-ep8-l12-serve-1chip --seeds 1 2 3

For every seed, the cell's own set-up (the weights ``init_inference`` makes
from the seed, the server, the warm-up requests) and then:

- the program's served tokens read by the float32 reference as the cell reads
  them (``served``: this one is correct), and by a reference with one thing
  changed (``reference_ling3.SKIPS`` and ``experts:<l>``): the state kept in
  bfloat16 (``state_bf16``: the nearest precision below), ONE decay a head
  (``scalar_decay``: Qwen3-Next's rule under this model's name), the gate
  without its bound (``no_bound``), beta 1, the convolution left out, the state
  or the convolution's rows dropped at every hand-over (``state_edge``,
  ``conv_edge``), the group limit left out or scored by the top 1, the bias in
  the weights, ``routed_scaling_factor`` 1, the head-wise gate left out, rotary
  left out of the latent score, one layer's routed experts left out. A program
  that differed so would be as far from the full reference as the full program
  is from the changed one;
- ``int8``: the SHORT warm-up prompt continued greedily (``--int8-tokens``) by
  a copy of the reference in which every matrix product takes both operands
  rounded to int8 (``tools/control.dot8``: the nearest precision below the
  configuration's bf16; the delta rule's state stays float32), read by the
  float32 reference;
- ``reused_slot``: the warm-up requests served AGAIN, by the same server, in
  the slots the first pass left: this one has to read CORRECT.

One line of JSON a seed: each reading's numbers beside the three limits
(``runners/serve_qwen3_next.Runner.reference_check``), the expert loads of the
warm-up's decode steps, and the controls that read correct: what is listed
there is a READING, to be written down as one, or a limit that is too wide.
``--dump DIR`` keeps every reading's arrays.
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

from perfbench import reference_ling3 as reference  # noqa: E402
from perfbench.tools import control_zaya  # noqa: E402
from perfbench.tools.control import dot8  # noqa: E402


@functools.partial(jax.jit, static_argnames=("arch",))
def next_token_int8(params, ids, n, *, arch):
    """Greedy next token after the first ``n`` of the padded ``ids``, by the
    reference with every product in int8."""
    h = jax.lax.dynamic_slice_in_dim(reference.hidden(params, ids, arch, dot=dot8)[0], n - 1, 1, 0)
    return jnp.argmax(dot8(h, params["head"].astype(jnp.float32))[0, : arch.vocab])


def int8_read(params, prompt, new_tokens: int, arch) -> dict:
    """One prompt continued greedily by the int8 reference, read by the
    float32 one: what ``Runner.served_gaps`` gives of a served request."""
    n_prompt = len(prompt)
    ids = np.zeros((-(-(n_prompt + new_tokens) // 256) * 256,), np.int32)
    ids[:n_prompt] = prompt
    for n in range(n_prompt, n_prompt + new_tokens):
        ids[n] = int(next_token_int8(params, jnp.asarray(ids), n, arch=arch))
    n_valid = n_prompt + new_tokens
    gap, std, ties = reference.served_gaps(params, jnp.asarray(ids), jnp.int32(n_prompt), jnp.int32(n_valid), arch=arch, rows=new_tokens)
    return {"gap": np.asarray(gap), "tie": np.asarray(ties).min(axis=0)[:n_valid], "n_prompt": n_prompt,
            "std": float(np.asarray(std).mean())}


def readings(r, skips, reuse: bool = True, dump: str = "", int8_tokens: int = 0) -> dict:
    """One seed's line from a set-up runner ``r``: ``control_zaya.readings`` as
    it is (the served reading, each skip's, the used slots', the arrays to
    ``dump``), and this configuration's own int8 continuation."""
    out = control_zaya.readings(r, None, skips, (0, 0), reuse, dump)
    if int8_tokens:
        short = min(r.warm, key=lambda w: len(w.prompt))
        read = int8_read(r.engine.params, np.asarray(short.prompt, np.int32), int8_tokens, reference.Arch.from_config(r.cfg))
        r.served_gaps = lambda skip="": [read]
        try:
            ok, notes = r.reference_check()
        finally:
            del r.served_gaps      # the class's own again
        out["int8"] = {k: notes[k] for k in control_zaya.KEEP}
        if ok:
            out["controls_read_correct"] = sorted([*out["controls_read_correct"], "int8"])
    return out


def expert_loads(r) -> dict:
    """Over the warm-up's decode steps so far: the fullest held expert of a
    layer over the mean, the share of the routed pairs that were held, and
    the share of the rows that kept the held group."""
    from deepspeed_tpu.telemetry import spans

    emits = [a for n, _, _, a in spans.snapshot() if n == "ds.serve.emit" and a.get("moe_pairs_held")]
    per_step = r.mcfg.num_experts * len(r.srv.family.sparse_layers)
    if not emits:
        return {}
    return {"moe_load_max_over_mean": float(np.mean([a["moe_load_max"] * per_step / a["moe_pairs_held"] for a in emits])),
            "held_share": float(sum(a["moe_pairs_held"] for a in emits) / sum(a["moe_pairs_routed"] for a in emits)),
            "group_rows_share": float(sum(a.get("group_rows", 0) for a in emits) / max(1, sum(a.get("rows", 0) for a in emits)))}


def main(argv=None) -> int:
    from perfbench import run
    from perfbench.context import Context
    from perfbench.manifest import Manifest

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--skips", nargs="*", default=[*reference.SKIPS, "experts:7"])
    ap.add_argument("--int8-tokens", type=int, default=64,
                    help="tokens the int8 control continues the short warm-up prompt by (0: leave it out)")
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
        print(json.dumps({"seed": seed, **loads, **readings(r, args.skips, dump=dump, int8_tokens=args.int8_tokens)}), flush=True)
        r.srv.drain(0.0)
        del r, ctx
        gc.collect()      # the next seed's model needs this one's memory
    return 0


if __name__ == "__main__":
    sys.exit(main())
