"""Records the xplane file that tests/perfbench/test_launches.py keeps
(tests/perfbench/data/launches.xplane.pb): a tiny GPT-2 served a step ahead
under the profiler, with every way a serving program is launched and read in
it: plain steps, chunks that ride a step and chunks alone, a whole prefill
waited for where it is launched and one left on its slot behind a step in
flight, a last chunk that rides and one left on its slot. Run on the chip:

    python3 perfbench/tools/record_launches_fixture.py <dir>

The session runs without the Python tracer; the file that is kept has the
host's events and the device's line of executed programs WHOLE (what the
reader joins by) and is cut of the rest, the device's operations and the
programs' HLO (1.4 MB as recorded, about 100 KB as kept: :func:`trim`, which
needs the xplane protocol that the installed tensorflow brings).
"""

from __future__ import annotations

import glob
import os
import shutil
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

SERVING = dict(max_slots=3, page_size=4, num_pages=96, max_prompt_len=40, max_new_tokens=12,
               prefill_chunk_tokens=8, temperature=0.0, kv_cache_dtype="float32")
# (the call of step() a request is submitted at, prompt length, new tokens): 5 is a whole prefill with nothing in
# flight, 20 chunks that ride, 19 chunks alone beside it, 7 a whole prefill behind a step in flight
PLAN = ((0, 5, 9), (2, 20, 12), (2, 19, 12), (4, 7, 6))


def play(srv, vocab: int, seed: int) -> list:
    import numpy as np

    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, vocab, n).astype(np.int32) for _, n, _ in PLAN]
    reqs, call = [], 0
    while len(reqs) < len(PLAN) or srv.queue or any(s.request is not None for s in srv.slots):
        for k, (at, _, new) in enumerate(PLAN):
            if at == call:
                reqs.append(srv.submit(prompts[k], max_new_tokens=new, seed=k))
        srv.step()
        call += 1
    srv.settle()
    return reqs


KEEP_LINES = ("XLA Modules",)     # of a device's plane; a host's lines are kept whole


def trim(path: str) -> None:
    """Cut the recorded file to what the reader's test needs, in place."""
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    space = xplane_pb2.XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    for plane in space.planes:
        if plane.name == "/host:metadata":
            plane.event_metadata.clear()          # the programs' HLO
        elif plane.name.startswith("/device:"):
            kept = [line for line in plane.lines if line.name in KEEP_LINES]
            used = {ev.metadata_id for line in kept for ev in line.events}
            del plane.lines[:]
            plane.lines.extend(kept)
            for key in [k for k in plane.event_metadata if k not in used]:
                del plane.event_metadata[key]
    with open(path, "wb") as f:
        f.write(space.SerializeToString())


def main(out_dir: str) -> int:
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.inference.engine import InferenceEngine
    from deepspeed_tpu.models import gpt2

    cfg = gpt2.get_config("gpt2-tiny", attn_impl="jnp")
    engine = InferenceEngine(gpt2.make_module(cfg), params=gpt2.init_params(cfg, jax.random.PRNGKey(0)),
                             dtype=jnp.float32)
    srv = engine.serve(SERVING)
    play(srv, cfg.vocab_size, seed=1)          # compiles and warms every program
    os.makedirs(out_dir, exist_ok=True)
    tmp = os.path.join(out_dir, "_trace")
    shutil.rmtree(tmp, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(tmp, profiler_options=options)
    with jax.profiler.TraceAnnotation("perfbench.window"):
        reqs = play(srv, cfg.vocab_size, seed=2)
    jax.profiler.stop_trace()
    src = sorted(glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True))[-1]
    dst = os.path.join(out_dir, "launches.xplane.pb")
    shutil.copy(src, dst)
    shutil.rmtree(tmp, ignore_errors=True)
    recorded = os.path.getsize(dst)
    trim(dst)
    print(recorded, "bytes recorded,", os.path.getsize(dst), "kept;", srv._launches, "launches;",
          "first_launch", [r.first_launch for r in reqs], "tokens", [len(r.tokens) for r in reqs])
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
