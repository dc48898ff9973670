"""Records the small xplane file the tests keep (tests/perfbench/data): a few
executions of a small program under the profiler, with the benchmark's spans
around them. Run on the chip: ``python3 perfbench/tools/record_fixture.py <dir>``."""

from __future__ import annotations

import glob
import os
import shutil
import sys
import time


def main(out_dir: str) -> int:
    import jax
    import jax.numpy as jnp

    @jax.jit
    def small_step(x, w):
        y = jnp.dot(x, w)
        return jnp.transpose(jax.nn.gelu(y)).copy() + 1.0

    x = jnp.ones((512, 512), jnp.bfloat16)
    w = jnp.ones((512, 512), jnp.bfloat16)
    jax.block_until_ready(small_step(x, w))
    tmp = os.path.join(out_dir, "_trace")
    shutil.rmtree(tmp, ignore_errors=True)
    jax.profiler.start_trace(tmp)
    with jax.profiler.TraceAnnotation("perfbench.window"):
        for _ in range(4):
            with jax.profiler.TraceAnnotation("perfbench.step"):
                jax.block_until_ready(small_step(x, w))
            time.sleep(0.002)
    jax.profiler.stop_trace()
    src = sorted(glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True))[-1]
    shutil.copy(src, os.path.join(out_dir, "small.xplane.pb"))
    shutil.rmtree(tmp, ignore_errors=True)
    print(os.path.getsize(os.path.join(out_dir, "small.xplane.pb")), "bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
