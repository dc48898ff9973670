"""Measure: optax (XLA-fused) AdamW vs the Pallas fused kernel on flat shards.

SURVEY §2.7 asks for exactly this measurement before keeping either path
("Pallas fused optimizer kernel over flat param shards (or jax.jit fused
update — measure)"). Run on a TPU chip:

    python benchmarks/fused_adam_bench.py [n_params]

The op is HBM-bandwidth-bound (28 B/param fp32 traffic), so the report also
shows achieved GB/s against the chip's peak. Result is printed as one JSON
line; paste the winner + number into RESULTS below when re-run on new
hardware.

RESULTS: the first round-4 capture (independent repeated calls timed with
``block_until_ready``) reported ~270 TB/s — impossible, so those numbers
were discarded and the timing switched to the chained-scan pattern
(benchmarks/device_timing.py). Re-run on hardware to fill this line.
"""

from __future__ import annotations

import json
import os
import sys

import jax

# runnable as a standalone script from anywhere in the repo
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax.numpy as jnp
import optax

from deepspeed_tpu.ops.fused_adam import fused_adamw_flat


def main():
    on_tpu = jax.default_backend() == "tpu"
    # CPU smoke: tiny shard + interpret-mode kernel (timings meaningless
    # there; the measurement this bench records is the TPU one)
    default_n = 256 * 1024 * 1024 if on_tpu else 64 * 1024
    n = int(sys.argv[1]) if len(sys.argv) > 1 else default_n
    key = jax.random.PRNGKey(0)
    p = jax.random.normal(key, (n,), jnp.float32)
    g = jax.random.normal(key, (n,), jnp.float32) * 1e-3
    m = jnp.zeros_like(p)
    v = jnp.zeros_like(p)

    tx = optax.adamw(1e-3, weight_decay=0.01)
    state = tx.init(p)

    from benchmarks.device_timing import chained_ms

    def optax_step(c):
        p, state = c
        u, s2 = tx.update(g, state, p)
        return optax.apply_updates(p, u), s2

    def pallas_step(c):
        p, m, v = c
        return fused_adamw_flat(
            p, g, m, v, jnp.int32(1), 1e-3, weight_decay=0.01,
            interpret=not on_tpu,
        )

    iters = 20 if on_tpu else 2
    t_optax = chained_ms(optax_step, (p, state), iters) / 1e3
    t_pallas = chained_ms(pallas_step, (p, m, v), iters) / 1e3
    traffic = 28.0 * n  # r(p,g,m,v fp32) + w(p,m,v fp32)
    result = {
        "metric": "fused_adam ms @ %dM params" % (n // 1e6),
        "optax_ms": round(t_optax * 1e3, 3),
        "pallas_ms": round(t_pallas * 1e3, 3),
        "optax_gbps": round(traffic / t_optax / 1e9, 1),
        "pallas_gbps": round(traffic / t_pallas / 1e9, 1),
        "winner": "optax" if t_optax <= t_pallas else "pallas",
    }
    print(json.dumps(result))


if __name__ == "__main__":
    main()
