"""Chained-execution device timing for micro-benchmarks.

A micro-benchmark that times N independently dispatched calls measures the
host's dispatch loop as much as the device, and is only as good as its final
barrier (the round-4 captures "measured" a 268M-param Adam update at
270 TB/s and a BERT-large forward at 0.21 ms — physically impossible
numbers that mean the host timer stopped before the device finished).

The pattern used here (the same reason bench.py's train-step timing is
sound — its loop threads the optimizer state, forcing sequential
execution): run K iterations inside ONE compiled program with a
data-dependent carry, reduce the final carry to a scalar INSIDE the
program, and fetch that scalar with ``jax.device_get``. The fetch cannot
return before the whole chain has executed, and transfers 4 bytes instead
of the carry.
"""

from __future__ import annotations

import time


def chained_ms(step, carry, iters: int) -> float:
    """ms per iteration of ``carry = step(carry)`` chained ``iters`` times
    inside one jitted ``lax.scan``. ``step`` must be jit-traceable and
    return a pytree matching ``carry``'s structure."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def k(c):
        final = jax.lax.scan(lambda c, _: (step(c), None), c, None, length=iters)[0]
        # cheap full-tree reduce: every iteration feeds this scalar, so XLA
        # cannot dead-code any part of the chain
        return sum(jnp.sum(l).astype(jnp.float32) for l in jax.tree.leaves(final))

    float(jax.device_get(k(carry)))  # compile + warm, hard barrier
    t0 = time.perf_counter()
    float(jax.device_get(k(carry)))
    return (time.perf_counter() - t0) / iters * 1e3
