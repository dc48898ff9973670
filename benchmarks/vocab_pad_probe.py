"""Measure whether vocab-dim alignment matters for the CE head matmul.

GPT-2's vocab (50257) is not a multiple of the 128-lane MXU tile; XLA pads
internally per matmul. If the unaligned head costs materially more than an
aligned 50304/50432 one, a Megatron-style padded-embedding feature (pad
rows + masked pad columns in the loss) is worth building; if not, skip it.
One JSON line with ms per (T,E)x(E,V) matmul for V in {50257, 50304, 50432}.

    python benchmarks/vocab_pad_probe.py
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from benchmarks.device_timing import chained_ms


def main():
    on_tpu = jax.default_backend() == "tpu"
    T = int(os.environ.get("PROBE_T", "16384" if on_tpu else "256"))
    E = int(os.environ.get("PROBE_E", "1024" if on_tpu else "64"))
    vocabs = (50257, 50304, 50432) if on_tpu else (509, 512)
    key = jax.random.PRNGKey(0)
    h = jax.random.normal(key, (T, E), jnp.bfloat16)
    result = {"metric": f"vocab-head matmul ms T{T} E{E}", "T": T, "E": E}
    for V in vocabs:
        W = jax.random.normal(key, (V, E), jnp.bfloat16) * 0.02

        # logits reduced to [T,E] via a second matmul so the carry (h) keeps
        # its shape — data-dependent chain, nothing hoistable (device_timing)
        def step(hc):
            logits = jax.lax.dot_general(
                hc, W, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            return jax.lax.dot_general(
                logits.astype(jnp.bfloat16), W, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            ).astype(jnp.bfloat16)

        ms = chained_ms(step, h, 10 if on_tpu else 2)
        # each step = fwd head + its transpose: 4*T*E*V flops
        result[f"ms_V{V}"] = round(ms, 3)
        result[f"tflops_V{V}"] = round(4.0 * T * E * V / (ms / 1e3) / 1e12, 1)
    print(json.dumps(result))


if __name__ == "__main__":
    main()
