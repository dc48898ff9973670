"""On-chip attention-impl sweep: Pallas flash (resident + grid) vs XLA jnp.

Times the attention core alone at the headline bench shapes (and a long-seq
shape) so the model dispatchers' "auto" policy is grounded in a measured
number instead of an assumption. Run on a real TPU:

    python benchmarks/flash_sweep.py            # default shapes
    BENCH_SHAPES=32x1024x16x64 python benchmarks/flash_sweep.py

Prints one JSON line per (shape, impl) with ms/iter and achieved TFLOP/s,
then a WINNERS summary line. RESULTS from the last hardware run are recorded
at the bottom of this file.
"""

from __future__ import annotations

import json
import os
import sys

import numpy as np

# runnable as a standalone script from anywhere in the repo
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def attention_flops(B, S, H, D, causal=True):
    # QK^T + PV: 2 * 2 * B*H*S*S*D, halved for causal
    f = 4.0 * B * H * S * S * D
    return f / 2 if causal else f


def time_fwd(fn, q, k, v, iters=20):
    """Chained-scan timing (see device_timing.py): q rides the carry so the
    attention call is neither loop-invariant nor un-barriered."""
    from benchmarks.device_timing import chained_ms

    return chained_ms(lambda c: (fn(*c), c[1], c[2]), (q, k, v), iters) / 1e3


def time_fwdbwd(grad_fn, q, k, v, iters=10):
    """(dq,dk,dv) feed the next iteration's (q,k,v): every grad output is
    live, so neither XLA DCE nor loop hoisting can skip work."""
    from benchmarks.device_timing import chained_ms

    return chained_ms(lambda c: grad_fn(*c), (q, k, v), iters) / 1e3


def main():
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.ops.attention import causal_attention_jnp
    from deepspeed_tpu.ops.pallas import flash_attention as fa_mod
    from deepspeed_tpu.ops.pallas.flash_attention import _flash, _flash_grid, flash_attention

    shapes_env = os.environ.get("BENCH_SHAPES")
    if shapes_env:
        shapes = [tuple(map(int, s.split("x"))) for s in shapes_env.split(",")]
    else:
        # (B, S, H, D): headline bench shape (gpt2-medium micro 32), a
        # larger-head variant, and a long-seq grid-kernel shape
        shapes = [(32, 1024, 16, 64), (8, 1024, 16, 128), (1, 8192, 8, 128)]

    fwd_only = os.environ.get("BENCH_FWD_ONLY") == "1"
    results = []
    for (B, S, H, D) in shapes:
        rs = np.random.RandomState(0)
        q, k, v = (jnp.asarray(rs.randn(B, S, H, D), jnp.bfloat16) for _ in range(3))
        scale = 1.0 / np.sqrt(D)

        def to3(x):
            return x.transpose(0, 2, 1, 3).reshape(B * H, S, D)

        q3, k3, v3 = to3(q), to3(k), to3(v)
        flops = attention_flops(B, S, H, D)

        impls = {
            "pallas-auto": jax.jit(lambda q, k, v: flash_attention(q, k, v)),
            "pallas-resident": jax.jit(
                lambda q, k, v: _flash(q, k, v, None, float(scale), True, False, 1)
            ),
            "pallas-grid": jax.jit(
                lambda q, k, v: _flash_grid(q, k, v, float(scale), True, False)
            ),
            "xla-jnp": jax.jit(causal_attention_jnp),
        }
        args = {
            "pallas-auto": (q, k, v),
            "pallas-resident": (q3, k3, v3),
            "pallas-grid": (q3, k3, v3),
            "xla-jnp": (q, k, v),
        }
        grads = {
            name: jax.jit(
                jax.grad(
                    (lambda f: lambda *a: jnp.sum(f(*a).astype(jnp.float32) ** 2))(f),
                    argnums=(0, 1, 2),
                )
            )
            for name, f in impls.items()
        }

        for name in impls:
            row = {"shape": f"{B}x{S}x{H}x{D}", "impl": name}
            try:
                dt = time_fwd(impls[name], *args[name])
                row["fwd_ms"] = round(dt * 1e3, 3)
                row["fwd_tflops"] = round(flops / dt / 1e12, 1)
                if not fwd_only:
                    dtg = time_fwdbwd(grads[name], *args[name])
                    row["fwdbwd_ms"] = round(dtg * 1e3, 3)
                    # bwd ≈ 2.5x fwd attention flops
                    row["fwdbwd_tflops"] = round(3.5 * flops / dtg / 1e12, 1)
                    if name == "pallas-auto" and fa_mod._fused_bwd_ok(S, D):
                        # A/B the fused single-pass backward against the
                        # split dq/dkv kernels. BOTH sides get a freshly
                        # built, unjitted-core grad fn: the prebuilt
                        # grads[name] was already traced with the fused
                        # dispatch baked in, so flipping the flag would
                        # re-time the fused kernel (cached jaxpr), not the
                        # split one.
                        def fresh_grad():
                            loss = lambda q, k, v: jnp.sum(
                                flash_attention(q, k, v).astype(jnp.float32) ** 2
                            )
                            return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))

                        fa_mod._FUSED_BWD_ENABLED = False
                        try:
                            dts = time_fwdbwd(fresh_grad(), *args[name])
                            row["fwdbwd_ms_splitbwd"] = round(dts * 1e3, 3)
                        finally:
                            fa_mod._FUSED_BWD_ENABLED = True
                        dtf = time_fwdbwd(fresh_grad(), *args[name])
                        row["fwdbwd_ms_fusedbwd"] = round(dtf * 1e3, 3)
            except Exception as e:
                row["error"] = f"{type(e).__name__}: {str(e)[:120]}"
            results.append(row)
            print(json.dumps(row), flush=True)

    winners = {}
    for r in results:
        key = r["shape"]
        metric = r.get("fwdbwd_ms") or r.get("fwd_ms")
        if metric is not None and (key not in winners or metric < winners[key][1]):
            winners[key] = (r["impl"], metric)
    print(json.dumps({"WINNERS": {k: v[0] for k, v in winners.items()}}))


if __name__ == "__main__":
    main()

# RESULTS (hardware): never captured. Until a number lands here, the model
# dispatchers' pallas-first "auto" policy rests on the chip CI only
# (tests/unit/ops/test_tpu_hardware.py).
