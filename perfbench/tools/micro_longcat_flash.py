"""The measured choices of the ``longcat_flash`` family (PERF.md, PR 41), each
timed alone on the chip at the served widths:

    python3 perfbench/tools/micro_longcat_flash.py [--out chiprun_out/micro_longcat_flash.json]

1. The held experts' products, one layer, at this routing (softmax, top-12 of
   512 + 256 identity columns, 16 held of 32 chips' share, E 6144, F 2048):
   MASKED (all held experts, weights of the unselected pairs 0) against
   GROUPED (``lax.ragged_dot`` over the ``T x 12`` sorted pair rows, of which
   one in 48 is in a group) at 64, 320 and 1 024 rows; their difference; the
   pairs held, hit and identity at seeded near-uniform routing.
2. The latent kernels at the new widths (64 heads, 640-lane rows of which 576
   are the row and the first 512 the values) against their jnp fallback, both
   shapes, and the decode shape's time at the cell's contexts (64 slots).

Times are medians of ``--reps`` calls after two warm ones, host clock around
``block_until_ready``; a tool, not a cell.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

_T0 = time.perf_counter()
_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)


def log(msg: str) -> None:
    print(f"[micro +{time.perf_counter() - _T0:6.1f}s] {msg}", file=sys.stderr, flush=True)


def timed(fn, *args, reps: int):
    import jax

    for _ in range(2):
        jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return statistics.median(ts) * 1e3


def experts(out: dict, reps: int):
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.moe import expert_share as es

    E, F, k, n_real, n_zero, held = 6144, 2048, 12, 512, 256, 16
    share = es.ExpertShare(n_real, n_real // held, 0, n_zero)
    ks = jax.random.split(jax.random.PRNGKey(1), 6)
    ex = {n: (jax.random.normal(kk, shape, jnp.float32) * 0.02).astype(jnp.bfloat16)
          for n, kk, shape in (("w_gate", ks[0], (held, E, F)), ("w_up", ks[1], (held, E, F)),
                               ("w_down", ks[2], (held, F, E)))}
    router = (jax.random.normal(ks[3], (E, n_real + n_zero), jnp.float32) * 0.02).astype(jnp.bfloat16)
    bias = (jax.random.normal(ks[4], (n_real + n_zero,), jnp.float32) / (n_real + n_zero)).astype(jnp.bfloat16)

    def masked(u, ex, router, bias):
        idx, w = es.route(u, router, bias, k, 6.0, False, "softmax")
        return es.held_experts(u, es.held_weights(idx, w, share), **ex)

    def grouped(u, ex, router, bias):
        idx, w = es.route(u, router, bias, k, 6.0, False, "softmax")
        return es.held_experts_grouped(u, idx, w, share, **ex)

    def census(u, router, bias):
        idx, w = es.route(u, router, bias, k, 6.0, False, "softmax")
        got = es.held_hits(idx, share)
        return jnp.sum(got), jnp.sum(jnp.any(got, axis=0)), jnp.sum(es.zero_weights(idx, w, share)[1])

    fm, fg, fc = jax.jit(masked), jax.jit(grouped), jax.jit(census)
    for rows in (64, 320, 1024):
        # unit-variance rows, as a norm's output is
        u = jax.random.normal(jax.random.fold_in(ks[5], rows), (rows, E), jnp.float32).astype(jnp.bfloat16)
        log(f"experts, {rows} rows")
        a, b = fm(u, ex, router, bias).astype(jnp.float32), fg(u, ex, router, bias).astype(jnp.float32)
        pairs, hit, zero = (int(x) for x in fc(u, router, bias))
        out[f"experts.rows{rows}.masked_ms"] = timed(fm, u, ex, router, bias, reps=reps)
        out[f"experts.rows{rows}.grouped_ms"] = timed(fg, u, ex, router, bias, reps=reps)
        out[f"experts.rows{rows}.max_abs_diff"] = float(jnp.abs(a - b).max())
        out[f"experts.rows{rows}.max_abs"] = float(jnp.abs(a).max())
        out[f"experts.rows{rows}.pairs_held"] = pairs
        out[f"experts.rows{rows}.experts_hit"] = hit
        out[f"experts.rows{rows}.pairs_zero"] = zero
        out[f"experts.rows{rows}.pairs_routed"] = rows * k


def attention(out: dict, reps: int):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.ops.attention import latent_paged_cached_attention
    from deepspeed_tpu.ops.pallas.latent_attention import latent_paged_attention, latent_token_write

    H, C, R, W, page, slots = 64, 512, 64, 640, 128, 64
    n_pg = 28
    P = slots * n_pg + 1
    scale = 1.0 / np.sqrt(192.0)
    ks = jax.random.split(jax.random.PRNGKey(0), 8)
    pool = (jax.random.normal(ks[0], (2, P, 1, page, W), jnp.float32) * 0.5).astype(jnp.bfloat16)
    pool = pool.at[..., C + R:].set(0)
    rng = np.random.default_rng(0)
    tables = jnp.asarray(1 + rng.permutation(P - 1)[: slots * n_pg].reshape(slots, n_pg), jnp.int32)

    # -- equality with the fallback, both shapes, a few slots ---------------
    for name, B, T in (("decode", 4, 1), ("chunk", 1, 256), ("verify", 4, 4)):
        q = (jax.random.normal(ks[3], (B, T, H, W), jnp.float32)).astype(jnp.bfloat16).at[..., C + R:].set(0)
        base = jnp.asarray([3000, 130, 1500, 255][:B], jnp.int32)
        # (big arrays go in as arguments: closed over, they would be constants of the program)
        got = jax.jit(lambda q, b, pool, bt: latent_paged_attention(q, pool, bt, b, C, scale, layer=1))(
            q, base, pool, tables[:B])
        want = jax.jit(lambda q, b, pool, bt: latent_paged_cached_attention(
            q, pool, bt, b, C, impl="jnp", sm_scale=scale, layer=1))(q, base, pool, tables[:B])
        log(f"kernel against fallback, {name}")
        out[f"kernel_vs_fallback_max_abs.{name}"] = float(jnp.abs(got.astype(jnp.float32) - want.astype(jnp.float32)).max())
        out[f"fallback_max_abs.{name}"] = float(jnp.abs(want.astype(jnp.float32)).max())

    # -- the token write against the scatter ---------------------------------
    pidx, poff = tables[:, 3], jnp.asarray(rng.integers(0, page, slots), jnp.int32)
    rows = jax.random.normal(ks[5], (slots, 1, W), jnp.float32).astype(jnp.bfloat16)
    wrote = jax.jit(lambda p, a, b, r: latent_token_write(p, 1, a, b, r))(pool, pidx, poff, rows)
    want = pool.at[1, pidx, 0, poff].set(rows[:, 0])
    out["token_write_equal"] = bool(jnp.array_equal(wrote, want))

    # -- the decode shape: 64 slots, contexts as the cell's -------------------
    lens = np.clip(np.exp(rng.normal(np.log(1024), 0.6, slots)), 288, 3072).astype(np.int32) + 256
    qd = jax.random.normal(ks[4], (slots, 1, H, W), jnp.float32).astype(jnp.bfloat16)
    f = jax.jit(lambda q, b, pool, bt: latent_paged_attention(q, pool, bt, b, C, scale, layer=1))
    log("decode shape")
    ms = timed(f, qd, jnp.asarray(lens), pool, tables, reps=reps)
    n_rows = int(lens.sum()) + slots
    out["decode.latent_ms"] = ms
    out["decode.rows"] = n_rows
    out["decode.GBps"] = n_rows * W * 2 / ms / 1e6

    # -- the chunk shape: 256 tokens against a context ------------------------
    qc = jax.random.normal(ks[6], (1, 256, H, W), jnp.float32).astype(jnp.bfloat16)
    for ctx in (0, 1024, 2816):
        log(f"chunk shape, context {ctx}")
        ms = timed(f, qc, jnp.asarray([ctx], jnp.int32), pool, tables[:1], reps=reps)
        pairs = 256 * ctx + 256 * 257 // 2
        out[f"chunk.latent_ms.ctx{ctx}"] = ms
        out[f"chunk.TFLOPs.ctx{ctx}"] = pairs * 2 * (C + R + C) * H / ms / 1e9


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", default=os.path.join(_ROOT, "chiprun_out", "micro_longcat_flash.json"))
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--only", default="", help="attention | experts")
    args = ap.parse_args(argv)
    import jax

    d = jax.devices()[0]
    if d.platform != "tpu":
        print(f"micro_longcat_flash: measures only on a TPU; JAX found {d.platform!r}", file=sys.stderr)
        return 2
    out = {"device": {"platform": d.platform, "kind": d.device_kind}}
    if args.only in ("", "experts"):
        experts(out, args.reps)
    if args.only in ("", "attention"):
        attention(out, args.reps)
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
