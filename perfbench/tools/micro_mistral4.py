"""The two measured choices of the latent family (PERF.md, PR 34), each timed
alone on the chip at the served widths:

    python3 perfbench/tools/micro_mistral4.py [--out chiprun_out/micro_mistral4.json]

1. The chunk program's attention, one layer, 1 024 query tokens against a
   context of ``ctx`` cached rows: ABSORBED (the absorbed query through
   ``w_uk``, the latent kernel on the 384-lane rows, the output through
   ``w_uv``) against EXPANDED (the slot's rows gathered, expanded through
   ``w_uk`` / ``w_uv`` to per-head keys and values of 128, and the per-head
   paged multi-token kernel over them). Also the decode shape of the latent
   kernel (48 slots), and both shapes against the jnp fallback for equality.
2. The held experts' products, one layer: MASKED (all held experts, weights
   of the unselected pairs 0) against GROUPED (``lax.ragged_dot`` over the
   sorted pairs) at 48, 256 and 1 024 rows (top-4 of 128, 16 held, E 4096,
   F 2048), and at the other served family's shapes (64 and 256 rows, top-8,
   E 6144). And whether ``ragged_dot`` pays for rows outside every group.

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


def attention(out: dict, reps: int):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.ops.attention import latent_paged_cached_attention
    from deepspeed_tpu.ops.pallas.decode_attention import paged_multitoken_attention
    from deepspeed_tpu.ops.pallas.latent_attention import latent_paged_attention

    H, C, R, N, V, W, page, slots = 32, 256, 64, 64, 128, 384, 128, 48
    n_pg = 194
    P = slots * n_pg + 1
    scale = 0.19497
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 8)
    pool = (jax.random.normal(ks[0], (2, P, 1, page, W), jnp.float32) * 0.5).astype(jnp.bfloat16)
    pool = pool.at[..., C + R:].set(0)
    w_uk = (jax.random.normal(ks[1], (C, H, N), jnp.float32) * 0.02).astype(jnp.bfloat16)
    w_uv = (jax.random.normal(ks[2], (C, H, V), jnp.float32) * 0.02).astype(jnp.bfloat16)
    rng = np.random.default_rng(0)
    tables = jnp.asarray(1 + rng.permutation(P - 1)[: slots * n_pg].reshape(slots, n_pg), jnp.int32)

    # -- equality with the fallback, both shapes, a few slots ---------------
    for name, B, T in (("decode", 4, 1), ("chunk", 1, 1024)):
        q = (jax.random.normal(ks[3], (B, T, H, W), jnp.float32)).astype(jnp.bfloat16).at[..., C + R:].set(0)
        base = jnp.asarray([9000, 130, 24000, 12287][:B], jnp.int32)
        # (big arrays go in as arguments: closed over, they would be constants of the program)
        got = jax.jit(lambda q, b, pool, bt: latent_paged_attention(q, pool, bt, b, C, scale, layer=1))(
            q, base, pool, tables[:B])
        want = jax.jit(lambda q, b, pool, bt: latent_paged_cached_attention(
            q, pool, bt, b, C, impl="jnp", sm_scale=scale, layer=1))(q, base, pool, tables[:B])
        log(f"kernel against fallback, {name}")
        out[f"kernel_vs_fallback_max_abs.{name}"] = float(jnp.abs(got.astype(jnp.float32) - want.astype(jnp.float32)).max())

    # -- the decode shape: 48 slots, contexts as the cell's ------------------
    lens = np.clip(np.exp(rng.normal(np.log(12288), 0.4, slots)), 6144, 24576).astype(np.int32) + 128
    qd = jax.random.normal(ks[4], (slots, 1, H, W), jnp.float32).astype(jnp.bfloat16)
    f = jax.jit(lambda q, b, pool, bt: latent_paged_attention(q, pool, bt, b, C, scale, layer=1))
    log("decode shape")
    ms = timed(f, qd, jnp.asarray(lens), pool, tables, reps=reps)
    rows = int(lens.sum()) + slots
    out["decode.latent_ms"] = ms
    out["decode.rows"] = rows
    out["decode.GBps"] = rows * W * 2 / ms / 1e6

    # -- the chunk shape: absorbed against expanded --------------------------
    T = 1024
    qn = jax.random.normal(ks[5], (1, T, H, N), jnp.float32).astype(jnp.bfloat16)
    qr = jax.random.normal(ks[6], (1, T, H, R), jnp.float32).astype(jnp.bfloat16)

    def absorbed(qn, qr, base, pool, tables, w_uk, w_uv):
        qa = jnp.einsum("bthn,chn->bthc", qn, w_uk, preferred_element_type=jnp.float32)
        q = jnp.concatenate([qa, qr.astype(jnp.float32), jnp.zeros((1, T, H, W - C - R), jnp.float32)], -1)
        o = latent_paged_attention(q.astype(jnp.bfloat16), pool, tables[:1], base, C, scale, layer=1)
        return jnp.einsum("bthc,chv->bthv", o, w_uv)

    def expanded(qn, qr, base, pool, tables, w_uk, w_uv):
        rows = pool[1][tables[0]].reshape(n_pg * page, W)          # the slot's rows, gathered
        c, kr = rows[:, :C], rows[:, C: C + R]
        k = jnp.concatenate([jnp.einsum("sc,chn->shn", c, w_uk),
                             jnp.broadcast_to(kr[:, None, :], (n_pg * page, H, R))], -1)
        v = jnp.einsum("sc,chv->shv", c, w_uv)
        kp = k.reshape(n_pg, page, H, N + R).transpose(0, 2, 1, 3)  # [pages, H, page, 128]
        vp = v.reshape(n_pg, page, H, V).transpose(0, 2, 1, 3)
        q = jnp.concatenate([qn, qr], -1)
        return paged_multitoken_attention(q, kp, vp, jnp.arange(n_pg, dtype=jnp.int32)[None], base, sm_scale=scale)

    fa, fe = jax.jit(absorbed), jax.jit(expanded)
    for ctx in (6144, 12288, 23552):
        base = jnp.asarray([ctx], jnp.int32)
        log(f"chunk shape, context {ctx}")
        out[f"chunk.absorbed_ms.ctx{ctx}"] = timed(fa, qn, qr, base, pool, tables, w_uk, w_uv, reps=reps)
        out[f"chunk.expanded_ms.ctx{ctx}"] = timed(fe, qn, qr, base, pool, tables, w_uk, w_uv, reps=reps)
        pairs = T * ctx + T * (T + 1) // 2
        out[f"chunk.absorbed_TFLOPs.ctx{ctx}"] = pairs * 2 * (C + R + C) * H / out[f"chunk.absorbed_ms.ctx{ctx}"] / 1e9


def experts(out: dict, reps: int):
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.moe import expert_share as es

    def one(tag, E, F, k, rows_list):
        share = es.ExpertShare(128, 8, 0)
        ks = jax.random.split(jax.random.PRNGKey(1), 6)
        ex = {n: (jax.random.normal(kk, shape, jnp.float32) * 0.02).astype(jnp.bfloat16)
              for n, kk, shape in (("w_gate", ks[0], (16, E, F)), ("w_up", ks[1], (16, E, F)),
                                   ("w_down", ks[2], (16, F, E)))}
        router = jax.random.normal(ks[3], (E, 128), jnp.float32) * 0.02
        bias = jax.random.normal(ks[4], (128,), jnp.float32) * 0.02

        def masked(u, ex):
            idx, w = es.route(u, router, bias, k, 1.0)
            return es.held_experts(u, es.held_weights(idx, w, share), **ex)

        def grouped(u, ex):
            idx, w = es.route(u, router, bias, k, 1.0)
            return es.held_experts_grouped(u, idx, w, share, **ex)

        fm, fg = jax.jit(masked), jax.jit(grouped)
        for rows in rows_list:
            u = jax.random.normal(ks[5], (rows, E), jnp.float32).astype(jnp.bfloat16)
            log(f"experts {tag}, {rows} rows")
            a, b = fm(u, ex).astype(jnp.float32), fg(u, ex).astype(jnp.float32)
            out[f"experts.{tag}.rows{rows}.masked_ms"] = timed(fm, u, ex, reps=reps)
            out[f"experts.{tag}.rows{rows}.grouped_ms"] = timed(fg, u, ex, reps=reps)
            out[f"experts.{tag}.rows{rows}.max_abs_diff"] = float(jnp.abs(a - b).max())
            out[f"experts.{tag}.rows{rows}.max_abs"] = float(jnp.abs(a).max())

    one("ms4", 4096, 2048, 4, (48, 256, 1024, 4096))
    one("kx", 6144, 2048, 8, (64, 256))

    # does ragged_dot pay for rows outside every group?
    log("ragged_dot, rows outside every group")
    x = jnp.ones((4096, 4096), jnp.bfloat16)
    w = jnp.ones((16, 4096, 2048), jnp.bfloat16) * 0.01
    f = jax.jit(lambda x, w, s: jax.lax.ragged_dot(x, w, s))
    out["ragged.rows4096_all_grouped_ms"] = timed(f, x, w, jnp.full((16,), 256, jnp.int32), reps=reps)
    out["ragged.rows4096_512_grouped_ms"] = timed(f, x, w, jnp.full((16,), 32, jnp.int32), reps=reps)
    out["ragged.rows4096_one_group_of_512_ms"] = timed(
        f, x, w, jnp.zeros((16,), jnp.int32).at[3].set(512), reps=reps)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", default=os.path.join(_ROOT, "chiprun_out", "micro_mistral4.json"))
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--only", default="", help="attention | experts")
    args = ap.parse_args(argv)
    import jax

    d = jax.devices()[0]
    if d.platform != "tpu":
        print(f"micro_mistral4: measures only on a TPU; JAX found {d.platform!r}", file=sys.stderr)
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
