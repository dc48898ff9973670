"""Per-collective benchmarks over the device mesh.

Analog of reference ``benchmarks/communication/{all_reduce,all_gather,
all_to_all,broadcast,pt2pt,run_all}.py`` (~800 LoC): sweep message sizes per
collective with warmups, print latency/algbw/busbw, and persist a JSON
artifact (``COMM_BENCH.json``) that PERF.md §3's ICI-scaling analysis can
cite as measured. Collectives run inside jitted shard_map over the dp axis
(XLA collectives over ICI on real hardware; host shared memory on the CPU
test mesh — the artifact records which).

Timing modes:
- independent dispatch (reference-style warmup+trials loop), and
- ``--chained`` (default on TPU): K iterations of a shape-preserving
  variant of the collective chained through a data-dependent carry inside
  one compiled scan (benchmarks/device_timing.py) — no per-call dispatch
  in the measurement, and the final scalar fetch is a hard barrier.

    python benchmarks/communication/run_all.py [--maxsize 26] [--trials 5]
    python benchmarks/communication/run_all.py --collective all_reduce
    python benchmarks/communication/run_all.py --chained --json COMM_BENCH.json
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import Callable, Dict

# runnable as a standalone script from anywhere in the repo
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402,F401
from jax import lax, shard_map  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

COLLECTIVES = ("all_reduce", "all_gather", "reduce_scatter", "all_to_all",
               "broadcast", "pt2pt")


def _mesh():
    from deepspeed_tpu.parallel.topology import MeshSpec

    return MeshSpec(dp=len(jax.devices())).build_mesh()


def _busbw_factor(coll: str, n: int) -> float:
    """Bus-bandwidth correction (ring-algorithm accounting, reference
    utils.py calc_bw semantics)."""
    if coll in ("all_reduce",):
        return 2.0 * (n - 1) / n
    if coll in ("all_gather", "reduce_scatter", "all_to_all"):
        return (n - 1) / n
    return 1.0  # broadcast / pt2pt


def make_ops(mesh) -> Dict[str, Callable]:
    """Reference-style one-shot collectives (shapes may change)."""
    n = mesh.devices.size

    def wrap(body, out_spec):
        return jax.jit(
            shard_map(body, mesh=mesh, in_specs=(P("dp"),), out_specs=out_spec, check_vma=False)
        )

    return {
        "all_reduce": wrap(lambda x: lax.psum(x, "dp"), P("dp")),
        "all_gather": wrap(lambda x: lax.all_gather(x, "dp", tiled=True), P("dp")),
        "reduce_scatter": wrap(lambda x: lax.psum_scatter(x, "dp", tiled=True), P("dp")),
        "all_to_all": wrap(
            lambda x: lax.all_to_all(
                x.reshape(n, -1), "dp", split_axis=0, concat_axis=0
            ).reshape(x.shape),
            P("dp"),
        ),
        "broadcast": wrap(
            lambda x: lax.all_gather(x, "dp")[0] * jnp.ones_like(x), P("dp")
        ),
        "pt2pt": wrap(
            lambda x: lax.ppermute(x, "dp", [(i, (i + 1) % n) for i in range(n)]),
            P("dp"),
        ),
    }


def make_chained_bodies(n: int) -> Dict[str, Callable]:
    """Shape-preserving variants (local view inside shard_map) so the
    collective can chain through a scan carry. The local math added to
    restore shapes (mean/tile) is negligible next to the transfer."""
    return {
        "all_reduce": lambda x: lax.pmean(x, "dp"),
        "all_gather": lambda x: lax.all_gather(x, "dp", tiled=True)
        .reshape(n, -1).mean(0).reshape(x.shape),
        "reduce_scatter": lambda x: jnp.tile(
            lax.psum_scatter(x.reshape(-1), "dp", tiled=True) / n, n
        ).reshape(x.shape),
        "all_to_all": lambda x: lax.all_to_all(
            x.reshape(n, -1), "dp", split_axis=0, concat_axis=0
        ).reshape(x.shape),
        "broadcast": lambda x: lax.all_gather(x, "dp")[0] * jnp.sign(x) * jnp.sign(x),
        "pt2pt": lambda x: lax.ppermute(
            x, "dp", [(i, (i + 1) % n) for i in range(n)]
        ),
    }


def bench_collective(name: str, mesh, maxsize_log2: int, trials: int,
                     chained: bool, ops=None):
    from benchmarks.device_timing import chained_ms

    n = mesh.devices.size
    rows = []
    print(f"\n--- {name} (world={n}, {'chained' if chained else 'independent'}) ---")
    print(f"{'size':>12} {'latency(us)':>12} {'algbw(GB/s)':>12} {'busbw(GB/s)':>12}")
    for logsz in range(12, maxsize_log2 + 1, 2):
        numel = (2**logsz) // 4
        x = jnp.ones((n * numel,), jnp.float32)
        if chained:
            body = make_chained_bodies(n)[name]
            stepped = shard_map(
                body, mesh=mesh, in_specs=(P("dp"),), out_specs=P("dp"),
                check_vma=False,
            )
            dt = chained_ms(stepped, x, trials) / 1e3
        else:
            op = ops[name]
            out = op(x)
            jax.block_until_ready(out)  # warmup (compile)
            t0 = time.perf_counter()
            for _ in range(trials):
                out = op(x)
            jax.block_until_ready(out)
            dt = (time.perf_counter() - t0) / trials
        nbytes = x.nbytes
        algbw = nbytes / dt / 1e9
        busbw = algbw * _busbw_factor(name, n)
        print(f"{nbytes:>12,} {dt * 1e6:>12.1f} {algbw:>12.2f} {busbw:>12.2f}")
        rows.append({
            "bytes": int(nbytes),
            "latency_us": round(dt * 1e6, 2),
            "algbw_gbs": round(algbw, 3),
            "busbw_gbs": round(busbw, 3),
        })
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--collective", default="all", choices=("all",) + COLLECTIVES)
    ap.add_argument("--maxsize", type=int, default=24, help="log2 max bytes")
    ap.add_argument("--trials", type=int, default=5)
    ap.add_argument("--chained", action="store_true", default=None,
                    help="chain iterations through one compiled scan "
                         "(default on non-CPU backends)")
    ap.add_argument("--json", default=os.path.join(ROOT, "COMM_BENCH.json"),
                    help="artifact path ('' disables)")
    args = ap.parse_args()

    mesh = _mesh()
    chained = args.chained
    if chained is None:
        chained = jax.default_backend() not in ("cpu",)
    ops = None if chained else make_ops(mesh)
    names = COLLECTIVES if args.collective == "all" else (args.collective,)
    results = {}
    for name in names:
        results[name] = bench_collective(
            name, mesh, args.maxsize, args.trials, chained, ops
        )
    if args.json:
        artifact = {
            "platform": jax.default_backend(),
            "world_size": int(mesh.devices.size),
            "timing": "chained_scan" if chained else "independent_dispatch",
            "trials": args.trials,
            "collectives": results,
        }
        existing = {}
        if os.path.exists(args.json):
            try:
                with open(args.json) as f:
                    existing = json.load(f)
            except ValueError:
                existing = {}
        # keyed by platform so a CPU-mesh artifact never overwrites a chip one
        existing[artifact["platform"]] = artifact
        tmp = args.json + ".tmp"
        with open(tmp, "w") as f:
            json.dump(existing, f, indent=1)
        os.replace(tmp, args.json)
        print(f"\nwrote {args.json}")


if __name__ == "__main__":
    main()
