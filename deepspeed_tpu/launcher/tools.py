"""Auxiliary CLI entry points (reference ``bin/ds_ssh``, ``bin/ds_bench``,
``bin/ds_elastic``; installed via setup.py console_scripts).

- ``ds_ssh``: run a shell command on every host of a hostfile (the
  cluster-wide fan-out the reference implements with a pdsh loop).
- ``ds_bench``: sweep the collective micro-benchmarks on the local mesh —
  reuses ``CommsLogger.measure`` so the numbers match ``comms_summary``.
- ``ds_elastic``: pretty-print the elastic batch ladder for a config
  (reference ds_elastic: compute_elastic_config from a ds_config JSON).
"""

from __future__ import annotations

import argparse
import json
import shlex
import subprocess
import sys

from ..utils.logging import logger


def ds_ssh(argv=None) -> int:
    p = argparse.ArgumentParser("ds_ssh", description="run a command on all hosts")
    p.add_argument("-f", "--hostfile", default="/job/hostfile")
    p.add_argument("command", nargs=argparse.REMAINDER)
    args = p.parse_args(argv)
    from .runner import fetch_hostfile

    hosts = fetch_hostfile(args.hostfile)
    if not hosts:
        print(f"ds_ssh: no hosts in {args.hostfile}", file=sys.stderr)
        return 1
    if not args.command:
        p.error("no command given")
    cmd = shlex.join(args.command)  # preserve quoting on the remote shell
    # pdsh-style parallel fan-out: launch every host, then collect
    procs = {
        host: subprocess.Popen(
            ["ssh", "-o", "StrictHostKeyChecking=no", host, cmd],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        )
        for host in hosts
    }
    rc = 0
    for host, proc in procs.items():
        out, _ = proc.communicate()
        print(f"--- {host} ---")
        if out:
            print(out, end="")
        rc = rc or proc.returncode
    return rc


def ds_bench(argv=None) -> int:
    p = argparse.ArgumentParser("ds_bench", description="collective micro-bench")
    p.add_argument("--ops", default="all_reduce,all_gather,reduce_scatter,all_to_all")
    p.add_argument("--bytes", type=int, default=16 * 1024 * 1024)
    p.add_argument("--iters", type=int, default=10)
    args = p.parse_args(argv)
    import jax

    from ..comm import comm as dscomm
    from ..parallel.topology import MeshSpec

    n = len(jax.devices())
    mesh = MeshSpec(dp=n).build_mesh()
    dscomm.comms_logger.configure(enabled=True)
    for op in args.ops.split(","):
        dscomm.comms_logger.comms_dict[(op.strip(), "dp")] = {
            "count": 1, "bytes": args.bytes, "time_ms": None, "world": None,
        }
    dscomm.comms_logger.measure(mesh, iters=args.iters)
    print(dscomm.log_summary())
    return 0


def _watch_and_run(cmd, backoff_s: float, max_runs: int, sleep_fn=None) -> int:
    """Run ``cmd``; while it fails, back off and run it again — the
    preemption-recovery loop for an idempotent, resumable command (e.g.
    training with checkpoint auto-resume). The command's own exit code is the
    health signal: a chip belongs to one process at a time, so this launcher
    never opens the device itself, neither here nor from a probe child.
    ``max_runs`` 0 = retry until the command succeeds."""
    import time as _time

    sleep = sleep_fn or _time.sleep
    runs = 0
    while True:
        runs += 1
        logger.info(f"ds_elastic --watch: run {runs}: {cmd}")
        rc = subprocess.call(cmd)
        if rc == 0:
            return 0
        logger.warning(f"ds_elastic --watch: command exited rc={rc}")
        if max_runs and runs >= max_runs:
            return rc
        sleep(backoff_s)


def ds_elastic(argv=None) -> int:
    p = argparse.ArgumentParser("ds_elastic", description="elastic config ladder")
    p.add_argument("-c", "--config", required=False, help="ds_config JSON path")
    p.add_argument("-w", "--world-size", type=int, default=0)
    p.add_argument(
        "--verify-resize",
        default=None,
        metavar="W1,W2,...",
        help="validate that a job could resize across these world sizes: each "
        "must sit on the ladder with the SAME effective batch; prints the "
        "micro x gas x dp split per size (rc 1 if any is incompatible)",
    )
    p.add_argument(
        "--watch", action="store_true",
        help="run CMD (everything after --) and retry with backoff while it "
        "fails — preemption recovery for an idempotent, checkpoint-resumable "
        "command",
    )
    p.add_argument("--backoff", type=float, default=240.0)
    p.add_argument("--max-runs", type=int, default=0, help="0 = until success")
    p.add_argument("cmd", nargs=argparse.REMAINDER)
    args = p.parse_args(argv)
    if args.watch:
        # drop only the LEADING separator: an inner "--" belongs to the
        # wrapped command (e.g. --watch -- ds_ssh -f hosts -- echo hi)
        cmd = args.cmd[1:] if args.cmd[:1] == ["--"] else args.cmd
        if not cmd:
            p.error("--watch needs a command after --")
        return _watch_and_run(cmd, args.backoff, args.max_runs)
    if args.cmd:
        p.error(f"unrecognized arguments: {' '.join(args.cmd)} (a trailing "
                "command is only accepted with --watch)")
    if not args.config:
        p.error("-c/--config is required (unless --watch)")
    from ..elasticity.elasticity import ElasticityError, compute_elastic_config

    with open(args.config) as f:
        doc = json.load(f)
    if args.verify_resize:
        sizes = [int(s) for s in args.verify_resize.split(",") if s]
        plan, ok = [], True
        for ws in sizes:
            try:
                batch, _, micro = compute_elastic_config(
                    doc, world_size=ws, return_microbatch=True
                )
                if micro is None:
                    raise ElasticityError(f"no micro batch for world size {ws}")
                plan.append({
                    "world_size": ws, "final_batch_size": batch,
                    "micro_batch_per_gpu": micro,
                    "gradient_accumulation_steps": batch // (micro * ws),
                })
            except ElasticityError as e:
                ok = False
                plan.append({"world_size": ws, "error": str(e)})
        batches = {e["final_batch_size"] for e in plan if "final_batch_size" in e}
        ok = ok and len(batches) == 1
        print(json.dumps({"resize_ok": ok, "plan": plan}, indent=2))
        return 0 if ok else 1
    res = compute_elastic_config(
        doc, world_size=args.world_size, return_microbatch=args.world_size > 0
    )
    out = {"final_batch_size": res[0], "valid_gpus": res[1]}
    if len(res) > 2:
        out["micro_batch_per_gpu"] = res[2]
    print(json.dumps(out, indent=2))
    return 0
