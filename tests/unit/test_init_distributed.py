"""Two-process rendezvous through ``deepspeed_tpu.init_distributed``.

The reference's DSElasticAgent participates in a real torch rendezvous
(reference deepspeed/elasticity/elastic_agent.py:23; comm/comm.py:577
init_distributed). The TPU-native analog is ``jax.distributed.initialize``
— this test proves the env-discovery path (MASTER_ADDR/WORLD_SIZE/RANK)
actually forms a 2-process group and runs a cross-process collective, not
just that the function exists. CPU backend (``JAX_PLATFORMS=cpu`` in each
worker's environment), one device per worker."""

import os
import socket
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

WORKER = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
import jax
import numpy as np
import deepspeed_tpu

deepspeed_tpu.init_distributed()
assert jax.process_count() == 2, jax.process_count()
assert deepspeed_tpu.comm.get_world_size() == 2

from jax.experimental import multihost_utils

ranks = multihost_utils.process_allgather(np.asarray([jax.process_index()]))
assert sorted(int(r) for r in np.asarray(ranks).ravel()) == [0, 1], ranks
print("DIST_OK", jax.process_index())
"""


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def test_two_process_rendezvous():
    port = _free_port()
    procs = []
    for rank in (0, 1):
        env = dict(
            os.environ,
            MASTER_ADDR="127.0.0.1",
            MASTER_PORT=str(port),
            WORLD_SIZE="2",
            RANK=str(rank),
            PYTHONPATH=ROOT,
            JAX_PLATFORMS="cpu",
        )
        env.pop("XLA_FLAGS", None)
        procs.append(
            subprocess.Popen(
                [sys.executable, "-c", WORKER],
                env=env, cwd=ROOT,
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            )
        )
    # collect per-process so one hung worker can't hide its peer's result:
    # a worker that FAILED (vs hung) is a real regression even if another
    # then timed out waiting at the rendezvous (ADVICE r4)
    outs = []
    timed_out = False
    for p in procs:
        try:
            out, _ = p.communicate(timeout=300)
        except subprocess.TimeoutExpired:
            timed_out = True
            p.kill()
            try:
                out, _ = p.communicate(timeout=10)
            except subprocess.TimeoutExpired:
                out = ""
        outs.append(out)
    for p, out in zip(procs, outs):
        if p.returncode == 0 and "DIST_OK" in (out or ""):
            continue
        if timed_out and p.returncode in (None, -9):
            continue  # killed by the timeout path, not a crash
        assert False, (out or "")[-2000:]
    if timed_out:
        pytest.skip("jax.distributed CPU rendezvous timed out on this host")
