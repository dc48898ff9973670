"""Test harness: in-process multi-device mesh on CPU.

The reference's ``DistributedTest`` (tests/unit/common.py:66) forks N
processes and rendezvouses NCCL to simulate a cluster. The TPU-native analog
is strictly simpler: 8 virtual CPU devices in ONE process via
``--xla_force_host_platform_device_count=8``; every sharded test runs the same
code that runs on a real TPU slice (SURVEY.md §4 "translation to the TPU
build"). Env vars must be set before jax initializes, hence this module-level
block.
"""

import os
import shutil
import tempfile

# DS_TPU_TESTS=1 keeps the real TPU backend so `pytest -m tpu` can compile
# Mosaic kernels on hardware (VERDICT r2 item 8); default is the CPU mesh.
_TPU_MODE = os.environ.get("DS_TPU_TESTS") == "1"
# the run's own compile cache: made here by the process that starts the run
# (an xdist worker inherits the controller's environment, and with it the one
# directory of its run), removed when that process ends its session
_CACHE_OWNER = not _TPU_MODE and "PYTEST_XDIST_WORKER" not in os.environ
if not _TPU_MODE:
    os.environ["JAX_PLATFORMS"] = "cpu"
    _flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()
    # every test builds its own engine or jit closure, so jax's in-memory
    # cache never hits between two tests, and each worker compiles what
    # another already has: one persistent cache a RUN (ISSUE 46: a quarter of
    # the run's CPU seconds), empty at its start, never another run's or
    # another checkout's
    if _CACHE_OWNER:
        os.environ["JAX_COMPILATION_CACHE_DIR"] = tempfile.mkdtemp(prefix="ds_tpu_tests_jax_cache_")
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "-1"

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_collection_modifyitems(config, items):
    """Under DS_TPU_TESTS=1 the real TPU backend is active: enforce that only
    tpu-marked tests run (CPU-mesh tests assume 8 virtual devices).

    On the CPU mesh, serving-, lint-, resilience-, dsan-, dsmem- and
    heat-marked tests are hoisted to the front of the run (stable sort): the tier-1
    sweep runs under a wall-clock budget and kills the tail of the
    alphabet, and the serving simulation suite, the dslint static-analysis
    gate (ISSUE 6), the fault-tolerance matrix (ISSUE 7), the concurrency
    sanitizer plane (ISSUE 8) and the memory-verification plane (ISSUE 9)
    are acceptance gates that must stay inside the budget regardless of
    where their files sort."""
    if not _TPU_MODE:
        _hoisted = ("serving", "lint", "resilience", "dsan", "dsmem", "heat",
                    "tiering", "fleet", "tsdb")
        items.sort(
            key=lambda item: 0
            if any(k in item.keywords for k in _hoisted) else 1
        )
        return
    skip = pytest.mark.skip(reason="DS_TPU_TESTS=1 runs only -m tpu tests")
    for item in items:
        if "tpu" not in item.keywords:
            item.add_marker(skip)


def pytest_sessionfinish(session):
    if _CACHE_OWNER:
        shutil.rmtree(os.environ["JAX_COMPILATION_CACHE_DIR"], ignore_errors=True)


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) >= 8, f"expected >=8 virtual devices, got {len(devs)}"
    return devs


@pytest.fixture
def mesh_dp8(devices):
    from deepspeed_tpu.parallel.topology import MeshSpec

    return MeshSpec(dp=8).build_mesh()


@pytest.fixture
def mesh_dp4_tp2(devices):
    from deepspeed_tpu.parallel.topology import MeshSpec

    return MeshSpec(dp=4, tp=2).build_mesh()


@pytest.fixture
def mesh_single(devices):
    from deepspeed_tpu.parallel.topology import MeshSpec

    return MeshSpec(dp=1, devices=devices[:1]).build_mesh()


@pytest.fixture
def rng():
    return np.random.RandomState(42)
