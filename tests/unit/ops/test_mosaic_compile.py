"""Mosaic-compile the serving path's paged decode kernel at real widths for a
described (not attached) TPU v5e: what the chip's compiler would refuse is
refused here, at no chip time. Interpret mode cannot show a misaligned slice
or a kernel over its VMEM.

The topology is described inside a fixture, never at import: only the worker
that runs this file loads the TPU library (see the on-chip-measurement
guide). Keep such tests in this one file."""

import os

import jax
import jax.numpy as jnp
import pytest


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("name,B,H,KV,D,page,n,P,dtype", [
    ("gpt2-xl", 8, 25, 25, 64, 16, 64, 512, jnp.bfloat16),
    ("gqa-rep5", 8, 25, 5, 64, 16, 64, 512, jnp.bfloat16),
    ("kv64-d128", 8, 64, 64, 128, 16, 64, 512, jnp.bfloat16),
    ("xl-tp5-shard", 8, 5, 5, 64, 16, 64, 512, jnp.bfloat16),
    ("xl-int8", 8, 25, 25, 64, 32, 32, 256, jnp.int8),
    ("head-blocks", 2, 64, 64, 128, 128, 4, 16, jnp.float32),
])
def test_paged_decode_kernel_compiles_for_v5e(one_chip, name, B, H, KV, D,
                                              page, n, P, dtype):
    from deepspeed_tpu.ops.pallas.decode_attention import (
        paged_decode_attention,
    )

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    args = [sds((B, H, D), jnp.bfloat16), sds((P, KV, page, D), dtype),
            sds((P, KV, page, D), dtype), sds((B, n), jnp.int32),
            sds((B,), jnp.int32)]
    if dtype == jnp.int8:
        args.append(sds((P, KV, 2), jnp.float32))

    def f(q, k, v, bt, pos, scales=None):
        return paged_decode_attention(q, k, v, bt, pos, scales=scales)

    compiled = jax.jit(f).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
