"""The serving programs' norm (the mean taken once) against the shared one."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.layer_norm import layer_norm, layer_norm_inference


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
@pytest.mark.parametrize("shape", [(8, 1, 1600), (1, 128, 1600), (3, 7, 64), (2, 5, 48)],
                         ids=["decode", "chunk", "small", "off-lane"])
def test_inference_norm_has_the_shared_norms_bits(dtype, shape):
    """Under jit, as every program runs it: same bits, a large mean included
    (the case a one-pass variance would lose)."""
    for seed, shift in ((0, 1.0), (1, 50.0), (2, -300.0)):
        kx, kg, kb = jax.random.split(jax.random.PRNGKey(seed), 3)
        x = (jax.random.normal(kx, shape) * 3 + shift).astype(dtype)
        g = jax.random.normal(kg, shape[-1:]).astype(dtype)
        b = jax.random.normal(kb, shape[-1:]).astype(dtype)
        want = jax.jit(lambda x, g, b: layer_norm(x, g, b, 1e-5))(x, g, b)
        got = jax.jit(lambda x, g, b: layer_norm_inference(x, g, b, 1e-5))(x, g, b)
        assert got.dtype == want.dtype
        np.testing.assert_array_equal(
            np.asarray(got.astype(jnp.float32)), np.asarray(want.astype(jnp.float32))
        )
