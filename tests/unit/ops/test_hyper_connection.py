"""The mixing of a multi-stream residual (``ops/pallas/hyper_connection.py``):
the kernel pair in interpret mode against the ``jax.numpy`` forms, and both
against the float32 reference's Python loop (``perfbench/reference_xing4.py``,
which imports nothing from the package); the Sinkhorn rounds at the clamp's
edges."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.pallas import hyper_connection as hc
from perfbench import reference_xing4 as reference

N, E = 4, 128
K = 2 * N + N * N
KW = dict(n=N, eps=1e-6, iters=20)


def _draw(T, dtype, seed=0, scale=1.0):
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = (jax.random.normal(k[0], (T, N * E)) * scale).astype(dtype)
    phi = (jax.random.normal(k[1], (K, N * E)) / np.sqrt(N * E)).astype(dtype)
    a = jnp.array([1.0, 0.75, 1.25], dtype)
    b = jax.random.normal(k[2], (K,)).astype(dtype)
    y = jax.random.normal(k[3], (T, E)).astype(dtype)
    return x, phi, a, b, y


def _reference(x, phi, a, b, y):
    """The sub-block's maps, ``u`` and ``X'`` by the reference's own lines."""
    arch = reference.Arch(latent=None, first_dense=0, n_streams=N, iters=20, hc_eps=1e-6, clamp=(-30.0, 30.0))
    X = x.astype(jnp.float32).reshape(-1, N, E)
    pre, post, res = reference.maps(X, {"phi": phi, "a": a, "b": b}, arch)
    u = jnp.sum(pre[:, :, None] * X, axis=1)
    out = jnp.einsum("sij,sje->sie", res, X, precision="highest") + post[:, :, None] * y.astype(jnp.float32)[:, None, :]
    return jnp.concatenate([pre, post, res.reshape(-1, N * N)], axis=-1), u, out.reshape(x.shape)


# 24 rows: one block; 150: two whole blocks of 64 and 22 rows of a third, which the grid pads
@pytest.mark.parametrize("impl,T,dtype,tol", [
    ("jnp", 24, jnp.float32, 2e-6), ("interpret", 24, jnp.float32, 2e-6), ("interpret", 150, jnp.float32, 2e-6),
    ("interpret", 64, jnp.bfloat16, 1e-5),
])
def test_both_forms_are_the_references_python_loop(impl, T, dtype, tol):
    x, phi, a, b, y = _draw(T, dtype, scale=3.0)
    maps_r, u_r, out_r = _reference(x, phi, a, b, y)
    u, maps = hc.hc_pre(x, phi, a, b, impl=impl, **KW)
    out = hc.hc_post(x, y, maps, n=N, impl=impl)
    assert maps.dtype == jnp.float32 and u.dtype == dtype and out.dtype == dtype and out.shape == x.shape
    assert float(jnp.abs(maps - maps_r).max()) <= tol          # float32 maps, from a bf16 stream too
    round_ = lambda v: v.astype(dtype).astype(jnp.float32)  # noqa: E731
    ulp = 0.0 if dtype == jnp.float32 else 2.0 ** -7           # a value near a rounding boundary may fall either way
    np.testing.assert_allclose(u.astype(jnp.float32), round_(u_r), atol=1e-5, rtol=ulp)
    np.testing.assert_allclose(out.astype(jnp.float32), round_(out_r), atol=1e-5, rtol=ulp)


def test_the_kernels_take_leading_axes_and_a_stream_of_another_width_takes_the_jnp_form():
    x, phi, a, b, y = _draw(12, jnp.float32, seed=1)
    u, maps = hc.hc_pre(x.reshape(3, 4, -1), phi, a, b, impl="interpret", **KW)
    assert u.shape == (3, 4, E) and maps.shape == (3, 4, K)
    out = hc.hc_post(x.reshape(3, 4, -1), y.reshape(3, 4, -1), maps, n=N, impl="interpret")
    np.testing.assert_allclose(out.reshape(12, -1), hc.hc_post(x, y, maps.reshape(12, K), n=N, impl="jnp"), atol=2e-6)
    assert hc.kernel_runs(128, "interpret") and not hc.kernel_runs(64, "interpret") and not hc.kernel_runs(128, "jnp")
    assert not hc.kernel_runs(3584)         # off the TPU, "auto" is the jnp form


@pytest.mark.parametrize("impl", ["jnp", "interpret"])
def test_twenty_rounds_at_the_clamps_edges_are_doubly_stochastic(impl):
    """``r`` driven to the clamp's two edges (the bias at +-100, past -30 and
    30): a permutation at +30 over -30 elsewhere, and a pattern with two
    matchings; rows and columns sum to 1 within 1e-5, no inf, no nan."""
    perm = np.full((N, N), -100.0, np.float32)
    perm[np.arange(N), [2, 0, 3, 1]] = 100.0
    two = np.full((N, N), -100.0, np.float32)
    two[np.arange(N), np.arange(N)] = 100.0
    two[np.arange(N), (np.arange(N) + 1) % N] = 100.0
    x, phi, a, _, _ = _draw(16, jnp.float32, seed=2)
    for pattern in (perm, two):
        b = jnp.concatenate([jnp.zeros((2 * N,)), jnp.asarray(pattern).reshape(-1)])
        _, maps = hc.hc_pre(x, phi, a, b, impl=impl, **KW)
        M = np.asarray(maps[:, 2 * N:]).reshape(-1, N, N)
        assert np.isfinite(M).all() and M.min() >= 0.0
        assert np.abs(M.sum(-1) - 1).max() <= 1e-5 and np.abs(M.sum(-2) - 1).max() <= 1e-5
        assert np.abs(M[:, pattern > 0].sum(-1) - N).max() <= 1e-4       # the mass lies where the +30 entries are
