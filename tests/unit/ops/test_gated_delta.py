"""The gated delta rule (``ops/pallas/gated_delta.py``): the chunk form and the
step form against the token-by-token recurrence: the lax forms on the CPU at
small heads, the kernels in interpret mode at ONE shape each, with decays that
sum far below -40 inside a sub-chunk (no inf, no nan), live slots alone moved."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.pallas import gated_delta as gd

TOL = 2e-6


def _draw(T, Hk, Hv, dk, dv, seed=0, gscale=1.0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(ks[0], (T, Hk, dk))) / np.sqrt(dk)
    k = unit(jax.random.normal(ks[1], (T, Hk, dk)))
    v = jax.random.normal(ks[2], (T, Hv, dv))
    g = -jnp.exp(jax.random.uniform(ks[3], (T, Hv), minval=-6.0, maxval=1.0)) * gscale
    beta = jax.random.uniform(ks[4], (T, Hv), minval=0.1, maxval=0.9)
    return q, k, v, g, beta, jax.random.normal(ks[5], (Hv, dk, dv))


@jax.jit
def _recurrence(q, k, v, g, beta, S0):
    Hv = v.shape[1]
    return gd.recurrence(gd._repeat(q, Hv), gd._repeat(k, Hv), v, g, beta, S0)


# 150 rows: two whole sub-chunks and 22 rows of a third (padded with g = beta = 0); 70: one and 6
@pytest.mark.parametrize("impl,shape,gscale", [
    ("jnp", (150, 2, 4, 16, 32), 1.0), ("jnp", (150, 2, 4, 16, 32), 30.0), ("interpret", (70, 1, 2, 128, 128), 30.0),
])
def test_the_chunk_form_is_the_recurrence(impl, shape, gscale):
    q, k, v, g, beta, S0 = _draw(*shape, gscale=gscale)
    assert gscale == 1.0 or float(g[:64].sum(0).min()) < -40.0      # a sub-chunk's decays sum far below what a ratio of exponentials holds
    o_r, S_r = _recurrence(q, k, v, g, beta, S0)
    o, S1 = jax.jit(lambda *a: gd.chunk_rows(*a, impl=impl))(q, k, v, g, beta, S0)
    assert bool(jnp.isfinite(o).all()) and bool(jnp.isfinite(S1).all())
    assert float(jnp.abs(o - o_r).max()) <= TOL and float(jnp.abs(S1 - S_r).max()) <= TOL


# (the lax step form runs under every served decode step of tests/unit/test_serving_qwen3_next.py)
@pytest.mark.parametrize("impl,shape", [("interpret", (1, 2, 128, 128))])
def test_the_step_form_is_the_recurrence_on_live_slots_and_leaves_the_others(impl, shape):
    B = 6
    q, k, v, g, beta, _ = _draw(B, *shape, seed=3)
    Hv, dk, dv = shape[1:]
    pool = jax.random.normal(jax.random.PRNGKey(9), (3, B, Hv, dk, dv))
    live = jnp.array([True, False, True, True, False, False])
    step = jax.jit(lambda q, k, v, g, beta, pool, live: gd.step(q, k, v, g, beta, pool, 1, live, impl=impl))
    o, out = step(q, k, v, g, beta, pool, live)
    o_rs, S_rs = jax.vmap(lambda *a: _recurrence(*(x[None] for x in a[:5]), a[5]))(q, k, v, g, beta, pool[1])
    for b in range(B):
        if live[b]:
            assert float(jnp.abs(o[b] - o_rs[b, 0]).max()) <= TOL and float(jnp.abs(out[1, b] - S_rs[b]).max()) <= TOL
        else:      # g = beta = 0 is how padding and idle slots are kept out: nothing moved
            assert float(jnp.abs(o[b]).max()) == 0.0 and bool((out[1, b] == pool[1, b]).all())
    assert bool((out[0] == pool[0]).all()) and bool((out[2] == pool[2]).all())      # the other layers as they lay
    o, out = step(q, k, v, g, beta, pool, jnp.zeros((B,), bool))                      # no live slot at all
    assert float(jnp.abs(o).max()) == 0.0 and bool((out == pool).all())


def test_the_kernels_run_for_whole_lane_tiles_only():
    assert gd.kernel_runs(128, 128, "pallas") and gd.kernel_runs(128, 256, "interpret")
    assert not gd.kernel_runs(128, 128, "jnp") and not gd.kernel_runs(64, 128, "pallas") and not gd.kernel_runs(128, 128, "auto")
