"""The gated delta rule (``ops/pallas/gated_delta.py``): the chunk form and the
step form against the token-by-token recurrence: the lax forms on the CPU at
small heads, the kernels in interpret mode at ONE shape each, with decays that
sum far below -40 inside a sub-chunk (no inf, no nan), live slots alone moved.
Every case runs for both rules (``rank``): ONE decay a head (``g [T, Hv]``,
Gated DeltaNet: ``gdn_*``) and a decay a key channel (``g [T, Hv, dk]``, KDA:
``kda_*``, every decay inside ``(G_MIN, 0)``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.pallas import gated_delta as gd

TOL = 2e-6
G_MIN = -5.0          # the vector rule's lower bound (Ling-3.0-flash's kda_lower_bound)
RANKS = pytest.mark.parametrize("rank", [2, 3], ids=["scalar", "channels"])


def _draw(T, Hk, Hv, dk, dv, seed=0, gscale=1.0, rank=2):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(ks[0], (T, Hk, dk))) / np.sqrt(dk)
    k = unit(jax.random.normal(ks[1], (T, Hk, dk)))
    v = jax.random.normal(ks[2], (T, Hv, dv))
    g = -jnp.exp(jax.random.uniform(ks[3], (T, Hv) if rank == 2 else (T, Hv, dk), minval=-6.0, maxval=1.0)) * gscale
    if rank == 3:     # a bounded gate: under ``gscale`` a third of the entries sit AT the bound
        g = jnp.maximum(g, 0.999 * G_MIN)
    beta = jax.random.uniform(ks[4], (T, Hv), minval=0.1, maxval=0.9)
    return q, k, v, g, beta, jax.random.normal(ks[5], (Hv, dk, dv))


@jax.jit
def _recurrence(q, k, v, g, beta, S0):
    Hv = v.shape[1]
    return gd.recurrence(gd._repeat(q, Hv), gd._repeat(k, Hv), v, g, beta, S0)


# 150 rows: two whole sub-chunks and 22 rows of a third (padded with g = beta = 0); 70: one and 6
@RANKS
@pytest.mark.parametrize("impl,shape,gscale", [
    ("jnp", (150, 2, 4, 16, 32), 1.0), ("jnp", (150, 2, 4, 16, 32), 30.0), ("interpret", (70, 1, 2, 128, 128), 30.0),
])
def test_the_chunk_form_is_the_recurrence(impl, shape, gscale, rank):
    q, k, v, g, beta, S0 = _draw(*shape, gscale=gscale, rank=rank)
    assert gscale == 1.0 or float(g[:64].sum(0).min()) < -40.0      # a sub-chunk's decays sum far below what a ratio of exponentials holds
    if rank == 3 and gscale > 1.0:    # decays AT the bound on a third of the channels and rows: 64 rows sum far below -100
        assert float(jnp.mean(g <= 0.99 * G_MIN)) > 0.3 and float(g[:64].sum(0).min()) < -100.0
    o_r, S_r = _recurrence(q, k, v, g, beta, S0)
    o, S1 = jax.jit(lambda *a: gd.chunk_rows(*a, impl=impl, g_min=G_MIN))(q, k, v, g, beta, S0)
    assert bool(jnp.isfinite(o).all()) and bool(jnp.isfinite(S1).all())
    # the state of a channel at the bound is e^-300 of what it was: the error is of what the rows wrote
    assert float(jnp.abs(o - o_r).max()) <= TOL and float(jnp.abs(S1 - S_r).max()) <= (4 if rank == 3 else 1) * TOL


def test_decays_at_the_bound_on_some_heads_and_near_zero_on_others_at_the_published_head_shape():
    """64 rows at 128 x 128 through the interpreted ``kda_chunk``: ``g`` = -4.99
    on every channel of head 0, near 0 on head 1's: no inf, no nan, the
    token-by-token rule's result within the float32 tolerance."""
    q, k, v, _, beta, S0 = _draw(64, 2, 2, 128, 128, seed=5, rank=3)
    g = jnp.broadcast_to(jnp.array([-4.99, -1e-4])[None, :, None], (64, 2, 128))
    o_r, S_r = _recurrence(q, k, v, g, beta, S0)
    o, S1 = jax.jit(lambda *a: gd.chunk_rows(*a, impl="interpret", g_min=G_MIN))(q, k, v, g, beta, S0)
    assert bool(jnp.isfinite(o).all()) and bool(jnp.isfinite(S1).all())
    assert float(jnp.abs(o - o_r).max()) <= TOL and float(jnp.abs(S1 - S_r).max()) <= 8 * TOL


@pytest.mark.parametrize("impl", ["jnp"])
def test_the_vector_rule_with_every_channel_equal_is_the_scalar_rule(impl):
    q, k, v, g, beta, S0 = _draw(150, 2, 4, 16, 32, seed=2)
    g = jnp.maximum(g, 0.999 * G_MIN)
    wide = jnp.broadcast_to(g[:, :, None], (*g.shape, 16))
    o, S1 = gd.chunk_rows(q, k, v, g, beta, S0, impl=impl)
    o3, S3 = gd.chunk_rows(q, k, v, wide, beta, S0, impl=impl, g_min=G_MIN)
    assert float(jnp.abs(o - o3).max()) <= TOL and float(jnp.abs(S1 - S3).max()) <= 2 * TOL
    pool, live = jnp.stack([S0] * 5)[None], jnp.array([True, True, False, True, True])
    a, b = gd.step(q[:5], k[:5], v[:5], g[:5], beta[:5], pool, 0, live, impl=impl), gd.step(
        q[:5], k[:5], v[:5], wide[:5], beta[:5], pool, 0, live, impl=impl)
    assert float(jnp.abs(a[0] - b[0]).max()) <= TOL and float(jnp.abs(a[1] - b[1]).max()) <= TOL


def test_a_bound_the_diagonal_block_cannot_hold_is_refused_by_name():
    q, k, v, g, beta, S0 = _draw(70, 2, 4, 16, 32, rank=3)
    with pytest.raises(ValueError, match=r"kda_chunk: a decay's lower bound of -6.*exponent of 96, over the 85"):
        gd.chunk_rows(q, k, v, g, beta, S0, impl="jnp", g_min=-6.0)
    with pytest.raises(ValueError, match="kda_chunk: a decay a key channel needs its lower bound"):
        gd.chunk_rows(q, k, v, g, beta, S0, impl="jnp")
    assert gd.BLOCK * 5.0 <= gd.EXP_ROOM < 88.7 and gd.SUB % gd.BLOCK == 0
    assert (gd.KDA_STEP_KERNEL, gd.KDA_CHUNK_KERNEL, gd.STEP_KERNEL, gd.CHUNK_KERNEL) == ("kda_step", "kda_chunk", "gdn_step", "gdn_chunk")


# (the lax step form runs under every served decode step of tests/unit/test_serving_qwen3_next.py)
@RANKS
@pytest.mark.parametrize("impl,shape", [("interpret", (1, 2, 128, 128))])
def test_the_step_form_is_the_recurrence_on_live_slots_and_leaves_the_others(impl, shape, rank):
    B = 6
    q, k, v, g, beta, _ = _draw(B, *shape, seed=3, rank=rank)
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
