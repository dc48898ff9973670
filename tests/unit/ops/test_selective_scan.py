"""``ops/pallas/selective_scan.py``: the two kernels in interpret mode against
the ``lax.scan`` / elementwise forms the CPU runs, the convolution against a
direct sum, and what the served programs lean on: a row of ``dt`` 0 leaves the
state as it was, and a scan cut into chunks is the scan."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.pallas import selective_scan as ss

N = 16


def _inputs(rows, d, seed=0):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa: E731
    x, dt = f(rows, d), jnp.abs(f(rows, d)) * 0.1
    return x, dt, f(rows, N), f(rows, N), -jnp.exp(f(N, d)), f(d)


def _by_hand(x, dt, Bm, Cm, A, D, h):
    """The recurrence as the layer's papers write it, state ``[d, N]``, in numpy."""
    x, dt, Bm, Cm, A, D, h = (np.asarray(a, np.float64) for a in (x, dt, Bm, Cm, A.T, D, h.T))
    out = []
    for t in range(x.shape[0]):
        h = np.exp(dt[t][:, None] * A) * h + (dt[t] * x[t])[:, None] * Bm[t][None, :]
        out.append(h @ Cm[t] + D * x[t])
    return np.stack(out), h.T


@pytest.mark.parametrize("rows", [1, 7, 8, 37, 64, 131])
@pytest.mark.parametrize("d", [128, 384])
def test_chunk_kernel_interpreted_is_the_lax_scan_at_odd_row_counts(rows, d):
    x, dt, Bm, Cm, A, D = _inputs(rows, d, rows)
    h0 = jnp.asarray(np.random.default_rng(1).normal(size=(N, d)), jnp.float32)
    s0, h_0 = ss.scan_rows(x, dt, Bm, Cm, A, D, h0, impl="jnp")
    s1, h_1 = ss.scan_rows(x, dt, Bm, Cm, A, D, h0, impl="interpret")
    assert s1.shape == (rows, d) and h_1.shape == (N, d)
    np.testing.assert_allclose(s1, s0, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(h_1, h_0, rtol=1e-5, atol=1e-6)
    want_s, want_h = _by_hand(x, dt, Bm, Cm, A, D, h0)
    np.testing.assert_allclose(s0, want_s, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(h_0, want_h, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("slots,layer", [(3, 0), (8, 2), (16, 0), (24, 2)])
def test_step_kernel_interpreted_is_the_elementwise_step_and_touches_its_layer_alone(slots, layer):
    d = 256
    x, dt, Bm, Cm, A, D = _inputs(slots, d, slots)
    pool = jnp.asarray(np.random.default_rng(2).normal(size=(3, slots, N, d)), jnp.float32)
    s0, p0 = ss.scan_step(x, dt, Bm, Cm, A, D, pool, layer, impl="jnp")
    s1, p1 = ss.scan_step(x, dt, Bm, Cm, A, D, pool, layer, impl="interpret")
    np.testing.assert_allclose(s1, s0, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(p1, p0, rtol=1e-5, atol=1e-6)
    for other in set(range(3)) - {layer}:
        assert np.array_equal(p1[other], pool[other]) and np.array_equal(p0[other], pool[other])
    for b in (0, slots // 2, slots - 1):      # a slot's row is one step of the chunk entry from its own state
        s_b, h_b = ss.scan_rows(x[b:b + 1], dt[b:b + 1], Bm[b:b + 1], Cm[b:b + 1], A, D, pool[layer, b], impl="jnp")
        np.testing.assert_allclose(s0[b], s_b[0], rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(p0[layer, b], h_b, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("impl", ["jnp", "interpret"])
def test_a_row_of_dt_zero_leaves_the_state_as_it_was(impl):
    d = 128
    x, dt, Bm, Cm, A, D = _inputs(12, d)
    h0 = jnp.asarray(np.random.default_rng(3).normal(size=(N, d)), jnp.float32)
    real = (jnp.arange(12) < 5)[:, None]
    _, h5 = ss.scan_rows(x[:5], dt[:5], Bm[:5], Cm[:5], A, D, h0, impl=impl)
    _, h12 = ss.scan_rows(x, jnp.where(real, dt, 0.0), Bm, Cm, A, D, h0, impl=impl)
    assert np.array_equal(h12, h5)                       # padding behind the 5 real rows: not a bit moved
    pool = jnp.stack([h0, h0 * 2.0])[None]               # [1, 2, N, d]; slot 1 idle
    s, out = ss.scan_step(x[:2], dt[:2] * jnp.array([[1.0], [0.0]]), Bm[:2], Cm[:2], A, D, pool, 0, impl=impl)
    assert np.array_equal(out[0, 1], pool[0, 1]) and not np.array_equal(out[0, 0], pool[0, 0])


@pytest.mark.parametrize("cuts", [(19,), (8, 11), (4, 4, 4, 4, 3), (1, 18)])
@pytest.mark.parametrize("impl", ["jnp", "interpret"])
def test_a_scan_cut_into_chunks_is_the_scan(cuts, impl):
    d = 128
    x, dt, Bm, Cm, A, D = _inputs(19, d, 5)
    h = jnp.zeros((N, d), jnp.float32)
    s_whole, h_whole = ss.scan_rows(x, dt, Bm, Cm, A, D, h, impl="jnp")
    parts, at = [], 0
    for n in cuts:
        s, h = ss.scan_rows(x[at:at + n], dt[at:at + n], Bm[at:at + n], Cm[at:at + n], A, D, h, impl=impl)
        parts.append(s)
        at += n
    np.testing.assert_allclose(jnp.concatenate(parts), s_whole, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(h, h_whole, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("lead", [(), (3,)])
def test_conv_rows_is_the_causal_depthwise_sum_and_hands_on_its_last_rows(lead):
    rng = np.random.default_rng(0)
    K, T, d = 4, 9, 6
    w, b = rng.normal(size=(d, K)), rng.normal(size=(d,))
    xs, prev = rng.normal(size=(*lead, T, d)), rng.normal(size=(*lead, K - 1, d))
    c, full = ss.conv_rows(jnp.asarray(w, jnp.float32), jnp.asarray(b, jnp.float32),
                           jnp.asarray(xs, jnp.float32), jnp.asarray(prev, jnp.float32))
    cat = np.concatenate([prev, xs], axis=-2)
    want = b + sum(w[:, k] * cat[..., k:k + T, :] for k in range(K))
    np.testing.assert_allclose(c, want / (1 + np.exp(-want)), rtol=1e-5, atol=1e-5)
    assert full.shape == (*lead, T + K - 1, d) and np.allclose(full[..., T:, :], xs[..., T - K + 1:, :])
    # in two calls: the second starts from the first's last K - 1 rows
    c1, f1 = ss.conv_rows(jnp.asarray(w, jnp.float32), jnp.asarray(b, jnp.float32),
                          jnp.asarray(xs[..., :5, :], jnp.float32), jnp.asarray(prev, jnp.float32))
    c2, _ = ss.conv_rows(jnp.asarray(w, jnp.float32), jnp.asarray(b, jnp.float32),
                         jnp.asarray(xs[..., 5:, :], jnp.float32), f1[..., 5:, :])
    np.testing.assert_allclose(jnp.concatenate([c1, c2], axis=-2), c, rtol=1e-6, atol=1e-6)


def test_the_kernels_run_on_whole_lane_tiles_of_channels_and_never_under_jnp(monkeypatch):
    assert not ss.kernel_runs(5120)                                   # the CPU: the lax.scan form
    assert ss.kernel_runs(5120, "pallas") and ss.kernel_runs(128, "interpret")
    assert not ss.kernel_runs(64, "pallas") and not ss.kernel_runs(5120, "jnp")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ss.kernel_runs(5120) and not ss.kernel_runs(5120, "jnp") and not ss.kernel_runs(100)
    assert (ss._lane_block(5120), ss._lane_block(768), ss._lane_block(128)) == (512, 256, 128)
