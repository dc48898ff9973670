"""Pallas flash-attention parity vs jnp reference (interpret mode on CPU).

Analog of reference tests/unit/test_cuda_forward.py / test_cuda_backward.py:
kernel vs reference-module outputs with tolerance sweeps.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.attention import causal_attention_jnp
from deepspeed_tpu.ops.pallas.flash_attention import flash_attention


def _qkv(B, S, H, D, seed=0, dtype=jnp.float32):
    rs = np.random.RandomState(seed)
    return [jnp.asarray(rs.randn(B, S, H, D), dtype) for _ in range(3)]


@pytest.mark.parametrize(
    "shape",
    [(1, 128, 2, 64), (2, 256, 2, 64), (1, 384, 1, 128), (1, 128, 3, 256),
     (3, 128, 2, 64)],
)
def test_forward_parity(shape):
    q, k, v = _qkv(*shape)
    o_ref = causal_attention_jnp(q, k, v)
    o = flash_attention(q, k, v, interpret=True)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal,sm_scale", [(True, None), (False, None), (True, 0.3)])
def test_backward_parity(causal, sm_scale):
    q, k, v = _qkv(2, 256, 2, 64, seed=1)
    scale = sm_scale if sm_scale is not None else 1.0 / np.sqrt(64)

    def ref_attn(q, k, v):
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
        if causal:
            mask = jnp.tril(jnp.ones((256, 256), jnp.bool_))
            logits = jnp.where(mask[None, None], logits, jnp.float32(-1e30))
        probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
        return jnp.einsum("bhqk,bkhd->bqhd", probs, v)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal, sm_scale=sm_scale, interpret=True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(ref_attn(q, k, v) ** 2)

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5, rtol=5e-4)


def test_non_causal():
    q, k, v = _qkv(1, 128, 2, 64, seed=2)
    o = flash_attention(q, k, v, causal=False, interpret=True)
    # full attention reference
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(64)
    probs = jax.nn.softmax(logits, axis=-1)
    o_ref = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref), atol=2e-5, rtol=2e-5)


def test_bf16_inputs():
    q, k, v = _qkv(1, 128, 2, 64, seed=3, dtype=jnp.bfloat16)
    o = flash_attention(q, k, v, interpret=True)
    o_ref = causal_attention_jnp(q, k, v)
    assert o.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(o, np.float32), np.asarray(o_ref, np.float32), atol=3e-2, rtol=3e-2
    )


def test_seq_not_multiple_raises():
    q, k, v = _qkv(1, 100, 1, 64)
    with pytest.raises(ValueError):
        flash_attention(q, k, v, interpret=True)


class TestGQA:
    """Grouped-query attention through the kernels: K/V carry fewer heads,
    read via divided batch index maps (never materialized per q head)."""

    def _ref(self, q, k, v, causal=True):
        B, S, H, D = q.shape
        KV = k.shape[2]
        rep = H // KV
        kf = jnp.repeat(k, rep, axis=2)  # reference materializes; kernel must not
        vf = jnp.repeat(v, rep, axis=2)
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, kf).astype(jnp.float32) / np.sqrt(D)
        if causal:
            mask = jnp.tril(jnp.ones((S, S), jnp.bool_))
            logits = jnp.where(mask[None, None], logits, jnp.float32(-1e30))
        probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
        return jnp.einsum("bhqk,bkhd->bqhd", probs, vf)

    @pytest.mark.parametrize("rep,causal,B", [(2, True, 1), (4, True, 1), (2, False, 1), (2, True, 2)])
    def test_forward_parity(self, rep, causal, B):
        # B=2 case guards the batch-major flattening invariant the
        # bh // kv_rep index-map trick depends on
        S, H, D = 256, 4, 64
        rs = np.random.RandomState(11)
        q = jnp.asarray(rs.randn(B, S, H, D), jnp.float32)
        k = jnp.asarray(rs.randn(B, S, H // rep, D), jnp.float32)
        v = jnp.asarray(rs.randn(B, S, H // rep, D), jnp.float32)
        o = flash_attention(q, k, v, causal=causal, interpret=True)
        np.testing.assert_allclose(
            np.asarray(o), np.asarray(self._ref(q, k, v, causal)), atol=2e-5, rtol=2e-5
        )

    def test_backward_parity(self):
        B, S, H, D, rep = 2, 256, 4, 64, 2
        rs = np.random.RandomState(12)
        q = jnp.asarray(rs.randn(B, S, H, D), jnp.float32)
        k = jnp.asarray(rs.randn(B, S, H // rep, D), jnp.float32)
        v = jnp.asarray(rs.randn(B, S, H // rep, D), jnp.float32)

        g1 = jax.grad(
            lambda q, k, v: jnp.sum(flash_attention(q, k, v, interpret=True) ** 2),
            argnums=(0, 1, 2),
        )(q, k, v)
        g2 = jax.grad(
            lambda q, k, v: jnp.sum(self._ref(q, k, v) ** 2), argnums=(0, 1, 2)
        )(q, k, v)
        for a, b in zip(g1, g2):
            assert a.shape == b.shape  # dk/dv at KV heads, not repeated
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5, rtol=5e-4)

    def test_gqa_through_grid_variant(self, monkeypatch):
        from deepspeed_tpu.ops.pallas import flash_attention as fa

        monkeypatch.setattr(fa, "VMEM_RESIDENT_BYTES", 1)  # force grid path
        B, S, H, D, rep = 1, 256, 4, 64, 2
        rs = np.random.RandomState(13)
        q = jnp.asarray(rs.randn(B, S, H, D), jnp.float32)
        k = jnp.asarray(rs.randn(B, S, H // rep, D), jnp.float32)
        v = jnp.asarray(rs.randn(B, S, H // rep, D), jnp.float32)
        o = fa.flash_attention(q, k, v, interpret=True)
        np.testing.assert_allclose(
            np.asarray(o), np.asarray(self._ref(q, k, v)), atol=2e-5, rtol=2e-5
        )
        gk = jax.grad(
            lambda k: jnp.sum(fa.flash_attention(q, k, v, interpret=True) ** 2)
        )(k)
        gk_ref = jax.grad(lambda k: jnp.sum(self._ref(q, k, v) ** 2))(k)
        np.testing.assert_allclose(np.asarray(gk), np.asarray(gk_ref), atol=5e-5, rtol=5e-4)

    def test_bad_head_ratio_raises(self):
        q = jnp.zeros((1, 128, 4, 64))
        k = jnp.zeros((1, 128, 3, 64))
        with pytest.raises(ValueError, match="divide"):
            flash_attention(q, k, k, interpret=True)


class TestGridVariant:
    """KV-blocked kernels: K/V stream through the grid with online-softmax
    state in VMEM scratch — the no-sequence-bound path used past the
    whole-K/V budget."""

    def _grid(self, q, k, v, causal=True, sm_scale=None):
        from deepspeed_tpu.ops.pallas.flash_attention import _flash_grid

        B, S, H, D = q.shape
        scale = sm_scale if sm_scale is not None else 1.0 / np.sqrt(D)

        def to3(x):
            return x.transpose(0, 2, 1, 3).reshape(B * H, S, D)

        o3 = _flash_grid(to3(q), to3(k), to3(v), float(scale), causal, True)
        return o3.reshape(B, H, S, D).transpose(0, 2, 1, 3)

    @pytest.mark.parametrize("shape", [(1, 256, 2, 64), (1, 384, 1, 128)])
    def test_forward_parity(self, shape):
        q, k, v = _qkv(*shape, seed=5)
        np.testing.assert_allclose(
            np.asarray(self._grid(q, k, v)),
            np.asarray(causal_attention_jnp(q, k, v)),
            atol=2e-5, rtol=2e-5,
        )

    def test_forward_matches_resident_kernel(self):
        q, k, v = _qkv(2, 256, 2, 64, seed=6)
        np.testing.assert_allclose(
            np.asarray(self._grid(q, k, v)),
            np.asarray(flash_attention(q, k, v, interpret=True)),
            atol=1e-6, rtol=1e-6,
        )

    @pytest.mark.parametrize("causal", [True, False])
    def test_backward_parity(self, causal):
        q, k, v = _qkv(1, 256, 2, 64, seed=7)
        scale = 1.0 / np.sqrt(64)

        def ref_attn(q, k, v):
            logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
            if causal:
                mask = jnp.tril(jnp.ones((256, 256), jnp.bool_))
                logits = jnp.where(mask[None, None], logits, jnp.float32(-1e30))
            probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
            return jnp.einsum("bhqk,bkhd->bqhd", probs, v)

        g1 = jax.grad(
            lambda q, k, v: jnp.sum(self._grid(q, k, v, causal=causal) ** 2),
            argnums=(0, 1, 2),
        )(q, k, v)
        g2 = jax.grad(
            lambda q, k, v: jnp.sum(ref_attn(q, k, v) ** 2), argnums=(0, 1, 2)
        )(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5, rtol=5e-4)

    def test_past_budget_dispatches_to_grid(self, monkeypatch):
        """flash_attention no longer raises past the VMEM budget: it streams."""
        from deepspeed_tpu.ops.pallas import flash_attention as fa

        monkeypatch.setattr(fa, "VMEM_RESIDENT_BYTES", 1)
        q, k, v = _qkv(1, 256, 1, 64, seed=8)
        o = fa.flash_attention(q, k, v, interpret=True)
        np.testing.assert_allclose(
            np.asarray(o), np.asarray(causal_attention_jnp(q, k, v)),
            atol=2e-5, rtol=2e-5,
        )

    def test_grid_ceiling_raises_and_predicate_agrees(self, monkeypatch):
        """Past GRID_KERNEL_MAX_SEQ flash_attention rejects with a clear
        message, and the shared flash_ok predicate agrees (so 'auto'
        dispatchers never route a shape the kernel would refuse)."""
        from deepspeed_tpu.ops.pallas import flash_attention as fa

        monkeypatch.setattr(fa, "GRID_KERNEL_MAX_SEQ", 128)
        assert fa.flash_ok(128, 64) and not fa.flash_ok(256, 64)
        q, k, v = _qkv(1, 256, 1, 64, seed=9)
        with pytest.raises(ValueError, match="ceiling"):
            fa.flash_attention(q, k, v, interpret=True)


def _windowed_ref(q, k, v, window, sm_scale=None):
    """jnp reference for sliding-window causal attention: key j visible to
    query i iff i - window < j <= i (window 0 = global)."""
    B, S, H, D = q.shape
    scale = sm_scale if sm_scale is not None else 1.0 / (D**0.5)
    logits = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * scale
    i = jnp.arange(S)[:, None]
    j = jnp.arange(S)[None, :]
    keep = j <= i
    if window > 0:
        keep = keep & (j > i - window)
    logits = jnp.where(keep[None, None], logits, jnp.float32(-1e30))
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


class TestSlidingWindow:
    """Sliding-window flash (Mistral sliding_window / GPT-Neo local layers):
    the kernel's loop bounds skip blocks wholly outside the band and the
    in-block mask trims the rest."""

    @pytest.mark.parametrize("window", [1, 37, 128, 200, 256, 1000])
    def test_forward_parity(self, window):
        q, k, v = _qkv(1, 256, 2, 64, seed=11)
        o = flash_attention(q, k, v, interpret=True, window=window)
        np.testing.assert_allclose(
            np.asarray(o), np.asarray(_windowed_ref(q, k, v, window)),
            atol=2e-5, rtol=2e-5,
        )

    def test_window_geq_seq_equals_global(self):
        q, k, v = _qkv(1, 128, 2, 64, seed=12)
        o = flash_attention(q, k, v, interpret=True, window=128)
        o_ref = flash_attention(q, k, v, interpret=True)
        np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref), atol=0, rtol=0)

    @pytest.mark.parametrize("window", [64, 130])
    def test_backward_parity(self, window):
        q, k, v = _qkv(1, 256, 2, 64, seed=13)

        def loss_k(q, k, v):
            return jnp.sum(
                flash_attention(q, k, v, interpret=True, window=window) ** 2
            )

        def loss_r(q, k, v):
            return jnp.sum(_windowed_ref(q, k, v, window) ** 2)

        g1 = jax.grad(loss_k, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(loss_r, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5, rtol=5e-4)

    def test_traced_window_one_compile_serves_all(self):
        """The window rides a scalar-prefetch operand, so a traced per-layer
        window works under jit/scan (GPT-Neo alternating local/global)."""
        q, k, v = _qkv(1, 256, 2, 64, seed=14)

        @jax.jit
        def f(w):
            return flash_attention(q, k, v, interpret=True, window=w)

        for w in (0, 64, 256):
            np.testing.assert_allclose(
                np.asarray(f(jnp.int32(w))),
                np.asarray(_windowed_ref(q, k, v, w)),
                atol=2e-5, rtol=2e-5,
            )

    def test_gqa_windowed(self):
        q, _, _ = _qkv(1, 256, 4, 64, seed=15)
        _, k, v = _qkv(1, 256, 2, 64, seed=16)
        o = flash_attention(q, k, v, interpret=True, window=100)
        kr = jnp.repeat(k, 2, axis=2)
        vr = jnp.repeat(v, 2, axis=2)
        np.testing.assert_allclose(
            np.asarray(o), np.asarray(_windowed_ref(q, kr, vr, 100)),
            atol=2e-5, rtol=2e-5,
        )

    def test_noncausal_window_rejected(self):
        q, k, v = _qkv(1, 128, 1, 64)
        with pytest.raises(ValueError, match="causal"):
            flash_attention(q, k, v, causal=False, interpret=True, window=8)

    def test_window_needs_resident(self, monkeypatch):
        from deepspeed_tpu.ops.pallas import flash_attention as fa

        monkeypatch.setattr(fa, "VMEM_RESIDENT_BYTES", 1)
        q, k, v = _qkv(1, 128, 1, 64)
        assert not fa.windowed_flash_ok(128, 64, 4)
        with pytest.raises(ValueError, match="resident"):
            fa.flash_attention(q, k, v, interpret=True, window=8)


# -- the plan of the resident kernels (ISSUE 33) -------------------------------

PLAN_SIZES = (128, 256, 512)
PLANS = [(bq, bk) for bq in PLAN_SIZES for bk in PLAN_SIZES]
# one narrower than any block, one that spans several, one that reaches past S
WINDOWS = (None, 100, 300, 2000)


def _set_plan(monkeypatch, bq, bk):
    """Make the rule answer (bq, bk) wherever they divide S, 128 elsewhere."""
    from deepspeed_tpu.ops.pallas import flash_attention as fa

    def plan(S, D, itemsize, kv_rep=1, backward=False, causal=True):
        return (bq, bk) if S % bq == 0 and S % bk == 0 else (fa.BQ, fa.BK)

    monkeypatch.setattr(fa, "flash_plan", plan)
    return fa


def _mask_every_pair(monkeypatch, fa):
    """The kernels as they were before the walk was split: one range, every
    live pair masked."""
    k_walk, q_walk = fa._k_walk, fa._q_walk

    def one_range(walk):
        def f(*args):
            (lo, _, _, _), _, (_, hi, _, _) = walk(*args)
            return ((lo, hi, True, None),)
        return f

    monkeypatch.setattr(fa, "_k_walk", one_range(k_walk))
    monkeypatch.setattr(fa, "_q_walk", one_range(q_walk))


class TestPlan:
    """The resident kernels under every plan: the q rows of a grid step and
    the width of one inner iteration, equal and unequal, with the window's
    edge inside a block, across several, and nowhere."""

    S = 1024

    @pytest.mark.parametrize("window", WINDOWS)
    @pytest.mark.parametrize("D", [64, 128])
    @pytest.mark.parametrize("bq,bk", PLANS)
    def test_forward_parity(self, monkeypatch, bq, bk, D, window):
        fa = _set_plan(monkeypatch, bq, bk)
        q, k, v = _qkv(1, self.S, 1, D, seed=21)
        o = fa.flash_attention(q, k, v, interpret=True, window=window)
        np.testing.assert_allclose(
            np.asarray(o), np.asarray(_windowed_ref(q, k, v, window or 0)),
            atol=2e-5, rtol=2e-5,
        )

    @pytest.mark.parametrize("window", WINDOWS[:3])
    @pytest.mark.parametrize("bq,bk", PLANS)
    def test_backward_parity(self, monkeypatch, bq, bk, window):
        self._backward(monkeypatch, bq, bk, 64, window)

    @pytest.mark.parametrize("bq,bk", [(128, 512), (512, 256), (512, 512)])
    def test_backward_parity_d128(self, monkeypatch, bq, bk):
        self._backward(monkeypatch, bq, bk, 128, None)

    @pytest.mark.parametrize("window", [None, 300])
    @pytest.mark.parametrize("bq,bk", [(128, 256), (256, 128), (512, 512)])
    def test_backward_parity_split_kernels(self, monkeypatch, bq, bk, window):
        """Past the fused backward's budget dq and dk/dv come from the two
        split kernels, which walk k blocks and q blocks."""
        from deepspeed_tpu.ops.pallas import flash_attention as fa

        monkeypatch.setattr(fa, "FUSED_BWD_BYTES", 1)
        self._backward(monkeypatch, bq, bk, 64, window)

    def _backward(self, monkeypatch, bq, bk, D, window, H=1, KV=1):
        fa = _set_plan(monkeypatch, bq, bk)
        q, _, _ = _qkv(1, self.S, H, D, seed=22)
        _, k, v = _qkv(1, self.S, KV, D, seed=23)

        def ref(q, k, v):
            kr, vr = (jnp.repeat(x, H // KV, axis=2) for x in (k, v))
            return jnp.sum(_windowed_ref(q, kr, vr, window or 0) ** 2)

        g1 = jax.grad(
            lambda q, k, v: jnp.sum(
                fa.flash_attention(q, k, v, interpret=True, window=window) ** 2
            ),
            argnums=(0, 1, 2),
        )(q, k, v)
        g2 = jax.grad(ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            assert a.shape == b.shape
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5, rtol=5e-4)

    @pytest.mark.parametrize("bq,bk", PLANS)
    def test_gqa(self, monkeypatch, bq, bk):
        fa = _set_plan(monkeypatch, bq, bk)
        q, _, _ = _qkv(1, self.S, 2, 64, seed=24)
        _, k, v = _qkv(1, self.S, 1, 64, seed=25)
        o = fa.flash_attention(q, k, v, interpret=True)
        kr, vr = (jnp.repeat(x, 2, axis=2) for x in (k, v))
        np.testing.assert_allclose(
            np.asarray(o), np.asarray(_windowed_ref(q, kr, vr, 0)),
            atol=2e-5, rtol=2e-5,
        )

    @pytest.mark.parametrize("bq,bk", [(256, 512), (512, 512)])
    def test_gqa_backward(self, monkeypatch, bq, bk):
        self._backward(monkeypatch, bq, bk, 64, None, H=2, KV=1)

    @pytest.mark.parametrize("bq,bk", [(256, 256), (512, 128)])
    def test_non_causal(self, monkeypatch, bq, bk):
        fa = _set_plan(monkeypatch, bq, bk)
        q, k, v = _qkv(1, 512, 1, 64, seed=26)
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(64)
        o_ref = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(logits, axis=-1), v)
        o = fa.flash_attention(q, k, v, causal=False, interpret=True)
        np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref), atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("window", [None, 300])
    @pytest.mark.parametrize("bq,bk", PLANS)
    def test_unmasked_pairs_change_no_value(self, monkeypatch, bq, bk, window):
        """Splitting the walk changes no bit: outputs and gradients are
        bitwise what the kernels give with the mask on every live pair
        (an interior pair's mask selects every element)."""
        self._bitwise(monkeypatch, _set_plan(monkeypatch, bq, bk), window)

    @pytest.mark.parametrize("window", [None, 300])
    @pytest.mark.parametrize("b", PLAN_SIZES)
    def test_unmasked_pairs_change_no_value_split_kernels(self, monkeypatch, b, window):
        fa = _set_plan(monkeypatch, b, b)
        monkeypatch.setattr(fa, "FUSED_BWD_BYTES", 1)
        self._bitwise(monkeypatch, fa, window)

    def _bitwise(self, monkeypatch, fa, window):
        q, k, v = _qkv(1, self.S, 1, 64, seed=27)

        def run():
            f = lambda q, k, v: fa.flash_attention(q, k, v, interpret=True, window=window)
            o, vjp = jax.vjp(f, q, k, v)
            return (o,) + vjp(jnp.ones_like(o))

        split = run()
        _mask_every_pair(monkeypatch, fa)
        for a, b in zip(split, run()):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    @pytest.mark.parametrize("rows,bk", PLANS + [(384, 128), (128, 384), (1024, 512)])
    def test_walks_cut_where_the_edges_are(self, rows, bk):
        """The k walk of a piece of rows and the q walk of a block of keys:
        a pair is visited iff some position of it is visible, is unmasked
        iff every position is, and a range's static trip count is its length;
        as Python ints (a grid step that takes the whole sequence) and as
        traced scalars alike. ``plan_pairs`` counts the same pairs."""
        from deepspeed_tpu.ops.pallas import flash_attention as fa

        S = 3072 if 384 in (rows, bk) else 2048
        nq, nk = S // rows, S // bk
        pos = np.arange(S)

        def classes(r, c, window):
            keep = c[None, :] <= r[:, None]
            if window:
                keep &= c[None, :] > r[:, None] - window
            return keep.any(), keep.all()

        def cuts(walk, edge):
            starts = [int(x[0]) for x in walk] + [int(walk[-1][1])]
            trips = walk[edge][3]
            assert trips is None or trips == starts[edge + 1] - starts[edge]
            assert [x[2] for x in walk] == [True, False, True]
            return starts

        for window in (None, 0, 1, 100, 128, 129, 300, 512, 1000, 5000):
            for as_int in (int, jnp.int32):
                win = None if window is None else jnp.int32(window)
                for i in range(nq):
                    lo, a, b, hi = cuts(fa._k_walk(as_int(i * rows), rows, nk, bk, win), 2)
                    for j in range(nk):
                        live, full = classes(pos[i * rows:(i + 1) * rows], pos[j * bk:(j + 1) * bk], window)
                        assert (live, full) == (lo <= j < hi, a <= j < b), (window, i, j)
                for j in range(nk):
                    lo, a, b, hi = cuts(fa._q_walk(as_int(j * bk), bk, nq, rows, win), 0)
                    for i in range(nq):
                        live, full = classes(pos[i * rows:(i + 1) * rows], pos[j * bk:(j + 1) * bk], window)
                        assert (live, full) == (lo <= i < hi, a <= i < b), (window, i, j)
        if rows <= bk:      # a kernel's pieces are min(bq, bk) rows
            masked = plain = 0
            for i in range(nq):
                lo, a, b, hi = cuts(fa._k_walk(i * rows, rows, nk, bk, None), 2)
                masked, plain = masked + (a - lo) + (hi - b), plain + (b - a)
            assert (masked, plain) == fa.plan_pairs(S, rows, bk)


class TestPlanRule:
    """flash_plan, the rule itself: what it answers has to divide S and fit
    the VMEM it reckons, and it answers 128 where the chip was not asked."""

    @pytest.mark.parametrize("backward", [False, True], ids=["fwd", "bwd"])
    @pytest.mark.parametrize(
        "S,D,kv_rep",
        [(1024, 64, 1),     # both training cells: [4, 1024, 25, 64] bf16 a chip
         (1024, 128, 1), (1024, 128, 4), (2048, 64, 1), (512, 64, 1), (768, 64, 1),
         (4096, 128, 1), (4096, 64, 1)],
    )
    def test_plan_divides_s_and_fits(self, S, D, kv_rep, backward):
        from deepspeed_tpu.ops.pallas import flash_attention as fa

        bq, bk = fa.flash_plan(S, D, 2, kv_rep, backward=backward)
        assert S % bq == 0 and S % bk == 0 and bq % fa.BQ == 0 and bk % fa.BK == 0
        assert max(bq, bk) % min(bq, bk) == 0      # the causal edge's range is static
        assert fa.plan_vmem_bytes(S, D, 2, kv_rep, bq, bk, backward) <= fa.PLAN_VMEM_BYTES
        assert (bq, bk) != (fa.BQ, fa.BK)          # a measured shape gets a measured plan

    @pytest.mark.parametrize(
        "S,D,itemsize",
        [(384, 64, 2),      # 128 is all that divides it
         (128, 64, 2), (256, 64, 2), (1024, 256, 2), (1024, 64, 4), (1024, 16, 2)],
    )
    def test_falls_back_to_128(self, S, D, itemsize):
        from deepspeed_tpu.ops.pallas import flash_attention as fa

        for backward in (False, True):
            assert fa.flash_plan(S, D, itemsize, backward=backward) == (128, 128)

    def test_flash_ok_agrees_with_what_the_kernels_accept(self):
        from deepspeed_tpu.ops.pallas import flash_attention as fa

        for S in (128, 384, 512, 1024, 1536, 4096):
            assert fa.flash_ok(S, 64)
            for backward in (False, True):
                bq, bk = fa.flash_plan(S, 64, 2, backward=backward)
                assert S % bq == 0 and S % bk == 0
        assert not fa.flash_ok(192, 64)

    @pytest.mark.parametrize("dtype", [jnp.bfloat16])
    def test_cells_shape_under_its_own_plan(self, dtype):
        """The training cells' S, D and dtype, under the plan the rule
        picks for them (no monkeypatch): forward and gradients against the
        reference."""
        from deepspeed_tpu.ops.pallas import flash_attention as fa

        assert fa.flash_plan(1024, 64, 2) != (128, 128)
        q, k, v = _qkv(1, 1024, 1, 64, seed=28, dtype=dtype)
        f = lambda q, k, v: fa.flash_attention(q, k, v, interpret=True)
        o, vjp = jax.vjp(f, q, k, v)
        o_ref, vjp_ref = jax.vjp(causal_attention_jnp, q, k, v)
        np.testing.assert_allclose(
            np.asarray(o, np.float32), np.asarray(o_ref, np.float32), atol=3e-2, rtol=3e-2
        )
        for a, b in zip(vjp(jnp.ones_like(o)), vjp_ref(jnp.ones_like(o))):
            np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(b, np.float32), atol=6e-2, rtol=6e-2
            )


class TestFlashPlanGauges:
    """What the train step's ``ds.init.programs`` phase and the registry say
    of the plan its flash forward was traced under (gpt2-tiny, the kernel
    body in Pallas's interpreter)."""

    def _engine(self, monkeypatch, attn_impl, trace_path):
        import functools

        import deepspeed_tpu
        from deepspeed_tpu.models import gpt2
        from deepspeed_tpu.ops.pallas import flash_attention as fa
        from deepspeed_tpu.parallel.topology import MeshSpec

        monkeypatch.setattr(
            fa, "flash_attention", functools.partial(fa.flash_attention, interpret=True)
        )
        cfg = gpt2.get_config("gpt2-tiny", attn_impl=attn_impl, n_positions=256)
        mesh = MeshSpec(dp=1, devices=jax.devices()[:1]).build_mesh()
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=gpt2.make_module(cfg), mesh=mesh, seed=0,
            config={
                "train_micro_batch_size_per_gpu": 2,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                "zero_optimization": {"stage": 3},
                "steps_per_print": 10**9,
                "telemetry": {"enabled": True, "trace_path": trace_path},
            },
        )
        return engine, cfg

    @pytest.mark.parametrize("attn_impl", ["pallas", "jnp"])
    def test_phase_attr_and_gauges(self, monkeypatch, tmp_path, attn_impl):
        from deepspeed_tpu.telemetry import spans

        t0 = spans._clock()
        engine, cfg = self._engine(monkeypatch, attn_impl, str(tmp_path / "traces"))
        ids = np.random.RandomState(0).randint(0, cfg.vocab_size, (2, 256)).astype(np.int32)
        for _ in range(2):      # the second call traces nothing and sets nothing
            engine.train_batch({"input_ids": ids})
        progs = [p for p in spans.phases(since=t0) if p[0] == "ds.init.programs"]
        assert len(progs) == 1 and set(progs[0][3]) == {"what", "flash_plan", "collectives", "gathers_ahead", "optim"}
        # two rows of 128 against two blocks of 128 a head: the diagonal pairs
        # are masked, the one below it is not; 2 x n_head heads a call
        heads = 2 * cfg.n_head
        want = (
            dict(bq=128, bk=128, masked=2 * heads, plain=heads)
            if attn_impl == "pallas" else dict(bq=0, bk=0, masked=0, plain=0)
        )
        assert progs[0][3]["flash_plan"] == " ".join(f"{k}={v}" for k, v in want.items())
        reg = engine.telemetry.registry
        pairs, block = reg.get("train_flash_block_pairs"), reg.get("train_flash_block")
        assert pairs.value(kind="masked") == want["masked"]
        assert pairs.value(kind="plain") == want["plain"]
        assert (block.value(dim="q"), block.value(dim="k")) == (want["bq"], want["bk"])
