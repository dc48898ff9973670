"""Pallas decode-attention (KV cache) vs dense reference, interpret mode.

Reference analog: the softmax_context fused inference kernel
(transformer_inference.py:231) correctness tests."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.pallas.decode_attention import decode_attention


def _ref(q, k_cache, v_cache, pos):
    B, H, D = q.shape
    S = k_cache.shape[1]
    scores = jnp.einsum("bhd,bshd->bhs", q.astype(jnp.float32),
                        k_cache.astype(jnp.float32)) / np.sqrt(D)
    mask = jnp.arange(S)[None, None, :] <= pos
    scores = jnp.where(mask, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhs,bshd->bhd", probs, v_cache.astype(jnp.float32))


@pytest.mark.parametrize("pos", [0, 7, 63])
@pytest.mark.parametrize("shape", [(2, 64, 2, 64), (1, 1024, 4, 128)])
def test_matches_dense_reference(shape, pos):
    B, S, H, D = shape
    if pos >= S:
        pytest.skip("pos beyond cache")
    rs = np.random.RandomState(0)
    q = jnp.asarray(rs.randn(B, H, D), jnp.float32)
    k = jnp.asarray(rs.randn(B, S, H, D), jnp.float32)
    v = jnp.asarray(rs.randn(B, S, H, D), jnp.float32)
    out = decode_attention(q, k, v, jnp.int32(pos), interpret=True)
    ref = _ref(q, k, v, pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_same_compiled_kernel_all_positions():
    """pos is a runtime scalar: results vary with pos without retracing."""
    B, S, H, D = 1, 128, 2, 64
    rs = np.random.RandomState(1)
    q = jnp.asarray(rs.randn(B, H, D), jnp.float32)
    k = jnp.asarray(rs.randn(B, S, H, D), jnp.float32)
    v = jnp.asarray(rs.randn(B, S, H, D), jnp.float32)
    f = jax.jit(lambda pos: decode_attention(q, k, v, pos, interpret=True))
    o0 = f(jnp.int32(0))
    o1 = f(jnp.int32(100))
    np.testing.assert_allclose(np.asarray(o0), np.asarray(_ref(q, k, v, 0)), atol=2e-5)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(_ref(q, k, v, 100)), atol=2e-5)
    assert not np.allclose(np.asarray(o0), np.asarray(o1))


class TestGQADecode:
    """GQA decode: caches at KV heads read via divided head index maps."""

    def _ref_gqa(self, q, k_cache, v_cache, pos):
        B, H, D = q.shape
        S, KV = k_cache.shape[1], k_cache.shape[2]
        rep = H // KV
        kf = jnp.repeat(k_cache, rep, axis=2)
        vf = jnp.repeat(v_cache, rep, axis=2)
        return _ref(q, kf, vf, pos)

    @pytest.mark.parametrize("rep", [2, 4])
    @pytest.mark.parametrize("pos", [0, 31])
    def test_kernel_matches_reference(self, rep, pos):
        B, S, H, D = 2, 64, 4, 64
        rs = np.random.RandomState(1)
        q = jnp.asarray(rs.randn(B, H, D), jnp.float32)
        k = jnp.asarray(rs.randn(B, S, H // rep, D), jnp.float32)
        v = jnp.asarray(rs.randn(B, S, H // rep, D), jnp.float32)
        out = decode_attention(q, k, v, jnp.int32(pos), interpret=True)
        ref = self._ref_gqa(q, k, v, pos)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)

    def test_dispatcher_gqa_fallback_is_grouped(self):
        """cached_attention's jnp GQA path (no kernel off-TPU) matches the
        repeat-based reference without materializing the repeat."""
        from deepspeed_tpu.ops.attention import cached_attention

        B, S, H, D, rep = 2, 64, 4, 64, 2
        rs = np.random.RandomState(2)
        q = jnp.asarray(rs.randn(B, H, D), jnp.float32)
        k = jnp.asarray(rs.randn(B, S, H // rep, D), jnp.float32)
        v = jnp.asarray(rs.randn(B, S, H // rep, D), jnp.float32)
        out = cached_attention(q, k, v, jnp.int32(31), impl="jnp")
        ref = self._ref_gqa(q, k, v, 31)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)

    def test_bad_ratio_raises(self):
        q = jnp.zeros((1, 4, 64))
        k = jnp.zeros((1, 64, 3, 64))
        with pytest.raises(ValueError, match="divide"):
            decode_attention(q, k, k, jnp.int32(0), interpret=True)


class TestPagedDecode:
    """Paged variant (ISSUE 3): K/V gathered through a block table from a
    shared page pool — the serving subsystem's cache layout."""

    def _setup(self, B=3, H=4, KV=4, D=64, page=8, P=16, n=4, seed=0):
        rs = np.random.RandomState(seed)
        q = jnp.asarray(rs.randn(B, H, D), jnp.float32)
        kp = jnp.asarray(rs.randn(P, KV, page, D), jnp.float32)
        vp = jnp.asarray(rs.randn(P, KV, page, D), jnp.float32)
        # distinct non-scratch pages per slot: the gather must actually
        # follow the table, not page order
        bt = jnp.asarray(
            rs.choice(np.arange(1, P), (B * n,), replace=False).reshape(B, n),
            jnp.int32,
        )
        return q, kp, vp, bt

    @pytest.mark.parametrize("pos", [[0, 13, 31], [5, 5, 5]])
    def test_kernel_matches_jnp_gather_fallback(self, pos):
        from deepspeed_tpu.ops.attention import paged_cached_attention
        from deepspeed_tpu.ops.pallas.decode_attention import paged_decode_attention

        q, kp, vp, bt = self._setup()
        pos = jnp.asarray(pos, jnp.int32)
        out = paged_decode_attention(q, kp, vp, bt, pos, interpret=True)
        ref = paged_cached_attention(q, kp, vp, bt, pos, impl="jnp")
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)

    def test_matches_dense_kernel_on_gathered_view(self):
        """Paged(pool, table) == dense decode kernel on the logically
        contiguous per-slot cache — paging is pure data movement."""
        from deepspeed_tpu.ops.pallas.decode_attention import paged_decode_attention

        q, kp, vp, bt = self._setup(seed=1)
        B, n, page = 3, 4, 8
        pos = jnp.asarray([0, 17, 31], jnp.int32)
        out = paged_decode_attention(q, kp, vp, bt, pos, interpret=True)
        kd = jnp.swapaxes(kp[bt], 2, 3).reshape(B, n * page, 4, 64)
        vd = jnp.swapaxes(vp[bt], 2, 3).reshape(B, n * page, 4, 64)
        for b in range(B):
            ref = decode_attention(
                q[b : b + 1], kd[b : b + 1], vd[b : b + 1], pos[b], interpret=True
            )
            np.testing.assert_allclose(
                np.asarray(out[b]), np.asarray(ref[0]), atol=2e-5, rtol=2e-5
            )

    def test_gqa_pool(self):
        from deepspeed_tpu.ops.attention import paged_cached_attention
        from deepspeed_tpu.ops.pallas.decode_attention import paged_decode_attention

        q, _, _, bt = self._setup()
        rs = np.random.RandomState(2)
        kp = jnp.asarray(rs.randn(16, 2, 8, 64), jnp.float32)  # KV=2 < H=4
        vp = jnp.asarray(rs.randn(16, 2, 8, 64), jnp.float32)
        pos = jnp.asarray([3, 9, 30], jnp.int32)
        out = paged_decode_attention(q, kp, vp, bt, pos, interpret=True)
        ref = paged_cached_attention(q, kp, vp, bt, pos, impl="jnp")
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)

    def test_scratch_padded_table_entries_are_ignored(self):
        """Entries past a slot's length point at the scratch page; whatever
        lives there must not leak into the output."""
        from deepspeed_tpu.ops.pallas.decode_attention import paged_decode_attention

        q, kp, vp, bt = self._setup(B=1, n=4)
        pos = jnp.asarray([7], jnp.int32)  # only page 0 of the slot is valid
        out1 = paged_decode_attention(q, kp, vp, bt, pos, interpret=True)
        # rewrite every page except the slot's first: output unchanged
        keep = int(bt[0, 0])
        poisoned = kp.at[jnp.arange(16) != keep].set(99.0)
        poisoned_v = vp.at[jnp.arange(16) != keep].set(-99.0)
        bt_scratch = bt.at[0, 1:].set(0)
        out2 = paged_decode_attention(q, poisoned, poisoned_v, bt_scratch, pos, interpret=True)
        np.testing.assert_allclose(np.asarray(out1), np.asarray(out2), atol=1e-6)

    def test_bad_head_ratio_raises(self):
        from deepspeed_tpu.ops.pallas.decode_attention import paged_decode_attention

        q, _, _, bt = self._setup()
        kp = jnp.zeros((16, 3, 8, 64), jnp.float32)
        with pytest.raises(ValueError, match="divide"):
            paged_decode_attention(q, kp, kp, bt, jnp.asarray([0, 0, 0], jnp.int32), interpret=True)


class TestPagedMultitoken:
    """Multi-token paged attention (ISSUE 10): T query tokens per slot, the
    attention shape of the speculative verify step and chunked prefill."""

    def _setup(self, B=3, T=4, H=4, KV=4, D=64, page=8, P=24, n=4, seed=0):
        rs = np.random.RandomState(seed)
        q = jnp.asarray(rs.randn(B, T, H, D), jnp.float32)
        kp = jnp.asarray(rs.randn(P, KV, page, D), jnp.float32)
        vp = jnp.asarray(rs.randn(P, KV, page, D), jnp.float32)
        bt = jnp.asarray(
            rs.choice(np.arange(1, P), (B * n,), replace=False).reshape(B, n),
            jnp.int32,
        )
        return q, kp, vp, bt

    def test_kernel_matches_jnp_fallback(self):
        from deepspeed_tpu.ops.attention import paged_multitoken_cached_attention
        from deepspeed_tpu.ops.pallas.decode_attention import (
            paged_multitoken_attention,
        )

        q, kp, vp, bt = self._setup()
        base = jnp.asarray([0, 13, 27], jnp.int32)
        out = paged_multitoken_attention(q, kp, vp, bt, base, interpret=True)
        ref = paged_multitoken_cached_attention(q, kp, vp, bt, base, impl="jnp")
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5
        )

    def test_each_query_slice_is_bitwise_the_single_token_path(self):
        """The property the speculative accept rule rests on: query t of the
        T-token jnp fallback produces EXACTLY the bits of the single-token
        dispatcher at pos = base + t."""
        from deepspeed_tpu.ops.attention import (
            paged_cached_attention,
            paged_multitoken_cached_attention,
        )

        q, kp, vp, bt = self._setup(seed=2)
        base = jnp.asarray([3, 11, 19], jnp.int32)
        mt = paged_multitoken_cached_attention(q, kp, vp, bt, base, impl="jnp")
        for t in range(q.shape[1]):
            st = paged_cached_attention(
                q[:, t], kp, vp, bt, base + t, impl="jnp"
            )
            assert bool(jnp.all(mt[:, t] == st)), f"query {t} diverged"

    def test_gqa_pool(self):
        from deepspeed_tpu.ops.attention import paged_multitoken_cached_attention
        from deepspeed_tpu.ops.pallas.decode_attention import (
            paged_multitoken_attention,
        )

        q, _, _, bt = self._setup()
        rs = np.random.RandomState(3)
        kp = jnp.asarray(rs.randn(24, 2, 8, 64), jnp.float32)  # KV=2 < H=4
        vp = jnp.asarray(rs.randn(24, 2, 8, 64), jnp.float32)
        base = jnp.asarray([1, 9, 22], jnp.int32)
        out = paged_multitoken_attention(q, kp, vp, bt, base, interpret=True)
        ref = paged_multitoken_cached_attention(q, kp, vp, bt, base, impl="jnp")
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5
        )

    def test_causal_offsets_mask_future_positions(self):
        """Query t sees positions <= base + t: poisoning position base+2
        changes queries 2.. but leaves queries 0..1 untouched."""
        from deepspeed_tpu.ops.attention import paged_multitoken_cached_attention

        q, kp, vp, bt = self._setup(B=1, seed=4)
        base = jnp.asarray([8], jnp.int32)  # positions 8..11 are queries 0..3
        out1 = paged_multitoken_cached_attention(q, kp, vp, bt, base, impl="jnp")
        pg, off = int(bt[0, 10 // 8]), 10 % 8  # position base+2 = 10
        kp2 = kp.at[pg, :, off].set(99.0)
        vp2 = vp.at[pg, :, off].set(-99.0)
        out2 = paged_multitoken_cached_attention(q, kp2, vp2, bt, base, impl="jnp")
        np.testing.assert_array_equal(
            np.asarray(out1[:, :2]), np.asarray(out2[:, :2])
        )
        assert not np.allclose(np.asarray(out1[:, 2:]), np.asarray(out2[:, 2:]))

    def test_vmem_gate(self):
        from deepspeed_tpu.ops.pallas.decode_attention import (
            paged_multitoken_attention_ok,
        )

        # CPU backend: gate is False regardless of shape
        assert not paged_multitoken_attention_ok(25, 16, 64, 5)


class TestQuantizedPagedAttention:
    """int8 KV pages (ISSUE 12): both paged kernels dequantize codes through
    the per-page scales operand gathered by the SAME block-table index map;
    the jnp fallbacks must agree with the interpret-mode kernels."""

    def _setup(self, B=2, H=2, D=64, page=8, P=16, n=4, seed=0):
        from deepspeed_tpu.ops.quantizer import quantize_kv_pages

        rs = np.random.RandomState(seed)
        kf = jnp.asarray(rs.randn(P, H, page, D), jnp.float32)
        vf = jnp.asarray(rs.randn(P, H, page, D), jnp.float32)
        kq, ks = quantize_kv_pages(kf)
        vq, vs = quantize_kv_pages(vf)
        scales = jnp.stack([ks, vs], axis=-1)  # [P, KV, 2]
        bt = jnp.asarray(
            rs.choice(np.arange(1, P), (B * n,), replace=False).reshape(B, n),
            jnp.int32,
        )
        q = jnp.asarray(rs.randn(B, H, D), jnp.float32)
        return q, (kf, vf), (kq, vq, scales), bt

    def test_single_token_kernel_matches_jnp_fallback(self):
        from deepspeed_tpu.ops.attention import paged_cached_attention
        from deepspeed_tpu.ops.pallas.decode_attention import (
            paged_decode_attention,
        )

        q, _, (kq, vq, scales), bt = self._setup()
        pos = jnp.asarray([13, 29], jnp.int32)
        out = paged_decode_attention(
            q, kq, vq, bt, pos, interpret=True, scales=scales
        )
        ref = paged_cached_attention(
            q, kq, vq, bt, pos, impl="jnp", scales=scales
        )
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5
        )

    def test_multitoken_kernel_matches_jnp_fallback(self):
        from deepspeed_tpu.ops.attention import (
            paged_multitoken_cached_attention,
        )
        from deepspeed_tpu.ops.pallas.decode_attention import (
            paged_multitoken_attention,
        )

        _, _, (kq, vq, scales), bt = self._setup(seed=1)
        rs = np.random.RandomState(9)
        T = 3
        qm = jnp.asarray(rs.randn(2, T, 2, 64), jnp.float32)
        base = jnp.asarray([9, 21], jnp.int32)
        out = paged_multitoken_attention(
            qm, kq, vq, bt, base, interpret=True, scales=scales
        )
        ref = paged_multitoken_cached_attention(
            qm, kq, vq, bt, base, impl="jnp", scales=scales
        )
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5
        )

    def test_dequantized_attention_close_to_full_precision(self):
        """End-to-end quantization error bound: attending the int8 pool is
        within the block codec's rounding of attending the exact pool."""
        from deepspeed_tpu.ops.attention import paged_cached_attention

        q, (kf, vf), (kq, vq, scales), bt = self._setup(seed=2)
        pos = jnp.asarray([20, 31], jnp.int32)
        exact = paged_cached_attention(q, kf, vf, bt, pos, impl="jnp")
        deq = paged_cached_attention(
            q, kq, vq, bt, pos, impl="jnp", scales=scales
        )
        amax = float(jnp.max(jnp.abs(exact)))
        assert float(jnp.max(jnp.abs(deq - exact))) <= 0.02 * amax + 1e-5

    def test_gqa_scale_columns(self):
        """GQA pools (KV < H): each q head dequantizes through its GROUP's
        scale column, kernel and fallback alike."""
        from deepspeed_tpu.ops.attention import paged_cached_attention
        from deepspeed_tpu.ops.pallas.decode_attention import (
            paged_decode_attention,
        )
        from deepspeed_tpu.ops.quantizer import quantize_kv_pages

        rs = np.random.RandomState(3)
        kq, ks = quantize_kv_pages(jnp.asarray(rs.randn(16, 2, 8, 64), jnp.float32))
        vq, vs = quantize_kv_pages(jnp.asarray(rs.randn(16, 2, 8, 64), jnp.float32))
        scales = jnp.stack([ks, vs], axis=-1)
        bt = jnp.asarray(
            rs.choice(np.arange(1, 16), (8,), replace=False).reshape(2, 4),
            jnp.int32,
        )
        q = jnp.asarray(rs.randn(2, 4, 64), jnp.float32)  # H=4 > KV=2
        pos = jnp.asarray([11, 27], jnp.int32)
        out = paged_decode_attention(
            q, kq, vq, bt, pos, interpret=True, scales=scales
        )
        ref = paged_cached_attention(
            q, kq, vq, bt, pos, impl="jnp", scales=scales
        )
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5
        )

    def test_scales_required_iff_int8(self):
        from deepspeed_tpu.ops.attention import paged_cached_attention

        q, (kf, vf), (kq, vq, scales), bt = self._setup(seed=4)
        pos = jnp.asarray([5, 9], jnp.int32)
        with pytest.raises(ValueError, match="scales"):
            paged_cached_attention(q, kq, vq, bt, pos, impl="jnp")
        with pytest.raises(ValueError, match="scales"):
            paged_cached_attention(
                q, kf, vf, bt, pos, impl="jnp", scales=scales
            )


class TestLayerIndexedPool:
    """ISSUE 29: both paged kernels take the serving engine's whole
    ``[L, P, KV, page, D]`` pool and a static layer (one more squeezed block
    index), so no caller slices ``pool[l]``. Same page blocks, same kernel
    body: the result is bitwise the ``[P, KV, page, D]`` call's on that
    layer, whatever the other layers hold."""

    L, LAYER = 3, 1

    def _pools(self, case, B=3, H=4, D=64, page=8, P=16, n=4, seed=0):
        from deepspeed_tpu.ops.quantizer import quantize_kv_pages

        KV = H // 2 if case == "gqa" else H
        rs = np.random.RandomState(seed)
        kf = rs.randn(self.L, P, KV, page, D).astype(np.float32)
        vf = rs.randn(self.L, P, KV, page, D).astype(np.float32)
        bt = jnp.asarray(
            rs.choice(np.arange(1, P), (B * n,), replace=False).reshape(B, n),
            jnp.int32,
        )
        scales = None
        if case == "int8":
            kq, ks = quantize_kv_pages(jnp.asarray(kf[self.LAYER]))
            vq, vs = quantize_kv_pages(jnp.asarray(vf[self.LAYER]))
            scales = jnp.stack([ks, vs], axis=-1)  # the layer's [P, KV, 2]
            k5 = jnp.asarray(rs.randint(-127, 128, kf.shape), jnp.int8)
            v5 = jnp.asarray(rs.randint(-127, 128, vf.shape), jnp.int8)
            k5, v5 = k5.at[self.LAYER].set(kq), v5.at[self.LAYER].set(vq)
        else:
            dt = jnp.bfloat16 if case == "bf16" else jnp.float32
            k5, v5 = jnp.asarray(kf, dt), jnp.asarray(vf, dt)
        qdt = jnp.bfloat16 if case == "bf16" else jnp.float32
        return rs, qdt, k5, v5, bt, scales

    @pytest.mark.parametrize("case", ["bf16", "int8", "gqa"])
    @pytest.mark.parametrize("kernel", ["decode", "multitoken"])
    def test_whole_pool_with_layer_is_bitwise_the_layer_slice(self, kernel, case):
        from deepspeed_tpu.ops.pallas.decode_attention import (
            paged_decode_attention,
            paged_multitoken_attention,
        )

        rs, qdt, k5, v5, bt, scales = self._pools(case)
        at = jnp.asarray([0, 13, 27], jnp.int32)
        if kernel == "decode":
            fn, q = paged_decode_attention, rs.randn(3, 4, 64)
        else:
            fn, q = paged_multitoken_attention, rs.randn(3, 5, 4, 64)
        q = jnp.asarray(q, qdt)
        whole = fn(q, k5, v5, bt, at, interpret=True, scales=scales,
                   layer=self.LAYER)
        sliced = fn(q, k5[self.LAYER], v5[self.LAYER], bt, at, interpret=True,
                    scales=scales)
        assert whole.dtype == sliced.dtype and whole.shape == sliced.shape
        assert bool(jnp.all(whole == sliced))
        assert np.isfinite(np.asarray(whole, np.float32)).all()

    @pytest.mark.parametrize("case", ["bf16", "int8", "gqa"])
    @pytest.mark.parametrize("kernel", ["decode", "multitoken"])
    def test_a_named_call_takes_the_layer_as_an_operand_and_is_bitwise_the_same(self, kernel, case, monkeypatch):
        """ISSUE 35: a call with a ``name`` into a deep pool goes through a
        jitted twin whose layer is a traced scalar (the pools' index maps read
        it from a prefetched operand), so that a program's layers share one
        traced kernel. Same bits as the static layer, for each layer, a
        window's lower bound included; the twin is traced once for all of
        them, and a shallow pool's calls stay in place."""
        from deepspeed_tpu.ops.pallas import decode_attention as da

        rs, qdt, k5, v5, bt, scales = self._pools(case)
        assert not da._shares_kernel(k5, 0, "decode_fn") and da.SHARED_FROM_LAYERS > self.L
        monkeypatch.setattr(da, "SHARED_FROM_LAYERS", self.L)
        assert da._shares_kernel(k5, 0, "decode_fn") and not da._shares_kernel(k5, 0, None)
        at = jnp.asarray([0, 13, 27], jnp.int32)
        lo = jnp.asarray([0, 5, 11], jnp.int32)
        if kernel == "decode":
            fn, q = da.paged_decode_attention, rs.randn(3, 4, 64)
        else:
            fn, q = da.paged_multitoken_attention, rs.randn(3, 5, 4, 64)
        q = jnp.asarray(q, qdt)
        before = da._for_all_layers._cache_size()
        for layer in ((self.LAYER,) if case == "int8" else range(self.L)):
            for bound in (None, lo):
                plain = fn(q, k5, v5, bt, at, interpret=True, scales=scales, layer=layer, lo=bound)
                named = fn(q, k5, v5, bt, at, interpret=True, scales=scales, layer=layer, lo=bound,
                           name="decode_fn")
                assert named.dtype == plain.dtype and bool(jnp.all(named == plain))
        assert da._for_all_layers._cache_size() - before == 2     # with and without the bound, not one a layer

    def test_the_shared_token_write_is_the_token_write(self, monkeypatch):
        from deepspeed_tpu.ops.pallas import decode_attention as da

        monkeypatch.setattr(da, "SHARED_FROM_LAYERS", self.L)
        rs, qdt, k5, v5, bt, _ = self._pools("bf16", seed=5)
        pidx = jnp.asarray([[3, 3], [7, 8], [0, 0]], jnp.int32)
        poff = jnp.asarray([[2, 3], [7, 0], [0, 1]], jnp.int32)
        kn = jnp.asarray(rs.randn(3, 2, k5.shape[2], 64), k5.dtype)
        vn = jnp.asarray(rs.randn(3, 2, k5.shape[2], 64), k5.dtype)
        for layer in range(self.L):
            want = da.paged_token_write(k5, v5, layer, pidx, poff, kn, vn, interpret=True)
            got = da.paged_token_write(k5, v5, layer, pidx, poff, kn, vn, interpret=True, shared=True)
            for g, w in zip(got, want):
                assert bool(jnp.all(g[:, 1:] == w[:, 1:]))         # page 0 is scratch: two rows name it
        assert da._token_write_for_all_layers._cache_size() == 1

    @pytest.mark.parametrize("kernel", ["decode", "multitoken"])
    def test_dispatcher_fallback_slices_the_layer(self, kernel):
        from deepspeed_tpu.ops.attention import (
            paged_cached_attention,
            paged_multitoken_cached_attention,
        )

        rs, qdt, k5, v5, bt, _ = self._pools("gqa", seed=3)
        at = jnp.asarray([2, 9, 30], jnp.int32)
        if kernel == "decode":
            fn, q = paged_cached_attention, rs.randn(3, 4, 64)
        else:
            fn, q = paged_multitoken_cached_attention, rs.randn(3, 2, 4, 64)
        q = jnp.asarray(q, qdt)
        whole = fn(q, k5, v5, bt, at, impl="jnp", layer=self.LAYER)
        sliced = fn(q, k5[self.LAYER], v5[self.LAYER], bt, at, impl="jnp")
        assert bool(jnp.all(whole == sliced))

    @pytest.mark.parametrize("layer", [None, 0])
    def test_pool_rank_and_layer_must_agree(self, layer):
        from deepspeed_tpu.ops.pallas.decode_attention import (
            paged_decode_attention,
        )

        rs, qdt, k5, v5, bt, _ = self._pools("f32")
        q = jnp.asarray(rs.randn(3, 4, 64), qdt)
        pools = (k5, v5) if layer is None else (k5[0], v5[0])
        with pytest.raises(ValueError, match="layer"):
            paged_decode_attention(
                q, *pools, bt, jnp.zeros((3,), jnp.int32), interpret=True,
                layer=layer,
            )


class TestPagedDecodeServedShape:
    """ISSUE 25: the paged decode kernel takes all kv-heads and a block of
    ``G`` pages to a grid step and stops at the slot's own last page. Cases
    at the benchmark's served shape (25 heads of 64, page 16, table 64 wide,
    8 slots) against the jnp fallback, with every page the slots do not own
    — the scratch page behind padded table entries included — set to NaN:
    a kernel that reads past a slot's length shows it."""

    PAGE = 16

    def _pool(self, pos, KV, D, page, n, dtype, seed):
        """Pools, a table whose entries past ``pos // page`` name scratch
        page 0, and the same pools with every page no slot owns poisoned."""
        rs = np.random.RandomState(seed)
        owned = [int(p) // page + 1 for p in pos]
        P = sum(owned) + 1
        ids = rs.permutation(np.arange(1, P))
        bt = np.zeros((len(pos), n), np.int32)
        at = 0
        for b, k in enumerate(owned):
            bt[b, :k] = ids[at:at + k]
            at += k
        kp = rs.randn(P, KV, page, D).astype(np.float32)
        vp = rs.randn(P, KV, page, D).astype(np.float32)
        return (jnp.asarray(kp, dtype), jnp.asarray(vp, dtype),
                jnp.asarray(bt), jnp.asarray(pos, jnp.int32))

    @staticmethod
    def _poison(pool):
        return pool.at[0].set(jnp.nan)

    def _check(self, pos, H=25, KV=25, D=64, page=16, n=64, dtype=jnp.float32,
               idle=(), tol=2e-5, seed=0):
        from deepspeed_tpu.ops.attention import paged_cached_attention
        from deepspeed_tpu.ops.pallas.decode_attention import (
            paged_decode_attention,
        )

        kp, vp, bt, pos = self._pool(pos, KV, D, page, n, dtype, seed)
        # an idle slot sits on the scratch page with pos 0, as the scheduler
        # leaves it: its row walks nothing (the pages it held are poisoned
        # with the scratch page) and comes out zeros
        live = np.array([b not in idle for b in range(len(pos))])
        gone = np.unique(np.asarray(bt)[~live])
        poison = lambda pool: self._poison(pool).at[gone].set(jnp.nan)  # noqa: E731
        for b in idle:
            bt = bt.at[b].set(0)
        rs = np.random.RandomState(seed + 1)
        q = jnp.asarray(rs.randn(len(pos), H, D), dtype)
        out = paged_decode_attention(
            q, poison(kp), poison(vp), bt, pos, interpret=True,
            live=jnp.asarray(live) if idle else None,
        )
        ref = paged_cached_attention(q, kp, vp, bt, pos, impl="jnp")
        np.testing.assert_allclose(
            np.asarray(out, np.float32)[live], np.asarray(ref, np.float32)[live],
            atol=tol, rtol=tol,
        )
        assert not np.asarray(out, np.float32)[~live].any()

    def _gp(self, itemsize=4, KV=25, D=64, page=16, n=64):
        from deepspeed_tpu.ops.pallas.decode_attention import (
            paged_decode_blocks,
        )

        return paged_decode_blocks(KV, page, D, itemsize, n)[1] * page

    @pytest.mark.parametrize("case", [
        "page_edges", "block_edges", "idle_slots", "one_full_seven_short",
    ])
    def test_lengths_at_served_shape(self, case):
        gp, page = self._gp(), self.PAGE
        pos, idle = {
            # the last row of a page, the first of the next
            "page_edges": ([page - 1, page, 2 * page - 1, 2 * page, 1, 0,
                            5 * page - 1, 5 * page], ()),
            # the last row of a page block, the first of the next
            "block_edges": ([gp - 1, gp, 2 * gp - 1, 2 * gp, gp + page,
                             3 * gp - 1, 3 * gp, gp - page], ()),
            "idle_slots": ([0, 200, 0, 37, 0, 0, 411, 0], (0, 2, 4, 5, 7)),
            "one_full_seven_short": ([1023, 3, 20, 15, 7, 33, 1, 12], ()),
        }[case]
        self._check(pos, idle=idle)

    def test_bf16_pool_at_served_shape(self):
        self._check([1023, 130, 0, 511, 16, 700, 64, 255], dtype=jnp.bfloat16,
                    tol=2e-2, idle=(2,))

    @pytest.mark.parametrize("rep", [2, 5])
    def test_gqa_groups_read_one_pool_column(self, rep):
        self._check([0, 17, 255, 256, 40, 1023, 100, 31], H=5 * rep, KV=5,
                    seed=rep)

    def test_head_dim_128(self):
        self._check([0, 15, 16, 300, 1023, 64, 63, 500], H=4, KV=4, D=128,
                    seed=3)

    def test_head_blocks_when_a_page_of_all_heads_does_not_fit(self):
        """KV=64 heads of a [128, 128] f32 page: 16 heads to a step, four
        head blocks a slot, one page at a time."""
        from deepspeed_tpu.ops.pallas.decode_attention import (
            paged_decode_blocks,
        )

        assert paged_decode_blocks(64, 128, 128, 4, 3) == (16, 1)
        self._check([5, 300], H=64, KV=64, D=128, page=128, n=3, seed=4)

    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    def test_few_kv_heads_of_long_pages_take_a_wide_block(self, dtype):
        """ISSUE 50, ZAYA's served shape: 2 kv heads of 4 query heads x 128,
        pages of 128 keys, a table of 48. A grid step holds 16 pages, 2 048
        keys; lengths on and around its block edges, an empty and a full
        slot, and slots of fewer pages than a block (their unused inputs of
        block 0 name the slot's first page)."""
        from deepspeed_tpu.ops.pallas.decode_attention import (
            paged_decode_blocks,
        )

        dtype = jnp.dtype(dtype)
        assert paged_decode_blocks(2, 128, 128, dtype.itemsize, 48) == (
            (2, 16) if dtype.itemsize == 2 else (2, 8)
        )
        self._check([0, 127, 128, 2047, 2048, 2049, 4096, 6143], H=8, KV=2,
                    D=128, page=128, n=48, dtype=dtype,
                    tol=2e-5 if dtype.itemsize == 4 else 2e-2, seed=11)

    @pytest.mark.parametrize("rep", [1, 2])
    def test_int8_pool_with_scales(self, rep):
        from deepspeed_tpu.ops.attention import paged_cached_attention
        from deepspeed_tpu.ops.pallas.decode_attention import (
            paged_decode_attention,
        )
        from deepspeed_tpu.ops.quantizer import quantize_kv_pages

        KV, D, page, n = 5, 64, 32, 32
        kf, vf, bt, pos = self._pool(
            [0, 31, 32, 1023, 255, 256, 700, 90], KV, D, page, n,
            jnp.float32, 5,
        )
        kq, ks = quantize_kv_pages(kf)
        vq, vs = quantize_kv_pages(vf)
        scales = jnp.stack([ks, vs], axis=-1)  # [P, KV, 2]
        q = jnp.asarray(
            np.random.RandomState(6).randn(8, KV * rep, D), jnp.float32
        )
        # the scratch page's codes cannot be NaN; its scales can
        out = paged_decode_attention(
            q, kq, vq, bt, pos, interpret=True, scales=self._poison(scales)
        )
        ref = paged_cached_attention(
            q, kq, vq, bt, pos, impl="jnp", scales=scales
        )
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5
        )


class TestPagedWalk:
    """ISSUE 58: the grid of the per-head paged kernels is the call's own
    ITEMS: a live grid row (a slot's head block) owns page blocks 0 .. the one
    its tokens reach, an idle one ONE item that names what the inputs hold
    already and writes zeros."""

    @staticmethod
    def _by_hand(bt, pos, idle, G, page, nhb, T=1):
        """(row, block or None, pages) of every item, written out."""
        n_pages = bt.shape[1]
        n_blk = -(-n_pages // G)
        items = []
        for b, p in enumerate(pos):
            for hb in range(nhb):
                if b in idle:
                    items.append((b * nhb + hb, None, None))
                    continue
                last = min((p + T - 1) // page, n_pages - 1)
                for j in range(min((p + T - 1) // (G * page), n_blk - 1) + 1):
                    e = [j * G + g for g in range(G)]
                    e = [min(max(x - G if x > last else x, 0), last) for x in e]
                    items.append((b * nhb + hb, j, [int(bt[b, x]) for x in e]))
        return items

    @staticmethod
    def _fetches(pages):
        """Page DMAs a pipeline issues over the items: the first item's, then
        an input's whenever what it names changes."""
        pages = np.asarray(pages)
        return pages.shape[1] + int((pages[1:] != pages[:-1]).sum())

    @pytest.mark.parametrize("KV,D,page,n,itemsize,blocks", [
        (2, 128, 128, 48, 2, (2, 16)),   # ZAYA's served shape
        (25, 64, 16, 64, 2, (25, 8)),    # GPT-2-XL's, per head
        (8, 128, 16, 224, 2, (8, 32)),   # K-EXAONE's full layer
        (64, 128, 128, 3, 4, (16, 1)),   # four head blocks a slot
    ], ids=["zaya", "xl", "kexaone", "head-blocks"])
    def test_the_item_table_by_hand(self, KV, D, page, n, itemsize, blocks):
        from deepspeed_tpu.ops.pallas import decode_attention as da

        assert da.paged_decode_blocks(KV, page, D, itemsize, n) == blocks
        HB, G = blocks
        nhb, GP = KV // HB, G * page
        pos = [5, GP // 4 - 1, GP - 1, 7, 9, min(2 * GP + 4, n * page - 1), n * page - 1, 3]
        idle = (0, 3, 4, 7)  # leading, two between live rows, trailing
        rs = np.random.RandomState(12)
        bt = np.zeros((8, n), np.int32)
        for b, p in enumerate(pos):
            k = p // page + 1
            bt[b, :k] = rs.randint(1, 10_000, k)
        live = np.array([b not in idle for b in range(8)])
        row, home, blk, at, pages, n_items = (
            np.asarray(x) for x in da._walk_items(
                jnp.asarray(bt), jnp.asarray(pos, jnp.int32), jnp.asarray(live), 1, G, page, nhb)
        )
        want = self._by_hand(bt, pos, idle, G, page, nhb)
        own = sum(p // GP + 1 for b, p in enumerate(pos) if b not in idle)
        # the live rows' own blocks and one item an idle row
        assert n_items[0] == len(want) == nhb * (own + len(idle))
        assert da.paged_walk_steps(pos, live, KV, page, D, itemsize, n) == (len(want), 8 * nhb * (n // G))
        pages = pages.reshape(-1, G)[:len(want)]
        first_live = next(w for w in want if w[1] is not None)
        before = None
        for s, (r, j, named) in enumerate(want):
            assert row[s] == r
            if j is None:
                # what the live item before holds, or ahead of every live row the first one's
                host = before or first_live
                assert at[s] == -1 and home[s] == host[0] and blk[s] == host[1]
                np.testing.assert_array_equal(pages[s], host[2])
            else:
                assert (at[s], home[s], blk[s]) == (pos[r // nhb], r, j)
                np.testing.assert_array_equal(pages[s], named)
                before = (r, j, named)
        # an idle row's item fetches nothing: the DMAs are the live items' own
        assert self._fetches(pages) == self._fetches([w[2] for w in want if w[1] is not None])
        # with no liveness given every row walks its blocks, an empty slot block 0
        every = da._walk_items(jnp.asarray(bt), jnp.asarray(pos, jnp.int32), None, 1, G, page, nhb)
        assert int(every[-1][0]) == nhb * sum(p // GP + 1 for p in pos)

    KV, D, PAGE, N = 2, 64, 8, 8

    @pytest.fixture(scope="class")
    def call(self):
        """ONE trace of the kernel for every liveness below: 6 slots of 2
        heads, pages of 8 in a table of 8 (4 pages, 32 keys, a grid step)."""
        from deepspeed_tpu.ops.pallas.decode_attention import paged_decode_attention

        return jax.jit(lambda q, kp, vp, bt, pos, live: paged_decode_attention(
            q, kp, vp, bt, pos, interpret=True, live=live))

    @pytest.mark.parametrize("idle", [(0, 1), (4, 5), (1, 2, 4), (0, 1, 2, 3, 4, 5), ()],
                             ids=["leading", "trailing", "between", "all_idle", "all_live"])
    def test_idle_rows_are_zeros_and_live_rows_the_same_bits(self, call, idle):
        from deepspeed_tpu.ops.attention import paged_cached_attention

        pos = [40, 7, 33, 63, 0, 17]
        shape = TestPagedDecodeServedShape()
        kp, vp, bt, posj = shape._pool(pos, self.KV, self.D, self.PAGE, self.N, jnp.float32, 21)
        q = jnp.asarray(np.random.RandomState(22).randn(6, self.KV, self.D), jnp.float32)
        live = np.array([b not in idle for b in range(6)])
        # what every row computes walked as a live one (the walk before ISSUE 58)
        every = call(q, kp, vp, bt, posj, jnp.ones((6,), bool))
        ref = paged_cached_attention(q, kp, vp, bt, posj, impl="jnp")
        np.testing.assert_allclose(np.asarray(every), np.asarray(ref), atol=2e-5, rtol=2e-5)
        # idle slots sit on the scratch page at length 0; the pages they held
        # and the scratch page are poisoned
        gone = np.unique(np.asarray(bt)[~live])
        poison = lambda pool: pool.at[0].set(jnp.nan).at[gone].set(jnp.nan)  # noqa: E731
        got = call(q, poison(kp), poison(vp), jnp.where(live[:, None], bt, 0),
                   jnp.where(live, posj, 0), jnp.asarray(live))
        assert bool(jnp.all(got[live] == every[live]))
        assert not np.asarray(got)[~live].any()

    def test_head_blocks_int8_scales_and_idle_rows(self, monkeypatch):
        """Two head blocks a slot over an int8 pool (a VMEM budget of two
        heads' pages): an idle row's item names the page and the scale block
        of the item before, the last head block's, and multiplies neither."""
        from deepspeed_tpu.ops.attention import paged_cached_attention
        from deepspeed_tpu.ops.pallas import decode_attention as da
        from deepspeed_tpu.ops.pallas import flash_attention as fa
        from deepspeed_tpu.ops.quantizer import quantize_kv_pages

        KV, D, page, n = 4, 128, 32, 4
        monkeypatch.setattr(fa, "VMEM_RESIDENT_BYTES", 8 * da._page_tile_bytes(page, D, 1))
        assert da.paged_decode_blocks(KV, page, D, 1, n) == (2, 1)
        kf, vf, bt, pos = TestPagedDecodeServedShape()._pool([70, 0, 9, 127], KV, D, page, n, jnp.float32, 23)
        (kq, ks), (vq, vs) = quantize_kv_pages(kf), quantize_kv_pages(vf)
        scales = jnp.stack([ks, vs], axis=-1)
        q = jnp.asarray(np.random.RandomState(24).randn(4, 2 * KV, D), jnp.float32)
        live = jnp.asarray([True, False, True, True])
        bt = jnp.where(live[:, None], bt, 0)
        got = da.paged_decode_attention(
            q, kq, vq, bt, pos, interpret=True, scales=scales.at[0].set(jnp.nan), live=live)
        ref = paged_cached_attention(q, kq, vq, bt, pos, impl="jnp", scales=scales, live=live)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-5, rtol=2e-5)
        assert not np.asarray(got)[1].any() and not np.asarray(ref)[1].any()

    @pytest.mark.parametrize("T", [1, 16], ids=["decode", "chunk16"])
    def test_windowed_rows_and_idle_rows(self, T):
        """Keys bounded from below (a window's ring view), one token or 16 a
        slot (more than a sublane tile of rows: the three-branch form)."""
        from deepspeed_tpu.ops import attention
        from deepspeed_tpu.ops.pallas import decode_attention as da

        KV, D, page, n = 2, 64, 8, 8
        kp, vp, bt, pos = TestPagedDecodeServedShape()._pool([45, 12, 30, 20], KV, D, page, n, jnp.float32, 25)
        live = np.array([True, False, False, True])
        lo = jnp.asarray([30, 0, 0, 5], jnp.int32)
        rs = np.random.RandomState(26)
        poison = lambda pool: pool.at[0].set(jnp.nan)  # noqa: E731
        bt = jnp.where(live[:, None], bt, 0)
        if T == 1:
            q = jnp.asarray(rs.randn(4, KV, D), jnp.float32)
            got = da.paged_decode_attention(
                q, poison(kp), poison(vp), bt, pos, interpret=True, lo=lo, live=jnp.asarray(live))
            ref = attention.paged_cached_attention(q, kp, vp, bt, pos, impl="jnp", lo=lo)
        else:
            q = jnp.asarray(rs.randn(4, T, KV, D), jnp.float32)
            got = da.paged_multitoken_attention(
                q, poison(kp), poison(vp), bt, pos - (T - 1), interpret=True, lo=lo, live=jnp.asarray(live))
            ref = attention.paged_multitoken_cached_attention(q, kp, vp, bt, pos - (T - 1), impl="jnp", lo=lo)
        np.testing.assert_allclose(np.asarray(got)[live], np.asarray(ref)[live], atol=2e-5, rtol=2e-5)
        assert not np.asarray(got)[~live].any()


class TestPagedDecodeBlocks:
    """The block chooser and the gate: parameters come from the shapes."""

    @pytest.mark.parametrize("shape,want", [
        # GPT-2-XL's pool, bf16 (the three GPT-2 cells' served shape): the
        # VMEM fit, 25 x 8 tiles of 4 KB, K and V, two buffers
        ((25, 16, 64, 2, 64), (25, 8)),
        ((64, 16, 128, 2, 64), (64, 4)),
        # a tensor-parallel shard of five heads: capped at PAGE_INPUTS pages
        # (at 16-key pages those are the 512 keys the cap once counted)
        ((5, 16, 64, 2, 64), (5, 32)),
        # a narrow table caps the block
        ((25, 16, 64, 2, 4), (25, 4)),
        ((25, 16, 64, 2, 3), (25, 2)),
        # int8 pages of 32
        ((25, 32, 64, 1, 32), (25, 8)),
        # a page of all heads does not fit: head blocks, one page a step
        ((64, 128, 128, 4, 8), (16, 1)),
        ((25, 256, 128, 4, 4), (5, 1)),
        # one head's page does not fit
        ((8, 2048, 256, 4, 2), None),
        # the other served shapes (ISSUE 50): each the pair it had, but ZAYA's.
        # K-EXAONE's full layer (32 page inputs) and its rings (the table)
        ((8, 16, 128, 2, 224), (8, 32)),
        ((8, 16, 128, 2, 25), (8, 16)),
        # Phi-4-mini-flash's paged layer and its rings: the fit, 3 pages of 10 heads
        ((10, 128, 128, 2, 48), (10, 2)),
        ((10, 128, 128, 2, 7), (10, 2)),
        # ZAYA: 2 kv heads of 128-key pages take what fits, 16 pages (4 under
        # the cap of 512 keys)
        ((2, 128, 128, 2, 48), (2, 16)),
        # PAGE_INPUTS alone: one kv head, int8 pages of 32, a wide table
        ((1, 32, 64, 1, 256), (1, 32)),
    ])
    def test_blocks_from_shapes(self, shape, want):
        from deepspeed_tpu.ops.pallas.decode_attention import (
            paged_decode_blocks,
        )
        from deepspeed_tpu.ops.pallas.flash_attention import (
            VMEM_RESIDENT_BYTES,
        )

        got = paged_decode_blocks(*shape)
        assert got == want
        if got is not None:
            KV, page, D, itemsize, _ = shape
            hb, g = got
            assert KV % hb == 0
            sub = 32 // itemsize
            tile = -(-page // sub) * sub * -(-D // 128) * 128 * itemsize
            assert 4 * hb * g * tile <= VMEM_RESIDENT_BYTES

    @pytest.mark.parametrize("args,want", [
        ((25, 16, 64, 2), True),       # XL
        ((64, 16, 128, 2), True),      # KV 64 x D 128
        ((5, 16, 64, 2), True),        # XL under tp=5
        ((25, 32, 64, 1), True),       # int8 pages of 32
        ((25, 16, 64, 1), False),      # int8 wants pages of 32
        ((25, 16, 80, 2), False),      # head dim off the lanes
        ((8, 2048, 256, 4), False),    # one head's page over the budget
    ])
    def test_gate_on_tpu(self, monkeypatch, args, want):
        from deepspeed_tpu.ops.pallas import decode_attention as da

        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert da.paged_decode_attention_ok(*args) is want

    def test_gate_off_tpu(self):
        from deepspeed_tpu.ops.pallas.decode_attention import (
            paged_decode_attention_ok,
        )

        assert not paged_decode_attention_ok(25, 16, 64, 2)


class TestPagedMultitokenServedShape:
    """ISSUE 31: the multi-token paged kernel on the decode kernel's plan —
    all kv-heads and ``G`` pages to a grid step, the walked table ending at
    the page the chunk reaches, the mask built only in the blocks that
    overlap the chunk. Cases at the benchmark's served shape (25 heads of
    64, page 16, table 64 wide, chunks of 128) against the jnp fallback,
    with every page a slot does not own set to NaN and the table entries
    past the chunk's reach naming that page."""

    def _pool(self, base, T, KV, D, page, n, dtype, seed):
        """``TestPagedDecodeServedShape._pool`` for slots that own the pages
        up to their chunk's last position."""
        reach = [int(b) + T - 1 for b in base]
        kp, vp, bt, _ = TestPagedDecodeServedShape()._pool(
            reach, KV, D, page, n, dtype, seed
        )
        return kp, vp, bt, jnp.asarray(base, jnp.int32)

    def _check(self, base, T=128, H=25, KV=25, D=64, page=16, n=64,
               dtype=jnp.float32, tol=2e-5, seed=0, garbage=None):
        from deepspeed_tpu.ops.attention import paged_multitoken_cached_attention
        from deepspeed_tpu.ops.pallas.decode_attention import (
            paged_multitoken_attention,
        )

        kp, vp, bt, base = self._pool(base, T, KV, D, page, n, dtype, seed)
        q = jnp.asarray(
            np.random.RandomState(seed + 1).randn(len(base), T, H, D), dtype
        )
        table = bt
        if garbage is not None:  # entries past the reach: ids of no page at all
            reach = (np.asarray(base) + T - 1) // page
            past = np.arange(n)[None, :] > reach[:, None]
            table = jnp.where(jnp.asarray(past), garbage, bt)
        out = paged_multitoken_attention(
            q, kp.at[0].set(jnp.nan), vp.at[0].set(jnp.nan), table, base,
            interpret=True,
        )
        ref = paged_multitoken_cached_attention(q, kp, vp, bt, base, impl="jnp")
        assert out.shape == ref.shape and out.dtype == q.dtype
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            atol=tol, rtol=tol,
        )

    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    @pytest.mark.parametrize("base", [0, 128, 896])
    def test_chunk_at_served_shape(self, base, dtype):
        """One slot's chunk of 128 at its first, second and last start."""
        self._check([base], dtype=jnp.dtype(dtype),
                    tol=2e-5 if dtype == "float32" else 2e-2, seed=base)

    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    def test_verify_shape_off_the_block_edges(self, dtype):
        """T = 5 queries a slot from positions that are no block multiple:
        inside a block, across a page edge, across a block edge, at the end
        of the table."""
        self._check([3, 14, 125, 130, 1019], T=5, dtype=jnp.dtype(dtype),
                    tol=2e-5 if dtype == "float32" else 2e-2, seed=5)

    def test_several_slots_a_call(self):
        """B > 1 with different reaches: what batching the prefilling slots
        into one call will ask of the kernel."""
        self._check([0, 640, 128, 896], seed=7)

    @pytest.mark.parametrize("rep", [2, 5])
    def test_gqa_groups_read_one_pool_column(self, rep):
        self._check([0, 17, 250, 1000], T=16, H=5 * rep, KV=5, seed=rep)

    def test_head_dim_128(self):
        self._check([0, 100, 992], T=32, H=4, KV=4, D=128, seed=3)

    def test_head_blocks_when_all_heads_do_not_fit(self):
        """KV=64 heads of a [128, 128] f32 page: 16 heads to a step, four
        head blocks a slot, one page at a time."""
        from deepspeed_tpu.ops.pallas.decode_attention import (
            paged_multitoken_blocks,
        )

        assert paged_multitoken_blocks(64, 128, 128, 8, 4, 3) == (16, 1)
        self._check([5, 300], T=8, H=64, KV=64, D=128, page=128, n=3, seed=4)

    def test_table_entries_past_the_reach_are_never_read(self):
        """Past ``(base + T - 1) // page`` the table may hold anything, ids
        of no page included: the walked table stops at the chunk's reach."""
        self._check([0, 130, 500], T=64, garbage=10 ** 6, seed=8)

    @pytest.mark.parametrize("rep", [1, 2])
    def test_int8_pool_with_scales(self, rep):
        from deepspeed_tpu.ops.attention import paged_multitoken_cached_attention
        from deepspeed_tpu.ops.pallas.decode_attention import (
            paged_multitoken_attention,
        )
        from deepspeed_tpu.ops.quantizer import quantize_kv_pages

        KV, D, page, n, T = 5, 64, 32, 32, 64
        kf, vf, bt, base = self._pool(
            [0, 31, 64, 960, 200], T, KV, D, page, n, jnp.float32, 5
        )
        kq, ks = quantize_kv_pages(kf)
        vq, vs = quantize_kv_pages(vf)
        scales = jnp.stack([ks, vs], axis=-1)  # [P, KV, 2]
        q = jnp.asarray(
            np.random.RandomState(6).randn(5, T, KV * rep, D), jnp.float32
        )
        # the scratch page's codes cannot be NaN; its scales can
        out = paged_multitoken_attention(
            q, kq, vq, bt, base, interpret=True,
            scales=scales.at[0].set(jnp.nan),
        )
        ref = paged_multitoken_cached_attention(
            q, kq, vq, bt, base, impl="jnp", scales=scales
        )
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5
        )

    def test_one_token_is_the_decode_kernel(self):
        """T = 1 is the decode step's attention: the same blocks, the same
        walk, the same numbers as :func:`paged_decode_attention`."""
        from deepspeed_tpu.ops.pallas.decode_attention import (
            paged_decode_attention,
            paged_multitoken_attention,
        )

        kp, vp, bt, pos = self._pool(
            [0, 15, 16, 127, 128, 700, 1023, 40], 1, 25, 64, 16, 64,
            jnp.float32, 9,
        )
        q = jnp.asarray(np.random.RandomState(10).randn(8, 25, 64), jnp.float32)
        one = paged_multitoken_attention(q[:, None], kp, vp, bt, pos,
                                         interpret=True)[:, 0]
        dec = paged_decode_attention(q, kp, vp, bt, pos, interpret=True)
        np.testing.assert_allclose(np.asarray(one), np.asarray(dec),
                                   atol=1e-6, rtol=1e-6)


class TestPagedMultitokenBlocks:
    """The multi-token block rule, the gate and the grid the gauge reports."""

    @pytest.mark.parametrize("shape,want", [
        # (KV, page, D, T, itemsize, n_pages[, rep])
        # GPT-2-XL's chunk of 128, bf16: all heads, 128 keys a step
        ((25, 16, 64, 128, 2, 64), (25, 8)),
        # the verify shape: a sublane tile of rows takes the decode kernel's block
        ((25, 16, 64, 5, 2, 64), (25, 8)),
        ((5, 16, 64, 5, 2, 64), (5, 32)),
        # a shard of five heads at T = 128: still 128 keys a step
        ((5, 16, 64, 128, 2, 64), (5, 8)),
        ((5, 16, 64, 128, 2, 64, 5), (5, 8)),
        # 128-wide heads (OLMoE: 16 of them) and 64 of them: head blocks
        ((16, 16, 128, 128, 2, 64), (16, 8)),
        ((64, 16, 128, 128, 2, 64), (32, 4)),
        # int8 pages of 32
        ((25, 32, 64, 128, 1, 32), (25, 4)),
        # a narrow table caps the block
        ((25, 16, 64, 128, 2, 3), (25, 2)),
        # a page of all heads does not fit; one head's page does not
        ((64, 128, 128, 128, 4, 4), (16, 1)),
        ((8, 2048, 256, 128, 4, 2), None),
    ])
    def test_blocks_from_shapes(self, shape, want):
        from deepspeed_tpu.ops.pallas.decode_attention import (
            paged_decode_blocks,
            paged_multitoken_blocks,
        )

        got = paged_multitoken_blocks(*shape)
        assert got == want
        if got is not None:
            KV, page, D, T, itemsize, n = shape[:6]
            hb, g = got
            assert KV % hb == 0 and g & (g - 1) == 0
            dec = paged_decode_blocks(KV, page, D, itemsize, n)
            assert hb <= dec[0] and g <= dec[1]

    @pytest.mark.parametrize("args,want", [
        ((25, 16, 64, 128, 2), True),       # XL, chunk of 128
        ((25, 16, 64, 5, 2), True),         # the verify shape
        ((16, 16, 128, 128, 2), True),      # OLMoE's heads
        ((5, 16, 64, 128, 2, 5), True),     # GQA, five query heads a column
        ((25, 32, 64, 128, 1), True),       # int8 pages of 32
        ((25, 16, 64, 128, 1), False),      # int8 wants pages of 32
        ((25, 16, 80, 128, 2), False),      # head dim off the lanes
        ((8, 2048, 256, 128, 4), False),    # one head's page over the budget
    ])
    def test_gate_on_tpu(self, monkeypatch, args, want):
        from deepspeed_tpu.ops.pallas import decode_attention as da

        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert da.paged_multitoken_attention_ok(*args) is want

    XL = (25, 16, 64, 2, 64)      # GPT-2-XL: KV, page, D, itemsize, table
    ZAYA = (2, 128, 128, 2, 48)   # 4 query heads to each of its 2 kv heads

    @pytest.mark.parametrize("impl,B,shape,T,rep,want", [
        ("pallas", 1, XL, 128, 1, 8),    # the chunk call: one slot, 8 page blocks (1 600 before)
        ("pallas", 8, XL, None, 1, 64),  # the decode step: 8 slots x 8 page blocks
        ("auto", 1, XL, 128, 1, 0),      # off the TPU the fallback runs
        ("jnp", 8, XL, None, 1, 0),
        # ZAYA's decode step: 64 slots x 3 blocks of 16 pages (12 of 4, 768 a call, before ISSUE 50)
        ("pallas", 64, ZAYA, None, 4, 192),
        ("pallas", 1, ZAYA, 256, 4, 48),  # its chunk call keeps one 128-key page a step
    ])
    def test_grid_steps_at_the_served_shape(self, impl, B, shape, T, rep, want):
        from deepspeed_tpu.ops.attention import paged_attention_grid_steps

        assert paged_attention_grid_steps(impl, B, *shape, T, rep) == want

    @pytest.mark.parametrize("rep,T,want", [
        (1, 256, 8),    # 4 kv-heads a step, 8 page blocks
        (8, 256, 16),   # 2 048 query rows a kv-head: 2 kv-heads a step
        (8, None, 2),   # the decode step does not widen with rep: 32 pages a block
    ])
    def test_grid_steps_count_the_group(self, rep, T, want):
        """``rep`` query heads share a kv-head's rows in a multi-token step,
        so the block rule gives fewer heads a step and the gauge must read
        what the kernel runs."""
        from deepspeed_tpu.ops.attention import paged_attention_grid_steps
        from deepspeed_tpu.ops.pallas import decode_attention as da

        got = paged_attention_grid_steps("pallas", 1, 4, 16, 64, 2, 64, T, rep)
        assert got == want
        if T is not None:
            HB, G = da.paged_multitoken_blocks(4, 16, 64, T, 2, 64, rep)
            assert got == (4 // HB) * -(-64 // G)

    def test_grid_steps_refuse_an_unknown_impl(self):
        from deepspeed_tpu.ops.attention import paged_attention_grid_steps

        with pytest.raises(ValueError, match="unknown attention impl"):
            paged_attention_grid_steps("flash", 1, 25, 16, 64, 2, 64, 128)


# -- the latent (MLA) paged kernels against their jnp fallbacks (interpret mode) --

class TestPagedPairedPool:
    """ISSUE 56: GPT-2's 64-wide heads cached as PAIRS. A pool of ``ceil(H /
    2)`` heads of 128 lanes ``[k_2p | k_2p+1]`` under the zero-padded queries
    ``[q_2p | 0]``, ``[0 | q_2p+1]`` (``rep`` 2, scaled by the published
    head's width) gives, in the lanes each query head owns, what the per-head
    pool gives: the one-token and the multi-token kernel, interpreted, against
    the jnp fallback over the per-head pool holding the same keys. An odd
    count's last pair ends in a zero head."""

    D, PAGE = 64, 16

    def _pools(self, pos, H, n, dtype, seed):
        """Per-head pools ``[P, H, page, 64]``, the paired pools ``[P, ceil(H
        / 2), page, 128]`` of the same keys, a table and ``pos``."""
        kp, vp, bt, pos = TestPagedDecodeServedShape()._pool(pos, H, self.D, self.PAGE, n, dtype, seed)
        return (kp, vp), (self._pair_pool(kp), self._pair_pool(vp)), bt, pos

    def _pair_pool(self, pool):
        P, H, page, D = pool.shape
        pool = jnp.pad(pool, ((0, 0), (0, H % 2), (0, 0), (0, 0)))
        return pool.reshape(P, -1, 2, page, D).transpose(0, 1, 3, 2, 4).reshape(P, -1, page, 2 * D)

    def _pair_queries(self, q):
        """``q [..., H, 64]`` → ``[..., 2 * ceil(H / 2), 128]``."""
        H, D = q.shape[-2:]
        q = jnp.pad(q, [(0, 0)] * (q.ndim - 2) + [(0, H % 2), (0, 0)])
        q = q.reshape(*q.shape[:-2], -1, 2, 1, D) * jnp.eye(2, dtype=q.dtype)[:, :, None]
        return q.reshape(*q.shape[:-4], -1, 2 * D)

    def _own_lanes(self, o, H):
        """``o [..., 2 * KV, 128]`` → ``[..., H, 64]``: each head's own half."""
        D = self.D
        o = o.reshape(*o.shape[:-2], -1, 2, 2, D)
        o = jnp.stack([o[..., 0, 0, :], o[..., 1, 1, :]], axis=-2)
        return o.reshape(*o.shape[:-3], -1, D)[..., :H, :]

    @pytest.mark.parametrize("H,dtype", [(25, "float32"), (25, "bfloat16"), (4, "float32")],
                             ids=["xl-odd", "xl-odd-bf16", "even"])
    def test_one_token_kernel_over_pairs_is_the_per_head_result(self, H, dtype):
        from deepspeed_tpu.ops.attention import paged_cached_attention
        from deepspeed_tpu.ops.pallas.decode_attention import (
            paged_decode_attention,
            paged_decode_blocks,
        )

        dtype = jnp.dtype(dtype)
        pos = [0, 15, 16, 255, 256, 735, 1023, 200]
        (kp, vp), (kq, vq), bt, posj = self._pools(pos, H, 64, dtype, 21)
        assert kq.shape[1:] == ((H + 1) // 2, 16, 128)
        if dtype.itemsize == 2:  # the served plan: all 13 pairs and 16 pages a grid step
            assert paged_decode_blocks(13, 16, 128, 2, 64) == (13, 16)
        q = jnp.asarray(np.random.RandomState(22).randn(len(pos), H, self.D), dtype)
        got = paged_decode_attention(
            self._pair_queries(q), kq.at[0].set(jnp.nan), vq.at[0].set(jnp.nan), bt, posj,
            sm_scale=1 / 8, interpret=True,
        )
        want = paged_cached_attention(q, kp, vp, bt, posj, impl="jnp")
        tol = 2e-5 if dtype.itemsize == 4 else 2e-2
        np.testing.assert_allclose(np.asarray(self._own_lanes(got, H), np.float32),
                                   np.asarray(want, np.float32), atol=tol, rtol=tol)

    @pytest.mark.parametrize("H,T", [(25, 128), (25, 5), (4, 8)], ids=["xl-chunk128", "xl-verify5", "even-8"])
    def test_multitoken_kernel_over_pairs_is_the_per_head_result(self, H, T):
        from deepspeed_tpu.ops.attention import paged_multitoken_cached_attention
        from deepspeed_tpu.ops.pallas.decode_attention import paged_multitoken_attention

        base = [0, 128, 896] if T == 128 else [3, 14, 125, 130]
        (kp, vp), (kq, vq), bt, _ = self._pools([b + T - 1 for b in base], H, 64, jnp.float32, 23)
        basej = jnp.asarray(base, jnp.int32)
        q = jnp.asarray(np.random.RandomState(24).randn(len(base), T, H, self.D), jnp.float32)
        got = paged_multitoken_attention(
            self._pair_queries(q), kq.at[0].set(jnp.nan), vq.at[0].set(jnp.nan), bt, basej,
            sm_scale=1 / 8, interpret=True,
        )
        want = paged_multitoken_cached_attention(q, kp, vp, bt, basej, impl="jnp")
        np.testing.assert_allclose(np.asarray(self._own_lanes(got, H)), np.asarray(want), atol=3e-5, rtol=3e-5)


class TestLatentPaged:
    L, P, page, W, VW, H = 2, 40, 8, 128, 64, 4

    def _pool(self, seed=0):
        rng = np.random.default_rng(seed)
        return jnp.asarray(rng.normal(size=(self.L, self.P, 1, self.page, self.W)), jnp.float32), rng

    # (B, T, n, base or None for a draw, CHUNK_ROWS, keys a step or None for the module's)
    @pytest.mark.parametrize("B,T,n,base,rows,keys", [
        (3, 1, 6, None, None, None), (2, 8, 6, None, None, None), (1, 16, 9, None, None, None),
        (2, 64, 12, None, 128, None),
        # what the walk of owned (query block, page block) pairs can get wrong: 32 tokens x 4
        # heads a step (two query blocks), 16 keys a step (two pages)
        (1, 64, 12, [0], 128, 16),        # the diagonal alone: walks of 2 and 4 blocks
        (1, 64, 12, [21], 128, 16),       # a context that ends inside a key block
        (1, 64, 12, [32], 128, 16),       # the chunk's last token is the table's last row
        (2, 64, 19, [0, 80], 128, 16),    # walks of 6 and 16 steps side by side
        (3, 1, 6, [3, 40, 0], None, 16),  # decode: one own page, three blocks, one key
    ], ids=["decode", "verify-shape", "chunk", "chunk-two-query-blocks", "chunk-base-0",
            "chunk-base-inside-a-block", "chunk-ends-in-the-last-page", "two-slots-walks-differ",
            "decode-one-own-page"])
    def test_attention_kernel_equals_the_fallback(self, B, T, n, base, rows, keys, monkeypatch):
        from jax.experimental.pallas import tpu as pltpu

        from deepspeed_tpu.ops.attention import latent_paged_cached_attention
        from deepspeed_tpu.ops.pallas import latent_attention as la

        if rows:
            monkeypatch.setattr(la, "CHUNK_ROWS", rows)     # 32 tokens x 4 heads a step: two query blocks
            assert la.latent_blocks(self.H, self.page, T, n)[0] == 32
        if keys:
            monkeypatch.setattr(la, "CHUNK_KEYS", keys)
            monkeypatch.setattr(la, "DECODE_KEYS", keys)
            assert la.latent_blocks(self.H, self.page, T, n)[1] == keys // self.page
        pool, rng = self._pool()
        bt = jnp.asarray(rng.permutation(np.arange(1, self.P))[: B * n].reshape(B, n), jnp.int32)
        base = jnp.asarray(rng.integers(0, n * self.page - T, B) if base is None else base, jnp.int32)
        if keys:   # the walk's length, by hand: blocks 0 .. the one of each query block's last token
            TQ = la.latent_blocks(self.H, self.page, T, n)[0]
            want_steps = sum(
                (int(b) + i * TQ + TQ - 1) // keys + 1 for b in np.asarray(base) for i in range(T // TQ)
            )
            assert la.latent_walk_steps(np.asarray(base), self.H, self.page, T, n) == (
                want_steps, B * (T // TQ) * -(-n * self.page // keys)
            )
        q = jnp.asarray(rng.normal(size=(B, T, self.H, self.W)), jnp.float32)
        want = latent_paged_cached_attention(q, pool, bt, base, self.VW, impl="jnp", sm_scale=0.2, layer=1)
        with pltpu.force_tpu_interpret_mode():
            got = la.latent_paged_attention(q, pool, bt, base, self.VW, 0.2, layer=1)
        assert got.shape == (B, T, self.H, self.VW)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=5e-6, rtol=1e-5)

    def test_the_values_are_the_rows_leading_lanes_and_padding_lanes_change_nothing(self):
        from deepspeed_tpu.ops.attention import latent_paged_cached_attention

        pool, rng = self._pool(1)
        bt = jnp.asarray(rng.permutation(np.arange(1, self.P))[:6].reshape(1, 6), jnp.int32)
        base = jnp.asarray([20], jnp.int32)
        q = jnp.asarray(rng.normal(size=(1, 4, self.H, self.W)), jnp.float32)
        a = latent_paged_cached_attention(q, pool, bt, base, self.VW, impl="jnp", sm_scale=0.2, layer=0)
        # zero lanes behind the row on both sides (the stored row's lane padding)
        pad = lambda x: jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, 32)])
        b = latent_paged_cached_attention(pad(q), pad(pool), bt, base, self.VW, impl="jnp", sm_scale=0.2, layer=0)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)
        # a dense softmax over the slot's first base + t + 1 rows
        rows = np.asarray(pool[0])[np.asarray(bt[0])].reshape(-1, self.W)
        for t in range(4):
            s = np.einsum("hw,sw->hs", np.asarray(q[0, t]), rows[: 21 + t]) * 0.2
            p = np.exp(s - s.max(-1, keepdims=True))
            want = (p / p.sum(-1, keepdims=True)) @ rows[: 21 + t, : self.VW]
            np.testing.assert_allclose(np.asarray(a[0, t]), want, atol=2e-5, rtol=1e-4)

    @pytest.mark.parametrize("T", [1, 3])
    def test_token_write_equals_the_scatter(self, T):
        from jax.experimental.pallas import tpu as pltpu

        from deepspeed_tpu.ops.pallas.latent_attention import latent_token_write

        pool, rng = self._pool(2)
        B = 3
        pos = jnp.asarray(rng.integers(0, 5 * self.page - T, B)[:, None] + np.arange(T)[None], jnp.int32)
        pages = jnp.asarray(rng.permutation(np.arange(1, self.P))[: B * 5].reshape(B, 5), jnp.int32)
        pidx, poff = jnp.take_along_axis(pages, pos // self.page, axis=1), pos % self.page
        rows = jnp.asarray(rng.normal(size=(B, T, 1, self.W)), jnp.float32)
        want = pool.at[1, pidx, 0, poff].set(rows[:, :, 0])
        args = (pidx[:, 0], poff[:, 0], rows[:, 0]) if T == 1 else (pidx, poff, rows)
        with pltpu.force_tpu_interpret_mode():
            got = latent_token_write(pool, 1, *args)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
