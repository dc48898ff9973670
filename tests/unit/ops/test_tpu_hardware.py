"""Hardware-mode kernel CI: compile — not interpret — the Mosaic kernels on
a real TPU chip and check parity against the jnp reference paths.

Run with:  DS_TPU_TESTS=1 python -m pytest tests/ -m tpu -q
(conftest skips its CPU forcing under DS_TPU_TESTS=1; everything here skips
unless the active backend is a TPU). From the sandbox, through the chip tool:
    chiprun -- env DS_TPU_TESTS=1 python -m pytest -m tpu -q
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = [
    pytest.mark.tpu,
    pytest.mark.skipif(
        jax.default_backend() != "tpu", reason="needs a real TPU backend"
    ),
]


def _qkv(B, S, H, D, seed=0, dtype=jnp.bfloat16):
    rs = np.random.RandomState(seed)
    return [jnp.asarray(rs.randn(B, S, H, D), dtype) for _ in range(3)]


def _grad_triple(fn, q, k, v):
    loss = lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32) ** 2)
    return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)


def _truth_grads(fn, q, k, v):
    """f32 inputs + highest MXU precision: the ground truth both bf16
    implementations are measured against. On TPU an f32 ``dot`` runs as a
    single truncated-bf16 MXU pass by default, so even the jnp reference
    carries bf16-level noise on hardware — comparing two noisy
    implementations against EACH OTHER (the round-4 session-2 test shape)
    double-counts that noise and fails on exactly-zero rows; each must be
    compared against this truth instead."""
    qf, kf, vf = (x.astype(jnp.float32) for x in (q, k, v))
    with jax.default_matmul_precision("highest"):
        return _grad_triple(fn, qf, kf, vf)


def _assert_grads_within_reference_noise(g_pallas, g_ref, g_truth, floor=2e-2):
    """The kernel's gradient error (vs f32-highest truth) may not exceed
    2x the jnp reference's own bf16 error at the same shape (plus a small
    absolute floor for exact-cancellation rows where the reference error
    is ~0). Normalized per-array by max|truth| so tolerances are
    shape/scale-robust."""
    for name, a, b, t in zip(("dq", "dk", "dv"), g_pallas, g_ref, g_truth):
        a, b, t = (np.asarray(x, np.float32) for x in (a, b, t))
        scale = np.abs(t).max() + 1e-6
        err_pal = np.abs(a - t).max() / scale
        err_ref = np.abs(b - t).max() / scale
        assert err_pal <= max(2.0 * err_ref, floor), (
            f"{name}: pallas err {err_pal:.4f} vs reference err {err_ref:.4f} "
            f"(scale {scale:.3f})"
        )


class TestFlashAttentionHardware:
    def test_forward_compiles_and_matches(self):
        from deepspeed_tpu.ops.attention import causal_attention_jnp
        from deepspeed_tpu.ops.pallas.flash_attention import flash_attention

        q, k, v = _qkv(2, 1024, 4, 64)
        o = jax.jit(lambda q, k, v: flash_attention(q, k, v))(q, k, v)
        o_ref = causal_attention_jnp(q, k, v)
        np.testing.assert_allclose(
            np.asarray(o, np.float32), np.asarray(o_ref, np.float32),
            atol=2e-2, rtol=2e-2,
        )

    def test_backward_compiles_and_matches(self):
        from deepspeed_tpu.ops.attention import causal_attention_jnp
        from deepspeed_tpu.ops.pallas.flash_attention import flash_attention

        q, k, v = _qkv(1, 512, 2, 64, seed=1)
        g = _grad_triple(flash_attention, q, k, v)
        g_ref = _grad_triple(causal_attention_jnp, q, k, v)
        g_truth = _truth_grads(causal_attention_jnp, q, k, v)
        _assert_grads_within_reference_noise(g, g_ref, g_truth)

    def test_fused_bwd_matches_split_on_chip(self):
        """The fused single-pass backward's new Mosaic surface (dynamic-slice
        scratch read-modify-write across the sequential q grid) compiles and
        agrees with the split dq/dkv kernels (bit-identical on CPU interpret;
        bf16-cast-level here)."""
        from deepspeed_tpu.ops.pallas import flash_attention as fa

        assert fa._fused_bwd_ok(512, 64)
        q, k, v = _qkv(1, 512, 2, 64, seed=4)

        def grads():
            loss = lambda q, k, v: jnp.sum(
                fa.flash_attention(q, k, v).astype(jnp.float32) ** 2
            )
            return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)

        g_fused = grads()
        budget = fa.FUSED_BWD_BYTES
        fa.FUSED_BWD_BYTES = 0  # no shape fits: the shape rule selects the two-pass backward
        try:
            assert not fa._fused_bwd_ok(512, 64)
            g_split = grads()
        finally:
            fa.FUSED_BWD_BYTES = budget
        for a, b in zip(g_fused, g_split):
            np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(b, np.float32),
                atol=1e-2, rtol=1e-2,
            )

    def test_head_dim_128(self):
        from deepspeed_tpu.ops.attention import causal_attention_jnp
        from deepspeed_tpu.ops.pallas.flash_attention import flash_attention

        q, k, v = _qkv(1, 256, 2, 128, seed=2)
        o = jax.jit(lambda q, k, v: flash_attention(q, k, v))(q, k, v)
        o_ref = causal_attention_jnp(q, k, v)
        np.testing.assert_allclose(
            np.asarray(o, np.float32), np.asarray(o_ref, np.float32),
            atol=2e-2, rtol=2e-2,
        )


class TestBlockSparseHardware:
    def test_fixed_pattern_compiles_and_matches(self):
        from deepspeed_tpu.ops.sparse_attention import FixedSparsityConfig
        from deepspeed_tpu.ops.sparse_attention.sparse_self_attention import (
            sparse_attention,
        )

        H, S, D, block = 2, 1024, 64, 128
        cfg = FixedSparsityConfig(num_heads=H, block=block)
        rs = np.random.RandomState(3)
        q, k, v = (
            jnp.asarray(rs.randn(1, S, H, D), jnp.bfloat16) for _ in range(3)
        )
        o = jax.jit(
            lambda q, k, v: sparse_attention(q, k, v, cfg, causal=True, impl="pallas")
        )(q, k, v)
        o_ref = sparse_attention(q, k, v, cfg, causal=True, impl="jnp")
        np.testing.assert_allclose(
            np.asarray(o, np.float32), np.asarray(o_ref, np.float32),
            atol=3e-2, rtol=3e-2,
        )

    def test_backward_compiles_and_matches(self):
        """dq/dkv kernels carry the dynamic-sublane lse/delta loads — the
        Mosaic-hazard class that only a chip compile can catch."""
        from deepspeed_tpu.ops.sparse_attention import FixedSparsityConfig
        from deepspeed_tpu.ops.sparse_attention.sparse_self_attention import (
            sparse_attention,
        )

        H, S, D, block = 2, 1024, 64, 128
        cfg = FixedSparsityConfig(num_heads=H, block=block)
        rs = np.random.RandomState(4)
        q, k, v = (
            jnp.asarray(rs.randn(1, S, H, D), jnp.bfloat16) for _ in range(3)
        )

        def f(impl):
            return lambda q, k, v: sparse_attention(q, k, v, cfg, causal=True, impl=impl)

        g = _grad_triple(f("pallas"), q, k, v)
        g_ref = _grad_triple(f("jnp"), q, k, v)
        g_truth = _truth_grads(f("jnp"), q, k, v)
        _assert_grads_within_reference_noise(g, g_ref, g_truth)


class TestFusedAdamHardware:
    def test_kernel_compiles_and_matches_optax(self):
        import optax

        from deepspeed_tpu.ops.fused_adam import fused_adamw_flat

        n = 1024 * 1024
        rs = np.random.RandomState(4)
        p = jnp.asarray(rs.randn(n), jnp.float32)
        g = jnp.asarray(rs.randn(n), jnp.float32)
        m = jnp.zeros_like(p)
        v = jnp.zeros_like(p)
        p2, m2, v2 = jax.jit(
            lambda p, g, m, v: fused_adamw_flat(p, g, m, v, jnp.int32(1), 1e-3, weight_decay=0.01)
        )(p, g, m, v)
        tx = optax.adamw(1e-3, weight_decay=0.01)
        u, _ = tx.update(g, tx.init(p), p)
        p_ref = optax.apply_updates(p, u)
        np.testing.assert_allclose(np.asarray(p2), np.asarray(p_ref), rtol=3e-6, atol=3e-7)


class TestFusedLambHardware:
    def test_lamb_kernel_compiles(self):
        from deepspeed_tpu.ops.fused_adam import fused_lamb_flat

        n = 1024 * 64
        rs = np.random.RandomState(5)
        p = jnp.asarray(rs.randn(n), jnp.float32)
        g = jnp.asarray(rs.randn(n), jnp.float32) * 0.1
        z = jnp.zeros_like(p)
        p2, m2, v2 = jax.jit(
            lambda p, g, m, v: fused_lamb_flat(p, g, m, v, jnp.int32(1), 1e-2)
        )(p, g, z, z)
        assert np.isfinite(np.asarray(p2)).all()
        assert not np.allclose(np.asarray(p2), np.asarray(p))


class TestDecodeAttentionHardware:
    def test_decode_kernel_compiles_and_matches(self):
        from deepspeed_tpu.ops.pallas.decode_attention import decode_attention

        B, S, H, D = 2, 1024, 4, 64
        rs = np.random.RandomState(6)
        q = jnp.asarray(rs.randn(B, H, D), jnp.bfloat16)
        k = jnp.asarray(rs.randn(B, S, H, D), jnp.bfloat16)
        v = jnp.asarray(rs.randn(B, S, H, D), jnp.bfloat16)
        out = jax.jit(lambda q, k, v, p: decode_attention(q, k, v, p))(
            q, k, v, jnp.int32(700)
        )
        scores = jnp.einsum(
            "bhd,bshd->bhs", q.astype(jnp.float32), k.astype(jnp.float32)
        ) / np.sqrt(D)
        mask = jnp.arange(S)[None, None, :] <= 700
        probs = jax.nn.softmax(jnp.where(mask, scores, -1e30), axis=-1)
        ref = jnp.einsum("bhs,bshd->bhd", probs, v.astype(jnp.float32))
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref), atol=2e-2, rtol=2e-2
        )


class TestPagedAttentionHardware:
    """The serving kernels at GPT-2-XL head shapes (25 heads of 64, page 16):
    compiled by Mosaic through the dispatcher, matched against its jnp
    gather path. The CPU suite only ever interprets them."""

    H, D = 25, 64

    def _pool(self, B, n, page, seed, int8=False):
        from deepspeed_tpu.ops.quantizer import quantize_kv_pages

        rs = np.random.RandomState(seed)
        P = B * n + 1  # page 0 is the scratch page; tables never name it
        kf = jnp.asarray(rs.randn(P, self.H, page, self.D), jnp.float32)
        vf = jnp.asarray(rs.randn(P, self.H, page, self.D), jnp.float32)
        bt = jnp.asarray(rs.permutation(np.arange(1, P)).reshape(B, n), jnp.int32)
        if not int8:
            return kf.astype(jnp.bfloat16), vf.astype(jnp.bfloat16), bt, None
        kq, ks = quantize_kv_pages(kf)
        vq, vs = quantize_kv_pages(vf)
        return kq, vq, bt, jnp.stack([ks, vs], axis=-1)

    @pytest.mark.parametrize("int8,page", [(False, 16), (True, 32)])
    def test_paged_decode_compiles_and_matches(self, int8, page):
        from deepspeed_tpu.ops.attention import paged_cached_attention

        B, n = 4, 8
        kp, vp, bt, scales = self._pool(B, n, page, seed=30, int8=int8)
        rs = np.random.RandomState(31)
        q = jnp.asarray(rs.randn(B, self.H, self.D), jnp.bfloat16)
        pos = jnp.asarray([0, page - 1, 3 * page + 5, n * page - 1], jnp.int32)

        def run(impl):
            return jax.jit(
                lambda q, kp, vp, bt, pos, sc: paged_cached_attention(
                    q, kp, vp, bt, pos, impl=impl, scales=sc
                )
            )(q, kp, vp, bt, pos, scales)

        np.testing.assert_allclose(
            np.asarray(run("pallas"), np.float32),
            np.asarray(run("jnp"), np.float32), atol=2e-2, rtol=2e-2,
        )

    @pytest.mark.parametrize(
        "int8,page,T", [(False, 16, 5), (False, 16, 128), (True, 32, 5)]
    )
    def test_paged_multitoken_compiles_and_matches(self, int8, page, T):
        from deepspeed_tpu.ops.attention import paged_multitoken_cached_attention

        B = 2
        n = (T + 3 * page) // page + 1
        kp, vp, bt, scales = self._pool(B, n, page, seed=32, int8=int8)
        rs = np.random.RandomState(33)
        q = jnp.asarray(rs.randn(B, T, self.H, self.D), jnp.bfloat16)
        base = jnp.asarray([0, 2 * page + 3], jnp.int32)

        def run(impl):
            return jax.jit(
                lambda q, kp, vp, bt, base, sc: paged_multitoken_cached_attention(
                    q, kp, vp, bt, base, impl=impl, scales=sc
                )
            )(q, kp, vp, bt, base, scales)

        np.testing.assert_allclose(
            np.asarray(run("pallas"), np.float32),
            np.asarray(run("jnp"), np.float32), atol=2e-2, rtol=2e-2,
        )

    @pytest.mark.parametrize("layered", [False, True], ids=["pool4d", "pool5d"])
    @pytest.mark.parametrize(
        "int8,page,T", [(False, 16, 128), (False, 16, 5), (True, 32, 128),
                        (True, 32, 5)],
    )
    def test_paged_multitoken_at_the_served_shape(self, int8, page, T, layered):
        """ISSUE 31: the chunk call's kernel at XL's shape (a table of 1 024
        positions, chunks at their first, second and last start and one off
        the block edges), a whole-pool ``layer`` or the layer's own pool,
        against the jnp fallback; 8 page blocks a slot where there were
        25 x 64 steps."""
        from deepspeed_tpu.ops.attention import (
            paged_attention_grid_steps,
            paged_multitoken_cached_attention,
        )
        from deepspeed_tpu.ops.pallas.decode_attention import (
            paged_multitoken_blocks,
        )

        n, base = 1024 // page, [0, 128, 896, 437]
        B = len(base)
        kp, vp, bt, scales = self._pool(B, n, page, seed=34, int8=int8)
        if layered:  # the layer between two others that hold something else
            kp = jnp.stack([kp[::-1], kp, jnp.zeros_like(kp)])
            vp = jnp.stack([vp[::-1], vp, jnp.zeros_like(vp)])
        rs = np.random.RandomState(35)
        q = jnp.asarray(rs.randn(B, T, self.H, self.D), jnp.bfloat16)
        base = jnp.asarray(base, jnp.int32)
        # entries past each chunk's reach name a page of another slot
        reach = (np.asarray(base) + T - 1) // page
        past = np.arange(n)[None, :] > reach[:, None]
        bt = jnp.where(jnp.asarray(past), bt[::-1], bt)
        # on the chip the gate takes the kernel, and its grid is the block rule's
        G = paged_multitoken_blocks(self.H, page, self.D, T, kp.dtype.itemsize, n)[1]
        assert G * page == (256 if int8 and T == 5 else 128)
        assert paged_attention_grid_steps(
            "auto", B, self.H, page, self.D, kp.dtype.itemsize, n, T
        ) == B * (n // G)

        def run(impl):
            return jax.jit(
                lambda q, kp, vp, bt, base, sc: paged_multitoken_cached_attention(
                    q, kp, vp, bt, base, impl=impl, scales=sc,
                    layer=1 if layered else None,
                )
            )(q, kp, vp, bt, base, scales)

        np.testing.assert_allclose(
            np.asarray(run("pallas"), np.float32),
            np.asarray(run("jnp"), np.float32), atol=2e-2, rtol=2e-2,
        )


class TestServingPoolLayoutHardware:
    """ISSUE 29: on the chip the K/V pools of a 64-wide head are stored with
    the page axis split (``kv_cache.pool_stored_shape``), so their default
    device layout is the row-major one the paged kernels and the page writes
    work in, and every serving program takes and returns them so: no program
    copies, slices or transposes a layer of a pool."""

    @pytest.mark.parametrize("kv_dtype,page", [("bfloat16", 16), ("int8", 32)])
    def test_programs_keep_the_pool_in_one_layout(self, kv_dtype, page):
        from deepspeed_tpu.inference.engine import InferenceEngine
        from deepspeed_tpu.models import gpt2

        cfg = gpt2.GPT2Config(vocab_size=512, n_positions=256, n_embd=256,
                              n_layer=3, n_head=4)
        eng = InferenceEngine(
            gpt2.make_module(cfg),
            params=gpt2.init_params(cfg, jax.random.PRNGKey(0)),
            dtype=jnp.bfloat16,
        )
        srv = eng.serve({
            "max_slots": 4, "page_size": page, "num_pages": 256,
            "max_prompt_len": 128, "max_new_tokens": 8,
            "prefill_chunk_tokens": 64, "kv_cache_dtype": kv_dtype,
        })
        sfx = "_int8" if kv_dtype == "int8" else ""
        names = [name for name, _ in srv.executable_names()]
        assert names == ["serving_decode" + sfx, "serving_chunk_prefill" + sfx]    # a server that chunks: no whole-prompt program
        assert srv.k_pool.shape == (3, 4, 64, 4, page, 64)
        row_major = tuple(range(6))
        assert srv.k_pool.format.layout.major_to_minor == row_major
        g = srv.metrics.get("serving_pool_relayout_ops")
        assert {n: g.value(program=n) for n in names} == {n: 0 for n in names}
        rs = np.random.RandomState(0)
        reqs = [srv.submit(rs.randint(0, 512, (n,)).astype(np.int32),
                           max_new_tokens=8, seed=i)
                for i, n in enumerate((5, 40, 100, 17))]
        srv.run()
        assert all(len(r.tokens) == 8 for r in reqs)
        # the donated pools came back in the layout they went in with
        assert srv.k_pool.format.layout.major_to_minor == row_major
        srv.check_no_leaks()

    def test_host_tier_and_verify_read_the_split_pool(self):
        """The readers outside the programs: the host tier demotes page
        columns of the split pool and restores them (no miss, tokens as
        without the tier), and ``verify()`` finds the pools donated and files
        them under ``kv-pool``."""
        from deepspeed_tpu.inference.engine import InferenceEngine
        from deepspeed_tpu.models import gpt2

        cfg = gpt2.GPT2Config(vocab_size=512, n_positions=256, n_embd=256,
                              n_layer=3, n_head=4)
        eng = InferenceEngine(
            gpt2.make_module(cfg),
            params=gpt2.init_params(cfg, jax.random.PRNGKey(0)),
            dtype=jnp.bfloat16,
        )
        base = {
            "max_slots": 4, "page_size": 16, "num_pages": 256,
            "max_prompt_len": 128, "max_new_tokens": 8,
            "prefill_chunk_tokens": 64, "prefix_cache": {"enabled": True},
        }
        rs = np.random.RandomState(0)
        shared = rs.randint(0, 512, (64,)).astype(np.int32)
        prompts = [np.concatenate([shared, rs.randint(0, 512, (n,)).astype(np.int32)])
                   for n in (5, 40, 17)]

        def rounds(srv):
            out = []
            for _ in range(2):
                reqs = [srv.submit(p, max_new_tokens=8, seed=i)
                        for i, p in enumerate(prompts)]
                srv.run()
                out += [list(r.tokens) for r in reqs]
                if srv.tiering_enabled:
                    srv.prefix_cache.evict(keep=0)
                    srv.tiering.flush()
            return out

        want = rounds(eng.serve(base))
        srv = eng.serve(dict(base, tiering={"enabled": True,
                                            "host_budget_pages": 64}))
        assert srv.k_pool.ndim == 6
        assert rounds(srv) == want
        t = srv.tiering
        assert t.spills > 0 and t.restores > 0 and t.restore_misses == 0
        # (the committed HBM pins are gpt2-tiny's: over budget here by design)
        findings = srv.verify()
        assert not [f for f in findings if f.rule == "donation-honored"], findings
        for name, ana in srv._memory_analyses.items():
            assert ana.by_category.get("kv-pool", 0) >= 2 * 3 * 256 * 4 * 16 * 64 * 2, name

    @pytest.mark.parametrize("form", ["decode", "verify", "head_blocks"])
    @pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.int8])
    def test_token_write_kernel_matches_the_scatter(self, dtype, form, monkeypatch):
        from deepspeed_tpu.ops.pallas import decode_attention as da
        from deepspeed_tpu.ops.pallas import flash_attention

        rs = np.random.RandomState(1)
        L, P, KV, page, D, B = 3, 64, 25, 32, 64, 8
        if form == "head_blocks":  # five kv-heads a grid step
            monkeypatch.setattr(flash_attention, "VMEM_RESIDENT_BYTES", 1 << 19)
            assert da.paged_token_write_blocks(KV, page, D, jnp.dtype(dtype).itemsize) == 5

        def draw(shape):
            if dtype == jnp.int8:
                return jnp.asarray(rs.randint(-127, 128, shape), dtype)
            return jnp.asarray(rs.randn(*shape), dtype)

        kp, vp = draw((L, P, KV, page, D)), draw((L, P, KV, page, D))
        pidx = np.asarray([5, 9, 0, 33, 0, 63, 1, 17], np.int32)
        poff = np.asarray([0, 31, 0, 7, 0, 16, 15, 8], np.int32)
        if form == "verify":
            # three tokens a slot at consecutive positions: slot 1 crosses
            # into its next page (10), slots 2 and 4 are idle on the scratch page
            T = 3
            nxt = {9: 10}
            pidx = np.stack([
                [p if o + t < page else nxt[p] for t in range(T)]
                for p, o in zip(pidx, poff)]).astype(np.int32)
            poff = np.stack([[(o + t) % page for t in range(T)] for o in poff]
                            ).astype(np.int32)
            pidx[[2, 4]], poff[[2, 4]] = 0, 0
            kv, vv = draw((B, T, KV, D)), draw((B, T, KV, D))
        else:
            kv, vv = draw((B, KV, D)), draw((B, KV, D))
        kv, vv = kv.at[4].set(kv[2]), vv.at[4].set(vv[2])  # the idle slots agree
        if form == "verify":  # and so do their tokens: one element, one value
            kv = kv.at[jnp.asarray([2, 4])].set(kv[2, :1])
            vv = vv.at[jnp.asarray([2, 4])].set(vv[2, :1])
        want = [np.asarray(kp).copy(), np.asarray(vp).copy()]
        for at in np.ndindex(*pidx.shape):
            want[0][1, int(pidx[at]), :, int(poff[at])] = np.asarray(kv[at])
            want[1][1, int(pidx[at]), :, int(poff[at])] = np.asarray(vv[at])
        got = jax.jit(
            lambda k, v: da.paged_token_write(
                k, v, 1, jnp.asarray(pidx), jnp.asarray(poff), kv, vv),
            donate_argnums=(0, 1),
        )(kp, vp)
        np.testing.assert_array_equal(np.asarray(got[0]), want[0])
        np.testing.assert_array_equal(np.asarray(got[1]), want[1])


class TestRingFlashHardware:
    def test_ring_flash_compiles_on_chip(self):
        """Single-chip sp=1 ring: one diagonal step — compiles the flash
        fwd/bwd kernels inside the ring scan + switch on hardware (the
        multi-device ring path itself is covered by the CPU-mesh tests)."""
        from jax import shard_map
        from jax.sharding import Mesh, PartitionSpec as P

        from deepspeed_tpu.ops.pallas.ring_flash_attention import ring_flash_attention

        mesh = Mesh(np.array(jax.devices()[:1]).reshape(1), ("sp",))
        q, k, v = _qkv(1, 256, 2, 64, seed=9)
        spec = P(None, "sp", None, None)

        def loss(q, k, v):
            o = shard_map(
                lambda a, b, c: ring_flash_attention(a, b, c, "sp"),
                mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
                check_vma=False,
            )(q, k, v)
            return jnp.sum(o.astype(jnp.float32) ** 2)

        with mesh:
            val, grads = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))(q, k, v)
        assert np.isfinite(float(val))
        for g in grads:
            assert np.isfinite(np.asarray(g, np.float32)).all()


class TestBidirectionalFlashHardware:
    """Encoder (non-causal) flash path: used by DeepSpeedTransformerLayer and
    the BERT family since they route through bidirectional_attention."""

    def test_noncausal_forward_compiles_and_matches(self):
        from deepspeed_tpu.ops.attention import bidirectional_attention_jnp
        from deepspeed_tpu.ops.pallas.flash_attention import flash_attention

        q, k, v = _qkv(2, 1024, 4, 64, seed=3)
        o = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=False))(q, k, v)
        o_ref = bidirectional_attention_jnp(q, k, v)
        np.testing.assert_allclose(
            np.asarray(o, np.float32), np.asarray(o_ref, np.float32),
            atol=2e-2, rtol=2e-2,
        )

    def test_transformer_layer_op_compiles_on_chip(self):
        from deepspeed_tpu.ops.transformer import (
            DeepSpeedTransformerConfig,
            DeepSpeedTransformerLayer,
        )

        cfg = DeepSpeedTransformerConfig(
            hidden_size=256, heads=4, attn_dropout_ratio=0.0,
            hidden_dropout_ratio=0.0, dtype=jnp.bfloat16,
        )
        layer = DeepSpeedTransformerLayer(cfg)
        params = layer.init(jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 1024, 256), jnp.bfloat16)
        y = jax.jit(lambda p, x: layer(p, x))(params, x)
        assert np.isfinite(np.asarray(y, np.float32)).all()
        # fwd+bwd in one compiled program
        g = jax.jit(jax.grad(lambda p: jnp.sum(layer(p, x).astype(jnp.float32) ** 2)))(params)
        assert all(np.isfinite(np.asarray(l, np.float32)).all() for l in jax.tree.leaves(g))


class TestHostOffloadCheckpointingHardware:
    """Pinned-host activation offload on a real chip (VERDICT r3 weak #7:
    the CPU suite's parity test skips where the backend lacks a pinned_host
    memory space — this twin runs the assert where it exists)."""

    def test_cpu_checkpointing_grads_match(self):
        from deepspeed_tpu.models import gpt2

        base = gpt2.get_config("gpt2-tiny", remat=True, dtype=jnp.float32)
        off = gpt2.get_config(
            "gpt2-tiny", remat=True, dtype=jnp.float32, cpu_checkpointing=True
        )
        params = jax.jit(lambda r: gpt2.init_params(base, r))(jax.random.PRNGKey(0))
        ids = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0, base.vocab_size)
        batch = {"input_ids": ids}

        def grads(cfg):
            return jax.jit(
                jax.grad(lambda p: gpt2.lm_loss(cfg, p, batch, None, True)[0])
            )(params)

        g_base = grads(base)
        try:
            g_off = grads(off)
        except Exception as e:  # transfer/compile rejection, not a wrong grad
            pytest.skip(f"host offload unsupported on this TPU backend: {e}")
        for a, b in zip(jax.tree.leaves(g_base), jax.tree.leaves(g_off)):
            np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(b, np.float32),
                atol=1e-4, rtol=1e-3,
            )


class TestGridFlashHardware:
    """KV-blocked flash kernels on a chip: a sequence past the whole-K/V
    VMEM budget streams through the grid variant (fwd + bwd)."""

    def test_long_seq_grid_forward_and_backward(self):
        from deepspeed_tpu.ops.pallas.flash_attention import (
            VMEM_RESIDENT_BYTES,
            flash_attention,
        )

        D = 128
        # first seq multiple of 128 past the resident budget for bf16
        S = 128 * ((VMEM_RESIDENT_BYTES // (D * 2)) // 128 + 1)
        q, k, v = _qkv(1, S, 1, D, seed=9)
        o = jax.jit(lambda q, k, v: flash_attention(q, k, v))(q, k, v)
        assert np.isfinite(np.asarray(o, np.float32)).all()
        g = jax.jit(
            jax.grad(lambda q: jnp.sum(flash_attention(q, k, v).astype(jnp.float32) ** 2))
        )(q)
        assert np.isfinite(np.asarray(g, np.float32)).all()

    def test_grid_matches_resident_at_shared_shape(self):
        from deepspeed_tpu.ops.pallas.flash_attention import _flash, _flash_grid

        rs = np.random.RandomState(10)
        q3, k3, v3 = [
            jnp.asarray(rs.randn(2, 1024, 64), jnp.bfloat16) for _ in range(3)
        ]
        scale = 1.0 / np.sqrt(64)
        o_res = jax.jit(lambda a, b, c: _flash(a, b, c, None, scale, True, False))(q3, k3, v3)
        o_grid = jax.jit(lambda a, b, c: _flash_grid(a, b, c, scale, True, False))(q3, k3, v3)
        np.testing.assert_allclose(
            np.asarray(o_res, np.float32), np.asarray(o_grid, np.float32),
            atol=2e-2, rtol=2e-2,
        )


class TestWindowedFlashHardware:
    """Sliding-window flash on a chip: the traced scalar-prefetch window and
    the dynamic fori_loop lower bound are the new Mosaic surface here."""

    def test_windowed_forward_and_backward(self):
        from deepspeed_tpu.ops.attention import causal_attention_windowed_jnp
        from deepspeed_tpu.ops.pallas.flash_attention import flash_attention

        rs = np.random.RandomState(21)
        q, k, v = (
            jnp.asarray(rs.randn(1, 1024, 2, 64), jnp.bfloat16) for _ in range(3)
        )
        f = jax.jit(lambda q, k, v, w: flash_attention(q, k, v, window=w))
        for w in (256, 0):  # one compiled kernel serves both (traced window)
            o = f(q, k, v, jnp.int32(w))
            o_ref = causal_attention_windowed_jnp(q, k, v, w)
            np.testing.assert_allclose(
                np.asarray(o, np.float32), np.asarray(o_ref, np.float32),
                atol=2e-2, rtol=2e-2,
            )

        fk = lambda q, k, v: flash_attention(q, k, v, window=256)
        fr = lambda q, k, v: causal_attention_windowed_jnp(q, k, v, 256)
        g = _grad_triple(fk, q, k, v)
        g_ref = _grad_triple(fr, q, k, v)
        g_truth = _truth_grads(fr, q, k, v)
        _assert_grads_within_reference_noise(g, g_ref, g_truth)


class TestGQAFlashHardware:
    """GQA through the flash kernels on a chip: K/V at fewer heads, read via
    divided index maps (Mistral/Mixtral/LLaMA-70B training path)."""

    def test_gqa_forward_and_backward(self):
        from deepspeed_tpu.ops.pallas.flash_attention import flash_attention

        B, S, H, D, rep = 1, 1024, 4, 128, 2
        rs = np.random.RandomState(14)
        q = jnp.asarray(rs.randn(B, S, H, D), jnp.bfloat16)
        k = jnp.asarray(rs.randn(B, S, H // rep, D), jnp.bfloat16)
        v = jnp.asarray(rs.randn(B, S, H // rep, D), jnp.bfloat16)
        o = jax.jit(lambda q, k, v: flash_attention(q, k, v))(q, k, v)
        assert np.isfinite(np.asarray(o, np.float32)).all()
        gk = jax.jit(
            jax.grad(lambda k: jnp.sum(flash_attention(q, k, v).astype(jnp.float32) ** 2))
        )(k)
        assert gk.shape == k.shape  # dk at KV heads
        assert np.isfinite(np.asarray(gk, np.float32)).all()

    def test_gqa_decode_kernel_on_chip(self):
        from deepspeed_tpu.ops.pallas.decode_attention import decode_attention

        B, S, H, D, rep = 2, 1024, 4, 128, 2
        rs = np.random.RandomState(15)
        q = jnp.asarray(rs.randn(B, H, D), jnp.bfloat16)
        k = jnp.asarray(rs.randn(B, S, H // rep, D), jnp.bfloat16)
        v = jnp.asarray(rs.randn(B, S, H // rep, D), jnp.bfloat16)
        out = jax.jit(lambda q, k, v, p: decode_attention(q, k, v, p))(
            q, k, v, jnp.int32(100)
        )
        assert out.shape == (B, H, D)
        assert np.isfinite(np.asarray(out, np.float32)).all()
