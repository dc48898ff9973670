"""int8 KV cache pages (ISSUE 12 tentpole): quantized-pool serving suite.

The load-bearing contracts, in descending strength:

1. BIT-equivalence *within* the int8 mode: the full PR-10 feature set
   (speculative verify, prefix cache, chunked prefill) emits streams
   bit-identical to plain int8 sequential decode — the frozen-per-page
   scale discipline makes scatter-then-attend order-independent, exactly
   like the bf16 contract.
2. Greedy parity *across* precisions: on the gpt2-tiny reference the int8
   pool's bounded quantization error does not flip any argmax for the
   pinned seed suite, so the streams equal the float32 pool's exactly —
   with a model-level logit-tolerance pin underneath it (the robust bound
   the ISSUE falls back to where exactness is impossible).
3. The sharing machinery: COW forks leave the shared original's codes AND
   scale row untouched; drains leak nothing; Engine E sees the halved pool.
"""

import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import gpt2
from deepspeed_tpu.serving.kv_cache import Cache, init_pools, pool_bytes, scales_bytes
from deepspeed_tpu.serving.request import RequestStatus

warnings.filterwarnings("ignore")

pytestmark = pytest.mark.serving


@pytest.fixture(scope="module")
def tiny_cfg():
    return gpt2.get_config("gpt2-tiny", attn_impl="jnp")


@pytest.fixture(scope="module")
def inference_engine(tiny_cfg):
    from deepspeed_tpu.inference.engine import InferenceEngine

    params = gpt2.init_params(tiny_cfg, jax.random.PRNGKey(0))
    return InferenceEngine(
        gpt2.make_module(tiny_cfg), params=params, dtype=jnp.float32
    )


BASE = {
    "max_slots": 4,
    "page_size": 4,
    "num_pages": 64,
    "max_prompt_len": 12,
    "max_new_tokens": 8,
}
ALL_FEATURES = {
    "speculative": {"enabled": True, "k": 3},
    "prefix_cache": {"enabled": True},
    "prefill_chunk_tokens": 8,
}


def _mixed_requests(vocab, n=16, seed=7):
    rs = np.random.RandomState(seed)
    plens = [2, 5, 8, 12, 7, 3, 11, 4] * 2
    return [
        (rs.randint(0, vocab, (plens[i],)).astype(np.int32), 6 if i % 7 else (1, 3, 8)[i // 7])
        for i in range(n)
    ]


def _run(srv, reqs):
    subs = [
        srv.submit(p, max_new_tokens=n, seed=i)
        for i, (p, n) in enumerate(reqs)
    ]
    srv.run()
    return subs


class TestInt8Parity:
    def test_mixed_suite_int8_features_bit_identical_and_f32_parity(
        self, tiny_cfg, inference_engine
    ):
        """The 16-request mixed suite with kv_cache_dtype=int8 and ALL
        PR-10 features on: (a) bit-identical to plain int8 sequential
        serving — the acceptance contract that speculation/sharing/chunking
        survive quantization — and (b) greedy outputs equal to the float32
        pool's for the pinned seeds (the tiny model's argmax margins exceed
        the int8 rounding; a mismatch here means the quantizer regressed
        past its error bound). Accept-length mean stays within 5% of the
        f32 run, and both engines drain leak-free."""
        reqs = _mixed_requests(tiny_cfg.vocab_size)

        srv_plain = inference_engine.serve(dict(BASE, kv_cache_dtype="int8"))
        plain = _run(srv_plain, reqs)
        srv_feat = inference_engine.serve(
            dict(BASE, kv_cache_dtype="int8", **ALL_FEATURES)
        )
        feat = _run(srv_feat, reqs)
        srv_f32 = inference_engine.serve(
            dict(BASE, kv_cache_dtype="float32", **ALL_FEATURES)
        )
        f32 = _run(srv_f32, reqs)

        for a, b, c in zip(plain, feat, f32):
            assert a.status == RequestStatus.FINISHED
            assert list(b.tokens) == list(a.tokens)   # features == sequential
            assert list(b.tokens) == list(c.tokens)   # int8 == f32 (greedy)

        # spec accept-length parity: within 5% of the f32 run
        acc_q = srv_feat.stats()["spec_accept_len_mean"]
        acc_f = srv_f32.stats()["spec_accept_len_mean"]
        assert acc_q is not None and acc_f is not None
        assert abs(acc_q - acc_f) <= 0.05 * acc_f

        for srv in (srv_plain, srv_feat, srv_f32):
            srv.release_prefix_cache()
            srv.check_no_leaks()
        assert srv_feat.stats()["kv_cache_dtype"] == "int8"

    def test_prefill_kv_tolerance_vs_f32(self, tiny_cfg, inference_engine):
        """Model-level pin under the stream-equality test: the int8 paged
        prefill's DEQUANTIZED first-layer K/V stays within the block
        codec's per-page bound of the float32 pool's exact values — the
        per-position tolerance the ISSUE accepts where exactness is
        impossible (logits are a Lipschitz image of the cached K/V, so
        bounding the cache bounds them) — and the greedy token matches."""
        from deepspeed_tpu.ops.quantizer import dequantize_kv_pages
        from deepspeed_tpu.serving import model as smodel

        cfg = tiny_cfg
        rs = np.random.RandomState(0)
        Sp = 8
        ids = rs.randint(0, cfg.vocab_size, (1, Sp)).astype(np.int32)
        page = 4
        kq, vq, sc = init_pools(cfg.n_layer, 16, cfg.n_head, page,
                                cfg.head_dim, dtype=jnp.int8)
        kf, vf, _ = init_pools(cfg.n_layer, 16, cfg.n_head, page,
                               cfg.head_dim, dtype=jnp.float32)
        params = inference_engine.params
        page_ids = np.arange(1, 1 + Sp // page).astype(np.int32)
        plen = jnp.asarray(Sp, jnp.int32)
        key = jax.random.PRNGKey(0)
        (kq2, vq2, sc2, *_), tok_q = smodel.paged_prefill(
            cfg, params, jnp.asarray(ids), plen, Cache(kq, vq, sc),
            jnp.asarray(page_ids), key,
        )
        (kf2, vf2, *_), tok_f = smodel.paged_prefill(
            cfg, params, jnp.asarray(ids), plen, Cache(kf, vf),
            jnp.asarray(page_ids), key,
        )
        assert int(tok_q[0]) == int(tok_f[0])
        # layer 0's prompt pages: |dequant(codes) - exact| <= scale/2
        # elementwise (round-to-nearest against the frozen per-page scale).
        # Layer >0 K/V additionally drifts because earlier layers ATTENDED
        # dequantized values — the first layer isolates the codec itself.
        for pool_q, pool_f, col in ((kq2, kf2, 0), (vq2, vf2, 1)):
            deq = np.asarray(dequantize_kv_pages(
                pool_q[0, page_ids], sc2[0, page_ids, :, col]
            ))
            exact = np.asarray(pool_f[0, page_ids])
            half_scale = np.asarray(sc2[0, page_ids, :, col])[..., None, None] / 2
            assert np.all(np.abs(deq - exact) <= half_scale + 1e-7)

    def test_cow_fork_leaves_original_page_and_scale_pristine(
        self, tiny_cfg, inference_engine
    ):
        """A full-prefix hit COW-forks BY RECOMPUTE: the fork requantizes
        into its own page + scale row; the shared original's codes and
        scale entries must be byte-identical before/after — the scales-
        ride-the-refcount contract."""
        srv = inference_engine.serve(dict(
            BASE, kv_cache_dtype="int8",
            prefix_cache={"enabled": True}, prefill_chunk_tokens=8,
        ))
        rs = np.random.RandomState(3)
        prompt = rs.randint(0, tiny_cfg.vocab_size, (8,)).astype(np.int32)
        r1 = srv.submit(prompt, max_new_tokens=6, seed=0)
        srv.run()
        shared = list(srv.prefix_cache.held_pages)
        assert shared, "prompt pages should be indexed"
        k_before = np.asarray(srv.k_pool)[:, shared].copy()
        s_before = np.asarray(srv.kv_scales)[:, shared].copy()
        r2 = srv.submit(prompt, max_new_tokens=6, seed=0)
        srv.run()
        assert srv.allocator.cow_forks_total == 1
        assert list(r2.tokens) == list(r1.tokens)
        np.testing.assert_array_equal(np.asarray(srv.k_pool)[:, shared], k_before)
        np.testing.assert_array_equal(np.asarray(srv.kv_scales)[:, shared], s_before)
        srv.release_prefix_cache()
        srv.check_no_leaks()

    def test_prefix_hit_tokens_identical_to_cold_engine(
        self, tiny_cfg, inference_engine
    ):
        """Partial-prefix reuse under int8: the hit maps the cold prompt's
        QUANTIZED pages — the same codes its own prefill would have written
        (deterministic content → deterministic scale → deterministic
        codes) — so the tokens match a cold engine's exactly."""
        cfg_d = dict(BASE, kv_cache_dtype="int8",
                     prefix_cache={"enabled": True}, prefill_chunk_tokens=8)
        srv = inference_engine.serve(cfg_d)
        rs = np.random.RandomState(5)
        head = rs.randint(0, tiny_cfg.vocab_size, (8,)).astype(np.int32)
        srv.submit(head, max_new_tokens=4, seed=0)
        srv.run()
        p2 = np.concatenate(
            [head, rs.randint(0, tiny_cfg.vocab_size, (3,)).astype(np.int32)]
        )
        r_hit = srv.submit(p2, max_new_tokens=6, seed=0)
        srv.run()
        assert r_hit.prefix_shared_tokens > 0
        cold = inference_engine.serve(cfg_d)
        r_cold = cold.submit(p2, max_new_tokens=6, seed=0)
        cold.run()
        assert list(r_hit.tokens) == list(r_cold.tokens)


class TestInt8Pool:
    def test_init_pools_grows_scales_and_bytes_split(self, tiny_cfg):
        k, v, sc = init_pools(2, 8, 2, 4, 8, dtype=jnp.int8)
        assert k.dtype == jnp.int8 and sc.shape == (2, 8, 2, 2)
        assert sc.dtype == jnp.float32 and float(jnp.max(jnp.abs(sc))) == 0.0
        kf, vf, none = init_pools(2, 8, 2, 4, 8, dtype=jnp.float32)
        assert none is None
        # codes pool is itemsize-proportional; scales accounted separately
        assert pool_bytes(2, 8, 2, 4, 8, itemsize=1) * 2 == pool_bytes(2, 8, 2, 4, 8, itemsize=2)
        assert scales_bytes(2, 8, 2) == 2 * 8 * 2 * 2 * 4

    def test_engine_e_kv_pool_halved_and_scales_under_metadata(
        self, tiny_cfg, inference_engine
    ):
        """Acceptance: Engine E's MEASURED kv-pool bytes-per-category under
        int8 ≤ 0.55x the bf16 pool's bytes at the same num_pages (it is
        exactly 0.5x: one code byte per two bf16 bytes; the bf16 pool is
        exact by construction), with the scales pool reported under
        metadata and split out in memory_report()."""
        srv_q = inference_engine.serve(dict(BASE, kv_cache_dtype="int8"))
        assert srv_q.verify() == []
        rep_q = srv_q.memory_report()
        bf16_pool = pool_bytes(
            tiny_cfg.n_layer, BASE["num_pages"], tiny_cfg.n_head,
            BASE["page_size"], tiny_cfg.head_dim, itemsize=2,
        )
        for qname in ("serving_prefill_int8", "serving_decode_int8"):
            q = rep_q[qname]
            # the ledger-measured quantized pool vs the bf16 pool's bytes
            assert q["kv_pool_bytes"] <= 0.55 * bf16_pool
            assert q["kv_pool_bytes"] == bf16_pool // 2  # exactly half
            assert q["kv_scales_bytes"] == scales_bytes(
                tiny_cfg.n_layer, BASE["num_pages"], tiny_cfg.n_head
            )
            # the scales land in the metadata category beside the tables
            assert q["metadata_bytes"] >= q["kv_scales_bytes"]
            assert q["kv_cache_dtype"] == "int8"

    def test_doubled_pool_budget_pin_stays_red(self, inference_engine):
        """The regression gate at the NEW int8 budgets: doubling num_pages
        must fire hbm-over-budget naming the quantized programs."""
        srv = inference_engine.serve(dict(BASE, kv_cache_dtype="int8",
                                          num_pages=128))
        findings = srv.verify()
        assert any(f.rule == "hbm-over-budget" for f in findings)

    def test_bad_kv_cache_dtype_rejected(self):
        from deepspeed_tpu.runtime.config import (
            DeepSpeedConfigError,
            ServingConfig,
        )

        with pytest.raises(DeepSpeedConfigError, match="kv_cache_dtype"):
            ServingConfig(kv_cache_dtype="int4")

    def test_drain_zero_leak_under_load(self, tiny_cfg, inference_engine):
        """SIGTERM-style drain mid-load with int8 + all features: every
        page (codes AND scale row holders) back on the free list."""
        srv = inference_engine.serve(
            dict(BASE, kv_cache_dtype="int8", **ALL_FEATURES)
        )
        rs = np.random.RandomState(11)
        for i in range(8):
            srv.submit(
                rs.randint(0, tiny_cfg.vocab_size, (6,)).astype(np.int32),
                max_new_tokens=8, seed=i,
            )
        srv.step()
        srv.drain(deadline_s=0.0)
        srv.release_prefix_cache()
        srv.check_no_leaks()
