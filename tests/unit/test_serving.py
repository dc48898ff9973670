"""Continuous-batching serving subsystem (ISSUE 3 tentpole): deterministic
CPU simulation tests.

The load-bearing assertion is token EQUIVALENCE: a stream of mixed-length
requests through :class:`ServingEngine` must be bit-identical to per-request
sequential ``generate`` — with exactly two compiled executables and zero
KV-page leaks at drain. Timeouts run under an injected fake clock so
eviction is deterministic.
"""

import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import gpt2
from deepspeed_tpu.serving import (
    PageAllocator,
    PageAllocatorError,
    PrefixCache,
    RequestStatus,
    pages_for,
)

warnings.filterwarnings("ignore")

pytestmark = pytest.mark.serving


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


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


@pytest.fixture(scope="module")
def shared_srv(inference_engine):
    """One ServingEngine (and its two executables) shared by every test that
    uses the default SERVING_CFG — the engine is reusable after drain."""
    return inference_engine.serve(SERVING_CFG)


SERVING_CFG = {
    "max_slots": 4,
    "page_size": 4,
    "num_pages": 64,
    "max_prompt_len": 12,
    "max_new_tokens": 8,
    "kv_cache_dtype": "float32",
}


class TestPageAllocator:
    def test_alloc_free_roundtrip(self):
        a = PageAllocator(8)
        assert a.capacity == 7  # page 0 is scratch
        pages = a.alloc(3)
        assert len(set(pages)) == 3 and 0 not in pages
        assert a.free_pages == 4 and a.pages_in_use == 3
        a.free(pages)
        a.check_no_leaks()
        assert a.free_pages == 7

    def test_exhaustion_is_all_or_nothing(self):
        a = PageAllocator(4)
        a.alloc(2)
        with pytest.raises(PageAllocatorError, match="exhausted"):
            a.alloc(2)
        assert a.free_pages == 1  # the failed alloc took nothing

    def test_double_free_and_foreign_page_raise(self):
        a = PageAllocator(8)
        pages = a.alloc(2)
        a.free(pages)
        with pytest.raises(PageAllocatorError, match="double free"):
            a.free([pages[0]])
        with pytest.raises(PageAllocatorError):
            a.free([0])  # scratch is never freeable

    def test_leak_detection(self):
        a = PageAllocator(8)
        a.alloc(1)
        with pytest.raises(PageAllocatorError, match="leaked"):
            a.check_no_leaks()

    def test_pages_for(self):
        assert pages_for(1, 4) == 1
        assert pages_for(4, 4) == 1
        assert pages_for(5, 4) == 2


class TestTokenEquivalence:
    def test_mixed_length_stream_bit_identical(self, tiny_cfg, inference_engine, shared_srv):
        """≥16 mixed-length requests through ServingEngine == per-request
        sequential generate, bit for bit; exactly 2 compiled executables;
        zero page leaks at drain (the ISSUE 3 acceptance criterion)."""
        srv = shared_srv
        rs = np.random.RandomState(7)
        # mixed lengths/budgets drawn from few pow2 buckets so the per-request
        # reference generates stay at ~6 compiled executables
        plens = [2, 5, 8, 12, 7, 3, 11, 4] * 2
        reqs = []
        for i in range(16):
            plen = plens[i]
            n = 6 if i % 7 else (1, 3, 8)[i // 7]  # mixed budgets, few shapes
            prompt = rs.randint(0, tiny_cfg.vocab_size, (plen,)).astype(np.int32)
            reqs.append((prompt, n, srv.submit(prompt, max_new_tokens=n, seed=i)))
        done = srv.run()
        assert len(done) == 16
        assert len(srv.executables) == 2  # one prefill + one decode program
        for prompt, n, req in reqs:
            assert req.status == RequestStatus.FINISHED
            assert len(req.tokens) == n
            ref = np.asarray(
                inference_engine.generate(prompt[None, :], max_new_tokens=n)
            )[0]
            np.testing.assert_array_equal(req.output, ref)
        srv.check_no_leaks()
        # telemetry wired through the registry
        m = srv.metrics
        assert m.counter(
            "serving_requests_total", labelnames=("status",)
        ).value(status="finished") == 16
        assert m.histogram("serving_ttft_seconds").stats()[1] == 16
        assert m.gauge("serving_kv_pages_in_use").value() == 0

    def test_sampled_stream_matches_seeded_generate(self, tiny_cfg, inference_engine):
        """Temperature sampling: per-slot keys reproduce each request's own
        B=1 generate key sequence exactly."""
        cfg = dict(SERVING_CFG, temperature=0.8, top_k=5)
        srv = inference_engine.serve(cfg)
        rs = np.random.RandomState(3)
        reqs = []
        for i, plen in enumerate((3, 8, 4, 7)):  # two reference buckets
            prompt = rs.randint(0, tiny_cfg.vocab_size, (plen,)).astype(np.int32)
            reqs.append((prompt, srv.submit(prompt, max_new_tokens=5, seed=100 + i)))
        srv.run()
        for prompt, req in reqs:
            ref = np.asarray(
                inference_engine.generate(
                    prompt[None, :], max_new_tokens=5,
                    temperature=0.8, top_k=5, seed=req.seed,
                )
            )[0]
            np.testing.assert_array_equal(req.output, ref)
        srv.check_no_leaks()

    def test_eos_stops_early_and_frees_pages(self, tiny_cfg, inference_engine, shared_srv):
        rs = np.random.RandomState(11)
        prompt = rs.randint(0, tiny_cfg.vocab_size, (6,)).astype(np.int32)
        ref = np.asarray(
            inference_engine.generate(prompt[None, :], max_new_tokens=8)
        )[0, 6:]
        eos = int(ref[2])
        stop_at = int(np.where(ref == eos)[0][0]) + 1  # first occurrence
        srv = shared_srv
        req = srv.submit(prompt, max_new_tokens=8, eos_token_id=eos)
        srv.run()
        assert req.status == RequestStatus.FINISHED
        assert req.tokens == ref[:stop_at].tolist()  # stopped AT the eos token
        srv.check_no_leaks()


class TestTokenWrite:
    """ISSUE 29: where the paged kernels run, the one-token pool write is one
    Pallas call for both pools, which asks no device layout of them, where
    it was one scatter a pool (and still is elsewhere). Same elements, same
    values: the pools it leaves are bitwise the scatter's, over random
    tables, ragged lengths and idle slots on the scratch page, for the
    decode step's targets and the verify step's. The kernel writes what it
    is pointed at, so the indices are pinned in range as well."""

    L, P, KV, PAGE, D, B, W = 2, 32, 2, 4, 8, 5, 4

    @staticmethod
    def _old_scatter(pool, l, pidx, poff, vals):
        kv = jnp.arange(pool.shape[2])
        return pool.at[l, pidx[..., None], kv, poff[..., None]].set(
            vals.astype(pool.dtype)
        )

    def _state(self, seed, idle=(), lens=None):
        rs = np.random.RandomState(seed)
        bt = rs.choice(
            np.arange(1, self.P), (self.B * self.W,), replace=False
        ).reshape(self.B, self.W).astype(np.int32)
        lens = np.asarray(
            lens if lens is not None
            else rs.randint(0, self.W * self.PAGE, (self.B,)), np.int32
        )
        for b in idle:  # as the scheduler leaves a free slot
            bt[b], lens[b] = 0, 0
        return rs, jnp.asarray(bt), jnp.asarray(lens)

    def _vals(self, rs, shape, dtype, same_rows=()):
        v = rs.randn(*shape, self.KV, self.D).astype(np.float32)
        for b in same_rows[1:]:  # idle slots hold the same token and position
            v[b] = v[same_rows[0]]
        if dtype == jnp.int8:
            return jnp.asarray(np.clip(v * 40, -127, 127), jnp.int8)
        return jnp.asarray(v, dtype)

    def _pool(self, rs, dtype):
        shape = (self.L, self.P, self.KV, self.PAGE, self.D)
        if dtype == jnp.int8:
            return jnp.asarray(rs.randint(-127, 128, shape), jnp.int8)
        return jnp.asarray(rs.randn(*shape), dtype)

    def _decode_case(self, seed, idle, dtype):
        """Pools, paged_decode_step's own write targets, and new K and V."""
        rs, bt, lens = self._state(seed, idle)
        pidx = jnp.take_along_axis(bt, (lens // self.PAGE)[:, None], axis=1)[:, 0]
        poff = lens % self.PAGE
        assert 0 <= int(pidx.min()) and int(pidx.max()) < self.P
        assert all(int(pidx[b]) == 0 and int(poff[b]) == 0 for b in idle)
        return (self._pool(rs, dtype), self._pool(rs, dtype), pidx, poff,
                self._vals(rs, (self.B,), dtype, same_rows=idle),
                self._vals(rs, (self.B,), dtype, same_rows=idle))

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int8])
    @pytest.mark.parametrize("idle", [(), (1, 3)])
    @pytest.mark.parametrize("how", ["scatter", "pallas_call"])
    def test_decode_write_is_bitwise_the_scatter(self, how, dtype, idle):
        from deepspeed_tpu.ops.pallas.decode_attention import paged_token_write
        from deepspeed_tpu.serving import model as smodel

        kp, vp, pidx, poff, kv, vv = self._decode_case(11 + len(idle), idle, dtype)
        for l in range(self.L):
            if how == "pallas_call":  # the TPU's path, its body in the interpreter
                k_new, v_new = paged_token_write(
                    kp, vp, l, pidx, poff, kv, vv, interpret=True
                )
            else:
                k_new, v_new = jax.jit(smodel._scatter_tokens, static_argnums=2)(
                    kp, vp, l, pidx, poff, kv, vv
                )
            for new, pool, vals in ((k_new, kp, kv), (v_new, vp, vv)):
                assert new.dtype == pool.dtype and new.shape == pool.shape
                np.testing.assert_array_equal(
                    np.asarray(new),
                    np.asarray(self._old_scatter(pool, l, pidx, poff, vals)),
                )
                assert not np.array_equal(np.asarray(new), np.asarray(pool))

    def _verify_case(self, case, T=3):
        from deepspeed_tpu.serving import model as smodel

        budget = self.W * self.PAGE
        lens = (
            [0, 3, 6, 9, 12] if case == "inside"  # 3, 6: the drafts cross a page
            else [budget - 1, 3, budget - 2, 9, 12]  # drafts run past the row
        )
        rs, bt, lens = self._state(23, lens=lens)
        return rs, lens, smodel._verify_write_targets(lens, bt, self.PAGE, T)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("case", ["inside", "past_the_row"])
    @pytest.mark.parametrize("how", ["scatter", "pallas_call", "head_blocks"])
    def test_verify_write_is_bitwise_the_scatter(self, how, case, dtype, monkeypatch):
        """The verify step's ``[B, T]`` form, in one call: a slot's tokens
        that share a page, that cross into the next one, and that run past
        the row onto the scratch page (whose content nothing reads)."""
        from deepspeed_tpu.ops.pallas import decode_attention as da
        from deepspeed_tpu.ops.pallas import flash_attention
        from deepspeed_tpu.serving import model as smodel

        T = 3
        rs, _, (pidx, poff) = self._verify_case(case, T)
        kp, vp = self._pool(rs, dtype), self._pool(rs, dtype)
        kv, vv = self._vals(rs, (self.B, T), dtype), self._vals(rs, (self.B, T), dtype)
        if how == "head_blocks":  # VMEM for one kv-head a grid step
            monkeypatch.setattr(flash_attention, "VMEM_RESIDENT_BYTES", 100_000)
            assert da.paged_token_write_blocks(
                self.KV, self.PAGE, self.D, kp.dtype.itemsize, T) == 1
        if how == "scatter":
            k_new, v_new = jax.jit(smodel._scatter_tokens, static_argnums=2)(
                kp, vp, 1, pidx, poff, kv, vv)
        else:
            k_new, v_new = da.paged_token_write(
                kp, vp, 1, pidx, poff, kv, vv, interpret=True)
        for new, pool, vals in ((k_new, kp, kv), (v_new, vp, vv)):
            want = np.asarray(self._old_scatter(pool, 1, pidx, poff, vals))
            np.testing.assert_array_equal(np.asarray(new)[:, 1:], want[:, 1:])
            if case == "inside":  # nothing landed on the scratch page
                np.testing.assert_array_equal(np.asarray(new)[:, 0], want[:, 0])
            assert not np.array_equal(np.asarray(new), np.asarray(pool))

    @pytest.mark.parametrize("case", ["inside", "past_the_row"])
    def test_verify_targets_stay_in_range(self, case):
        T = 3
        budget = self.W * self.PAGE
        _, lens, (pidx, poff) = self._verify_case(case, T)
        assert 0 <= int(pidx.min()) and int(pidx.max()) < self.P
        assert 0 <= int(poff.min()) and int(poff.max()) < self.PAGE
        past = np.asarray(lens)[:, None] + np.arange(T)[None, :] >= budget
        assert past.any() == (case == "past_the_row")
        # what runs past the slot's row lands on the scratch page, nowhere else
        np.testing.assert_array_equal(np.asarray(pidx)[past], 0)
        assert (np.asarray(pidx)[~past] > 0).all()

    def test_the_write_takes_the_kernel_where_the_paged_kernels_run(self, monkeypatch):
        """``_scatter_tokens`` asks ``paged_token_write_ok`` (a TPU, the page
        rule, a K and a V page of a block of heads in VMEM): every shape the
        attention kernels take."""
        from deepspeed_tpu.ops.pallas import decode_attention as da
        from deepspeed_tpu.serving import model as smodel

        assert not da.paged_token_write_ok(25, 16, 64)  # this backend is no TPU
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert da.paged_token_write_ok(25, 16, 64)
        assert da.paged_token_write_ok(64, 16, 128)
        assert da.paged_token_write_ok(25, 32, 64, itemsize=1)
        assert not da.paged_token_write_ok(25, 12, 64)       # the page rule
        assert da.paged_token_write_ok(25, 16, 64, T=5)      # the verify step
        assert da.paged_token_write_blocks(25, 16, 64) == 25
        assert da.paged_token_write_blocks(64, 128, 128, 4) == 4  # 64 KB a page
        assert da.paged_token_write_ok(64, 128, 128, 4)
        assert not da.paged_token_write_ok(4, 1024, 128, 4)  # no head's pages fit
        calls = []

        def spy(k_pool, v_pool, l, pidx, poff, k_vals, v_vals, shared=False):
            calls.append((l, k_vals.shape) + ((shared,) if shared else ()))
            return k_pool, v_pool

        monkeypatch.setattr(da, "paged_token_write", spy)
        pool = jnp.zeros((2, 8, 5, 16, 64), jnp.bfloat16)
        i = jnp.zeros((3,), jnp.int32)
        vals = jnp.zeros((3, 5, 64))
        smodel._scatter_tokens(pool, pool, 1, i, i, vals, vals)
        assert calls == [(1, (3, 5, 64))]
        # the mixed step asks for one kernel for all its layers (ISSUE 35)
        smodel._scatter_tokens(pool, pool, 0, i, i, vals, vals, shared=True)
        assert calls[1:] == [(0, (3, 5, 64), True)]

    def test_int8_token_write_keeps_scale_discipline(self):
        """``_write_pool_tokens``: offset 0 establishes the page's scale from
        this token, any other offset codes against the frozen one; K's and
        V's scales sit in their own columns."""
        from deepspeed_tpu.ops.quantizer import kv_page_scale, quantize_kv_token
        from deepspeed_tpu.serving.kv_cache import Cache
        from deepspeed_tpu.serving import model as smodel

        rs, bt, lens = self._state(5, lens=[0, 1, 4, 7, 8])
        pidx = jnp.take_along_axis(bt, (lens // self.PAGE)[:, None], axis=1)[:, 0]
        poff = lens % self.PAGE
        kp, vp = self._pool(rs, jnp.int8), self._pool(rs, jnp.int8)
        scales = jnp.asarray(
            rs.rand(self.L, self.P, self.KV, 2) + 0.5, jnp.float32
        )
        kv = jnp.asarray(rs.randn(self.B, self.KV, self.D), jnp.float32)
        vv = jnp.asarray(rs.randn(self.B, self.KV, self.D), jnp.float32)
        k_new, v_new, new_scales = smodel._write_pool_tokens(
            Cache(kp, vp, scales), 0, pidx, poff, kv, vv
        )[:3]
        fresh = np.asarray(poff) == 0
        for col, (new, pool, vals) in enumerate(((k_new, kp, kv), (v_new, vp, vv))):
            want_s = np.where(
                fresh[:, None], np.asarray(kv_page_scale(vals)),
                np.asarray(scales[0, pidx, :, col]),
            )
            np.testing.assert_array_equal(
                np.asarray(new_scales[0, pidx, :, col]), want_s
            )
            codes = np.asarray(quantize_kv_token(vals, jnp.asarray(want_s)))
            got = np.asarray(new)[0, np.asarray(pidx), :, np.asarray(poff)]
            np.testing.assert_array_equal(got, codes)
            # every other layer stays as it was
            np.testing.assert_array_equal(np.asarray(new[1]), np.asarray(pool[1]))
        np.testing.assert_array_equal(np.asarray(new_scales[1]), np.asarray(scales[1]))


class TestSplitPagePoolEngine:
    """The whole engine over pools stored with the page axis split in two, as
    on a TPU with a narrow head (``kv_cache.pool_stored_shape``; forced here:
    the choice reads the backend). Every program works on the
    ``[L, P, KV, page, D]`` view: prefill, chunked prefill, decode, the
    speculative verify step, the prefix cache's copy-on-write, int8 pages,
    tensor-parallel shards, the disaggregated hand-off and the host tier's
    demotion and restore give the token streams of the plainly stored pool."""

    @staticmethod
    def _split(n_layer, num_pages, n_kv_head, page_size, head_dim, dtype):
        group = max(g for g in (1, 2, 3, 4) if num_pages % g == 0)
        return (n_layer, num_pages // group, group, n_kv_head, page_size, head_dim)

    @pytest.mark.parametrize("features", ["plain", "int8", "tp2_disaggregated"])
    def test_verify_reads_the_stored_pool_as_it_reads_the_plain_one(
        self, inference_engine, monkeypatch, features
    ):
        """``ServingEngine.verify()`` over the split pool: the donation rule
        finds the pools' aliased entry parameters by their stored dims and
        the memory engine files them under ``kv-pool``, so findings and
        categories are the plain pool's."""
        from deepspeed_tpu.serving import kv_cache

        cfg = dict(SERVING_CFG, prefill_chunk_tokens=4)
        if features == "int8":
            cfg.update(kv_cache_dtype="int8")
        elif features == "tp2_disaggregated":
            cfg.update(placement={"tp": 2, "disaggregate": True})

        def read(srv):
            findings = sorted((f.rule, f.path) for f in srv.verify())
            # what enters: the weights alone under params, the pools donated
            # (kv-pool's peak also counts what the CPU copies of a view)
            entry = {
                name: (ana.by_category.get("params", 0), ana.args_bytes,
                       ana.aliased_bytes)
                for name, ana in srv._memory_analyses.items()
            }
            kv = {name: ana.by_category.get("kv-pool", 0)
                  for name, ana in srv._memory_analyses.items()}
            return findings, entry, kv

        want = read(inference_engine.serve(cfg))
        monkeypatch.setattr(kv_cache, "pool_stored_shape", self._split)
        stored = inference_engine.serve(cfg)
        assert stored.k_pool.ndim == 6
        assert not [f for f in stored.verify() if f.rule == "donation-honored"]
        got = read(stored)
        assert got[:2] == want[:2]
        assert all(got[2][name] >= n > 0 for name, n in want[2].items())

    @pytest.mark.parametrize("features", [
        "plain", "chunk_spec_prefix", "int8", "tp2_disaggregated", "tiering",
    ])
    def test_token_streams_match_the_plain_pool(
        self, tiny_cfg, inference_engine, monkeypatch, features
    ):
        from deepspeed_tpu.serving import kv_cache

        cfg = dict(SERVING_CFG)
        if features == "chunk_spec_prefix":
            cfg.update(prefill_chunk_tokens=4, prefix_cache={"enabled": True},
                       speculative={"enabled": True, "k": 2})
        elif features == "int8":
            cfg.update(kv_cache_dtype="int8", prefill_chunk_tokens=4)
        elif features == "tp2_disaggregated":
            cfg.update(placement={"tp": 2, "disaggregate": True},
                       prefill_chunk_tokens=4)
        elif features == "tiering":
            cfg.update(num_pages=24, prefix_cache={"enabled": True},
                       tiering={"enabled": True, "host_budget_pages": 64})
        rs = np.random.RandomState(5)
        shared = rs.randint(0, tiny_cfg.vocab_size, (8,)).astype(np.int32)
        prompts = [rs.randint(0, tiny_cfg.vocab_size, (n,)).astype(np.int32)
                   for n in (2, 5, 12, 7, 11, 3)]
        prompts += [np.concatenate([shared, p[:3]]) for p in prompts[:2]] + [shared]
        prompts += prompts[:3]  # again, after the index has turned over

        def streams(srv):
            out = []
            for again in range(2 if features == "tiering" else 1):
                reqs = [srv.submit(p, max_new_tokens=6, seed=i)
                        for i, p in enumerate(prompts)]
                srv.run()
                out += [list(r.tokens) for r in reqs]
                if features == "tiering":
                    # every indexed page down to the host tier; the second
                    # round then restores what it shares with the first
                    srv.prefix_cache.evict(keep=0)
                    srv.tiering.flush()
            if srv.prefix_cache is not None:
                srv.release_prefix_cache()
            srv.check_no_leaks()
            return out

        plain = inference_engine.serve(cfg)
        assert plain.k_pool.ndim == 5
        want = streams(plain)

        monkeypatch.setattr(kv_cache, "pool_stored_shape", self._split)
        stored = inference_engine.serve(cfg)
        assert stored.k_pool.ndim == 6 and stored.k_pool.shape[2] == 4
        assert streams(stored) == want
        if features == "tiering":  # the host tier worked, and alike
            tiers = [srv.tiering for srv in (plain, stored)]
            for t in tiers:
                assert t.spills > 0 and t.restores > 0 and t.restore_misses == 0
            assert len({(t.spills, t.restores) for t in tiers}) == 1
        if not stored.disaggregated:  # there the hand-off's timing picks the pages
            np.testing.assert_array_equal(
                np.asarray(kv_cache.viewed(stored.decode_set.cache).k),
                np.asarray(plain.k_pool),
            )


class TestMidFlightAdmission:
    def test_queued_requests_fill_vacated_slots(self, tiny_cfg, inference_engine, shared_srv):
        """More requests than slots: finished sequences vacate mid-flight and
        queued requests are prefill-inserted without a fresh compile."""
        srv = shared_srv
        base_prefills = srv.metrics.counter("serving_prefills_total").value()
        rs = np.random.RandomState(5)
        reqs = []
        for i in range(6):
            plen = int(rs.randint(1, 13))
            n = 6  # same decode budget: references reuse compiled executables
            prompt = rs.randint(0, tiny_cfg.vocab_size, (plen,)).astype(np.int32)
            reqs.append((prompt, n, srv.submit(prompt, max_new_tokens=n, seed=i)))
        # after one step at most max_slots of 6 can have run
        srv.step()
        assert sum(1 for s in srv.slots if s.request is not None) <= srv.max_slots
        assert len(srv.queue) == 6 - srv.max_slots
        srv.run()
        assert srv.metrics.counter("serving_prefills_total").value() == base_prefills + 6
        assert len(srv.executables) == 2
        for prompt, n, req in reqs:
            ref = np.asarray(
                inference_engine.generate(prompt[None, :], max_new_tokens=n)
            )[0]
            np.testing.assert_array_equal(req.output, ref)
        srv.check_no_leaks()

    def test_page_budget_gates_admission(self, tiny_cfg, inference_engine):
        """A pool sized for ~one max request forces serial admission, but the
        stream still drains correctly (token-budget backpressure)."""
        # one request of 12+6=18 tokens needs 5 pages; the pool has 11 usable
        # so a third request must wait for pages even with two slots FREE —
        # pages, not slots, gate here
        srv = inference_engine.serve(dict(SERVING_CFG, num_pages=12))
        rs = np.random.RandomState(9)
        reqs = []
        for i in range(3):
            prompt = rs.randint(0, tiny_cfg.vocab_size, (12,)).astype(np.int32)
            reqs.append((prompt, srv.submit(prompt, max_new_tokens=6, seed=i)))
        srv.step()
        # 5 pages per request, 11 free: only two admitted although 4 slots exist
        assert sum(1 for s in srv.slots if s.request is not None) == 2
        assert any(s.request is None for s in srv.slots)  # gated by pages, not slots
        srv.run()
        for prompt, req in reqs:
            assert req.status == RequestStatus.FINISHED
            ref = np.asarray(
                inference_engine.generate(prompt[None, :], max_new_tokens=6)
            )[0]
            np.testing.assert_array_equal(req.output, ref)
        srv.check_no_leaks()


class TestAdmissionControl:
    def test_queue_depth_backpressure(self, inference_engine):
        srv = inference_engine.serve(dict(SERVING_CFG, max_queue_depth=2))
        p = np.arange(4, dtype=np.int32)
        r1 = srv.submit(p)
        r2 = srv.submit(p)
        r3 = srv.submit(p)
        assert r1.status == RequestStatus.QUEUED
        assert r2.status == RequestStatus.QUEUED
        assert r3.status == RequestStatus.REJECTED
        assert "queue full" in r3.detail
        assert srv.metrics.counter(
            "serving_requests_total", labelnames=("status",)
        ).value(status="rejected") == 1

    def test_oversize_prompt_rejected(self, inference_engine):
        srv = inference_engine.serve(SERVING_CFG)
        r = srv.submit(np.zeros(40, np.int32))  # max_prompt_len = 12
        assert r.status == RequestStatus.REJECTED

    def test_overlong_ask_degrades_to_truncated(self, tiny_cfg, inference_engine, shared_srv):
        """An over-long max_new_tokens is clamped at the door and the response
        marked TRUNCATED — never wedges, never over-allocates."""
        srv = shared_srv
        prompt = np.arange(5, dtype=np.int32) % tiny_cfg.vocab_size
        req = srv.submit(prompt, max_new_tokens=10**6)
        assert req.requested_new_tokens == 10**6
        assert req.max_new_tokens == SERVING_CFG["max_new_tokens"]
        srv.run()
        assert req.status == RequestStatus.TRUNCATED
        assert len(req.tokens) == SERVING_CFG["max_new_tokens"]
        srv.check_no_leaks()


class TestTimeoutEviction:
    def test_midflight_deadline_truncates_without_wedging(
        self, tiny_cfg, inference_engine, shared_srv
    ):
        """A slow/stuck request past its deadline is evicted mid-flight with a
        partial response; its co-batched neighbor completes bit-identically."""
        clock = FakeClock()
        srv = shared_srv
        old_clock, srv.clock = srv.clock, clock
        rs = np.random.RandomState(13)
        p_slow = rs.randint(0, tiny_cfg.vocab_size, (6,)).astype(np.int32)
        p_ok = rs.randint(0, tiny_cfg.vocab_size, (9,)).astype(np.int32)
        r_slow = srv.submit(p_slow, max_new_tokens=8, deadline_s=5.0)
        r_ok = srv.submit(p_ok, max_new_tokens=8)
        srv.step()  # both admitted, 2 tokens each (prefill + 1 decode)
        srv.step()
        clock.t = 10.0  # past r_slow's deadline
        srv.run()
        assert r_slow.status == RequestStatus.TRUNCATED
        assert 0 < len(r_slow.tokens) < 8  # partial output, not empty
        assert r_ok.status == RequestStatus.FINISHED
        ref = np.asarray(
            inference_engine.generate(p_ok[None, :], max_new_tokens=8)
        )[0]
        np.testing.assert_array_equal(r_ok.output, ref)
        # the truncated prefix still matches the sequential reference
        ref_slow = np.asarray(
            inference_engine.generate(p_slow[None, :], max_new_tokens=8)
        )[0, 6:]
        np.testing.assert_array_equal(r_slow.tokens, ref_slow[: len(r_slow.tokens)])
        assert srv.metrics.counter("serving_timeout_evictions_total").value() == 1
        srv.check_no_leaks()
        srv.clock = old_clock

    def test_queued_deadline_times_out_before_admission(self, inference_engine, shared_srv):
        clock = FakeClock()
        srv = shared_srv
        old_clock, srv.clock = srv.clock, clock
        try:
            p = np.arange(4, dtype=np.int32)
            # fill every slot so the deadline request has to queue
            running = [srv.submit(p, max_new_tokens=8) for _ in range(srv.max_slots)]
            r_wait = srv.submit(p, max_new_tokens=8, deadline_s=1.0)
            srv.step()  # the running requests take all slots
            clock.t = 2.0
            srv.run()
            assert all(r.status == RequestStatus.FINISHED for r in running)
            assert r_wait.status == RequestStatus.TIMED_OUT
            assert r_wait.tokens == []
            srv.check_no_leaks()
        finally:
            srv.clock = old_clock


class TestBucketedGenerate:
    def test_bucketing_collapses_compiles_and_keeps_tokens(self, tiny_cfg):
        """ISSUE 3 satellite: prompt lengths 5..8 share ONE compiled
        executable (pow2 bucket 8) and outputs stay bit-identical to the
        unbucketed gpt2.generate."""
        from deepspeed_tpu.inference.engine import InferenceEngine

        params = gpt2.init_params(tiny_cfg, jax.random.PRNGKey(1))
        eng = InferenceEngine(
            gpt2.make_module(tiny_cfg), params=params, dtype=jnp.float32
        )
        rs = np.random.RandomState(17)
        for S in (5, 8):
            ids = rs.randint(0, tiny_cfg.vocab_size, (2, S)).astype(np.int32)
            out = eng.generate(ids, max_new_tokens=4)
            ref = gpt2.generate(
                tiny_cfg, params, jnp.asarray(ids), 4, cache_dtype=jnp.float32
            )
            np.testing.assert_array_equal(out[:, S:], np.asarray(ref))
        assert len(eng._generate_cache) == 1  # one bucket, one executable

    def test_explicit_buckets_and_disable(self, tiny_cfg):
        from deepspeed_tpu.inference.engine import InferenceEngine

        params = gpt2.init_params(tiny_cfg, jax.random.PRNGKey(1))
        eng = InferenceEngine(
            gpt2.make_module(tiny_cfg), params=params, dtype=jnp.float32,
            config={"prompt_bucket_sizes": [6, 12]},
        )
        for S in (3, 6):
            eng.generate(
                np.zeros((1, S), np.int32) + S, max_new_tokens=2
            )
        assert len(eng._generate_cache) == 1  # all land in the 6 bucket
        off = InferenceEngine(
            gpt2.make_module(tiny_cfg), params=params, dtype=jnp.float32,
            config={"prompt_bucket_sizes": []},
        )
        for S in (3, 5):
            off.generate(np.zeros((1, S), np.int32) + S, max_new_tokens=2)
        assert len(off._generate_cache) == 2  # legacy: one per length


class TestServingConfig:
    def test_config_section_roundtrip(self):
        from deepspeed_tpu.runtime.config import DeepSpeedConfig, ServingConfig

        cfg = DeepSpeedConfig.load(
            {
                "train_micro_batch_size_per_gpu": 1,
                "serving": {"enabled": True, "max_slots": 16, "page_size": 32},
            }
        )
        assert cfg.serving.enabled and cfg.serving.max_slots == 16
        with pytest.raises(Exception):
            ServingConfig(page_size=0)

    def test_pool_too_small_raises(self, inference_engine):
        with pytest.raises(ValueError, match="num_pages"):
            inference_engine.serve(dict(SERVING_CFG, num_pages=3))

    def test_non_gpt2_model_rejected(self):
        from deepspeed_tpu.models import bert
        from deepspeed_tpu.inference.engine import InferenceEngine

        cfg = bert.get_config("bert-tiny")
        params = bert.init_params(cfg, jax.random.PRNGKey(0))
        eng = InferenceEngine(
            bert.make_module(cfg), params=params, dtype=jnp.float32
        )
        with pytest.raises(ValueError, match="gpt2 family"):
            eng.serve(SERVING_CFG)


# ---------------------------------------------------------------------------
# ISSUE 10: speculative decode + shared-prefix KV reuse + chunked prefill
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def spec_srv(inference_engine):
    """All ISSUE-10 features on: speculation (k=3), prefix cache, chunking."""
    return inference_engine.serve(dict(
        SERVING_CFG,
        speculative={"enabled": True, "k": 3},
        prefix_cache={"enabled": True},
        prefill_chunk_tokens=4,
    ))


class TestRefcountedAllocator:
    def test_retain_free_roundtrip(self):
        a = PageAllocator(16)
        pages = a.alloc(3)
        a.retain(pages)
        assert a.pages_shared == 3
        assert all(a.refcount(p) == 2 for p in pages)
        a.free(pages)  # drops to 1 — still in use
        assert a.pages_in_use == 3 and a.free_pages == 12
        assert a.pages_shared == 0
        a.free(pages)  # last holder: returns to the free list
        a.check_no_leaks()
        assert a.free_pages == 15

    def test_free_below_zero_and_retain_free_raise(self):
        a = PageAllocator(8)
        pages = a.alloc(1)
        a.free(pages)
        with pytest.raises(PageAllocatorError, match="double free"):
            a.free(pages)
        with pytest.raises(PageAllocatorError, match="retain of free"):
            a.retain(pages)
        with pytest.raises(PageAllocatorError):
            a.retain([0])  # scratch is never retainable

    def test_leak_check_with_allowed_refcounts(self):
        a = PageAllocator(16)
        pages = a.alloc(2)
        with pytest.raises(PageAllocatorError, match="leaked"):
            a.check_no_leaks()
        a.check_no_leaks(allowed=pages)  # refcount exactly 1 each: fine
        a.retain([pages[0]])
        # an allowed page with a second (unaccounted) reference is a leak
        with pytest.raises(PageAllocatorError, match="refcount"):
            a.check_no_leaks(allowed=pages)


class TestPrefixCacheIndex:
    def test_insert_lookup_probe_chain(self):
        a = PageAllocator(32)
        pc = PrefixCache(a, page_size=4)
        prompt = np.arange(12, dtype=np.int32)
        pages = a.alloc(3)
        assert pc.insert(prompt, pages) == 3
        assert all(a.refcount(p) == 2 for p in pages)
        # page-aligned full match: 2 mappable pages + the last page as COW
        shared, ntok, cow = pc.lookup(prompt)
        assert shared == pages[:2] and ntok == 8 and cow == pages[2]
        assert pc.hits_full == 1
        # diverging third page: partial, no COW
        p2 = np.concatenate([prompt[:8], np.array([99, 98, 97], np.int32)])
        shared, ntok, cow = pc.lookup(p2)
        assert shared == pages[:2] and ntok == 8 and cow is None
        assert pc.hits_partial == 1
        # probe never mutates counters
        before = (pc.hits_full, pc.hits_partial, pc.misses)
        assert pc.probe(prompt) == 2
        assert (pc.hits_full, pc.hits_partial, pc.misses) == before

    def test_lookup_never_shares_the_last_token(self):
        a = PageAllocator(32)
        pc = PrefixCache(a, page_size=4)
        prompt = np.arange(8, dtype=np.int32)
        pc.insert(prompt, a.alloc(2))
        # a 5-token prompt sharing page 0 only: token 5 must stay in the tail
        shared, ntok, cow = pc.lookup(prompt[:5])
        assert ntok == 4 and cow is None

    def test_leaf_first_eviction_keeps_chains_reachable(self):
        a = PageAllocator(32)
        pc = PrefixCache(a, page_size=4)
        prompt = np.arange(12, dtype=np.int32)
        pages = a.alloc(3)
        pc.insert(prompt, pages)
        a.free(pages)  # only the index holds them now
        assert pc.evict(keep=2) == 1
        # the LEAF (page 3 of the chain) went first; the root chain survives
        shared, ntok, _ = pc.lookup(prompt)
        assert ntok == 8
        pc.clear()
        a.check_no_leaks()


class TestDraftIndex:
    """The incremental ngram→position drafter must reproduce the brute-force
    backward scan EXACTLY — the committed bench's accept-length distribution
    depends on the drafts, and the index is the per-step O(appended) hot-path
    replacement for an O(context) rescan."""

    K, N = 4, 2

    @staticmethod
    def _scan_draft(ctx, k, n):
        last = ctx[-1]
        if len(ctx) >= n + 1:
            tgt = ctx[len(ctx) - n:]
            for s in range(len(ctx) - n - 1, -1, -1):
                if ctx[s:s + n] == tgt:
                    return ((ctx[s + n:s + n + k] + [last] * k)[:k])
        return [last] * k

    def _shim(self):
        import types
        from deepspeed_tpu.serving.scheduler import ServingEngine
        shim = types.SimpleNamespace(spec_k=self.K, spec_ngram=self.N)
        return lambda req: ServingEngine._draft(shim, req)

    def test_incremental_matches_scan_as_stream_grows(self):
        from deepspeed_tpu.serving.request import Request
        draft = self._shim()
        rs = np.random.RandomState(0)
        # small vocab so repeats (and therefore non-trivial lookups) are common
        req = Request(
            prompt=rs.randint(0, 7, (23,)).astype(np.int32), max_new_tokens=64
        )
        for _ in range(60):
            got = [int(t) for t in draft(req)]
            assert got == self._scan_draft(
                req.prompt_list + req.tokens, self.K, self.N
            )
            req.tokens.append(int(rs.randint(0, 7)))

    def test_retry_rewind_rebuilds_index(self):
        from deepspeed_tpu.serving.request import Request
        draft = self._shim()
        rs = np.random.RandomState(1)
        req = Request(
            prompt=rs.randint(0, 5, (9,)).astype(np.int32), max_new_tokens=64
        )
        for _ in range(12):
            draft(req)
            req.tokens.append(int(rs.randint(0, 5)))
        # transient-failure retry: generation restarts from scratch
        # (_fail_slot resets tokens and drops the drafter state)
        req.tokens = []
        object.__setattr__(req, "_draft_state", None)
        for _ in range(12):
            got = [int(t) for t in draft(req)]
            assert got == self._scan_draft(
                req.prompt_list + req.tokens, self.K, self.N
            )
            req.tokens.append(int(rs.randint(0, 5)))

    def test_length_guard_alone_recovers_from_rewind(self):
        # even WITHOUT the explicit state reset, a shrunk context (rewind)
        # must trigger a rebuild via the length guard
        from deepspeed_tpu.serving.request import Request
        draft = self._shim()
        rs = np.random.RandomState(2)
        req = Request(
            prompt=rs.randint(0, 5, (9,)).astype(np.int32), max_new_tokens=64
        )
        for _ in range(10):
            draft(req)
            req.tokens.append(int(rs.randint(0, 5)))
        req.tokens = []
        got = [int(t) for t in draft(req)]
        assert got == self._scan_draft(req.prompt_list, self.K, self.N)


class TestSpeculativeDecode:
    def test_spec_greedy_bit_identical_mixed_stream(
        self, tiny_cfg, inference_engine, spec_srv
    ):
        """The ISSUE 10 acceptance pin: ≥16 mixed-length requests through a
        speculative + prefix-cached + chunked engine are BIT-identical to
        per-request sequential generate, with the feature-derived
        executable count and zero leaks."""
        srv = spec_srv
        rs = np.random.RandomState(7)
        plens = [2, 5, 8, 12, 7, 3, 11, 4] * 2
        reqs = []
        for i in range(16):
            plen = plens[i]
            n = 6 if i % 7 else (1, 3, 8)[i // 7]
            prompt = rs.randint(0, tiny_cfg.vocab_size, (plen,)).astype(np.int32)
            reqs.append((prompt, n, srv.submit(prompt, max_new_tokens=n, seed=i)))
        done = srv.run()
        assert len(done) == 16
        # verify + chunk-prefill: the verify step REPLACES decode, and an
        # engine that chunks its cold prompts builds no whole-prompt program
        assert len(srv.executables) == 2
        assert srv.expected_executables == 2
        for prompt, n, req in reqs:
            assert req.status == RequestStatus.FINISHED
            assert len(req.tokens) == n
            ref = np.asarray(
                inference_engine.generate(prompt[None, :], max_new_tokens=n)
            )[0]
            np.testing.assert_array_equal(req.output, ref)
        srv.check_no_leaks()
        st = srv.stats()
        # speculation actually sped the batch up: steps < tokens emitted
        assert st["spec_steps"] > 0
        assert st["spec_accept_len_mean"] is not None
        total_tokens = sum(len(r.tokens) for _, _, r in reqs)
        assert st["spec_accepted"] + st["spec_steps"] * 1 <= total_tokens + 16

    def test_accepted_drafts_advance_multiple_tokens(
        self, tiny_cfg, inference_engine
    ):
        """Greedy decode of the tiny model loops, so prompt-lookup drafts
        must accept > 1 token/step on average — the mechanism, not just the
        equality, is pinned."""
        srv = inference_engine.serve(dict(
            SERVING_CFG, speculative={"enabled": True, "k": 3}
        ))
        rs = np.random.RandomState(11)
        prompt = rs.randint(0, tiny_cfg.vocab_size, (6,)).astype(np.int32)
        req = srv.submit(prompt, max_new_tokens=8, seed=0)
        srv.run()
        ref = np.asarray(
            inference_engine.generate(prompt[None, :], max_new_tokens=8)
        )[0]
        np.testing.assert_array_equal(req.output, ref)
        st = srv.stats()
        assert st["spec_steps"] < 8  # sequential would take 8 decode steps
        assert st["spec_accept_len_mean"] > 1.0
        srv.check_no_leaks()

    def test_eos_inside_accepted_run_stops_exactly_at_eos(
        self, tiny_cfg, inference_engine, spec_srv
    ):
        rs = np.random.RandomState(13)
        prompt = rs.randint(0, tiny_cfg.vocab_size, (6,)).astype(np.int32)
        ref = np.asarray(
            inference_engine.generate(prompt[None, :], max_new_tokens=8)
        )[0, 6:]
        eos = int(ref[2])
        stop_at = int(np.where(ref == eos)[0][0]) + 1
        req = spec_srv.submit(prompt, max_new_tokens=8, eos_token_id=eos)
        spec_srv.run()
        assert req.status == RequestStatus.FINISHED
        assert req.tokens == ref[:stop_at].tolist()
        spec_srv.check_no_leaks()

    def test_speculative_rejects_sampling(self, inference_engine):
        from deepspeed_tpu.runtime.config import DeepSpeedConfigError

        with pytest.raises(DeepSpeedConfigError, match="greedy"):
            inference_engine.serve(dict(
                SERVING_CFG, temperature=0.8,
                speculative={"enabled": True},
            ))


class TestPrefixCacheServing:
    def test_prefix_hit_identical_tokens_fewer_prefilled_pages(
        self, tiny_cfg, inference_engine
    ):
        """Second submission of a prompt maps its indexed pages instead of
        re-prefilling them: identical tokens, strictly fewer newly
        allocated pages, hit + reuse counters firing."""
        srv = inference_engine.serve(dict(
            SERVING_CFG, prefix_cache={"enabled": True}
        ))
        rs = np.random.RandomState(21)
        prompt = rs.randint(0, tiny_cfg.vocab_size, (11,)).astype(np.int32)
        total = pages_for(11 + 6, srv.page_size)
        r1 = srv.submit(prompt, max_new_tokens=6, seed=0)
        srv.run()
        pages_after_first = srv.allocator.pages_in_use  # index-held prompt pages
        r2 = srv.submit(prompt, max_new_tokens=6, seed=0)
        srv.step()  # r2 admitted: shared pages mapped, not re-allocated
        newly_allocated = srv.allocator.pages_in_use - pages_after_first
        assert newly_allocated == total - 2  # 2 of 3 prompt pages shared
        assert r2.prefix_shared_tokens == 8
        srv.run()
        np.testing.assert_array_equal(r1.output, r2.output)
        ref = np.asarray(
            inference_engine.generate(prompt[None, :], max_new_tokens=6)
        )[0]
        np.testing.assert_array_equal(r2.output, ref)
        st = srv.stats()
        assert st["prefix_hits_partial"] == 1 and st["prefix_misses"] == 1
        assert srv.metrics.counter(
            "serving_prefix_pages_reused_total"
        ).value() == 2
        srv.check_no_leaks()
        srv.release_prefix_cache()
        srv.allocator.check_no_leaks()

    def test_concurrent_sharing_and_divergent_tails_are_isolated(
        self, tiny_cfg, inference_engine
    ):
        """Requests sharing a prefix mid-flight hold refcounted pages; a
        request with a DIVERGENT tail past the shared pages never corrupts
        its neighbors' streams."""
        srv = inference_engine.serve(dict(
            SERVING_CFG, prefix_cache={"enabled": True}
        ))
        rs = np.random.RandomState(23)
        base = rs.randint(0, tiny_cfg.vocab_size, (12,)).astype(np.int32)
        divergent = base.copy()
        divergent[9:] = (divergent[9:] + 7) % tiny_cfg.vocab_size
        r0 = srv.submit(base, max_new_tokens=6, seed=0)
        srv.run()
        # warm index; now share + diverge concurrently
        ra = srv.submit(base, max_new_tokens=6, seed=0)
        rb = srv.submit(divergent, max_new_tokens=6, seed=0)
        srv.step()
        assert srv.allocator.pages_shared > 0  # shared while resident
        srv.run()
        for req, prompt in ((r0, base), (ra, base), (rb, divergent)):
            ref = np.asarray(
                inference_engine.generate(prompt[None, :], max_new_tokens=6)
            )[0]
            np.testing.assert_array_equal(req.output, ref)
        assert ra.prefix_shared_tokens > 0
        assert rb.prefix_shared_tokens == 8  # shares 2 pages, diverges in page 3
        srv.check_no_leaks()

    def test_cow_fork_on_full_prefix_hit(self, tiny_cfg, inference_engine):
        """A page-aligned full-prefix hit forks the last prompt page
        copy-on-write: the resubmission decodes correctly, the ORIGINAL
        indexed page stays pristine (a third submission still hits and
        matches), and the fork counter fires."""
        srv = inference_engine.serve(dict(
            SERVING_CFG, prefix_cache={"enabled": True}
        ))
        rs = np.random.RandomState(29)
        prompt = rs.randint(0, tiny_cfg.vocab_size, (12,)).astype(np.int32)
        ref = np.asarray(
            inference_engine.generate(prompt[None, :], max_new_tokens=6)
        )[0]
        r1 = srv.submit(prompt, max_new_tokens=6, seed=0)
        srv.run()
        r2 = srv.submit(prompt, max_new_tokens=6, seed=0)
        srv.run()
        r3 = srv.submit(prompt, max_new_tokens=6, seed=0)
        srv.run()
        assert not r1.cow_forked and r2.cow_forked and r3.cow_forked
        assert srv.allocator.cow_forks_total == 2
        assert srv.metrics.counter("serving_kv_cow_forks_total").value() == 2
        for r in (r1, r2, r3):
            np.testing.assert_array_equal(r.output, ref)
        st = srv.stats()
        assert st["prefix_hits_full"] == 2
        srv.check_no_leaks()

    def test_eviction_and_preemption_of_sharing_slots_leak_free(
        self, tiny_cfg, inference_engine
    ):
        """Deadline-evict one of two prefix-sharing in-flight requests,
        drain the other: every page is either free or exactly index-held,
        and releasing the index leaves the allocator pristine."""
        clock = FakeClock()
        srv = inference_engine.serve(
            dict(SERVING_CFG, prefix_cache={"enabled": True})
        )
        srv.clock = clock
        rs = np.random.RandomState(31)
        prompt = rs.randint(0, tiny_cfg.vocab_size, (12,)).astype(np.int32)
        warm = srv.submit(prompt, max_new_tokens=6, seed=0)
        srv.run()
        assert warm.status == RequestStatus.FINISHED
        r_doomed = srv.submit(prompt, max_new_tokens=8, deadline_s=5.0)
        r_ok = srv.submit(prompt, max_new_tokens=8)
        srv.step()
        assert srv.allocator.pages_shared > 0
        clock.t = 10.0  # r_doomed's deadline passes mid-flight
        srv.run()
        assert r_doomed.status == RequestStatus.TRUNCATED
        assert r_ok.status == RequestStatus.FINISHED
        srv.check_no_leaks()  # index refs allowed, slots all clear
        drained = srv.drain()
        assert not drained["deadline_hit"]
        released = srv.release_prefix_cache()
        assert released > 0
        srv.allocator.check_no_leaks()

    def test_index_yields_pages_under_pool_pressure(
        self, tiny_cfg, inference_engine
    ):
        """A cold request that cannot fit beside the index evicts cold
        entries (LRU leaves) instead of head-of-line blocking."""
        # pool of 15 usable pages; one 12+6-token request = 5 pages
        srv = inference_engine.serve(dict(
            SERVING_CFG, num_pages=16, prefix_cache={"enabled": True}
        ))
        rs = np.random.RandomState(37)
        p1 = rs.randint(0, tiny_cfg.vocab_size, (12,)).astype(np.int32)
        p2 = rs.randint(0, tiny_cfg.vocab_size, (12,)).astype(np.int32)
        p3 = rs.randint(0, tiny_cfg.vocab_size, (12,)).astype(np.int32)
        for p in (p1, p2, p3):
            srv.submit(p, max_new_tokens=6, seed=0)
            srv.run()
        held_before = len(srv.prefix_cache)
        assert held_before > 0
        # three fresh cold prompts at once: 15 pages needed, index must yield
        rs2 = np.random.RandomState(41)
        reqs = [
            srv.submit(
                rs2.randint(0, tiny_cfg.vocab_size, (12,)).astype(np.int32),
                max_new_tokens=6, seed=i,
            )
            for i in range(3)
        ]
        srv.run()
        assert all(r.status == RequestStatus.FINISHED for r in reqs)
        assert srv.prefix_cache.evictions > 0
        srv.check_no_leaks()

    def test_pressure_eviction_is_bounded_not_total(self):
        """evict(need_free=n) frees only what pool pressure demands — one
        starved admission must not dump the whole index."""
        a = PageAllocator(8)  # 7 usable
        pc = PrefixCache(a, page_size=4)
        pages = a.alloc(3)
        pc.insert(np.arange(12, dtype=np.int32), pages)
        a.free(pages)  # only the index holds them; free_pages == 4
        evicted = pc.evict(need_free=5)
        assert evicted == 1 and a.free_pages == 5
        assert len(pc) == 2  # the rest of the chain survives
        pc.clear()
        a.check_no_leaks()

    def test_eviction_of_probed_pages_never_crashes_admission(
        self, tiny_cfg, inference_engine
    ):
        """The probe/evict race: pool pressure evicts the very index pages
        the admission gate counted as mappable. The gate must re-probe —
        pre-fix this raised PageAllocatorError out of step() with the
        request already dequeued."""
        srv = inference_engine.serve(dict(
            SERVING_CFG, num_pages=10, prefix_cache={"enabled": True}
        ))
        rs = np.random.RandomState(61)
        prompt_a = rs.randint(0, tiny_cfg.vocab_size, (12,)).astype(np.int32)
        prompt_b = rs.randint(0, tiny_cfg.vocab_size, (12,)).astype(np.int32)
        warm = srv.submit(prompt_a, max_new_tokens=2, seed=0)
        srv.run()  # index now holds A's 3 prompt pages
        assert warm.status == RequestStatus.FINISHED
        rb = srv.submit(prompt_b, max_new_tokens=8, seed=0)
        srv.step()  # B resident: 5 pages; free = 9 - 3 - 5 = 1
        ra = srv.submit(prompt_a, max_new_tokens=8, seed=0)
        srv.run()  # must not raise; A' admits once B drains
        assert ra.status == RequestStatus.FINISHED
        assert rb.status == RequestStatus.FINISHED
        assert srv.prefix_cache.evictions >= 1
        for req, prompt in ((ra, prompt_a), (rb, prompt_b)):
            ref = np.asarray(
                inference_engine.generate(prompt[None, :], max_new_tokens=8)
            )[0]
            np.testing.assert_array_equal(req.output, ref)
        srv.check_no_leaks()

    def test_single_page_prompt_reports_no_phantom_cow(
        self, tiny_cfg, inference_engine
    ):
        """A one-page prompt has nothing to reuse (the tail IS the prompt):
        resubmission must not count a COW fork or a full hit."""
        srv = inference_engine.serve(dict(
            SERVING_CFG, prefix_cache={"enabled": True}
        ))
        prompt = np.arange(4, dtype=np.int32)
        r1 = srv.submit(prompt, max_new_tokens=3, seed=0)
        srv.run()
        r2 = srv.submit(prompt, max_new_tokens=3, seed=0)
        srv.run()
        assert not r2.cow_forked
        assert srv.allocator.cow_forks_total == 0
        st = srv.stats()
        assert st["prefix_hits_full"] == 0
        np.testing.assert_array_equal(r1.output, r2.output)
        srv.check_no_leaks()

    def test_max_pages_caps_the_index(self, tiny_cfg, inference_engine):
        srv = inference_engine.serve(dict(
            SERVING_CFG, prefix_cache={"enabled": True, "max_pages": 2}
        ))
        rs = np.random.RandomState(43)
        for i in range(3):
            p = rs.randint(0, tiny_cfg.vocab_size, (12,)).astype(np.int32)
            srv.submit(p, max_new_tokens=6, seed=i)
            srv.run()
        assert len(srv.prefix_cache) <= 2
        srv.check_no_leaks()


class TestChunkedPrefill:
    def test_chunked_cold_prompt_tokens_identical(
        self, tiny_cfg, inference_engine
    ):
        srv = inference_engine.serve(dict(SERVING_CFG, prefill_chunk_tokens=4))
        rs = np.random.RandomState(47)
        for plen in (12, 9, 3):
            prompt = rs.randint(0, tiny_cfg.vocab_size, (plen,)).astype(np.int32)
            req = srv.submit(prompt, max_new_tokens=6, seed=0)
            srv.run()
            ref = np.asarray(
                inference_engine.generate(prompt[None, :], max_new_tokens=6)
            )[0]
            np.testing.assert_array_equal(req.output, ref)
        # 12 and 9 in 3 chunks each, 3 as ONE chunk: no prompt's length selects a program
        assert srv.metrics.counter("serving_chunk_prefills_total").value() == 7
        assert srv.metrics.counter("serving_prefills_total").value() == 3
        assert srv._prefill_exec is None
        srv.check_no_leaks()

    def test_chunked_prefill_does_not_stall_decode(
        self, tiny_cfg, inference_engine
    ):
        """TPOT invariance: while a long prompt pays out its prefill one
        chunk per step, a co-resident decode slot advances one token EVERY
        step — the long prompt never freezes its neighbor's cadence."""
        srv = inference_engine.serve(dict(SERVING_CFG, prefill_chunk_tokens=4))
        rs = np.random.RandomState(53)
        short = rs.randint(0, tiny_cfg.vocab_size, (3,)).astype(np.int32)
        long_p = rs.randint(0, tiny_cfg.vocab_size, (12,)).astype(np.int32)
        r_short = srv.submit(short, max_new_tokens=8, seed=0)
        srv.step()  # short admitted (whole prefill: 3 < chunk) + 1 decode
        base_tokens = len(r_short.tokens)
        r_long = srv.submit(long_p, max_new_tokens=6, seed=0)
        srv.step()  # admits r_long: chunk 1 of 3 AND the neighbor's decode
        assert any(s.prefilling for s in srv.slots if s.request is not None)
        assert len(r_short.tokens) == base_tokens + 1
        steps_during_prefill = 1
        while any(s.prefilling for s in srv.slots if s.request is not None):
            before = len(r_short.tokens)
            srv.step()
            steps_during_prefill += 1
            if r_short.status != RequestStatus.FINISHED:
                # every prefill-chunk step also decoded the neighbor
                assert len(r_short.tokens) == before + 1
        assert steps_during_prefill == 3  # 12-token prompt, 4-token chunks
        srv.run()
        for req, prompt in ((r_short, short), (r_long, long_p)):
            ref = np.asarray(
                inference_engine.generate(
                    prompt[None, :], max_new_tokens=req.max_new_tokens
                )
            )[0]
            np.testing.assert_array_equal(req.output, ref)
        srv.check_no_leaks()

    def test_chunked_prefill_timeout_eviction_mid_prefill(
        self, tiny_cfg, inference_engine
    ):
        """A deadline that expires while a slot is still PREFILLING reclaims
        its pages without it ever joining the decode batch."""
        clock = FakeClock()
        srv = inference_engine.serve(dict(SERVING_CFG, prefill_chunk_tokens=4))
        srv.clock = clock
        rs = np.random.RandomState(59)
        prompt = rs.randint(0, tiny_cfg.vocab_size, (12,)).astype(np.int32)
        req = srv.submit(prompt, max_new_tokens=6, deadline_s=1.0)
        srv.step()  # admitted, first chunk in flight
        clock.t = 5.0
        srv.run()
        assert req.status == RequestStatus.TRUNCATED
        assert req.tokens == []  # never produced a first token
        srv.check_no_leaks()


class TickingClock:
    """Fake clock that advances a fixed delta on every read — decode steps
    get a nonzero measured latency without real sleeping."""

    def __init__(self, dt=0.05):
        self.t = 0.0
        self.dt = dt

    def __call__(self):
        t, self.t = self.t, self.t + self.dt
        return t


class TestServingStats:
    def test_stats_quantiles_with_fake_clock(self, inference_engine):
        """ISSUE 5 satellite: p50/p95/p99 TTFT/TPOT summaries from the
        existing histograms, surfaced as registry gauges for the textfile
        export."""
        srv = inference_engine.serve(SERVING_CFG)
        srv.clock = TickingClock(0.05)
        rs = np.random.RandomState(7)
        for i in range(6):
            p = rs.randint(0, 512, (4 + i,)).astype(np.int32)
            srv.submit(p, max_new_tokens=4, seed=i)
        srv.run()
        srv.check_no_leaks()
        st = srv.stats()
        for name in ("ttft", "tpot", "decode_step"):
            entry = st[name]
            assert entry["count"] > 0
            assert entry["p50_s"] is not None
            assert entry["p50_s"] <= entry["p95_s"] <= entry["p99_s"]
        assert st["completed"] == 6 and st["active_slots"] == 0
        # the quantile gauges back the telemetry textfile export
        g = srv.metrics.get("serving_latency_quantile_seconds")
        assert g is not None
        assert g.value(metric="ttft", q="p50") == st["ttft"]["p50_s"]
        prom = srv.metrics.to_prometheus()
        assert "serving_latency_quantile_seconds" in prom

    def test_program_census_gauges_and_phase_attrs(self, inference_engine):
        """ISSUE 29: ``_ensure_compiled`` leaves, per compiled program, the
        count of pool-layer-sized copies / slices / transposes in its HLO
        and its temp bytes, as two gauges and as attrs of the
        ``ds.init.programs`` phase. (The count is only 0 where the kernels
        run and the pool is stored for them: on a TPU, ``-m tpu``.)"""
        from deepspeed_tpu.serving.placement import pool_relayout_ops
        from deepspeed_tpu.telemetry import spans

        srv = inference_engine.serve(dict(SERVING_CFG, prefill_chunk_tokens=4))
        t0 = spans._clock()
        names = [name for name, _ in srv.executable_names()]
        assert names == ["serving_decode", "serving_chunk_prefill"]
        ph = [r for r in spans.phases(since=t0) if r[0] == "ds.init.programs"]
        assert len(ph) == 1
        attrs = ph[0][3]
        for key, gauge in (("relayout_ops", "serving_pool_relayout_ops"),
                           ("temp_bytes", "serving_program_temp_bytes"),
                           ("grid_steps", "serving_paged_grid_steps")):
            got = dict(kv.split("=") for kv in attrs[key].split())
            assert sorted(got) == sorted(names)
            g = srv.metrics.get(gauge)
            for name in names:
                assert g.value(program=name) == int(got[name])
        rec = srv._program_info["serving_decode"]
        census = srv.decode_set.program_census("serving_decode", rec["exe"])
        assert census[:2] == (
            srv.metrics.get("serving_pool_relayout_ops").value(
                program="serving_decode"),
            srv.metrics.get("serving_program_temp_bytes").value(
                program="serving_decode"),
        )
        # ISSUE 61: and the whole weight leaves it copies for another order
        # (none of gpt2-tiny's holds the megabyte the census starts at)
        assert census[2:] == (0, 0)
        got = dict(kv.split("=") for kv in attrs["weight_relayout"].split())
        assert got == {name: "0/0" for name in names}
        for name in names:
            assert srv.metrics.get("serving_weight_relayout_bytes").value(program=name) == 0
        assert srv.k_pool.ndim == 5  # stored as it is viewed off the TPU
        # the count itself, on HLO as the TPU compiler prints it
        hlo = """
  %copy.1 = bf16[4,512,25,16,64]{4,3,2,1,0:T(8,128)(2,1)} copy(%p)
  %slice.7 = bf16[1,512,25,16,64]{1,4,3,2,0:T(8,128)(2,1)S(1)} slice(%p), slice={[0:1]}
  %copy.2 = bf16[50257,1600]{1,0:T(8,128)(2,1)} copy(%wte)
  %dus = bf16[4,512,25,16,64]{4,3,2,1,0} dynamic-update-slice(%p, %u, %i)
  ROOT %transpose.3 = bf16[512,25,64,16]{3,2,1,0} transpose(%x), dimensions={0,1,3,2}
  %copy.9 = bf16[25,16,64]{2,1,0} copy(%page)
"""
        assert pool_relayout_ops(hlo, 512 * 25 * 16 * 64) == 3

    @pytest.mark.parametrize("case", ["relayout_around_a_kernel", "moved_pool",
                                      "program_layout_differs"])
    def test_a_pool_out_of_its_layout_fails_at_set_up(
        self, inference_engine, monkeypatch, case
    ):
        """ISSUE 29: a pool that is not where the paged kernels read it, or a
        program that does not keep it there, raises at set-up and is not
        re-laid out silently in every call."""
        from types import SimpleNamespace as NS

        from deepspeed_tpu.ops.pallas import decode_attention
        from deepspeed_tpu.serving.kv_cache import PoolLayoutError

        srv = inference_engine.serve(SERVING_CFG)
        pset = srv.decode_set
        if case == "relayout_around_a_kernel":
            srv._ensure_compiled()
            exe = srv._program_info["serving_decode"]["exe"]
            assert pset.program_census("serving_decode", exe)[0] > 0  # jnp: counted
            with_kernel = NS(
                as_text=lambda: exe.as_text() + '\ncustom_call_target="tpu_custom_call"',
                memory_analysis=exe.memory_analysis,
            )
            with pytest.raises(PoolLayoutError, match="serving_decode: .* around its kernels"):
                pset.program_census("serving_decode", with_kernel)
        elif case == "moved_pool":
            pset._check_pool_layout()  # off the TPU nothing is asked
            monkeypatch.setattr(decode_attention, "paged_page_ok", lambda *a: True)
            pset._check_pool_layout()  # row-major, as the CPU lays everything
            moved = NS(layout=NS(major_to_minor=(0, 2, 3, 4, 1)))
            pset.cache = pset.cache._replace(k=NS(
                format=moved, dtype=pset.cache.k.dtype, ndim=5, shape=pset.cache.k.shape))
            with pytest.raises(PoolLayoutError, match="num_pages"):
                pset._check_pool_layout()
        else:
            real = pset.placement.aot

            def aot(*a):
                exe = real(*a)
                took, kw = exe.input_formats
                other = NS(layout="pages-minor")
                return NS(input_formats=((took[0]._replace(k=other),) + took[1:], kw),
                          output_formats=exe.output_formats)

            monkeypatch.setattr(pset.placement, "aot", aot)
            with pytest.raises(PoolLayoutError, match="takes a float32"):
                pset.aot(lambda cache, i: (cache, i), (jnp.zeros((), jnp.int32),))

    @pytest.mark.parametrize("num_pages, axes", [
        (24, (24,)), (64, (64,)), (65, (5, 13)), (256, (4, 64)), (512, (8, 64)),
        (1000, (20, 50)), (8192, (2, 64, 64)), (509, (509,)), (508, (127, 4)),
    ])
    def test_page_axes_of_a_stored_pool(self, num_pages, axes):
        from deepspeed_tpu.serving.kv_cache import _page_axes

        assert _page_axes(num_pages) == axes and np.prod(axes) == num_pages

    def test_straggler_detection_with_fake_clock(self, inference_engine):
        """ISSUE 5 watchdog: a request resident in its slot far beyond the
        straggler budget is flagged exactly once."""
        from deepspeed_tpu.runtime.config import WatchdogConfig
        from deepspeed_tpu.telemetry.watchdog import AnomalyWatchdog

        srv = inference_engine.serve(SERVING_CFG)
        clock = TickingClock(0.05)
        srv.clock = clock
        srv.watchdog = AnomalyWatchdog(
            WatchdogConfig(enabled=True, straggler_factor=2.0)
        )
        p = np.arange(6, dtype=np.int32)
        req = srv.submit(p, max_new_tokens=8)
        srv.step()  # admit + first decode (EMA step time learned)
        srv.step()
        assert srv.metrics.counter("serving_stragglers_total").value() == 0
        # a step is in flight between two calls: read it before the clock
        # jumps, or the jump is that step's latency and moves the budget
        srv.settle()
        clock.t += 1000.0  # the request now looks wedged in its slot
        srv.step()
        assert srv.metrics.counter("serving_stragglers_total").value() == 1
        anoms = [a for a in srv.watchdog.anomalies
                 if a["anomaly_kind"] == "straggler"]
        assert len(anoms) == 1 and f"request_{req.id}" == anoms[0]["signal"]
        srv.step()  # flagged once, not every step
        assert srv.metrics.counter("serving_stragglers_total").value() == 1
        srv.run()
        srv.check_no_leaks()


# ---------------------------------------------------------------------------
# ISSUE 54: one step in flight while the host reads the step before it
# ---------------------------------------------------------------------------

AHEAD_FAMILIES = ("gpt2", "exaone_moe", "mistral4", "longcat_flash", "phi4flash", "zaya", "qwen3_next")
AHEAD_SERVING = dict(max_slots=3, page_size=4, num_pages=96, max_prompt_len=40, max_new_tokens=12,
                     prefill_chunk_tokens=8, temperature=0.0, retry_max=1, retry_backoff_s=0.0)


@pytest.fixture(scope="module")
def family_engines(inference_engine, tiny_cfg):
    """family -> (engine, vocabulary) at the tiny configuration its own test
    file serves, each built once a module."""
    import deepspeed_tpu

    made = {}

    def get(family):
        if family in made:
            return made[family]
        if family == "gpt2":
            made[family] = (inference_engine, tiny_cfg.vocab_size)
            return made[family]
        import importlib

        mod = importlib.import_module(f"deepspeed_tpu.models.{family}")
        if family == "zaya":
            from .test_zaya import CFG, seeded

            mcfg = mod.ZayaConfig.from_dict(CFG)
            eng = deepspeed_tpu.init_inference(
                model=mod.make_module(mcfg), dtype=jnp.float32, params=seeded(mcfg, 3))
        else:
            test = "exaone" if family == "exaone_moe" else family
            CFG = importlib.import_module(f"tests.unit.test_serving_{test}").CFG
            cls = next(v for k, v in vars(mod).items() if k.endswith("Config") and hasattr(v, "from_dict")
                       and k.lower().startswith(family.replace("_", "")[:4]))
            eng = deepspeed_tpu.init_inference(
                model=mod.make_module(cls.from_dict(CFG)), dtype=jnp.float32, seed=3)
        made[family] = (eng, int(CFG["vocab_size"]))
        return made[family]

    return get


def _ahead_srv(engine, ahead=True, clock=None, **over):
    """A server of the family; ``ahead=False``: the same loop held to depth 0,
    which launches, reads and emits a step in one call: the parent's order."""
    cfg = dict(AHEAD_SERVING, **over)
    if engine.model_config.__class__.__name__ == "GPT2Config":
        cfg.setdefault("kv_cache_dtype", "float32")
    srv = engine.serve(cfg, **({"clock": clock} if clock is not None else {}))
    if not ahead:
        srv._ahead_ok = False
    return srv


def _ahead_prompts(vocab, lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, n).astype(np.int32) for n in lens]


def _play(srv, plan, after=None):
    """Submit ``plan``'s requests at their calls of ``step`` — ``(call,
    prompt, submit's keywords)`` each — and step until nothing is left.
    ``after(call)`` runs behind each call. → the requests in plan order."""
    reqs, call = {}, 0
    while True:
        for k, (at, prompt, kw) in enumerate(plan):
            if at == call:
                reqs[k] = srv.submit(prompt, **kw)
        if len(reqs) == len(plan) and not srv.queue and all(s.request is None for s in srv.slots):
            break
        srv.step()
        if after is not None:
            after(call)
        call += 1
        assert call < 400
    srv.settle()
    return [reqs[k] for k in range(len(plan))]


def _same_service(got, want):
    for a, b in zip(got, want):
        assert list(a.tokens) == list(b.tokens)
        assert a.status == b.status and len(a.t_emissions) == len(a.tokens)


def _span_attrs(t0, name):
    from deepspeed_tpu.telemetry import spans

    return [r[3] for r in spans.snapshot(since=t0) if r[0] == name]


def _span_clock():
    from deepspeed_tpu.telemetry import spans

    return spans._clock()


class TestStepAhead:
    @pytest.mark.parametrize("temperature", [0.0, 0.8], ids=["greedy", "sampled"])
    @pytest.mark.parametrize("family", AHEAD_FAMILIES + ("gpt2-whole",))
    def test_staggered_requests_are_served_what_the_synchronous_order_serves(
        self, family_engines, family, temperature
    ):
        """(a) Prompts that end in ONE chunk, their first and last, with
        nothing in flight (5), in a last chunk that rides (20), in a last
        chunk alone (27: the second slot prefilling) and in one chunk behind a
        step in flight (7), staggered over the calls: tokens, status and
        stamps a token equal the loop held to depth 0, and gpt2's equal
        ``generate``'s. ``gpt2-whole``: an engine that chunks no cold prompt,
        so each of the four is a whole prefill (ISSUE 63: only such an engine
        has the whole-prompt program), the first with nothing in flight, the
        others behind a step."""
        over = {"temperature": temperature}
        if family == "gpt2-whole":
            family, over["prefill_chunk_tokens"] = "gpt2", 0
        engine, vocab = family_engines(family)
        prompts = _ahead_prompts(vocab, (5, 20, 27, 7), seed=2)
        plan = [(0, prompts[0], dict(max_new_tokens=9, seed=0)), (2, prompts[1], dict(max_new_tokens=12, seed=1)),
                (2, prompts[2], dict(max_new_tokens=12, seed=2)), (4, prompts[3], dict(max_new_tokens=6, seed=3))]
        t0 = _span_clock()
        srv = _ahead_srv(engine, **over)
        got = _play(srv, plan)
        waits = len(_span_attrs(t0, "ds.serve.prefill.wait")), len(_span_attrs(t0, "ds.serve.chunk.wait"))
        launches = _span_attrs(t0, "ds.serve.decode.dispatch")
        want = _play(_ahead_srv(engine, ahead=False, **over), plan)
        _same_service(got, want)
        assert all(r.status == RequestStatus.FINISHED for r in got)
        # only the empty server's first prompt (one chunk; a whole prefill where nothing chunks) waited where it
        # was launched: the other first tokens were left on their slots (a last chunk alone, a whole prefill) or
        # in the step their chunk rode
        assert waits == ((0, 1) if srv._chunk_cold else (1, 0))
        kinds = {a["kind"] for a in _span_attrs(t0, "ds.serve.launch")}
        assert kinds == ({"chunk", "mixed"} if srv._chunk_cold else {"prefill"})
        st = srv.stats()
        assert st["steps_ahead"] == sum(d["ahead"] for d in launches) >= len(launches) - 2
        assert st["rows_dropped"] == 0
        assert sum(d["active"] for d in launches) == sum(len(r.tokens) - 1 for r in got)   # no row but a token's
        srv.check_no_leaks()
        if family == "gpt2" and temperature == 0.0:
            for p, r in zip(prompts, got):
                ref = np.asarray(engine.generate(p[None, :], max_new_tokens=len(r.tokens)))[0]
                np.testing.assert_array_equal(r.output, ref)

    @pytest.mark.parametrize("family", AHEAD_FAMILIES)
    def test_a_step_is_launched_before_the_step_before_it_is_read(self, family_engines, family, monkeypatch):
        """(b) With the executables and ``jax.device_get`` instrumented: step
        n+1's launch precedes the fetch of step n, what is fetched IS an
        output of the step program (no slice of it, which would be a program
        more, queued behind the step launched ahead), a call holds at most one
        token fetch, and the program set is what it was."""
        from deepspeed_tpu.serving import scheduler as sched

        engine, vocab = family_engines(family)
        srv = _ahead_srv(engine)
        assert len(srv.executable_names()) == srv.expected_executables == 2     # the step program and the chunk program
        log = []

        def spy(name):
            exe = getattr(srv, name)

            def call(*a):
                out = exe(*a)
                # a step: the decode program, or the chunk program with a decode row (params, cache, tokens,
                # lengths, block tables, ...: a call that rides nothing takes the idle table's)
                log.append(("launch", name == "_decode_exec" or bool(np.asarray(a[4]).any()), out))
                return out

            setattr(srv, name, call)

        for name in ("_decode_exec", "_chunk_exec"):
            spy(name)
        get = jax.device_get
        monkeypatch.setattr(sched.jax, "device_get", lambda x: log.append(("fetch", x)) or get(x))
        prompts = _ahead_prompts(vocab, (5, 20), seed=4)
        srv.submit(prompts[0], max_new_tokens=12, seed=0)
        calls = []
        for call in range(14):
            if call == 3:
                srv.submit(prompts[1], max_new_tokens=8, seed=1)
            mark = len(log)
            srv.step()
            calls.append(log[mark:])
        steps = [e for e in log if e[0] == "launch" and e[1]]
        step_at = {id(e): n for n, e in enumerate(steps)}
        read = 0
        for k, events in enumerate(calls):
            kinds = [e[0] for e in events]
            if k == 0:
                # the empty server: its prompt's one chunk waits where it is launched, then two steps go out
                # and one is read
                assert kinds == ["launch", "fetch", "launch", "launch", "fetch"] and not events[0][1]
                events, kinds = events[2:], kinds[2:]
            assert kinds.count("fetch") <= 1
            if "fetch" not in kinds:
                continue
            at = kinds.index("fetch")
            ahead = [step_at[id(e)] for e in events[:at] if id(e) in step_at]
            # the fetch of step n follows the launch of step n+1 in the same call (but where n was the last)
            assert ahead[-1:] == [read + 1] or read + 1 == len(steps)
            assert kinds[at + 1:].count("launch") == 0
            fetched = jax.tree_util.tree_leaves(events[at][1])
            assert any(fetched[0] is leaf for leaf in jax.tree_util.tree_leaves(steps[read][2]))
            read += 1
        assert read >= 12
        srv.run()
        srv.check_no_leaks()
        assert len(srv.executables) == srv.expected_executables == 2

    @pytest.mark.parametrize("family", AHEAD_FAMILIES)
    def test_a_stop_by_count_launches_no_row_and_a_late_stop_drops_one(self, family_engines, family):
        """(c) A stop by count is known at the launch: the slot gets no row in
        the next one. An EOS in mid-request, an injected stall and a deadline
        are seen a step late: ``req.tokens``, the status and the freed pages
        are the synchronous order's, the row launched ahead is dropped and
        counted."""
        engine, vocab = family_engines(family)
        clocks = FakeClock(), FakeClock()
        srv = _ahead_srv(engine, clock=clocks[0])
        ref = _ahead_srv(engine, ahead=False, clock=clocks[1])
        p = _ahead_prompts(vocab, (5, 20, 6), seed=5)
        # by count: a lone request of 5 tokens is 4 rows in all, and nothing is dropped
        t0 = _span_clock()
        (r,) = _play(srv, [(0, p[0], dict(max_new_tokens=5, seed=0))])
        assert sum(d["active"] for d in _span_attrs(t0, "ds.serve.decode.dispatch")) == 4 == len(r.tokens) - 1
        assert srv.stats()["rows_dropped"] == 0
        (full,) = _play(ref, [(0, p[0], dict(max_new_tokens=12, seed=0))])
        # EOS: the first token from the fourth on that the request has not sampled before
        k = next(i for i in range(3, 12) if full.tokens[i] not in full.tokens[:i])
        plan = [(0, p[0], dict(max_new_tokens=12, seed=0, eos_token_id=full.tokens[k])),
                (1, p[1], dict(max_new_tokens=12, seed=1))]
        got, want = _play(srv, plan), _play(ref, plan)
        _same_service(got, want)
        assert list(got[0].tokens) == list(full.tokens[:k + 1]) and got[0].status == RequestStatus.FINISHED
        assert srv.stats()["rows_dropped"] == 1
        srv.check_no_leaks()
        # an injected stall in mid-decode: evicted, retried from scratch (into the slot it left, while the row
        # launched ahead for its first residency is still in flight)
        for s in (srv, ref):
            s.fault_injector = _StallOnce()
        got, want = (_play(s, [(0, p[2], dict(max_new_tokens=8, seed=2))]) for s in (srv, ref))
        _same_service(got, want)
        assert got[0].retries == 1 and len(got[0].tokens) == 8
        assert srv.stats()["rows_dropped"] == 2
        for s in (srv, ref):
            s.fault_injector = None
        # a deadline that passes in mid-decode, on a clock both servers read alike
        def late(s, clock):
            clock.t = 0.0
            return _play(s, [(0, p[0], dict(max_new_tokens=12, seed=0, deadline_s=5.0)),
                             (0, p[2], dict(max_new_tokens=12, seed=1))],
                         after=lambda call: setattr(clock, "t", 10.0) if call == 3 else None)

        got, want = late(srv, clocks[0]), late(ref, clocks[1])
        _same_service(got, want)
        assert got[0].status == RequestStatus.TRUNCATED and 0 < len(got[0].tokens) < 12
        assert srv.stats()["rows_dropped"] == 3
        for s in (srv, ref):
            s.check_no_leaks()
            assert s.allocator.pages_in_use == 0

    @pytest.mark.parametrize("family", AHEAD_FAMILIES)
    def test_a_drain_and_a_release_with_a_step_in_flight_leak_no_page_and_lose_no_token(
        self, family_engines, family
    ):
        """(d) ``release_slot``, ``check_no_leaks`` and ``drain(0.0)`` with a
        step in flight: every request keeps a prefix of what it is served
        whole, a stamp a token; nothing is emitted for a released slot after
        its release; no page leaks."""
        engine, vocab = family_engines(family)
        prompts = _ahead_prompts(vocab, (5, 20, 6), seed=6)
        plan = [(0, prompts[0], dict(max_new_tokens=12, seed=0)), (1, prompts[1], dict(max_new_tokens=12, seed=1)),
                (1, prompts[2], dict(max_new_tokens=12, seed=2))]
        whole = _play(_ahead_srv(engine, ahead=False), plan)
        srv = _ahead_srv(engine)
        reqs = [srv.submit(plan[0][1], **plan[0][2])]
        srv.step()
        reqs += [srv.submit(p, **kw) for _, p, kw in plan[1:]]
        for _ in range(4):
            srv.step()
        assert srv._flight is not None and srv._flight.rows
        i = next(i for i, s in enumerate(srv.slots) if s.request is reqs[0])
        n_before = len(reqs[0].tokens)
        assert srv.release_slot(i) is reqs[0] and reqs[0].status == RequestStatus.RUNNING
        dropped = srv.stats()["rows_dropped"]
        srv.step()                                # reads the step that held the released slot's row: dropped
        assert len(reqs[0].tokens) == n_before and srv.stats()["rows_dropped"] == dropped + 1
        srv.step()
        assert srv._flight is not None
        with pytest.raises(PageAllocatorError):
            srv.check_no_leaks()                  # slots are live; it read the step in flight first all the same
        assert srv._flight is None
        srv.step()
        assert srv._flight is not None
        n_mid = [len(r.tokens) for r in reqs]
        out = srv.drain(0.0)
        assert srv._flight is None and out["preempted"] == 2
        # the drain read the step in flight: one token more a live slot, none lost and none doubled
        assert [len(r.tokens) for r in reqs[1:]] == [n + 1 for n in n_mid[1:]]
        for r, w in zip(reqs, whole):
            assert list(r.tokens) == list(w.tokens[:len(r.tokens)]) and len(r.t_emissions) == len(r.tokens)
        assert [r.status for r in reqs[1:]] == [RequestStatus.PREEMPTED] * 2
        srv.check_no_leaks()
        assert srv.allocator.pages_in_use == 0

    @pytest.mark.parametrize("family", AHEAD_FAMILIES)
    def test_a_slot_sits_out_no_step_between_two_requests(self, family_engines, family):
        """A stop by count is known at the launch of a request's last row: its
        slot is handed to the queue's next request a call before that row is
        read, so a backlog takes the calls the synchronous order takes (and
        one more, to read the last step)."""
        engine, vocab = family_engines(family)
        # every prompt in chunks, so that each but the first two (a server with nothing to ride) rides to its end
        prompts = _ahead_prompts(vocab, (20, 13, 27, 20, 19, 30, 15, 18), seed=9)
        plan = [(0, p, dict(max_new_tokens=5 + k % 3, seed=k)) for k, p in enumerate(prompts)]

        def calls(srv):
            n = [0]
            reqs = _play(srv, plan, after=lambda call: n.__setitem__(0, call + 1))
            return n[0], reqs

        srv = _ahead_srv(engine, max_slots=2)
        n_ahead, got = calls(srv)
        n_sync, want = calls(_ahead_srv(engine, ahead=False, max_slots=2))
        _same_service(got, want)
        kept = _ahead_srv(engine, max_slots=2)
        kept._hand_on_spent_slot = lambda now: None     # a spent slot kept until its last row is read
        n_kept, same = calls(kept)
        _same_service(same, want)
        assert n_ahead <= n_sync + 1 < n_kept
        assert srv.stats()["rows_dropped"] == 0 and all(r.status == RequestStatus.FINISHED for r in got)
        srv.check_no_leaks()

    @pytest.mark.parametrize("temperature", [0.0, 0.8], ids=["greedy", "sampled"])
    def test_a_session_moves_with_a_step_in_flight(self, inference_engine, tiny_cfg, temperature):
        """(d) ``export_session`` / ``adopt_session``: the session that moves
        holds every token computed for it, and the peer goes on where the
        source stopped (the dispatched side of the adopted slot stands where
        its emitted side does: the next rows take the token in flight and the
        next keys), a step in flight on both sides and no row dropped."""
        prompts = _ahead_prompts(tiny_cfg.vocab_size, (5, 9), seed=7)
        kw = dict(prefill_chunk_tokens=0, temperature=temperature)
        plan = [(0, p, dict(max_new_tokens=12, seed=k)) for k, p in enumerate(prompts)]
        want = _play(_ahead_srv(inference_engine, ahead=False, **kw), plan)
        src, dst = (_ahead_srv(inference_engine, **kw) for _ in range(2))
        a = src.submit(prompts[0], max_new_tokens=12, seed=0)
        b = dst.submit(prompts[1], max_new_tokens=12, seed=1)
        for s in (src, dst):
            for _ in range(3):
                s.step()
            assert s._flight is not None
        n = len(a.tokens)
        state, arrays = src.export_session(0)
        assert src._flight is None and len(state["tokens"]) == n + 1 == len(a.tokens)
        assert state["step"] >= 2
        src.release_slot(0)
        assert dst.adopt_session(state, arrays, request=a) is a and dst._flight is not None
        t0 = _span_clock()
        dst.run()
        # every launch of the destination's but the last went out behind a step in flight
        launches = _span_attrs(t0, "ds.serve.decode.dispatch")
        assert sum(d["ahead"] for d in launches) >= len(launches) - 1
        assert dst.stats()["rows_dropped"] == 0 == src.stats()["rows_dropped"]
        for s in (src, dst):
            s.check_no_leaks()
        _same_service((a, b), want)
        for p, r in zip(prompts, (a, b)):
            assert r.status == RequestStatus.FINISHED and len(r.t_emissions) == 12
            if temperature:
                assert len(set(r.tokens[n:])) > 2     # a stale token fed or a key out of place would show
            else:
                ref = np.asarray(inference_engine.generate(p[None, :], max_new_tokens=12))[0]
                np.testing.assert_array_equal(r.output, ref)

    @pytest.mark.parametrize("over", [
        {"speculative": {"enabled": True, "k": 3, "ngram": 2}},
        {"placement": {"disaggregate": True}},
    ], ids=["speculation", "disaggregated"])
    def test_a_server_whose_rows_wait_for_its_tokens_launches_nothing_ahead(
        self, inference_engine, tiny_cfg, over
    ):
        """(e) A verify step's accepted count moves ``seq_lens`` and a
        handoff is polled between two steps: such a server reads every step
        in the call that launched it (``ahead`` 0 on every dispatch) and
        emits what ``generate`` does."""
        prompts = _ahead_prompts(tiny_cfg.vocab_size, (5, 20, 27, 7), seed=8)
        t0 = _span_clock()
        srv = _ahead_srv(inference_engine, **over)
        assert not srv._ahead_ok
        reqs = []
        for k, p in enumerate(prompts):
            reqs.append(srv.submit(p, max_new_tokens=10, seed=k))
            srv.step()
            assert srv._flight is None
        srv.run()
        launches = _span_attrs(t0, "ds.serve.decode.dispatch")
        assert launches and all(d["ahead"] == 0 for d in launches)
        assert srv.stats()["steps_ahead"] == 0 == srv.stats()["rows_dropped"]
        for p, r in zip(prompts, reqs):
            assert r.status == RequestStatus.FINISHED
            ref = np.asarray(inference_engine.generate(p[None, :], max_new_tokens=10))[0]
            np.testing.assert_array_equal(r.output, ref)
        srv.release_prefix_cache()
        srv.check_no_leaks()


class _StallOnce:
    """A fault injector whose first ``serving_stall`` fires."""

    def __init__(self):
        self.fired = False

    def fire(self, kind, ordinal):
        if kind == "serving_stall" and not self.fired:
            self.fired = True
            return True
        return False
