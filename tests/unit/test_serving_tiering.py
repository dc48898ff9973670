"""ISSUE 17: tiered KV cache — host-DRAM second tier for cold pages.

The acceptance pins:

- the 16-request mixed suite (speculative + prefix sharing + chunked
  prefill + int8 KV pages, and the TP=2 variant on the forced 8-device
  mesh) emits BIT-IDENTICAL token streams with tiering ON vs OFF, with the
  tier demonstrably engaged (spills AND restores observed);
- mid-load drain and SIGTERM leak zero pages across BOTH tiers: the
  allocator, the host store, and the heat ledger's cross-tier mirror all
  reconcile at quiescence;
- restore-under-pressure: demoted chains come back through the compiled
  ``serving_kv_restore`` program (restores > 0) with identical tokens;
- a corrupted host buffer is a COLD MISS, never silent corruption: the
  CRC check drops the entry, the prefix recomputes, streams stay
  identical;
- satellite 2: demotion's D event lands atomically BEFORE the device-side
  F/E pair (lockstep-fuzzed, seeded) — no trace prefix shows a page owned
  by neither tier;
- Engine G explores the tiered protocol completely with zero violations,
  the seeded ``drop-host-free`` mutation yields a minimal counterexample
  whose replay turns the REAL engine red;
- satellite 1: ``tools/kv_heat.py --policy`` agrees with the live tier on
  a recorded trace (exit 0) and rejects unknown policies (exit 2).
"""

import json
import signal
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import gpt2

warnings.filterwarnings("ignore")

pytestmark = pytest.mark.tiering

needs_8_devices = pytest.mark.skipif(
    jax.device_count() < 4, reason="needs the forced 8-device CPU mesh"
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
TIERED = {"tiering": {"enabled": True, "host_budget_pages": 64}}


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


def _mixed_requests(vocab, n=16, seed=7):
    rs = np.random.RandomState(seed)
    plens = [2, 5, 8, 12, 7, 3, 11, 4] * 2
    return [
        (rs.randint(0, vocab, (plens[i],)).astype(np.int32),
         6 if i % 7 else (1, 3, 8)[i // 7])
        for i in range(n)
    ]


def _streams(srv, reqs, seed0=0):
    subs = [
        srv.submit(p, max_new_tokens=n, seed=seed0 + i)
        for i, (p, n) in enumerate(reqs)
    ]
    srv.run()
    return [list(r.tokens) for r in subs]


def _demote_all(srv):
    """Force every index entry through the demotion path and wait for the
    spill worker to land the copies host-side."""
    srv.prefix_cache.evict(keep=0)
    srv.tiering.flush()


# ---------------------------------------------------------------------------
# config validation
# ---------------------------------------------------------------------------

class TestTieringConfig:
    def test_requires_prefix_cache(self, inference_engine):
        from deepspeed_tpu.runtime.config import DeepSpeedConfigError

        with pytest.raises(DeepSpeedConfigError, match="prefix_cache"):
            inference_engine.serve(dict(BASE, **TIERED))

    def test_unknown_policy_rejected(self, inference_engine):
        from deepspeed_tpu.runtime.config import DeepSpeedConfigError

        cfg = dict(BASE, prefix_cache={"enabled": True},
                   tiering={"enabled": True, "policy": "clairvoyant"})
        with pytest.raises(DeepSpeedConfigError, match="policy"):
            inference_engine.serve(cfg)

    def test_host_budget_auto_sizes_to_pool(self, inference_engine):
        cfg = dict(BASE, prefix_cache={"enabled": True},
                   tiering={"enabled": True})  # host_budget_pages=0 → auto
        srv = inference_engine.serve(cfg)
        assert srv.tiering.store.budget_pages == srv.allocator.capacity
        srv.tiering.close()


# ---------------------------------------------------------------------------
# HostPageStore unit behaviour
# ---------------------------------------------------------------------------

def _store(budget=4, quantized=False, crc=True):
    from deepspeed_tpu.serving.tiering import HostPageStore

    return HostPageStore(
        budget, n_layer=2, n_kv_head=1, page_size=4, head_dim=2,
        dtype=np.int8 if quantized else np.float32,
        quantized=quantized, crc=crc,
    )


class TestHostPageStore:
    def test_put_get_roundtrip_and_accounting(self):
        st = _store()
        k = np.arange(2 * 1 * 4 * 2, dtype=np.float32).reshape(2, 1, 4, 2)
        st.put(("a",), 3, k, k * 2)
        assert ("a",) in st and len(st) == 1
        got_k, got_v, got_s = st.get(("a",))
        assert np.array_equal(got_k, k) and np.array_equal(got_v, k * 2)
        assert got_s is None
        assert st.used_bytes() == st.page_bytes
        assert st.host_bytes() == st.page_bytes * st.budget_pages
        st.check_consistent()

    def test_crc_mismatch_is_a_cold_miss(self):
        st = _store()
        k = np.ones((2, 1, 4, 2), np.float32)
        st.put(("a",), 0, k, k)
        slot = st._entries[("a",)].slot
        st.k_codes[0, slot, 0, 0, 0] += 1.0  # bit-rot the host buffer
        assert st.get(("a",)) is None        # dropped, not returned corrupt
        assert st.crc_failures == 1
        assert ("a",) not in st              # entry retired on the spot
        st.check_consistent()

    def test_duplicate_key_and_full_store_raise(self):
        from deepspeed_tpu.serving.tiering import HostTierError

        st = _store(budget=2)
        k = np.zeros((2, 1, 4, 2), np.float32)
        st.put(("a",), 0, k, k)
        with pytest.raises(HostTierError, match="already holds"):
            st.reserve(("a",), 1)
        st.put(("b",), 1, k, k)
        with pytest.raises(HostTierError, match="full"):
            st.reserve(("c",), 2)

    def test_drop_lru_is_spill_order(self):
        st = _store(budget=3)
        k = np.zeros((2, 1, 4, 2), np.float32)
        for i, key in enumerate([("a",), ("b",), ("c",)]):
            st.put(key, i, k, k)
        key, _hid = st.drop_lru()
        assert key == ("a",)  # first spilled goes first
        st.check_consistent()

    def test_quantized_scale_sidecar_roundtrip(self):
        st = _store(quantized=True)
        k = np.full((2, 1, 4, 2), 7, np.int8)
        s = np.full((2, 1, 2), 0.5, np.float32)
        st.put(("q",), 0, k, k, s)
        _, _, got_s = st.get(("q",))
        assert np.array_equal(got_s, s)


# ---------------------------------------------------------------------------
# headline: bit-identical mixed suite, tiering ON vs OFF
# ---------------------------------------------------------------------------

class TestBitIdenticalMixedSuite:
    def test_mixed_suite_all_features_int8(self, tiny_cfg, inference_engine):
        """16-request mixed suite with speculation + prefix sharing +
        chunked prefill + int8 KV pages: tiering ON re-emits the OFF
        streams exactly, and a demote-everything + resubmit round proves
        the restore path carries the same bits."""
        cfg = dict(BASE, kv_cache_dtype="int8", **ALL_FEATURES)
        reqs = _mixed_requests(tiny_cfg.vocab_size)
        off = _streams(inference_engine.serve(cfg), reqs)

        srv = inference_engine.serve(dict(cfg, **TIERED))
        assert _streams(srv, reqs) == off
        # round 2: push every cached prefix to host, then replay the suite —
        # warm-from-host hits must still be bit-identical
        _demote_all(srv)
        assert srv.tiering.spills > 0
        assert _streams(srv, reqs, seed0=0) == off
        assert srv.tiering.restores > 0, "host tier never restored"
        assert srv.tiering.store.crc_failures == 0
        srv.drain()
        srv.release_prefix_cache()
        srv.check_no_leaks()

    @needs_8_devices
    def test_mixed_suite_tp2(self, tiny_cfg, inference_engine):
        cfg = dict(BASE, kv_cache_dtype="int8", **ALL_FEATURES)
        reqs = _mixed_requests(tiny_cfg.vocab_size)
        off = _streams(inference_engine.serve(cfg), reqs)
        srv = inference_engine.serve(
            dict(cfg, placement={"tp": 2}, **TIERED)
        )
        assert _streams(srv, reqs) == off
        _demote_all(srv)
        assert _streams(srv, reqs, seed0=0) == off
        assert srv.tiering.restores > 0
        srv.drain()
        srv.release_prefix_cache()
        srv.check_no_leaks()


# ---------------------------------------------------------------------------
# zero-leak drain / SIGTERM across tiers
# ---------------------------------------------------------------------------

class TestCrossTierDrain:
    def _tiered(self, inference_engine, **extra):
        cfg = dict(BASE, prefix_cache={"enabled": True}, **TIERED, **extra)
        return inference_engine.serve(cfg)

    def test_mid_load_drain_deadline_zero_leak_free(self, inference_engine):
        srv = self._tiered(inference_engine)
        rs = np.random.RandomState(3)
        # wave 1 runs to completion so the index holds sole references —
        # demotion only fires on index-last-reference pages
        for i in range(3):
            srv.submit(rs.randint(0, 50257, (8,)).astype(np.int32),
                       max_new_tokens=4, seed=i)
        srv.run()
        _demote_all(srv)
        assert len(srv.tiering.store) > 0
        # wave 2 is mid-flight when the zero-grace drain lands
        for i in range(6):
            srv.submit(rs.randint(0, 50257, (8,)).astype(np.int32),
                       max_new_tokens=8, seed=10 + i)
        for _ in range(3):
            srv.step()
        srv.drain(deadline_s=0.0)
        srv.release_prefix_cache()
        srv.check_no_leaks()  # asserts cross-tier consistency too

    def test_drain_reconciles_heat_ledger_across_tiers(
        self, inference_engine, tmp_path
    ):
        from deepspeed_tpu.telemetry.kv_heat import KVHeatTracer

        srv = self._tiered(inference_engine)
        tracer = KVHeatTracer(str(tmp_path / "heat.jsonl"))
        srv.attach_heat(tracer)
        rs = np.random.RandomState(4)
        prompts = [rs.randint(0, 50257, (8,)).astype(np.int32)
                   for _ in range(4)]
        for i, p in enumerate(prompts):
            srv.submit(p, max_new_tokens=4, seed=i)
        srv.run()
        _demote_all(srv)
        # resubmit one → restore traffic while the ledger watches
        srv.submit(prompts[0], max_new_tokens=2, seed=99)
        srv.run()
        led = srv._heat_prefill
        err = led.reconcile(
            srv.prefill_set.allocator, srv.prefix_cache,
            host_store=srv.tiering.store,
        )
        assert err is None, err
        srv.drain()
        srv.release_prefix_cache()
        srv.check_no_leaks()
        assert led.host_handles == srv.tiering.store.handles()
        srv.detach_heat()
        tracer.close()

    def test_sigterm_under_tiered_load_leak_free(self, inference_engine):
        from deepspeed_tpu.elasticity.preemption import PreemptionGuard
        from deepspeed_tpu.serving import RequestStatus

        srv = self._tiered(inference_engine)
        rs = np.random.RandomState(5)
        reqs = [
            srv.submit(rs.randint(0, 50257, (8,)).astype(np.int32),
                       max_new_tokens=6, seed=i)
            for i in range(5)
        ]
        with PreemptionGuard() as guard:
            steps = 0
            while srv.queue or any(s.request is not None for s in srv.slots):
                srv.step()
                steps += 1
                if steps == 2:
                    signal.raise_signal(signal.SIGTERM)
                if guard.should_stop():
                    srv.drain(deadline_s=30.0)
                    break
        assert all(r.done for r in reqs)
        assert {r.status for r in reqs} <= {
            RequestStatus.FINISHED, RequestStatus.PREEMPTED,
        }
        srv.release_prefix_cache()
        srv.check_no_leaks()


# ---------------------------------------------------------------------------
# restore under pressure + corrupt host buffers
# ---------------------------------------------------------------------------

class TestRestorePath:
    def test_restore_under_pool_pressure(self, inference_engine):
        """A deliberately tight pool (the spill pump and the admission
        relief valve both engage) with sessions resubmitted after demotion:
        restores fire and every stream matches the roomy-pool baseline."""
        roomy = dict(BASE, prefix_cache={"enabled": True})
        tight = dict(roomy, num_pages=24, **TIERED)
        rs = np.random.RandomState(11)
        prompts = [rs.randint(0, 50257, (12,)).astype(np.int32)
                   for _ in range(8)]
        reqs = [(p, 4) for p in prompts]

        base = _streams(inference_engine.serve(roomy), reqs)
        srv = inference_engine.serve(tight)
        assert _streams(srv, reqs) == base
        _demote_all(srv)
        assert _streams(srv, reqs, seed0=0) == base
        st = srv.tiering.stats()
        assert st["restores"] > 0
        assert st["crc_failures"] == 0
        srv.drain()
        srv.release_prefix_cache()
        srv.check_no_leaks()

    def test_corrupt_host_buffer_recomputes_cold(self, inference_engine):
        """Flip one byte of a spilled page: the CRC check turns the restore
        into a cold miss (counted), the prefix recomputes, and the tokens
        are STILL identical — corruption never reaches decode."""
        cfg = dict(BASE, prefix_cache={"enabled": True}, **TIERED)
        rs = np.random.RandomState(13)
        p = rs.randint(0, 50257, (12,)).astype(np.int32)

        srv = inference_engine.serve(cfg)
        r0 = srv.submit(p, max_new_tokens=6, seed=0)
        srv.run()
        _demote_all(srv)
        store = srv.tiering.store
        assert len(store) > 0
        # corrupt the chain ROOT — the first key the restore walk reads
        # (the deepest spilled leaf sits past the chain_keys cap)
        key = srv.prefix_cache.chain_keys(p)[0]
        assert key in store
        slot = store._entries[key].slot
        store.k_codes[0, slot, 0, 0, 0] += 1.0  # bit-rot
        r1 = srv.submit(p, max_new_tokens=6, seed=0)
        srv.run()
        assert list(r1.tokens) == list(r0.tokens)
        st = srv.tiering.stats()
        assert st["crc_failures"] >= 1
        assert st["restore_misses"] >= 1
        srv.drain()
        srv.release_prefix_cache()
        srv.check_no_leaks()

    def test_kv_restore_is_a_traced_wait_cause(self):
        from deepspeed_tpu.telemetry.request_trace import WAIT_CAUSES

        assert "kv_restore" in WAIT_CAUSES


# ---------------------------------------------------------------------------
# satellite 2: demotion ordering — D lands atomically before F/E
# ---------------------------------------------------------------------------

class _FakePSet:
    """Numpy stand-in for the device ProgramSet: enough surface for
    demote_begin's page-column reads."""

    def __init__(self, n_layer=2, pages=33, kv=1, page=2, d=2):
        self.k_pool = np.random.RandomState(0).rand(
            n_layer, pages, kv, page, d
        ).astype(np.float32)
        self.v_pool = self.k_pool * 2

    def page_column(self, pid):
        return self.k_pool[:, pid], self.v_pool[:, pid], None


class TestDemoteOrderingLockstep:
    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_lockstep_fuzz_d_before_f_e(self, seed):
        """Seeded random op walks over PageAllocator + PrefixCache with the
        tier wired as demote_sink: at EVERY step the heat ledger's
        cross-tier mirror reconciles bit-exact, and in the event stream
        each demotion's D record is immediately followed by its page's
        F then E — the atomic pair, no interleaving, so no trace prefix
        shows the page owned by neither tier."""
        from types import SimpleNamespace

        from deepspeed_tpu.serving.kv_cache import PageAllocator, PrefixCache
        from deepspeed_tpu.serving.tiering import HostPageStore, KVTieringEngine
        from deepspeed_tpu.telemetry.kv_heat import KVHeatLedger

        rs = np.random.RandomState(seed)
        page = 2
        alloc = PageAllocator(num_pages=33)
        cache = PrefixCache(alloc, page_size=page, max_pages=12)
        led = KVHeatLedger(
            "fuzz", alloc.capacity,
            sink=SimpleNamespace(
                _seal=lambda led: None,
                _observe_lifetime=lambda pool, dt: None,
            ),
            segment_events=1 << 30,  # keep every event in the buffer
        )
        alloc.heat = led
        cache.heat = led
        store = HostPageStore(8, n_layer=2, n_kv_head=1, page_size=page,
                              head_dim=2, dtype=np.float32)
        tier = KVTieringEngine(store, _FakePSet(page=page))
        tier.ledger = led
        cache.demote_sink = tier
        cache.victim_order = tier.select_leaf
        try:
            live = []
            for _ in range(150):
                op = rs.randint(3)
                if op == 0 and alloc.free_pages >= 8:  # admit + insert
                    plen = int(rs.randint(1, 5)) * page
                    prompt = rs.randint(0, 3, (plen,)).astype(np.int32)
                    shared, _s_tokens, _cow = cache.lookup(prompt)
                    if shared:
                        alloc.retain(shared)
                    total = plen // page + 1
                    priv = alloc.alloc(total - len(shared))
                    pages = shared + priv
                    cache.insert(prompt, pages[: plen // page])
                    live.append(pages)
                elif op == 1 and live:  # finish a request
                    alloc.free(live.pop(int(rs.randint(len(live)))))
                elif op == 2:  # pool-pressure eviction → demotion
                    cache.evict(need_free=int(rs.randint(0, 4)))
                tier.flush()
                assert led.reconcile(alloc, cache, host_store=store) is None
                store.check_consistent()
            for pages in live:
                alloc.free(pages)
            cache.clear()
            tier.flush()
            alloc.check_no_leaks()
            assert led.reconcile(alloc, cache, host_store=store) is None
            assert cache.demotions > 0, "fuzz never exercised demotion"

            # the ordering pin: every D is IMMEDIATELY followed by F then E
            # for the same page — demote-before-free, atomically
            evs = led._events
            d_seen = 0
            for i, ev in enumerate(evs):
                if ev[0] != "D":
                    continue
                d_seen += 1
                p = ev[2]
                assert evs[i + 1][0] == "F" and p in evs[i + 1][2], (
                    f"D({p}) not followed by its free: {evs[i:i + 3]}"
                )
                assert evs[i + 2][0] == "E" and evs[i + 2][2] == p, (
                    f"D({p}) free not paired with evict: {evs[i:i + 3]}"
                )
            assert d_seen == cache.demotions
        finally:
            tier.close()


# ---------------------------------------------------------------------------
# Engine G: third-tier model + drop-host-free mutation
# ---------------------------------------------------------------------------

TIERED_SCFG = {
    "max_slots": 2, "page_size": 4, "num_pages": 32,
    "max_prompt_len": 8, "max_new_tokens": 4,
    "prefix_cache": {"enabled": True},
    "tiering": {"enabled": True, "host_budget_pages": 8},
}


class TestEngineGTiered:
    def test_tiered_exploration_complete_and_clean(self):
        from deepspeed_tpu.analysis.protocol_model import (
            ProtoModelConfig, explore,
        )

        plain = explore(ProtoModelConfig())
        tiered = explore(ProtoModelConfig(tiering=True, host_budget=2))
        assert tiered.complete and tiered.ok, tiered.violations
        # the host dimension genuinely grows the state space
        assert tiered.states > plain.states

    def test_tiering_requires_prefix_cache_in_model(self):
        from deepspeed_tpu.analysis.protocol_model import ProtoModelConfig

        with pytest.raises(ValueError, match="prefix_cache"):
            ProtoModelConfig(tiering=True, prefix_cache=False)

    def test_drop_host_free_minimal_counterexample(self):
        from deepspeed_tpu.analysis.protocol_model import (
            ProtoModelConfig, explore,
        )

        rep = explore(ProtoModelConfig(
            tiering=True, host_budget=2,
            mutations=frozenset({"drop-host-free"}),
        ))
        bad = [v for v in rep.violations
               if v.rule == "proto-refcount-conservation"]
        assert bad, [v.rule for v in rep.violations]
        assert "demote_prefix" in bad[0].trace

    def test_counterexample_replays_red_on_real_engine(
        self, inference_engine
    ):
        from deepspeed_tpu.analysis.protocol_model import (
            ProtoModelConfig, apply_engine_mutation, explore, replay_trace,
        )

        rep = explore(ProtoModelConfig(
            tiering=True, host_budget=2,
            mutations=frozenset({"drop-host-free"}),
        ))
        trace = [v for v in rep.violations
                 if v.rule == "proto-refcount-conservation"][0].trace
        rs = np.random.RandomState(21)
        prompts = [rs.randint(0, 50257, (8,)).astype(np.int32)
                   for _ in range(2)]

        srv = inference_engine.serve(dict(TIERED_SCFG))
        clean = replay_trace(srv, trace, prompts, max_new_tokens=2)
        assert clean["ok"], clean["violations"]

        srv2 = inference_engine.serve(dict(TIERED_SCFG))
        undo = apply_engine_mutation(srv2, "drop-host-free")
        try:
            red = replay_trace(srv2, trace, prompts, max_new_tokens=2)
        finally:
            undo()
        assert not red["ok"], "engine twin of drop-host-free stayed green"

    def test_verify_runs_clean_with_tiering_on(self, inference_engine):
        srv = inference_engine.serve(dict(TIERED_SCFG))
        assert srv.verify() == []


# ---------------------------------------------------------------------------
# satellite 1: --policy cross-check (simulator vs live tier)
# ---------------------------------------------------------------------------

def _scripted_trace(path):
    """A small deterministic heat trace with enough churn that the spill
    policies actually diverge from 'never spilled anything'."""
    from deepspeed_tpu.telemetry.kv_heat import KVHeatLedger, KVHeatTracer

    class Clock:
        t = 0.0

        def __call__(self):
            return self.t

    clk = Clock()
    tr = KVHeatTracer(str(path), clock=clk, flush_interval=1)
    led = tr.pool("decode", 16, page_size=4, page_bytes=2048)
    led._clock = clk
    led.seed({}, set(), 0.0)
    for rid in range(4):
        pages = list(range(rid * 3, rid * 3 + 3))
        led.alloc(pages)
        led.session_start(clk.t, rid % 2, rid, f"t{rid % 2}", pages)
        for s in range(4):
            clk.t += 0.25
            led.touch_step(clk.t, s + 1, [(rid % 2, pages[-1], len(pages))])
        led.register(pages[:1])
        clk.t += 0.5
        led.free(pages[1:])
    tr.flush()
    tr.close()
    return str(path)


class TestPolicyCrosscheck:
    @pytest.mark.parametrize("policy",
                             ["idle_lru", "prefix_aware", "slot_priority"])
    def test_live_tier_agrees_with_simulator(self, tmp_path, policy, capsys):
        from deepspeed_tpu.tools.kv_heat import main

        trace = _scripted_trace(tmp_path / "heat.jsonl")
        rc = main([trace, "--pool", "decode", "--policy", policy,
                   "--resident-fraction", "0.3", "--json"])
        out = json.loads(capsys.readouterr().out)
        assert rc == 0, out
        assert out["mismatches"] == 0
        assert any(r["field"] == "spills" and r["predicted"] > 0
                   for r in out["rows"])

    def test_unknown_policy_exits_2(self, tmp_path):
        from deepspeed_tpu.tools.kv_heat import main

        trace = _scripted_trace(tmp_path / "heat.jsonl")
        assert main([trace, "--pool", "decode", "--policy", "oracle"]) == 2

    def test_replay_live_tier_matches_simulator_dict(self, tmp_path):
        from deepspeed_tpu.serving.tiering import replay_live_tier
        from deepspeed_tpu.telemetry.kv_heat import (
            evaluate_spill_policies, load_heat_records,
        )

        trace = _scripted_trace(tmp_path / "heat.jsonl")
        records = load_heat_records(trace)
        sim = evaluate_spill_policies(
            records, "decode", resident_fraction=0.3,
            policies=("idle_lru",),
        )["policies"]["idle_lru"]
        live = replay_live_tier(records, "decode", "idle_lru",
                                resident_fraction=0.3)
        for field in sim:
            assert live.get(field) == sim[field], (
                f"{field}: live {live.get(field)} != sim {sim[field]}"
            )


# ---------------------------------------------------------------------------
# stats / budgets surface
# ---------------------------------------------------------------------------

class TestStatsSurface:
    def test_stats_and_host_metadata_itemize_host_bytes(
        self, inference_engine
    ):
        cfg = dict(BASE, prefix_cache={"enabled": True}, **TIERED)
        rs = np.random.RandomState(31)
        srv = inference_engine.serve(cfg)
        srv.submit(rs.randint(0, 50257, (12,)).astype(np.int32),
                   max_new_tokens=4, seed=0)
        srv.run()
        _demote_all(srv)
        st = srv.stats()["kv_tiering"]
        assert st["enabled"] and st["spills"] > 0
        assert st["host_bytes"] == srv.tiering.store.host_bytes()
        meta = srv.host_metadata_breakdown()
        assert meta["kv_host_tier_bytes"] == st["host_bytes"]
        assert meta["total_bytes"] >= meta["kv_host_tier_bytes"]
        srv.drain()
        srv.release_prefix_cache()
        srv.check_no_leaks()

    def test_tiering_off_has_no_host_tier_bytes(self, inference_engine):
        srv = inference_engine.serve(dict(BASE))
        assert "kv_tiering" not in srv.stats()
        assert srv.host_metadata_breakdown()["kv_host_tier_bytes"] == 0
