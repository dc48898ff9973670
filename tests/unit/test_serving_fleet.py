"""ISSUE 18: serving fleet — multi-replica router with live migration.

The acceptance pins:

- the 16-request mixed suite (speculative + prefix sharing + chunked
  prefill + int8 KV pages + tiering) through a 2-replica fleet with a
  forced mid-stream preemption emits BIT-IDENTICAL token streams vs a
  single un-migrated engine, with at least one live session actually
  migrating, and zero leaked pages on EVERY replica's allocators;
- a SIGTERM delivered by the FaultInjector mid-decode drains the victim:
  every live session migrates (or restarts), every request finishes, and
  no replica leaks;
- a crc-corrupted migration payload is a COUNTED failure that re-queues
  the session (``fleet_migrations_total{status="crc_failed"}``) — the
  request still finishes, the fleet never wedges;
- satellite 1 (PR-17 edge): a host-tier entry whose parent chain link has
  left BOTH tiers is dropped eagerly (ledger V event) — pinned by a
  lockstep-fuzz seed with the reachability invariant checked per step and
  the D→F→E adjacency pin intact;
- Engine G explores the fleet protocol completely with zero violations;
  the seeded ``drop-migration-free`` mutation yields a minimal
  counterexample ending in ``replica_die`` that replays RED on a real
  mutated fleet (and green clean);
- satellite 2: ``tools/request_trace.py --by replica`` groups the
  terminal records by the replica stamp.
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

pytestmark = pytest.mark.fleet

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
    "kv_cache_dtype": "int8",
    "tiering": {"enabled": True, "host_budget_pages": 64},
}
FLEET2 = {"fleet": {"enabled": True, "replicas": 2}}


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


def _fleet(inference_engine, extra=None, **kw):
    from deepspeed_tpu.serving import FleetRouter

    cfg = dict(BASE, **ALL_FEATURES, **FLEET2)
    if extra:
        cfg.update(extra)
    return FleetRouter(inference_engine, cfg, **kw)


# ---------------------------------------------------------------------------
# config surface
# ---------------------------------------------------------------------------

class TestFleetConfig:
    def test_defaults_off_and_coercion(self):
        from deepspeed_tpu.runtime.config import ServingConfig

        cfg = ServingConfig()
        assert cfg.fleet.enabled is False
        cfg = ServingConfig(fleet={"enabled": True, "replicas": 3})
        assert cfg.fleet.replicas == 3 and cfg.fleet.policy == "affinity"

    @pytest.mark.parametrize("bad", [
        {"replicas": 0},
        {"policy": "hash_ring"},
        {"preempt_policy": "newest"},
        {"admit_attainment_floor": 1.5},
        {"min_slo_samples": 0},
    ])
    def test_validation_rejects(self, bad):
        from deepspeed_tpu.runtime.config import (
            DeepSpeedConfigError, FleetConfig,
        )

        with pytest.raises(DeepSpeedConfigError):
            FleetConfig(**bad)


# ---------------------------------------------------------------------------
# tentpole: bit-identity across a forced live migration
# ---------------------------------------------------------------------------

class TestMigrationBitIdentity:
    def test_16_request_suite_identical_after_migration(
        self, tiny_cfg, inference_engine
    ):
        reqs = _mixed_requests(tiny_cfg.vocab_size)

        # reference: one engine, nothing migrates
        srv = inference_engine.serve(dict(BASE, **ALL_FEATURES))
        ref_subs = [srv.submit(p, max_new_tokens=n, seed=i)
                    for i, (p, n) in enumerate(reqs)]
        srv.run()
        ref = [list(r.tokens) for r in ref_subs]
        srv.drain()
        srv.release_prefix_cache()
        srv.check_no_leaks()

        fleet = _fleet(inference_engine)
        try:
            subs = [fleet.submit(p, max_new_tokens=n, seed=i)
                    for i, (p, n) in enumerate(reqs)]
            # let decodes get mid-stream, then retire the loaded replica
            for _ in range(3):
                fleet.step()
            victim = max(fleet.alive(), key=type(fleet)._load)
            live = [
                s for s in victim.srv.slots
                if s.request is not None and not s.prefilling
                and s.request.tokens
            ]
            assert live, "preempt landed before any session went mid-stream"
            fleet.preempt(victim.rid)
            fleet.run()
            assert not fleet.replica(victim.rid).alive
            st = fleet.stats()["fleet"]
            assert st["migrations_ok"] >= 1, st
            got = [list(r.tokens) for r in subs]
            assert got == ref, [
                i for i, (a, b) in enumerate(zip(ref, got)) if a != b
            ]
            # a migrated request carries the destination replica stamp
            assert all(r.replica for r in subs)
            fleet.drain()
            fleet.check_no_leaks()  # every replica, dead one included
        finally:
            fleet.close()


# ---------------------------------------------------------------------------
# SIGTERM via the fault injector
# ---------------------------------------------------------------------------

class TestSigtermMigration:
    def test_injected_sigterm_mid_decode_migrates_and_finishes(
        self, tiny_cfg, inference_engine
    ):
        from deepspeed_tpu.resilience import FaultInjector
        from deepspeed_tpu.runtime.config import FaultInjectionConfig
        from deepspeed_tpu.serving import RequestStatus

        inj = FaultInjector(FaultInjectionConfig(
            enabled=True, sigterm_steps=[2],
        ))
        fleet = _fleet(
            inference_engine,
            extra={"fleet": {"enabled": True, "replicas": 2,
                             "install_sigterm": True}},
        )
        try:
            reqs = _mixed_requests(tiny_cfg.vocab_size, n=8)
            subs = [fleet.submit(p, max_new_tokens=n, seed=i)
                    for i, (p, n) in enumerate(reqs)]
            steps = 0
            while any(
                rep.srv.queue or any(s.request is not None
                                     for s in rep.srv.slots)
                for rep in fleet.alive()
            ) or fleet._pending_preemption():
                if inj.fire("sigterm", steps):
                    assert inj.deliver_sigterm(), "no SIGTERM handler"
                fleet.step()
                steps += 1
                assert steps < 2000
            assert inj.counts().get("sigterm") == 1
            assert len(fleet.alive()) == 1  # one replica retired
            assert all(r.done for r in subs)
            assert {r.status for r in subs} <= {
                RequestStatus.FINISHED, RequestStatus.PREEMPTED,
            }
            st = fleet.stats()["fleet"]
            assert st["migrations_ok"] + st["requeues"] >= 1
            fleet.drain()
            fleet.check_no_leaks()
        finally:
            prev = signal.getsignal(signal.SIGTERM)
            fleet.close()
            # close() must release the process-wide SIGTERM handler
            assert signal.getsignal(signal.SIGTERM) is not prev


# ---------------------------------------------------------------------------
# crc-corrupted migration payload: counted failure, request re-queues
# ---------------------------------------------------------------------------

class TestCorruptPayload:
    def test_crc_failure_requeues_never_wedges(
        self, tiny_cfg, inference_engine
    ):
        import glob
        import os

        fleet = _fleet(inference_engine)

        def corrupt(tag_dir, req):
            # flip one byte in the first array file AFTER the manifest
            # recorded its crc — validate_tag must now refuse the payload
            fname = sorted(glob.glob(os.path.join(tag_dir, "*.bin")))[0]
            with open(fname, "r+b") as fh:
                b = fh.read(1)
                fh.seek(0)
                fh.write(bytes([b[0] ^ 0xFF]))

        fleet.on_migration_payload = corrupt
        try:
            reqs = _mixed_requests(tiny_cfg.vocab_size, n=8)
            subs = [fleet.submit(p, max_new_tokens=n, seed=i)
                    for i, (p, n) in enumerate(reqs)]
            for _ in range(3):
                fleet.step()
            victim = max(fleet.alive(), key=type(fleet)._load)
            assert any(
                s.request is not None and s.request.tokens
                and not s.prefilling for s in victim.srv.slots
            )
            fleet.preempt(victim.rid)
            fleet.run()  # must terminate: corrupted sessions restart
            st = fleet.stats()["fleet"]
            assert st["migrations_crc_failed"] >= 1, st
            assert st["migrations_ok"] == 0
            assert st["requeues"] >= 1
            assert all(r.done for r in subs)
            fleet.drain()
            fleet.check_no_leaks()
        finally:
            fleet.close()


# ---------------------------------------------------------------------------
# satellite 1: orphaned host-tier entries drop eagerly (PR-17 edge)
# ---------------------------------------------------------------------------

class _FakePSet:
    """Numpy stand-in for the device ProgramSet (demote_begin's reads)."""

    def __init__(self, n_layer=2, pages=33, kv=1, page=2, d=2):
        self.k_pool = np.random.RandomState(0).rand(
            n_layer, pages, kv, page, d
        ).astype(np.float32)
        self.v_pool = self.k_pool * 2

    def page_column(self, pid):
        return self.k_pool[:, pid], self.v_pool[:, pid], None


class TestOrphanHostDrop:
    def _rig(self, seed):
        from types import SimpleNamespace

        from deepspeed_tpu.serving.kv_cache import PageAllocator, PrefixCache
        from deepspeed_tpu.serving.tiering import (
            HostPageStore, KVTieringEngine,
        )
        from deepspeed_tpu.telemetry.kv_heat import KVHeatLedger

        page = 2
        alloc = PageAllocator(num_pages=33)
        cache = PrefixCache(alloc, page_size=page, max_pages=12)
        led = KVHeatLedger(
            "fuzz", alloc.capacity,
            sink=SimpleNamespace(
                _seal=lambda led: None,
                _observe_lifetime=lambda pool, dt: None,
            ),
            segment_events=1 << 30,
        )
        alloc.heat = led
        cache.heat = led
        # a SMALL host budget: parents get LRU-dropped from the host tier
        # while still on device-evicted chains → their spilled children
        # become unreachable and must go too
        store = HostPageStore(4, n_layer=2, n_kv_head=1, page_size=page,
                              head_dim=2, dtype=np.float32)
        tier = KVTieringEngine(store, _FakePSet(page=page))
        tier.ledger = led
        tier.device_resident = cache._entries.__contains__
        cache.demote_sink = tier
        cache.victim_order = tier.select_leaf
        return alloc, cache, store, tier, led

    def _assert_reachable(self, cache, store, tier):
        """PR-17 edge invariant: every host entry's parent chain link is
        resident in SOME tier (device index or host store)."""
        for key in store._entries:
            parent = key[0] if isinstance(key, tuple) and key else None
            if not isinstance(parent, tuple):
                continue
            assert parent in store or parent in cache._entries, (
                f"host entry {key!r} orphaned: parent left both tiers"
            )

    @pytest.mark.parametrize("seed", [0, 3])
    def test_lockstep_fuzz_orphans_drop_eagerly(self, seed):
        alloc, cache, store, tier, led = self._rig(seed)
        rs = np.random.RandomState(seed)
        page = 2
        try:
            live = []
            for _ in range(200):
                op = rs.randint(3)
                if op == 0 and alloc.free_pages >= 8:
                    plen = int(rs.randint(1, 5)) * page
                    prompt = rs.randint(0, 3, (plen,)).astype(np.int32)
                    shared, _st, _cow = cache.lookup(prompt)
                    if shared:
                        alloc.retain(shared)
                    total = plen // page + 1
                    priv = alloc.alloc(total - len(shared))
                    pages = shared + priv
                    cache.insert(prompt, pages[: plen // page])
                    live.append(pages)
                elif op == 1 and live:
                    alloc.free(live.pop(int(rs.randint(len(live)))))
                elif op == 2:
                    cache.evict(need_free=int(rs.randint(0, 4)))
                tier.flush()
                self._assert_reachable(cache, store, tier)
                assert led.reconcile(alloc, cache, host_store=store) is None
                store.check_consistent()
            for pages in live:
                alloc.free(pages)
            cache.clear()
            tier.flush()
            alloc.check_no_leaks()
            assert cache.demotions > 0
            # the pinned seeds genuinely exercise the orphan path
            assert tier.orphan_drops > 0, tier.stats()
            assert tier.stats()["orphan_drops"] == tier.orphan_drops

            # the ISSUE-17 ordering pin survives: every D immediately
            # followed by its page's F then E — orphan V events never
            # split the atomic triple
            evs = led._events
            for i, ev in enumerate(evs):
                if ev[0] != "D":
                    continue
                p = ev[2]
                assert evs[i + 1][0] == "F" and p in evs[i + 1][2]
                assert evs[i + 2][0] == "E" and evs[i + 2][2] == p
        finally:
            tier.close()


# ---------------------------------------------------------------------------
# Engine G: fleet model + drop-migration-free mutation
# ---------------------------------------------------------------------------

class TestEngineGFleet:
    def test_fleet_exploration_complete_and_clean(self):
        from deepspeed_tpu.analysis.protocol_model import (
            ProtoModelConfig, explore,
        )

        plain = explore(ProtoModelConfig())
        rep = explore(ProtoModelConfig(fleet=True))
        assert rep.complete and rep.ok, rep.violations[:3]
        # replica B's machinery genuinely grows the state space
        assert rep.states > plain.states

    def test_fleet_excludes_disaggregated_in_model(self):
        from deepspeed_tpu.analysis.protocol_model import ProtoModelConfig

        with pytest.raises(ValueError, match="fleet"):
            ProtoModelConfig(fleet=True, disaggregated=True)

    def test_fleet_in_default_gate_sweep(self):
        from deepspeed_tpu.analysis.protocol_model import (
            default_model_configs,
        )

        assert default_model_configs()["fleet"].fleet is True

    def test_drop_migration_free_minimal_counterexample(self):
        from deepspeed_tpu.analysis.protocol_model import (
            ProtoModelConfig, explore,
        )

        rep = explore(ProtoModelConfig(
            fleet=True, mutations=frozenset({"drop-migration-free"}),
        ))
        bad = [v for v in rep.violations
               if v.rule == "proto-replica-page-leak"]
        assert bad, [v.rule for v in rep.violations]
        v = min(bad, key=lambda v: len(v.trace))
        assert "migrate_commit(r0)" in v.trace
        assert v.trace[-1] == "replica_die"

    def test_counterexample_replays_red_on_real_fleet(
        self, inference_engine
    ):
        from deepspeed_tpu.analysis.protocol_model import (
            ProtoModelConfig, ReplayClock, apply_engine_mutation, explore,
            replay_fleet_trace,
        )
        from deepspeed_tpu.serving import FleetRouter

        rep = explore(ProtoModelConfig(
            fleet=True, mutations=frozenset({"drop-migration-free"}),
        ))
        bad = [v for v in rep.violations
               if v.rule == "proto-replica-page-leak"]
        trace = min(bad, key=lambda v: len(v.trace)).trace
        prompts = [np.arange(1, 6, dtype=np.int32)]
        cfg = dict(BASE, **FLEET2)

        clock = ReplayClock()
        fleet = FleetRouter(inference_engine, dict(cfg), clock=clock)
        try:
            out = replay_fleet_trace(
                fleet, trace, prompts, max_new_tokens=6, clock=clock,
            )
            assert out["ok"], out["violations"][:3]
            assert fleet.stats()["fleet"]["migrations_ok"] >= 1
        finally:
            fleet.close()

        clock = ReplayClock()
        fleet = FleetRouter(inference_engine, dict(cfg), clock=clock)
        try:
            undo = apply_engine_mutation(fleet, "drop-migration-free")
            try:
                out = replay_fleet_trace(
                    fleet, trace, prompts, max_new_tokens=6, clock=clock,
                )
            finally:
                undo()
            assert not out["ok"]
            assert any("leak" in v for v in out["violations"])
        finally:
            fleet.close()


# ---------------------------------------------------------------------------
# satellite 2: trace grouping by replica
# ---------------------------------------------------------------------------

class TestTraceByReplica:
    def test_cli_by_replica_groups_terminal_records(
        self, tiny_cfg, inference_engine, tmp_path, capsys
    ):
        from deepspeed_tpu.telemetry.request_trace import RequestTracer
        from deepspeed_tpu.tools import request_trace as cli

        path = str(tmp_path / "trace.jsonl")
        tracer = RequestTracer(path)
        fleet = _fleet(inference_engine, tracer=tracer)
        try:
            reqs = _mixed_requests(tiny_cfg.vocab_size, n=8)
            for i, (p, n) in enumerate(reqs):
                fleet.submit(p, max_new_tokens=n, seed=i)
            for _ in range(3):
                fleet.step()
            fleet.preempt(max(fleet.alive(), key=type(fleet)._load).rid)
            fleet.run()
            fleet.drain()
        finally:
            fleet.close()
        tracer.close()

        assert cli.main([path, "--by", "replica", "--json"]) == 0
        doc = json.loads(capsys.readouterr().out)
        assert doc["by"] == "replica" and doc["records"] == 8
        groups = set(doc["score"]["groups"])
        # every record carries a replica stamp; migration restamps survivors
        assert groups and groups <= {"r0", "r1"}, groups
        assert cli.main([path, "--by", "replica"]) == 0
        out = capsys.readouterr().out
        assert "(replica)" in out and ("r0" in out or "r1" in out)
