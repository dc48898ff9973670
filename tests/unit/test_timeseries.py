"""Metrics time-series plane (ISSUE 20): MetricsJournal snapshot/encode/
rotation/torn-tail semantics, SeriesStore query API (counter-reset-tolerant
``increase``/``rate``, ``quantile_over_time`` == live ``stats()`` pin),
seeded-replay byte-identity, the SLO error-budget burn-rate alert state
machine (fires on an injected sustained violation, resolves after
recovery), fleet backpressure flipping only on *firing* (never pending),
windowed goodput under a fake clock, the ``fleet_dash`` CLI 0/1/2 exit
matrix, and the serving acceptance: the journal attached
leaves the 16-request mixed suite's token streams bit-identical."""

import json
import os
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import gpt2
from deepspeed_tpu.runtime.config import SLOAlertsConfig
from deepspeed_tpu.telemetry.registry import MetricsRegistry
from deepspeed_tpu.telemetry.slo_budget import SLOBudgetEngine
from deepspeed_tpu.telemetry.timeseries import (
    SCHEMA,
    MetricsJournal,
    SeriesStore,
    TimeseriesError,
    load_journal,
)
from deepspeed_tpu.tools import fleet_dash

warnings.filterwarnings("ignore")

pytestmark = pytest.mark.tsdb


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


SERVING_CFG = {
    "max_slots": 4,
    "page_size": 4,
    "num_pages": 64,
    "max_prompt_len": 12,
    "max_new_tokens": 8,
    "kv_cache_dtype": "float32",
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
        (rs.randint(0, vocab, (plens[i],)).astype(np.int32),
         6 if i % 7 else (1, 3, 8)[i // 7])
        for i in range(n)
    ]


def _streams(srv, reqs):
    subs = [
        srv.submit(p, max_new_tokens=n, seed=i)
        for i, (p, n) in enumerate(reqs)
    ]
    srv.run()
    return [list(r.tokens) for r in subs]


def _journal(tmp_path, name="tsdb.jsonl", registry=None, clock=None, **kw):
    kw.setdefault("flush_interval", 1)
    return MetricsJournal(
        str(tmp_path / name), registry=registry,
        clock=clock if clock is not None else FakeClock(), **kw,
    )


# ---------------------------------------------------------------------------
# journal encode / decode
# ---------------------------------------------------------------------------

class TestJournalRoundTrip:
    def test_scalars_hists_round_trip(self, tmp_path):
        clk = FakeClock()
        reg = MetricsRegistry()
        g = reg.gauge("g_x", "x")
        c = reg.counter("c_y", "y", labelnames=("k",))
        h = reg.histogram("h_z", "z")
        j = _journal(tmp_path, registry=reg, clock=clk)
        g.set(1.5)
        c.inc(3, k="a")
        h.observe(0.02)
        j.snapshot(0.0)
        clk.t = 1.0
        g.set(2.5)
        h.observe(0.7)
        j.snapshot(1.0)
        j.close()
        st = load_journal(j.file_path)
        assert st.range("g_x") == [(0.0, 1.5), (1.0, 2.5)]
        assert st.latest('c_y{k="a"}') == 3.0
        win = st.hist_window("h_z", None, None)
        assert win is not None and win[2] == 2
        assert st.meta["schema"] == SCHEMA

    def test_delta_encoding_skips_unchanged(self, tmp_path):
        reg = MetricsRegistry()
        g = reg.gauge("g_x", "x")
        j = _journal(tmp_path, registry=reg)
        g.set(1.0)
        j.snapshot(0.0)
        j.snapshot(1.0)  # nothing changed: no record
        g.set(2.0)
        j.snapshot(2.0)
        j.close()
        assert j.records_emitted == 2
        st = load_journal(j.file_path)
        assert st.range("g_x") == [(0.0, 1.0), (2.0, 2.0)]

    def test_maybe_snapshot_interval_gating(self, tmp_path):
        reg = MetricsRegistry()
        g = reg.gauge("g_x", "x")
        j = _journal(tmp_path, registry=reg, interval_s=1.0)
        g.set(1.0)
        assert j.maybe_snapshot(0.0) is True
        g.set(2.0)
        assert j.maybe_snapshot(0.5) is False   # inside the interval
        assert j.maybe_snapshot(1.0) is True
        assert j.snapshots == 2

    def test_rotation_rebaselines(self, tmp_path):
        reg = MetricsRegistry()
        g = reg.gauge("g_x", "x")
        h = reg.histogram("h_z", "z")
        j = _journal(tmp_path, registry=reg, max_bytes=2000)
        for i in range(100):
            g.set(float(i))
            h.observe(0.01 * (i + 1))
            j.snapshot(float(i))
        last = 99.0
        j.close()
        assert j.rotations >= 1
        assert os.path.exists(j.file_path + ".1")
        # the post-rotation generation is self-contained: meta + baseline
        # re-emitted, so the LIVE file alone is a valid journal
        import shutil

        solo = tmp_path / "solo.jsonl"
        shutil.copy(j.file_path, solo)
        st = load_journal(str(solo))
        assert st.latest("g_x") == last
        assert st.quantile_over_time("h_z", 0.5) is not None
        # both generations together give the full history
        full = load_journal(j.file_path)
        assert full.latest("g_x") == last
        assert len(full.range("g_x")) > len(st.range("g_x"))

    def test_torn_tail_tolerated_mid_file_raises(self, tmp_path):
        reg = MetricsRegistry()
        g = reg.gauge("g_x", "x")
        j = _journal(tmp_path, registry=reg)
        g.set(1.0)
        j.snapshot(0.0)
        j.close()
        with open(j.file_path, "a") as fh:
            fh.write('{"kind": "tsdb", "t": 1.0, "se')  # crash mid-append
        st = load_journal(j.file_path)
        assert st.range("g_x") == [(0.0, 1.0)]
        # the same garbage NOT at the tail is corruption
        with open(j.file_path, "a") as fh:
            fh.write('\n{"kind": "tsdb_meta", "schema": "%s"}\n' % SCHEMA)
        with pytest.raises(TimeseriesError, match="undecodable"):
            load_journal(j.file_path)

    def test_missing_and_wrong_schema_raise(self, tmp_path):
        with pytest.raises(TimeseriesError, match="no journal"):
            load_journal(str(tmp_path / "nope.jsonl"))
        bad = tmp_path / "bad.jsonl"
        bad.write_text('{"kind": "tsdb_meta", "schema": "other-v9"}\n')
        with pytest.raises(TimeseriesError, match="schema"):
            load_journal(str(bad))
        nometa = tmp_path / "nometa.jsonl"
        nometa.write_text('{"kind": "tsdb", "t": 0.0, "set": {"a": 1}}\n')
        with pytest.raises(TimeseriesError, match="tsdb_meta"):
            load_journal(str(nometa))

    def test_events_ride_the_journal(self, tmp_path):
        reg = MetricsRegistry()
        reg.gauge("g_x", "x").set(1.0)
        j = _journal(tmp_path, registry=reg)
        j.snapshot(0.0)
        j.emit_event({"kind": "slo_alert", "state": "firing", "t": 0.5})
        j.close()
        st = load_journal(j.file_path)
        assert st.events == [{"kind": "slo_alert", "state": "firing", "t": 0.5}]


# ---------------------------------------------------------------------------
# query API
# ---------------------------------------------------------------------------

class TestQueries:
    def test_increase_tolerates_counter_reset(self):
        st = SeriesStore()
        for t, v in [(0, 0.0), (1, 10.0), (2, 20.0), (3, 3.0), (4, 8.0)]:
            st.add_scalar(float(t), "c", v)
        # 0→10→20, reset, 3 (the new absolute IS the post-reset increase),
        # then 3→8
        assert st.increase("c", 0.0, 4.0) == pytest.approx(28.0)
        assert st.rate("c", 0.0, 4.0) == pytest.approx(7.0)
        # window baselines at the last sample <= t0
        assert st.increase("c", 1.0, 2.0) == pytest.approx(10.0)
        # unseen-before-t0 counters baseline at zero
        assert st.increase("c", -5.0, 1.0) == pytest.approx(10.0)
        assert st.increase("unknown", 0.0, 4.0) == 0.0

    def test_range_latest_trim(self):
        st = SeriesStore()
        for t in range(10):
            st.add_scalar(float(t), "g", float(t * t))
        assert st.range("g", 2.0, 4.0) == [(2.0, 4.0), (3.0, 9.0), (4.0, 16.0)]
        assert st.latest("g", 3.5) == 9.0
        assert st.latest("g") == 81.0
        st.trim(5.0)
        # the baseline sample at t=5 survives the trim
        assert st.range("g")[0] == (5.0, 25.0)
        assert st.increase("g", 5.0, 9.0) == pytest.approx(81.0 - 25.0)

    def test_quantile_over_time_matches_live(self, tmp_path):
        reg = MetricsRegistry()
        h = reg.histogram("h_lat", "lat")
        rs = np.random.RandomState(3)
        j = _journal(tmp_path, registry=reg)
        for i in range(5):
            for v in rs.gamma(2.0, 0.05, size=50):
                h.observe(float(v))
            j.snapshot(float(i))
        j.close()
        st = load_journal(j.file_path)
        for q in (0.5, 0.9, 0.99):
            assert st.quantile_over_time("h_lat", q) == h.quantile(q)
        # a WINDOW reproduces the bucket-count difference, not the total
        full = st.hist_window("h_lat", None, None)
        tail = st.hist_window("h_lat", 1.0, 4.0)
        assert full[2] == 250 and tail[2] == 150


# ---------------------------------------------------------------------------
# burn-rate alerting
# ---------------------------------------------------------------------------

def _drive(journal, budget, c_ev, c_met, clk, start, end, miss_every=0):
    """Advance the virtual clock one second at a time, 10 completions per
    second; ``miss_every=2`` misses every other one. Returns transitions."""
    out = []
    for sec in range(start, end):
        clk.t = float(sec)
        for i in range(10):
            c_ev.inc(slo_class="interactive")
            if not miss_every or i % miss_every != 0:
                c_met.inc(slo_class="interactive")
        journal.maybe_snapshot(clk.t)
        out.extend(budget.maybe_evaluate())
    return out


def _alert_rig(tmp_path, **cfg_kw):
    clk = FakeClock()
    reg = MetricsRegistry()
    c_ev = reg.counter("serving_slo_evaluated_total", "t",
                       labelnames=("slo_class",))
    c_met = reg.counter("serving_slo_met_total", "t",
                        labelnames=("slo_class",))
    j = _journal(tmp_path, name="alerts.jsonl", registry=reg, clock=clk)
    cfg_kw.setdefault("enabled", True)
    cfg_kw.setdefault("objective", 0.99)
    cfg_kw.setdefault("fast_short_s", 5.0)
    cfg_kw.setdefault("fast_long_s", 30.0)
    cfg_kw.setdefault("fast_burn_threshold", 10.0)
    cfg_kw.setdefault("slow_short_s", 30.0)
    cfg_kw.setdefault("slow_long_s", 120.0)
    cfg_kw.setdefault("slow_burn_threshold", 1.0)
    acfg = SLOAlertsConfig(**cfg_kw)
    budget = SLOBudgetEngine(j, acfg, registry=reg, clock=clk)
    return clk, reg, c_ev, c_met, j, budget


class TestBurnRateAlerts:
    def test_fires_on_sustained_violation_resolves_after_recovery(
        self, tmp_path
    ):
        clk, reg, c_ev, c_met, j, budget = _alert_rig(tmp_path, for_s=2.0)
        trs = _drive(j, budget, c_ev, c_met, clk, 0, 60)
        assert trs == [] and not budget.firing()
        # sustained violation: half of all completions miss for 60s
        trs = _drive(j, budget, c_ev, c_met, clk, 60, 120, miss_every=2)
        fired = [t for t in trs if t["state"] == "firing"]
        assert fired and budget.firing()
        assert all(60.0 <= t["t"] < 120.0 for t in fired)
        assert budget.firing_classes() == ["interactive"]
        # budget gauges exported
        assert reg.gauge(
            "slo_error_budget_remaining", "", labelnames=("slo_class",)
        ).value(slo_class="interactive") < 1.0
        # recovery: the short windows drain and every rule resolves
        trs = _drive(j, budget, c_ev, c_met, clk, 120, 300)
        resolved = [t for t in trs if t["state"] == "resolved"]
        assert resolved and not budget.firing()
        assert all(t["t"] >= 120.0 for t in resolved)
        # transitions landed in the journal as slo_alert events
        j.close()
        st = load_journal(j.file_path)
        kinds = [(e["state"], e["rule"]) for e in st.events]
        assert ("firing", "fast") in kinds and ("resolved", "fast") in kinds

    def test_single_bad_window_never_fires(self, tmp_path):
        """The multi-window AND: one bad short window with a clean long
        window stays inactive (de-flapping). Slow rule threshold is
        parked out of reach to isolate the fast rule."""
        clk, reg, c_ev, c_met, j, budget = _alert_rig(
            tmp_path, for_s=0.0, slow_burn_threshold=1e9
        )
        _drive(j, budget, c_ev, c_met, clk, 0, 100)
        # 3 seconds of violation: short burn spikes, long stays clean
        trs = _drive(j, budget, c_ev, c_met, clk, 100, 103, miss_every=2)
        assert [t for t in trs if t["state"] == "firing"] == []
        trs = _drive(j, budget, c_ev, c_met, clk, 103, 140)
        assert [t for t in trs if t["state"] == "firing"] == []

    def test_for_s_dwell_gates_pending(self, tmp_path):
        clk, reg, c_ev, c_met, j, budget = _alert_rig(tmp_path, for_s=1e9)
        _drive(j, budget, c_ev, c_met, clk, 0, 30)
        _drive(j, budget, c_ev, c_met, clk, 30, 120, miss_every=2)
        # condition holds but the dwell never elapses: pending, not firing
        states = {st["state"] for st in budget._states.values()}
        assert "pending" in states and not budget.firing()

    def test_budget_remaining_math(self, tmp_path):
        clk, reg, c_ev, c_met, j, budget = _alert_rig(tmp_path)
        assert budget.budget_remaining("interactive") == 1.0
        # 1000 evaluated, 10 bad at objective 0.99: budget exactly spent
        for i in range(1000):
            c_ev.inc(slo_class="interactive")
            if i >= 10:
                c_met.inc(slo_class="interactive")
        clk.t = 1.0
        j.snapshot(1.0)
        assert budget.budget_remaining("interactive") == pytest.approx(0.0)


# ---------------------------------------------------------------------------
# fleet backpressure
# ---------------------------------------------------------------------------

class TestFleetBackpressure:
    def _fleet(self, inference_engine, tmp_path, for_s):
        from deepspeed_tpu.serving.fleet import FleetRouter

        clk = FakeClock()
        j = _journal(tmp_path, name="fleet.jsonl", clock=clk, interval_s=1.0)
        fleet = FleetRouter(inference_engine, dict(
            SERVING_CFG,
            slo={"classes": {"interactive": {"ttft_target_s": 1.0}},
                 "default_class": "interactive"},
            fleet={"enabled": True, "replicas": 2, "slo_alerts": {
                "enabled": True, "backpressure": True, "objective": 0.99,
                "fast_short_s": 5.0, "fast_long_s": 30.0,
                "fast_burn_threshold": 10.0,
                "slow_short_s": 30.0, "slow_long_s": 120.0,
                "slow_burn_threshold": 1.0, "for_s": for_s,
            }},
        ), clock=clk, journal=j)
        return clk, j, fleet

    def test_sheds_only_on_firing_never_pending(
        self, inference_engine, tmp_path
    ):
        clk, j, fleet = self._fleet(inference_engine, tmp_path, for_s=20.0)
        m = fleet.metrics
        c_ev = m.counter("serving_slo_evaluated_total", "",
                         labelnames=("slo_class",))
        c_met = m.counter("serving_slo_met_total", "",
                          labelnames=("slo_class",))
        budget = fleet.slo_budget
        assert budget is not None and not fleet._should_shed()
        _drive(j, budget, c_ev, c_met, clk, 0, 40)
        assert not fleet._should_shed()
        # violation starts: rules go PENDING (for_s=20 dwell) — no shed
        _drive(j, budget, c_ev, c_met, clk, 40, 50, miss_every=2)
        assert any(st["state"] == "pending"
                   for st in budget._states.values())
        assert not fleet._should_shed()
        req = fleet.submit(np.arange(4, dtype=np.int32), max_new_tokens=2)
        assert req.status != "rejected"
        # dwell elapses under sustained violation: FIRING — shed, with the
        # sustained-burn detail on the rejected request
        _drive(j, budget, c_ev, c_met, clk, 50, 75, miss_every=2)
        assert budget.firing() and fleet._should_shed()
        req = fleet.submit(np.arange(4, dtype=np.int32), max_new_tokens=2)
        assert req.status == "rejected"
        assert "sustained error-budget burn" in req.detail
        # recovery: resolved — admissions reopen
        _drive(j, budget, c_ev, c_met, clk, 75, 200)
        assert not budget.firing() and not fleet._should_shed()
        fleet.drain()
        fleet.close()

    def test_fleet_step_drives_journal_and_alerts(
        self, inference_engine, tmp_path
    ):
        clk, j, fleet = self._fleet(inference_engine, tmp_path, for_s=0.0)
        reqs = _mixed_requests(
            inference_engine.model_config.vocab_size, n=4
        )
        for i, (p, n) in enumerate(reqs):
            fleet.submit(p, max_new_tokens=n, seed=i)
        fleet.run()
        assert j.snapshots > 0
        # per-replica gauges journaled under {replica="..."} labels
        sids = j.sids("fleet_replica_occupancy")
        assert sorted(sids) == [
            'fleet_replica_occupancy{replica="r0"}',
            'fleet_replica_occupancy{replica="r1"}',
        ]
        assert j.sids("fleet_replica_queue_depth")
        st = fleet.stats()
        assert st["slo_alerts"]["firing"] is False
        fleet.drain()
        fleet.check_no_leaks()
        fleet.close()


# ---------------------------------------------------------------------------
# windowed goodput
# ---------------------------------------------------------------------------

class TestWindowedGoodput:
    def _run_phase(self, srv, clk, reqs, dt):
        subs = [srv.submit(p, max_new_tokens=n, seed=i)
                for i, (p, n) in enumerate(reqs)]
        while srv.queue or any(s.request is not None for s in srv.slots):
            clk.t += dt  # advance BEFORE the step so TTFT sees the latency
            srv.step()
        return subs

    def test_late_degradation_drops_windowed_not_cumulative(
        self, tiny_cfg, inference_engine
    ):
        clk = FakeClock()
        srv = inference_engine.serve(dict(
            SERVING_CFG,
            slo={"classes": {"any": {"ttft_target_s": 5.0}},
                 "default_class": "any", "goodput_window_s": 10.0},
        ), clock=clk)
        reqs = _mixed_requests(tiny_cfg.vocab_size, n=4)
        # healthy phase: fast virtual steps, every request beats its TTFT
        self._run_phase(srv, clk, reqs, dt=0.05)
        snap = srv.slo_snapshot()
        assert snap["met"] == 4 and snap["good_tokens"] > 0
        healthy_windowed = snap["goodput_tokens_per_sec"]
        assert healthy_windowed > 0
        # late degradation: the engine crawls (10s virtual per step) — every
        # completion misses TTFT, no good tokens enter the window
        clk.t = 100.0
        self._run_phase(srv, clk, reqs, dt=10.0)
        snap = srv.slo_snapshot()
        assert snap["evaluated"] == 8 and snap["met"] == 4
        # the PIN: windowed goodput collapses to 0 (nothing good in the
        # trailing 10s), cumulative still smears the early good tokens
        assert snap["goodput_tokens_per_sec"] == 0.0
        assert snap["goodput_cumulative_tokens_per_sec"] > 0.0
        st = srv.stats()
        assert st["slo"]["goodput_tokens_per_sec"] == 0.0
        assert st["slo"]["goodput_cumulative_tokens_per_sec"] > 0.0
        srv.release_prefix_cache()
        srv.check_no_leaks()

    def test_journal_backed_window_matches_ring(
        self, tiny_cfg, inference_engine, tmp_path
    ):
        """The same run with and without a journal attached reports the
        same windowed goodput (journal increase() vs ring fallback)."""
        scfg = dict(
            SERVING_CFG,
            slo={"classes": {"any": {"ttft_target_s": 5.0}},
                 "default_class": "any", "goodput_window_s": 10.0},
        )
        reqs = _mixed_requests(tiny_cfg.vocab_size, n=4)
        vals = []
        for use_journal in (False, True):
            clk = FakeClock()
            j = (_journal(tmp_path, name=f"gw{use_journal}.jsonl",
                          clock=clk, interval_s=0.1)
                 if use_journal else None)
            srv = inference_engine.serve(scfg, clock=clk, journal=j)
            self._run_phase(srv, clk, reqs, dt=0.05)
            vals.append(srv.slo_snapshot()["goodput_tokens_per_sec"])
            srv.release_prefix_cache()
            srv.check_no_leaks()
            if j is not None:
                j.close()
        assert vals[0] == pytest.approx(vals[1], rel=1e-6)


# ---------------------------------------------------------------------------
# serving acceptance
# ---------------------------------------------------------------------------

class TestServingAcceptance:
    def test_mixed_suite_bit_identical_journal_on(
        self, tiny_cfg, inference_engine, tmp_path
    ):
        """The acceptance pin: journaling is pure host-side observation —
        spec + prefix + chunk streams match exactly with it attached."""
        cfg = dict(SERVING_CFG, **ALL_FEATURES)
        reqs = _mixed_requests(tiny_cfg.vocab_size)
        base = _streams(inference_engine.serve(cfg), reqs)
        clk = FakeClock()
        j = _journal(tmp_path, clock=clk, interval_s=0.0001)
        srv = inference_engine.serve(cfg, clock=clk, journal=j)
        assert _streams(srv, reqs) == base
        assert j.snapshots > 0 and j.records_emitted > 0
        srv.release_prefix_cache()
        srv.check_no_leaks()
        j.close()
        load_journal(j.file_path)  # well-formed

    def test_seeded_replay_byte_identical_journal(
        self, tiny_cfg, inference_engine, tmp_path
    ):
        """Two identical seeded virtual-clock replays write byte-identical
        journals (no wall-clock fields anywhere)."""
        from deepspeed_tpu.serving import (
            WorkloadSpec,
            generate_workload,
            replay,
        )
        from deepspeed_tpu.serving.replay import ReplayClock

        items = generate_workload(WorkloadSpec(
            n_requests=12, seed=11, vocab_size=tiny_cfg.vocab_size,
            max_prompt_len=SERVING_CFG["max_prompt_len"],
            max_new_tokens=6, base_interarrival_s=0.01,
            slo_classes=["interactive"],
        ))
        blobs = []
        for run in range(2):
            j = _journal(tmp_path, name=f"replay{run}.jsonl",
                         interval_s=0.02)
            srv = inference_engine.serve(dict(
                SERVING_CFG,
                slo={"classes": {"interactive": {"ttft_target_s": 1.0}},
                     "default_class": "interactive"},
            ), clock=ReplayClock(), journal=j)
            replay(srv, items, step_dt=0.005)
            srv.drain()
            srv.release_prefix_cache()
            srv.check_no_leaks()
            j.close()
            with open(j.file_path, "rb") as fh:
                blobs.append(fh.read())
        assert blobs[0] == blobs[1] and len(blobs[0]) > 0

    def test_journal_quantiles_reproduce_stats(
        self, tiny_cfg, inference_engine, tmp_path
    ):
        """Acceptance: full-range quantile_over_time == the live stats()
        quantile, exactly — one estimator, one answer."""
        clk = FakeClock()
        j = _journal(tmp_path, name="q.jsonl", clock=clk, interval_s=0.0001)
        srv = inference_engine.serve(dict(SERVING_CFG, **ALL_FEATURES),
                                     clock=clk, journal=j)
        reqs = _mixed_requests(tiny_cfg.vocab_size)
        for i, (p, n) in enumerate(reqs):
            subs = srv.submit(p, max_new_tokens=n, seed=i)
            clk.t += 0.013  # spread submits so latencies are non-trivial
        while srv.queue or any(s.request is not None for s in srv.slots):
            srv.step()
            clk.t += 0.002
        j.snapshot(clk.t)  # capture the final registry state
        st = srv.stats()
        live_ttft = srv._h_ttft
        live_tpot = srv._h_tpot
        for q in (0.5, 0.9, 0.99):
            assert j.quantile_over_time("serving_ttft_seconds", q) \
                == live_ttft.quantile(q)
            assert j.quantile_over_time("serving_tpot_seconds", q) \
                == live_tpot.quantile(q)
        assert st["ttft"]["p50_s"] == j.quantile_over_time(
            "serving_ttft_seconds", 0.5
        )
        srv.release_prefix_cache()
        srv.check_no_leaks()
        j.close()

    def test_telemetry_config_builds_journal(self, tiny_cfg, tmp_path):
        from deepspeed_tpu.inference.engine import InferenceEngine

        params = gpt2.init_params(tiny_cfg, jax.random.PRNGKey(0))
        eng = InferenceEngine(
            gpt2.make_module(tiny_cfg), params=params, dtype=jnp.float32,
            config={"telemetry": {
                "enabled": True,
                "trace_path": str(tmp_path / "tel"),
                "timeseries": {"enabled": True},
            }},
        )
        assert eng.telemetry.metrics_journal is not None
        srv = eng.serve(SERVING_CFG)
        assert srv._journal is eng.telemetry.metrics_journal
        srv.submit(np.arange(4, dtype=np.int32), max_new_tokens=3)
        srv.run()
        assert srv.stats()["timeseries"]["snapshots"] > 0
        srv.check_no_leaks()
        eng.telemetry.close()
        st = load_journal(eng.telemetry.metrics_journal.file_path)
        assert st.sids("serving_queue_depth")

    def test_env_report_tsdb_section(self, capsys):
        from deepspeed_tpu import env_report

        assert env_report.main() == 0
        assert "Time series / SLO budget" in capsys.readouterr().out


# ---------------------------------------------------------------------------
# CLIs
# ---------------------------------------------------------------------------

def _dash_journal(tmp_path):
    """An alert-rig journal with budget gauges + events for the CLI."""
    clk, reg, c_ev, c_met, j, budget = _alert_rig(tmp_path, for_s=2.0)
    _drive(j, budget, c_ev, c_met, clk, 0, 60)
    _drive(j, budget, c_ev, c_met, clk, 60, 120, miss_every=2)
    _drive(j, budget, c_ev, c_met, clk, 120, 260)
    j.close()
    return j.file_path


class TestFleetDashCLI:
    def test_exit_matrix(self, tmp_path, capsys):
        path = _dash_journal(tmp_path)
        assert fleet_dash.main([path]) == 0
        assert fleet_dash.main([path, "--json"]) == 0
        # gates: the run overspent its budget → a high floor trips
        assert fleet_dash.main([path, "--min-budget", "-100"]) == 0
        assert fleet_dash.main([path, "--min-budget", "0.99"]) == 1
        assert fleet_dash.main([path, "--max-burn", "1e9"]) == 0
        # diff against itself is clean
        assert fleet_dash.main([path, "--diff", path]) == 0
        # operational errors exit 2
        assert fleet_dash.main([str(tmp_path / "nope.jsonl")]) == 2
        bad = tmp_path / "bad.jsonl"
        bad.write_text('{"kind": "tsdb_meta", "schema": "other"}\n')
        assert fleet_dash.main([str(bad)]) == 2
        assert fleet_dash.main([path, "--bins", "0"]) == 2
        capsys.readouterr()

    def test_watch_iterations_bounded(self, tmp_path, capsys):
        path = _dash_journal(tmp_path)
        assert fleet_dash.main(
            [path, "--watch", "0.01", "--iterations", "2"]
        ) == 0
        capsys.readouterr()

    def test_report_and_forecast(self, tmp_path, capsys):
        path = _dash_journal(tmp_path)
        st = load_journal(path)
        rep = fleet_dash.dash_report(st)
        assert rep["slo"]["interactive"]["budget_remaining"] is not None
        assert rep["fleet"]["alerts_fired"] >= 1
        assert "budget_exhaustion_s" in rep["forecast"]
        out = fleet_dash.render(rep)
        assert "slo_class" in out and "alerts" in out
        capsys.readouterr()

    def test_diff_flags_regression(self, tmp_path):
        a = {"goodput_tokens_per_sec": 100.0, "alerts_fired": 0.0}
        b = {"goodput_tokens_per_sec": 50.0, "alerts_fired": 0.0}
        dr = fleet_dash.diff_reports(a, b, threshold_pct=10.0)
        assert dr["regressions"] == ["goodput_tokens_per_sec"]
        dr = fleet_dash.diff_reports(a, dict(a), threshold_pct=10.0)
        assert dr["regressions"] == []
