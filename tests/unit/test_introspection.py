"""What is left of the performance introspection plane (ISSUE 5): the peak
table, the anomaly watchdog with auto-capture, trace rotation, and the
trace_diff CLI. (The HLO cost/MFU analyzer went in PR 30.)

Acceptance pins:
- the watchdog trips on an injected NaN and an injected loss spike, emits an
  ``anomaly`` event and a bounded profiler capture; a disabled config
  constructs nothing and adds zero host callbacks;
- ``trace_diff`` flags the right span of a known injected regression with a
  non-zero exit code, and exits 0 on identical runs.
"""

import json
import os

import numpy as np
import pytest

from deepspeed_tpu.runtime.config import (
    DeepSpeedConfig,
    DeepSpeedConfigError,
    TelemetryConfig,
    WatchdogConfig,
)
from deepspeed_tpu.telemetry import introspect
from deepspeed_tpu.telemetry.watchdog import AnomalyError, AnomalyWatchdog
from deepspeed_tpu.telemetry.watchdog import from_config as watchdog_from_config


# ---------------------------------------------------------------------------
# peak table
# ---------------------------------------------------------------------------

def test_chip_peak_lookup_and_fallback():
    v5p = introspect.chip_peak("TPU v5p")
    assert v5p.source == "table" and v5p.peak_flops == 459e12
    # longest-match: "TPU v5 lite" must not resolve through "TPU v4"
    v5e = introspect.chip_peak("TPU v5 lite")
    assert v5e.peak_flops == 197e12
    # 1,600 Gbit/s a chip, as published and as perfbench/peaks.py has it
    assert v5e.ici_bytes_per_s == introspect.chip_peak("TPU v5e").ici_bytes_per_s == 2.0e11
    cpu = introspect.chip_peak("cpu")
    assert cpu.source == "fallback" and cpu.peak_flops > 0
    # an accelerator missing from the table is an error, not the CPU entry
    with pytest.raises(ValueError, match="not in PEAK_TABLE"):
        introspect.chip_peak("TPU v9 mega")
    over = introspect.chip_peak("TPU v5p", peak_flops_override=123e12)
    assert over.peak_flops == 123e12 and over.source == "override"


# ---------------------------------------------------------------------------
# an engine with telemetry on, for the watchdog tests
# ---------------------------------------------------------------------------

def _engine(mesh, tmp_path, telemetry=None):
    from deepspeed_tpu.runtime.engine import DeepSpeedEngine

    from .simple_model import make_simple_model

    ds = DeepSpeedConfig.load(
        {
            "train_micro_batch_size_per_gpu": 2,
            "gradient_accumulation_steps": 1,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
            "zero_optimization": {"stage": 0},
            "mesh": {"dp": 8},
            "steps_per_print": 10**9,
            "telemetry": telemetry or {},
        },
        dp_world_size=8,
    )
    return DeepSpeedEngine(make_simple_model(), ds, mesh=mesh, seed=0)


# ---------------------------------------------------------------------------
# watchdog (acceptance: NaN + spike trips, bounded capture, disabled = None)
# ---------------------------------------------------------------------------

def test_watchdog_trips_on_injected_nan_with_capture(mesh_dp8, tmp_path):
    engine = _engine(
        mesh_dp8, tmp_path,
        telemetry={
            "enabled": True, "trace_path": str(tmp_path / "tr"),
            "flush_interval": 1, "sample_every": 10**9,
            "watchdog": {
                "enabled": True, "warmup_steps": 3, "zscore": 5.0,
                "capture_dir": str(tmp_path / "anomalies"), "max_captures": 2,
            },
        },
    )
    from .simple_model import random_batches

    batch = random_batches(1, engine.train_batch_size)[0]
    for _ in range(4):
        m = engine.train_batch(batch)
    assert "anomaly_flags" not in m  # popped before the metrics surface
    wd = engine._watchdog
    assert wd is not None and not wd.anomalies  # healthy steps: no trips
    bad = {"x": batch["x"].copy(), "y": batch["y"]}
    bad["x"][0, 0] = np.nan
    engine.train_batch(bad)
    kinds = {(a["anomaly_kind"], a["signal"]) for a in wd.anomalies}
    assert ("nonfinite", "loss") in kinds
    # the anomaly event is a structured trace record, flushed immediately
    recs = [json.loads(l) for l in open(engine.telemetry.tracer.file_path)]
    anoms = [r for r in recs if r["kind"] == "anomaly"]
    assert anoms and anoms[0]["anomaly_kind"] == "nonfinite"
    assert engine.telemetry.registry.get("anomalies_total").value(
        kind="nonfinite") >= 1
    # the NEXT step runs under a bounded profiler capture
    assert wd.capture_pending
    engine.train_batch(batch)
    caps = sorted(os.listdir(tmp_path / "anomalies"))
    assert len(caps) >= 1
    # the capture actually wrote profiler output
    cap_files = [
        os.path.join(dp, f)
        for dp, _, fs in os.walk(tmp_path / "anomalies" / caps[0]) for f in fs
    ]
    assert cap_files
    # bounded: never more than max_captures dirs
    assert len(caps) <= 2


def test_watchdog_nan_flags_judged_off_cadence(mesh_dp8, tmp_path):
    """check_every thins the spike/EMA judgement only: the in-graph NaN
    flags are computed every compiled step and must trip even on
    off-cadence steps."""
    engine = _engine(
        mesh_dp8, tmp_path,
        telemetry={
            "enabled": True, "trace_path": str(tmp_path / "tr"),
            "sample_every": 10**9,
            "watchdog": {
                "enabled": True, "check_every": 100,
                "capture_dir": str(tmp_path / "anomalies"),
            },
        },
    )
    from .simple_model import random_batches

    batch = random_batches(1, engine.train_batch_size)[0]
    engine.train_batch(batch)
    bad = {"x": batch["x"].copy(), "y": batch["y"]}
    bad["x"][0, 0] = np.inf
    engine.train_batch(bad)  # step 2: off the check_every=100 cadence
    kinds = {(a["anomaly_kind"], a["signal"]) for a in engine._watchdog.anomalies}
    assert ("nonfinite", "loss") in kinds or ("nonfinite", "grad_norm") in kinds


def test_watchdog_spike_trip_and_descent_immunity():
    wd = AnomalyWatchdog(WatchdogConfig(enabled=True, warmup_steps=5, zscore=6.0))
    for i in range(30):
        # healthy fast-descending loss + noisy gnorm: must NOT trip
        wd.observe_step(i, {"loss": 3.0 - i * 0.05, "grad_norm": 1.0 + 0.01 * (i % 3)})
    assert wd.anomalies == []
    trips = wd.observe_step(30, {"loss": 25.0, "grad_norm": 1.0})
    assert [a["anomaly_kind"] for a in trips] == ["spike"]
    assert trips[0]["signal"] == "loss" and trips[0]["z"] > 6.0
    # self-masking guard: an immediately repeated spike still trips (the
    # first one was clamped into the EMA, not absorbed at face value)
    trips2 = wd.observe_step(31, {"loss": 25.0, "grad_norm": 1.0})
    assert any(a["signal"] == "loss" for a in trips2)


def test_watchdog_flag_and_host_nonfinite_dedup():
    """The in-graph flag and the host isfinite fallback must not
    double-report the same signal in one step."""
    from deepspeed_tpu.telemetry.watchdog import (
        FLAG_GRAD_NONFINITE,
        FLAG_LOSS_NONFINITE,
    )

    wd = AnomalyWatchdog(WatchdogConfig(enabled=True))
    trips = wd.observe_step(
        1, {"loss": float("nan"), "grad_norm": float("inf")},
        flags=FLAG_LOSS_NONFINITE | FLAG_GRAD_NONFINITE,
    )
    assert [(a["anomaly_kind"], a["signal"]) for a in trips] == [
        ("nonfinite", "loss"), ("nonfinite", "grad_norm"),
    ]


def test_watchdog_kill_policy_raises_after_recording(tmp_path):
    cfg = WatchdogConfig(enabled=True, policy="kill", warmup_steps=2, zscore=4.0)
    wd = AnomalyWatchdog(cfg)
    with pytest.raises(AnomalyError, match="nonfinite"):
        wd.observe_step(5, {"loss": float("nan")})
    assert wd.anomalies  # recorded before raising


def test_watchdog_disabled_constructs_nothing(mesh_dp8, tmp_path):
    engine = _engine(
        mesh_dp8, tmp_path,
        telemetry={
            "enabled": True, "trace_path": str(tmp_path / "tr"),
            "sample_every": 10**9,
        },
    )
    assert engine._watchdog is None
    assert watchdog_from_config(WatchdogConfig(enabled=False)) is None
    assert watchdog_from_config(None) is None
    from .simple_model import random_batches

    m = engine.train_batch(random_batches(1, engine.train_batch_size)[0])
    assert "anomaly_flags" not in m
    # no watchdog metric families declared
    assert engine.telemetry.registry.get("anomalies_total") is None


def test_watchdog_config_validation():
    with pytest.raises(DeepSpeedConfigError):
        WatchdogConfig(policy="panic")
    with pytest.raises(DeepSpeedConfigError):
        WatchdogConfig(zscore=0.0)
    with pytest.raises(DeepSpeedConfigError):
        WatchdogConfig(ema_alpha=0.0)


# ---------------------------------------------------------------------------
# tracer rotation (satellite: telemetry.trace_max_mb)
# ---------------------------------------------------------------------------

def test_tracer_size_capped_rotation(tmp_path):
    from deepspeed_tpu.telemetry import StepTracer

    tr = StepTracer(
        str(tmp_path / "tr"), flush_interval=1, max_bytes=2048
    )
    for i in range(100):
        tr.emit({"kind": "train_step", "step": i, "pad": "x" * 64})
    tr.close()
    assert tr.rotations >= 1
    live, rolled = tr.file_path, tr.file_path + ".1"
    assert os.path.exists(live) and os.path.exists(rolled)
    # bounded: live file below cap (+ one flush of slack), one rolled gen
    assert os.path.getsize(live) <= 2048 + 512
    assert os.path.getsize(rolled) <= 2048 + 512
    assert not os.path.exists(tr.file_path + ".2")
    # rolled + live still parse as clean JSONL (atomic roll, no torn lines)
    for path in (live, rolled):
        for line in open(path):
            json.loads(line)


def test_tracer_no_rotation_when_unbounded(tmp_path):
    from deepspeed_tpu.telemetry import StepTracer

    tr = StepTracer(str(tmp_path / "tr"), flush_interval=1, max_bytes=0)
    for i in range(50):
        tr.emit({"kind": "train_step", "step": i, "pad": "x" * 64})
    tr.close()
    assert tr.rotations == 0
    assert not os.path.exists(tr.file_path + ".1")


# ---------------------------------------------------------------------------
# trace_diff CLI (acceptance: flags the right span, exit codes)
# ---------------------------------------------------------------------------

def _write_trace(path, dispatch_ms, steps=20):
    with open(path, "w") as fh:
        for s in range(steps):
            fh.write(json.dumps({
                "kind": "train_step", "step": s, "dur_ms": 10.0 + dispatch_ms,
                "loss": 2.0,
                "spans": {
                    "total_ms": 10.0 + dispatch_ms,
                    "children": {"prepare": 4.0, "dispatch": dispatch_ms,
                                 "sync": 6.0},
                },
                "comm_bytes": {"dp": 4096},
            }) + "\n")


def test_trace_diff_flags_injected_regression(tmp_path, capsys):
    from deepspeed_tpu.tools import trace_diff

    a, b = str(tmp_path / "a.jsonl"), str(tmp_path / "b.jsonl")
    _write_trace(a, dispatch_ms=2.0)
    _write_trace(b, dispatch_ms=6.0)  # 3x regression in the dispatch span
    rc = trace_diff.main([a, b, "--threshold-pct", "10", "--json"])
    assert rc == 1
    report = json.loads(capsys.readouterr().out)
    flagged = {r["metric"] for r in report["regressions"]}
    assert "span:dispatch_ms" in flagged
    # un-regressed spans stay clean
    assert "span:prepare_ms" not in flagged and "span:sync_ms" not in flagged


def test_trace_diff_identical_runs_exit_zero(tmp_path, capsys):
    from deepspeed_tpu.tools import trace_diff

    a = str(tmp_path / "a.jsonl")
    _write_trace(a, dispatch_ms=2.0)
    rc = trace_diff.main([a, a])
    assert rc == 0
    assert "no regressions" in capsys.readouterr().out


def test_trace_diff_usage_errors(tmp_path, capsys):
    from deepspeed_tpu.tools import trace_diff

    empty = str(tmp_path / "empty.jsonl")
    open(empty, "w").close()
    a = str(tmp_path / "a.jsonl")
    _write_trace(a, 2.0)
    assert trace_diff.main([a, empty]) == 2
    assert trace_diff.main([str(tmp_path / "missing.jsonl"), a]) == 2


# ---------------------------------------------------------------------------
# histogram quantiles (backing the serving stats() satellite)
# ---------------------------------------------------------------------------

def test_histogram_quantile_estimation():
    from deepspeed_tpu.telemetry import MetricsRegistry

    reg = MetricsRegistry()
    h = reg.histogram("lat", buckets=(0.1, 0.2, 0.4, 0.8))
    assert h.quantile(0.5) is None  # no observations
    for v in np.linspace(0.01, 0.79, 100):
        h.observe(float(v))
    p50, p95, p99 = h.quantile(0.5), h.quantile(0.95), h.quantile(0.99)
    assert 0.3 < p50 < 0.5
    assert p50 < p95 < p99 <= 0.8
    with pytest.raises(ValueError):
        h.quantile(1.5)
