"""Performance introspection plane (ISSUE 5): HLO cost/MFU analyzer,
anomaly watchdog with auto-capture, trace rotation, and the trace_diff CLI.

Acceptance pins:
- MFU + per-category flops/bytes appear in StepTracer records and registry
  gauges for a compiled train step on CPU, with the analyzer within 5% of
  hand-computed flops on known matmul shapes;
- the watchdog trips on an injected NaN and an injected loss spike, emits an
  ``anomaly`` event and a bounded profiler capture; a disabled config
  constructs nothing and adds zero host callbacks;
- ``trace_diff`` flags the right span of a known injected regression with a
  non-zero exit code, and exits 0 on identical runs.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.runtime.config import (
    DeepSpeedConfig,
    DeepSpeedConfigError,
    TelemetryConfig,
    WatchdogConfig,
)
from deepspeed_tpu.runtime.module import ModuleSpec
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
    cpu = introspect.chip_peak("cpu")
    assert cpu.source == "fallback" and cpu.peak_flops > 0
    # an accelerator missing from the table is an error, not the CPU entry
    with pytest.raises(ValueError, match="not in PEAK_TABLE"):
        introspect.chip_peak("TPU v9 mega")
    over = introspect.chip_peak("TPU v5p", peak_flops_override=123e12)
    assert over.peak_flops == 123e12 and over.source == "override"


# ---------------------------------------------------------------------------
# HLO analyzer on known matmul shapes (acceptance: within 5% of hand count)
# ---------------------------------------------------------------------------

def test_analyzer_exact_on_known_matmuls():
    def f(x, w1, w2):
        h = jnp.tanh(x @ w1)
        return (h @ w2).sum()

    x = jnp.ones((64, 128))
    w1 = jnp.ones((128, 256))
    w2 = jnp.ones((256, 32))
    compiled = jax.jit(f).lower(x, w1, w2).compile()
    ana = introspect.analyze_compiled(compiled)
    hand = 2 * 64 * 256 * 128 + 2 * 64 * 32 * 256  # the two dots, exactly
    assert abs(ana.categories["matmul"].flops - hand) / hand < 0.05
    # and against XLA's own count (dots dominate; elementwise conventions
    # match HloCostAnalysis)
    assert ana.xla_flops is not None
    assert abs(ana.total_flops - ana.xla_flops) / ana.xla_flops < 0.05


def test_analyzer_loop_multiplier():
    def scanned(x, w):
        def body(c, _):
            return jnp.tanh(c @ w), ()

        out, _ = jax.lax.scan(body, x, None, length=4)
        return out.sum()

    x = jnp.ones((16, 32))
    w = jnp.ones((32, 32))
    compiled = jax.jit(scanned).lower(x, w).compile()
    once = introspect.analyze_compiled(compiled, loop_iterations=1)
    four = introspect.analyze_compiled(compiled, loop_iterations=4)
    body_dot = 2 * 16 * 32 * 32
    assert once.categories["matmul"].flops >= body_dot
    # the in-loop dot scales with the trip count hint
    assert four.categories["matmul"].flops - once.categories["matmul"].flops \
        == pytest.approx(3 * body_dot)


def test_analyzer_counts_async_tuple_collective_starts():
    """The latency-hiding scheduler splits collectives into tuple-typed
    -start/-done pairs; their bytes must count once (at -start) and tally
    as overlappable."""
    txt = "\n".join([
        "ENTRY %main.1 (p: f32[256]) -> f32[2048] {",
        "  %p = f32[256]{0} parameter(0)",
        "  %ags = (f32[256]{0}, f32[2048]{0}) all-gather-start(f32[256]{0} %p), "
        "replica_groups={{0,1,2,3,4,5,6,7}}, dimensions={0}",
        "  %agd = f32[2048]{0} all-gather-done((f32[256]{0}, f32[2048]{0}) %ags)",
        "  %ar = f32[256]{0} all-reduce(f32[256]{0} %p), to_apply=%add",
        "}",
    ])
    ana = introspect.analyze_hlo_text(txt)
    # async all-gather: gathered result (2048·4 B) upper-bounds the wire;
    # sync all-reduce: operand (256·4 B); -done contributes nothing
    assert ana.collective_bytes == 2048 * 4 + 256 * 4
    assert ana.overlappable_collective_bytes == 2048 * 4
    assert ana.categories["collective"].count == 2
    assert ana.overlap_fraction == pytest.approx(8192 / 9216)


def test_step_report_roofline_and_overlap():
    ana = introspect.HloAnalysis()
    ana.categories["matmul"] = introspect.CategoryCost(flops=1e12, bytes=1e9, count=1)
    ana.categories["collective"] = introspect.CategoryCost(bytes=4e9, count=2)
    ana.total_flops, ana.total_bytes = 1e12, 5e9
    ana.collective_bytes = 4e9
    ana.overlappable_collective_bytes = 1e9
    peak = introspect.PeakSpec("test", 1e14, 1e12, 1e10, "table")
    rep = introspect.step_report(ana, duration_s=0.1, peak=peak)
    assert rep["mfu"] == pytest.approx(1e12 / 0.1 / 1e14)
    assert rep["overlap_fraction"] == 0.25
    # unhidden 3e9 B at 1e10 B/s = 0.3s > memory 5e-3 > compute 1e-2 → comm
    assert rep["roofline_bound"] == "comm"
    # no collectives → nothing to hide → overlap 1.0
    empty = introspect.HloAnalysis()
    assert empty.overlap_fraction == 1.0


# ---------------------------------------------------------------------------
# engine end-to-end: MFU + categories in record and gauges (acceptance)
# ---------------------------------------------------------------------------

def _matmul_model(hidden=32, out=64):
    """One dot forward, one dot backward — hand-countable."""

    def init(rng):
        return {"w": jax.random.normal(rng, (hidden, out)) * 0.1}

    def loss_fn(params, batch, rng, train):
        logits = batch["x"] @ params["w"]
        return jnp.mean(jnp.square(logits)), {}

    return ModuleSpec(init=init, loss_fn=loss_fn)


def _engine(mesh, tmp_path, micro=2, telemetry=None, model=None):
    from deepspeed_tpu.runtime.engine import DeepSpeedEngine

    from .simple_model import make_simple_model

    ds = DeepSpeedConfig.load(
        {
            "train_micro_batch_size_per_gpu": micro,
            "gradient_accumulation_steps": 1,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
            "zero_optimization": {"stage": 0},
            "mesh": {"dp": 8},
            "steps_per_print": 10**9,
            "telemetry": telemetry or {},
        },
        dp_world_size=8,
    )
    return DeepSpeedEngine(model or make_simple_model(), ds, mesh=mesh, seed=0)


HIDDEN, OUT = 32, 64


def test_engine_mfu_and_categories_in_record_and_gauges(mesh_dp8, tmp_path):
    micro = 2
    engine = _engine(
        mesh_dp8, tmp_path, micro=micro,
        telemetry={
            "enabled": True, "trace_path": str(tmp_path / "tr"),
            "flush_interval": 1, "sample_every": 1,
        },
        model=_matmul_model(HIDDEN, OUT),
    )
    rs = np.random.RandomState(0)
    batch = {"x": rs.randn(engine.train_batch_size, HIDDEN).astype(np.float32)}
    engine.train_batch(batch)
    engine.telemetry.flush()
    recs = [json.loads(l) for l in open(engine.telemetry.tracer.file_path)]
    intro = recs[0].get("introspection")
    assert intro is not None
    assert intro["mfu"] > 0
    assert intro["roofline_bound"] in ("compute", "memory", "comm")
    # hand count (per-device program, batch dim sharded over dp=8):
    # fwd x@w = 2·B·H·O, bwd dw = xᵀ@dy = 2·B·H·O
    hand = 2 * 2 * micro * HIDDEN * OUT
    got = intro["flops_per_category"]["matmul"]
    assert abs(got - hand) / hand < 0.05, (got, hand)
    assert intro["bytes_per_category"]["matmul"] > 0
    assert 0.0 <= intro["overlap_fraction"] <= 1.0
    # registry gauges carry the same numbers
    reg = engine.telemetry.registry
    assert reg.get("step_mfu").value() == intro["mfu"]
    assert reg.get("flops_per_category").value(category="matmul") == got
    assert reg.get("overlap_fraction").value() == intro["overlap_fraction"]
    one_hot = [
        reg.get("roofline_bound").value(bound=b)
        for b in ("compute", "memory", "comm")
    ]
    assert sorted(one_hot) == [0.0, 0.0, 1.0]
    prom = reg.to_prometheus()
    assert "step_mfu" in prom and "flops_per_category" in prom


def test_introspection_disabled_adds_nothing(mesh_dp8, tmp_path):
    engine = _engine(
        mesh_dp8, tmp_path,
        telemetry={
            "enabled": True, "trace_path": str(tmp_path / "tr"),
            "flush_interval": 1, "sample_every": 1,
            "introspection": {"enabled": False},
        },
    )
    from .simple_model import random_batches

    engine.train_batch(random_batches(1, engine.train_batch_size)[0])
    engine.telemetry.flush()
    recs = [json.loads(l) for l in open(engine.telemetry.tracer.file_path)]
    assert "introspection" not in recs[0]
    assert engine.telemetry.registry.get("step_mfu") is None


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
                "introspection": {"mfu": 0.4, "overlap_fraction": 0.9,
                                  "flops_per_category": {"matmul": 1e9}},
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


def test_trace_diff_mfu_drop_is_a_regression(tmp_path, capsys):
    from deepspeed_tpu.tools import trace_diff

    a, b = str(tmp_path / "a.jsonl"), str(tmp_path / "b.jsonl")
    _write_trace(a, dispatch_ms=2.0)
    recs = [json.loads(l) for l in open(a)]
    with open(b, "w") as fh:
        for r in recs:
            r["introspection"]["mfu"] = 0.2  # halved MFU, times unchanged
            fh.write(json.dumps(r) + "\n")
    rc = trace_diff.main([a, b, "--json"])
    assert rc == 1
    report = json.loads(capsys.readouterr().out)
    assert {r["metric"] for r in report["regressions"]} == {"mfu"}


def test_trace_diff_usage_errors(tmp_path, capsys):
    from deepspeed_tpu.tools import trace_diff

    empty = str(tmp_path / "empty.jsonl")
    open(empty, "w").close()
    a = str(tmp_path / "a.jsonl")
    _write_trace(a, 2.0)
    assert trace_diff.main([a, empty]) == 2
    assert trace_diff.main([str(tmp_path / "missing.jsonl"), a]) == 2


# ---------------------------------------------------------------------------
# flops_profiler reconciliation (satellite: agree within 5% on gpt2)
# ---------------------------------------------------------------------------

def test_flops_profiler_verify_against_hlo_gpt2():
    from deepspeed_tpu.models import gpt2
    from deepspeed_tpu.profiling.flops_profiler import verify_against_hlo

    cfg = gpt2.get_config("gpt2-tiny", attn_impl="jnp")
    module = gpt2.make_module(cfg)
    params = gpt2.init_params(cfg, jax.random.PRNGKey(0))
    batch = {
        "input_ids": np.arange(2 * 32, dtype=np.int32).reshape(2, 32) % cfg.vocab_size
    }
    rng = jax.random.PRNGKey(1)

    def loss(params, batch):
        l, _ = module.loss_fn(params, batch, rng, True)
        return l

    out = verify_against_hlo(loss, params, batch)
    assert out["xla_flops"] > 0 and out["hlo_flops"] > 0
    assert out["agree"], f"rel_err={out['rel_err']:.4f}"
    # gpt2 attention runs through ops/attention.py → categorized
    assert out["categories"]["attention"]["flops"] > 0


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
