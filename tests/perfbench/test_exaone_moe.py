"""The ``exaone_moe`` configuration's benchmark files on the CPU: its stand-in
cell through the harness (``tiny.make`` finds it by its runner), the float32
reference against each control at the small size, the new readers on
hand-made spans, and the cost functions (a share over 100% is impossible at
any input the cell can produce). Nothing here is a device number."""

import json
import os
from types import SimpleNamespace

import numpy as np
import pytest

from perfbench import kernel_costs as kc
from perfbench import kernel_costs_exaone_moe as kx
from perfbench import run
from perfbench.manifest import Manifest
from perfbench.peaks import peak_for

from . import tiny

CELL = "serve-kexaone-gen-backlog"
SEED = 2**31 + 77
REPO = tiny.REPO


@pytest.fixture(scope="module")
def manifest(tmp_path_factory):
    m = tiny.make(tmp_path_factory.mktemp("bench"))
    m.validate()
    return m


@pytest.fixture(scope="module")
def results(manifest, tmp_path_factory):
    trace_dir = str(tmp_path_factory.mktemp("trace"))
    return {t: run.run_cell(manifest, CELL, SEED, 1.0, t, require_tpu=False, trace_dir=trace_dir) for t in (False, True)}


def test_the_stand_in_cell_is_in_the_tiny_copy(manifest):
    assert CELL in [w["name"] for w in manifest.doc["workloads"]]
    assert manifest.config(manifest.cell(CELL)["config"])["runner"] == "serve_exaone_moe"


@pytest.mark.parametrize("trace", [False, True])
def test_stand_in_cell_runs_correct_with_nothing_compiled_in_the_window(results, trace):
    out, _ = results[trace]
    line = json.loads(json.dumps(out))
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert line["notes"]["compilations_in_window"] == 0 and "leak" not in line["notes"]
    ref = line["notes"]["reference"]
    assert ref["max_logit_gap"] <= ref["margin"] and ref["positions"] == 16 and ref["mean_logit_gap"] <= ref["max_logit_gap"]


def test_untraced_run_reports_serve_tok_s_and_setup(manifest, results):
    out, _ = results[False]
    assert set(out["metrics"]) == {m["name"] for m in manifest.metrics_for(CELL, "end_to_end")} == {"serve_tok_s", "setup_s"}
    assert all(v["value"] > 0 for v in out["metrics"].values())


def test_traced_run_reports_the_metrics_that_need_no_device(manifest, results):
    out, ctx = results[True]
    listed = {m["name"]: m for m in manifest.metrics_for(CELL, "per_layer")}
    host = {n for n, m in listed.items() if m["source"] != "device_trace"}
    assert {"moe_load_max_over_mean.kx", "decode_slots_active.backlog", "gen_tok_s.backlog"} <= host <= set(out["metrics"])
    assert not (set(out["metrics"]) - host)      # no device plane on the CPU: those readers found nothing
    assert out["metrics"]["moe_load_max_over_mean.kx"]["value"] >= 1.0


@pytest.mark.parametrize("skip", ["experts:1", "experts:2", "window", "rotary"])
def test_reference_catches_each_control_at_the_small_size(manifest, skip):
    """The served tokens read against a reference with one thing left out:
    the gap is far over the stand-in's margin."""
    from perfbench.context import Context

    cell = manifest.cell(CELL)
    c = Context(cell=cell, config=manifest.config(cell["config"]), traffic=manifest.traffic(cell["traffic"]), chips=1,
                peak=peak_for("TPU v5 lite"))
    r = manifest.runner(c.config["runner"]).Runner(c, SEED, [], lambda n: None, lambda msg: None)
    r.setup()
    ok, notes = r.reference_check()
    assert ok and notes["max_logit_gap"] <= notes["margin"]
    ok, notes = r.reference_check(skip=skip)
    assert not ok and notes["max_logit_gap"] > 10 * notes["margin"]


# -- readers on hand-made spans -------------------------------------------------

def _ctx(recs, window=(0.0, 10.0), traced=(5.0, 10.0), ops_s=0.01):
    cfg = Manifest(REPO).config("k-exaone-236b-ep8-serve-1chip")
    trace = SimpleNamespace(seconds_matching=lambda pattern: ops_s)
    return SimpleNamespace(config=cfg, window=window, traced=traced, trace=trace, peak=peak_for("TPU v5 lite")), recs


@pytest.fixture
def spans_ring(monkeypatch):
    """Feeds the readers a list of (name, t0, t1, attrs) as the program's ring."""
    from perfbench import program_spans

    box = {"recs": []}
    monkeypatch.setattr(program_spans, "program",
                        lambda: SimpleNamespace(snapshot=lambda since=0.0: [r for r in box["recs"] if r[1] >= since]))
    return box


def test_load_max_over_mean_is_one_for_an_even_split(spans_ring):
    reader = Manifest(REPO).reader("moe_load_max_over_mean")
    ctx, _ = _ctx(None)
    spans_ring["recs"] = [("ds.serve.emit", 1.0, 1.1, {"moe_pairs_held": 4 * 16 * 4, "moe_load_max": 4}),
                          ("ds.serve.emit", 2.0, 2.1, {"moe_pairs_held": 4 * 16 * 4, "moe_load_max": 12})]
    assert reader.read(ctx) == pytest.approx((1.0 + 3.0) / 2)
    spans_ring["recs"] = [("ds.serve.emit", 1.0, 1.1, {"tokens": 3})]       # a program without the attributes
    assert reader.read(ctx) is None


def test_weight_stream_roofline_charges_what_was_hit(spans_ring):
    reader = Manifest(REPO).reader("moe_weight_stream_roofline")
    emit = {"moe_experts_hit": 64, "moe_pairs_held": 256, "moe_load_max": 9, "moe_pairs_routed": 2048}
    spans_ring["recs"] = [("ds.serve.emit", 6.0, 6.01, emit)]
    ctx, _ = _ctx(None, ops_s=0.02)
    f, b = kx.routed_experts(64, 256, 64 * 4, 6144, 2048, 2)
    assert reader.read(ctx, pattern="x") == pytest.approx(100.0 * kc.min_seconds(f, b, ctx.peak)[0] / 0.02)
    # chunk calls in the traced part are charged the window's mean a call, capped at every held expert
    spans_ring["recs"] += [("ds.serve.chunk", 1.0, 1.1, {"chunks": 1, "moe_calls": 4, "moe_experts_hit": 4 * 64,
                                                        "moe_pairs_held": 4 * 1024}),
                           ("ds.serve.chunk", 7.0, 7.1, {"chunks": 2})]
    f2, b2 = kx.routed_experts(64 + 2 * 64, 256 + 2 * 1024, 64 * 4 + 2 * 256 * 4, 6144, 2048, 2)
    assert reader.read(ctx, pattern="x") == pytest.approx(100.0 * kc.min_seconds(f2, b2, ctx.peak)[0] / 0.02)
    spans_ring["recs"] = [("ds.serve.emit", 6.0, 6.01, {"tokens": 3})]
    assert reader.read(ctx, pattern="x") is None


def test_mixed_decode_roofline_reads_the_programs_count(spans_ring):
    reader = Manifest(REPO).reader("paged_decode_roofline_mixed")
    spans_ring["recs"] = [("ds.serve.decode.dispatch", 6.0, 6.01, {"attended": 20000, "active": 64, "pages": 1})]
    ctx, _ = _ctx(None, ops_s=0.001)
    f, b = kx.paged_decode_keys(5 * 20000, 8, 64, 128, 2, 5 * 64)
    assert reader.read(ctx, pattern="x") == pytest.approx(100.0 * kc.min_seconds(f, b, ctx.peak)[0] / 0.001)
    spans_ring["recs"] = []
    assert reader.read(ctx, pattern="x") is None


# -- costs: never more than an implementation must move ---------------------------

@pytest.mark.parametrize("hit,pairs,tokens", [(64, 256, 256), (1, 1, 64), (64, 2048, 1024)])
def test_routed_experts_cost_is_below_what_streaming_every_held_expert_takes(hit, pairs, tokens):
    """The cell's expert layer reads all 16 held experts of a layer a call;
    the bytes charged are those of the experts hit, at most that."""
    cfg = Manifest(REPO).config("k-exaone-236b-ep8-serve-1chip")
    E, F = cfg["hidden_size"], cfg["moe_intermediate_size"]
    f, b = kx.routed_experts(hit, pairs, tokens, E, F, 2)
    all_held = 16 * kx.sparse_layers(cfg) * 3 * E * F * 2 + 2 * tokens * E * 2
    assert b <= all_held and f == 6 * pairs * E * F
    assert kx.sparse_layers(cfg) == 4


def test_decode_keys_cost_counts_kv_heads_not_query_heads():
    f, b = kx.paged_decode_keys(1000, 8, 64, 128, 2, 10)
    assert b == 2 * 1000 * 8 * 128 * 2 + 2 * 10 * 64 * 128 * 2
    assert f == 4 * 1000 * 64 * 128
    # a window layer's keys are capped by the program's count: 64 slots, window 128
    full, win = kx.paged_decode_keys(64 * 3000, 8, 64, 128, 2, 64), kx.paged_decode_keys(64 * 128, 8, 64, 128, 2, 64)
    assert win[1] < full[1] / 20
