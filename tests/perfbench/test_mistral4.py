"""The ``mistral4`` configuration's benchmark files on the CPU: its stand-in
cell through the harness (``tiny.make`` finds it by its runner), the float32
reference (expanded) against each control at the small size, the new readers
on hand-made spans, and the cost functions (a share over 100% is impossible at
any input the cell can produce). Nothing here is a device number."""

import json
from types import SimpleNamespace

import pytest

from perfbench import kernel_costs as kc
from perfbench import kernel_costs_mistral4 as km
from perfbench import run
from perfbench.manifest import Manifest
from perfbench.peaks import peak_for

from . import tiny

CELL = "serve-ms4-longdoc-backlog"
CONFIG = "mistral-small-4-119b-ep8-serve-1chip"
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
    assert manifest.config(manifest.cell(CELL)["config"])["runner"] == "serve_mistral4"


@pytest.mark.parametrize("trace", [False, True])
def test_stand_in_cell_runs_correct_with_nothing_compiled_in_the_window(results, trace):
    out, _ = results[trace]
    line = json.loads(json.dumps(out))
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert line["notes"]["compilations_in_window"] == 0 and "leak" not in line["notes"]
    ref = line["notes"]["reference"]
    assert ref["max_logit_gap"] <= ref["margin"] and ref["positions"] == 16 and ref["mean_logit_gap"] <= ref["mean_gap_limit"]


def test_untraced_run_reports_serve_tok_s_and_setup(manifest, results):
    out, _ = results[False]
    assert set(out["metrics"]) == {m["name"] for m in manifest.metrics_for(CELL, "end_to_end")} == {"serve_tok_s", "setup_s"}
    assert all(v["value"] > 0 for v in out["metrics"].values())


def test_traced_run_reports_the_metrics_that_need_no_device(manifest, results):
    out, _ = results[True]
    listed = {m["name"]: m for m in manifest.metrics_for(CELL, "per_layer")}
    host = {n for n, m in listed.items() if m["source"] != "device_trace"}
    assert {"moe_load_max_over_mean.ms4", "decode_slots_active.backlog", "gen_tok_s.backlog"} <= host <= set(out["metrics"])
    assert not (set(out["metrics"]) - host)      # no device plane on the CPU: those readers found nothing
    assert out["metrics"]["moe_load_max_over_mean.ms4"]["value"] >= 1.0


@pytest.mark.parametrize("skip", ["rope_score", "yarn", "qscale", "latent_norm", "experts:1", "fp8_rows", "int8_rows"])
def test_reference_catches_each_control_at_the_small_size(manifest, skip):
    """The served tokens read against a reference with one thing changed: the
    run reads NOT correct by one of the stand-in's two limits."""
    from perfbench.context import Context

    cell = manifest.cell(CELL)
    c = Context(cell=cell, config=manifest.config(cell["config"]), traffic=manifest.traffic(cell["traffic"]), chips=1,
                peak=peak_for("TPU v5 lite"))
    r = manifest.runner(c.config["runner"]).Runner(c, SEED, [], lambda n: None, lambda msg: None)
    r.setup()
    ok, notes = r.reference_check()
    assert ok and notes["max_logit_gap"] <= notes["margin"] and notes["mean_logit_gap"] <= notes["mean_gap_limit"]
    ok, notes = r.reference_check(skip=skip)
    assert not ok and (notes["max_logit_gap"] > notes["margin"] or notes["mean_logit_gap"] > notes["mean_gap_limit"])


def test_the_configuration_file_holds_the_published_widths_and_the_cut():
    m = Manifest(REPO)
    c, entry = m.config(CONFIG), m.config_entry(CONFIG)
    assert entry["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert c["published"] == {"num_hidden_layers": 36, "n_routed_experts": 128, "vocab_size": 131072}
    assert (c["num_hidden_layers"], c["n_routed_experts"], c["vocab_size"]) == (6, 16, 16384)
    assert (c["hidden_size"], c["q_lora_rank"], c["kv_lora_rank"], c["qk_nope_head_dim"], c["qk_rope_head_dim"],
            c["v_head_dim"], c["moe_intermediate_size"], c["num_experts_per_tok"]) == (4096, 1024, 256, 64, 64, 128, 2048, 4)
    sv, tr = c["serving"], m.traffic(m.cell(CELL)["traffic"])
    slot_tokens = sv["max_prompt_len"] + sv["max_new_tokens"]
    assert sv["num_pages"] == sv["max_slots"] * -(-slot_tokens // sv["page_size"]) + 1
    comp = tr["components"][0]
    assert comp["prompt_len"] == {"dist": "lognormal", "median": 12288, "sigma": 0.4, "min": 6144, "max": 24576}
    assert tr["block_requests"] == sv["max_slots"] == 48 and tr["queue_depth"] == 2 and comp["new_tokens"]["value"] == 256
    assert c["warmup_long_prompt"] > c["rope_parameters"]["original_max_position_embeddings"]


# -- readers on hand-made spans -------------------------------------------------

def _ctx(ops_s=0.01, window=(0.0, 10.0), traced=(5.0, 10.0)):
    cfg = Manifest(REPO).config(CONFIG)
    trace = SimpleNamespace(seconds_matching=lambda pattern: ops_s)
    return SimpleNamespace(config=cfg, window=window, traced=traced, trace=trace, peak=peak_for("TPU v5 lite"))


@pytest.fixture
def spans_ring(monkeypatch):
    """Feeds the readers a list of (name, t0, t1, attrs) as the program's ring."""
    from perfbench import program_spans

    box = {"recs": []}
    monkeypatch.setattr(program_spans, "program",
                        lambda: SimpleNamespace(snapshot=lambda since=0.0: [r for r in box["recs"] if r[1] >= since]))
    return box


def test_mla_decode_roofline_reads_the_programs_rows(spans_ring):
    reader = Manifest(REPO).reader("mla_roofline")
    spans_ring["recs"] = [("ds.serve.decode.dispatch", 6.0, 6.01, {"attended": 650000, "active": 48, "pages": 1})]
    ctx = _ctx(ops_s=0.01)
    f, b = km.latent_attention(6 * 650000, 6 * 650000, 6 * 48, 32, 320, 256, 2)
    assert b == 6 * 650000 * 640 + 6 * 48 * 32 * 576 * 2
    least, bound = kc.min_seconds(f, b, ctx.peak)
    assert bound == "memory"
    assert reader.read(ctx, pattern="x", kind="decode") == pytest.approx(100.0 * least / 0.01)
    spans_ring["recs"] = [("ds.serve.decode.dispatch", 6.0, 6.01, {"active": 3})]      # a program without the count
    assert reader.read(ctx, pattern="x", kind="decode") is None


def test_mla_chunk_roofline_counts_the_triangle_and_is_compute_bound(spans_ring):
    reader = Manifest(REPO).reader("mla_roofline")
    pairs = 1024 * 6144 + 1024 * 1025 // 2
    spans_ring["recs"] = [("ds.serve.chunk", 6.0, 6.03, {"chunks": 1, "tokens": 1024, "attended": pairs})]
    ctx = _ctx(ops_s=0.02)
    f, b = km.latent_attention(6 * pairs, 6 * pairs / 1024, 6 * 1024, 32, 320, 256, 2)
    least, bound = kc.min_seconds(f, b, ctx.peak)
    assert bound == "compute" and f == 2 * 576 * 32 * 6 * pairs
    assert reader.read(ctx, pattern="x", kind="chunk") == pytest.approx(100.0 * least / 0.02)
    spans_ring["recs"] = [("ds.serve.chunk", 6.0, 6.03, {"chunks": 1, "tokens": 1024})]   # the parent's span: no attended
    assert reader.read(ctx, pattern="x", kind="chunk") is None


def test_load_max_over_mean_and_weight_stream_read_this_files_keys(spans_ring):
    m = Manifest(REPO)
    ctx = _ctx(ops_s=0.02)
    spans_ring["recs"] = [("ds.serve.emit", 6.0, 6.01, {"moe_pairs_held": 2 * 16 * 6, "moe_load_max": 6,
                                                       "moe_experts_hit": 75, "moe_pairs_routed": 1152})]
    assert m.reader("moe_load_max_over_mean_ms4").read(ctx) == pytest.approx(3.0)
    f, b = km.routed_experts(75, 192, 48 * 6, 4096, 2048, 2)
    assert m.reader("moe_weight_stream_roofline_ms4").read(ctx, pattern="x") == pytest.approx(
        100.0 * kc.min_seconds(f, b, ctx.peak)[0] / 0.02)
    # chunk calls in the traced part are charged the window's mean a call, capped at every held expert
    spans_ring["recs"] += [("ds.serve.chunk", 1.0, 1.1, {"chunks": 1, "moe_calls": 2, "moe_experts_hit": 2 * 96,
                                                        "moe_pairs_held": 2 * 3000}),
                           ("ds.serve.chunk", 7.0, 7.1, {"chunks": 3})]
    f2, b2 = km.routed_experts(75 + 3 * 96, 192 + 3 * 3000, 48 * 6 + 3 * 1024 * 6, 4096, 2048, 2)
    assert m.reader("moe_weight_stream_roofline_ms4").read(ctx, pattern="x") == pytest.approx(
        100.0 * kc.min_seconds(f2, b2, ctx.peak)[0] / 0.02)
    spans_ring["recs"] = [("ds.serve.emit", 6.0, 6.01, {"tokens": 3})]
    assert m.reader("moe_load_max_over_mean_ms4").read(ctx) is None
    assert m.reader("moe_weight_stream_roofline_ms4").read(ctx, pattern="x") is None


# -- costs: never more than an implementation must move ---------------------------

@pytest.mark.parametrize("ctx_before,tokens", [(0, 1024), (6144, 1024), (23552, 1024), (12288, 37)])
def test_chunk_attention_cost_is_below_what_the_kernel_does(ctx_before, tokens):
    """The kernel multiplies whole blocks of 384-lane rows for all 1 024 rows
    of a call; the cost charges the real tokens' triangle at 320 + 256 values
    a pair and the rows once for every 1 024 queries."""
    cfg = Manifest(REPO).config(CONFIG)
    row, val = km.widths(cfg)
    assert (row, val) == (320, 256) and km.sparse_layers(cfg) == 6
    pairs = tokens * ctx_before + tokens * (tokens + 1) // 2
    f, b = km.latent_attention(pairs, pairs / 1024, tokens, 32, row, val, 2)
    done = 1024 * (ctx_before + 1024) * 2 * (384 + 256) * 32           # every row of the call against every key it reaches
    assert f <= done and b <= (ctx_before + 1024) * 768 + 1024 * 32 * (384 + 256) * 2


@pytest.mark.parametrize("rows,slots", [(48 * 13500, 48), (6144, 1), (48 * 24832, 48)])
def test_decode_attention_cost_counts_the_unpadded_row_once(rows, slots):
    f, b = km.latent_attention(rows, rows, slots, 32, 320, 256, 2)
    assert b == rows * 640 + slots * 32 * 576 * 2 and b < rows * 768 + slots * 32 * 640 * 2
    assert f == rows * 2 * 576 * 32
