"""The ``xing4_0`` configuration's benchmark files on the CPU: its stand-in
cell through the harness (``tiny.make`` finds it by its runner), the float32
reference against controls at the small size, the new reader on hand-made
rows, and the cost function against a hand count. Nothing here is a device
number."""

import json
import re
from types import SimpleNamespace

import pytest

from perfbench import kernel_costs as kc
from perfbench import kernel_costs_xing4 as kx
from perfbench import reference_xing4 as reference
from perfbench import run
from perfbench.manifest import Manifest
from perfbench.peaks import peak_for

from . import tiny

CELL = "serve-xing4-gen-backlog"
CONFIG = "xing4.0-29b-a4b-ep8-l20-serve-1chip"
SEED = 2**31 + 157
REPO = tiny.REPO
X4 = ("part_hc_share", "hc_mix_roofline")       # the cell's own entries, in the order PR 57 appended them
MS4 = ("mla_decode_roofline", "mla_chunk_roofline", "moe_weight_stream_roofline", "moe_load_max_over_mean")
SHARED = ("gen_tok_s", "copy_layout_share", "srv_step_host_p50_s", "decode_slots_active", "dispatched_ahead_share",
          "idle_outside_spans_share", "part_unattributed_share", "part_attn_share",
          "part_moe_route_share", "moe_layer_share", "moe_streamed_per_hit", "mla_attention_share", "plain_step_p50_s",
          "mixed_step_p50_s", "chunk_step_p50_s", "paged_walk_share")
JOINED = {n + ".backlog" for n in SHARED} | {n + ".ms4" for n in MS4}


@pytest.fixture(scope="module")
def manifest(tmp_path_factory):
    m = tiny.make(tmp_path_factory.mktemp("bench"))
    m.validate()
    return m


@pytest.fixture(scope="module")
def runner(manifest):
    """The stand-in cell's runner, set up: the server and its warm-up requests."""
    from perfbench.context import Context

    cell = manifest.cell(CELL)
    c = Context(cell=cell, config=manifest.config(cell["config"]), traffic=manifest.traffic(cell["traffic"]), chips=1,
                peak=peak_for("TPU v5 lite"))
    r = manifest.runner(c.config["runner"]).Runner(c, SEED, [], lambda n: None, lambda msg: None)
    r.setup()
    return r


def test_the_manifest_validates_with_the_cell_in_every_list_it_joined(table):
    m = table
    d = m.doc
    cell = m.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "gen-backlog-s64", 1)
    assert "40 four-stream mixings" in cell["why"] and "20 of 40 layers" in cell["why"] and "8x its share" in cell["why"]
    entry = m.config_entry(CONFIG)
    assert entry["file"] == f"perfbench/configs/{CONFIG}.json"
    assert entry["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size", "num_nextn_predict_layers"]
    assert entry["source"] == "https://huggingface.co/XingChen-AGI/Xing4.0-29B-A4B/blob/main/config.json"
    assert sum(w["chips"] == 4 for w in d["workloads"]) <= max(1, len(d["workloads"]) // 4)
    by_name = {x["name"]: x for x in d["per_layer"]}
    tiny.check_cell_keeps(m, CELL, [n + ".x4" for n in X4], JOINED)      # at least these, by name
    shares = [x for x in d["per_layer"] if "roofline" in x["name"] and CELL in x.get("workloads", ())]
    assert all(x["unit"] == "%" and x["source"] == "device_trace" for x in shares) and len(shares) >= 4
    assert m.metric_spec("part_hc_share.x4")["args"] == {"parts": ["hc.mix"], "of": "busy"}
    assert by_name["hc_mix_roofline.x4"]["layer"] == "kernels (ops/pallas/)"
    assert by_name["part_hc_share.x4"]["layer"] == "serve programs (serving/model.py)"


def test_the_patterns_find_this_programs_kernels_by_the_names_they_have_in_a_trace():
    from deepspeed_tpu.ops.pallas import grouped_experts, hyper_connection
    from deepspeed_tpu.telemetry import parts

    m = Manifest(REPO)
    pattern = m.metric_spec("hc_mix_roofline.x4")["args"]["pattern"]
    assert re.search(pattern, f"%{hyper_connection.PRE_KERNEL}.12 = (bf16[64,3584]{{1,0}}, f32[64,24]{{1,0}}) custom-call(")
    assert re.search(pattern, f"%{hyper_connection.POST_KERNEL} = bf16[320,14336]{{1,0}} custom-call(")
    assert not re.search(pattern, "%hc_pre_fusion.3 = ") and not re.search(pattern, "%mla_paged_decode.4 = ")
    assert re.search(m.metric_spec("moe_weight_stream_roofline.ms4")["args"]["pattern"], grouped_experts.KERNEL_NAME)
    assert "hc.mix" in parts.PARTS and parts.KERNEL_FILES["ops/pallas/hyper_connection.py"] == "hc.mix"


def test_traced_and_untraced_stand_in_runs_are_correct_and_print_every_metric_that_needs_no_device(manifest, tmp_path_factory):
    for traced in (True, False):
        out, ctx = run.run_cell(manifest, CELL, SEED + traced, 1.0, traced, require_tpu=False,
                                trace_dir=str(tmp_path_factory.mktemp("trace")))
        line = json.loads(json.dumps(out))
        assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
        assert line["notes"]["compilations_in_window"] == 0 and "leak" not in line["notes"]
        ref = line["notes"]["reference"]
        assert ref["max_logit_gap"] <= ref["margin"] and ref["positions"] == 16 and ref["mean_logit_gap"] <= ref["mean_gap_limit"]
        if not traced:      # per-layer metrics are a traced run's: the line holds what the driver compares
            assert set(out["metrics"]) == {"serve_tok_s", "setup_s"} and out["metrics"]["serve_tok_s"]["value"] > 0
            continue
        listed = {m["name"]: m for m in manifest.metrics_for(CELL, "per_layer")}
        assert JOINED | {n + ".x4" for n in X4} <= set(listed)
        host = {n for n, m in listed.items() if m["source"] != "device_trace"}
        assert {"gen_tok_s.backlog", "decode_slots_active.backlog", "srv_step_host_p50_s.backlog", "moe_streamed_per_hit.backlog",
                "dispatched_ahead_share.backlog", "moe_load_max_over_mean.ms4", "setup_compile_s", "setup_trace_lower_s",
                "setup_params_s"} <= host
        assert set(out["metrics"]) == host       # no device plane on the CPU: the device readers found nothing, and said so
        assert out["metrics"]["moe_load_max_over_mean.ms4"]["value"] >= 1.0
        spec = manifest.metric_spec("serve_tok_s")
        assert manifest.reader(spec["reader"]).read(ctx, **spec.get("args", {})) > 0 and ctx.window[0] > 0


def test_the_program_records_a_rows_bytes_where_the_reader_looks_for_them(runner):
    from deepspeed_tpu.telemetry import spans

    prog = [p[3] for p in spans.phases() if p[0] == "ds.init.programs" and "hc_row_bytes" in p[3]][-1]
    assert prog["hc_row_bytes"] == 4 * 64 * 4 == runner.srv.metrics.gauge("serving_hc_row_bytes", "").value()


def test_reference_catches_controls_at_the_small_size(runner):
    """The served tokens read against a reference with one thing changed: NOT
    correct by one of the stand-in's two limits. ``stat_E``, ``maps_bf16`` and
    ``sinkhorn_1`` need the chip's hundreds of positions and 40 sub-blocks to
    show and are read there (PERF.md, PR 57)."""
    from perfbench.tools import control_xing4 as control

    out = control.readings(runner, ["hres_identity", "hpost_one", "static_maps", "rope_score", "scale_1", "experts:1"])
    assert out["served_correct"] and out["controls_read_correct"] == [], out
    assert out["served"]["positions"] == 16 and out["served"]["max_logit_gap"] <= out["margin"]


def test_the_configuration_file_holds_every_number_of_the_catalog_row_and_cuts_depth_experts_vocabulary_and_mtp_alone():
    m = Manifest(REPO)
    c = m.config(CONFIG)
    row = {"attention_bias": False, "ep_size": 1, "first_k_dense_replace": 2, "hidden_act": "silu", "hidden_size": 3584,
           "intermediate_size": 9216, "kv_lora_rank": 512, "max_position_embeddings": 262144, "model_type": "xing4_0",
           "moe_intermediate_size": 1024, "moe_layer_freq": 1, "n_group": 1, "n_shared_experts": 1, "norm_topk_prob": True,
           "num_attention_heads": 32, "num_experts_per_tok": 4, "num_key_value_heads": 32, "hc_mult": 4, "hc_sinkhorn_iters": 20,
           "hc_eps": 1e-06, "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30, "q_lora_rank": 768, "qk_nope_head_dim": 128,
           "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06, "rope_theta": 10000,
           "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1, "mscale_all_dim": 1,
                            "original_max_position_embeddings": 4096, "type": "yarn"},
           "routed_scaling_factor": 2, "scoring_func": "sigmoid", "tie_word_embeddings": False, "topk_group": 1,
           "topk_method": "noaux_tc", "v_head_dim": 128}
    assert {k: c[k] for k in row} == row
    assert (c["num_hidden_layers"], c["n_routed_experts"], c["vocab_size"], c["num_nextn_predict_layers"]) == (20, 8, 16384, 0)
    assert c["published"] == {"num_hidden_layers": 40, "n_routed_experts": 64, "vocab_size": 131072, "num_nextn_predict_layers": 1}
    assert c["expert_share"] == {"chips": 8, "index": 0} and c["dtype"] == "bfloat16" and c["runner"] == "serve_xing4"
    said = " ".join(c["assumed"])
    for word in ("arXiv 2512.24880", "NO gain", "INSIDE the square root", "COLUMNS normalised before rows", "pre-norm is KEPT",
                 "copies in", "plain sum out", "float32 from the bf16 stream", "std 1 / sqrt(n E)", "interleaved rotary pairs",
                 "[k_nope 128 | v 128]", "RMS norms with a gain on both latents", "multi-token-prediction"):
        assert word in said, word
    assert "two pipeline stages of eight" in c["deployment"] and "5.37 GB" in c["deployment"] and "5.88 GB" in c["deployment"]
    sv = c["serving"]
    assert sv == {"max_slots": 64, "page_size": 128, "num_pages": 1793, "max_prompt_len": 3072, "max_new_tokens": 512,
                  "prefill_chunk_tokens": 256, "max_queue_depth": 4096, "temperature": 0.0}
    assert sv["num_pages"] == sv["max_slots"] * -(-(sv["max_prompt_len"] + sv["max_new_tokens"]) // sv["page_size"]) + 1
    assert (c["warmup_short_prompt"], c["warmup_long_prompt"], c["warmup_new_tokens"]) == (96, 1024, 256)
    ref = c["reference"]
    assert min(ref["logit_margin"], ref["mean_gap_limit"]) > 0 and "PLACEHOLDER" not in ref["why"]
    # the resident bytes the cell was sized by
    E, H, K = 3584, 32, 24
    attn = E * 768 + 768 + 768 * H * 192 + E * 576 + 512 + 512 * H * 256 + H * 128 * E
    hc = 2 * (K * 4 * E + K + 3)
    dense = attn + hc + 2 * E + 3 * E * 9216
    sparse = attn + hc + 2 * E + E * 64 + 64 + 9 * 3 * E * 1024
    assert dense == pytest.approx(128.2e6, rel=2e-3) and sparse == pytest.approx(128.4e6, rel=2e-3)
    total = 2 * dense + 18 * sparse + 2 * 16384 * E + E
    assert 2 * total == pytest.approx(5.37e9, rel=2e-3)
    assert sv["num_pages"] * 128 * 20 * 640 * 2 == pytest.approx(5.88e9, rel=2e-3)


def test_the_mixings_cost_against_a_hand_count_at_the_published_shapes():
    n, E, K = 4, 3584, 24
    # a decode step of 64 real rows through 40 sub-blocks: each sub-block's phi from HBM, the rows stay on the chip
    f, b = kx.hc_mix(64, 1, 40, n, E, 2)
    assert b == 40 * 24 * 28672 and b == pytest.approx(27.5e6, rel=1e-2)
    assert f == 64 * 40 * (2 * n * E * K + 3 * 2 * n * E + 2 * n * n * E) and f == pytest.approx(2.27e9, rel=1e-2)
    least, bound = kc.min_seconds(f, b, peak_for("TPU v5 lite"))
    assert bound == "memory" and least == pytest.approx(33.6e-6, rel=2e-2)          # 0.84 us a sub-block
    # a mixed step's 320 rows: the operations lead
    assert kc.min_seconds(*kx.hc_mix(320, 1, 40, n, E, 2), peak_for("TPU v5 lite"))[1] == "compute"
    # two calls carry phi twice, the rows' operations once each
    f2, b2 = kx.hc_mix(64 + 320, 2, 40, n, E, 2)
    assert b2 == 2 * b and f2 == 6 * f


def _launch(kind, rows, tokens):
    return SimpleNamespace(kind=kind, rows=rows, tokens=tokens)


def test_the_roofline_reader_on_hand_made_launches(monkeypatch):
    from perfbench import launches, program_spans
    from perfbench.metrics.readers import hc_mix_roofline as reader

    cfg = Manifest(REPO).config(CONFIG)
    peak = peak_for("TPU v5 lite")
    rows = [_launch("plain", 60, 0)] * 3 + [_launch("mixed", 62, 256)]
    phases = [("ds.init.programs", 0.0, 1.0, {"hc_row_bytes": 4 * 3584 * 2})]
    monkeypatch.setattr(program_spans, "program", lambda: SimpleNamespace(phases=lambda: phases))
    monkeypatch.setattr(launches, "rows", lambda ctx: rows)
    ctx = SimpleNamespace(config=cfg, trace=SimpleNamespace(seconds_matching=lambda p: 0.01), peak=peak, extra={})
    f, b = kx.hc_mix(3 * 60 + 62 + 256, 4, 40, 4, 3584, 2)
    assert reader.read(ctx, "x") == pytest.approx(100.0 * kc.min_seconds(f, b, peak)[0] / 0.01)
    assert 0 < reader.read(ctx, "x") < 100
    # a program without the gauge (the parent), without launches, without the kernels, or another family: nothing
    monkeypatch.setattr(program_spans, "program", lambda: SimpleNamespace(phases=lambda: [("ds.init.programs", 0, 1, {})]))
    assert reader.read(ctx, "x") is None
    monkeypatch.setattr(program_spans, "program", lambda: SimpleNamespace(phases=lambda: phases))
    monkeypatch.setattr(launches, "rows", lambda ctx: None)
    assert reader.read(ctx, "x") is None
    monkeypatch.setattr(launches, "rows", lambda ctx: rows)
    assert reader.read(SimpleNamespace(config=cfg, trace=SimpleNamespace(seconds_matching=lambda p: 0.0), peak=peak, extra={}), "x") is None
    other = dict(cfg)
    other.pop("hc_mult")
    assert reader.read(SimpleNamespace(config=other, trace=ctx.trace, peak=peak, extra={}), "x") is None
    assert reader.read(SimpleNamespace(config=cfg, trace=None, peak=peak, extra={}), "x") is None


def test_the_reference_imports_nothing_from_the_program():
    import inspect

    src = inspect.getsource(reference)
    assert "deepspeed_tpu" not in src.replace("``deepspeed_tpu``", "") and "pallas" not in src
