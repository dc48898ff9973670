"""The ``bailing_hybrid`` configuration's (Ling-3.0-flash) benchmark files on
the CPU: its stand-in cell through the harness (``tiny.make`` finds it by its
runner), the float32 reference against controls at the small size, the new
readers on hand-made spans, and the cost functions against hand counts.
Nothing here is a device number."""

import json
import re
from types import SimpleNamespace

import pytest

from perfbench import kernel_costs as kc
from perfbench import kernel_costs_ling3 as kl
from perfbench import reference_ling3 as reference
from perfbench import run
from perfbench.manifest import Manifest
from perfbench.peaks import peak_for

from . import tiny

CELL = "serve-ling3-reason-backlog"
CONFIG = "ling-3.0-flash-ep8-l12-serve-1chip"
SEED = 2**31 + 160
REPO = tiny.REPO
# the cell's own entries (`.l3`) and the entries of other cells it is listed in: what the cell must KEEP, found by name
L3 = ("kda_step_roofline", "kda_chunk_roofline", "lin_state_bytes_share", "mla_decode_roofline", "mla_chunk_roofline",
      "moe_group_rows_share", "moe_weight_stream_roofline", "moe_load_max_over_mean")
SHARED = ("gen_tok_s", "copy_layout_share", "srv_step_host_p50_s", "decode_slots_active", "dispatched_ahead_share",
          "idle_outside_spans_share", "part_unattributed_share", "part_attn_share", "part_moe_route_share", "moe_layer_share",
          "moe_streamed_per_hit", "mla_attention_share", "paged_walk_share", "plain_step_p50_s", "mixed_step_p50_s")
MINE = {n + ".l3" for n in L3} | {n + ".backlog" for n in SHARED} | {"part_lin_share.q3n"}


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


def test_the_manifest_validates_with_the_cell_in_the_lists_it_joined_and_the_patterns_match_the_kernels(table):
    m = table
    d = m.doc
    cell = m.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "reason-backlog-s128", 1)
    assert "128 x 10 KDA states of 2 MB" in cell["why"] and "one held group of 64 experts" in cell["why"] and len(cell["why"]) <= 200
    entry = m.config_entry(CONFIG)
    assert entry["file"] == f"perfbench/configs/{CONFIG}.json" and entry["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert entry["source"] == "https://huggingface.co/inclusionAI/Ling-3.0-flash-VL/blob/main/config.json"
    assert sum(w["chips"] == 4 for w in d["workloads"]) <= max(1, len(d["workloads"]) // 4) and len(d["per_layer"]) <= 128
    by_name = {x["name"]: x for x in d["per_layer"]}
    assert all(CELL in by_name[n]["workloads"] for n in MINE)                                   # `CELL in`, never `== [CELL]`
    assert CELL in next(x["workloads"] for x in d["end_to_end"] if x["name"] == "serve_tok_s")
    assert all(by_name[n + ".l3"]["moves"] == "serve_tok_s" for n in L3)
    # it joins no entry whose reader counts another family's layers
    assert all(CELL not in x.get("workloads", ()) for n, x in by_name.items() if n.endswith((".zaya", ".ms4", ".lcf", ".kx")))
    shares = {n: x for n, x in by_name.items() if "roofline" in n and CELL in x.get("workloads", ())}
    assert {"kda_step_roofline.l3", "kda_chunk_roofline.l3", "mla_decode_roofline.l3", "mla_chunk_roofline.l3",
            "moe_weight_stream_roofline.l3"} <= set(shares)
    assert all(x["unit"] == "%" and x["source"] == "device_trace" for x in shares.values())
    # the patterns find this program's kernels by the names they have in a trace, and not the scalar rule's
    from deepspeed_tpu.ops.pallas import gated_delta, grouped_experts
    step, chunk = (m.metric_spec(f"kda_{k}_roofline.l3")["args"]["pattern"] for k in ("step", "chunk"))
    assert re.search(step, f"%{gated_delta.KDA_STEP_KERNEL}.12 = f32[128,32,128]") and re.search(chunk, f"%{gated_delta.KDA_CHUNK_KERNEL} = (f32[32,4,64,128]")
    assert not re.search(step, f"%{gated_delta.KDA_CHUNK_KERNEL} = ") and not re.search(step, f"%{gated_delta.STEP_KERNEL}.3 = ")
    assert not re.search(m.metric_spec("gdn_step_roofline.q3n")["args"]["pattern"], f"%{gated_delta.KDA_STEP_KERNEL}.3 = ")
    assert re.search(m.metric_spec("moe_weight_stream_roofline.l3")["args"]["pattern"], grouped_experts.KERNEL_NAME)
    assert m.metric_spec("moe_group_rows_share.l3")["args"] == {"name": ["ds.serve.emit", "ds.serve.chunk"], "attr": "group_rows", "over": "rows"}


def test_traced_and_untraced_stand_in_runs_are_correct_and_print_every_metric_that_needs_no_device(manifest, tmp_path_factory):
    for traced in (False, True):      # the traced run last: its line carries the per-layer metrics
        out, ctx = run.run_cell(manifest, CELL, SEED, 1.0, traced, require_tpu=False, trace_dir=str(tmp_path_factory.mktemp("trace")))
        line = json.loads(json.dumps(out))
        assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
        assert line["notes"]["compilations_in_window"] == 0 and "leak" not in line["notes"]
        ref = line["notes"]["reference"]
        assert ref["max_logit_gap"] <= ref["margin"] and ref["positions"] == 40 and ref["mean_logit_gap"] <= ref["mean_gap_limit"]
        assert ref["handover_gap"] <= ref["handover_largest"] <= ref["handover_margin"] and ref["left_out"] < 40
    listed = {m["name"]: m for m in manifest.metrics_for(CELL, "per_layer")}
    assert MINE <= set(listed)
    host = {n for n, m in listed.items() if m["source"] != "device_trace"}
    assert {"gen_tok_s.backlog", "decode_slots_active.backlog", "srv_step_host_p50_s.backlog", "moe_streamed_per_hit.backlog",
            "dispatched_ahead_share.backlog", "paged_walk_share.backlog", "moe_load_max_over_mean.l3", "moe_group_rows_share.l3",
            "lin_state_bytes_share.l3"} | tiny.SETUP <= host
    assert set(out["metrics"]) == host       # no device plane on the CPU: the device readers found nothing, and said so
    assert out["metrics"]["moe_load_max_over_mean.l3"]["value"] >= 1.0
    assert 0 < out["metrics"]["lin_state_bytes_share.l3"]["value"] < 100
    assert 50 < out["metrics"]["moe_group_rows_share.l3"]["value"] <= 100     # the stand-in share holds 2 of 4 groups, a token keeps 2
    spec = manifest.metric_spec("serve_tok_s")
    assert manifest.reader(spec["reader"]).read(ctx, **spec.get("args", {})) > 0 and ctx.window[0] > 0


def test_reference_catches_controls_at_the_small_size_and_a_used_slot_reads_correct(runner, monkeypatch):
    """The served tokens read against a reference with one thing changed: NOT
    correct by one of the stand-in's three limits (the ``*_edge`` controls cut
    at every 8th position here, the stand-in's chunk, and at the first row a
    decode step computes). The same requests served again in used slots read
    CORRECT."""
    from perfbench.tools import control_ling3 as control

    monkeypatch.setattr(reference, "CHUNK", 8)
    skips = ["state_edge", "conv_edge", "scalar_decay", "no_bound", "no_group_limit", "group_top1", "no_head_gate", "experts:3"]
    out = control.readings(runner, skips)     # state_bf16 needs hundreds of tokens to show: the chip's
    assert out["served_correct"] and out["controls_read_correct"] == [], out
    assert out["state_edge"]["handover_gap"] > out["handover_margin"]
    assert out["reused_slot_reads_correct"] and out["reused_slot_same_tokens"] and out["reused_slot"]["positions"] == 40
    assert set(reference.SKIPS) == {"state_bf16", "scalar_decay", "no_bound", "beta_1", "no_conv", "state_edge", "conv_edge",
                                    "no_group_limit", "group_top1", "bias_in_weights", "scale_1", "no_head_gate", "rope_score"}


def test_the_configuration_file_holds_every_number_of_the_catalog_row_and_cuts_depth_experts_and_vocabulary_alone():
    m = Manifest(REPO)
    c = m.config(CONFIG)
    row = {"image_patch_token": 157157, "video_patch_token": 156909, "image_start_token": 157158, "video_start_token": 157160,
           "hidden_size": 2560, "intermediate_size": 6144, "first_k_dense_replace": 2, "max_position_embeddings": 131072,
           "moe_intermediate_size": 768, "num_experts_per_tok": 8, "num_attention_heads": 32, "q_lora_rank": None,
           "kv_lora_rank": 512, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128, "num_key_value_heads": 32,
           "rope_theta": 6000000, "rms_norm_eps": 1e-06, "head_dim": 128, "partial_rotary_factor": 0.5,
           "moe_router_enable_expert_bias": True, "routed_scaling_factor": 2.5, "n_group": 8, "topk_group": 4, "use_qk_norm": True,
           "score_function": "sigmoid", "moe_shared_expert_intermediate_size": 768, "layer_group_size": 6,
           "num_kv_heads_for_linear_attn": 0, "group_norm_size": 1, "linear_silu": True, "rotary_dim": 64, "use_mla_nope": False,
           "short_conv_kernel_size": 4, "use_nGPT": False, "scale_router_input": False, "value_norm": False, "up_proj_norm": False,
           "gated_attention_proj_granularity_type": "head_wise", "mtp_use_kda": False, "no_kda_lora": True, "use_kda_lora": False,
           "kda_safe_gate": True, "kda_lower_bound": -5, "norm_topk_prob": True}
    assert {k: c[k] for k in row} == row
    assert c["expert_swiglu_limit_list"] == [0] * 35 + [4] * 7 and c["share_expert_swiglu_limit_list"] == [0] * 34 + [5] * 6 + [7] * 2
    assert (c["num_hidden_layers"], c["num_experts"], c["vocab_size"]) == (12, 64, 19648)
    assert c["published"] == {"num_hidden_layers": 42, "num_experts": 512, "vocab_size": 157184} and 8 * 19648 == 157184
    assert c["expert_share"] == {"chips": 8, "index": 0} and c["dtype"] == "bfloat16" and c["runner"] == "serve_ling3"
    assert min(c[k] for k in ("image_patch_token", "video_patch_token", "image_start_token", "video_start_token")) >= c["vocab_size"]
    said = " ".join(c["assumed"])
    for word in ("arXiv:2510.26692", "kda_lower_bound", "no_kda_lora", "group_norm_size", "linear_silu", "use_qk_norm", "use_mla_nope",
                 "head-wise", "interleaved", "tie_word_embeddings", "expert_swiglu_limit_list", "vision tower", "4 to 4 096 tokens",
                 "top 2", "mtp_use_kda"):
        assert word in said, word
    assert "one chip of EIGHT" in c["deployment"] and "3.5 stages" in c["deployment"] and "one whole routing group" in c["deployment"]
    sv, tr = c["serving"], m.traffic(m.cell(CELL)["traffic"])
    assert sv == {"max_slots": 128, "page_size": 128, "num_pages": 6145, "max_prompt_len": 2048, "max_new_tokens": 4096,
                  "prefill_chunk_tokens": 256, "max_queue_depth": 4096, "temperature": 0.0}
    assert sv == m.config("qwen3-next-80b-ep8-l12-serve-1chip")["serving"] and tr["loop"] == "backlog"
    chunked = [n for n in (c["warmup_long_prompt"], *c["warmup_edge_prompts"]) if n > sv["prefill_chunk_tokens"]]
    assert len(chunked) == 4 and all(n % sv["prefill_chunk_tokens"] in (1, 2) for n in chunked) and c["warmup_short_prompt"] == 96
    ref = c["reference"]
    assert min(ref["tie_margin"], ref["logit_margin"], ref["mean_gap_limit"], ref["gap_cap"], ref["handover_margin"]) > 0
    assert "PLACEHOLDER" not in ref["why"] and "PLACEHOLDER" not in c["deployment"]
    # the model reads the file, and the resident bytes the cell was sized by
    from deepspeed_tpu.models import ling3
    mc = ling3.Ling3Config.from_dict(c)
    assert (mc.num_experts, mc.num_experts_published, mc.expert_chips, mc.n_layer) == (64, 512, 8, 12)
    E, V, W = 2560, 19648, 4096
    lin = E * 4 * W + E * (W + 32) + 3 * W * 4 + W * E + 32 + W + 128
    attn = E * 32 * 192 + E * 576 + 512 + 512 * 32 * 256 + 4096 * E + E * 32
    moe = E * 512 + 512 + 65 * 3 * E * 768
    total = 10 * lin + 2 * attn + 2 * 3 * E * 6144 + 10 * moe + 2 * V * E
    assert lin == pytest.approx(63.05e6, rel=1e-3) and attn == pytest.approx(31.97e6, rel=3e-3) and moe == pytest.approx(384.7e6, rel=1e-3)
    assert 2 * total == pytest.approx(9.47e9, rel=5e-3)
    assert 128 * 10 * kl.state_bytes(c) == pytest.approx(2.68e9, rel=2e-3) and kl.state_bytes(c) == 2_097_152
    assert sv["num_pages"] * 128 * 2 * 640 * 2 == pytest.approx(2.01e9, rel=2e-3)       # 576 lanes stored as 640


def test_kernel_costs_against_hand_counts_at_the_published_shapes():
    big = Manifest(REPO).config(CONFIG)
    assert kl.kinds(big) == (["lin"] * 5 + ["attn"]) * 2 and kl.heads(big) == (32, 128, 128) and kl.sparse_layers(big) == 10
    # a decode step at 128 live slots, one KDA layer: every slot's 2 MB state in and out, its row (the 32 x 128 decays among it)
    f, b = kl.kda_step(128, big)
    row = 4 * (3 * 32 * 128 + 2 * 32 * 128 + 32)
    assert b == 128 * (2 * 2_097_152 + row) and f == 128 * 32 * 8 * 128 * 128 and row == 82_048
    assert 10 * b == pytest.approx(5.47e9, rel=5e-3) and kc.min_seconds(f, b, peak_for("TPU v5 lite"))[1] == "memory"
    # a chunk call of 256 rows: the state once, the rows, four sub-chunks a head: the scalar rule's products and two [c, dk] exponentials
    f, b = kl.kda_chunk(256, 1, big)
    per_sub = 4 * 64 * 64 * 128 + 64 * 64 * 256 + 6 * 64 * 128 * 128 + 2 * 64 * 64 * 128 + 2 * 64 * 128
    assert f == 4 * 32 * per_sub and b == 2 * 2_097_152 + 256 * row
    assert kl.kda_chunk(257, 2, big)[0] == 5 * 32 * per_sub                    # a sub-chunk begun is a sub-chunk
    # what a decode step must move, by part: the states and the experts lead
    parts = kl.decode_step_bytes(big, 128, 128 * 2600, int(0.86 * 64 * 10))
    assert parts["state"] == pytest.approx(5.37e9, rel=1e-3) and parts["experts"] == pytest.approx(6.5e9, rel=2e-2)
    assert parts["keys"] == pytest.approx(0.77e9, rel=2e-2) and parts["shared_weights"] == pytest.approx(1.82e9, rel=2e-2)
    assert parts["state"] / sum(parts.values()) == pytest.approx(0.37, abs=0.02)


# -- readers on hand-made spans -------------------------------------------------------------------

def _ctx(ops_s=0.01, traced=(5.0, 10.0)):
    cfg = Manifest(REPO).config(CONFIG)
    trace = SimpleNamespace(seconds_matching=lambda pattern: ops_s)
    return SimpleNamespace(config=cfg, window=(0.0, 10.0), traced=traced, trace=trace, peak=peak_for("TPU v5 lite"))


@pytest.fixture
def spans_ring(monkeypatch):
    """Feeds the readers a list of (name, t0, t1, attrs) as the program's ring."""
    from perfbench import program_spans

    box = {"recs": [], "phases": []}
    monkeypatch.setattr(program_spans, "program",
                        lambda: SimpleNamespace(snapshot=lambda since=0.0: [r for r in box["recs"] if r[1] >= since],
                                                phases=lambda: box["phases"]))
    return box


def test_the_readers_count_this_configurations_layers_and_give_nothing_where_the_program_has_nothing(spans_ring):
    m = Manifest(REPO)
    kda = m.reader("kda_roofline")
    step_args, chunk_args = m.metric_spec("kda_step_roofline.l3")["args"], m.metric_spec("kda_chunk_roofline.l3")["args"]
    emit = {"moe_experts_hit": 550, "moe_pairs_held": 10 * 128, "moe_pairs_routed": 10 * 1024, "moe_load_max": 8,
            "moe_experts_streamed": 550, "group_rows": 640, "rows": 1280}
    spans_ring["recs"] = [("ds.serve.decode.dispatch", 6.0, 6.01, {"attended": 128 * 2600, "active": 128, "pages": 1}),
                          ("ds.serve.emit", 6.01, 6.02, emit),
                          ("ds.serve.chunk", 6.1, 6.2, {"rows_self": 513, "chunks": 1, "rode": 2, "attended": 513 * 300, "tokens": 513})]
    ctx = _ctx(ops_s=0.008)
    f, b = kl.kda_step(128, ctx.config)
    assert kda.read(ctx, **step_args) == pytest.approx(100.0 * kc.min_seconds(10 * f, 10 * b, ctx.peak)[0] / 0.008)
    assert 70 < kda.read(ctx, **step_args) < 100
    f, b = kl.kda_chunk(513, 3, ctx.config)
    assert kda.read(ctx, **chunk_args) == pytest.approx(100.0 * kc.min_seconds(10 * f, 10 * b, ctx.peak)[0] / 0.008)
    assert kda.read(_ctx(ops_s=0.0), **step_args) is None                              # a trace without the kernel
    other = _ctx()
    other.config = {k: v for k, v in other.config.items() if not k.startswith("kda_")}
    assert kda.read(other, **step_args) is None                                        # another family's configuration
    # the latent kernels over the 2 latent layers of 12, the experts over the 10 expert layers at the 64 held
    mla, args = m.reader("mla_roofline_l3"), m.metric_spec("mla_decode_roofline.l3")["args"]
    f, b = kl.latent_attention(2 * 128 * 2600, 2 * 128 * 2600, 2 * 128, 32, 576, 512, 2)
    assert mla.read(ctx, **args) == pytest.approx(100.0 * kc.min_seconds(f, b, ctx.peak)[0] / 0.008)
    assert mla.read(ctx, **m.metric_spec("mla_chunk_roofline.l3")["args"]) > 0
    stream, args = m.reader("moe_weight_stream_roofline_l3"), m.metric_spec("moe_weight_stream_roofline.l3")["args"]
    f, b = kl.routed_experts(550, 10 * 128, 10 * 128, 2560, 768, 2)
    assert stream.read(ctx, **args) == pytest.approx(100.0 * kc.min_seconds(f, b, ctx.peak)[0] / 0.008)
    assert m.reader("moe_load_max_over_mean_l3").read(ctx) == pytest.approx(8 * 64 * 10 / (10 * 128))
    assert m.reader("span_attr_ratio").read(ctx, **m.metric_spec("moe_group_rows_share.l3")["args"]) == pytest.approx(50.0)
    share = m.reader("lin_state_bytes_share_l3")
    assert share.read(ctx) is None                                                     # a program without the gauge
    spans_ring["phases"] = [("ds.init.programs", 0.0, 1.0, {"lin_state_bytes": 10 * 128 * 2_097_152})]
    parts = kl.decode_step_bytes(ctx.config, 128, 128 * 2600, 550)
    assert share.read(ctx) == pytest.approx(100.0 * parts["state"] / sum(parts.values())) and 30 < share.read(ctx) < 45
    spans_ring["recs"] = [("ds.serve.chunk", 6.1, 6.2, {"chunks": 1})]                 # a program without the counts
    assert kda.read(ctx, **step_args) is None and kda.read(ctx, **chunk_args) is None
    assert mla.read(ctx, **m.metric_spec("mla_decode_roofline.l3")["args"]) is None
    assert m.reader("span_attr_ratio").read(ctx, **m.metric_spec("moe_group_rows_share.l3")["args"]) is None
