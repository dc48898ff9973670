"""The ``qwen3_next`` configuration's benchmark files on the CPU: its stand-in
cell through the harness (``tiny.make`` finds it by its runner), the float32
reference against controls at the small size, the new readers on hand-made
spans, and the cost functions against hand counts. Nothing here is a device
number."""

import json
import re
from types import SimpleNamespace

import pytest

from perfbench import kernel_costs as kc
from perfbench import kernel_costs_qwen3_next as kq
from perfbench import reference_qwen3_next as reference
from perfbench import run
from perfbench.manifest import Manifest
from perfbench.peaks import peak_for

from . import tiny

CELL = "serve-qwen3next-reason-backlog"
CONFIG = "qwen3-next-80b-ep8-l12-serve-1chip"
SEED = 2**31 + 152
REPO = tiny.REPO
# the cell's own entries (`.q3n`), in the order PR 52 appended them, and the `.backlog` entries it is listed in: what
# the cell must KEEP, found by name (a later PR may list it in an entry more; PR 59 listed it in five, and its steps are
# timed by kind since: `plain_step_p50_s` and `mixed_step_p50_s`)
Q3N = ("gdn_step_roofline", "gdn_chunk_roofline", "part_lin_share", "paged_decode_roofline", "lin_state_bytes_share")
# ... and the two expert readings whose reader and arguments are the ZAYA cell's own: ONE entry a reading (PR 47), the cell listed there
ZAYAS = ("moe_weight_stream_roofline.zaya", "moe_load_max_over_mean.zaya")
SHARED = ("plain_step_p50_s", "mixed_step_p50_s", "dispatched_ahead_share", "paged_walk_share", "decode_slots_active", "idle_outside_spans_share", "copy_layout_share", "srv_step_host_p50_s",
          "gen_tok_s", "part_unattributed_share", "part_attn_share", "part_moe_route_share", "moe_streamed_per_hit",
          "moe_layer_share")
MINE = {n + ".q3n" for n in Q3N} | {n + ".backlog" for n in SHARED} | set(ZAYAS)


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


def test_the_manifest_validates_with_the_cell_its_entries_last_and_the_lists_it_joined(table):
    m = table
    d = m.doc
    cell = m.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "reason-backlog-s128", 1)
    assert "128 x 9 DeltaNet states of 2 MB" in cell["why"] and "attention sees 8x share" in cell["why"]
    entry = m.config_entry(CONFIG)
    assert entry["file"] == f"perfbench/configs/{CONFIG}.json" and entry["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert entry["source"] == "https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct/blob/main/config.json"
    assert sum(w["chips"] == 4 for w in d["workloads"]) <= max(1, len(d["workloads"]) // 4)
    tiny.check_cell_keeps(m, CELL, [n + ".q3n" for n in Q3N], MINE)
    shares = {x["name"]: x for x in d["per_layer"] if "roofline" in x["name"] and CELL in x.get("workloads", ())}
    assert {"gdn_step_roofline.q3n", "gdn_chunk_roofline.q3n", "paged_decode_roofline.q3n",
            "moe_weight_stream_roofline.zaya"} <= set(shares)                                      # at least these four
    assert all(x["unit"] == "%" and x["source"] == "device_trace" for x in shares.values())
    assert m.metric_spec("part_lin_share.q3n")["args"]["parts"] == ["lin.proj", "lin.scan"]
    # the patterns find this program's kernels by the names they have in a trace
    from deepspeed_tpu.ops.pallas import gated_delta, grouped_experts
    assert re.search(m.metric_spec("gdn_step_roofline.q3n")["args"]["pattern"], f"%{gated_delta.STEP_KERNEL}.12 = f32[128,32,128]")
    assert re.search(m.metric_spec("gdn_chunk_roofline.q3n")["args"]["pattern"], f"%{gated_delta.CHUNK_KERNEL} = (f32[32,4,64,128]")
    assert not re.search(m.metric_spec("gdn_step_roofline.q3n")["args"]["pattern"], f"%{gated_delta.CHUNK_KERNEL} = ")
    assert re.search(m.metric_spec("moe_weight_stream_roofline.zaya")["args"]["pattern"], grouped_experts.KERNEL_NAME)


def test_the_zaya_cell_keeps_its_entries_with_this_cell_behind_it_in_two_of_them(table):
    """Two of the ZAYA cell's own entries are this cell's readings too (the same reader and arguments: ONE entry a
    reading), so this cell is listed BEHIND it there. What the ZAYA cell keeps besides is ``test_zaya_cell.py``'s to
    hold (until PR 59 that file pinned its cell as the last of two lists, and this test held what it could not)."""
    zaya, d = "serve-zaya1-reason-backlog", table.doc
    by_name = {x["name"]: x for x in d["per_layer"]}
    assert all(by_name[n]["workloads"][:2] == [zaya, CELL] for n in ZAYAS)
    tok = next(x["workloads"] for x in d["end_to_end"] if x["name"] == "serve_tok_s")
    assert tok.index(zaya) + 1 == tok.index(CELL)


def test_traced_stand_in_run_is_correct_and_prints_every_metric_that_needs_no_device(manifest, tmp_path_factory):
    out, ctx = run.run_cell(manifest, CELL, SEED, 1.0, True, require_tpu=False, trace_dir=str(tmp_path_factory.mktemp("trace")))
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
            "dispatched_ahead_share.backlog", "paged_walk_share.backlog", "moe_load_max_over_mean.zaya",
            "lin_state_bytes_share.q3n"} | tiny.SETUP <= host
    assert set(out["metrics"]) == host       # no device plane on the CPU: the device readers found nothing, and said so
    assert out["metrics"]["moe_load_max_over_mean.zaya"]["value"] >= 1.0
    assert 0 < out["metrics"]["lin_state_bytes_share.q3n"]["value"] < 100
    spec = manifest.metric_spec("serve_tok_s")
    assert manifest.reader(spec["reader"]).read(ctx, **spec.get("args", {})) > 0 and ctx.window[0] > 0


def test_reference_catches_controls_at_the_small_size_and_a_used_slot_reads_correct(runner, monkeypatch):
    """The served tokens read against a reference with one thing changed: NOT
    correct by one of the stand-in's three limits (the ``*_edge`` controls cut
    at every 8th position here, the stand-in's chunk, and at the first row a
    decode step computes), ``state_edge`` by the hand-over's own. The same
    requests served again in used slots read CORRECT."""
    from perfbench.tools import control_qwen3_next as control

    monkeypatch.setattr(reference, "CHUNK", 8)
    out = control.readings(runner, ["state_edge", "conv_edge"])     # state_bf16 needs hundreds of tokens to show: the chip's
    assert out["served_correct"] and out["controls_read_correct"] == [], out
    assert out["state_edge"]["handover_gap"] > out["handover_margin"]      # the FOURTH largest: every hand-over moved
    assert out["reused_slot_reads_correct"] and out["reused_slot_same_tokens"] and out["reused_slot"]["positions"] == 40
    assert set(reference.SKIPS) == {"state_bf16", "no_delta", "no_decay", "state_edge", "conv_edge", "no_out_gate",
                                    "no_attn_gate", "no_shared_gate"}


def test_the_configuration_file_holds_every_number_of_the_catalog_row_and_cuts_depth_experts_and_vocabulary_alone():
    m = Manifest(REPO)
    c = m.config(CONFIG)
    row = {"decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256, "hidden_act": "silu", "hidden_size": 2048,
           "intermediate_size": 5120, "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128, "linear_num_key_heads": 16,
           "linear_num_value_heads": 32, "linear_value_head_dim": 128, "max_position_embeddings": 262144, "mlp_only_layers": [],
           "model_type": "qwen3_next", "moe_intermediate_size": 512, "norm_topk_prob": True, "num_attention_heads": 16,
           "num_experts_per_tok": 10, "num_key_value_heads": 2, "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06,
           "rope_scaling": None, "rope_theta": 10000000, "shared_expert_intermediate_size": 512, "tie_word_embeddings": False,
           "use_sliding_window": False}
    assert {k: c[k] for k in row} == row
    assert (c["num_hidden_layers"], c["num_experts"], c["vocab_size"]) == (12, 64, 18992)
    assert c["published"] == {"num_hidden_layers": 48, "num_experts": 512, "vocab_size": 151936} and 8 * 18992 == 151936
    assert c["expert_share"] == {"chips": 8, "index": 0} and c["dtype"] == "bfloat16" and c["runner"] == "serve_qwen3_next"
    said = " ".join(c["assumed"])
    for word in ("arXiv:2412.06464", "zero-centred", "PLAIN gain", "NO bias", "softplus(a + dt_bias)", "1/sqrt(dk)", "h // 2",
                 "sigmoid(gate)", "64 lanes", "top-10", "sigmoid(w . w_sg)", "multi-token-prediction", "[q (16 x 128) | k",
                 "4 to 4 096 tokens", "+-1/sqrt(4)", "(0.1, 0.9)", "updated in place"):
        assert word in said, word
    assert "one chip of EIGHT" in c["deployment"] and "13.2 GB" in c["deployment"]
    sv, tr = c["serving"], m.traffic(m.cell(CELL)["traffic"])
    assert sv == {"max_slots": 128, "page_size": 128, "num_pages": 6145, "max_prompt_len": 2048, "max_new_tokens": 4096,
                  "prefill_chunk_tokens": 256, "max_queue_depth": 4096, "temperature": 0.0}
    assert sv["num_pages"] == sv["max_slots"] * -(-(sv["max_prompt_len"] + sv["max_new_tokens"]) // sv["page_size"]) + 1
    assert tr["ramp"] == {"requests": 156, "aged": True} and tr["loop"] == "backlog" and tr["queue_depth"] == 2 and tr["block_requests"] == 128
    s64 = m.traffic("reason-backlog-s64")
    assert tr["components"] == s64["components"] and 156 == 128 + 28 and 28 / 128 == pytest.approx(14 / 64)
    # every chunked warm-up prompt ends one or two rows into its last chunk, and no sub-chunk of 64 ends with it
    chunked = [n for n in (c["warmup_long_prompt"], *c["warmup_edge_prompts"]) if n > sv["prefill_chunk_tokens"]]
    assert len(chunked) == 4 and all(n % sv["prefill_chunk_tokens"] in (1, 2) for n in chunked) and c["warmup_short_prompt"] == 96
    ref = c["reference"]
    assert min(ref["tie_margin"], ref["logit_margin"], ref["mean_gap_limit"], ref["gap_cap"], ref["handover_margin"]) > 0
    assert "PLACEHOLDER" not in ref["why"]
    # the resident bytes the cell was sized by
    E, F, V = 2048, 512, 18992
    lin = E * 12288 + E * 64 + 8192 * 4 + 4096 * E
    attn = E * 8192 + 2 * E * 512 + 4096 * E
    shared = E * 512 + 3 * E * F + E
    assert lin == pytest.approx(33.72e6, rel=2e-3) and attn == pytest.approx(27.26e6, rel=1e-3) and shared == pytest.approx(4.20e6, rel=1e-2)
    total = 9 * (lin + shared + 64 * 3 * E * F) + 3 * (attn + shared + 64 * 3 * E * F) + 2 * V * E
    assert 2 * total == pytest.approx(5.86e9, rel=5e-3)
    assert 128 * 9 * kq.state_bytes(c) == pytest.approx(2.42e9, rel=2e-3) and kq.state_bytes(c) == 2_097_152
    assert sv["num_pages"] * 128 * 3 * 2 * 256 * 2 * 2 == pytest.approx(4.83e9, rel=1e-3)       # 6 KB a token (the issue's 4.72 took 6 000 bytes)


# -- the cost functions against hand counts ----------------------------------------------------

def test_kernel_costs_against_hand_counts_at_the_published_shapes():
    big = Manifest(REPO).config(CONFIG)
    assert kq.kinds(big) == ["lin", "lin", "lin", "attn"] * 3 and kq.heads(big) == (16, 32, 128, 128)
    # a decode step at 128 live slots, one DeltaNet layer: every slot's 2 MB state in and out, its row beside it
    f, b = kq.delta_step(128, big)
    row = 4 * (2 * 16 * 128 + 2 * 32 * 128 + 2 * 32)
    assert b == 128 * (2 * 2_097_152 + row) and f == 128 * 32 * 8 * 128 * 128
    assert 9 * b == pytest.approx(4.87e9, rel=5e-3) and kc.min_seconds(f, b, peak_for("TPU v5 lite"))[1] == "memory"
    assert kc.min_seconds(f, b, peak_for("TPU v5 lite"))[0] / 128 == pytest.approx(5.1e-6, rel=3e-2)      # 5.1 us a slot and layer
    # a chunk call of 256 rows: the state once, the rows, four sub-chunks of products a value head
    f, b = kq.delta_chunk(256, 1, big)
    per_sub = 4 * 64 * 64 * 128 + 64 * 64 * 256 + 6 * 64 * 128 * 128 + 2 * 64 * 64 * 128
    assert f == 4 * 32 * per_sub and b == 2 * 2_097_152 + 256 * row
    assert kq.delta_chunk(257, 2, big)[0] == 5 * 32 * per_sub                  # a sub-chunk begun is a sub-chunk
    # what a decode step must move, by part: the state leads
    parts = kq.decode_step_bytes(big, 128, 128 * 2750, int(0.92 * 64 * 12))
    assert parts["state"] == pytest.approx(4.83e9, rel=1e-3) and parts["experts"] == pytest.approx(4.45e9, rel=2e-2)
    assert parts["keys"] == pytest.approx(2.16e9, rel=2e-2) and parts["shared_weights"] == pytest.approx(0.93e9, rel=5e-2)
    assert parts["state"] / sum(parts.values()) == pytest.approx(0.39, abs=0.02)


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


def test_the_delta_rule_readers_count_a_state_a_call_and_a_row_a_row(spans_ring):
    m = Manifest(REPO)
    reader = m.reader("gdn_roofline")
    step_args, chunk_args = m.metric_spec("gdn_step_roofline.q3n")["args"], m.metric_spec("gdn_chunk_roofline.q3n")["args"]
    spans_ring["recs"] = [("ds.serve.decode.dispatch", 6.0, 6.01, {"attended": 128 * 2700, "active": 128, "pages": 1}),
                          ("ds.serve.chunk", 6.1, 6.2, {"rows_self": 513, "chunks": 1, "rode": 2})]
    ctx = _ctx(ops_s=0.008)
    f, b = kq.delta_step(128, ctx.config)
    assert reader.read(ctx, **step_args) == pytest.approx(100.0 * kc.min_seconds(9 * f, 9 * b, ctx.peak)[0] / 0.008)
    assert 70 < reader.read(ctx, **step_args) < 100
    f, b = kq.delta_chunk(513, 3, ctx.config)
    assert reader.read(ctx, **chunk_args) == pytest.approx(100.0 * kc.min_seconds(9 * f, 9 * b, ctx.peak)[0] / 0.008)
    assert reader.read(_ctx(ops_s=0.0), **step_args) is None                          # a trace without the kernel
    no_lin = _ctx()
    no_lin.config = {k: v for k, v in no_lin.config.items() if not k.startswith("linear_")}
    assert reader.read(no_lin, **step_args) is None                                   # another family's configuration
    spans_ring["recs"] = [("ds.serve.chunk", 6.1, 6.2, {"chunks": 1})]                # a program without the counts
    assert reader.read(ctx, **step_args) is None and reader.read(ctx, **chunk_args) is None


def test_paged_decode_roofline_counts_three_reads_of_two_kv_heads_of_256_lanes(spans_ring):
    m = Manifest(REPO)
    reader, args = m.reader("paged_decode_roofline_q3n"), m.metric_spec("paged_decode_roofline.q3n")["args"]
    spans_ring["recs"] = [("ds.serve.decode.dispatch", 6.0, 6.01, {"attended": 128 * 2700, "active": 128, "pages": 1})]
    ctx = _ctx(ops_s=0.004)
    f, b = kq.paged_decode_keys(3 * 128 * 2700, 2, 16, 256, 2, 3 * 128)
    assert reader.read(ctx, **args) == pytest.approx(100.0 * kc.min_seconds(f, b, ctx.peak)[0] / 0.004)
    assert 0 < reader.read(ctx, **args) < 100
    spans_ring["recs"] = [("ds.serve.decode.dispatch", 6.0, 6.01, {"active": 3})]      # a program without the count
    assert reader.read(ctx, **args) is None


def test_the_state_share_reads_the_gauge_and_the_steps_counts_and_the_moe_readers_the_zaya_keys(spans_ring):
    m = Manifest(REPO)
    share = m.reader("lin_state_bytes_share")
    emit = {"moe_experts_hit": 700, "moe_pairs_held": 12 * 160, "moe_pairs_routed": 12 * 1280, "moe_load_max": 9,
            "moe_experts_streamed": 700}
    spans_ring["recs"] = [("ds.serve.decode.dispatch", 6.0, 6.01, {"attended": 128 * 2750, "active": 128, "pages": 1}),
                          ("ds.serve.emit", 6.01, 6.02, emit)]
    ctx = _ctx(ops_s=0.008)
    assert share.read(ctx) is None                                                     # a program without the gauge
    spans_ring["phases"] = [("ds.init.programs", 0.0, 1.0, {"lin_state_bytes": 9 * 128 * 2_097_152})]
    parts = kq.decode_step_bytes(ctx.config, 128, 128 * 2750, 700)
    assert share.read(ctx) == pytest.approx(100.0 * parts["state"] / sum(parts.values()))
    assert 35 < share.read(ctx) < 45
    stream, args = m.reader("moe_weight_stream_roofline_zaya"), m.metric_spec("moe_weight_stream_roofline.zaya")["args"]
    f, b = kq.routed_experts(700, 12 * 160, 12 * 128, 2048, 512, 2)
    assert stream.read(ctx, **args) == pytest.approx(100.0 * kc.min_seconds(f, b, ctx.peak)[0] / 0.008)
    assert m.reader("moe_load_max_over_mean_zaya").read(ctx) == pytest.approx(9 * 64 * 12 / (12 * 160))
