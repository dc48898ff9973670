"""The ``longcat_flash`` configuration's benchmark files on the CPU: its
stand-in cell through the harness (``tiny.make`` finds it by its runner), the
float32 reference (expanded) against each control at the small size, the new
readers on hand-made spans, and the cost functions (a share over 100% is
impossible at any input the cell can produce). Nothing here is a device
number."""

import json
from types import SimpleNamespace

import pytest

from perfbench import kernel_costs as kc
from perfbench import kernel_costs_longcat_flash as kl
from perfbench import reference_longcat_flash as reference
from perfbench import run
from perfbench.manifest import Manifest
from perfbench.peaks import peak_for

from . import tiny

CELL = "serve-lcflash-gen-backlog"
CONFIG = "longcat-flash-560b-ep32-serve-1chip"
SEED = 2**31 + 141
REPO = tiny.REPO
# the cell's own entries (`.lcf`: a reader or arguments of this configuration), in the order PR 41 appended them
LCF = ("part_dense_ffn_share", "moe_weight_stream_roofline", "moe_load_max_over_mean", "moe_zero_pair_share",
       "mla_decode_roofline", "mla_chunk_roofline")
# the readings it takes the way other backlog cells do: one entry each, the cell listed in its `workloads` (PR 47; its
# steps are timed by kind since PR 59: the blend had read 20.4 ms where a plain step is 14.7 and a mixed one 24.3).
# MINE is what the cell must KEEP, found by name: a later PR may list it in an entry more
SHARED = ("plain_step_p50_s", "mixed_step_p50_s", "chunk_step_p50_s", "dispatched_ahead_share", "paged_walk_share",
          "gen_tok_s", "decode_slots_active", "srv_step_host_p50_s", "idle_outside_spans_share", "copy_layout_share",
          "part_unattributed_share", "part_attn_share", "part_moe_route_share", "moe_layer_share", "mla_attention_share",
          "moe_streamed_per_hit")
MINE = {n + ".lcf" for n in LCF} | {n + ".backlog" for n in SHARED}


@pytest.fixture(scope="module")
def manifest(tmp_path_factory):
    m = tiny.make(tmp_path_factory.mktemp("bench"))
    m.validate()
    return m


@pytest.fixture(scope="module")
def results(manifest, tmp_path_factory):
    trace_dir = str(tmp_path_factory.mktemp("trace"))
    return {t: run.run_cell(manifest, CELL, SEED, 1.0, t, require_tpu=False, trace_dir=trace_dir) for t in (False, True)}


@pytest.fixture(scope="module")
def runner(manifest):
    """The stand-in cell's runner, set up: the server and its two warm-up requests."""
    from perfbench.context import Context

    cell = manifest.cell(CELL)
    c = Context(cell=cell, config=manifest.config(cell["config"]), traffic=manifest.traffic(cell["traffic"]), chips=1,
                peak=peak_for("TPU v5 lite"))
    r = manifest.runner(c.config["runner"]).Runner(c, SEED, [], lambda n: None, lambda msg: None)
    r.setup()
    return r


def test_the_stand_in_cell_is_in_the_tiny_copy(manifest):
    assert CELL in [w["name"] for w in manifest.doc["workloads"]]
    assert manifest.config(manifest.cell(CELL)["config"])["runner"] == "serve_longcat_flash"


def test_the_benchmark_lists_the_cell_and_its_nineteen_metrics_last(table):
    m = table
    d = m.doc
    # the cell, its configuration and its metrics by name: a later PR appends behind them. Nineteen entries of the
    # cell's own until PR 47: six still are, eleven are one entry with the other backlog cells' and two were twins
    cell = m.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "gen-backlog-s64", 1)
    assert m.config_entry(CONFIG)["file"] == f"perfbench/configs/{CONFIG}.json"
    tiny.check_cell_keeps(m, CELL, [n + ".lcf" for n in LCF], MINE)
    shares = [x["name"] for x in d["per_layer"] if "roofline" in x["name"] and CELL in x.get("workloads", ())]
    assert {"moe_weight_stream_roofline.lcf", "mla_decode_roofline.lcf", "mla_chunk_roofline.lcf"} <= set(shares)      # at least these


def test_the_23_part_metrics_of_pr_36_are_where_they_were_and_each_cell_has_its_unattributed_share(table):
    """PR 36's entries are found by their first name and lie where they were
    appended (as `test_program_parts.py::test_the_manifest_holds_the_23_metrics_and_validates`
    finds them since PR 47: no slice from the list's end, so an append breaks
    neither), and this cell's part shares are the three it shares and one of
    its own."""
    from .test_program_parts import NEW, PR_36

    m = table
    names = [e["name"] for e in m.doc["per_layer"]]
    first = names.index(PR_36[0])
    assert names[first:first + len(PR_36)] == PR_36     # nothing moved (23 until PR 47 made one entry of a shared reading)
    new = [e for e in m.doc["per_layer"] if e["name"].startswith(NEW)]
    # this cell's part shares: its dense FFNs are an entry of its own, the other three it shares
    assert {e["name"] for e in new if CELL in e["workloads"]} >= {
        "part_unattributed_share.backlog", "part_attn_share.backlog", "part_dense_ffn_share.lcf", "part_moe_route_share.backlog"}
    assert all(e["source"] == "device_trace" and e["unit"] == "%" for e in new)
    assert {m.metric_spec(e["name"])["reader"] for e in new} == {"part_share"}
    for cell in m.doc["workloads"]:
        mine = [e["name"] for e in m.metrics_for(cell["name"], "per_layer") if e["name"].startswith(NEW)]
        assert sum(n.startswith("part_unattributed_share.") for n in mine) == 1, cell["name"]


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


def test_traced_run_reports_every_lcf_metric_that_needs_no_device(manifest, results):
    out, _ = results[True]
    listed = {m["name"]: m for m in manifest.metrics_for(CELL, "per_layer")}
    assert MINE | tiny.SETUP <= set(listed)         # `<=`: a later PR may list the cell in an entry more
    host = {n for n, m in listed.items() if m["source"] != "device_trace"}
    assert {"gen_tok_s.backlog", "decode_slots_active.backlog", "srv_step_host_p50_s.backlog", "moe_streamed_per_hit.backlog",
            "moe_load_max_over_mean.lcf", "moe_zero_pair_share.lcf"} | tiny.SETUP <= host <= set(out["metrics"])
    assert not (set(out["metrics"]) - host)      # no device plane on the CPU: those readers found nothing
    assert out["metrics"]["moe_load_max_over_mean.lcf"]["value"] >= 1.0
    # 8 of the stand-in's 24 router columns are identity experts: about a third of the pairs at seeded weights
    assert 15.0 < out["metrics"]["moe_zero_pair_share.lcf"]["value"] < 55.0


@pytest.mark.parametrize("skip", list(reference.SKIPS) + ["experts:1", "int8"])
def test_reference_catches_each_control_at_the_small_size(runner, skip):
    """The served tokens read against a reference with one thing changed (or
    the reference continued in int8 read by the float32 one): NOT correct by
    one of the stand-in's two limits."""
    from perfbench.tools import control_longcat_flash as control

    ok, notes = runner.reference_check()
    assert ok and notes["max_logit_gap"] <= notes["margin"] and notes["mean_logit_gap"] <= notes["mean_gap_limit"]
    if skip == "int8":
        arch = reference.Arch.from_config(runner.cfg)
        out = control.readings(runner, arch, [], 48)      # 8 tokens of a 96-row vocabulary can all agree
        assert out["served_correct"] and out["controls_read_correct"] == [] and out["int8"]["max_logit_gap"] > out["margin"]
        return
    ok, notes = runner.reference_check(skip=skip)
    assert not ok and (notes["max_logit_gap"] > notes["margin"] or notes["mean_logit_gap"] > notes["mean_gap_limit"])


def test_the_configuration_file_holds_the_published_widths_and_the_cut():
    m = Manifest(REPO)
    c, entry = m.config(CONFIG), m.config_entry(CONFIG)
    assert entry["reduced"] == ["num_layers", "n_routed_experts", "vocab_size"]
    assert entry["source"] == "https://huggingface.co/meituan-longcat/LongCat-Flash-Chat/blob/main/config.json"
    assert c["published"] == {"num_layers": 28, "n_routed_experts": 512, "vocab_size": 131072}
    assert (c["num_layers"], c["n_routed_experts"], c["vocab_size"]) == (4, 16, 16384)
    assert c["expert_share"] == {"chips": 32, "index": 0} and c["dtype"] == "bfloat16"
    assert (c["hidden_size"], c["ffn_hidden_size"], c["expert_ffn_hidden_size"], c["q_lora_rank"], c["kv_lora_rank"],
            c["qk_rope_head_dim"], c["qk_nope_head_dim"], c["v_head_dim"], c["num_attention_heads"], c["moe_topk"],
            c["zero_expert_num"]) == (6144, 12288, 2048, 1536, 512, 64, 128, 128, 64, 12, 256)
    assert c["published"]["n_routed_experts"] + c["zero_expert_num"] == 768       # the router's width
    assert (c["routed_scaling_factor"], c["rope_theta"], c["rms_norm_eps"], c["zero_expert_type"]) == (6, 10000000, 1e-05, "identity")
    assert c["mla_scale_q_lora"] is True and c["mla_scale_kv_lora"] is True and len(c["assumed"]) >= 7
    assert "32" in c["deployment"] and "identity" in c["deployment"] and "distorts" in c["deployment"]
    sv, tr = c["serving"], m.traffic(m.cell(CELL)["traffic"])
    slot_tokens = sv["max_prompt_len"] + sv["max_new_tokens"]
    assert sv["num_pages"] == sv["max_slots"] * -(-slot_tokens // sv["page_size"]) + 1 == 1793
    comp = tr["components"][0]
    assert comp["prompt_len"] == {"dist": "lognormal", "median": 1024, "sigma": 0.6, "min": 288, "max": 3072}
    assert tr["block_requests"] == sv["max_slots"] == 64 and tr["queue_depth"] == 2 and comp["new_tokens"]["value"] == 512
    assert sv["prefill_chunk_tokens"] == 256 and c["warmup_long_prompt"] > 2 * sv["prefill_chunk_tokens"]
    ref = c["reference"]
    assert ref["logit_margin"] > 0 and ref["mean_gap_limit"] > 0 and "PLACEHOLDER" not in ref["why"]
    # the resident bytes the cell was sized by: 10.35 GB of weights and 2.35 GB of pool
    E, F, X, H = 6144, 12288, 2048, 64
    mla = E * 1536 + 1536 * H * 192 + E * 576 + 512 * H * 128 * 2 + H * 128 * E
    layer = 2 * mla + 2 * 3 * E * F + E * 768 + 16 * 3 * E * X
    assert abs(2 * (4 * layer + 2 * 16384 * E) - 10.35e9) < 0.02e9
    assert sv["num_pages"] * 128 * 640 * 2 * 8 == 2350120960


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


def test_mla_decode_roofline_counts_eight_sub_blocks_of_unpadded_rows(spans_ring):
    reader = Manifest(REPO).reader("mla_roofline_lcf")
    spans_ring["recs"] = [("ds.serve.decode.dispatch", 6.0, 6.01, {"attended": 90000, "active": 64, "pages": 1})]
    ctx = _ctx(ops_s=0.002)
    f, b = kl.latent_attention(8 * 90000, 8 * 90000, 8 * 64, 64, 576, 512, 2)
    assert b == 8 * 90000 * 1152 + 8 * 64 * 64 * 1088 * 2
    least, bound = kc.min_seconds(f, b, ctx.peak)
    assert bound == "memory"
    assert reader.read(ctx, pattern="x", kind="decode") == pytest.approx(100.0 * least / 0.002)
    spans_ring["recs"] = [("ds.serve.decode.dispatch", 6.0, 6.01, {"active": 3})]      # a program without the count
    assert reader.read(ctx, pattern="x", kind="decode") is None


def test_mla_chunk_roofline_counts_the_triangle_and_is_compute_bound(spans_ring):
    reader = Manifest(REPO).reader("mla_roofline_lcf")
    pairs = 256 * 768 + 256 * 257 // 2
    spans_ring["recs"] = [("ds.serve.chunk", 6.0, 6.03, {"chunks": 1, "tokens": 256, "attended": pairs})]
    ctx = _ctx(ops_s=0.02)
    f, b = kl.latent_attention(8 * pairs, 8 * pairs / 256, 8 * 256, 64, 576, 512, 2)
    least, bound = kc.min_seconds(f, b, ctx.peak)
    assert bound == "compute" and f == 2 * 1088 * 64 * 8 * pairs
    assert reader.read(ctx, pattern="x", kind="chunk") == pytest.approx(100.0 * least / 0.02)
    spans_ring["recs"] = [("ds.serve.chunk", 6.0, 6.03, {"chunks": 1, "tokens": 256})]   # the parent's span: no attended
    assert reader.read(ctx, pattern="x", kind="chunk") is None


def test_load_weight_stream_and_zero_share_read_this_files_keys(spans_ring):
    m = Manifest(REPO)
    ctx = _ctx(ops_s=0.02)
    emit = {"moe_pairs_held": 64, "moe_load_max": 4, "moe_experts_hit": 40, "moe_pairs_routed": 64 * 12 * 4, "moe_pairs_zero": 1000}
    spans_ring["recs"] = [("ds.serve.emit", 6.0, 6.01, dict(emit))]
    assert m.reader("moe_load_max_over_mean_lcf").read(ctx) == pytest.approx(4 * 16 * 4 / 64)
    f, b = kl.routed_experts(40, 64, 64 * 4, 6144, 2048, 2)
    assert m.reader("moe_weight_stream_roofline_lcf").read(ctx, pattern="x") == pytest.approx(
        100.0 * kc.min_seconds(f, b, ctx.peak)[0] / 0.02)
    args = m.metric_spec("moe_zero_pair_share.lcf")["args"]
    assert m.reader("span_attr_ratio").read(ctx, **args) == pytest.approx(100.0 * 1000 / 3072)
    # chunk calls in the traced part are charged the window's mean a call, capped at every held expert
    spans_ring["recs"] += [("ds.serve.chunk", 1.0, 1.1, {"chunks": 1, "moe_calls": 2, "moe_experts_hit": 2 * 60,
                                                        "moe_pairs_held": 2 * 250, "moe_pairs_routed": 2 * 256 * 48,
                                                        "moe_pairs_zero": 8000}),
                           ("ds.serve.chunk", 7.0, 7.1, {"chunks": 3})]
    f2, b2 = kl.routed_experts(40 + 3 * 60, 64 + 3 * 250, 64 * 4 + 3 * 256 * 4, 6144, 2048, 2)
    assert m.reader("moe_weight_stream_roofline_lcf").read(ctx, pattern="x") == pytest.approx(
        100.0 * kc.min_seconds(f2, b2, ctx.peak)[0] / 0.02)
    assert m.reader("span_attr_ratio").read(ctx, **args) == pytest.approx(100.0 * 9000 / (3072 + 2 * 256 * 48))
    spans_ring["recs"] = [("ds.serve.emit", 6.0, 6.01, {"tokens": 3, "moe_pairs_routed": 10})]    # the other families' emit
    assert m.reader("moe_load_max_over_mean_lcf").read(ctx) is None
    assert m.reader("moe_weight_stream_roofline_lcf").read(ctx, pattern="x") is None
    assert m.reader("span_attr_ratio").read(ctx, **args) is None


# -- costs: never more than an implementation must move ---------------------------

@pytest.mark.parametrize("ctx_before,tokens", [(0, 256), (1024, 256), (2816, 256), (768, 37)])
def test_chunk_attention_cost_is_below_what_the_kernel_does(ctx_before, tokens):
    """The kernel multiplies whole blocks of 640-lane rows for all 256 rows of
    a call; the cost charges the real tokens' triangle at 576 + 512 values a
    pair and the rows once for every 256 queries."""
    cfg = Manifest(REPO).config(CONFIG)
    row, val = kl.widths(cfg)
    assert (row, val) == (576, 512) and kl.sub_blocks(cfg) == 8 and kl.sparse_layers(cfg) == 4
    pairs = tokens * ctx_before + tokens * (tokens + 1) // 2
    f, b = kl.latent_attention(pairs, pairs / 256, tokens, 64, row, val, 2)
    done = 256 * (ctx_before + 256) * 2 * (640 + 512) * 64           # every row of the call against every key it reaches
    assert f <= done and b <= (ctx_before + 256) * 1280 + 256 * 64 * (640 + 512) * 2


@pytest.mark.parametrize("rows,slots", [(64 * 1400, 64), (288, 1), (64 * 3584, 64)])
def test_decode_attention_cost_counts_the_unpadded_row_once(rows, slots):
    f, b = kl.latent_attention(rows, rows, slots, 64, 576, 512, 2)
    assert b == rows * 1152 + slots * 64 * 1088 * 2 and b < rows * 1280 + slots * 64 * 1152 * 2
    assert f == rows * 2 * 1088 * 64


@pytest.mark.parametrize("hit,pairs,tokens", [(0, 0, 64), (10, 16, 64), (16, 64 * 12, 64), (16, 320 * 12, 320)])
def test_routed_experts_cost_is_below_what_the_masked_form_does(hit, pairs, tokens):
    """Per expert layer call: the masked form streams all 16 held experts and
    multiplies every row by each; the cost charges the experts HIT and the held
    pairs (at most every row by every pick)."""
    f, b = kl.routed_experts(hit, pairs, tokens, 6144, 2048, 2)
    assert b <= 16 * 3 * 6144 * 2048 * 2 + 2 * tokens * 6144 * 2
    assert f <= 2 * 3 * (tokens * 16) * 6144 * 2048
