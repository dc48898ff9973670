"""The ``phi4flash`` configuration's benchmark files on the CPU: its stand-in
cell through the harness (``tiny.make`` finds it by its runner), the float32
reference against each control at the small size, the new readers on hand-made
spans, and the cost functions against hand counts. Nothing here is a device
number."""

import json
from types import SimpleNamespace

import pytest

from perfbench import kernel_costs as kc
from perfbench import kernel_costs_phi4flash as kp
from perfbench import reference_phi4flash as reference
from perfbench import run
from perfbench.manifest import Manifest
from perfbench.peaks import peak_for

from . import tiny

CELL = "serve-phi4flash-reason-backlog"
CONFIG = "phi-4-mini-flash-serve-1chip"
SEED = 2**31 + 143
REPO = tiny.REPO
# the cell's own entries (`.p4f`), in the order PR 43 appended them, and the readings it takes the way the other
# backlog cells do (`.backlog`, one entry each since PR 47: two were `.p4f` entries, six were new for this cell; since
# PR 59 its steps are timed by kind, `plain_step_p50_s`: the blend it had read IS that here, 25.083 against 25.081 ms).
# MINE is what the cell must KEEP, found by name: a later PR may list it in an entry more
P4F = ("ssm_scan_roofline", "paged_decode_roofline", "part_ssm_share")
SHARED = ("plain_step_p50_s", "part_attn_share", "decode_slots_active", "idle_outside_spans_share", "copy_layout_share",
          "srv_step_host_p50_s", "gen_tok_s", "part_unattributed_share")
MINE = {n + ".p4f" for n in P4F} | {n + ".backlog" for n in SHARED}


@pytest.fixture(scope="module")
def manifest(tmp_path_factory):
    m = tiny.make(tmp_path_factory.mktemp("bench"))
    m.validate()
    return m


def _run(manifest, tmp_path_factory, trace: bool):
    return run.run_cell(manifest, CELL, SEED, 1.0, trace, require_tpu=False, trace_dir=str(tmp_path_factory.mktemp("trace")))


def _sound(out):
    line = json.loads(json.dumps(out))
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert line["notes"]["compilations_in_window"] == 0 and "leak" not in line["notes"]
    ref = line["notes"]["reference"]
    assert ref["max_logit_gap"] <= ref["margin"] and ref["positions"] == 16 and ref["mean_logit_gap"] <= ref["mean_gap_limit"]


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
    assert manifest.config(manifest.cell(CELL)["config"])["runner"] == "serve_phi4flash"


def test_the_benchmark_lists_the_cell_and_its_five_metrics_last_and_is_full(table):
    """By name since PR 47 (the table is no longer full, and the five are three
    of the cell's own and two it shares): the cell, its configuration, its own
    entries in the order they were appended, and every reading of a backlog
    cell it now has at no entry of its own."""
    m = table
    d = m.doc
    cell = m.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "reason-backlog-s64", 1)
    assert m.config_entry(CONFIG)["file"] == f"perfbench/configs/{CONFIG}.json"
    assert sum(w["chips"] == 4 for w in d["workloads"]) <= max(1, len(d["workloads"]) // 4)
    tiny.check_cell_keeps(m, CELL, [n + ".p4f" for n in P4F], MINE)
    shares = {x["name"]: x for x in d["per_layer"] if "roofline" in x["name"] and CELL in x.get("workloads", ())}
    assert {"ssm_scan_roofline.p4f", "paged_decode_roofline.p4f"} <= set(shares)       # at least these
    assert all(x["unit"] == "%" and x["source"] == "device_trace" for x in shares.values())
    assert all(shares[n + ".p4f"]["layer"] == "kernels (ops/pallas/)" for n in P4F[:2])
    assert m.metric_spec("part_ssm_share.p4f")["args"]["parts"] == ["ssm.proj", "ssm.scan"]
    assert m.metric_spec("part_attn_share.backlog")["args"]["parts"] == ["attn.qkv", "attn.core", "attn.out", "kv.write"]


def test_untraced_stand_in_run_is_correct_compiles_nothing_in_the_window_and_reports_serve_tok_s_and_setup(manifest, tmp_path_factory):
    out, _ = _run(manifest, tmp_path_factory, False)
    _sound(out)
    assert set(out["metrics"]) == {m["name"] for m in manifest.metrics_for(CELL, "end_to_end")} == {"serve_tok_s", "setup_s"}
    assert all(v["value"] > 0 for v in out["metrics"].values())


def test_traced_stand_in_run_is_correct_lists_the_five_and_reads_what_needs_no_device(manifest, tmp_path_factory):
    out, ctx = _run(manifest, tmp_path_factory, True)
    _sound(out)
    listed = {m["name"]: m for m in manifest.metrics_for(CELL, "per_layer")}
    assert MINE | tiny.SETUP <= set(listed)       # `<=`: a later PR may list the cell in an entry more
    assert all(listed[n + ".p4f"]["source"] == "device_trace" for n in P4F)
    host = {n for n, m in listed.items() if m["source"] != "device_trace"}
    assert {"gen_tok_s.backlog", "decode_slots_active.backlog", "srv_step_host_p50_s.backlog"} | tiny.SETUP <= host
    assert set(out["metrics"]) == host       # no device plane on the CPU: the device readers found nothing, and said so
    from perfbench import program_spans
    recs = program_spans.records_in(ctx.window) or ()
    chunks = [r[3] for r in recs if r[0] == "ds.serve.chunk"]
    assert chunks and all("rows_self" in c and "rows_cross" in c for c in chunks)     # what the scan's reader counts rows by


CONTROLS = [s for s in reference.SKIPS if s not in ("conv_edge", "state_bf16", "cross_own")]


def test_reference_catches_each_control_at_the_small_size(runner):
    """The served tokens read against a reference with one thing changed (or
    the reference continued in int8 read by the float32 one): NOT correct by
    one of the stand-in's two limits. (``conv_edge`` cuts at 256 tokens, past
    the stand-in's prompts, and a bfloat16 state moves a 96-row vocabulary's
    logits by hundredths over 90 tokens, and the stand-in's ONE cross layer
    reading its own projections by tenths, which flips no argmax of 16:
    ``tests/unit/test_phi4flash.py`` moves all three; the chip's readings are in the
    configuration's ``reference.why``.) The same requests served again in used
    slots read CORRECT."""
    from perfbench.tools import control_phi4flash as control

    ok, notes = runner.reference_check()
    assert ok and notes["max_logit_gap"] <= notes["margin"] and notes["mean_logit_gap"] <= notes["mean_gap_limit"]
    arch = reference.Arch.from_config(runner.cfg)
    assert "no_state" in CONTROLS and "state_dirty" in CONTROLS
    out = control.readings(runner, arch, CONTROLS, 48)      # 8 tokens of a 96-row vocabulary can all agree in int8
    assert out["served_correct"] and out["controls_read_correct"] == [], out
    assert out["int8"]["max_logit_gap"] > out["margin"]
    assert out["reused_slot_reads_correct"] and out["reused_slot_same_tokens"] and out["reused_slot"]["positions"] == 16


def test_the_configuration_file_holds_every_number_of_the_catalog_row_uncut():
    m = Manifest(REPO)
    c, entry = m.config(CONFIG), m.config_entry(CONFIG)
    assert entry["reduced"] == [] and entry["source"] == "https://huggingface.co/microsoft/Phi-4-mini-flash-reasoning/blob/main/config.json"
    row = {"embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560, "intermediate_size": 10240, "layer_norm_eps": 1e-05,
           "max_position_embeddings": 262144, "mb_per_layer": 2, "model_type": "phi4flash", "num_attention_heads": 40,
           "num_hidden_layers": 32, "num_key_value_heads": 20, "resid_pdrop": 0, "sliding_window": 512,
           "tie_word_embeddings": True, "mlp_bias": False, "lm_head_bias": False, "vocab_size": 200064}
    assert {k: c[k] for k in row} == row
    assert c["published"]["num_hidden_layers"] == 32 and c["published"]["vocab_size"] == 200064 and c["dtype"] == "bfloat16"
    said = " ".join(c["assumed"])
    for word in ("d_state 16", "d_conv 4", "expand 2", "160", "lambda0", "head pairing", "W_qkv", "absence of positions",
                 "which layer is which", "BEFORE the gate", "512 keys counting the query", "A_log = log(1..16)", "log-uniform"):
        assert word in said, word
    assert "one v5e chip holds the model whole" in c["deployment"] and "replicas behind a router" in c["deployment"]
    sv, tr = c["serving"], m.traffic(m.cell(CELL)["traffic"])
    assert sv == {"max_slots": 64, "page_size": 128, "num_pages": 3073, "max_prompt_len": 2048, "max_new_tokens": 4096,
                  "prefill_chunk_tokens": 256, "max_queue_depth": 4096, "temperature": 0.0}
    assert sv["num_pages"] == sv["max_slots"] * -(-(sv["max_prompt_len"] + sv["max_new_tokens"]) // sv["page_size"]) + 1
    comp = tr["components"][0]
    assert comp["prompt_len"] == {"dist": "lognormal", "median": 512, "sigma": 0.6, "min": 288, "max": 2048}
    assert tr["block_requests"] == 64 and tr["queue_depth"] == 2 and comp["new_tokens"] == {"dist": "const", "value": 4096}
    assert tr["ramp"] == {"requests": 78, "aged": True} and tr["loop"] == "backlog"   # 24 s of the clock until PR 47
    assert (c["warmup_short_prompt"], c["warmup_long_prompt"], c["warmup_new_tokens"]) == (96, 1024, 256)
    ref = c["reference"]
    assert ref["logit_margin"] > 0 and ref["mean_gap_limit"] > 0 and "PLACEHOLDER" not in ref["why"]
    # the resident bytes the cell was sized by: weights, ONE paged layer, 8 rings, 9 recurrent states
    assert 2 * (9 * 119.9e6 + 9 * 98.3e6 + 7 * 104.9e6 + 7 * 91.8e6 + 200064 * 2560) == pytest.approx(7.70e9, rel=2e-3)
    assert sv["num_pages"] * 128 * 5120 == 2013921280                                  # 5 120 B a token, one layer
    assert 8 * (1 + 64 * 7) * 128 * 5120 == 2354053120 and 9 * 64 * (5120 * 16 * 4 + 3 * 5120 * 2) == 206438400


# -- (k) the cost functions against hand counts ------------------------------------------------

def test_kernel_costs_against_hand_counts_at_the_tiny_and_the_published_shapes(manifest):
    tiny_cfg = manifest.config(manifest.cell(CELL)["config"])
    assert kp.sizes(tiny_cfg) == (64, 16) and kp.kinds(tiny_cfg) == ["ssm", "attn", "ssm", "attn", "ssm", "attn", "gmu", "cross"]
    assert kp.attending(tiny_cfg) == 4 and kp.pair_heads(tiny_cfg) == (1, 4, 16)
    # one row: x, dt, s (64 floats each), B and C (16 each); one call on a slot: the [16, 64] state in and out
    assert kp.selective_scan(1, 1, 64, 16) == 4 * (3 * 64 + 2 * 16) + 4 * 2 * 16 * 64 == 9088
    assert kp.selective_scan(8, 1, 64, 16) == 8 * 896 + 8192            # a chunk of 8 rows moves the state once
    assert kp.selective_scan(3, 3, 64, 16) == 3 * 9088                    # a decode step of 3 slots
    big = Manifest(REPO).config(CONFIG)
    assert kp.sizes(big) == (5120, 16) and kp.attending(big) == 16 and kp.kinds(big).count("ssm") == 9
    assert kp.pair_heads(big) == (10, 40, 128)
    assert kp.selective_scan(64, 64, 5120, 16) == 64 * (4 * (3 * 5120 + 32) + 655360) == 45883392   # 46 MB a layer a step
    # a decode step at 64 slots of 2 048 tokens: 8 rings of 512 keys and 8 reads of the whole context, K and V
    keys = 64 * (8 * 512 + 8 * 2048)
    f, b = kp.paged_decode_keys(keys, 10, 40, 128, 2, 16 * 64)
    assert b == 2 * keys * 10 * 128 * 2 + 2 * 16 * 64 * 40 * 128 * 2 and f == 4 * keys * 40 * 128
    assert b == pytest.approx(6.73e9, rel=1e-2) and kc.min_seconds(f, b, peak_for("TPU v5 lite"))[1] == "memory"


# -- readers on hand-made spans -------------------------------------------------------------------

def _ctx(ops_s=0.01, traced=(5.0, 10.0)):
    cfg = Manifest(REPO).config(CONFIG)
    trace = SimpleNamespace(seconds_matching=lambda pattern: ops_s)
    return SimpleNamespace(config=cfg, window=(0.0, 10.0), traced=traced, trace=trace, peak=peak_for("TPU v5 lite"))


@pytest.fixture
def spans_ring(monkeypatch):
    """Feeds the readers a list of (name, t0, t1, attrs) as the program's ring."""
    from perfbench import program_spans

    box = {"recs": []}
    monkeypatch.setattr(program_spans, "program",
                        lambda: SimpleNamespace(snapshot=lambda since=0.0: [r for r in box["recs"] if r[1] >= since]))
    return box


def test_ssm_scan_roofline_counts_rows_and_calls_on_nine_layers(spans_ring):
    m = Manifest(REPO)
    reader, args = m.reader("ssm_scan_roofline"), m.metric_spec("ssm_scan_roofline.p4f")["args"]
    spans_ring["recs"] = [("ds.serve.decode.dispatch", 6.0, 6.01, {"attended": 1, "active": 64, "pages": 1}),
                          ("ds.serve.chunk", 6.0, 6.03, {"chunks": 0, "rode": 1, "tokens": 200, "rows_self": 200, "rows_cross": 1})]
    ctx = _ctx(ops_s=0.004)
    want = 9 * kp.selective_scan(64 + 200, 64 + 1, 5120, 16)
    assert reader.read(ctx, **args) == pytest.approx(100.0 * want / 819e9 / 0.004)
    assert 0 < reader.read(ctx, **args) < 100
    import re
    assert re.search(args["pattern"], "%ssm_scan_step.3 = (f32[64,5120]{1,0}, f32[9,64,16,5120]) custom-call(")
    assert re.search(args["pattern"], "%ssm_scan_chunk = f32[256,5120] custom-call(") and not re.search(args["pattern"], "%decode_fn.2 = ")
    assert reader.read(_ctx(ops_s=0.0), **args) is None                    # the parent's trace: no such kernel
    spans_ring["recs"] = []
    assert reader.read(ctx, **args) is None


def test_paged_decode_roofline_counts_sixteen_reads_of_pair_heads(spans_ring):
    m = Manifest(REPO)
    reader, args = m.reader("paged_decode_roofline_p4f"), m.metric_spec("paged_decode_roofline.p4f")["args"]
    # the program's count: the mean over the 32 sub-blocks of what each reads (0 for 16 of them)
    keys = 64 * (8 * 512 + 8 * 2048)
    spans_ring["recs"] = [("ds.serve.decode.dispatch", 6.0, 6.01, {"attended": keys // 32, "active": 64, "pages": 1})]
    ctx = _ctx(ops_s=0.012)
    f, b = kp.paged_decode_keys(keys, 10, 40, 128, 2, 16 * 64)
    assert reader.read(ctx, **args) == pytest.approx(100.0 * kc.min_seconds(f, b, ctx.peak)[0] / 0.012)
    assert 0 < reader.read(ctx, **args) < 100
    spans_ring["recs"] = [("ds.serve.decode.dispatch", 6.0, 6.01, {"active": 3})]      # a program without the count
    assert reader.read(ctx, **args) is None


def test_module_time_and_part_share_specs_read_their_fixtures():
    m = Manifest(REPO)
    # the reader that timed this cell's steps until PR 59, by the chat cells' entry that keeps it: "decode" matches both
    # programs, so where chunks ride it reads a blend of two kinds of step (what retired it for the backlog cells)
    spec = m.metric_spec("decode_step_p50_s.loaded")
    trace = SimpleNamespace(module_durations={"jit_decode_fn": [0.02, 0.03, 0.04], "jit_chunk_decode_fn": [0.05], "jit_prefill_fn": [9.0]})
    assert m.reader(spec["reader"]).read(SimpleNamespace(trace=trace), **spec["args"]) == pytest.approx(0.035)
    from perfbench import program_parts
    seconds = {("ssm.proj", "none", True): 2.0, ("ssm.scan", "none", True): 1.0, ("attn.core", "none", True): 3.0,
               ("kv.write", "none", False): 1.0, ("mlp", "none", True): 13.0}
    got = {name: program_parts.share(seconds, 20.0, m.metric_spec(name)["args"]["parts"], None, None)
           for name in ("part_ssm_share.p4f", "part_attn_share.backlog")}
    assert got == {"part_ssm_share.p4f": pytest.approx(15.0), "part_attn_share.backlog": pytest.approx(20.0)}
