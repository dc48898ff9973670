"""The ``zaya`` configuration's benchmark files on the CPU: its stand-in cell
through the harness (``tiny.make`` finds it by its runner), the float32
reference against each control at the small size, the new readers on hand-made
spans, and the cost functions against hand counts. Nothing here is a device
number."""

import json
import re
from types import SimpleNamespace

import pytest

from perfbench import kernel_costs as kc
from perfbench import kernel_costs_zaya as kz
from perfbench import reference_zaya as reference
from perfbench import run
from perfbench.manifest import Manifest
from perfbench.peaks import peak_for

from . import tiny

CELL = "serve-zaya1-reason-backlog"
CONFIG = "zaya1-8b-l14-serve-1chip"
SEED = 2**31 + 149
REPO = tiny.REPO
# the cell's own entries (`.zaya`), in the order PR 49 appended them, and the `.backlog` entries it is listed in: what
# the cell must KEEP, found by name (a later PR may list it in an entry more; PR 59 listed it in five, and its steps are
# timed by kind since: `plain_step_p50_s` 14.09 ms, where the blend had read 14.10, and `mixed_step_p50_s` 15.98)
ZAYA = ("part_cca_share", "part_head_share", "moe_weight_stream_roofline", "paged_decode_roofline", "moe_load_max_over_mean")
SHARED = ("plain_step_p50_s", "mixed_step_p50_s", "dispatched_ahead_share", "paged_walk_share", "decode_slots_active", "idle_outside_spans_share", "copy_layout_share", "srv_step_host_p50_s",
          "gen_tok_s", "part_unattributed_share", "part_attn_share", "part_moe_route_share", "moe_streamed_per_hit",
          "moe_layer_share")
MINE = {n + ".zaya" for n in ZAYA} | {n + ".backlog" for n in SHARED}


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
    assert ref["max_logit_gap"] <= ref["margin"] and ref["positions"] == 40 and ref["mean_logit_gap"] <= ref["mean_gap_limit"]
    assert ref["handover_gap"] <= ref["handover_largest"] <= ref["handover_margin"] and ref["handover_positions"] == 14
    assert ref["left_out"] < 40


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
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "reason-backlog-s64", 1)
    assert "14 of 40 layers" in cell["why"]           # fewer layers than a deployment: the host's share is larger
    assert m.config_entry(CONFIG)["file"] == f"perfbench/configs/{CONFIG}.json"
    assert sum(w["chips"] == 4 for w in d["workloads"]) <= max(1, len(d["workloads"]) // 4)
    tiny.check_cell_keeps(m, CELL, [n + ".zaya" for n in ZAYA], MINE)
    shares = {x["name"]: x for x in d["per_layer"] if "roofline" in x["name"] and CELL in x.get("workloads", ())}
    assert {"moe_weight_stream_roofline.zaya", "paged_decode_roofline.zaya"} <= set(shares)       # at least these
    assert all(x["unit"] == "%" and x["source"] == "device_trace" for x in shares.values())
    assert m.metric_spec("part_cca_share.zaya")["args"]["parts"] == ["attn.cca"]
    assert m.metric_spec("part_head_share.zaya")["args"]["parts"] == ["head"]       # the 1.07 GB head alone, not the sampler
    # the shared expert-kernel pattern finds this program's kernel by the name it has in every expert family
    from deepspeed_tpu.ops.pallas import grouped_experts
    for name in ("moe_layer_share.backlog", "moe_weight_stream_roofline.zaya"):
        assert re.search(m.metric_spec(name)["args"]["pattern"], grouped_experts.KERNEL_NAME)


def test_the_stand_in_cell_is_in_the_tiny_copy(manifest):
    assert CELL in [w["name"] for w in manifest.doc["workloads"]]
    assert manifest.config(manifest.cell(CELL)["config"])["runner"] == "serve_zaya"


def test_traced_stand_in_run_is_correct_and_prints_every_metric_that_needs_no_device(manifest, tmp_path_factory):
    out, ctx = _run(manifest, tmp_path_factory, True)
    _sound(out)
    listed = {m["name"]: m for m in manifest.metrics_for(CELL, "per_layer")}
    assert MINE <= set(listed)
    host = {n for n, m in listed.items() if m["source"] != "device_trace"}
    assert {"gen_tok_s.backlog", "decode_slots_active.backlog", "srv_step_host_p50_s.backlog", "moe_streamed_per_hit.backlog",
            "dispatched_ahead_share.backlog", "paged_walk_share.backlog", "moe_load_max_over_mean.zaya"} | tiny.SETUP <= host
    assert set(out["metrics"]) == host       # no device plane on the CPU: the device readers found nothing, and said so
    assert out["metrics"]["moe_load_max_over_mean.zaya"]["value"] >= 1.0
    assert out["metrics"]["moe_streamed_per_hit.backlog"]["value"] >= 1.0          # the masked form streams every held expert
    from perfbench import program_spans
    recs = program_spans.records_in(ctx.window) or ()
    emits = [r[3] for r in recs if r[0] == "ds.serve.emit"]
    assert emits and all(a["moe_pairs_held"] == a["moe_pairs_routed"] for a in emits)      # one pick, every expert held
    # ... and what an untraced run prints, read from the same run: the cell's end-to-end metrics
    e2e = {m["name"] for m in manifest.metrics_for(CELL, "end_to_end")}
    assert e2e == {"serve_tok_s", "setup_s"}
    spec = manifest.metric_spec("serve_tok_s")
    assert manifest.reader(spec["reader"]).read(ctx, **spec.get("args", {})) > 0 and ctx.window[0] > 0


def test_reference_catches_each_control_at_the_small_size_on_weights_whose_routers_are_settled(runner, monkeypatch):
    """The served tokens read against a reference with one thing changed (or
    the reference continued in int8 read by the float32 one): NOT correct by
    one of the stand-in's three limits (``carry_edge`` cuts at every 8th
    position here, the stand-in's chunk, and at the first row a decode step
    computes), ``carry_edge`` by the hand-over's own. The same requests served
    again in used slots read CORRECT. And the weights all of it ran on:
    ``weights_zaya.settle`` moves ``moe.wd`` and ``moe.bias`` and nothing else,
    and under it the deepest layer's fullest expert holds less of the picks
    of OTHER tokens than under the module's plain draw (one case: a case of
    its own sets the runner up again on another worker)."""
    from perfbench.tools import control_zaya as control

    monkeypatch.setattr(reference, "CHUNK", 8)
    ok, notes = runner.reference_check()
    assert ok and notes["max_logit_gap"] <= notes["margin"] and notes["mean_logit_gap"] <= notes["mean_gap_limit"]
    arch = reference.Arch.from_config(runner.cfg)
    # two of the seven (tests/unit/test_zaya.py moves the logits with each of them): a served call that lost
    # its carried rows, and the shift; int8 from the shortest prompt alone
    out = control.readings(runner, arch, ["carry_edge", "no_shift"], (24, 0))
    assert out["served_correct"] and out["controls_read_correct"] == [], out
    assert out["carry_edge"]["handover_gap"] > out["handover_margin"]      # the FOURTH largest: every hand-over moved
    assert out["int8"]["max_logit_gap"] > out["margin"] and out["int8"]["positions"] == 24
    assert out["reused_slot_reads_correct"] and out["reused_slot_same_tokens"] and out["reused_slot"]["positions"] == 40

    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models import zaya
    from perfbench import weights_zaya

    cfg = runner.mcfg
    # the module's draw by ONE program (alone it compiles one a distinct leaf)
    drawn = jax.jit(lambda key: zaya.init_params(cfg, key, jnp.float32))(jax.random.PRNGKey(runner.seed % (2**31 - 1)))
    settled = runner.engine.params
    moved = {jax.tree_util.keystr(k) for (k, a), b in zip(jax.tree_util.tree_leaves_with_path(drawn), jax.tree_util.tree_leaves(settled))
             if not jnp.array_equal(a, b)}
    assert moved == {f"['layers'][{l}]['moe']['{n}']" for l in range(cfg.n_layer) for n in ("wd", "bias")}
    assert (weights_zaya.LENGTH, weights_zaya.ROUNDS, weights_zaya.RATE) == (256, 400, 0.02)

    def fullest(p, ids):
        fam = zaya.ZayaFamily(cfg)
        pos = jnp.broadcast_to(jnp.arange(ids.shape[1])[None], ids.shape)
        h, carry = fam.embed(p, ids, pos), None
        for l in range(cfg.n_layer):
            lp = fam.layer(p, l)
            o = zaya.dense_attention(fam, lp, h, pos)
            logits = fam.before_experts(lp, h, o, carry)[2]
            pick = jnp.argmax(jax.nn.softmax(logits, axis=-1) + lp["moe"]["bias"], axis=-1)
            h, carry, _ = fam.after_attention(lp, h, o, l, carry=carry)
        return jnp.mean(jax.nn.one_hot(pick, cfg.num_experts), axis=0).max()

    ids = jax.random.randint(jax.random.PRNGKey(5), (4, 64), 0, cfg.vocab_size)
    both = jax.jit(lambda a, b, i: (fullest(a, i), fullest(b, i)))(drawn, settled, ids)
    assert float(both[1]) < float(both[0]) and float(both[1]) < 0.5


def test_the_configuration_file_holds_every_number_of_the_catalog_row_and_cuts_the_depth_alone():
    m = Manifest(REPO)
    c, entry = m.config(CONFIG), m.config_entry(CONFIG)
    assert entry["reduced"] == ["num_hidden_layers"] and entry["source"] == "https://huggingface.co/Zyphra/ZAYA1-8B/blob/main/config.json"
    row = {"attention_bias": False, "cca_time0": 2, "cca_time1": 2, "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
           "lm_head_bias": False, "max_position_embeddings": 131072, "model_type": "zaya", "moe_intermediate_size": 2048,
           "num_attention_heads": 8, "num_experts": 16, "num_experts_per_tok": 1, "num_key_value_heads": 2,
           "partial_rotary_factor": 0.5, "rms_norm_eps": 1e-05, "router_hidden_size": 256, "sliding_window": None,
           "tie_word_embeddings": True, "vocab_size": 262272}
    assert {k: c[k] for k in row} == row
    assert c["num_hidden_layers"] == 14 and c["published"]["num_hidden_layers"] == 40 and c["layer_types"] == ["hybrid"] * 40
    assert c["rope_parameters"]["hybrid"] == {"partial_rotary_factor": 0.5, "rope_theta": 5000000, "rope_type": "default"}
    assert c["dtype"] == "bfloat16" and c["runner"] == "serve_zaya"
    said = " ".join(c["assumed"])
    for word in ("arXiv:2510.04476", "arXiv:2511.17127", "hybrid_sliding", "16 wide", "padded ONCE", "BEFORE the convolutions",
                 "exp(tau_j)", "first d/2 = 64 lanes", "u_{t-1} Wv2", "four learned vectors", "gamma_l", "exact gelu",
                 "selects only", "not renormalised", "+-1/sqrt(2)", "4/sqrt(256)", "2 688"):
        assert word in said, word
    assert "14 of the 40 layers" in c["deployment"] and "pipeline stages" in c["deployment"]
    sv, tr = c["serving"], m.traffic(m.cell(CELL)["traffic"])
    assert sv == {"max_slots": 64, "page_size": 128, "num_pages": 3073, "max_prompt_len": 2048, "max_new_tokens": 4096,
                  "prefill_chunk_tokens": 256, "max_queue_depth": 4096, "temperature": 0.0}
    assert sv["num_pages"] == sv["max_slots"] * -(-(sv["max_prompt_len"] + sv["max_new_tokens"]) // sv["page_size"]) + 1
    assert tr["ramp"] == {"requests": 78, "aged": True} and tr["loop"] == "backlog" and tr["queue_depth"] == 2
    assert (c["warmup_short_prompt"], c["warmup_long_prompt"], c["warmup_new_tokens"]) == (96, 1025, 256)
    # every chunked warm-up prompt ends one or two rows into its last chunk: its first served logits come of carried rows
    chunked = [n for n in (c["warmup_long_prompt"], *c["warmup_edge_prompts"]) if n > sv["prefill_chunk_tokens"]]
    assert len(chunked) == 4 and all(n % sv["prefill_chunk_tokens"] in (1, 2) for n in chunked)
    ref = c["reference"]
    assert min(ref["tie_margin"], ref["logit_margin"], ref["mean_gap_limit"], ref["gap_cap"], ref["handover_margin"]) > 0
    assert "PLACEHOLDER" not in ref["why"]
    # the resident bytes the cell was sized by: a layer's CCA, router and 16 experts; the tied embedding; the pages
    E, C, Dq, Dk, R, N, F = 2048, 1280, 1024, 256, 256, 16, 2048
    cca = E * (C + Dk) + Dq * E + 2 * C + 10 * 2 * 128 * 128 + 2 * C + 2
    router = E * R + 2 * R * R + R * N + 2 * R + N
    layer = cca + router + N * 3 * E * F + 10 * E
    assert cca == pytest.approx(5.57e6, rel=2e-3) and router == pytest.approx(0.66e6, rel=1e-2) and layer == pytest.approx(207.6e6, rel=1e-3)
    assert 2 * (14 * layer + 262272 * E) == pytest.approx(6.89e9, rel=2e-3)
    assert sv["num_pages"] * 128 * 14 * 2 * 2 * 128 * 2 == pytest.approx(5.64e9, rel=1e-3)     # 1 KB a token a layer
    assert 64 * 14 * (2 * C + 128) * 2 == 4816896                                               # the carried rows: 4.8 MB


# -- the cost functions against hand counts ----------------------------------------------------

def test_kernel_costs_against_hand_counts_at_the_published_shapes():
    big = Manifest(REPO).config(CONFIG)
    assert kz.sparse_layers(big) == 14
    # a decode step at 64 slots: every one of 14 x 16 experts hit, 64 pairs a layer (one pick)
    f, b = kz.routed_experts(14 * 16, 14 * 64, 14 * 64, 2048, 2048, 2)
    assert b == 14 * 16 * 3 * 2048 * 2048 * 2 + 2 * 14 * 64 * 2048 * 2 and f == 2 * 3 * 14 * 64 * 2048 * 2048
    assert b == pytest.approx(5.64e9, rel=2e-3) and kc.min_seconds(f, b, peak_for("TPU v5 lite"))[1] == "memory"
    # ... and its attention at 2 700 tokens a slot: K and V of 2 kv heads of 128, whatever the 8 query heads
    keys = 14 * 64 * 2700
    f, b = kz.paged_decode_keys(keys, 2, 8, 128, 2, 14 * 64)
    assert b == 2 * keys * 2 * 128 * 2 + 2 * 14 * 64 * 8 * 128 * 2 and f == 4 * keys * 8 * 128
    assert b == pytest.approx(2.48e9, rel=1e-2) and kc.min_seconds(f, b, peak_for("TPU v5 lite"))[1] == "memory"
    ctx = kz.with_mistral4_keys(SimpleNamespace(config=big))
    assert ctx.config["n_routed_experts"] == 16 and ctx.config["first_k_dense_replace"] == 0 and ctx.config["num_hidden_layers"] == 14


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


def test_paged_decode_roofline_counts_fourteen_reads_of_two_kv_heads(spans_ring):
    m = Manifest(REPO)
    reader, args = m.reader("paged_decode_roofline_zaya"), m.metric_spec("paged_decode_roofline.zaya")["args"]
    spans_ring["recs"] = [("ds.serve.decode.dispatch", 6.0, 6.01, {"attended": 64 * 2700, "active": 64, "pages": 1})]
    ctx = _ctx(ops_s=0.004)
    f, b = kz.paged_decode_keys(14 * 64 * 2700, 2, 8, 128, 2, 14 * 64)
    assert reader.read(ctx, **args) == pytest.approx(100.0 * kc.min_seconds(f, b, ctx.peak)[0] / 0.004)
    assert 0 < reader.read(ctx, **args) < 100
    assert re.search(args["pattern"], "%decode_fn.3 = bf16[64,8,128]{2,1,0} custom-call(") and not re.search(args["pattern"], "%chunk_fn = ")
    assert reader.read(_ctx(ops_s=0.0), **args) is None                    # a trace without the kernel
    spans_ring["recs"] = [("ds.serve.decode.dispatch", 6.0, 6.01, {"active": 3})]      # a program without the count
    assert reader.read(ctx, **args) is None


def test_moe_readers_count_sixteen_held_one_pick_over_fourteen_layers(spans_ring):
    m = Manifest(REPO)
    stream, args = m.reader("moe_weight_stream_roofline_zaya"), m.metric_spec("moe_weight_stream_roofline.zaya")["args"]
    load = m.reader("moe_load_max_over_mean_zaya")
    emit = {"moe_experts_hit": 14 * 16 - 3, "moe_pairs_held": 14 * 64, "moe_pairs_routed": 14 * 64, "moe_load_max": 11,
            "moe_experts_streamed": 14 * 16 - 3}
    spans_ring["recs"] = [("ds.serve.emit", 6.0, 6.01, emit)]
    ctx = _ctx(ops_s=0.008)
    f, b = kz.routed_experts(14 * 16 - 3, 14 * 64, 14 * 64, 2048, 2048, 2)
    assert stream.read(ctx, **args) == pytest.approx(100.0 * kc.min_seconds(f, b, ctx.peak)[0] / 0.008)
    assert 0 < stream.read(ctx, **args) < 100
    assert load.read(ctx) == pytest.approx(11 * 16 * 14 / (14 * 64))          # the fullest expert over the mean of 4 a layer
    spans_ring["recs"] = [("ds.serve.emit", 6.0, 6.01, {"tokens": 64})]         # the parent's program: no such attributes
    assert stream.read(ctx, **args) is None and load.read(ctx) is None


def test_part_share_specs_read_their_fixtures():
    from perfbench import program_parts

    m = Manifest(REPO)
    seconds = {("attn.cca", "none", False): 1.0, ("attn.qkv", "none", True): 1.0, ("attn.core", "none", True): 2.0,
               ("head", "none", True): 3.0, ("sample", "none", False): 0.5, ("moe.experts", "none", True): 12.5}
    got = {name: program_parts.share(seconds, 20.0, m.metric_spec(name)["args"]["parts"], None, None)
           for name in ("part_cca_share.zaya", "part_head_share.zaya", "part_attn_share.backlog")}
    assert got == {"part_cca_share.zaya": pytest.approx(5.0), "part_head_share.zaya": pytest.approx(15.0),
                   "part_attn_share.backlog": pytest.approx(15.0)}          # attn.cca is no part of the shared attention entry
