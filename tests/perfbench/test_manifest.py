"""BENCHMARK.json as committed is inside the contract's limits, the validator
refuses what the contract refuses, and a cell, a configuration (of any
architecture), a runner, a traffic mix and a metric with a new reader are
added without editing a file that is there. For the per-layer table that last
sentence is tested by the tests themselves since PR 59: ``real`` is
``conftest.py``'s ``table``, the committed benchmark and then the committed
benchmark with a stand-in cell appended (``tiny.with_stand_in``: twelve entries
of its own, a place in every shared list), and
``test_the_stand_in_is_appended_and_edits_no_file_that_is_there`` holds the
overlay itself to the rule."""

import json
import os
import shutil

import pytest

from perfbench.manifest import Manifest, ManifestError

from . import tiny

REPO = tiny.REPO


@pytest.fixture(scope="module")
def real(table):
    return table


# the cells in the order they were proved on the chip (PRs 23 and 26); a later PR's come after them
PROVED = ["train-xl-l16-1chip", "serve-xl-chat-open", "serve-xl-doc-batch", "train-xl-dp4", "serve-xl-chat-loaded"]
GPT2_XL = {"n_embd": 1600, "n_head": 25, "vocab_size": 50257, "n_positions": 1024}
GPT2_XL_CUT = {"n_layer": 48, "attn_pdrop": 0.1, "embd_pdrop": 0.1, "resid_pdrop": 0.1}   # what `reduced` has named so far


def check_manifest_and_configs(m):
    """What holds of a committed benchmark, whatever its configurations are:
    each file carries its source's value of every key it names in ``reduced``
    (``published``), and a key differs from that value if and only if it is
    in ``reduced``."""
    m.validate()
    assert os.path.getsize(os.path.join(m.root, "BENCHMARK.json")) < 64 * 1024
    for c in m.doc["configs"]:
        cfg = m.config(c["name"])
        published = cfg.get("published", {})
        assert set(c["reduced"]) <= set(published), (c["name"], "a reduced key without its published value")
        for k, v in published.items():
            assert (cfg[k] != v) == (k in c["reduced"]), (c["name"], k)
        if cfg.get("model_type") == "gpt2":
            assert {k: cfg[k] for k in GPT2_XL} == GPT2_XL, c["name"]
            assert GPT2_XL_CUT.items() <= published.items(), c["name"]
    for w in m.doc["workloads"]:
        assert isinstance(m.traffic(w["traffic"])["loop"], str)


def check_order(m):
    """The cells that are there keep their relative order, and new ones come
    after them."""
    names = [w["name"] for w in m.doc["workloads"]]
    known = [n for n in names if n in PROVED]
    assert known == [n for n in PROVED if n in names]
    assert names[: len(known)] == known
    assert sum(1 for w in m.doc["workloads"] if w["chips"] == 4) <= max(1, len(names) // 4)


def test_committed_manifest_validates_and_every_name_finds_its_file(real):
    check_manifest_and_configs(real)


def test_cells_are_in_the_order_they_were_proved_with_one_on_four_chips(real):
    check_order(real)


def test_every_cell_reports_setup_another_end_to_end_metric_and_a_layer_metric(real):
    for w in real.doc["workloads"]:
        e2e = {m["name"] for m in real.metrics_for(w["name"], "end_to_end")}
        assert "setup_s" in e2e and len(e2e) >= 2
        for m in real.metrics_for(w["name"], "per_layer"):
            assert m["moves"] in e2e


BREAKS = {
    "space_in_name": lambda d: d["workloads"][0].update(name="train xl"),
    "slash_in_name": lambda d: d["per_layer"][0].update(name="a/b"),
    "greek_unit": lambda d: d["per_layer"][0].update(unit="µs"),
    "unit_with_space": lambda d: d["end_to_end"][0].update(unit="tokens per s"),
    "bound_over_a_tenth": lambda d: d["end_to_end"][0].update(bound=0.2),
    "moves_a_metric_the_cell_lacks": lambda d: d["per_layer"][0].update(moves="serve_tok_s"),
    "moves_nothing_known": lambda d: d["per_layer"][0].update(moves="nope"),
    "two_four_chip_cells": lambda d: [w.update(chips=4) for w in d["workloads"][: max(1, len(d["workloads"]) // 4) + 1]],
    "reduced_names_a_width": lambda d: d["configs"][0].update(reduced=["n_embd"]),
    "reduced_names_a_head_dim": lambda d: d["configs"][0].update(reduced=["head_dim"]),
    "reduced_names_a_window": lambda d: d["configs"][0].update(reduced=["sliding_window"]),
    "extra_key_on_a_metric": lambda d: d["per_layer"][0].update(why="because"),
    "extra_top_level_key": lambda d: d.update(notes="x"),
    "no_setup_s": lambda d: d.update(end_to_end=[m for m in d["end_to_end"] if m["name"] != "setup_s"]),
    "run_seconds_over_51": lambda d: d.update(run_seconds=52),
    "command_leaves_the_repo": lambda d: d.update(command=["python3", "../x.py"]),
    "absolute_command": lambda d: d.update(command=["/usr/bin/python3", "perfbench/run.py"]),
    "duplicate_cell": lambda d: d["workloads"].append(dict(d["workloads"][0])),
    "same_pair_twice": lambda d: d["workloads"].append(dict(d["workloads"][0], name="again")),
    "unused_config": lambda d: d["configs"].append(dict(d["configs"][0], name="spare", file="perfbench/configs/spare.json")),
    "config_file_outside_paths": lambda d: d["configs"][0].update(file="deepspeed_tpu/x.json"),
    "end_to_end_from_a_counter": lambda d: d["end_to_end"][0].update(source="program_counter"),
    "tab_in_why": lambda d: d["workloads"][0].update(why="a\tb"),
    "unknown_workload_on_metric": lambda d: d["per_layer"][0].update(workloads=["ghost"]),
    "chips_two": lambda d: d["workloads"][0].update(chips=2),
    "129_per_layer_entries": lambda d: d["per_layer"].extend(dict(d["per_layer"][0], name=f"pad-{i}")
                                                             for i in range(129 - len(d["per_layer"]))),
}


@pytest.mark.parametrize("case", sorted(BREAKS))
def test_validator_refuses(real, case):
    with pytest.raises(ManifestError):
        tiny.broken(real, BREAKS[case]).validate(check_files=False)


# `reduced` takes depth and counts of things, in any architecture's spelling, and no width
DEPTHS = ["num_hidden_layers", "n_layer", "num_layers", "first_k_dense_replace", "max_position_embeddings",
          "num_experts", "n_routed_experts"]
WIDTHS = ["hidden_size", "intermediate_size", "moe_intermediate_size", "head_dim", "kv_lora_rank",
          "num_experts_per_tok", "n_embd", "d_model", "ssm_state_size", "sliding_window"]


@pytest.mark.parametrize("key", DEPTHS)
def test_reduced_may_name_a_depth_or_a_count(real, key):
    tiny.broken(real, lambda d: d["configs"][0].update(reduced=[key])).validate(check_files=False)


@pytest.mark.parametrize("key", WIDTHS)
def test_reduced_may_not_name_a_width(real, key):
    with pytest.raises(ManifestError, match="a width"):
        tiny.broken(real, lambda d: d["configs"][0].update(reduced=[key])).validate(check_files=False)


def _resize(d, n):
    """``n`` cells whatever the table holds: a cell past the ``n``-th leaves
    (from every metric's list too; a metric left with no cell and a
    configuration left with none go with it), a missing one is a copy of the
    first under a name and a mix of its own."""
    del d["workloads"][n:]
    tiny.keep_cells(d, {w["name"] for w in d["workloads"]})
    used = {w["config"] for w in d["workloads"]}
    d["configs"] = [c for c in d["configs"] if c["name"] in used]
    first = d["workloads"][0]
    while len(d["workloads"]) < n:
        i = len(d["workloads"])
        d["workloads"].append(dict(first, name=f"extra-{i}", traffic=f"extra-mix-{i}"))
        for m in d["end_to_end"] + d["per_layer"]:
            if first["name"] in m.get("workloads", ()):
                m["workloads"].append(f"extra-{i}")


@pytest.mark.parametrize("start", [3, None, 11])
def test_a_table_of_eight_cells_takes_two_on_four_chips_and_not_three(real, start):
    """The eight are built from the committed table as it is (None), from one
    cut to three cells and from one grown to eleven: how many cells are
    committed is not this test's to pin."""
    def eight_with_two_on_four(d):
        if start is not None:
            _resize(d, start)
        _resize(d, 8)
        for i, w in enumerate(d["workloads"]):
            w.update(chips=4 if i < 2 else 1)

    eight = tiny.broken(real, eight_with_two_on_four)
    assert len(eight.doc["workloads"]) == 8 and sum(w["chips"] == 4 for w in eight.doc["workloads"]) == 2
    eight.validate(check_files=False)
    with pytest.raises(ManifestError, match="four-chip"):
        tiny.broken(eight, BREAKS["two_four_chip_cells"]).validate(check_files=False)


def _files(root):
    out = {}
    for dirpath, dirs, files in os.walk(root):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for f in files:
            p = os.path.join(dirpath, f)
            out[os.path.relpath(p, root)] = open(p, "rb").read()
    return out


def _is_grown(old, new):
    """``new`` is ``old`` with entries appended: to lists, at any depth."""
    if isinstance(old, list):
        return isinstance(new, list) and len(new) >= len(old) and all(_is_grown(a, b) for a, b in zip(old, new))
    if isinstance(old, dict):
        return isinstance(new, dict) and set(new) == set(old) and all(_is_grown(v, new[k]) for k, v in old.items())
    return old == new


def test_the_stand_in_is_appended_and_edits_no_file_that_is_there(real):
    """The overlay that every table-reading test runs against is itself what it says: the committed files byte for
    byte, BENCHMARK.json with entries appended to its lists, new files beside them; one cell, twelve entries of its
    own, and a place in every list that two or more backlog cells share."""
    if real.root == REPO:
        assert tiny.STAND_IN_CELL not in [w["name"] for w in real.doc["workloads"]]     # nothing of it is committed
        return
    tree = Manifest(REPO)
    assert _is_grown(tree.doc, real.doc)
    before = {rel: data for path in tree.doc["paths"] for rel, data in _files(os.path.join(REPO, path)).items()
              if not rel.startswith("data" + os.sep)}
    after = {rel: data for path in real.doc["paths"] for rel, data in _files(os.path.join(real.root, path)).items()}
    assert before.items() <= after.items(), sorted(rel for rel in before if after.get(rel) != before[rel])
    assert len(after) == len(before) + 1 + len(tiny.STAND_IN_OWN)       # its configuration file and a spec file an entry
    assert [w["name"] for w in real.doc["workloads"]][len(tree.doc["workloads"]):] == [tiny.STAND_IN_CELL]
    own = [e["name"] for e in real.doc["per_layer"] if e.get("workloads") == [tiny.STAND_IN_CELL]]
    assert own == [row[0] for row in tiny.STAND_IN_OWN] and len(own) == 12
    shared = tiny.shared_lists(tree.doc)
    assert len(shared) >= 20
    for e in shared:
        now = next(x for x in real.doc["end_to_end"] + real.doc["per_layer"] if x["name"] == e["name"])
        assert now["workloads"] == e["workloads"] + [tiny.STAND_IN_CELL], e["name"]
    # the tiny copy builds from it too, and is the tree's: the stand-in's pair of stand-ins is taken (tiny.make), so
    # the tests that RUN a tiny cell have nothing to run twice
    small = tiny.make(os.path.join(real.root, os.pardir, "tiny"), repo=real.root)
    small.validate()
    assert small.doc == tiny.make(os.path.join(real.root, os.pardir, "tiny_tree")).doc


@pytest.mark.parametrize("with_stand_in", [False, True])
def test_a_new_architecture_goes_in_as_new_files_and_appended_entries(real, tmp_path, with_stand_in):
    """What the next ``model_config`` PR does, on a copy of the *committed*
    benchmark: a configuration of another ``model_type`` with Hugging Face's
    keys and its depth cut, a runner of its own, a cell, one per-layer metric.
    The copy validates, the checks of the committed manifest and of the order
    hold of it, the tiny copy builds from it (with the cell if the PR brought
    a stand-in for its runner, without it if not), and no file that was there
    differs."""
    root = str(tmp_path / "committed")
    for sub in ("perfbench", os.path.join("tests", "perfbench")):
        shutil.copytree(os.path.join(real.root, sub), os.path.join(root, sub), ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(real.root, "BENCHMARK.json"), root)
    before = _files(root)

    def write(rel, text):
        p = os.path.join(root, rel)
        assert not os.path.exists(p)
        os.makedirs(os.path.dirname(p), exist_ok=True)
        with open(p, "w") as f:
            f.write(text)

    write("perfbench/configs/probe-moe-serve-1chip.json", json.dumps({
        "model_type": "olmoe", "hidden_size": 2048, "intermediate_size": 1024, "num_hidden_layers": 10,
        "num_attention_heads": 16, "num_experts": 64, "num_experts_per_tok": 8, "vocab_size": 50304,
        "max_position_embeddings": 4096, "published": {"num_hidden_layers": 16}, "runner": "probe_runner",
        "dtype": "bfloat16"}))
    write("perfbench/runners/probe_runner.py",
          "class Runner:\n    def __init__(self, ctx, seed, devices, span, log):\n        self.ctx = ctx\n")
    write("perfbench/metrics/probe_load_share.json", json.dumps({"reader": "probe_load_share", "args": {}}))
    write("perfbench/metrics/readers/probe_load_share.py", "def read(ctx):\n    return None\n")
    if with_stand_in:
        write("tests/perfbench/stand_ins/probe_runner.json", json.dumps({
            "config": tiny.serve_config(runner="probe_runner"), "traffic": {"backlog": tiny.BACKLOG}}))
    doc = json.loads(before["BENCHMARK.json"])
    doc["configs"].append({"name": "probe-moe-serve-1chip", "source": "https://huggingface.co/allenai/OLMoE-1B-7B-0125-Instruct",
                           "file": "perfbench/configs/probe-moe-serve-1chip.json", "reduced": ["num_hidden_layers"],
                           "why": "tests"})
    doc["workloads"].append({"name": "serve-probe-doc", "config": "probe-moe-serve-1chip", "traffic": "doc-backlog",
                             "chips": 1, "why": "tests"})
    for e in doc["end_to_end"]:
        if e["name"] == "serve_tok_s":
            e["workloads"].append("serve-probe-doc")
    doc["per_layer"].append({"name": "probe_load_share", "unit": "%", "better": "higher", "source": "program_counter",
                             "layer": "tests", "moves": "serve_tok_s", "workloads": ["serve-probe-doc"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(doc, f)

    m = Manifest(root)
    check_manifest_and_configs(m)
    check_order(m)
    t = tiny.make(tmp_path / "tiny", repo=root)
    t.validate()
    cells = [w["name"] for w in t.doc["workloads"]]
    metrics = [x["name"] for x in t.doc["per_layer"]]
    assert ("serve-probe-doc" in cells) == with_stand_in == ("probe_load_share" in metrics)
    assert cells[:3] == ["train-xl-l16-1chip", "serve-xl-chat-open", "serve-xl-doc-batch"]
    after = _files(root)
    assert _is_grown(json.loads(before.pop("BENCHMARK.json")), json.loads(after["BENCHMARK.json"]))
    for rel, data in before.items():
        assert after[rel] == data, f"{rel} was edited"


def test_discovery_needs_no_edit_to_a_file_that_is_there(tmp_path):
    """One of each is added to a temporary copy: files are written, entries are
    appended to BENCHMARK.json, nothing that exists is touched; the harness
    validates the copy and runs the new cell with the new reader."""
    m = tiny.make(tmp_path)
    before = _files(m.bench_dir)

    def write(rel, text):
        p = os.path.join(m.root, rel)
        assert not os.path.exists(p)
        with open(p, "w") as f:
            f.write(text)

    write("perfbench/configs/tiny-serve-wide.json", json.dumps(tiny.serve_config(
        serving={"max_slots": 2, "page_size": 8, "num_pages": 48, "max_prompt_len": 96, "max_new_tokens": 8,
                 "prefill_chunk_tokens": 32, "max_queue_depth": 512})))
    write("perfbench/traffic/tiny-burst.json", json.dumps(dict(tiny.OPEN, profile=[[0, 0.2, 3.0], [0.2, 1.0, 1.0]])))
    write("perfbench/metrics/slots_times_requests.json", json.dumps({"reader": "slots_times_requests", "args": {"scale": 2}}))
    write("perfbench/metrics/readers/slots_times_requests.py",
          "def read(ctx, scale):\n    return scale * ctx.config['serving']['max_slots'] * sum(1 for r in ctx.recs if r.counted)\n")
    write("perfbench/metrics/absent_span.json", json.dumps({"reader": "absent_span", "args": {}}))
    write("perfbench/metrics/readers/absent_span.py", "def read(ctx):\n    return None\n")
    doc = m.doc
    doc["configs"].append({"name": "tiny-serve-wide", "source": "tests", "file": "perfbench/configs/tiny-serve-wide.json",
                           "reduced": [], "why": "tests"})
    doc["workloads"].append({"name": "tiny-burst-cell", "config": "tiny-serve-wide", "traffic": "tiny-burst",
                             "chips": 1, "why": "tests"})
    for e in doc["end_to_end"]:
        if e["name"] == "latency_per_token_p50_s":
            e["workloads"].append("tiny-burst-cell")   # an entry of a list grows; no file is edited
    for name in ("slots_times_requests", "absent_span"):
        doc["per_layer"].append({"name": name, "unit": "1", "better": "higher", "source": "program_counter",
                                 "layer": "tests", "moves": "latency_per_token_p50_s", "workloads": ["tiny-burst-cell"]})
    with open(os.path.join(m.root, "BENCHMARK.json"), "w") as f:
        json.dump(doc, f)

    m2 = Manifest(m.root)
    m2.validate()
    from perfbench import run

    out, ctx = run.run_cell(m2, "tiny-burst-cell", 3, 1.0, True, require_tpu=False, trace_dir=str(tmp_path / "trace"))
    assert out["correct"] and out["attempted"] == 6
    assert out["metrics"]["slots_times_requests"] == {"value": 2.0 * 2 * 6, "unit": "1"}
    assert "absent_span" not in out["metrics"]   # a reader that finds nothing returns nothing
    after = _files(m.bench_dir)
    for rel, data in before.items():
        assert after[rel] == data, f"{rel} was edited"
