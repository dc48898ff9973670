"""BENCHMARK.json as committed is inside the contract's limits, the validator
refuses what the contract refuses, and a cell, a configuration, a traffic mix
and a metric with a new reader are added without editing a file that is there."""

import copy
import json
import os

import pytest

from perfbench.manifest import Manifest, ManifestError

from . import tiny

REPO = tiny.REPO


@pytest.fixture(scope="module")
def real():
    return Manifest(REPO)


def test_committed_manifest_validates_and_every_name_finds_its_file(real):
    real.validate()
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) < 64 * 1024
    for c in real.doc["configs"]:
        cfg = real.config(c["name"])
        assert cfg["n_embd"] == 1600 and cfg["n_head"] == 25 and cfg["vocab_size"] == 50257 and cfg["n_positions"] == 1024
        for k in ("n_layer", "attn_pdrop", "embd_pdrop", "resid_pdrop"):
            published = {"n_layer": 48, "attn_pdrop": 0.1, "embd_pdrop": 0.1, "resid_pdrop": 0.1}[k]
            assert (cfg[k] != published) == (k in c["reduced"]), (c["name"], k)
    for w in real.doc["workloads"]:
        assert real.traffic(w["traffic"])["loop"] in ("open", "backlog", "train_steps")


def test_cells_are_in_the_order_they_were_proved_with_one_on_four_chips(real):
    names = [w["name"] for w in real.doc["workloads"]]
    order = ["train-xl-l16-1chip", "serve-xl-chat-open", "serve-xl-doc-batch", "train-xl-dp4"]
    assert names == [n for n in order if n in names]
    assert sum(1 for w in real.doc["workloads"] if w["chips"] == 4) <= 1


def test_every_cell_reports_setup_another_end_to_end_metric_and_a_layer_metric(real):
    for w in real.doc["workloads"]:
        e2e = {m["name"] for m in real.metrics_for(w["name"], "end_to_end")}
        assert "setup_s" in e2e and len(e2e) >= 2
        for m in real.metrics_for(w["name"], "per_layer"):
            assert m["moves"] in e2e


def _broken(real, edit):
    m = copy.copy(real)
    m.doc = copy.deepcopy(real.doc)
    edit(m.doc)
    return m


BREAKS = {
    "space_in_name": lambda d: d["workloads"][0].update(name="train xl"),
    "slash_in_name": lambda d: d["per_layer"][0].update(name="a/b"),
    "greek_unit": lambda d: d["per_layer"][0].update(unit="µs"),
    "unit_with_space": lambda d: d["end_to_end"][0].update(unit="tokens per s"),
    "bound_over_a_tenth": lambda d: d["end_to_end"][0].update(bound=0.2),
    "moves_a_metric_the_cell_lacks": lambda d: d["per_layer"][0].update(moves="serve_tok_s"),
    "moves_nothing_known": lambda d: d["per_layer"][0].update(moves="nope"),
    "two_four_chip_cells": lambda d: [w.update(chips=4) for w in d["workloads"][:2]],
    "reduced_names_a_width": lambda d: d["configs"][0].update(reduced=["n_embd"]),
    "reduced_names_a_head_dim": lambda d: d["configs"][0].update(reduced=["head_dim"]),
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
}


@pytest.mark.parametrize("case", sorted(BREAKS))
def test_validator_refuses(real, case):
    with pytest.raises(ManifestError):
        _broken(real, BREAKS[case]).validate(check_files=False)


def test_discovery_needs_no_edit_to_a_file_that_is_there(tmp_path):
    """One of each is added to a temporary copy: files are written, entries are
    appended to BENCHMARK.json, nothing that exists is touched; the harness
    validates the copy and runs the new cell with the new reader."""
    m = tiny.make(tmp_path)
    before = {}
    for dirpath, _, files in os.walk(m.bench_dir):
        for f in files:
            p = os.path.join(dirpath, f)
            before[p] = open(p, "rb").read()

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
    for p, data in before.items():
        assert open(p, "rb").read() == data, f"{p} was edited"
