"""The per-layer table's rule since PR 47: ONE entry a reading. Two entries
never have equal spec files (reader and arguments) and the same ``moves``; a
reading that several cells take the same way is one entry whose ``workloads``
lists them. The rename table below is the record of what PR 47 merged and
retired (a ledger line older than PR 47 names a reading by its old name); it
is the one place under ``paths`` where an old name may stand. Everything is
found by name, nothing by position, and no count is pinned: the next append
breaks none of these."""

import json
import os

import pytest

from perfbench.manifest import Manifest

from . import tiny

REPO = tiny.REPO
METRICS = os.path.join(REPO, "perfbench", "metrics")

DOC, KX, MS4, LCF, P4F = ("serve-xl-doc-batch", "serve-kexaone-gen-backlog", "serve-ms4-longdoc-backlog",
                          "serve-lcflash-gen-backlog", "serve-phi4flash-reason-backlog")

# new entry: (the suffixes of the entries it replaced, the cells it lists in the table's order, reader, arguments)
MERGED = {
    "decode_step_p50_s.backlog": ("doc kx ms4 lcf p4f", [DOC, KX, MS4, LCF, P4F],
                                  "module_time", {"pattern": "decode", "q": 0.5}),
    "decode_slots_active.backlog": ("doc kx ms4 lcf", [DOC, KX, MS4, LCF, P4F], "span_attr_share",
                                    {"name": "ds.serve.decode.dispatch", "attr": "active", "over": "serving.max_slots"}),
    "idle_outside_spans_share.backlog": ("doc kx ms4 lcf", [DOC, KX, MS4, LCF, P4F], "idle_outside_spans", {}),
    "copy_layout_share.backlog": ("doc kx ms4 lcf", [DOC, KX, MS4, LCF, P4F],
                                  "op_share", {"category": "copy/layout", "of": "busy"}),
    "srv_step_host_p50_s.backlog": ("doc kx ms4 lcf", [DOC, KX, MS4, LCF, P4F],
                                    "span_quantile", {"name": "ds.serve.step", "q": 0.5, "minus_suffix": ".wait"}),
    "gen_tok_s.backlog": ("doc kx ms4 lcf", [DOC, KX, MS4, LCF, P4F], "tokens_in_window", {"prompt": False}),
    "part_unattributed_share.backlog": ("doc kx ms4 lcf", [DOC, KX, MS4, LCF, P4F],
                                        "part_share", {"parts": "none", "of": "busy"}),
    "part_attn_share.backlog": ("kx ms4 lcf p4f", [KX, MS4, LCF, P4F], "part_share",
                                {"parts": ["attn.qkv", "attn.core", "attn.out", "kv.write"], "of": "busy"}),
    "part_moe_route_share.backlog": ("kx ms4 lcf", [KX, MS4, LCF], "part_share", {"parts": ["moe.route"], "of": "busy"}),
    "moe_streamed_per_hit.backlog": ("kx ms4", [KX, MS4, LCF], "span_attr_ratio",
                                     {"name": ["ds.serve.emit", "ds.serve.chunk"], "attr": "moe_experts_streamed",
                                      "over": "moe_experts_hit"}),
    "mla_attention_share.backlog": ("ms4 lcf", [MS4, LCF], "op_share",
                                    {"pattern": "^%?mla_paged_(decode|chunk)[.\\d]* = |w_u[kv]\\b", "of": "busy"}),
    "moe_layer_share.backlog": ("ms4 lcf", [MS4, LCF], "op_share",
                                {"pattern": "moe_+experts_+w_(gate|up|down)|ragged-dot", "of": "busy"}),
}
# the first seven: what every cell that reports serve_tok_s reports, at no entry of its own
EVERY_BACKLOG_CELL = list(MERGED)[:7]
# old name -> new name, 42 of them
RENAMED = {f"{new.rsplit('.', 1)[0]}.{suffix}": new for new, (suffixes, *_) in MERGED.items() for suffix in suffixes.split()}
# retired stem: (the suffixes that went, reader, arguments, what reads the same thing now)
RETIRED_STEMS = {
    "device_idle_share": ("train chat doc loaded kx ms4 lcf", "device_idle", {},
                          "the ledger's idle_share (1 - device.busy_s / device.window_s of the traced run's line) x 100"),
    "decode_occupancy": ("chat doc loaded", "decode_occupancy", {}, "decode_slots_active.* (the scheduler's own count)"),
    "prefill_step_p50_s": ("doc kx ms4 lcf", "module_time", {"pattern": "prefill|chunk", "q": 0.5},
                           "decode_step_p50_s.backlog: both patterns match the one mixed program, jit_chunk_decode_fn"),
}
RETIRED = {f"{stem}.{suffix}": (reader, args) for stem, (suffixes, reader, args, _) in RETIRED_STEMS.items()
           for suffix in suffixes.split()}


@pytest.fixture(scope="module")
def m():
    real = Manifest(REPO)
    real.validate()
    return real


def _spec(manifest, name):
    spec = manifest.metric_spec(name)
    return json.dumps({"reader": spec["reader"], "args": spec.get("args", {})}, sort_keys=True)


def test_the_rename_table_holds_the_42_merged_and_the_14_retired():
    assert len(RENAMED) == 42 and len(RETIRED) == 14 and not set(RENAMED) & set(RETIRED)
    assert not (set(RENAMED) | set(RETIRED)) & set(MERGED)


def test_no_two_entries_have_equal_specs_and_the_same_moves(m):
    seen = {}
    for e in m.doc["per_layer"]:
        key = (_spec(m, e["name"]), e["moves"])
        assert key not in seen, f"{e['name']} reads what {seen[key]} reads and moves the same metric: list its cells there"
        seen[key] = e["name"]


@pytest.mark.parametrize("old", sorted(RENAMED))
def test_a_merged_entry_reads_what_the_entry_it_replaced_read(m, old):
    new = RENAMED[old]
    _, cells, reader, args = MERGED[new]
    by_name = {e["name"]: e for e in m.doc["per_layer"]}
    assert old not in by_name and not os.path.exists(os.path.join(METRICS, old + ".json"))
    assert m.metric_spec(new) == {"reader": reader, "args": args}
    entry = by_name[new]
    assert entry["moves"] == "serve_tok_s" and entry["workloads"][: len(cells)] == cells    # a later cell is appended
    order = [w["name"] for w in m.doc["workloads"]]
    assert entry["workloads"] == sorted(entry["workloads"], key=order.index)


@pytest.mark.parametrize("old", sorted(RETIRED))
def test_a_retired_name_is_in_no_table_and_has_no_file(m, old):
    assert old not in {e["name"] for e in m.doc["per_layer"] + m.doc["end_to_end"]}
    assert not os.path.exists(os.path.join(METRICS, old + ".json"))
    reader, _ = RETIRED[old]
    if reader != "module_time":       # the two readers nothing names any more went with their entries
        assert not os.path.exists(os.path.join(METRICS, "readers", reader + ".py"))


def test_no_file_under_paths_names_an_old_entry_but_this_one(m):
    old = sorted(set(RENAMED) | set(RETIRED))
    stems = ["device_idle", "decode_occupancy"]     # the retired readers and their metrics, whatever the suffix
    for path in m.doc["paths"]:
        for dirpath, dirs, files in os.walk(os.path.join(REPO, path)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for f in files:
                p = os.path.join(dirpath, f)
                if os.path.abspath(p) == os.path.abspath(__file__) or not f.endswith((".py", ".json", ".md", ".txt", ".toml")):
                    continue
                text = open(p, encoding="utf-8", errors="replace").read()
                found = [n for n in old + stems if n in text]
                assert not found, f"{os.path.relpath(p, REPO)} names {found}"


def test_every_cell_that_reports_serve_tok_s_is_listed_in_the_seven_shared_entries(m):
    by_name = {e["name"]: e for e in m.doc["per_layer"]}
    backlog = [w["name"] for w in m.doc["workloads"]
               if "serve_tok_s" in {e["name"] for e in m.metrics_for(w["name"], "end_to_end")}]
    assert set(backlog) >= {DOC, KX, MS4, LCF, P4F}
    for name in EVERY_BACKLOG_CELL:
        assert by_name[name]["workloads"] == backlog, name
    for e in m.doc["per_layer"]:
        if e["name"].endswith(".backlog"):      # the suffix names the group of cells that report serve_tok_s
            assert e["moves"] == "serve_tok_s" and set(e["workloads"]) <= set(backlog), e["name"]


def test_every_cell_reports_exactly_one_unattributed_share_and_one_decode_step(m):
    for w in m.doc["workloads"]:
        mine = [e["name"] for e in m.metrics_for(w["name"], "per_layer")]
        assert sum(n.startswith("part_unattributed_share.") for n in mine) == 1, w["name"]
        if w["name"].startswith("serve"):
            assert sum(n.split(".")[0] == "decode_step_p50_s" for n in mine) == 1, w["name"]
    assert "moe_streamed_per_hit.backlog" in {e["name"] for e in m.metrics_for(LCF, "per_layer")}


def test_at_least_24_entries_are_free(m):
    assert len(m.doc["per_layer"]) <= 128 - 24      # an upper limit, not a count: room for two cells' own entries


def test_every_spec_file_belongs_to_an_entry_and_every_entry_and_reader_is_found(m):
    names = {e["name"] for e in m.doc["per_layer"] + m.doc["end_to_end"]} - {"setup_s"}
    files = {f[: -len(".json")] for f in os.listdir(METRICS) if f.endswith(".json")}
    assert files == names, (sorted(files - names), sorted(names - files))
    readers = {f[: -len(".py")] for f in os.listdir(os.path.join(METRICS, "readers")) if f.endswith(".py")} - {"__init__"}
    assert readers == {m.metric_spec(n)["reader"] for n in names}, "a reader that no spec names, or a spec without its reader"
