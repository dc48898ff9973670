"""The per-layer table's rule since PR 47: ONE entry a reading. Two entries
never have equal spec files (reader and arguments) and the same ``moves``; a
reading that several cells take the same way is one entry whose ``workloads``
lists them. The rename table below is the record of what PR 47 merged and
retired (a ledger line older than PR 47 names a reading by its old name); it
is the one place under ``paths`` where an old name may stand. Everything is
found by name, nothing by position, and no count is pinned: the next append
breaks none of these, and since PR 59 the tests show it themselves. Every test
here takes the table from ``conftest.py``'s ``table``: the tree's, and the
tree's with a stand-in cell appended (``tiny.with_stand_in``: twelve entries
of its own, a place in every shared list). The table's LENGTH is limited in
one place, ``manifest.validate`` (128, the contract's); the room under it is
there to be used by the PR that adds a cell (PERF.md section 3)."""

import json
import os
import re

import pytest

from perfbench.manifest import ManifestError

from . import tiny

REPO = tiny.REPO

DOC, KX, MS4, LCF, P4F = ("serve-xl-doc-batch", "serve-kexaone-gen-backlog", "serve-ms4-longdoc-backlog",
                          "serve-lcflash-gen-backlog", "serve-phi4flash-reason-backlog")

# new entry: (the suffixes of the entries it replaced, the cells it lists in the table's order, reader, arguments)
MERGED = {
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
# what every cell that reports serve_tok_s reports, at no entry of its own: the first six, and since PR 59 the share of
# its dispatches launched a step ahead (all eight cells read it; the blend of step kinds that was the seventh is retired)
EVERY_BACKLOG_CELL = list(MERGED)[:6] + ["dispatched_ahead_share.backlog"]
# ... and one or more of these, the device time of a step by its kind (PR 55; `chunk` since PR 59): a cell is listed
# where every traced run the builder made of it held 8 steps of the kind or more
STEP_KINDS = ("plain_step_p50_s.backlog", "mixed_step_p50_s.backlog", "chunk_step_p50_s.backlog")
# old name -> new name, 37 of them (42 until PR 59 retired the entry five of them had become)
RENAMED = {f"{new.rsplit('.', 1)[0]}.{suffix}": new for new, (suffixes, *_) in MERGED.items() for suffix in suffixes.split()}
# retired stem: (the suffixes that went, reader, arguments, what reads the same thing now)
RETIRED_STEMS = {
    "device_idle_share": ("train chat doc loaded kx ms4 lcf", "device_idle", {},
                          "the ledger's idle_share (1 - device.busy_s / device.window_s of the traced run's line) x 100"),
    "decode_occupancy": ("chat doc loaded", "decode_occupancy", {}, "decode_slots_active.* (the scheduler's own count)"),
    "prefill_step_p50_s": ("doc kx ms4 lcf", "module_time", {"pattern": "prefill|chunk", "q": 0.5},
                           "decode_step_p50_s.backlog until PR 59 (both patterns match the one mixed program, "
                           "jit_chunk_decode_fn), since then the three entries of STEP_KINDS"),
    # PR 59: `.backlog` was what PR 47 made of the five before it. "decode" matches jit_decode_fn AND jit_chunk_decode_fn,
    # so it read a blend of two kinds of step (LongCat 20.4 ms where a plain step is 14.7 and a mixed one 24.3: neither);
    # the chat cells' `decode_step_p50_s` and `.loaded` stay (192 of 201 programs there are plain steps)
    "decode_step_p50_s": ("doc kx ms4 lcf p4f backlog", "module_time", {"pattern": "decode", "q": 0.5},
                          "plain_step_p50_s.backlog, mixed_step_p50_s.backlog, chunk_step_p50_s.backlog: one a kind of step"),
}
RETIRED = {f"{stem}.{suffix}": (reader, args) for stem, (suffixes, reader, args, _) in RETIRED_STEMS.items()
           for suffix in suffixes.split()}


@pytest.fixture(scope="module")
def m(table):
    return table


def _metrics_dir(m):
    return os.path.join(m.bench_dir, "metrics")


def _spec(manifest, name):
    spec = manifest.metric_spec(name)
    return json.dumps({"reader": spec["reader"], "args": spec.get("args", {})}, sort_keys=True)


def test_the_rename_table_holds_the_37_merged_and_the_20_retired():
    assert len(RENAMED) == 37 and len(RETIRED) == 20 and not set(RENAMED) & set(RETIRED)
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
    assert old not in by_name and not os.path.exists(os.path.join(_metrics_dir(m), old + ".json"))
    assert m.metric_spec(new) == {"reader": reader, "args": args}
    entry = by_name[new]
    assert entry["moves"] == "serve_tok_s" and entry["workloads"][: len(cells)] == cells    # a later cell is appended
    order = [w["name"] for w in m.doc["workloads"]]
    assert entry["workloads"] == sorted(entry["workloads"], key=order.index)


@pytest.mark.parametrize("old", sorted(RETIRED))
def test_a_retired_name_is_in_no_table_and_has_no_file(m, old):
    assert old not in {e["name"] for e in m.doc["per_layer"] + m.doc["end_to_end"]}
    assert not os.path.exists(os.path.join(_metrics_dir(m), old + ".json"))
    reader, _ = RETIRED[old]
    if reader != "module_time":       # the two readers nothing names any more went with their entries
        assert not os.path.exists(os.path.join(_metrics_dir(m), "readers", reader + ".py"))


def test_no_file_under_paths_names_an_old_entry_but_this_one(m):
    old = sorted(set(RENAMED) | set(RETIRED))
    stems = ["device_idle", "decode_occupancy"]     # the retired readers and their metrics, whatever the suffix
    this, walked = os.path.relpath(os.path.abspath(__file__), REPO), 0
    for path in m.doc["paths"]:
        for dirpath, dirs, files in os.walk(os.path.join(m.root, path)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for f in files:
                p = os.path.join(dirpath, f)
                if os.path.relpath(p, m.root) == this or not f.endswith((".py", ".json", ".md", ".txt", ".toml")):
                    continue
                walked += 1
                text = open(p, encoding="utf-8", errors="replace").read()
                found = [n for n in old + stems if n in text]
                assert not found, f"{os.path.relpath(p, m.root)} names {found}"
    assert walked > 200       # both of the benchmark's paths were there to walk


def _backlog(m):
    return [w["name"] for w in m.doc["workloads"]
            if "serve_tok_s" in {e["name"] for e in m.metrics_for(w["name"], "end_to_end")}]


def check_shared_lists(m):
    """The shared-list rule: the seven list every cell that reports serve_tok_s, in the cells' order; an entry whose
    suffix names that group of cells moves serve_tok_s and lists none but them."""
    by_name = {e["name"]: e for e in m.doc["per_layer"]}
    backlog = _backlog(m)
    assert set(backlog) >= {DOC, KX, MS4, LCF, P4F}
    for name in EVERY_BACKLOG_CELL:
        assert by_name[name]["workloads"] == backlog, name
    for e in m.doc["per_layer"]:
        if e["name"].endswith(".backlog"):      # the suffix names the group of cells that report serve_tok_s
            assert e["moves"] == "serve_tok_s" and set(e["workloads"]) <= set(backlog), e["name"]


def check_steps_and_unattributed(m):
    """A cell reports exactly one ``part_unattributed_share.*``; a served cell times its steps: a backlog cell by one
    or more of the three kinds (and by no blend of them), a chat cell by its one ``decode_step_p50_s*``."""
    backlog = _backlog(m)
    for w in m.doc["workloads"]:
        mine = [e["name"] for e in m.metrics_for(w["name"], "per_layer")]
        assert sum(n.startswith("part_unattributed_share.") for n in mine) == 1, w["name"]
        blends = sum(n.split(".")[0] == "decode_step_p50_s" for n in mine)
        kinds = [n for n in mine if n in STEP_KINDS]
        if w["name"] in backlog:
            assert kinds and not blends, w["name"]
        elif w["name"].startswith("serve"):
            assert blends == 1 and not kinds, w["name"]


def test_every_cell_that_reports_serve_tok_s_is_listed_in_the_seven_shared_entries(m):
    check_shared_lists(m)


@pytest.mark.parametrize("name", EVERY_BACKLOG_CELL)
def test_the_shared_list_rule_bites_when_the_newest_cell_leaves_one_of_the_seven(m, name):
    """Under the stand-in the newest cell IS the stand-in: one of its joins taken away is what a PR that forgot a
    list would leave."""
    newest = _backlog(m)[-1]
    assert (newest == tiny.STAND_IN_CELL) == (m.root != REPO)

    def leave(d):
        next(e for e in d["per_layer"] if e["name"] == name)["workloads"].remove(newest)

    gone = tiny.broken(m, leave)
    gone.validate(check_files=False)        # the contract takes it: the rule is the table's own
    with pytest.raises(AssertionError, match=re.escape(name)):
        check_shared_lists(gone)


def test_every_cell_reports_exactly_one_unattributed_share_and_times_its_steps_by_kind(m):
    check_steps_and_unattributed(m)
    assert "moe_streamed_per_hit.backlog" in {e["name"] for e in m.metrics_for(LCF, "per_layer")}
    by_name = {e["name"]: e for e in m.doc["per_layer"]}
    for name in STEP_KINDS:
        assert m.metric_spec(name) == {"reader": "launch_time", "args": {"kind": name.split("_")[0], "q": 0.5}}
        assert by_name[name]["workloads"] and by_name[name]["source"] == "device_trace"

    newest = _backlog(m)[-1]

    def no_kind(d):     # the newest backlog cell out of all three: it would time no step at all
        for e in d["per_layer"]:
            if e["name"] in STEP_KINDS and newest in e["workloads"]:
                e["workloads"].remove(newest)

    with pytest.raises(AssertionError):
        check_steps_and_unattributed(tiny.broken(m, no_kind))


def _padded(m, n):
    """The table grown to ``n`` entries by copies of its first under names of their own."""
    def pad(d):
        d["per_layer"].extend(dict(d["per_layer"][0], name=f"pad-{i}") for i in range(n - len(d["per_layer"])))
    return tiny.broken(m, pad)


def test_the_tables_length_is_limited_by_the_manifest_alone_128_fit_and_129_are_refused(m):
    """The one rule on the length, in the one place: no test compares it with a number of its own."""
    full = _padded(m, 128)
    full.validate(check_files=False)
    with pytest.raises(ManifestError, match="1 to 128 per-layer"):
        _padded(m, 129).validate(check_files=False)


def test_every_spec_file_belongs_to_an_entry_and_every_entry_and_reader_is_found(m):
    names = {e["name"] for e in m.doc["per_layer"] + m.doc["end_to_end"]} - {"setup_s"}
    files = {f[: -len(".json")] for f in os.listdir(_metrics_dir(m)) if f.endswith(".json")}
    assert files == names, (sorted(files - names), sorted(names - files))
    readers = {f[: -len(".py")] for f in os.listdir(os.path.join(_metrics_dir(m), "readers")) if f.endswith(".py")} - {"__init__"}
    assert readers == {m.metric_spec(n)["reader"] for n in names}, "a reader that no spec names, or a spec without its reader"
