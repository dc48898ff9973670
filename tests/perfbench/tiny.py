"""A tiny copy of the benchmark for the CPU tests: the harness's own files
(copied, not edited) with ``gpt2-tiny`` configurations and traffic that fits a
second. ``make(tmp)`` writes a checkout-shaped directory and returns its
manifest.

A real cell's stand-in is found from what the cell is: its configuration's
``runner`` and its traffic's ``loop``. The two runners that are there have
theirs below; a PR that brings a runner brings
``tests/perfbench/stand_ins/<runner>.json`` (``{"config": {...}, "traffic":
{"<loop>": {...}}}``) as a new file. A cell with no stand-in, or whose stand-in
pair another cell already took, is left out of the copy and of every metric's
``workloads`` list.

``with_stand_in(tmp)`` is the other copy: the committed benchmark at its real
size with ONE MORE backlog cell appended, twelve per-layer entries of its own
and a place in every shared list, as the next PR that adds a cell would leave
it. ``conftest.py``'s ``table`` fixture hands the tests both."""

from __future__ import annotations

import copy
import glob
import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

TINY_MODEL = {"model_type": "gpt2", "vocab_size": 512, "n_positions": 128, "n_embd": 64, "n_head": 4,
              "n_layer": 2, "layer_norm_epsilon": 1e-5}


def serve_config(**over):
    cfg = dict(TINY_MODEL)
    cfg.update({
        "runner": "serve", "dtype": "float32",
        "serving": {"max_slots": 4, "page_size": 8, "num_pages": 64, "max_prompt_len": 96,
                    "max_new_tokens": 8, "prefill_chunk_tokens": 32, "max_queue_depth": 512},
        "warmup_short_prompt": 16, "warmup_long_prompt": 80, "warmup_new_tokens": 6, "trace_s": 0.5,
        "reference": {"logit_margin": 1e-3},
    })
    cfg.update(over)
    return cfg


def train_config(**over):
    cfg = dict(TINY_MODEL)
    cfg.update({
        "runner": "train", "dtype": "bfloat16", "seq": 64, "model_overrides": {"remat": True},
        "engine": {"train_micro_batch_size_per_gpu": 2, "gradient_accumulation_steps": 1,
                   "optimizer": {"type": "AdamW", "params": {"lr": 1e-4, "weight_decay": 0.01}},
                   "zero_optimization": {"stage": 3}, "gradient_clipping": 1.0,
                   "bf16": {"enabled": True}, "steps_per_print": 10**9},
        "warmup_steps": 1, "trace_s": 0.5, "reference": {"loss_tol": 0.05},
    })
    cfg.update(over)
    return cfg


OPEN = {"loop": "open", "rate_rps": 6.0, "block_s": 1, "ramp_s": 0.5, "tail_s": 20,
        "components": [{"share": 1.0,
                        "prompt_len": {"dist": "lognormal", "median": 24, "sigma": 0.8, "min": 4, "max": 90},
                        "new_tokens": {"dist": "const", "value": 8}}]}
BACKLOG = {"loop": "backlog", "block_requests": 8, "block_s": 1, "queue_depth": 2,
           "ramp": {"seconds": 0.4, "aged": True},
           "components": [{"share": 1.0,
                           "prompt_len": {"dist": "uniform", "min": 40, "max": 90},
                           "new_tokens": {"dist": "const", "value": 8}}]}
TRAIN = {"loop": "train_steps"}


def stand_ins(repo: str = REPO) -> dict:
    """runner -> {"config": (name, configuration), "traffic": {loop: (name, mix)}}"""
    out = {
        "serve": {"config": ("tiny-serve", serve_config()),
                  "traffic": {"open": ("tiny-open", OPEN), "backlog": ("tiny-backlog", BACKLOG)}},
        "train": {"config": ("tiny-train", train_config()), "traffic": {"train_steps": ("train-steady", TRAIN)}},
    }
    for path in sorted(glob.glob(os.path.join(repo, "tests", "perfbench", "stand_ins", "*.json"))):
        runner = os.path.splitext(os.path.basename(path))[0]
        with open(path) as f:
            doc = json.load(f)
        out[runner] = {"config": (f"tiny-{runner}", doc["config"]),
                       "traffic": {loop: (f"tiny-{runner}-{loop}", mix) for loop, mix in doc["traffic"].items()}}
    return out


def keep_cells(bm: dict, kept: set) -> None:
    """Every metric's ``workloads`` cut to the cells in ``kept``; a metric left
    with no cell goes."""
    for group in ("end_to_end", "per_layer"):
        for m in bm[group]:
            if "workloads" in m:
                m["workloads"] = [w for w in m["workloads"] if w in kept]
        bm[group] = [m for m in bm[group] if m.get("workloads", True)]


def make(tmp: str, repo: str = REPO):
    """``repo`` is the checkout whose benchmark is copied: this one, or a copy
    of it that a test has added a cell to."""
    from perfbench.manifest import Manifest

    root = os.path.join(str(tmp), "checkout")
    shutil.copytree(os.path.join(repo, "perfbench"), os.path.join(root, "perfbench"),
                    ignore=shutil.ignore_patterns("__pycache__", "configs", "traffic"))
    real = Manifest(repo)
    bm = real.doc   # this function's own copy: edited below and written to the tiny checkout
    kinds = stand_ins(repo)
    cells, pairs = [], set()
    for w in bm["workloads"]:
        kind = kinds.get(real.config(w["config"])["runner"], {"traffic": {}})
        mix = kind["traffic"].get(real.traffic(w["traffic"])["loop"])
        if mix is None:
            continue   # no stand-in for this runner under this loop
        (c, cfg), (t, tr) = kind["config"], mix
        if (c, t) in pairs:
            continue   # one cell per pair of configuration and traffic
        pairs.add((c, t))
        for rel, doc in ((f"perfbench/configs/{c}.json", cfg), (f"perfbench/traffic/{t}.json", tr)):
            os.makedirs(os.path.dirname(os.path.join(root, rel)), exist_ok=True)
            with open(os.path.join(root, rel), "w") as f:
                json.dump(doc, f)
        # the real cell's name, so that every metric's `workloads` list still holds
        cells.append({"name": w["name"], "config": c, "traffic": t, "chips": 1, "why": "tests"})
    bm["configs"] = [{"name": c, "source": "tests", "file": f"perfbench/configs/{c}.json", "reduced": [], "why": "tests"}
                     for c in sorted({c for c, _ in pairs})]
    bm["workloads"] = cells
    keep_cells(bm, {w["name"] for w in cells})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bm, f)
    return Manifest(root)


def broken(m, edit):
    """A manifest whose tables are ``m``'s with ``edit`` applied to a copy: ``m`` itself (a fixture many tests share)
    is left as it was."""
    out = copy.copy(m)
    out.doc = copy.deepcopy(m.doc)
    edit(out.doc)
    return out


SETUP = {"setup_compile_s", "setup_trace_lower_s", "setup_params_s"}      # every cell's: they move setup_s


def check_cell_keeps(m, cell: str, own, joined, moves: str = "serve_tok_s") -> None:
    """What a cell's test holds of the committed table: what the cell must KEEP, found by name, never what it may
    ever list. ``own`` are the entries the cell brought, still in the order they were appended and with the cell first
    in each (a later cell of the same reading is listed behind it); ``joined`` are the shared entries it is listed in.
    A later PR may list the cell in an entry more, or bring it another of its own."""
    own, names = list(own), [e["name"] for e in m.doc["per_layer"]]
    by_name = {e["name"]: e for e in m.doc["per_layer"]}
    assert [n for n in names if n in set(own)] == own
    for n in own:
        assert by_name[n]["workloads"][0] == cell, n
    listed = [e for e in m.metrics_for(cell, "per_layer") if e["moves"] != "setup_s"]
    missing = (set(own) | set(joined)) - {e["name"] for e in listed}
    assert not missing, f"{cell} is no longer listed in {sorted(missing)}"
    assert {e["moves"] for e in listed} == {moves}
    assert SETUP <= {e["name"] for e in m.metrics_for(cell, "per_layer") if e["moves"] == "setup_s"}
    assert {moves, "setup_s"} <= {e["name"] for e in m.metrics_for(cell, "end_to_end")}
    assert cell in next(e["workloads"] for e in m.doc["end_to_end"] if e["name"] == moves)


# -- the stand-in cell: what the next PR that adds a cell does to the table ---------------------------------
STAND_IN_CELL = "serve-standin-reason-backlog"
STAND_IN_CONFIG = "standin-reason-serve-1chip"
STAND_IN_COPIES = "serve-qwen3next-reason-backlog"     # its configuration file is a copy of this cell's, its mix this cell's
# twelve entries of its own: (name, unit, better, source, the entry whose layer it takes, moves, reader, arguments).
# Every reader is one that is there; no entry of the tree has these arguments.
STAND_IN_OWN = [
    ("part_embed_share.standin", "%", "lower", "device_trace", "part_attn_share.backlog", "serve_tok_s",
     "part_share", {"parts": ["embed"], "of": "busy"}),
    ("part_norm_share.standin", "%", "lower", "device_trace", "part_attn_share.backlog", "serve_tok_s",
     "part_share", {"parts": ["norm"], "of": "busy"}),
    ("srv_emit_p90_s.standin", "s", "lower", "program_span", "srv_step_host_p50_s.backlog", "serve_tok_s",
     "span_quantile", {"name": "ds.serve.emit", "q": 0.9}),
    ("srv_admit_p90_s.standin", "s", "lower", "program_span", "srv_step_host_p50_s.backlog", "serve_tok_s",
     "span_quantile", {"name": "ds.serve.admit", "q": 0.9}),
    ("prefill_launch_p50_s.standin", "s", "lower", "device_trace", "part_attn_share.backlog", "serve_tok_s",
     "launch_time", {"kind": "prefill", "q": 0.5}),
    ("token_hold_p90_s.standin", "s", "lower", "device_trace", "srv_step_host_p50_s.backlog", "serve_tok_s",
     "launch_hold", {"of": "token", "q": 0.9}),
    ("chunk_module_p90_s.standin", "s", "lower", "device_trace", "part_attn_share.backlog", "serve_tok_s",
     "module_time", {"pattern": "chunk_decode", "q": 0.9}),
    ("chunks_rode_share.standin", "%", "higher", "program_counter", "decode_slots_active.backlog", "serve_tok_s",
     "span_attr_ratio", {"name": "ds.serve.chunk", "attr": "rode", "over": "chunks"}),
    ("emit_finished_share.standin", "%", "lower", "program_counter", "decode_slots_active.backlog", "serve_tok_s",
     "span_flag_share", {"name": "ds.serve.emit", "attr": "finished"}),
    ("standin_kernel_share.standin", "%", "lower", "device_trace", "copy_layout_share.backlog", "serve_tok_s",
     "op_share", {"pattern": "stand_in_kernel", "of": "busy"}),
    ("queue_wait_p90_s.standin", "s", "lower", "host_clock", "gen_tok_s.backlog", "serve_tok_s",
     "stamp_quantile", {"field": "queue_wait", "q": 0.9}),
    ("setup_programs_s.standin", "s", "lower", "program_span", "setup_compile_s", "setup_s",
     "phase_sum", {"names": ["ds.init.programs"]}),
]


def shared_lists(bm: dict) -> list:
    """The entries a backlog cell joins at no entry of its own: ``serve_tok_s`` itself and every per-layer entry that
    moves it and lists two cells or more (a list of one cell is that cell's own reading)."""
    return [e for e in bm["end_to_end"] if e["name"] == "serve_tok_s"] + \
        [e for e in bm["per_layer"] if e["moves"] == "serve_tok_s" and len(e.get("workloads", ())) >= 2]


def with_stand_in(tmp: str, repo: str = REPO) -> str:
    """A checkout-shaped copy of ``repo``'s benchmark (``BENCHMARK.json`` and its ``paths``, the recorded traces
    apart) with the stand-in cell appended: files are written, lists grow, nothing that is there is edited. Returns
    the copy's root."""
    root = os.path.join(str(tmp), "with_stand_in")
    with open(os.path.join(repo, "BENCHMARK.json")) as f:
        bm = json.load(f)
    for path in bm["paths"]:
        shutil.copytree(os.path.join(repo, path), os.path.join(root, path),
                        ignore=shutil.ignore_patterns("__pycache__", "data"))
    bench = os.path.join(root, bm["paths"][0])

    def write(rel, doc):
        p = os.path.join(bench, rel)
        assert not os.path.exists(p), f"{rel} is there: the stand-in may edit no file"
        with open(p, "w") as f:
            json.dump(doc, f)

    copied = next(w for w in bm["workloads"] if w["name"] == STAND_IN_COPIES)
    entry = next(c for c in bm["configs"] if c["name"] == copied["config"])
    with open(os.path.join(repo, entry["file"])) as f:
        write(f"configs/{STAND_IN_CONFIG}.json", json.load(f))
    bm["configs"].append(dict(entry, name=STAND_IN_CONFIG, file=f"{bm['paths'][0]}/configs/{STAND_IN_CONFIG}.json"))
    bm["workloads"].append(dict(copied, name=STAND_IN_CELL, config=STAND_IN_CONFIG, why="tests: the next cell"))
    for e in shared_lists(bm):
        e["workloads"].append(STAND_IN_CELL)
    layer = {e["name"]: e["layer"] for e in bm["per_layer"]}
    for name, unit, better, source, layer_of, moves, reader, args in STAND_IN_OWN:
        write(f"metrics/{name}.json", {"reader": reader, "args": args})
        bm["per_layer"].append({"name": name, "unit": unit, "better": better, "source": source, "layer": layer[layer_of],
                                "moves": moves, "workloads": [STAND_IN_CELL]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bm, f)
    return root
