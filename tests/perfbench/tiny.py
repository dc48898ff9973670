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
``workloads`` list."""

from __future__ import annotations

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
