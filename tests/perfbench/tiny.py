"""A tiny copy of the benchmark for the CPU tests: the harness's own files
(copied, not edited) with ``gpt2-tiny`` configurations and traffic that fits a
second. ``make(tmp)`` writes a checkout-shaped directory and returns its
manifest."""

from __future__ import annotations

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


def make(tmp: str):
    from perfbench.manifest import Manifest

    root = os.path.join(str(tmp), "checkout")
    shutil.copytree(os.path.join(REPO, "perfbench"), os.path.join(root, "perfbench"),
                    ignore=shutil.ignore_patterns("__pycache__", "configs", "traffic"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bm = json.load(f)
    files = {
        "perfbench/configs/tiny-serve.json": serve_config(),
        "perfbench/configs/tiny-train.json": train_config(),
        "perfbench/traffic/tiny-open.json": OPEN,
        "perfbench/traffic/tiny-backlog.json": BACKLOG,
        "perfbench/traffic/train-steady.json": TRAIN,
    }
    for rel, doc in files.items():
        os.makedirs(os.path.dirname(os.path.join(root, rel)), exist_ok=True)
        with open(os.path.join(root, rel), "w") as f:
            json.dump(doc, f)
    # the real cells' names, so that every metric's `workloads` list still holds
    kinds = {"train-xl-l16-1chip": ("tiny-train", "train-steady"), "train-xl-dp4": ("tiny-train", "train-steady"),
             "serve-xl-chat-open": ("tiny-serve", "tiny-open"), "serve-xl-doc-batch": ("tiny-serve", "tiny-backlog")}
    bm["configs"] = [
        {"name": "tiny-serve", "source": "tests", "file": "perfbench/configs/tiny-serve.json", "reduced": [], "why": "tests"},
        {"name": "tiny-train", "source": "tests", "file": "perfbench/configs/tiny-train.json", "reduced": [], "why": "tests"},
    ]
    cells = []
    for w in bm["workloads"]:
        if w["name"] == "train-xl-dp4":
            continue   # one cell per pair of configuration and traffic
        c, t = kinds[w["name"]]
        cells.append({"name": w["name"], "config": c, "traffic": t, "chips": 1, "why": "tests"})
    bm["workloads"] = cells
    for m in bm["end_to_end"] + bm["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [w for w in m["workloads"] if w != "train-xl-dp4"]
    bm["per_layer"] = [m for m in bm["per_layer"] if m.get("workloads", True)]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bm, f)
    return Manifest(root)
