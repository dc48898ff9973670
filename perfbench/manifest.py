"""BENCHMARK.json and the data files it names: loading, discovery by name,
and the validation the contract asks for.

Nothing here knows a cell, a configuration, a traffic mix or a metric by
name. A cell is ``{name, config, traffic, chips, why}``; the harness finds

- ``configs/<config>.json``          the configuration as it is run,
- ``traffic/<traffic>.json``         the mix's parameters,
- ``metrics/<metric>.json``          which reader computes the metric, with
                                     what arguments,
- ``metrics/readers/<reader>.py``    the reader (``read(ctx, **args)``),
- ``runners/<runner>.py``            how a kind of configuration is driven,

under the directory of ``BENCHMARK.json``'s first path. Adding any of them
needs no edit to a file that is there (tests/perfbench/test_manifest.py).
"""

from __future__ import annotations

import importlib.util
import json
import os
import re

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
# reduced may never name a width (contract): a hidden, intermediate, latent,
# state or projection size, a key that ends in _dim or _rank, a head size, an
# expansion factor, a window, the experts per token. A key that counts layers
# is depth, whatever it counts them of (num_hidden_layers), and is let through.
WIDTH_RE = re.compile(
    r"^(?!.*layers?$).*"
    r"((_dim|_rank)$|hidden|intermediate|latent|state|proj|head_size|n_embd|n_inner|d_model|d_ff|d_kv"
    r"|expan|experts_per|window)"
)


class ManifestError(ValueError):
    pass


def _one_line(s, what):
    if not isinstance(s, str) or not (1 <= len(s) <= 200) or "\n" in s or "\t" in s:
        raise ManifestError(f"{what}: 1 to 200 characters on one line, got {s!r}")


class Manifest:
    def __init__(self, root: str):
        """``root`` is the checkout (the directory of BENCHMARK.json)."""
        self.root = os.path.abspath(root)
        with open(os.path.join(self.root, "BENCHMARK.json")) as f:
            self.doc = json.load(f)
        self.bench_dir = os.path.join(self.root, self.doc["paths"][0])

    # -- lookup by name ---------------------------------------------------
    def cell(self, name: str) -> dict:
        for w in self.doc["workloads"]:
            if w["name"] == name:
                return w
        raise ManifestError(
            f"no workload {name!r} in BENCHMARK.json; it has {[w['name'] for w in self.doc['workloads']]}"
        )

    def config_entry(self, name: str) -> dict:
        for c in self.doc["configs"]:
            if c["name"] == name:
                return c
        raise ManifestError(f"no config {name!r} in BENCHMARK.json")

    def load_json(self, path: str) -> dict:
        """``path`` relative to the checkout, or absolute."""
        with open(os.path.join(self.root, path)) as f:
            return json.load(f)

    def config(self, name: str) -> dict:
        return self.load_json(self.config_entry(name)["file"])

    def traffic(self, name: str) -> dict:
        return self.load_json(os.path.join(self.bench_dir, "traffic", name + ".json"))

    def metric_spec(self, name: str) -> dict:
        return self.load_json(os.path.join(self.bench_dir, "metrics", name + ".json"))

    def _module(self, sub: str, name: str):
        if not NAME_RE.match(name):
            raise ManifestError(f"bad module name {name!r}")
        path = os.path.join(self.bench_dir, *sub.split("/"), name + ".py")
        spec = importlib.util.spec_from_file_location(f"perfbench_{sub.replace('/', '_')}_{name}", path)
        if spec is None or not os.path.exists(path):
            raise ManifestError(f"no {sub}/{name}.py under {self.bench_dir}")
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def reader(self, name: str):
        return self._module("metrics/readers", name)

    def runner(self, name: str):
        return self._module("runners", name)

    def metrics_for(self, cell_name: str, group: str) -> list:
        """Entries of ``end_to_end`` or ``per_layer`` that ``cell_name`` reports."""
        return [
            m for m in self.doc[group]
            if "workloads" not in m or cell_name in m["workloads"]
        ]

    # -- validation -------------------------------------------------------
    def validate(self, check_files: bool = True) -> None:
        d = self.doc
        if set(d) != TOP_KEYS:
            raise ManifestError(f"BENCHMARK.json keys {sorted(d)} != {sorted(TOP_KEYS)}")
        if not (isinstance(d["run_seconds"], int) and 1 <= d["run_seconds"] <= 51):
            raise ManifestError("run_seconds: a whole number from 1 to 51")
        if not (1 <= len(d["command"]) <= 32):
            raise ManifestError("command: 1 to 32 strings")
        for w in d["command"]:
            _one_line(w, "command word")
            if w.startswith("/") or ".." in w.split("/"):
                raise ManifestError(f"command word {w!r} leaves the repo")
        if not (1 <= len(d["paths"]) <= 16):
            raise ManifestError("paths: 1 to 16 directories")
        for p in d["paths"]:
            if not re.match(r"^[A-Za-z0-9_.\-/]{1,200}$", p) or p.startswith("/") or ".." in p.split("/"):
                raise ManifestError(f"bad path {p!r}")

        def names(entries, what, keys, optional=()):
            seen = set()
            for e in entries:
                extra = set(e) - set(keys) - set(optional)
                missing = set(keys) - set(e)
                if extra or missing:
                    raise ManifestError(f"{what} {e.get('name')!r}: extra keys {sorted(extra)}, missing {sorted(missing)}")
                if not NAME_RE.match(e["name"]):
                    raise ManifestError(f"{what} name {e['name']!r} outside the allowed characters")
                if e["name"] in seen:
                    raise ManifestError(f"{what} name {e['name']!r} twice")
                seen.add(e["name"])
            return seen

        if not (1 <= len(d["configs"]) <= 24) or not (1 <= len(d["workloads"]) <= 24):
            raise ManifestError("1 to 24 configs and workloads")
        cfg_names = names(d["configs"], "config", ("name", "source", "file", "reduced", "why"))
        files = set()
        for c in d["configs"]:
            _one_line(c["source"], "config source")
            _one_line(c["why"], "config why")
            if not any(c["file"].startswith(p.rstrip("/") + "/") for p in d["paths"]):
                raise ManifestError(f"config file {c['file']} is not under paths")
            if c["file"] in files:
                raise ManifestError(f"config file {c['file']} used twice")
            files.add(c["file"])
            if len(c["reduced"]) > 16:
                raise ManifestError("reduced: at most 16 keys")
            for k in c["reduced"]:
                if not NAME_RE.match(k) or WIDTH_RE.search(k):
                    raise ManifestError(f"config {c['name']}: reduced may not name {k!r} (a width)")
        cell_names = names(d["workloads"], "workload", ("name", "config", "traffic", "chips", "why"))
        pairs = set()
        for w in d["workloads"]:
            _one_line(w["why"], "workload why")
            if w["config"] not in cfg_names:
                raise ManifestError(f"workload {w['name']}: unknown config {w['config']!r}")
            if not NAME_RE.match(w["traffic"]):
                raise ManifestError(f"workload {w['name']}: bad traffic name")
            if w["chips"] not in (1, 4):
                raise ManifestError(f"workload {w['name']}: chips is 1 or 4")
            if (w["config"], w["traffic"]) in pairs:
                raise ManifestError(f"pair {(w['config'], w['traffic'])} twice")
            pairs.add((w["config"], w["traffic"]))
        used = {w["config"] for w in d["workloads"]}
        if used != cfg_names:
            raise ManifestError(f"configs used by no cell: {sorted(cfg_names - used)}")
        four = sum(1 for w in d["workloads"] if w["chips"] == 4)
        if four > max(1, len(d["workloads"]) // 4):
            raise ManifestError(f"{four} four-chip cells; at most {max(1, len(d['workloads']) // 4)}")

        if not (1 <= len(d["end_to_end"]) <= 16) or not (1 <= len(d["per_layer"]) <= 128):
            raise ManifestError("1 to 16 end-to-end and 1 to 128 per-layer metrics")
        e2e = names(d["end_to_end"], "end_to_end", ("name", "unit", "better", "bound", "source"), ("workloads",))
        pl = names(d["per_layer"], "per_layer", ("name", "unit", "better", "source", "layer", "moves"), ("workloads",))
        if e2e & pl:
            raise ManifestError(f"metric names in both groups: {sorted(e2e & pl)}")
        if "setup_s" not in e2e:
            raise ManifestError("end_to_end must hold setup_s")
        for m in d["end_to_end"] + d["per_layer"]:
            if not UNIT_RE.match(m["unit"]):
                raise ManifestError(f"metric {m['name']}: bad unit {m['unit']!r}")
            if m["better"] not in ("lower", "higher"):
                raise ManifestError(f"metric {m['name']}: better is lower or higher")
            if m["source"] not in SOURCES:
                raise ManifestError(f"metric {m['name']}: source {m['source']!r}")
            for w in m.get("workloads", ()):
                if w not in cell_names:
                    raise ManifestError(f"metric {m['name']}: unknown workload {w!r}")
        for m in d["end_to_end"]:
            if m["source"] not in ("host_clock", "device_trace"):
                raise ManifestError(f"end-to-end metric {m['name']}: host_clock or device_trace only")
            if not (0 < m["bound"] <= 0.1):
                raise ManifestError(f"metric {m['name']}: bound in (0, 0.1]")
            if "workloads" in m and m["name"] == "setup_s":
                raise ManifestError("setup_s is reported by every cell")
        for m in d["per_layer"]:
            _one_line(m["layer"], "layer")
            if m["moves"] not in e2e:
                raise ManifestError(f"metric {m['name']}: moves {m['moves']!r} is no end-to-end metric")
        for w in d["workloads"]:
            mine = {m["name"] for m in self.metrics_for(w["name"], "end_to_end")}
            if len(mine - {"setup_s"}) < 1:
                raise ManifestError(f"workload {w['name']} reports no end-to-end metric besides setup_s")
            layer = self.metrics_for(w["name"], "per_layer")
            if not layer:
                raise ManifestError(f"workload {w['name']} reports no per-layer metric")
            for m in layer:
                if m["moves"] not in mine:
                    raise ManifestError(
                        f"per-layer metric {m['name']} moves {m['moves']}, which workload {w['name']} does not report"
                    )
        if not check_files:
            return
        for c in d["configs"]:
            cfg = self.load_json(c["file"])
            if "runner" not in cfg:
                raise ManifestError(f"{c['file']}: no runner")
            self.runner(cfg["runner"])
        for w in d["workloads"]:
            self.traffic(w["traffic"])
        for m in d["end_to_end"] + d["per_layer"]:
            if m["name"] == "setup_s":
                continue
            spec = self.metric_spec(m["name"])
            if not hasattr(self.reader(spec["reader"]), "read"):
                raise ManifestError(f"reader {spec['reader']} has no read()")
