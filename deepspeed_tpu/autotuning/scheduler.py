"""Pod-sweep experiment orchestration: real training jobs per candidate config.

Analog of reference ``deepspeed/autotuning/scheduler.py`` (ResourceManager:27
+ run_job/experiment queue): the reference allocates experiments to free
nodes through the launcher, polls for completion, and scrapes metrics files.
The TPU single-controller formulation: every experiment is a SUBPROCESS
running the user's training script against its own generated ds_config JSON,
so each candidate gets a clean backend (a TPU chip admits one process at a
time — the default is one slot, sequential). Metrics come back as the
script's final JSON line (the contract: one line, one dict), so
no shared-filesystem metrics protocol is needed.

The in-process :class:`~.autotuner.Autotuner` remains the cheap path when
trials can share one process; ``PodSweep`` is the "run N configs on the pod,
pick the winner" path, and reuses the same tuner
strategies — including the least-squares cost model — for trial selection.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

from ..utils.logging import log_dist
from .tuner import GridSearchTuner, ModelBasedTuner, RandomTuner

Experiment = Dict[str, Any]

TUNERS = {"gridsearch": GridSearchTuner, "random": RandomTuner, "model_based": ModelBasedTuner}


def _parse_metric_line(stdout: str, metric_key: str) -> Optional[Dict[str, Any]]:
    """Last JSON object line carrying ``metric_key`` wins."""
    found = None
    for line in stdout.splitlines():
        line = line.strip()
        if line.startswith("{") and line.endswith("}"):
            try:
                doc = json.loads(line)
            except json.JSONDecodeError:
                continue
            if metric_key in doc:
                found = doc
    return found


class ResourceManager:
    """Run experiment jobs over ``num_slots`` concurrent subprocess slots.

    Reference ResourceManager (scheduler.py:27) schedules onto free
    node-slots; here a slot is one accelerator-capable process. With the
    default single slot jobs run strictly sequentially — required on a
    single chip, where two concurrent JAX processes deadlock.
    """

    def __init__(self, num_slots: int = 1, env: Optional[Dict[str, str]] = None,
                 timeout: float = 1800.0):
        self.num_slots = max(1, int(num_slots))
        self.env = env
        self.timeout = float(timeout)

    def run_job(self, cmd: Sequence[str], cwd: Optional[str] = None) -> Tuple[int, str, str]:
        env = dict(os.environ)
        if self.env:
            env.update(self.env)
        try:
            proc = subprocess.run(
                list(cmd), cwd=cwd, env=env, capture_output=True, text=True,
                timeout=self.timeout, stdin=subprocess.DEVNULL,
            )
            return proc.returncode, proc.stdout, proc.stderr
        except subprocess.TimeoutExpired as e:
            return -1, (e.stdout or ""), f"timeout after {self.timeout}s"

    def run_batch(self, jobs: Sequence[Tuple[Any, Sequence[str]]], cwd=None):
        """[(tag, cmd)] -> [(tag, rc, stdout, stderr)], ``num_slots`` at a time."""
        out = []
        pending = list(jobs)
        while pending:
            wave, pending = pending[: self.num_slots], pending[self.num_slots :]
            if self.num_slots == 1:
                for tag, cmd in wave:
                    rc, so, se = self.run_job(cmd, cwd=cwd)
                    out.append((tag, rc, so, se))
                continue
            env = dict(os.environ)
            if self.env:
                env.update(self.env)
            procs = [
                (tag, subprocess.Popen(list(cmd), cwd=cwd, env=env, text=True,
                                       stdout=subprocess.PIPE, stderr=subprocess.PIPE))
                for tag, cmd in wave
            ]
            deadline = time.monotonic() + self.timeout
            for tag, p in procs:
                try:
                    so, se = p.communicate(timeout=max(1.0, deadline - time.monotonic()))
                    out.append((tag, p.returncode, so, se))
                except subprocess.TimeoutExpired:
                    p.kill()
                    try:
                        # reap + keep partial output (a job that printed its
                        # metric line before stalling still scores normally,
                        # matching run_job's e.stdout preservation)
                        so, se = p.communicate(timeout=10)
                    except subprocess.TimeoutExpired:
                        so, se = "", ""
                    out.append((tag, -1, so, (se or "") + f"\ntimeout after {self.timeout}s"))
        return out


class PodSweep:
    """Sweep K ds_configs by launching the user's training script per config.

    ``script`` must accept ``--deepspeed_config <path>`` (the standard
    ``add_config_arguments`` surface) and print one JSON line containing
    ``metric_key``. Experiments are dicts of
    {zero_stage, micro_batch, gradient_accumulation_steps, config} where the
    optional ``config`` entry deep-merges arbitrary ds_config overrides.
    """

    def __init__(
        self,
        script: str,
        base_config: Dict[str, Any],
        experiments: Sequence[Experiment],
        results_dir: str = "autotuning_results",
        metric_key: str = "samples_per_sec",
        num_slots: int = 1,
        env: Optional[Dict[str, str]] = None,
        timeout: float = 1800.0,
        script_args: Sequence[str] = (),
        tuner_type: str = "gridsearch",
        python: Optional[str] = None,
    ):
        self.script = str(script)
        self.base_config = base_config
        self.experiments = list(experiments)
        self.results_dir = results_dir
        self.metric_key = metric_key
        self.rm = ResourceManager(num_slots=num_slots, env=env, timeout=timeout)
        self.script_args = list(script_args)
        self.tuner_type = tuner_type
        self.python = python or sys.executable

    # -- config materialization --------------------------------------------
    @staticmethod
    def _deep_merge(dst: Dict[str, Any], src: Dict[str, Any]) -> None:
        for k, v in src.items():
            if isinstance(v, dict) and isinstance(dst.get(k), dict):
                PodSweep._deep_merge(dst[k], v)
            else:
                dst[k] = v

    def _cfg_for(self, exp: Experiment) -> Dict[str, Any]:
        cfg = json.loads(json.dumps(self.base_config))  # deep copy
        if "micro_batch" in exp:
            cfg["train_micro_batch_size_per_gpu"] = int(exp["micro_batch"])
        if "gradient_accumulation_steps" in exp:
            cfg["gradient_accumulation_steps"] = int(exp["gradient_accumulation_steps"])
        if "zero_stage" in exp:
            cfg.setdefault("zero_optimization", {})["stage"] = int(exp["zero_stage"])
        self._deep_merge(cfg, exp.get("config") or {})
        return cfg

    def _exp_dir(self, i: int) -> str:
        d = os.path.join(self.results_dir, f"exp_{i:03d}")
        os.makedirs(d, exist_ok=True)
        return d

    def _prepare(self, i: int, exp: Experiment) -> List[str]:
        d = self._exp_dir(i)
        cfg_path = os.path.join(d, "ds_config.json")
        with open(cfg_path, "w") as fh:
            json.dump(self._cfg_for(exp), fh, indent=2)
        return [self.python, self.script, "--deepspeed_config", cfg_path, *self.script_args]

    def _collect(self, i: int, exp: Experiment, rc: int, stdout: str, stderr: str) -> float:
        d = self._exp_dir(i)
        with open(os.path.join(d, "stdout.log"), "w") as fh:
            fh.write(stdout)
        with open(os.path.join(d, "stderr.log"), "w") as fh:
            fh.write(stderr)
        doc = _parse_metric_line(stdout, self.metric_key)
        if rc != 0 or doc is None:
            log_dist(
                f"pod-sweep exp_{i:03d} {exp} infeasible "
                f"(rc={rc}, metric line {'missing' if doc is None else 'ok'})"
            )
            return float("-inf")
        metric = float(doc[self.metric_key])
        log_dist(f"pod-sweep exp_{i:03d} {exp} -> {metric:.2f} {self.metric_key}")
        return metric

    def _launch(self, i: int, exp: Experiment) -> float:
        rc, stdout, stderr = self.rm.run_job(self._prepare(i, exp))
        return self._collect(i, exp, rc, stdout, stderr)

    # -- the sweep ----------------------------------------------------------
    def run(self, max_trials: Optional[int] = None) -> Dict[str, Any]:
        import numpy as np

        os.makedirs(self.results_dir, exist_ok=True)
        if self.tuner_type == "gridsearch" and self.rm.num_slots > 1:
            # gridsearch has no measurement-dependent trial selection, so it
            # can fan out num_slots-wide waves through the ResourceManager
            exps = self.experiments[: max_trials or len(self.experiments)]
            raw = self.rm.run_batch(
                [(i, self._prepare(i, e)) for i, e in enumerate(exps)]
            )
            trials = [
                (exps[i], self._collect(i, exps[i], rc, so, se))
                for i, rc, so, se in raw
            ]
            best_exp, best_metric = None, float("-inf")
            for e, m in trials:
                if m > best_metric:
                    best_exp, best_metric = e, m
        else:
            if self.rm.num_slots > 1:
                log_dist(
                    f"pod-sweep: tuner '{self.tuner_type}' selects trials from "
                    "measurements, so experiments run sequentially "
                    f"(num_slots={self.rm.num_slots} ignored)"
                )
            index = {id(e): i for i, e in enumerate(self.experiments)}
            tuner_cls = TUNERS[self.tuner_type]
            kwargs = {}
            if self.tuner_type == "model_based":
                feats = [
                    k for k in ("zero_stage", "micro_batch", "gradient_accumulation_steps")
                    if all(k in e for e in self.experiments)
                ]
                kwargs = {"features": feats}
            tuner = tuner_cls(
                self.experiments, lambda e: self._launch(index[id(e)], e), **kwargs
            )
            best_exp, best_metric = tuner.tune(max_trials)
            trials = tuner.results

        result = {
            "best": best_exp,
            self.metric_key: best_metric if np.isfinite(best_metric) else None,
            "trials": [
                {"exp": e, self.metric_key: m if np.isfinite(m) else None}
                for e, m in trials
            ],
        }
        with open(os.path.join(self.results_dir, "autotuning_results.json"), "w") as fh:
            json.dump(result, fh, indent=2)
        if best_exp is not None and np.isfinite(best_metric):
            best_cfg = self._cfg_for(best_exp)
            with open(os.path.join(self.results_dir, "ds_config_optimal.json"), "w") as fh:
                json.dump(best_cfg, fh, indent=2)
            result["ds_config"] = best_cfg
        return result
