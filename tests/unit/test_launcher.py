"""Launcher CLI: hostfile parsing, resource filters, command construction,
ds_report, the auxiliary CLIs.

Reference analog: tests/unit/test_ds_arguments.py + launcher runner tests.
"""

import os
import subprocess
import sys
from collections import OrderedDict

import pytest

from deepspeed_tpu.launcher.runner import (
    build_launch_commands,
    fetch_hostfile,
    parse_resource_filter,
)

# the checkout this file lives in: a copy of the tree tests itself
REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


@pytest.fixture
def hostfile(tmp_path):
    p = tmp_path / "hostfile"
    p.write_text(
        """
# TPU pod hosts
worker-0 slots=4
worker-1 slots=4
worker-2 slots=4
"""
    )
    return str(p)


class TestHostfile:
    def test_parse(self, hostfile):
        res = fetch_hostfile(hostfile)
        assert res == OrderedDict([("worker-0", 4), ("worker-1", 4), ("worker-2", 4)])

    def test_missing_returns_none(self):
        assert fetch_hostfile("/nonexistent/hostfile") is None

    def test_malformed_raises(self, tmp_path):
        p = tmp_path / "bad"
        p.write_text("worker-0 gpus=4\n")
        with pytest.raises(ValueError):
            fetch_hostfile(str(p))


class TestResourceFilter:
    def setup_method(self):
        self.res = OrderedDict([("w0", 4), ("w1", 4)])

    def test_no_filter(self):
        act = parse_resource_filter(self.res)
        assert act == OrderedDict([("w0", [0, 1, 2, 3]), ("w1", [0, 1, 2, 3])])

    def test_include_host(self):
        act = parse_resource_filter(self.res, include_str="w1")
        assert list(act) == ["w1"]

    def test_include_slots(self):
        act = parse_resource_filter(self.res, include_str="w0:0,2")
        assert act == OrderedDict([("w0", [0, 2])])

    def test_exclude(self):
        act = parse_resource_filter(self.res, exclude_str="w0@w1:3")
        assert act == OrderedDict([("w1", [0, 1, 2])])

    def test_both_raises(self):
        with pytest.raises(ValueError):
            parse_resource_filter(self.res, include_str="w0", exclude_str="w1")

    def test_unknown_host_raises(self):
        with pytest.raises(ValueError):
            parse_resource_filter(self.res, include_str="nope")


class TestLaunchCommands:
    def test_one_process_per_host_with_jax_env(self):
        active = OrderedDict([("w0", [0, 1, 2, 3]), ("w1", [0, 1])])
        cmds = build_launch_commands(active, "train.py", ["--flag", "v"], master_port=9999)
        assert len(cmds) == 2
        h0, c0 = cmds[0]
        assert h0 == "w0"
        assert "COORDINATOR_ADDRESS=w0:9999" in c0
        assert "NUM_PROCESSES=2" in c0
        assert "PROCESS_ID=0" in c0
        assert "TPU_VISIBLE_CHIPS=0,1,2,3" in c0
        _, c1 = cmds[1]
        assert "PROCESS_ID=1" in c1 and "TPU_VISIBLE_CHIPS=0,1" in c1
        assert "train.py --flag v" in c0

    def test_cli_trains_end_to_end(self, tmp_path):
        """The single-host launcher path actually TRAINS: CLI -> runner ->
        user script -> engine -> loss drops -> exit 0 (reference single-node
        deepspeed launch), on one CPU device."""
        script = tmp_path / "train_tiny.py"
        script.write_text(
            "import os\n"
            "os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=1'\n"
            "import numpy as np\n"
            "import deepspeed_tpu\n"
            "from deepspeed_tpu.models import gpt2\n"
            "cfg = gpt2.get_config('gpt2-tiny')\n"
            "eng, _, _, _ = deepspeed_tpu.initialize(model=gpt2.make_module(cfg), config={\n"
            "    'train_micro_batch_size_per_gpu': 2,\n"
            "    'optimizer': {'type': 'AdamW', 'params': {'lr': 1e-3}},\n"
            "    'zero_optimization': {'stage': 1}, 'steps_per_print': 10**9})\n"
            "rs = np.random.RandomState(0)\n"
            "b = {'input_ids': rs.randint(0, cfg.vocab_size, (2, 64)).astype(np.int32)}\n"
            "losses = [float(eng.train_batch(b)['loss']) for _ in range(8)]\n"
            "assert losses[-1] < losses[0], losses\n"
            "print('E2E_TRAIN_OK', round(losses[0], 3), '->', round(losses[-1], 3))\n"
        )
        out = subprocess.run(
            [sys.executable, "-m", "deepspeed_tpu.launcher.runner", str(script)],
            capture_output=True, text=True, cwd=REPO_ROOT, timeout=600,
            env={**os.environ, "PYTHONPATH": REPO_ROOT},
        )
        assert out.returncode == 0, (out.stdout[-1500:], out.stderr[-1500:])
        assert "E2E_TRAIN_OK" in out.stdout

    def test_cli_dry_run(self, hostfile):
        out = subprocess.run(
            [sys.executable, "-m", "deepspeed_tpu.launcher.runner",
             "-H", hostfile, "--dry_run", "train.py", "--lr", "1e-4"],
            capture_output=True, text=True, cwd=REPO_ROOT,
        )
        assert out.returncode == 0, out.stderr
        lines = [l for l in out.stdout.splitlines() if l.startswith("[worker-")]
        assert len(lines) == 3
        assert "NUM_PROCESSES=3" in lines[0]


class TestDsReport:
    def test_runs(self, tmp_path):
        # from an empty directory: the report reads nothing beside itself
        out = subprocess.run(
            [sys.executable, "-m", "deepspeed_tpu.env_report"],
            capture_output=True, text=True, cwd=str(tmp_path),
            env={**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO_ROOT},
        )
        assert out.returncode == 0, out.stderr
        assert "op report" in out.stdout
        assert "jax" in out.stdout
        assert "cpu_adam" in out.stdout


class TestAuxCLIs:
    """bin/ equivalents (reference bin/ds_ssh, ds_bench, ds_elastic)."""

    def test_ds_elastic(self, tmp_path, capsys):
        import json

        from deepspeed_tpu.launcher.tools import ds_elastic

        cfg = {
            "elasticity": {
                "enabled": True,
                "max_train_batch_size": 1024,
                "micro_batch_sizes": [2, 4],
                "min_gpus": 1,
                "max_gpus": 32,
                "min_time": 0,
                "version": 0.1,
            },
            "train_batch_size": 4,
        }
        p = tmp_path / "ds_config.json"
        p.write_text(json.dumps(cfg))
        assert ds_elastic(["-c", str(p)]) == 0
        out = json.loads(capsys.readouterr().out)
        assert out["final_batch_size"] >= 4 and out["valid_gpus"]

    def test_watch_and_run_recovers_then_succeeds(self, tmp_path):
        """--watch: a failing command backs off and reruns; success stops
        the loop. The command's exit code is the only health signal — the
        launcher never opens the device itself."""
        from deepspeed_tpu.launcher.tools import _watch_and_run

        marks = tmp_path / "runs"
        code = (
            "import sys, pathlib; p = pathlib.Path(sys.argv[1]);"
            "n = len(p.read_text()) if p.exists() else 0;"
            "p.write_text('x' * (n + 1)); sys.exit(0 if n == 2 else 5)"
        )
        sleeps = []
        rc = _watch_and_run(
            [sys.executable, "-c", code, str(marks)],
            backoff_s=7.0, max_runs=0, sleep_fn=sleeps.append,
        )
        assert rc == 0
        assert sleeps == [7.0, 7.0]  # two failed runs back off, the third succeeds

    def test_watch_and_run_max_runs_caps_retries(self):
        from deepspeed_tpu.launcher.tools import _watch_and_run

        sleeps = []
        rc = _watch_and_run(
            [sys.executable, "-c", "import sys; sys.exit(3)"],
            backoff_s=1.0, max_runs=2, sleep_fn=sleeps.append,
        )
        assert rc == 3 and sleeps == [1.0]  # one backoff between the two runs

    def test_watch_cli_plumbs_through(self):
        from deepspeed_tpu.launcher.tools import ds_elastic

        rc = ds_elastic([
            "--watch", "--max-runs", "1", "--",
            sys.executable, "-c", "print('cli ok')",
        ])
        assert rc == 0

    def test_watch_preserves_inner_separator(self, monkeypatch):
        """Only the LEADING -- is the ds_elastic separator; an inner one
        belongs to the wrapped command."""
        from deepspeed_tpu.launcher import tools

        seen = {}

        def fake_run(cmd, *a, **k):
            seen["cmd"] = cmd
            return 0

        monkeypatch.setattr(tools.subprocess, "call", fake_run)
        rc = tools.ds_elastic(["--watch", "--", "tool", "--", "inner", "args"])
        assert rc == 0 and seen["cmd"] == ["tool", "--", "inner", "args"]

    def test_stray_args_without_watch_error(self, tmp_path):
        import json as _json

        from deepspeed_tpu.launcher.tools import ds_elastic

        p = tmp_path / "c.json"
        p.write_text(_json.dumps({"train_batch_size": 4}))
        with pytest.raises(SystemExit):
            ds_elastic(["-c", str(p), "stray", "typo"])

    def test_ds_bench_runs(self, capsys, devices):
        from deepspeed_tpu.launcher.tools import ds_bench

        assert ds_bench(["--bytes", "4096", "--iters", "1", "--ops", "all_reduce"]) == 0
        assert "all_reduce" in capsys.readouterr().out

    def test_ds_ssh_missing_hostfile(self, tmp_path):
        from deepspeed_tpu.launcher.tools import ds_ssh

        assert ds_ssh(["-f", str(tmp_path / "nope"), "echo", "hi"]) == 1
