"""The harness end to end at ``gpt2-tiny`` on the CPU mesh: each kind of cell
runs once with the profiler off and once with it on (module fixtures), and the
tests read the result. Nothing here is a device number."""

import json
import os
import subprocess
import sys
from types import SimpleNamespace

import pytest

from perfbench import run

from . import tiny

SEED = 2**31 + 123   # the driver's seeds are large
LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes"}


@pytest.fixture(scope="module")
def manifest(tmp_path_factory):
    m = tiny.make(tmp_path_factory.mktemp("bench"))
    m.validate()
    return m


@pytest.fixture(scope="module")
def results(manifest, tmp_path_factory):
    out = {}
    trace_dir = str(tmp_path_factory.mktemp("trace"))
    for cell in ("train-xl-l16-1chip", "serve-xl-chat-open", "serve-xl-doc-batch"):
        for trace in (False, True):
            out[cell, trace] = run.run_cell(manifest, cell, SEED, 1.0, trace, require_tpu=False, trace_dir=trace_dir)
    return out


CELLS = ["train-xl-l16-1chip", "serve-xl-chat-open", "serve-xl-doc-batch"]


@pytest.mark.parametrize("trace", [False, True])
@pytest.mark.parametrize("cell", CELLS)
def test_last_line_has_the_contracts_keys(results, cell, trace):
    out, _ = results[cell, trace]
    line = json.loads(json.dumps(out))   # what is printed
    assert LINE_KEYS <= set(line)
    assert DEVICE_KEYS <= set(line["device"])
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    for name, m in line["metrics"].items():
        assert set(m) == {"value", "unit"} and isinstance(m["value"], float), name
    assert line["notes"]["compilations_in_window"] == 0


@pytest.mark.parametrize("cell", CELLS)
def test_untraced_run_reports_the_cells_end_to_end_metrics(manifest, results, cell):
    out, ctx = results[cell, False]
    want = {m["name"] for m in manifest.metrics_for(cell, "end_to_end")}
    assert set(out["metrics"]) == want and "setup_s" in want
    assert all(v["value"] > 0 for v in out["metrics"].values())   # metrics that are never 0
    assert "breakdown" not in out
    # set-up counts from the chip reached to the window's opening; what came before is in the notes
    assert out["notes"]["reach_chip_s"] > 0 and out["metrics"]["setup_s"]["value"] < ctx.window[0] - run._T_PROCESS


@pytest.mark.parametrize("cell", CELLS)
def test_traced_run_reports_per_layer_metrics_that_need_no_device(manifest, results, cell):
    out, ctx = results[cell, True]
    listed = {m["name"]: m for m in manifest.metrics_for(cell, "per_layer")}
    assert set(out["metrics"]) <= set(listed)
    host = {n for n, m in listed.items() if m["source"] != "device_trace"}
    assert host <= set(out["metrics"])
    # the CPU has no device plane: every device_trace reader found nothing and
    # its metric is left out, never reported as 0
    assert not (set(out["metrics"]) - host)
    assert ctx.traced is not None and ctx.trace is None


def test_chat_window_counts_what_was_due_in_it(manifest, results):
    out, ctx = results["serve-xl-chat-open", False]
    tr = manifest.traffic("tiny-open")
    assert out["attempted"] == round(tr["rate_rps"] * 1.0) == out["notes"]["offered_in_window"]
    t0, t1 = ctx.window
    counted = [r for r in ctx.recs if r.counted]
    assert all(t0 <= r.due < t1 for r in counted)
    assert any(r.due < t0 for r in ctx.recs), "the ramp offered load before the window"
    assert all(r.n_tokens == r.new_tokens and r.status == "finished" for r in counted)
    assert all(r.t_submit >= r.due for r in ctx.recs)


def test_backlog_keeps_the_slots_busy_and_counts_tokens_inside_only(results):
    out, ctx = results["serve-xl-doc-batch", True]
    assert out["metrics"]["decode_slots_active.backlog"]["value"] > 50
    out0, ctx0 = results["serve-xl-doc-batch", False]
    from perfbench import arith

    t0, t1 = ctx0.window
    inside = arith.tokens_in_window(ctx0.recs, t0, t1)
    everything = arith.tokens_in_window(ctx0.recs, t0 - 100, t1 + 100)
    assert 0 < inside < everything
    assert out0["metrics"]["serve_tok_s"]["value"] == pytest.approx(inside / (t1 - t0))


class _FakeServer:
    """A scheduler on a clock the test owns: ``slots`` requests at a time, each
    prefills for ``prefill_steps`` steps and then emits a token a step; a step
    takes ``step_s``. Stamps as the program puts them."""

    def __init__(self, now, slots, prefill_steps, step_s):
        self.now, self.n_slots, self.prefill_steps, self.step_s = now, slots, prefill_steps, step_s
        self.queue, self.running = [], []

    @property
    def slots(self):
        return [SimpleNamespace(request=r) for r in self.running]

    def submit(self, ids, max_new_tokens, seed):
        r = SimpleNamespace(prompt=ids, prompt_len=len(ids), tokens=[], t_emissions=[], t_submit=self.now[0], t_admit=None,
                            t_first_token=None, t_finish=None, status="queued", done=False, want=max_new_tokens, left=0)
        self.queue.append(r)
        return r

    def step(self):
        while self.queue and len(self.running) < self.n_slots:
            r = self.queue.pop(0)
            r.t_admit, r.left = self.now[0], self.prefill_steps
            self.running.append(r)
        self.now[0] += self.step_s
        for r in list(self.running):
            if r.left > 1:
                r.left -= 1
                continue
            r.left = 0
            r.tokens.append(0)
            r.t_emissions.append(self.now[0])
            r.t_first_token = r.t_first_token or self.now[0]
            if len(r.tokens) == r.want:
                r.done, r.status, r.t_finish = True, "finished", self.now[0]
                self.running.remove(r)


def _fake_backlog_run(manifest, monkeypatch, step_s, seconds, ramp=None, stall=None):
    import contextlib

    from perfbench import arith
    from perfbench.context import Context
    from perfbench.runners import serve

    now = [100.0]
    monkeypatch.setattr(serve, "clock", lambda: now[0])
    cfg = dict(manifest.config("tiny-serve"))
    cfg["serving"] = dict(cfg["serving"], max_slots=1)
    tr = dict(manifest.traffic("tiny-backlog"), ramp=ramp or {"seconds": 0.0, "aged": False}, queue_depth=1)
    ctx = Context(cell={}, config=cfg, traffic=tr, chips=1, peak=None)
    r = serve.Runner(ctx, 1, [], lambda name: contextlib.nullcontext(), lambda msg: None)
    r.mcfg = serve.model_config(cfg)
    r.srv = _FakeServer(now, slots=1, prefill_steps=5, step_s=step_s)   # 5 + 7 steps a request
    if stall:   # the host stands still once, for stall[1] seconds, during step number stall[0]
        step, n = r.srv.step, [0]

        def stalled():
            n[0] += 1
            now[0] += stall[1] if n[0] == stall[0] else 0.0
            step()
        r.srv.step = stalled
    r._backlog(seconds, type("T", (), {"tick": lambda self, rel, s: None})())
    reqs = [q for q, _, _ in r.done + r.live]
    recs = [arith.Rec(due=0, prompt_len=q.prompt_len, new_tokens=q.want, t_submit=q.t_submit, t_admit=q.t_admit,
                      t_first_token=q.t_first_token, t_emissions=q.t_emissions) for q in reqs]
    return ctx.window, recs, now[0]


def test_a_prefill_the_close_catches_in_flight_is_counted_in_proportion(manifest, monkeypatch):
    """Two runs 0.3% apart in speed: the third request's first token comes at
    2.9 s in one and at 2.909 s in the other, and the window closes at 2.905 s
    between them. The caught prefill is stepped to its end, outside the
    window, and credited by the share inside; before PR 28 the slower run got
    nothing for it and the two read a whole prompt apart."""
    from perfbench import arith

    (a0, a1), fast, _ = _fake_backlog_run(manifest, monkeypatch, 0.1, 2.905)
    (b0, b1), slow, end = _fake_backlog_run(manifest, monkeypatch, 0.1003, 2.905)
    assert not [x for x in fast if x.t_admit is not None and x.t_admit < a1 and (x.t_first_token is None or x.t_first_token >= a1)]
    caught = [x for x in slow if x.t_admit is not None and x.t_admit < b1 and (x.t_first_token is None or x.t_first_token >= b1)]
    assert len(caught) == 1 and caught[0].t_first_token is not None      # stepped to its first token, after the close
    assert end < b1 + 2 * 0.1003                                           # the step that ends it, not a drain
    share = (b1 - caught[0].t_admit) / (caught[0].t_first_token - caught[0].t_admit)
    assert 0.98 < share < 1.0
    got = arith.tokens_in_window(slow, b0, b1)
    whole = [x for x in slow if x.t_first_token is not None and x.t_first_token < b1]
    assert got == pytest.approx(sum(x.prompt_len + sum(1 for t in x.t_emissions if t < b1) for x in whole)
                                + share * caught[0].prompt_len)
    assert got == pytest.approx(arith.tokens_in_window(fast, a0, a1), rel=0.01)
    caught[0].t_first_token = None       # as the run stood at the close before PR 28
    assert arith.tokens_in_window(slow, b0, b1) < 0.85 * got


@pytest.mark.parametrize("rule, same", [({"requests": 4, "aged": False}, True), ({"seconds": 4.0, "aged": False}, False)])
def test_a_ramp_told_in_requests_opens_the_window_at_the_same_request_whatever_the_host_did(manifest, monkeypatch, rule, same):
    """A host that stands still for 1.5 s of the ramp. Told in requests, the
    ramp ends at the same step boundary of the schedule in both runs (after
    the fourth submission), so both windows hold the same requests from the
    same one on and read the same; told in seconds, the stalled run's window
    opens earlier in the cycle and holds other work (PERF.md, PR 47)."""
    from perfbench import arith

    (a0, a1), calm, _ = _fake_backlog_run(manifest, monkeypatch, 0.1, 3.0, ramp=rule)
    (b0, b1), held, _ = _fake_backlog_run(manifest, monkeypatch, 0.1, 3.0, ramp=rule, stall=(7, 1.5))
    first = lambda recs, t0: min(i for i, x in enumerate(recs) if x.t_admit is not None and x.t_admit >= t0)
    assert (first(calm, a0) == first(held, b0)) is same
    if same:
        assert b0 - a0 == pytest.approx(1.5)      # the stall is set-up's, whole
        assert arith.tokens_in_window(held, b0, b1) == pytest.approx(arith.tokens_in_window(calm, a0, a1))


def test_training_counts_whole_steps_and_checks_the_reference(results):
    out, ctx = results["train-xl-l16-1chip", False]
    assert out["attempted"] == len([t for t in ctx.step_ends if ctx.window[0] <= t <= ctx.window[1]]) - 1
    ref = out["notes"]["reference"]
    assert ref["abs_diff"] <= ref["tol"] and ref["reference_loss"] > 5.0


def test_the_chips_loss_tolerance_would_catch_a_dropped_layer(results):
    """The tolerance the training configurations carry (1e-3) against what a
    missing layer does to the reference's loss at this size."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.models import gpt2
    from perfbench import reference

    _, ctx = results["train-xl-l16-1chip", False]
    cfg = ctx.config
    keys = ("vocab_size", "n_positions", "n_embd", "n_layer", "n_head")
    params = gpt2.init_params(gpt2.GPT2Config(**{k: cfg[k] for k in keys}), jax.random.PRNGKey(0))
    ids = jnp.asarray(np.random.default_rng(0).integers(0, cfg["vocab_size"], (2, cfg["seq"])).astype(np.int32))
    kw = dict(n_head=cfg["n_head"], eps=cfg["layer_norm_epsilon"], vocab=cfg["vocab_size"])
    full, cut = reference.lm_loss(params, ids, **kw), reference.lm_loss(params, ids, skip_layer=0, **kw)
    assert abs(float(full) - float(cut)) > 1e-3


def test_refuses_to_measure_on_a_cpu(manifest):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, os.path.join(tiny.REPO, "perfbench", "run.py"), "--workload", "train-xl-l16-1chip",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tiny.REPO, env=env, capture_output=True, text=True, timeout=300,
    )
    assert p.returncode == 2 and p.stdout.strip() == "" and "only on a TPU" in p.stderr


def test_unknown_cell_fails_before_jax_is_touched():
    p = subprocess.run(
        [sys.executable, os.path.join(tiny.REPO, "perfbench", "run.py"), "--workload", "nope", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=tiny.REPO, capture_output=True, text=True, timeout=120,
    )
    assert p.returncode != 0 and p.stdout.strip() == "" and "no workload" in p.stderr


def test_fails_without_the_program_beside_it(manifest):
    """In a directory that holds only BENCHMARK.json and the files under
    ``paths`` there is nothing to measure: non-zero, no result line."""
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable, os.path.join(manifest.root, "perfbench", "run.py"), "--workload", "train-xl-l16-1chip",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=manifest.root, env=env, capture_output=True, text=True, timeout=300,
    )
    assert p.returncode != 0 and p.stdout.strip() == ""
