"""``telemetry.spans``: the ring and the phases, the profiler annotation, and the
spans the program opens inside ``ServingEngine.step``, ``train_batch`` and
set-up at ``gpt2-tiny`` on the CPU mesh. Nothing here is a device number."""

import collections
import glob
import json
import os
import re
import statistics

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import gpt2
from deepspeed_tpu.telemetry import compile_stats, spans

SERVE_LEAVES = {"ds.serve.admit", "ds.serve.chunk", "ds.serve.handoff", "ds.serve.decode.dispatch",
                "ds.serve.decode.wait", "ds.serve.emit", "ds.serve.housekeep"}
TRAIN_LEAVES = ["ds.train.prepare", "ds.train.dispatch", "ds.train.wait", "ds.train.post"]


# -- the core ---------------------------------------------------------------

@pytest.fixture
def small_ring(monkeypatch):
    monkeypatch.setattr(spans, "_ring", collections.deque(maxlen=8))
    monkeypatch.setattr(spans, "_phases", collections.deque(maxlen=8))


def test_ring_is_bounded_and_ordered(small_ring):
    for i in range(20):
        with spans.span("ds.t", i=i):
            pass
    recs = spans.snapshot()
    assert [r[3]["i"] for r in recs] == list(range(12, 20))   # the newest 8, oldest first
    assert all(r[0] == "ds.t" and r[1] <= r[2] for r in recs)
    assert all(a[2] <= b[1] for a, b in zip(recs, recs[1:]))   # one clock, in order
    assert [r[3]["i"] for r in spans.snapshot(since=recs[5][2])] == list(range(17, 20))


def test_attributes_set_before_exit_are_recorded(small_ring):
    with spans.span("ds.t", queue=3) as s:
        assert s.t0 > 0 and s.elapsed() >= 0
        s.set(tokens=5, blocked="page_budget")
    (name, t0, t1, attrs), = spans.snapshot()
    assert attrs == {"queue": 3, "tokens": 5, "blocked": "page_budget"}
    assert s.duration == t1 - t0 >= 0


def test_a_span_is_recorded_when_its_block_raises(small_ring):
    with pytest.raises(KeyError):
        with spans.span("ds.t"):
            raise KeyError("x")
    assert [r[0] for r in spans.snapshot()] == ["ds.t"]


def test_phases_survive_a_full_ring(small_ring):
    with spans.phase("ds.init.params", what="test"):
        pass
    spans.note_phase("ds.jit.compile", 1.0, 3.5, fun="f")
    for _ in range(50):
        with spans.span("ds.t"):
            pass
    assert len(spans.snapshot()) == 8
    got = spans.phases()
    assert [r[0] for r in got] == ["ds.init.params", "ds.jit.compile"]
    assert got[1][1:] == (1.0, 3.5, {"fun": "f"})
    assert not any(r[0].startswith("ds.init") for r in spans.snapshot())


def test_summary_counts_and_quantiles(small_ring):
    for d in (1.0, 2.0, 3.0, 4.0):
        spans._ring.append(("ds.a", 10.0, 10.0 + d, {}))
    spans._ring.append(("ds.b", 0.0, 0.5, {}))
    s = spans.summary()
    assert s["ds.a"] == {"count": 4, "total_s": 10.0, "p50_s": 2.0, "p95_s": 4.0}
    assert s["ds.b"]["count"] == 1 and s["ds.b"]["p50_s"] == 0.5
    assert set(spans.summary(since=11.5)) == {"ds.a"}


def test_a_span_lands_in_an_open_profiler_session_with_its_attributes(tmp_path):
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        with spans.span("ds.test.traced", step=7, queue=3) as s:
            s.set(active=2)
        with spans.phase("ds.test.phase", what="x"):
            pass
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"), recursive=True))[-1]
    found = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("ds.test."):
                    found[ev.name] = (dict(ev.stats), ev.duration_ns)
    assert found["ds.test.traced"][0] == {"step": 7, "queue": 3, "active": 2}
    assert found["ds.test.phase"][0] == {"what": "x"}
    # outside a session a span opens no annotation at all
    with spans.span("ds.test.untraced") as s:
        assert s._ann is None


# -- ServingEngine.step -----------------------------------------------------

def _children(recs, parent):
    """Spans that lie inside ``parent``'s interval, the parent itself excluded."""
    return [r for r in recs if r is not parent and r[1] >= parent[1] and r[2] <= parent[2]]


def _top_level(children):
    """Of the spans inside one parent, those not nested in another of them."""
    return [c for c in children
            if not any(o is not c and o[1] <= c[1] and c[2] <= o[2] for o in children)]


def _serve(ahead=True):
    """A short run through a tiny server: mixed prompts, in one chunk and in
    several, more requests than slots. ``ahead=False`` holds the loop to
    depth 0: a call reads the step it launched, nothing is ever in flight
    between two calls. Returns (records, phases, requests, engine)."""
    from deepspeed_tpu.inference.engine import InferenceEngine

    cfg = gpt2.get_config("gpt2-tiny", attn_impl="jnp")
    t_start = spans._clock()
    eng = InferenceEngine(gpt2.make_module(cfg), params=gpt2.init_params(cfg, jax.random.PRNGKey(0)),
                          dtype=jnp.float32)
    srv = eng.serve({"max_slots": 4, "page_size": 4, "num_pages": 96, "max_prompt_len": 24,
                     "max_new_tokens": 6, "prefill_chunk_tokens": 8, "kv_cache_dtype": "float32"})
    if not ahead:
        srv._ahead_ok = False
    rng = np.random.default_rng(0)
    reqs = [srv.submit(rng.integers(0, cfg.vocab_size, n).astype(np.int32), max_new_tokens=6, seed=i)
            for i, n in enumerate([5, 20, 7, 18, 6, 24, 4])]
    srv.run()
    return spans.snapshot(since=t_start), spans.phases(since=t_start), reqs, srv


@pytest.fixture(scope="module")
def served():
    return _serve()


@pytest.fixture(scope="module")
def served_in_turn():
    return _serve(ahead=False)


def _tiling(recs):
    """Hold every ``ds.serve.step`` of ``recs`` to the tiling: leaves from
    ``admit`` to ``housekeep`` that do not overlap, nothing nested in them but
    a wait on the device or one call of a program where its leaf makes
    several (``ds.serve.launch``). → each step's (share of its time inside a leaf,
    seconds outside every leaf)."""
    steps = [r for r in recs if r[0] == "ds.serve.step"]
    assert len(steps) >= 10
    out = []
    for st in steps:
        inside = _children(recs, st)
        top = _top_level(inside)
        assert {c[0] for c in top} <= SERVE_LEAVES
        assert [c[0] for c in top][0] == "ds.serve.admit" and top[-1][0] == "ds.serve.housekeep"
        assert all(a[2] <= b[1] for a, b in zip(top, top[1:]))   # siblings do not overlap
        # whatever is nested deeper is a wait on the device, or the leaf around one launch of several
        assert all(c[0].endswith(".wait") or c[0] == "ds.serve.launch" for c in inside if c not in top)
        covered = sum(c[2] - c[1] for c in top)
        out.append((covered / (st[2] - st[1]), st[2] - st[1] - covered))
        assert set(st[3]) == {"step", "queue", "active"}
    return out


def _attr_sets(recs, names):
    seen = collections.defaultdict(set)
    for name, _, _, attrs in recs:
        if name in names:
            seen[name].add(frozenset(attrs))
    return dict(seen)


# the sets of attributes a leaf may carry, the one every server shows first. The launch number (ISSUE 55): the leaf
# that makes ONE call of a program carries LAUNCH, the leaves that read a step carry ``flight``, the emit that hands
# out a first token left on the device ``firsts``, a synchronous wait for one ``launch``
LAUNCH = {"launch", "kind", "rows", "tokens"}
DISPATCH = {"active", "ahead", "attended", "pages", "walk_steps", "rect_steps"}   # the attention kernel's walk (ISSUE 58)
# (``whole``, ISSUE 63: a chunk call that carries a whole prompt, and how many of a step's chunks do. A server that
# chunks has no whole-prompt program, so no ``ds.serve.prefill.wait``: tests/unit/test_serving_launches.py holds that
# leaf to its ``launch`` on a server that does not chunk)
LEAF_ATTRS = {"ds.serve.admit": [{"admitted", "blocked"}],
              "ds.serve.chunk": [{"chunks", "rode", "tokens", "attended", "whole"}],
              "ds.serve.decode.dispatch": [DISPATCH | LAUNCH, DISPATCH],      # a plain step; a chunk rides
              "ds.serve.launch": [LAUNCH, LAUNCH | {"whole"}], "ds.serve.decode.wait": [{"flight"}],
              "ds.serve.emit": [{"tokens", "finished", "flight"}, {"tokens", "finished", "flight", "firsts"}],
              "ds.serve.housekeep": [{"stats", "journal", "pump", "stragglers"}],
              "ds.serve.chunk.wait": [{"launch"}]}


def _hold_to_leaf_attrs(recs, want):
    seen = _attr_sets(recs, want)
    assert set(seen) == set(want)
    for name, allowed in want.items():
        assert seen[name] <= {frozenset(a) for a in allowed} and frozenset(allowed[0]) in seen[name], name
    return seen


def test_leaves_tile_each_serve_step_and_carry_the_documented_attrs(served):
    recs, _, reqs, srv = served
    assert all(r.done for r in reqs)
    outside = statistics.median(o for _, o in _tiling(recs))
    # A step is in flight through every call, and here the "device" is this host's own cores: a call is as much
    # shorter as its wait is (0.55-0.75 ms for 0.9-1.4), so the SHARE of it inside a leaf says less than it did:
    # 0.94-0.96 where the loop held to depth 0 reads 0.953-0.97, the next test's. What a step may not do is run
    # a statement of any weight outside a leaf, so its time outside them is held in seconds: 26-63 us a call with
    # six such processes on this host, 33-69 us with the loop held to depth 0 (the spans' own entries and exits).
    assert outside < 100e-6
    # two ds.serve.chunk.wait, for the one-chunk prompts (5 and 7 tokens) the empty server's first call admits:
    # with a step in flight a last chunk's token stays on its slot for that step's fetch
    seen = _hold_to_leaf_attrs(recs, LEAF_ATTRS)
    assert sum(r[0] == "ds.serve.chunk.wait" for r in recs) == 2
    assert not any(r[0] == "ds.serve.prefill.wait" or r[3].get("kind") == "prefill" for r in recs)
    # chunks rode steps, and first tokens were left on the device for a step's fetch
    assert len(seen["ds.serve.decode.dispatch"]) == 2 == len(seen["ds.serve.emit"])
    ahead = [r[3]["ahead"] for r in recs if r[0] == "ds.serve.decode.dispatch"]
    assert ahead[0] == 0 and sum(ahead) >= len(ahead) - 2
    # every request was admitted once; a full house names what blocked the queue
    admits = [r[3] for r in recs if r[0] == "ds.serve.admit"]
    assert sum(a["admitted"] for a in admits) == len(reqs)
    assert {a["blocked"] for a in admits} == {"", "no_free_slot"}
    assert sum(r[3]["finished"] for r in recs if r[0] == "ds.serve.emit") == len(reqs)
    # every prompt went through the 8-token chunk program, those of 4 to 7 tokens in ONE call that says so
    chunks = [r[3] for r in recs if r[0] == "ds.serve.chunk"]
    assert sum(c["tokens"] for c in chunks) == sum(r.prompt_len for r in reqs) == 84
    launches = [r[3] for r in recs if r[0] == "ds.serve.launch"]
    assert sum(c["whole"] for c in chunks) == 4 == sum(a.get("whole", 0) for a in launches)
    assert sorted(a["tokens"] for a in launches if a.get("whole")) == [4, 5, 6, 7]


def test_a_loop_with_nothing_in_flight_tiles_its_steps_and_waits_where_it_launches(served_in_turn):
    """Depth 0 (what speculation and a disaggregated placement run at): a
    call reads the step it launched, so its leaves cover it as they covered
    the synchronous loop's, and a prompt's last chunk that does not ride
    waits in ``ds.serve.chunk.wait``."""
    recs, _, reqs, srv = served_in_turn
    assert all(r.done for r in reqs) and srv.stats()["steps_ahead"] == 0
    assert statistics.median(r for r, _ in _tiling(recs)) > 0.95
    seen = _hold_to_leaf_attrs(recs, LEAF_ATTRS)
    # nothing rides and nothing is left on the device at depth 0: every dispatch is its step's one call
    assert len(seen["ds.serve.decode.dispatch"]) == 1 == len(seen["ds.serve.emit"])
    assert {r[3]["ahead"] for r in recs if r[0] == "ds.serve.decode.dispatch"} == {0}
    assert sum(r[3]["finished"] for r in recs if r[0] == "ds.serve.emit") == len(reqs)


def test_decode_counters_agree_with_the_tokens_the_requests_got(served):
    recs, _, reqs, srv = served
    disp = [r[3] for r in recs if r[0] == "ds.serve.decode.dispatch"]
    emit = [r[3] for r in recs if r[0] == "ds.serve.emit"]
    by_decode = sum(len(r.tokens) - 1 for r in reqs)    # the first token comes from the prefill
    assert sum(d["active"] for d in disp) == by_decode == sum(e["tokens"] for e in emit)
    # a slot at position p attends p + 1 tokens and holds ceil((p + 1) / page) pages
    attended = sum(r.prompt_len + k for r in reqs for k in range(1, len(r.tokens)))
    assert sum(d["attended"] for d in disp) == attended
    assert sum(d["pages"] for d in disp) == sum(
        -(-(r.prompt_len + k) // 4) for r in reqs for k in range(1, len(r.tokens)))
    reg = srv.metrics
    assert reg.counter("serving_decode_slot_steps_total").value() == by_decode
    assert reg.counter("serving_attended_tokens_total").value() == attended
    assert reg.counter("serving_decode_steps_total").value() == len(disp)


def test_serving_setup_is_recorded_as_phases_that_name_their_programs(served):
    _, phases, _, _ = served
    names = [p[0] for p in phases]
    assert names.count("ds.init.params") == 1
    progs = [p for p in phases if p[0] == "ds.init.programs"]
    assert len(progs) == 1 and progs[0][3]["what"] == "serving"
    # and the census of the compiled programs (ISSUE 29): <program>=<n> each
    # and, since ISSUE 31, the grid steps of one call of its paged attention kernel,
    # since ISSUE 61 the whole weight leaves it copies for another order
    assert set(progs[0][3]) == {"what", "relayout_ops", "weight_relayout", "temp_bytes", "grid_steps",
                                "kv_bytes", "window_pages_per_slot", "moe_experts_held", "kv_row_bytes"}
    # GPT-2: every layer's KV is paged, no window ring, no expert layer
    assert progs[0][3]["kv_bytes"].endswith("window=0") and progs[0][3]["kv_bytes"].startswith("paged=")
    assert progs[0][3]["window_pages_per_slot"] == 0 and progs[0][3]["moe_experts_held"] == 0
    counts = {progs[0][3][k].count("=") for k in ("relayout_ops", "weight_relayout", "temp_bytes", "grid_steps")}
    assert len(counts) == 1 and counts.pop() >= 2
    inside = [p for p in phases if p[0].startswith("ds.jit.") and progs[0][1] <= p[1] and p[2] <= progs[0][2]]
    assert {p[0] for p in inside} == {"ds.jit.trace", "ds.jit.lower", "ds.jit.compile"}
    compiled = {p[3]["fun"] for p in inside if p[0] == "ds.jit.compile"}
    for program in ("decode_fn", "chunk_decode_fn"):   # jax calls them jit(decode_fn), ...
        assert any(program in f for f in compiled), (program, compiled)
    assert not any("prefill_fn" in f for f in compiled)    # a server that chunks builds no whole-prompt program


# -- compile_stats ------------------------------------------------------------

def test_compile_phases_carry_the_program_name_and_events_match_exactly():
    from deepspeed_tpu.telemetry.registry import MetricsRegistry

    reg = MetricsRegistry()
    compile_stats.install(reg)
    try:
        t0 = spans._clock()

        def a_named_program(x):
            return x * 5 + 17

        jax.jit(a_named_program)(jnp.ones((3,)))
        mine = [p for p in spans.phases(since=t0) if "a_named_program" in p[3].get("fun", "")]
        assert {p[0] for p in mine} == {"ds.jit.trace", "ds.jit.lower", "ds.jit.compile"}
        assert all(t0 <= p[1] <= p[2] for p in mine)
        before = (reg.counter("jit_trace_seconds_total").value(), len(spans.phases()))
        # an event that merely has "trace" or "backend_compile" in its path is not one of the three
        compile_stats._on_duration("/jax/some/other/trace_duration", 9.0, fun_name="x")
        compile_stats._on_duration("/jax/pjit/backend_compile_helper", 9.0)
        assert (reg.counter("jit_trace_seconds_total").value(), len(spans.phases())) == before
        compile_stats._on_event("/jax/compilation_cache/cache_hits")
        compile_stats._on_duration("/jax/core/compile/backend_compile_duration", 0.25, fun_name="jit_f")
        last = spans.phases()[-1]
        assert last[0] == "ds.jit.compile" and last[3] == {"fun": "jit_f", "cache_hit": True}
        assert last[2] - last[1] == pytest.approx(0.25)
        compile_stats._on_duration("/jax/core/compile/backend_compile_duration", 0.25, fun_name="jit_g")
        assert "cache_hit" not in spans.phases()[-1][3]   # jax said nothing about this one
    finally:
        compile_stats.uninstall()


# -- train_batch ----------------------------------------------------------------

def _train_engine(tmp_path=None, **telemetry):
    import deepspeed_tpu
    from deepspeed_tpu.parallel.topology import MeshSpec

    cfg = gpt2.get_config("gpt2-tiny", attn_impl="jnp")
    config = {"train_micro_batch_size_per_gpu": 2, "gradient_accumulation_steps": 1,
              "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
              "zero_optimization": {"stage": 3}, "bf16": {"enabled": True}, "steps_per_print": 10**9}
    if telemetry:
        config["telemetry"] = telemetry
    mesh = MeshSpec(dp=1, devices=jax.devices()[:1]).build_mesh()
    engine, _, _, _ = deepspeed_tpu.initialize(model=gpt2.make_module(cfg), config=config, mesh=mesh, seed=0)
    rng = np.random.default_rng(0)
    batches = [{"input_ids": rng.integers(0, cfg.vocab_size, (2, 32)).astype(np.int32)} for _ in range(6)]
    return engine, batches


def test_leaves_tile_train_batch_and_the_first_call_is_a_programs_phase():
    t_start = spans._clock()
    engine, batches = _train_engine()
    for b in batches:
        engine.train_batch(b)
    recs, phases = spans.snapshot(since=t_start), spans.phases(since=t_start)
    steps = [r for r in recs if r[0] == "ds.train.batch"]
    assert [s[3] for s in steps] == [{"step": i + 1} for i in range(len(batches))]
    ratios = []
    for st in steps:
        top = _top_level(_children(recs, st))
        assert [c[0] for c in top] == TRAIN_LEAVES
        ratios.append(sum(c[2] - c[1] for c in top) / (st[2] - st[1]))
    assert statistics.median(ratios) > 0.95
    assert [p[3] for p in phases if p[0] == "ds.init.params"] == [{"what": "train_state"}]
    progs = [p for p in phases if p[0] == "ds.init.programs"]
    # on the CPU the jnp attention runs: no flash kernel, so no plan (ISSUE 33); on one dp
    # rank the step's text is not read for its collectives: all zero (ISSUE 40); what the optimizer's
    # instructions read and write, and how many write a whole leaf, is read on any mesh (ISSUE 45)
    attrs = dict(progs[0][3])
    assert len(progs) == 1 and re.fullmatch(r"\d+\.\d\dr\+\d+\.\d\dw/[1-9]\d*p", attrs.pop("optim"))
    assert attrs == {
        "what": "train_step", "flash_plan": "bq=0 bk=0 masked=0 plain=0",
        "collectives": "all_gather=0w+0a reduce_scatter=0w+0a all_reduce=0w+0a all_to_all=0w+0a",
        "gathers_ahead": "0/0"}   # (ISSUE 51: of no weight gather, none ahead)
    # the step's compilation happened inside it, and inside the first step's dispatch leaf
    first_dispatch = next(r for r in recs if r[0] == "ds.train.dispatch")
    assert first_dispatch[1] <= progs[0][1] and progs[0][2] <= first_dispatch[2]
    assert any(p[0] == "ds.jit.compile" and progs[0][1] <= p[1] and p[2] <= progs[0][2] for p in phases)


def test_sampled_step_record_keeps_its_span_keys_and_reads_the_leaves(tmp_path):
    t_start = spans._clock()
    engine, batches = _train_engine(enabled=True, trace_path=str(tmp_path / "traces"),
                                    flush_interval=1, sample_every=1)
    for b in batches[:3]:
        engine.train_batch(b)
    engine.telemetry.flush()
    with open(engine.telemetry.tracer.file_path) as f:
        recs = [json.loads(line) for line in f]
    assert [r["step"] for r in recs] == [1, 2, 3]
    ring = spans.snapshot(since=t_start)
    for i, r in enumerate(recs):
        # the three keys as before; what train_batch did after the wait and before
        # the record (watchdog, rollback snapshot) is the tracer's "other" remainder
        assert list(r["spans"]["children"])[:3] == ["prepare", "dispatch", "sync"]
        assert set(r["spans"]["children"]) <= {"prepare", "dispatch", "sync", "other"}
        assert r["spans"]["total_ms"] >= sum(r["spans"]["children"].values()) - 2e-3
        for key, leaf in (("prepare", "ds.train.prepare"), ("dispatch", "ds.train.dispatch"),
                          ("sync", "ds.train.wait")):
            _, t0, t1, _ = [x for x in ring if x[0] == leaf][i]
            assert r["spans"]["children"][key] == pytest.approx((t1 - t0) * 1e3, abs=1e-3)
