"""perfbench/program_spans.py and the readers that use it: the arithmetic on
hand-made spans and intervals, the walk on the xplane file recorded on the v5e
(tests/perfbench/data/small.xplane.pb), and what a program without spans, or a
run without a trace, gives: nothing."""

import collections
import os

import pytest

from perfbench import program_spans as ps, xplane
from perfbench.context import Context
from perfbench.manifest import Manifest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data", "small.xplane.pb")


def ctx_for(window=(100.0, 200.0), **config):
    return Context(cell={"name": "no-such-cell"}, config=config, traffic={}, chips=1, peak=None, window=window)


@pytest.fixture
def ring(monkeypatch):
    """The program's ring and phases, emptied for one test, filled by hand."""
    from deepspeed_tpu.telemetry import spans

    monkeypatch.setattr(spans, "_ring", collections.deque(maxlen=1024))
    monkeypatch.setattr(spans, "_phases", collections.deque(maxlen=1024))
    return spans


def step(ring, t0, admitted=0, active=2, wait=0.080):
    """One hand-made served step at ``t0``: 1 ms admit (plus a 50 ms prefill wait
    inside it when it admitted), 0.4 ms dispatch, the wait, 0.3 ms emit."""
    t = t0
    a1 = t + 0.001 + (0.050 if admitted else 0.0)
    if admitted:
        ring._ring.append(("ds.serve.prefill.wait", t + 0.0005, t + 0.0505, {}))
    ring._ring.append(("ds.serve.admit", t, a1, {"admitted": admitted, "blocked": ""}))
    ring._ring.append(("ds.serve.decode.dispatch", a1, a1 + 0.0004, {"active": active, "attended": 10, "pages": 2}))
    ring._ring.append(("ds.serve.decode.wait", a1 + 0.0004, a1 + 0.0004 + wait, {}))
    e0 = a1 + 0.0004 + wait
    ring._ring.append(("ds.serve.emit", e0, e0 + 0.0003, {"tokens": active, "finished": 0}))
    ring._ring.append(("ds.serve.step", t0, e0 + 0.0004, {"step": 0, "queue": 0, "active": active}))
    return e0 + 0.0004


def reader(name):
    return Manifest(REPO).reader(name)


# -- host clock -----------------------------------------------------------------

def test_span_quantile_takes_the_waits_out_and_keeps_to_the_window(ring):
    t = 90.0
    for i in range(14):   # the first steps lie before the window or across its edge, the last past its end
        t = step(ring, t, admitted=1 if i % 4 == 0 else 0, active=1 + i % 4, wait=8.0 + 0.001 * i) + 0.001
    ctx = ctx_for(max_slots=4, serving={"max_slots": 4})
    rd = reader("span_quantile")
    # host time of a step: 1 ms + 0.4 ms + 0.3 ms + 0.1 ms, whatever the waits were
    assert rd.read(ctx, name="ds.serve.step", q=0.5, minus_suffix=".wait") == pytest.approx(0.0018, rel=1e-6)
    assert rd.read(ctx, name="ds.serve.step", q=0.5) > 8.0
    assert rd.read(ctx, name="ds.serve.decode.dispatch", q=0.5) == pytest.approx(0.0004, rel=1e-6)
    # admissions only: 51 ms with the prefill wait inside, 1 ms without it
    kw = dict(name="ds.serve.admit", q=0.5, min_attr={"admitted": 1})
    assert rd.read(ctx, **kw) == pytest.approx(0.051, rel=1e-6)
    assert rd.read(ctx, minus_suffix=".wait", **kw) == pytest.approx(0.001, rel=1e-6)
    inside = ps.records_in(ctx.window)
    assert len([r for r in inside if r[0] == "ds.serve.step"]) == 11
    assert all(100.0 <= r[1] and r[2] <= 200.0 for r in inside)
    assert rd.read(ctx, name="ds.no.such.span", q=0.5) is None


def test_span_attr_share_is_the_mean_over_a_configuration_value(ring):
    t = 100.0
    for active in (1, 2, 3, 4, 4, 4):
        t = step(ring, t, active=active)
    ctx = ctx_for(serving={"max_slots": 4})
    rd = reader("span_attr_share")
    assert rd.read(ctx, name="ds.serve.decode.dispatch", attr="active", over="serving.max_slots") == pytest.approx(75.0)
    assert rd.read(ctx, name="ds.serve.decode.dispatch", attr="nope", over="serving.max_slots") is None


def test_phase_sum_covers_nested_events_once_and_stops_at_the_window(ring):
    P = ring._phases
    P.append(("ds.init.params", 10.0, 14.0, {"what": "inference"}))
    P.append(("ds.jit.trace", 10.5, 11.0, {"fun": "init"}))       # inside params
    P.append(("ds.jit.compile", 11.0, 12.0, {"fun": "init"}))     # inside params
    P.append(("ds.jit.trace", 20.0, 30.0, {"fun": "decode_fn"}))
    P.append(("ds.jit.trace", 22.0, 23.0, {"fun": "inner"}))      # nested in decode_fn's trace
    P.append(("ds.jit.compile", 22.5, 22.75, {"fun": "inner"}))   # an eager op compiled while tracing
    P.append(("ds.jit.lower", 30.0, 34.0, {"fun": "decode_fn"}))
    P.append(("ds.jit.compile", 34.0, 40.0, {"fun": "decode_fn", "cache_hit": True}))
    P.append(("ds.jit.compile", 150.0, 151.0, {"fun": "late"}))   # in the window: not set-up
    ctx = ctx_for()
    rd = reader("phase_sum")
    jit = ["ds.jit.trace", "ds.jit.lower", "ds.jit.compile"]
    params = rd.read(ctx, names=["ds.init.params"], minus_nested=jit)
    trace_lower = rd.read(ctx, names=jit[:2], minus_nested=jit[2:])
    compile_ = rd.read(ctx, names=jit[2:])
    assert params == pytest.approx(4.0 - 0.5 - 1.0)
    assert trace_lower == pytest.approx(0.5 + 14.0 - 0.25)
    assert compile_ == pytest.approx(1.0 + 0.25 + 6.0)
    assert params + trace_lower + compile_ == pytest.approx(4.0 + 20.0)   # every second once
    ring._phases.clear()
    assert rd.read(ctx, names=jit[2:]) == 0.0     # a later cell of one process: 0.0, not nothing


def test_a_program_without_spans_reports_nothing(monkeypatch, ring):
    step(ring, 100.0)
    monkeypatch.setattr(ps, "program", lambda: None)
    ctx = ctx_for(serving={"max_slots": 4})
    assert reader("span_quantile").read(ctx, name="ds.serve.step", q=0.5) is None
    assert reader("span_attr_share").read(ctx, name="ds.serve.decode.dispatch", attr="active",
                                          over="serving.max_slots") is None
    assert reader("phase_sum").read(ctx, names=["ds.jit.compile"]) is None
    ctx.trace = object()
    assert reader("idle_outside_spans").read(ctx) is None


# -- one clock, and the gaps ------------------------------------------------------

def test_innermost_segments_name_every_piece_once():
    spans = [("ds.serve.step", 0, 100), ("ds.serve.admit", 5, 40), ("ds.serve.prefill.wait", 10, 30),
             ("ds.serve.emit", 60, 90), ("ds.serve.step", 120, 150)]
    assert ps.innermost_segments(spans) == [
        ("ds.serve.step", 0, 5), ("ds.serve.admit", 5, 10), ("ds.serve.prefill.wait", 10, 30),
        ("ds.serve.admit", 30, 40), ("ds.serve.step", 40, 60), ("ds.serve.emit", 60, 90),
        ("ds.serve.step", 90, 100), ("ds.serve.step", 120, 150)]


def test_gaps_are_split_by_intersection_not_by_midpoint():
    spans = [("ds.serve.step", 0, 100), ("ds.serve.decode.wait", 0, 20), ("ds.serve.emit", 20, 26),
             ("ds.serve.housekeep", 26, 30), ("ds.serve.step", 104, 200), ("ds.serve.admit", 104, 110),
             ("ds.serve.decode.dispatch", 112, 130)]
    # one gap from the device's last operation (t=18) to its next (t=125): its
    # midpoint lies in the parent alone; by intersection every leaf gets its part
    got = ps.split_gaps([(18, 125)], spans)
    assert got == {"ds.serve.decode.wait": 2, "ds.serve.emit": 6, "ds.serve.housekeep": 4,
                   ps.IN_STEP: 70 + 2, ps.OUTSIDE: 4, "ds.serve.admit": 6, "ds.serve.decode.dispatch": 13}
    assert sum(got.values()) == 125 - 18
    assert ps.split_gaps([(300, 310)], spans) == {ps.OUTSIDE: 10}
    assert ps.split_gaps([], spans) == {}


def test_clock_offset_is_the_most_a_program_seems_to_start_before_its_launch():
    launches = [("d", 1_000_000, 1_400_000), ("d", 90_000_000, 90_400_000), ("d", 180_000_000, 180_400_000)]
    # device clock 260 us behind, launch latencies 60, 30 and 100 us; the second
    # program was queued behind another and started late
    starts = [1_000_000 + 60_000 - 260_000, 90_000_000 + 30_000 + 5_000_000 - 260_000,
              180_000_000 + 100_000 - 260_000]
    assert ps.clock_offset_ns(starts, launches) == 200_000   # short of the truth by the least latency
    assert ps.clock_offset_ns([s + 260_000 for s in starts], launches) == 0
    assert ps.clock_offset_ns(starts, []) == 0 and ps.clock_offset_ns([], launches) == 0
    # the search reaches half a step back (launches 89 ms apart): 2 ms early is the clock,
    # most of a step early is a program of the step before and not evidence
    assert ps.clock_offset_ns([90_000_000 - 2_000_000], launches) == 2_000_000
    assert ps.clock_offset_ns([90_000_000 - 50_000_000], launches) == 0
    assert ps.clock_offset_ns([90_000_000 - 2_000_000], launches, max_ns=1_000_000) == 0


@pytest.fixture(scope="module")
def recorded():
    return xplane.load(DATA, span_prefixes=("ds.", "perfbench.", ps.RUNTIME_LAUNCH))


def test_recorded_trace_gives_the_offset_and_keeps_every_idle_nanosecond(recorded):
    dev = recorded.devices[0]
    steps = [s for s in recorded.host_spans if s[0] == "perfbench.step"]
    off = ps.clock_offset_ns([s for _, s, _ in dev.modules], steps)
    assert 0.2e6 < off < 0.4e6    # PERF.md, PR 23: "about 0.26 ms behind"; all four programs start that early
    # the runtime's own launch event lies 0.1-0.2 ms inside each step span: a closer bound
    runtime = [s for s in recorded.host_spans if s[0] == ps.RUNTIME_LAUNCH]
    assert len(runtime) == 4 and all(a[1] < r[1] < a[2] for a, r in zip(steps, runtime))
    assert 0.4e6 < ps.clock_offset_ns([s for _, s, _ in dev.modules], runtime) < 0.5e6
    t0, t1 = xplane.window_of(recorded)
    moved = [(n, s + off, e + off) for n, s, e in dev.ops]
    assert len(xplane.clip(moved, t0, t1)) == 12 and len(xplane.clip(dev.ops, t0, t1)) == 9
    gaps = xplane.gaps(moved, t0, t1)
    bench = [s for s in recorded.host_spans if s[0].startswith("perfbench.")]
    got = ps.split_gaps(gaps, bench, roots=("perfbench.window",))
    assert sum(got.values()) == sum(e - s for s, e in gaps)
    assert set(got) == {"perfbench.step", ps.IN_STEP}
    # the fixture has no ds.* span: nothing to attribute to
    assert ps.idle_by_leaf_of(recorded) is None


def test_idle_by_leaf_of_a_hand_made_trace():
    ms = 1_000_000
    ops = [("a", 1 * ms, 50 * ms), ("b", 60 * ms, 99 * ms)]        # device clock, 0.2 ms behind
    mods = [("jit_decode_fn(1)", 1 * ms, 50 * ms), ("jit_decode_fn(1)", 60 * ms, 99 * ms)]
    host = [("perfbench.window", 0, 100 * ms), ("ds.serve.step", 1 * ms, 55 * ms),
            ("ds.serve.decode.dispatch", int(1.2 * ms), 3 * ms), ("ds.serve.decode.wait", 3 * ms, 53 * ms),
            ("ds.serve.emit", 53 * ms, int(54.5 * ms)), ("ds.serve.step", 58 * ms, 100 * ms),
            ("ds.serve.decode.dispatch", 59 * ms, int(60.5 * ms))]
    trace = xplane.Trace([xplane.DeviceTrace("/device:TPU:0", ops=ops, modules=mods)], host, [])
    idle, off = ps.idle_by_leaf_of(trace)
    assert off == 0.2 * ms   # the first program seems to start 0.2 ms before its dispatch leaf opened
    # with the runtime's launch events in the trace the bound is the launch, not the leaf's start
    launched = host + [(ps.RUNTIME_LAUNCH, int(1.5 * ms), 2 * ms), (ps.RUNTIME_LAUNCH, int(59.5 * ms), 60 * ms),
                       (ps.RUNTIME_LAUNCH, 70 * ms, 71 * ms)]   # the last one outside every dispatch leaf
    _, off2 = ps.idle_by_leaf_of(xplane.Trace(trace.devices, launched, []))
    assert off2 == 0.5 * ms
    # on the host's clock the device idles 0-1.2, 50.2-60.2 and 99.2-100 ms
    # what lies under no span of the program is filed under the runner's span around it
    assert {k: round(v * 1e3, 6) for k, v in idle.items()} == {
        "perfbench.window": 1.0 + 3.0, ps.IN_STEP: 0.2 + 0.5 + 1.0 + 0.8, "ds.serve.decode.wait": 2.8,
        "ds.serve.emit": 1.5, "ds.serve.decode.dispatch": 1.2}
    from perfbench.context import Context

    ctx = Context(cell={"name": "x"}, config={}, traffic={}, chips=1, peak=None, trace=object())
    ctx.extra["program_spans.idle_by_leaf"] = idle
    assert reader("idle_outside_spans").read(ctx) == pytest.approx(100 * (4.0 + 2.5) / 12.0)


def test_a_missing_trace_directory_gives_none(ring):
    step(ring, 100.0)
    ctx = ctx_for()
    assert ps.idle_by_leaf(ctx) is None            # no device trace in the context at all
    ctx = ctx_for()
    ctx.trace = object()                           # the harness reduced one, but its directory is gone
    assert not os.path.exists(ps.trace_dir("no-such-cell"))
    assert ps.idle_by_leaf(ctx) is None
    assert reader("idle_outside_spans").read(ctx) is None
