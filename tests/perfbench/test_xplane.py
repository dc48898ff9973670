"""The trace reduction: arithmetic on hand-made intervals, and the walk on a
small xplane file recorded on the v5e (perfbench/tools/record_fixture.py: four
executions of one small program inside a ``perfbench.window`` span)."""

import os

import pytest

from perfbench import xplane

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data", "small.xplane.pb")


def test_union_and_busy_clip_to_the_window():
    ops = [("a", 0, 10), ("b", 5, 12), ("c", 20, 30), ("d", 29, 29)]
    assert xplane.union((s, e) for _, s, e in ops) == [(0, 12), (20, 30)]
    assert xplane.busy_ns(ops, 0, 30) == 22
    assert xplane.busy_ns(ops, 8, 25) == 4 + 5
    assert xplane.gaps(ops, 0, 40) == [(12, 20), (30, 40)]
    assert xplane.gaps(ops, 5, 25) == [(12, 20)]


def test_self_time_takes_children_out_of_wrappers():
    evs = [("while", 0, 100), ("fusion.1", 10, 30), ("copy.2", 30, 50), ("tail", 120, 130)]
    got = dict(xplane.self_times(evs))
    assert got == {"while": 60, "fusion.1": 20, "copy.2": 20, "tail": 10}
    secs = xplane.op_self_seconds(evs, 0, 200)
    assert sum(secs.values()) == pytest.approx(110e-9)


def test_gaps_go_to_the_innermost_open_span():
    spans = [("perfbench.window", 0, 100), ("perfbench.srv.step", 10, 40), ("perfbench.srv.step", 50, 60)]
    got = xplane.attribute_gaps([(12, 20), (44, 48), (52, 54), (150, 160)], spans)
    assert got == {"perfbench.srv.step": 10, "perfbench.window": 4, "none": 10}
    # neither the gaps nor the spans need come in time order
    assert xplane.attribute_gaps([(150, 160), (52, 54), (12, 20), (44, 48)], spans[::-1]) == got


def test_gaps_name_the_programs_leaf_where_the_trace_holds_one():
    """With the program's spans beside the runner's (the default of ``load``),
    ``breakdown.idle_gaps`` names what the host was doing inside a step."""
    spans = [("perfbench.window", 0, 100), ("perfbench.srv.step", 10, 40), ("ds.serve.step", 11, 39),
             ("ds.serve.decode.dispatch", 12, 15), ("ds.serve.decode.wait", 15, 35), ("ds.serve.emit", 35, 38)]
    ops = [("a", 0, 13), ("b", 14, 16), ("c", 30, 36), ("d", 38, 100)]
    trace = xplane.Trace([xplane.DeviceTrace("/device:TPU:0", ops=ops)], spans, [])
    r = xplane.reduce(trace)
    assert {k: round(v * 1e9) for k, v in r.idle_by_span.items()} == {
        "ds.serve.decode.dispatch": 1, "ds.serve.decode.wait": 14, "ds.serve.emit": 2}
    assert xplane.breakdown(r)["idle_gaps"][0] == ["sum:ds.serve.decode.wait", pytest.approx(14e-9)]
    only_runner = xplane.Trace(trace.devices, [s for s in spans if s[0].startswith("perfbench.")], [])
    assert set(xplane.reduce(only_runner).idle_by_span) == {"perfbench.srv.step"}


@pytest.mark.parametrize("name,cat", [
    ("%decode_fn.56 = bf16[8,25,1,64]{3,2,1,0:T(2,128)(2,1)S(1)} custom-call(s32[8,64]{1,0} %copy-done)", "pallas-kernel"),
    ("%checkpoint.9 = (bf16[100,1024,64]{2,1,0:T(8,128)(2,1)}, bf16[100,1024,64]{2,1,0}) custom-call(s32[1]{0} %x)", "pallas-kernel"),
    ("%rematted_computation.9 = (bf16[100,1024,64]{2,1,0:T(8,128)(2,1)S(1)}) custom-call(s32[1]{0:T(128)} %g)", "pallas-kernel"),
    ("%copy.162 = f32[50257,1600]{0,1:T(8,128)} copy(f32[50257,1600]{1,0:T(8,128)} %get-tuple-element.1000)", "copy/layout"),
    ("%convolution_add_fusion.13 = bf16[4,1024,6400]{2,1,0:T(8,128)(2,1)S(1)} fusion(bf16[6400]{0} %d)", "matmul-fusion"),
    ("%all-gather.3 = bf16[16,1600,6400]{2,1,0} all-gather(bf16[4,1600,6400]{2,1,0} %p), dimensions={0}", "collective"),
    ("%reduce-scatter.1 = f32[4,1600]{1,0} reduce-scatter(f32[16,1600]{1,0} %g)", "collective"),
    ("%add_add_fusion.2 = bf16[4,1024,1600]{1,2,0:T(8,128)(2,1)S(1)} fusion(bf16[4,1024,1600]{1,2,0} %copy.3)", "fusion-elementwise"),
    ("%slice.4 = bf16[1,25,16,64]{3,2,1,0} slice(bf16[512,25,16,64]{3,2,1,0} %pool)", "copy/layout"),
])
def test_categories_come_from_head_and_opcode(name, cat):
    assert xplane.categorize(name) == cat


def test_short_name_drops_the_instance_counter():
    assert xplane.short_name("%fusion.123 = bf16[2]{0} fusion(...)") == "fusion"
    assert xplane.short_name("%decode_fn.56 = bf16[8] custom-call(...)") == "decode_fn"
    assert xplane.short_name("copy-done") == "copy-done"


@pytest.fixture(scope="module")
def recorded():
    return xplane.load(DATA)


def test_recorded_trace_has_one_device_and_the_benchmarks_spans(recorded):
    assert [d.name for d in recorded.devices] == ["/device:TPU:0"]
    names = [n for n, _, _ in recorded.host_spans]
    assert names.count("perfbench.window") == 1 and names.count("perfbench.step") == 4
    dev = recorded.devices[0]
    assert len(dev.modules) == 4 and all(n.startswith("jit_small_step") for n, _, _ in dev.modules)
    assert len(dev.ops) == 12   # copy-start, copy-done, fusion per execution


def test_recorded_trace_reduces_to_busy_under_the_window(recorded):
    r = xplane.reduce(recorded)
    # the device's clock runs about a quarter of a millisecond behind the host's
    # in this file, so the first of the four executions falls before the span
    assert r.n_devices == 1 and len(r.module_durations["jit_small_step(2960185764617555699)"]) == 3
    assert 0 < r.busy_s < r.window_s and r.window_s == pytest.approx(0.0132, rel=0.01)
    assert r.busy_s == pytest.approx(sum(r.op_seconds.values()))   # no wrappers here: busy is the self times
    assert sum(r.idle_by_span.values()) == pytest.approx(r.window_s - r.busy_s)
    assert r.seconds_in_category("fusion-elementwise") == pytest.approx(r.seconds_matching(r"^%fusion"))
    b = xplane.breakdown(r)
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert b["device_ops"][0][0] == "fusion-elementwise:fusion"
    assert all(k.startswith(("sum:", "longest:")) for k, _ in b["idle_gaps"])


def test_load_keeps_the_runners_and_the_programs_spans_by_default(tmp_path):
    """A trace recorded here (host planes only: the CPU has no device plane)
    with a span of each: ``load`` keeps ``perfbench.*`` and ``ds.*`` and drops
    the rest. The fixture from the chip holds no ``ds.*`` span (it was recorded
    around a bare program), so it reads the same under either set."""
    import jax
    import jax.numpy as jnp

    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("perfbench.window"):
        with jax.profiler.TraceAnnotation("ds.serve.decode.wait"):
            with jax.profiler.TraceAnnotation("someone.elses.span"):
                jnp.ones((8,)).sum().block_until_ready()
    jax.profiler.stop_trace()
    names = {n for n, _, _ in xplane.load(str(tmp_path)).host_spans}
    assert names == {"perfbench.window", "ds.serve.decode.wait"}
    assert {n for n, _, _ in xplane.load(str(tmp_path), span_prefixes=("perfbench.",)).host_spans} == {"perfbench.window"}
    assert xplane.load(DATA).host_spans == xplane.load(DATA, span_prefixes=("perfbench.",)).host_spans


def test_window_falls_back_to_the_device_ops_extent():
    t = xplane.Trace([xplane.DeviceTrace("/device:TPU:0", ops=[("a", 5, 10), ("b", 30, 50)])], [], [])
    assert xplane.window_of(t) == (5, 50)
    r = xplane.reduce(t)
    assert r.busy_s == pytest.approx(25e-9) and r.window_s == pytest.approx(45e-9)
    assert xplane.reduce(xplane.Trace([], [], [])) is None
