"""perfbench/program_parts.py and the ``part_share`` reader: the join on
hand-made intervals and a hand-made table, the walk on the xplane file
recorded on the v5e (tests/perfbench/data/small.xplane.pb), each of the
reader's arguments, the 23 metrics' spec files against the tables the
``gpt2-tiny`` cells' own programs give, and what a program without the parts
module, or a run without a trace, gives: nothing. Nothing here is a device
number."""

import math
import os
from types import SimpleNamespace

import pytest

from perfbench import program_parts as pp, xplane
from perfbench.context import Context
from perfbench.manifest import Manifest

from . import tiny

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data", "small.xplane.pb")
NEW = ("part_", "recompute_share.", "dense_matmul_share.")
# PR 36's 23 entries, in the order they were appended; since PR 47 the readings several cells take the same way
# are one entry each (`.kx` and `.ms4` of two, `.doc`, `.kx` and `.ms4` of the unattributed share: `.backlog`), so 19
PR_36 = (["part_mlp_share.train", "part_attn_proj_share.train", "part_attn_core_share.train", "part_head_share.train",
          "part_optim_share.train", "recompute_share.train", "dense_matmul_share.train"]
         + [f"part_{p}_share.{c}" for p in ("weights", "head") for c in ("chat", "loaded", "doc")]
         + ["part_moe_route_share.backlog", "part_attn_share.backlog"]
         + [f"part_unattributed_share.{c}" for c in ("train", "chat", "loaded", "backlog")])


def entry(part, phase="none", dot=False, inside=(), op_name="x", source=""):
    return (part, phase, dot, tuple(inside), op_name, source)


TABLES = {
    "jit_train_step": {
        "while.1": entry(None, "fwd", op_name="jit(train_step)/jvp()/while"),
        "fusion.1": entry("mlp", "fwd", True, ("mlp", "norm")),
        "copy.2": entry("attn.core", "recompute"),
        "fusion.9": entry("optim"),
        "all-gather.3": entry("attn.qkv", "fwd", op_name="jit(train_step)/jvp()/dspart.attn.qkv/dot_general",
                              source="zero/partitioning.py:88"),
    },
    "jit_decode_fn": {"fusion.1": entry("head", dot=True), "fusion.2": entry(None, op_name="")},
}


def test_names_as_the_trace_writes_them():
    assert pp.instruction_of("%fusion.123 = bf16[8,64]{1,0:T(8,128)(2,1)} fusion(bf16[8,64]{1,0} %p)") == "fusion.123"
    assert pp.instruction_of("copy-start.4") == "copy-start.4"
    assert pp.module_of("jit_decode_fn(2960185764617555699)") == "jit_decode_fn"
    assert pp.module_of("jit_train_step") == "jit_train_step"


def test_join_nests_under_a_while_and_keeps_two_programs_apart():
    modules = [("jit_train_step(7)", 0, 200), ("jit_decode_fn(9)", 300, 400)]
    ops = [
        ("%while.1 = (s32[]) while(...)", 0, 100), ("%fusion.1 = bf16[8] fusion(...)", 10, 30),
        ("%copy.2 = bf16[8] copy(...)", 30, 50), ("%all-gather.3 = bf16[8] all-gather(...)", 100, 110),
        ("%fusion.9 = f32[8] fusion(...)", 120, 160),
        ("%unknown.5 = f32[8] fusion(...)", 160, 170),          # in no table
        ("%fusion.1 = f32[8,512] fusion(...)", 300, 340),        # the other program's fusion.1
        ("%fusion.2 = f32[8] fusion(...)", 340, 350),
        ("%fusion.1 = f32[8] fusion(...)", 500, 510),            # under no program event
        ("%fusion.9 = f32[8] fusion(...)", 900, 950),            # outside the window
    ]
    j = pp.join(ops, modules, TABLES, 0, 600)
    assert dict(j.by_key) == {
        (None, "fwd", False): 60,            # the while's own time: its body's events are taken out
        ("mlp", "fwd", True): 20, ("attn.core", "recompute", False): 20, ("attn.qkv", "fwd", False): 10,
        ("optim", "none", False): 40, ("head", "none", True): 40,
        (None, "none", False): 10 + 10 + 10,   # in no table, no part, under no program
    }
    assert (j.events, j.missed) == (9, 2)
    assert j.mixed_ns == 20
    assert dict(j.no_part) == {("jit_train_step", "while.1"): 60, ("jit_train_step", "unknown.5"): 10,
                               ("jit_decode_fn", "fusion.2"): 10, ("", "fusion.1"): 10}
    assert dict(j.collectives) == {("jit_train_step", "all-gather.3"): 10}
    assert j.by_label["fusion-elementwise:fusion", "mlp", True] == 20      # under `breakdown`'s own name for it
    assert j.by_label["fusion-elementwise:fusion", "head", True] == 40
    assert j.by_label["control-flow:while", None, False] == 60
    assert sum(j.by_key.values()) == xplane.busy_ns(ops, 0, 600)


def test_two_devices_are_averaged_and_one_that_was_idle_is_left_out():
    modules = [("jit_train_step(7)", 0, 100)]
    busy = lambda n: [("%fusion.9 = f32[8] fusion(...)", 0, n)]     # noqa: E731
    trace = xplane.Trace(
        [xplane.DeviceTrace("/device:TPU:0", ops=busy(40), modules=modules),
         xplane.DeviceTrace("/device:TPU:1", ops=busy(20), modules=modules),
         xplane.DeviceTrace("/device:TPU:2")],
        [("perfbench.window", 0, 100)], [])
    joined = pp.joined_of(trace, TABLES)
    assert len(joined) == 2
    assert pp.seconds_by_key(joined) == {("optim", "none", False): pytest.approx(30e-9)}
    assert pp.joined_of(trace, {"jit_other": {}}) == []              # no event found its instruction
    assert pp.joined_of(xplane.Trace([], [], []), TABLES) == []


def test_the_walk_on_a_trace_recorded_on_the_chip():
    """The fixture's four executions of ``jit_small_step``: every operation
    event lies under a program event whose name is the module's, and is named
    after its instruction."""
    trace = xplane.load(DATA)
    (dev,) = trace.devices
    assert {pp.module_of(n) for n, _, _ in dev.modules} == {"jit_small_step"}
    instrs = {pp.instruction_of(n) for n, _, _ in dev.ops}
    assert instrs == {"copy-start", "copy-done", "fusion.7"}
    tables = {"jit_small_step": {"fusion.7": entry("mlp", dot=True), "copy-start": entry("mlp"),
                                 "copy-done": entry(None, op_name="")}}
    (j,) = pp.joined_of(trace, tables)
    assert j.events >= 9 and j.missed == 0      # (the window's span cuts the first execution off)
    seconds = pp.seconds_by_key([j])
    busy = xplane.reduce(trace).busy_s
    assert sum(seconds.values()) == pytest.approx(busy, rel=1e-6)
    assert pp.share(seconds, busy, ["mlp"], dot=True) > 99.0
    assert pp.share(seconds, busy, ["mlp"]) + pp.share(seconds, busy, "none") == pytest.approx(100.0, abs=1e-6)


SECONDS = {
    ("mlp", "fwd", True): 2.0, ("mlp", "bwd", True): 3.0, ("mlp", "recompute", True): 1.0, ("mlp", "bwd", False): 0.5,
    ("attn.qkv", "fwd", True): 1.0, ("attn.core", "recompute", True): 0.5, ("optim", "none", False): 1.0,
    (None, "none", False): 0.75, (None, "bwd", False): 0.25,
}


@pytest.mark.parametrize("args, want", [
    (dict(parts=["mlp"]), 65.0),
    (dict(parts=["attn"]), 15.0),                     # a prefix of parts
    (dict(parts=["attn.qkv", "optim"]), 20.0),
    (dict(parts=[""]), 90.0),                         # every part
    (dict(parts="none"), 10.0),
    (dict(parts=[""], phase="recompute"), 15.0),
    (dict(parts=["mlp"], phase="bwd", dot=True), 30.0),
    (dict(parts=["mlp"], dot=False), 5.0),
    (dict(parts=["head"]), 0.0),
])
def test_part_share_with_each_argument(args, want):
    assert pp.share(SECONDS, 10.0, **args) == pytest.approx(want)
    ctx = Context(cell={"name": "x"}, config={}, traffic={}, chips=1, peak=None)
    ctx.trace = SimpleNamespace(busy_s=10.0)
    ctx.extra["program_parts.by_part"] = SECONDS
    assert Manifest(REPO).reader("part_share").read(ctx, of="busy", **args) == pytest.approx(want)


def test_nothing_without_a_trace_a_busy_device_or_the_parts_module(monkeypatch):
    rd = Manifest(REPO).reader("part_share")
    ctx = Context(cell={"name": "no-such-cell"}, config={}, traffic={}, chips=1, peak=None)
    assert rd.read(ctx, parts=["mlp"], of="busy") is None             # no trace
    ctx = Context(cell={"name": "no-such-cell"}, config={}, traffic={}, chips=1, peak=None)
    ctx.trace = SimpleNamespace(busy_s=1.0)
    assert rd.read(ctx, parts=["mlp"], of="busy") is None             # no trace directory
    assert pp.share(SECONDS, 0.0, ["mlp"]) is None
    ctx = Context(cell={"name": "x"}, config={}, traffic={}, chips=1, peak=None)
    ctx.trace = SimpleNamespace(busy_s=1.0)
    monkeypatch.setattr(pp, "program", lambda: None)                 # the parent of the PR that added the module
    assert pp.by_part(ctx) is None


def test_the_manifest_holds_the_23_metrics_and_validates(table):
    m = table
    names = [e["name"] for e in m.doc["per_layer"]]
    first = names.index(PR_36[0])       # found by name: a later PR appends behind them, or before
    assert names[first:first + len(PR_36)] == PR_36                       # where they were appended, nothing moved
    new = [e for e in m.doc["per_layer"] if e["name"].startswith(NEW)]
    assert all(e["source"] == "device_trace" and e["unit"] == "%" for e in new)
    assert {m.metric_spec(e["name"])["reader"] for e in new} == {"part_share"}
    for cell in m.doc["workloads"]:
        mine = [e["name"] for e in m.metrics_for(cell["name"], "per_layer") if e["name"].startswith(NEW)]
        assert sum(n.startswith("part_unattributed_share.") for n in mine) == 1, cell["name"]


# -- the specs against the tables of the tiny cells' own programs ------------------------

@pytest.fixture(scope="module")
def tiny_cells(tmp_path_factory):
    """cell -> (the tiny manifest, seconds by key): each kind of cell run for a
    moment on the CPU, then one event a leaf instruction of its programs'
    tables, 1 us each, back to back under one program event a module: what a
    device trace of those programs would hold, the times apart."""
    from deepspeed_tpu.telemetry import parts
    from perfbench import run

    manifest = tiny.make(tmp_path_factory.mktemp("bench"))
    out, alive, runner_of = {}, [], manifest.runner

    def keeping(name):
        """The runner's module, its Runner kept past the run: the registry
        holds a program's engine weakly, and the tables are asked for after."""
        mod = runner_of(name)

        def make(*args, **kwargs):
            alive.append(mod.Runner(*args, **kwargs))
            return alive[-1]

        return SimpleNamespace(Runner=make)

    manifest.runner = keeping
    for cell in ("train-xl-l16-1chip", "serve-xl-chat-open", "serve-xl-doc-batch"):
        parts.clear()
        run.run_cell(manifest, cell, 2**31 + 7, 0.3, False, require_tpu=False)
        assert parts.registered() and not parts._built        # an untraced run builds no table
        tables = parts.tables()
        devices = []
        for shift in (0, 1):   # two devices, the second a little behind
            ops, modules, t = [], [], 1000 * shift
            for module, table in tables.items():
                start = t
                for instr in table:
                    ops.append((f"%{instr} = f32[8]{{0}} fusion(...)", t, t + 1000))
                    t += 1000
                modules.append((f"{module}(12345)", start, t))
            devices.append(xplane.DeviceTrace(f"/device:TPU:{shift}", ops=ops, modules=modules))
        trace = xplane.Trace(devices, [("perfbench.window", 0, 10**12)], [])
        joined = pp.joined_of(trace, tables)
        assert sum(j.missed for j in joined) == 0
        out[cell] = (manifest, pp.seconds_by_key(joined), xplane.reduce(trace).busy_s)
    parts.clear()
    return out


@pytest.mark.parametrize("cell", ["train-xl-l16-1chip", "serve-xl-chat-open", "serve-xl-doc-batch"])
def test_a_tiny_cell_reports_every_new_metric_and_its_parts_partition_the_busy_time(tiny_cells, cell):
    manifest, seconds, busy = tiny_cells[cell]
    ctx = Context(cell={"name": cell}, config={}, traffic={}, chips=1, peak=None)
    ctx.trace = SimpleNamespace(busy_s=busy)
    ctx.extra["program_parts.by_part"] = seconds
    mine = [m["name"] for m in manifest.metrics_for(cell, "per_layer") if m["name"].startswith(NEW)]
    kept = {"train-xl-l16-1chip": {f"part_{p}_share.train" for p in ("mlp", "attn_proj", "attn_core", "head", "optim", "unattributed")}
            | {"recompute_share.train", "dense_matmul_share.train"},
            "serve-xl-chat-open": {f"part_{p}_share.chat" for p in ("weights", "head", "unattributed")},
            "serve-xl-doc-batch": {"part_weights_share.doc", "part_head_share.doc", "part_unattributed_share.backlog"}}[cell]
    assert kept <= set(mine)        # at least these, by name: a later PR may bring the cell a part share more
    got = {}
    for name in mine:
        spec = manifest.metric_spec(name)
        got[name] = manifest.reader(spec["reader"]).read(ctx, **spec["args"])
        assert got[name] is not None and math.isfinite(got[name]) and 0.0 <= got[name] <= 100.0, name
    none = next(v for k, v in got.items() if k.startswith("part_unattributed_share."))
    every = pp.share(seconds, busy, [""])
    assert none + every == pytest.approx(100.0, abs=0.5)
    if cell.startswith("train"):
        named = sum(got[f"part_{p}_share.train"] for p in ("mlp", "attn_proj", "attn_core", "head", "optim"))
        rest = pp.share(seconds, busy, ["embed", "norm"])            # the parts no metric of the cell reads
        assert named + rest + none == pytest.approx(100.0, abs=0.5)
        assert got["recompute_share.train"] > 0 and got["dense_matmul_share.train"] > 0
        assert all(got[f"part_{p}_share.train"] > 0 for p in ("mlp", "attn_proj", "attn_core", "head", "optim"))
    else:
        assert got[f"part_weights_share.{'chat' if 'chat' in cell else 'doc'}"] > 0
        assert got[f"part_head_share.{'chat' if 'chat' in cell else 'doc'}"] > 0


def test_the_log_names_parts_passes_the_unnamed_and_the_collectives(capsys):
    modules = [("jit_train_step(7)", 0, 200)]
    ops = [("%while.1 = (s32[]) while(...)", 0, 100), ("%fusion.1 = bf16[8] fusion(...)", 10, 30),
           ("%all-gather.3 = bf16[8] all-gather(...)", 100, 110), ("%fusion.9 = f32[8] fusion(...)", 120, 160),
           ("%unknown.5 = f32[8] fusion(...)", 160, 170)]
    trace = xplane.Trace([xplane.DeviceTrace("/device:TPU:0", ops=ops, modules=modules)],
                         [("perfbench.window", 0, 200)], [])
    joined = pp.joined_of(trace, TABLES)
    ctx = SimpleNamespace(trace=xplane.reduce(trace))
    mod = SimpleNamespace(costs=lambda: {"jit_train_step": {"instructions": 5, "bytes": 1234, "seconds": 0.01}})
    pp._log_table(ctx, mod, TABLES, joined, pp.seconds_by_key(joined), 0.01)
    err = capsys.readouterr().err
    assert "5 operation events in the window, 1 found in no table (20.00%)" in err
    assert "jit_train_step:unknown.5  in no table" in err
    assert "jit_train_step:all-gather.3  attn.qkv/fwd  jit(train_step)/jvp()/dspart.attn.qkv/dot_general  zero/partitioning.py:88" in err
    rows = {line.split()[2]: line.split()[3:] for line in err.splitlines() if len(line.split()) == 10}
    assert set(rows) == {"mlp", "attn.qkv", "optim", "(none)"}        # the table: a row a part
    assert rows["mlp"][5] == "12.50%" and rows["(none)"][5] == "56.25%"     # 20 and 60 + 20 + 10 of 160 ns
