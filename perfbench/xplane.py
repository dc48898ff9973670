"""From a profiler trace (``*.xplane.pb``) to numbers: device busy and idle
time, time per device operation, time per executed program, collective time
on the compute line, and the idle gaps attributed to the host span that was
open. The walk (self time by interval nesting, one line at a time) was copied
from ``benchmarks/profile_attr.py`` and grown here; the original is listed in
PERF.md for deletion.

The arithmetic works on plain tuples ``(name, start_ns, end_ns)`` so that the
tests can drive it with hand-made intervals; :func:`load` turns an xplane file
into those tuples with nothing but ``jax.profiler.ProfileData``.

On a TPU plane the profiler writes one line of leaf operations ("XLA Ops"),
one of executed programs ("XLA Modules") and one of steps; asynchronous copies
have a line of their own. Busy time is the union of the intervals on the
operations line: an operation in flight on the async line with nothing on the
compute line is the device waiting, not working.
"""

from __future__ import annotations

import collections
import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Event = Tuple[str, int, int]  # name, start_ns, end_ns

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
COLLECTIVE_RE = re.compile(r"all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute|all_gather|all_reduce|reduce_scatter|psum")


@dataclass
class DeviceTrace:
    name: str
    ops: List[Event] = field(default_factory=list)
    modules: List[Event] = field(default_factory=list)
    other_lines: Dict[str, int] = field(default_factory=dict)  # line name -> events


@dataclass
class Trace:
    devices: List[DeviceTrace]
    host_spans: List[Event]          # TraceAnnotations of the benchmark (perfbench.*) and of the program (ds.*)
    plane_names: List[str]


def short_name(name: str) -> str:
    """'%fusion.123 = bf16[..] fusion(...)' or 'fusion.123' -> 'fusion';
    a trailing numeric suffix is the compiler's instance counter."""
    head = name.split(" = ")[0].strip().lstrip("%")
    return re.sub(r"([._]\d+)+$", "", head)


def categorize(name: str) -> str:
    """Bucket an operation by its name and opcode only (the operand list of the
    long form names producers, which would misfile the consumer)."""
    head = name.split(" = ")[0].lower()
    m = re.search(r"\}\)?\s+([a-z][a-z_-]*)\(", name)  # the opcode follows the result type (a tuple's ends in ')')
    opcode = (m.group(1) if m else "").lower()
    n = head + " " + opcode
    if COLLECTIVE_RE.search(n):
        return "collective"
    # a Mosaic kernel is a custom-call named after the function that holds the
    # pallas_call (decode_fn, chunk_fn; checkpoint / rematted_computation /
    # closed_call for the flash kernels inside a remat block): the opcode
    # decides, whatever the head says
    if opcode == "custom-call" or "pallas" in n or "mosaic" in n or "flash" in n:
        return "pallas-kernel"
    if "checkpoint" in n or "rematted" in n or "closed_call" in n:
        return "remat/call-wrapper"
    if "fusion" in n and ("dot" in n or "conv" in n or "matmul" in n):
        return "matmul-fusion"
    if head.lstrip("%").startswith("dot") or "dot_general" in n or opcode == "dot" or "einsum" in n:
        return "matmul"
    if "copy" in n or "reshape" in n or "transpose" in n or "bitcast" in n or "slice" in n and "dynamic" not in n or opcode in ("bitcast", "copy", "copy-start", "copy-done", "slice"):
        return "copy/layout"
    if "gather" in n or "scatter" in n or "dynamic-update" in n or "dynamic_update" in n or "dynamic-slice" in n or "dynamic_slice" in n:
        return "gather/scatter"
    if "infeed" in n or "outfeed" in n or opcode.startswith("host") or "host" in head:
        return "host-transfer"
    if "while" in n or "conditional" in n or opcode == "call":
        return "control-flow"
    if "fusion" in n:
        return "fusion-elementwise"
    return "other"


def self_times(events: Iterable[Event]):
    """Per-event self time by interval nesting on one line: a ``while`` or
    ``call`` wrapper spans its body operations, which are events of their own
    on the same line. Yields (name, self_ns)."""
    evs = sorted(((s, e, n) for n, s, e in events), key=lambda t: (t[0], -t[1]))
    stack: list = []  # [start, end, name, child_ns]

    def pop():
        st = stack.pop()
        out = (st[2], max(0, (st[1] - st[0]) - st[3]))
        if stack:
            stack[-1][3] += max(0, min(st[1], stack[-1][1]) - st[0])
        return out

    for s, e, name in evs:
        while stack and s >= stack[-1][1]:
            yield pop()
        stack.append([s, e, name, 0])
    while stack:
        yield pop()


def union(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(events: Iterable[Event], t0: int, t1: int) -> List[Event]:
    return [(n, max(s, t0), min(e, t1)) for n, s, e in events if e > t0 and s < t1]


def busy_ns(ops: Sequence[Event], t0: int, t1: int) -> int:
    return sum(e - s for s, e in union((s, e) for _, s, e in clip(ops, t0, t1)))


def gaps(ops: Sequence[Event], t0: int, t1: int) -> List[Tuple[int, int]]:
    """Idle intervals of the operations line inside [t0, t1]."""
    out, cur = [], t0
    for s, e in union((s, e) for _, s, e in clip(ops, t0, t1)):
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if t1 > cur:
        out.append((cur, t1))
    return out


def attribute_gaps(gap_list, host_spans: Sequence[Event]) -> Dict[str, int]:
    """Idle nanoseconds by the innermost span open at each gap's midpoint
    ('none' where no span was open)."""
    out: Dict[str, int] = collections.Counter()
    # one pass over gaps and spans in time order: a traced window holds some
    # 10^5 gaps and, with the program's leaves, some 10^3 spans
    spans = sorted(host_spans, key=lambda x: x[1])
    open_spans: List[Event] = []
    i = 0
    for s, e in sorted(gap_list, key=lambda g: g[0] + g[1]):
        mid = (s + e) // 2
        while i < len(spans) and spans[i][1] <= mid:
            open_spans.append(spans[i])
            i += 1
        open_spans = [sp for sp in open_spans if sp[2] > mid]
        best = min(open_spans, key=lambda sp: sp[2] - sp[1], default=None)
        out[best[0] if best else "none"] += e - s
    return dict(out)


def op_self_seconds(ops: Sequence[Event], t0: int, t1: int) -> Dict[str, float]:
    """Seconds of self time by full event name inside [t0, t1]."""
    out: Dict[str, float] = collections.Counter()
    for name, ns in self_times(clip(ops, t0, t1)):
        out[name] += ns / 1e9
    return dict(out)


def window_of(trace: Trace, span_name: str = "perfbench.window") -> Tuple[int, int]:
    """The traced window: the benchmark's own span if the trace holds it,
    else the extent of the device operations."""
    for n, s, e in trace.host_spans:
        if n == span_name:
            return s, e
    starts = [s for d in trace.devices for _, s, _ in d.ops]
    ends = [e for d in trace.devices for _, _, e in d.ops]
    if not starts:
        return 0, 0
    return min(starts), max(ends)


def load(path: str, span_prefixes: Sequence[str] = ("perfbench.", "ds."), device_re: str = r"^/device:TPU:\d+$") -> Trace:
    """Read the newest ``*.xplane.pb`` under ``path`` (or ``path`` itself)."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        files = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True), key=os.path.getmtime)
        if not files:
            raise FileNotFoundError(f"no *.xplane.pb under {path}")
        path = files[-1]
    pd = ProfileData.from_file(path)
    devices, host, names = [], [], []
    for plane in pd.planes:
        names.append(plane.name)
        if re.match(device_re, plane.name):
            dev = DeviceTrace(plane.name)
            for line in plane.lines:
                evs = [(ev.name, int(ev.start_ns), int(ev.start_ns + ev.duration_ns)) for ev in line.events]
                if line.name == OPS_LINE:
                    dev.ops = evs
                elif line.name == MODULES_LINE:
                    dev.modules = evs
                else:
                    dev.other_lines[line.name] = len(evs)
            devices.append(dev)
        else:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(tuple(span_prefixes)):
                        host.append((ev.name, int(ev.start_ns), int(ev.start_ns + ev.duration_ns)))
    return Trace(devices, host, names)


@dataclass
class Reduced:
    """What the readers see of a trace: seconds, averaged over the devices
    that ran an operation in the window."""
    window_s: float
    busy_s: float
    n_devices: int
    op_seconds: Dict[str, float]          # full name -> self seconds (mean over devices)
    module_durations: Dict[str, List[float]]  # program name -> device seconds per execution
    idle_by_span: Dict[str, float]
    longest_gaps: List[Tuple[str, float]]
    line_names: Dict[str, int]

    def seconds_matching(self, pattern: str) -> float:
        rx = re.compile(pattern)
        return sum(v for k, v in self.op_seconds.items() if rx.search(k))

    def seconds_in_category(self, cat: str) -> float:
        return sum(v for k, v in self.op_seconds.items() if categorize(k) == cat)


def reduce(trace: Trace) -> Optional[Reduced]:
    t0, t1 = window_of(trace)
    used = [d for d in trace.devices if clip(d.ops, t0, t1)]
    if not used or t1 <= t0:
        return None
    n = len(used)
    op_s: Dict[str, float] = collections.Counter()
    mods: Dict[str, List[float]] = collections.defaultdict(list)
    idle: Dict[str, float] = collections.Counter()
    longest: List[Tuple[str, float]] = []
    busy = 0
    lines: Dict[str, int] = collections.Counter()
    for d in used:
        busy += busy_ns(d.ops, t0, t1)
        for k, v in op_self_seconds(d.ops, t0, t1).items():
            op_s[k] += v / n
        for name, s, e in d.modules:
            if s >= t0 and e <= t1:
                mods[name].append((e - s) / 1e9)
        g = gaps(d.ops, t0, t1)
        for k, v in attribute_gaps(g, trace.host_spans).items():
            idle[k] += v / 1e9 / n
        for s, e in sorted(g, key=lambda x: x[0] - x[1])[:5]:
            longest.append((next(iter(attribute_gaps([(s, e)], trace.host_spans))), (e - s) / 1e9))
        lines[OPS_LINE] += len(d.ops)
        lines[MODULES_LINE] += len(d.modules)
        for k, v in d.other_lines.items():
            lines[k] += v
    longest.sort(key=lambda x: -x[1])
    return Reduced(
        window_s=(t1 - t0) / 1e9, busy_s=busy / 1e9 / n, n_devices=n, op_seconds=dict(op_s),
        module_durations=dict(mods), idle_by_span=dict(idle), longest_gaps=longest[:5], line_names=dict(lines),
    )


def breakdown(r: Reduced) -> dict:
    """The contract's ``breakdown``: the ten device operations that took most
    time (category:short name), and idle time by open span plus the longest
    single gaps."""
    by_short: Dict[str, float] = collections.Counter()
    for k, v in r.op_seconds.items():
        by_short[f"{categorize(k)}:{short_name(k)}"] += v
    ops = sorted(by_short.items(), key=lambda kv: -kv[1])[:10]
    idle = [[f"sum:{k}", v] for k, v in sorted(r.idle_by_span.items(), key=lambda kv: -kv[1])][:5]
    idle += [[f"longest:{k}", v] for k, v in r.longest_gaps][: 10 - len(idle)]
    return {"device_ops": [[k, v] for k, v in ops], "idle_gaps": idle}
