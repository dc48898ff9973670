"""One row a program the serving loop launched, from the run's own trace: the
launch number its leaf span carries, what it carried, its time on the device
and when the host had what it sampled. For the readers ``launch_time`` and
``launch_hold``.

The program numbers every call of a compiled serving program and says so on
the leaf span that makes the call (``launch``, ``kind``, ``rows``, ``tokens``
on ``ds.serve.decode.dispatch`` or a ``ds.serve.launch`` of the call's own);
the leaves that read a program name it: ``flight`` on ``ds.serve.decode.wait``
and ``ds.serve.emit`` (the step program they fetch and emit), ``firsts`` on
``ds.serve.emit`` (the programs whose first token it hands out) and ``launch``
on a synchronous ``ds.serve.*.wait`` (docs/OBSERVABILITY.md). The runtime
numbers its side: a call's launch event and its program event on the device
share ``xplane.CallId``. The two meet in time on the calling thread: the
runtime's event that asks for the launch (:data:`EXECUTE`) lies inside the
leaf, and where the launch itself (``xplane.LAUNCH``) runs on a thread of the
runtime's, after the leaf has closed, the event around it (:data:`ISSUED`)
names the asking event by a flow id. So a row is joined by the number and by
the call's ids, never by order and never by a program's name.

A program whose leaf carries no number (the parent of the PR that brought the
numbers) gives no row, and every reader then gives nothing.
"""

from __future__ import annotations

import glob
import os
import re
import sys
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

from perfbench import arith, program_spans, xplane

ATTRS = ("launch", "kind", "rows", "tokens", "flight", "firsts")
STEP_KINDS = ("plain", "mixed", "verify")     # the programs a ``flight`` names: they carry decode rows
EXECUTE = "tpu::System::Execute"              # on the calling thread, inside the call: flow id ``_p``
ISSUED = "tpu::System::Execute=>IssueSequencedEvent"   # around a LAUNCH on the runtime's thread: flow id ``_c``
SERVING = re.compile(r"prefill_fn|decode_fn|verify_fn")  # for the log's count alone: the join knows no name
MIN_PROGRAMS = 8

Annotated = Tuple[str, int, int, Dict[str, object]]   # name, start_ns, end_ns, the stats of ATTRS it carries


def _log(msg: str) -> None:
    print(f"[perfbench launches] {msg}", file=sys.stderr, flush=True)


@dataclass
class Launch:
    number: int
    kind: str
    rows: int
    tokens: int
    asked: int                       # the leaf's start (host clock, ns)
    module: Optional[str] = None     # the device program paired with it
    launched: Optional[int] = None   # the runtime's launch of the call (host clock)
    start: Optional[int] = None      # the program on the device, moved onto the host's clock
    end: Optional[int] = None
    read: Optional[int] = None       # the host has its outputs: the start of the emit that names it ``flight``
    first: Optional[int] = None      # the host has its first token: the emit that names it in ``firsts``, or
    #                                  the END of the synchronous wait that names it ``launch``


@dataclass
class Loaded:
    trace: "xplane.Trace"            # device programs with their calls, the launches; ``host_spans`` without stats
    spans: List[Annotated] = field(default_factory=list)
    asked: Dict["xplane.CallId", int] = field(default_factory=dict)   # call -> start of the EXECUTE that asked for it


def load(path: str, device_re: str = r"^/device:TPU:\d+$") -> Loaded:
    """Read the newest ``*.xplane.pb`` under ``path`` (or ``path`` itself):
    the line of executed programs of every device, the ``ds.serve.*`` and
    ``perfbench.window`` annotations with the stats of :data:`ATTRS`, the
    launches and, for a launch that ran on a thread of the runtime's, the
    event on the calling thread that asked for it."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        files = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True), key=os.path.getmtime)
        if not files:
            raise FileNotFoundError(f"no *.xplane.pb under {path}")
        path = files[-1]
    out = Loaded(xplane.Trace([], [], []))
    executes: Dict[int, int] = {}                 # flow id -> start of the EXECUTE event
    issued: List[Tuple[int, int, int, int]] = []  # (line, start, end, flow id) of the ISSUED events
    launched: List[Tuple[int, int, "xplane.CallId"]] = []   # (line, start, call) of the LAUNCH events
    for plane in ProfileData.from_file(path).planes:
        out.trace.plane_names.append(plane.name)
        if re.match(device_re, plane.name):
            dev = xplane.DeviceTrace(plane.name)
            ordinal = int((re.search(r"\d+$", plane.name) or [0])[0])
            for line in plane.lines:
                if line.name == xplane.MODULES_LINE:
                    for ev in line.events:
                        dev.modules.append((ev.name, int(ev.start_ns), int(ev.start_ns + ev.duration_ns)))
                        dev.module_calls.append(xplane._call_id(ev, ordinal))
            out.trace.devices.append(dev)
            continue
        for n_line, line in enumerate(plane.lines):
            for ev in line.events:
                name = ev.name
                if name.startswith("ds.serve.") or name == "perfbench.window":
                    s = int(ev.start_ns)
                    e = s + int(ev.duration_ns)
                    out.trace.host_spans.append((name, s, e))
                    out.spans.append((name, s, e, {k: v for k, v in ev.stats if k in ATTRS}))
                elif name == xplane.LAUNCH:
                    call = xplane._call_id(ev)
                    if call is not None:
                        out.trace.launches[call] = int(ev.start_ns)
                        launched.append((n_line, int(ev.start_ns), call))
                elif name == EXECUTE:
                    flow = dict(ev.stats).get("_p")
                    if flow is not None:
                        executes[flow] = int(ev.start_ns)
                elif name == ISSUED:
                    flow = dict(ev.stats).get("_c")
                    if flow is not None:
                        issued.append((n_line, int(ev.start_ns), int(ev.start_ns + ev.duration_ns), flow))
    for n_line, t, call in launched:
        for m_line, s, e, flow in issued:
            if m_line == n_line and s <= t <= e and flow in executes:
                out.asked[call] = executes[flow]
                break
    return out


def rows_of(loaded: Loaded) -> Tuple[List[Launch], dict]:
    """(one :class:`Launch` a leaf span that carries a number, by number; the
    counts of the join). A leaf whose call the trace does not hold (launched
    before the session opened, or its program ended after it closed) keeps its
    row without a program."""
    trace = loaded.trace
    rows: Dict[int, Launch] = {}
    leaves = []
    for name, s, e, a in loaded.spans:
        if "launch" in a and "kind" in a:
            n = int(a["launch"])
            rows[n] = Launch(n, str(a["kind"]), int(a.get("rows", 0)), int(a.get("tokens", 0)), s)
            leaves.append((s, e, n))
    leaves.sort()
    offset = program_spans.clock_offset_ns(program_spans.launch_pairs(trace))
    t0, t1 = xplane.window_of(trace)
    stats = {"programs": 0, "joined": 0, "doubled": 0, "offset_ns": offset, "leaves": len(leaves)}
    for d in trace.devices:
        for (name, s, e), call in zip(d.modules, d.module_calls):
            at = trace.launches.get(call)
            if at is None:
                continue
            serving = bool(SERVING.search(name)) and s + offset >= t0 and e + offset <= t1
            stats["programs"] += serving
            asked = loaded.asked.get(call, at)
            mine = [n for ls, le, n in leaves if ls <= asked <= le]
            if len(mine) != 1:
                continue
            row = rows[mine[0]]
            if row.module is not None:
                stats["doubled"] += 1
                continue
            row.module, row.launched, row.start, row.end = name, at, s + offset, e + offset
            stats["joined"] += serving
    for name, s, e, a in loaded.spans:
        if name == "ds.serve.emit":
            if a.get("flight") in rows:
                rows[a["flight"]].read = s
            for n in str(a.get("firsts", "")).split(","):
                if n and int(n) in rows:
                    rows[int(n)].first = s
        elif name.endswith(".wait") and a.get("launch") in rows:
            rows[a["launch"]].first = e
    return [rows[n] for n in sorted(rows)], stats


def in_window(rows: Sequence[Launch], window: Tuple[int, int]) -> List[Launch]:
    """The rows whose program ran wholly inside ``window`` (host clock, ns)."""
    return [r for r in rows if r.start is not None and r.start >= window[0] and r.end <= window[1]]


def device_seconds(rows: Sequence[Launch], kind: str) -> List[float]:
    return [(r.end - r.start) / 1e9 for r in rows if r.kind == kind]


def holds(rows: Sequence[Launch], of: str) -> List[float]:
    """Seconds from a program's end on the device to the host's having what it
    sampled: ``of`` ``token``, a step program's rows (its emit's start);
    ``first``, a first token (the emit that hands it out, or the end of the
    synchronous wait). Read too long by at most the shortest launch latency of
    the trace: the clock offset is an estimate from below."""
    if of == "token":
        return [(r.read - r.end) / 1e9 for r in rows if r.kind in STEP_KINDS and r.read is not None]
    if of == "first":
        return [(r.first - r.end) / 1e9 for r in rows if r.first is not None]
    raise ValueError(f"launch_hold of {of!r}: 'token' or 'first'")


def quantile(values: Sequence[float], q: float, least: int = MIN_PROGRAMS) -> Optional[float]:
    """The quantile of at least ``least`` values, else nothing."""
    return arith.quantile(list(values), float(q)) if len(values) >= int(least) else None


def rows(ctx) -> Optional[List[Launch]]:
    """The traced window's rows of the run's own trace, read once a run and
    kept on the context; logs the join's counts and the split of a first
    token's way. None without a device trace, without the program's spans or
    where no leaf carries a number."""
    key = "launches.rows"
    if key in ctx.extra:
        return ctx.extra[key]
    ctx.extra[key] = None
    if ctx.trace is None or program_spans.program() is None:
        return None
    path = program_spans.trace_dir(ctx.cell["name"])
    if not os.path.isdir(path):
        return None
    try:
        loaded = load(path)
    except FileNotFoundError:
        return None
    all_rows, stats = rows_of(loaded)
    if not all_rows:
        _log("no leaf span of the trace carries a launch number: no row")
        return None
    mine = in_window(all_rows, xplane.window_of(loaded.trace))
    _log(f"{stats['joined']} of {stats['programs']} serving programs in the traced window whose launch the trace holds "
         f"joined to one launch number each, {stats['doubled']} numbers met by a second program "
         f"({stats['leaves']} numbered leaves in the trace, clock offset {stats['offset_ns'] / 1e6:.3f} ms)")
    for line in summary(mine):
        _log(line)
    ctx.extra[key] = mine
    return mine


def summary(rows: Sequence[Launch]) -> List[str]:
    """What the rows say, in milliseconds at the median: a line a kind
    (programs, what they carried, asked-to-start, device time, end-to-read)
    and, for the programs that sampled a first token, a line a kind of the
    way from the launch to the emission."""
    def med(vals, scale=1e3):
        return f"{arith.quantile(vals, 0.5) * scale:8.3f}" if vals else "       -"

    def way(mine):
        return (f"asked->start {med([(r.start - r.asked) / 1e9 for r in mine])} ms  device "
                f"{med([(r.end - r.start) / 1e9 for r in mine])} ms")

    out = []
    for kind in sorted({r.kind for r in rows}):
        mine = [r for r in rows if r.kind == kind]
        out.append(f"  {kind:8s} n={len(mine):4d}  rows p50 {med([r.rows for r in mine], 1)}  tokens p50 "
                   f"{med([r.tokens for r in mine], 1)}  {way(mine)}  end->read "
                   f"{med([(r.read - r.end) / 1e9 for r in mine if r.read is not None])} ms")
    firsts = [r for r in rows if r.first is not None]
    if firsts:
        carried = sum(r.tokens > 0 for r in rows)
        out.append(f"  first tokens n={len(firsts)}: {carried} programs carried prompt tokens, "
                   f"{carried / len(firsts):.2f} a first token; the program that sampled it:")
        for kind in sorted({r.kind for r in firsts}):
            mine = [r for r in firsts if r.kind == kind]
            out.append(f"    {kind:8s} n={len(mine):4d}  {way(mine)}  end->emission {med(holds(mine, 'first'))} ms")
    return out
