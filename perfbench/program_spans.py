"""What the program's own spans say (``deepspeed_tpu.telemetry.spans``: the
``ds.*`` names PERF.md section 3 lists), for the readers under
``metrics/readers``.

Host-clock readings come from the program's ring and cover the whole window
(``ctx.window``; the ring's clock is ``time.perf_counter``, the benchmark's).
The device-side reading loads the run's own xplane a second time with the
``ds.`` prefix, puts device and host on one clock (:func:`clock_offset_ns`) and
splits every idle gap of the device over the leaf spans it overlaps
(:func:`idle_by_leaf`), by intersection: the gaps are about 3 ms long and most
leaves are shorter, so a gap's midpoint would hand all of it to one of them.

A program without the spans module (the parent of the PR that added it) gives
``None`` everywhere, and the metric is left out of the line.
"""

from __future__ import annotations

import bisect
import collections
import os
import re
import sys
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from perfbench import arith, xplane

Event = Tuple[str, int, int]
ROOTS = ("ds.serve.step", "ds.train.batch")   # parents: time under them alone is under no leaf
IN_STEP, OUTSIDE = "(in a step, under no leaf)", "(outside every ds.* span)"
# the leaves that launch exactly one program each, and that program's name in the trace
LAUNCHES = (("decode_fn|verify_fn", "ds.serve.decode.dispatch"), ("train_step", "ds.train.dispatch"))
# the runtime's own host event around handing a program to the device (one per device): where
# the trace has it inside a launching leaf, the launch is known more closely than the leaf's start
RUNTIME_LAUNCH = "TpuLoadedExecutable::ExecuteLaunch"
MAX_OFFSET_NS = 20_000_000   # two clocks of one machine: 0.3-0.5 ms on one chip, over 1 ms on a four-chip host


def _log(msg: str) -> None:
    print(f"[perfbench program_spans] {msg}", file=sys.stderr, flush=True)


def program():
    """The program's spans module, or None where the program has none."""
    try:
        from deepspeed_tpu.telemetry import spans
    except ImportError:
        return None
    return spans


# -- host clock: the ring -------------------------------------------------------

def records_in(span: Tuple[float, float]) -> Optional[list]:
    """Ring records that lie wholly inside ``span`` (benchmark clock)."""
    mod = program()
    if mod is None:
        return None
    return [r for r in mod.snapshot(since=span[0]) if r[1] >= span[0] and r[2] <= span[1]]


def own_durations(recs, name: str, minus_suffix: Optional[str] = None, min_attr: Optional[dict] = None):
    """Durations of the spans called ``name``; with ``minus_suffix``, less the
    spans nested in each whose name ends in it (the waits on the device); with
    ``min_attr`` only the spans whose attributes reach those values."""
    out = []
    waits = [r for r in recs if minus_suffix and r[0].endswith(minus_suffix)]
    for n, t0, t1, attrs in recs:
        if n != name or any(attrs.get(k, 0) < v for k, v in (min_attr or {}).items()):
            continue
        out.append((t1 - t0) - sum(w[2] - w[1] for w in waits if w[1] >= t0 and w[2] <= t1))
    return out


def _covered(intervals, within=None) -> float:
    """Length of the union of ``intervals``, clipped to the union ``within``."""
    merged = xplane.union(intervals)
    if within is None:
        return sum(e - s for s, e in merged)
    return sum(arith.overlap(s, e, ws, we) for s, e in merged for ws, we in within)


def log_phases(ctx, longer_than: float = 0.2) -> None:
    """The set-up phases that took a while, once per run: which program was
    traced, lowered, compiled or fetched from the cache, and for how long."""
    mod = program()
    if mod is None or ctx.extra.get("program_spans.phases_logged"):
        return
    ctx.extra["program_spans.phases_logged"] = True
    for name, t0, t1, attrs in mod.phases():
        if t1 - t0 >= longer_than and t1 <= ctx.window[0]:
            _log(f"phase {name:18s} {t1 - t0:8.2f} s  {attrs}")


def phase_seconds(names: Sequence[str], before: float, minus_nested: Sequence[str] = ()) -> Optional[float]:
    """Seconds covered by the phases called one of ``names`` that ended before
    ``before``, less what the phases called one of ``minus_nested`` cover of
    them. Covered, not summed: jax's trace events nest (a jitted function that
    calls jitted functions)."""
    mod = program()
    if mod is None:
        return None
    recs = [p for p in mod.phases() if p[2] <= before]
    mine = xplane.union((p[1], p[2]) for p in recs if p[0] in names)
    return _covered(mine) - _covered([(p[1], p[2]) for p in recs if p[0] in minus_nested], within=mine)


# -- one clock for the device and the host ----------------------------------------

def clock_offset_ns(program_starts: Iterable[int], launch_spans: Sequence[Event],
                    max_ns: Optional[int] = None) -> int:
    """How far the device's clock runs behind the host's in one trace, from
    below: no device program may start before the host span that launched it.
    ``launch_spans`` each launch exactly one of the programs whose starts are
    given (a decode step's dispatch leaf and the decode program): each program
    start is held against the latest span that opened no more than ``max_ns``
    after it, by default half the shortest distance between two launches (a
    program that seems earlier than that belongs to the launch before) and at
    most MAX_OFFSET_NS. The largest amount by which a program seems to start
    early is the estimate (0 if none does); it falls short of the true offset
    by the shortest launch latency."""
    starts = sorted(s for _, s, _ in launch_spans)
    if max_ns is None:
        max_ns = min([MAX_OFFSET_NS] + [(b - a) // 2 for a, b in zip(starts, starts[1:])])
    worst, j = 0, 0
    for m in sorted(program_starts):
        while j < len(starts) and starts[j] <= m + max_ns:
            j += 1
        if j and starts[j - 1] > m:
            worst = max(worst, starts[j - 1] - m)
    return worst


def innermost_segments(spans: Sequence[Event]) -> List[Event]:
    """Disjoint pieces of the time the spans cover, each named after the
    innermost span open in it. Spans of one thread nest or are disjoint."""
    out: List[Event] = []
    stack: List[Event] = []
    pos = 0

    def emit(name, a, b):
        if b > a:
            out.append((name, a, b))

    for name, s, e in sorted(spans, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][2] <= s:
            top = stack.pop()
            emit(top[0], pos, top[2])
            pos = top[2]
        if stack:
            emit(stack[-1][0], pos, s)
            e = min(e, stack[-1][2])
        stack.append((name, s, e))
        pos = s
    while stack:
        top = stack.pop()
        emit(top[0], pos, top[2])
        pos = max(pos, top[2])
    return out


def split_gaps(gap_list: Sequence[Tuple[int, int]], spans: Sequence[Event],
               roots: Sequence[str] = ROOTS) -> Dict[str, int]:
    """Idle nanoseconds by the innermost leaf span each part of each gap lies
    under, by intersection. What lies under a root alone goes to IN_STEP, what
    lies under nothing to OUTSIDE."""
    segs = innermost_segments(spans)
    out: Dict[str, int] = collections.Counter()
    i = 0
    for g0, g1 in sorted(gap_list):
        while i < len(segs) and segs[i][2] <= g0:
            i += 1
        covered, j = 0, i
        while j < len(segs) and segs[j][1] < g1:
            name, s, e = segs[j]
            ov = min(e, g1) - max(s, g0)
            if ov > 0:
                out[IN_STEP if name in roots else name] += ov
                covered += ov
            j += 1
        if g1 - g0 > covered:
            out[OUTSIDE] += (g1 - g0) - covered
    return dict(out)


def idle_by_leaf_of(trace: "xplane.Trace") -> Optional[Tuple[Dict[str, float], int]]:
    """(idle seconds by innermost span, a leaf of the program or not, averaged
    over the devices that worked in the window; the clock offset applied, ns) of a trace loaded with the ``ds.``
    and ``perfbench.`` prefixes (and RUNTIME_LAUNCH, where the offset is to be
    tight); None where no device worked in the window."""
    t0, t1 = xplane.window_of(trace)
    used = [d for d in trace.devices if xplane.clip(d.ops, t0, t1)]
    ds = [s for s in trace.host_spans if s[0].startswith("ds.")]
    # the runner's own spans lie around the program's: idle time under them alone is
    # still under no leaf of the program, but the table can say whose it is
    bench = [s for s in trace.host_spans if s[0].startswith("perfbench.")]
    if not used or t1 <= t0 or not ds:
        return None
    runtime = sorted(s for n, s, _ in trace.host_spans if n == RUNTIME_LAUNCH)
    offset = 0
    for prog, leaf in LAUNCHES:
        launches = []
        for n, s, e in ds:
            if n == leaf:
                i = bisect.bisect_left(runtime, s)
                launches.append((n, runtime[i] if i < len(runtime) and runtime[i] < e else s, e))
        starts = (s for d in used for n, s, _ in d.modules if re.search(prog, n))
        offset = max(offset, clock_offset_ns(starts, launches))
    idle: Dict[str, float] = collections.Counter()
    for d in used:
        # the window is a host span: the device's events move onto the host's clock
        ops = [(n, s + offset, e + offset) for n, s, e in d.ops]
        for k, v in split_gaps(xplane.gaps(ops, t0, t1), ds + bench).items():
            idle[k] += v / 1e9 / len(used)
    return dict(idle), offset


def trace_dir(cell_name: str) -> str:
    """Where ``run.py`` writes a cell's trace when it is given no other place."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.path.join(root, ".perfbench_trace", cell_name)


def idle_by_leaf(ctx) -> Optional[Dict[str, float]]:
    """The run's own trace, read again with the program's spans. Cached on the
    context; logs the table. None without a device trace or without spans."""
    key = "program_spans.idle_by_leaf"
    if key in ctx.extra:
        return ctx.extra[key]
    ctx.extra[key] = None
    if ctx.trace is None or program() is None:
        return None
    path = trace_dir(ctx.cell["name"])
    if not os.path.isdir(path):
        return None
    try:
        trace = xplane.load(path, span_prefixes=("ds.", "perfbench.", RUNTIME_LAUNCH))
    except FileNotFoundError:   # the directory holds no xplane file
        return None
    got = idle_by_leaf_of(trace)
    if got is None:
        return None
    idle, offset = got
    total = sum(idle.values())
    _log(f"clock offset applied {offset / 1e6:.3f} ms; device idle {total:.4f} s, by leaf:")
    for k, v in sorted(idle.items(), key=lambda kv: -kv[1]):
        _log(f"  {k:32s} {v:9.5f} s  {100 * v / total if total else 0:5.1f}%")
    log_profiler_cost(ctx)
    ctx.extra[key] = idle
    return idle


def log_profiler_cost(ctx) -> None:
    """The host time of a step with and without a profiler session open: the
    same run, the part of the window before the trace and the traced part."""
    recs = records_in(ctx.window)
    if not recs or ctx.traced is None:
        return
    for root in ROOTS:
        off = own_durations([r for r in recs if r[2] <= ctx.traced[0]], root, ".wait")
        on = own_durations([r for r in recs if r[1] >= ctx.traced[0] and r[2] <= ctx.traced[1]], root, ".wait")
        if off and on:
            _log(f"{root} host p50: {arith.quantile(off, 0.5) * 1e3:.3f} ms with no profiler session "
                 f"({len(off)} steps), {arith.quantile(on, 0.5) * 1e3:.3f} ms under one ({len(on)} steps)")
