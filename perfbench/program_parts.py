"""Device time by model part: the run's own trace joined to the part tables the
program reads from its compiled programs (``deepspeed_tpu.telemetry.parts``:
the ``dspart.*`` scopes PERF.md section 3 lists), for ``metrics/readers/
part_share.py``.

A trace names an operation by its HLO instruction and the line of programs says
which module ran it, so the join is by (module, instruction): each operation
event goes to the program event that contains it, its instruction is looked up
in that module's table, and its SELF time (``xplane.self_times``: a ``while``
spans its body's operations, which are events of their own) is summed by
(part, phase, has_dot) and averaged over the devices that worked in the window.
An event whose module or instruction no table knows counts as having no part
(and is counted as a miss), so the parts and what has none partition the
device's busy time.

A program without the parts module (the parent of the PR that added it) gives
``None``, and the metric is left out of the line.
"""

from __future__ import annotations

import bisect
import collections
import os
import re
import sys
import time
from typing import Dict, List, Optional, Sequence, Tuple

from perfbench import program_spans, xplane

Event = Tuple[str, int, int]
Key = Tuple[Optional[str], str, bool]          # part (None: none), phase, has_dot
# an Entry of the program's table, by position (the program may grow it at the end)
PART, PHASE, HAS_DOT, PARTS_INSIDE, OP_NAME, SOURCE = range(6)


def _log(msg: str) -> None:
    print(f"[perfbench program_parts] {msg}", file=sys.stderr, flush=True)


def program():
    """The program's parts module, or None where the program has none."""
    try:
        from deepspeed_tpu.telemetry import parts
    except ImportError:
        return None
    return parts


def instruction_of(event_name: str) -> str:
    """'%fusion.123 = bf16[..] fusion(...)' or 'fusion.123' -> 'fusion.123':
    an operation event is named after its HLO instruction."""
    return event_name.split(" = ")[0].strip().lstrip("%")


def module_of(event_name: str) -> str:
    """'jit_decode_fn(1234567890)' -> 'jit_decode_fn': an event on the line of
    programs is the HloModule's name, with the program's id behind it."""
    m = re.match(r"[\w.\-]+", event_name.strip())
    return m.group(0) if m else event_name


class Joined:
    """One device's window, joined: nanoseconds of self time."""

    def __init__(self):
        self.by_key: Dict[Key, int] = collections.Counter()
        self.no_part: Dict[Tuple[str, str], int] = collections.Counter()   # (module, instruction) -> ns
        self.collectives: Dict[Tuple[str, str], int] = collections.Counter()
        # (the name `breakdown.device_ops` files the event under, part, has_dot) -> ns
        self.by_label: Dict[Tuple[str, Optional[str], bool], int] = collections.Counter()
        self.mixed_ns = 0
        self.events = 0
        self.missed = 0


def join(ops: Sequence[Event], modules: Sequence[Event], tables: Dict[str, dict], t0: int, t1: int) -> Joined:
    """The operation events of one device inside [t0, t1], each under the
    program event that contains its start, looked up in ``tables`` (``{module:
    {instruction: entry}}``) and summed by self time."""
    mods = sorted((s, e, module_of(n)) for n, s, e in modules)
    starts = [m[0] for m in mods]
    tagged = []
    for name, s, e in xplane.clip(ops, t0, t1):
        i = bisect.bisect_right(starts, s) - 1
        module = mods[i][2] if i >= 0 and s < mods[i][1] else ""
        tagged.append(((module, instruction_of(name), name), s, e))
    out = Joined()
    labels: Dict[str, Tuple[str, bool]] = {}   # event name -> (`breakdown`'s name for it, a collective)
    for (module, instr, name), ns in xplane.self_times(tagged):
        if name not in labels:
            labels[name] = (f"{xplane.categorize(name)}:{xplane.short_name(name)}",
                            bool(xplane.COLLECTIVE_RE.search(name.split(" = ")[0].lower())))
        label, collective = labels[name]
        out.events += 1
        entry = tables.get(module, {}).get(instr)
        if entry is None:
            out.missed += 1
            key: Key = (None, "none", False)
        else:
            key = (entry[PART], entry[PHASE], bool(entry[HAS_DOT]))
            if len(entry[PARTS_INSIDE]) > 1:
                out.mixed_ns += ns
        out.by_key[key] += ns
        out.by_label[(label, key[0], key[2])] += ns
        if key[0] is None:
            out.no_part[(module, instr)] += ns
        if collective:
            out.collectives[(module, instr)] += ns
    return out


def seconds_by_key(devices: Sequence[Joined]) -> Dict[Key, float]:
    """Seconds by (part, phase, has_dot), averaged over the devices."""
    out: Dict[Key, float] = collections.Counter()
    for d in devices:
        for k, ns in d.by_key.items():
            out[k] += ns / 1e9 / len(devices)
    return dict(out)


def share(seconds: Dict[Key, float], base_s: float, parts, phase: Optional[str] = None,
          dot: Optional[bool] = None) -> Optional[float]:
    """Percent of ``base_s`` in the keys whose part starts with one of
    ``parts`` (a part or a prefix of parts: ``attn`` takes ``attn.qkv``; ``""``
    takes every part) or, with ``parts == "none"``, that have no part; with
    ``phase`` / ``dot`` only the keys of that phase / that do or do not hold a
    matmul."""
    if base_s <= 0:
        return None

    def takes(part: Optional[str]) -> bool:
        if parts == "none":
            return part is None
        return part is not None and any(
            p == "" or part == p or part.startswith(p + ".") for p in parts
        )

    got = sum(
        v for (part, ph, has_dot), v in seconds.items()
        if takes(part) and (phase is None or ph == phase) and (dot is None or has_dot == bool(dot))
    )
    return 100.0 * got / base_s


def joined_of(trace: "xplane.Trace", tables: Dict[str, dict]) -> List[Joined]:
    """:func:`join` for each device that worked in the trace's window; []
    where none did or no event found its instruction in a table."""
    t0, t1 = xplane.window_of(trace)
    used = [d for d in trace.devices if xplane.clip(d.ops, t0, t1)]
    joined = [join(d.ops, d.modules, tables, t0, t1) for d in used] if t1 > t0 else []
    return joined if any(j.events - j.missed for j in joined) else []


def by_part(ctx) -> Optional[Dict[Key, float]]:
    """The run's own trace joined to the program's tables. Cached on the
    context; logs the whole table. None without a device trace, without the
    parts module, or where no registered program ran in the window."""
    key = "program_parts.by_part"
    if key in ctx.extra:
        return ctx.extra[key]
    ctx.extra[key] = None
    mod = program()
    if ctx.trace is None or mod is None:
        return None
    path = program_spans.trace_dir(ctx.cell["name"])
    if not os.path.isdir(path):
        return None
    try:
        trace = xplane.load(path)
    except FileNotFoundError:   # the directory holds no xplane file
        return None
    t_build = time.perf_counter()
    tables = mod.tables()
    t_build = time.perf_counter() - t_build
    joined = joined_of(trace, tables)
    if not joined:
        return None
    seconds = seconds_by_key(joined)
    _log_table(ctx, mod, tables, joined, seconds, t_build)
    ctx.extra[key] = seconds
    return seconds


def _log_table(ctx, mod, tables, joined: List[Joined], seconds: Dict[Key, float], t_build: float) -> None:
    n = len(joined)
    busy = ctx.trace.busy_s
    total = sum(seconds.values())
    for module, cost in sorted(mod.costs().items()):
        _log(f"table {module}: {cost['instructions']} instructions from {cost['bytes']} bytes of text "
             f"in {cost['seconds']:.2f} s")
    events, missed = sum(j.events for j in joined), sum(j.missed for j in joined)
    _log(f"tables built in {t_build:.2f} s; {events} operation events in the window, {missed} found in no table "
         f"({100.0 * missed / max(events, 1):.2f}%); self time {total:.4f} s a device, busy {busy:.4f} s")
    phases = ("fwd", "recompute", "bwd", "none")
    _log(f"  {'part':14s} " + " ".join(f"{p:>10s}" for p in phases) + f" {'all':>10s} {'of busy':>8s} {'with dot':>9s}")
    parts_seen = sorted({k[0] for k in seconds if k[0]}) + [None]
    for part in parts_seen:
        row = [sum(v for (p, ph, _), v in seconds.items() if p == part and ph == phase) for phase in phases]
        dot = sum(v for (p, _, d), v in seconds.items() if p == part and d)
        _log(f"  {part or '(none)':14s} " + " ".join(f"{v:10.4f}" for v in row)
             + f" {sum(row):10.4f} {100 * sum(row) / busy if busy else 0:7.2f}% {dot:9.4f}")
    col = [sum(v for (_, ph, _), v in seconds.items() if ph == phase) for phase in phases]
    _log(f"  {'(all)':14s} " + " ".join(f"{v:10.4f}" for v in col) + f" {sum(col):10.4f}")
    mixed = sum(j.mixed_ns for j in joined) / 1e9 / n
    _log(f"in fusions the compiler drew across a part boundary (charged to their root's part): "
         f"{mixed:.4f} s, {100 * mixed / busy if busy else 0:.2f}% of busy")
    by_label: Dict[str, Dict[Tuple[Optional[str], bool], float]] = collections.defaultdict(collections.Counter)
    for j in joined:
        for (label, part, dot), ns in j.by_label.items():
            by_label[label][(part, dot)] += ns / 1e9 / n
    _log("the operations `breakdown.device_ops` lists first, by part (seconds; * holds a matmul or is a kernel):")
    for label, split in sorted(by_label.items(), key=lambda kv: -sum(kv[1].values()))[:8]:
        cells = ", ".join(f"{part or '(none)'}{'*' if dot else ''} {v:.4f}"
                          for (part, dot), v in sorted(split.items(), key=lambda kv: -kv[1]) if v >= 0.0005)
        _log(f"  {sum(split.values()):9.4f} s  {label}: {cells}")
    no_part: Dict[Tuple[str, str], float] = collections.Counter()
    collectives: Dict[Tuple[str, str], float] = collections.Counter()
    for j in joined:
        for k, ns in j.no_part.items():
            no_part[k] += ns / 1e9 / n
        for k, ns in j.collectives.items():
            collectives[k] += ns / 1e9 / n
    _log("the ten longest instructions with no part:")
    for (module, instr), s in sorted(no_part.items(), key=lambda kv: -kv[1])[:10]:
        entry = tables.get(module, {}).get(instr)
        what = "in no table" if entry is None else (entry[OP_NAME] or "(the compiler's own)")
        _log(f"  {s:9.5f} s  {module}:{instr}  {what}")
    if collectives:
        _log(f"collectives (self time: what no compute hid), the longest of {len(collectives)}, by what asked for them:")
        for (module, instr), s in sorted(collectives.items(), key=lambda kv: -kv[1])[:16]:
            entry = tables.get(module, {}).get(instr)
            _log(f"  {s:9.5f} s  {module}:{instr}  "
                 + ("in no table" if entry is None else f"{entry[PART]}/{entry[PHASE]}  {entry[OP_NAME]}  {entry[SOURCE]}"))
