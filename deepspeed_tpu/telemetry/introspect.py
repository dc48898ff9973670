"""The HLO instruction grammar and the per-chip peak table.

What is left of the ISSUE-5 introspection plane after its cost/MFU analyzer
went (it took operand shapes from the instruction line, which the installed
jax no longer prints, and so read zero; the benchmark computes MFU and
roofline shares from shapes, ``perfbench/kernel_costs.py``). Two things stay:

- the **instruction grammar** over post-optimization HLO text that the
  analysis plane shares: :func:`parse_instruction`, :func:`operand_shapes`,
  :func:`shape_bytes`, ``DTYPE_BYTES`` (Engines A and D,
  ``analysis/hlo_rules.py`` / ``collective_rules.py``) and
  :class:`NamedInstruction` / :func:`parse_named_instruction` /
  :func:`split_computations` / :func:`entry_computation` (Engine E,
  ``analysis/memory_rules.py``), kept in one place so the HLO readers cannot
  drift;
- the **peak table** (:data:`PEAK_TABLE`, :func:`chip_peak`) that
  ``chip_smoke.py``, ``env_report.py`` and the comms logger read.

Known limit: :func:`operand_shapes` reads typed shapes inside the call
parens, and jax 0.9.0 prints operands by name only (``dot(%a.1, %b.1)``), so
on the installed grammar it returns ``[]`` (ROADMAP D14).
"""

from __future__ import annotations

import functools
import math
import re
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

# ---------------------------------------------------------------------------
# per-chip peak table
# ---------------------------------------------------------------------------

# bf16 matmul peak flop/s, HBM bytes/s and ICI bytes/s by device kind. The
# v5e row is Google Cloud's documentation, "TPU v5e" system architecture:
# 197 TFLOP/s bf16, 819 GB/s HBM, 1,600 Gbit/s of interchip interconnect a
# chip (2.0e11 B/s, all links), the same figures as ``perfbench/peaks.py``.
# The other rows' ICI figures have not been checked against a publication;
# nothing has run on those chips. ``chip_smoke.py``, ``env_report.py`` and the
# comm logger read the table through :func:`chip_peak`. Keys match
# ``jax.Device.device_kind`` substrings, checked longest first so "TPU v5p"
# wins over "TPU v5".
PEAK_TABLE: Dict[str, Dict[str, float]] = {
    "TPU v4": dict(peak_flops=275e12, hbm_bytes_per_s=1.23e12, ici_bytes_per_s=4.8e10),
    "TPU v5 lite": dict(peak_flops=197e12, hbm_bytes_per_s=8.19e11, ici_bytes_per_s=2.0e11),
    "TPU v5e": dict(peak_flops=197e12, hbm_bytes_per_s=8.19e11, ici_bytes_per_s=2.0e11),
    "TPU v5p": dict(peak_flops=459e12, hbm_bytes_per_s=2.765e12, ici_bytes_per_s=9.0e10),
    "TPU v6e": dict(peak_flops=918e12, hbm_bytes_per_s=1.64e12, ici_bytes_per_s=4.0e10),
    "TPU v6 lite": dict(peak_flops=918e12, hbm_bytes_per_s=1.64e12, ici_bytes_per_s=4.0e10),
}

# nominal entry for the CPU test mesh only (``device_kind == "cpu"``), flagged
# ``source="fallback"``. An accelerator that is not in the table is an error,
# never this entry.
CPU_FALLBACK = dict(peak_flops=2.0e11, hbm_bytes_per_s=5.0e10, ici_bytes_per_s=2.0e10)


@dataclass(frozen=True)
class PeakSpec:
    """Resolved peak capabilities of the chip the program runs on."""

    device_kind: str
    peak_flops: float
    hbm_bytes_per_s: float
    ici_bytes_per_s: float
    source: str  # "table" | "fallback" | "override"

    def to_dict(self) -> Dict[str, Any]:
        return {
            "device_kind": self.device_kind,
            "peak_flops": self.peak_flops,
            "hbm_bytes_per_s": self.hbm_bytes_per_s,
            "ici_bytes_per_s": self.ici_bytes_per_s,
            "source": self.source,
        }


def chip_peak(device_kind: Optional[str] = None,
              peak_flops_override: float = 0.0) -> PeakSpec:
    """Look up the peak entry for ``device_kind`` (default: first jax device).

    The CPU host gets the nominal ``CPU_FALLBACK`` entry, flagged
    ``source="fallback"``; any other kind that is not in the table raises — a
    utilization computed against another chip's peak is worse than none.
    ``peak_flops_override`` replaces the flops column only.
    """
    if device_kind is None:
        import jax

        device_kind = jax.devices()[0].device_kind
    kind = str(device_kind).lower()
    for key in sorted(PEAK_TABLE, key=len, reverse=True):
        if key.lower() in kind:
            entry, source = PEAK_TABLE[key], "table"
            break
    else:
        if kind != "cpu":
            raise ValueError(
                f"chip_peak: device_kind {device_kind!r} is not in PEAK_TABLE "
                f"({sorted(PEAK_TABLE)}); add its published peaks there"
            )
        entry, source = CPU_FALLBACK, "fallback"
    flops = float(peak_flops_override) or entry["peak_flops"]
    if peak_flops_override:
        source = "override"
    return PeakSpec(
        device_kind=str(device_kind),
        peak_flops=flops,
        hbm_bytes_per_s=entry["hbm_bytes_per_s"],
        ici_bytes_per_s=entry["ici_bytes_per_s"],
        source=source,
    )


# ---------------------------------------------------------------------------
# HLO instruction grammar (shared by the analysis plane's HLO readers)
# ---------------------------------------------------------------------------

DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
}

# one HLO instruction: "%name = type[dims]{layout} opcode("
_INSTR = re.compile(
    r"=\s*(?P<dtype>[\w]+)\[(?P<dims>[0-9,]*)\][^\s]*\s*"
    r"(?P<op>[\w\-]+)\("
)
# tuple-typed result: "%name = (type[dims]{l}, ...) opcode(" — the form the
# latency-hiding scheduler emits for async collective starts (all-gather-start
# returns (operand-alias, result)); tuple element shapes never nest parens
_INSTR_TUPLE = re.compile(
    r"=\s*\((?P<shapes>[^()]*)\)\s*(?P<op>[\w\-]+)\("
)
_SHAPE = re.compile(r"(\w+)\[([0-9,]*)\]")


def _numel(dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n


def shape_bytes(dtype: str, dims: str) -> int:
    return _numel(dims) * DTYPE_BYTES.get(dtype, 4)


def operand_shapes(line: str) -> List[tuple]:
    """Typed operand shapes inside the instruction's call parens."""
    start = line.find("(", line.find("= "))
    if start < 0:
        return []
    depth, end = 0, len(line)
    for i in range(start, len(line)):
        if line[i] == "(":
            depth += 1
        elif line[i] == ")":
            depth -= 1
            if depth == 0:
                end = i
                break
    return _SHAPE.findall(line[start:end])


def split_computations(txt: str) -> Dict[str, List[str]]:
    """Computation name → its instruction lines (HLO text is one flat file
    of ``%comp (params) -> type { ... }`` blocks plus the ENTRY block; its
    name is what :func:`entry_computation` returns)."""
    comps: Dict[str, List[str]] = {}
    cur = "_module"
    # header: "[ENTRY ]%name (params...) -> type {" — params can nest
    # parens (tuple-typed args), so key on the "-> ... {" tail and the
    # absence of an "=" (instructions always assign)
    header = re.compile(r"^\s*(?:ENTRY\s+)?%?([\w.\-]+)\s*\(")
    for line in txt.splitlines():
        stripped = line.rstrip()
        hm = header.match(line)
        if (
            hm
            and stripped.endswith("{")
            and "->" in stripped
            and " = " not in stripped
        ):
            cur = hm.group(1)
            comps.setdefault(cur, [])
            continue
        comps.setdefault(cur, []).append(line)
    return comps


def parse_instruction(line: str):
    """One HLO instruction line → ``(op, result_bytes, tuple_shapes)``.

    ``tuple_shapes`` is the parsed ``[(dtype, dims), ...]`` list for
    tuple-typed results (async collective starts) and None for plain
    results; ``result_bytes`` is the result size (largest tuple element
    for tuples, 0 for unknown dtypes). Returns ``(None, 0, None)`` for
    non-instruction lines."""
    m = _INSTR.search(line)
    if m:
        dtype, dims = m.group("dtype"), m.group("dims")
        nbytes = shape_bytes(dtype, dims) if dtype in DTYPE_BYTES else 0
        return m.group("op"), nbytes, None
    tm = _INSTR_TUPLE.search(line)
    if tm:
        shapes = _SHAPE.findall(tm.group("shapes"))
        sizes = [
            shape_bytes(dt, dd) for dt, dd in shapes if dt in DTYPE_BYTES
        ]
        return tm.group("op"), (max(sizes) if sizes else 0), shapes
    return None, 0, None


_NAMED_INSTR = re.compile(
    r"^\s*(?P<root>ROOT\s+)?%(?P<name>[\w.\-]+)\s*=\s*(?P<rest>.*)$"
)
_RESTYPE_PLAIN = re.compile(r"[\w]+\[[0-9,]*\](\{[^}]*\})?(\S*)")
_OP_AFTER_TYPE = re.compile(r"\s*(?P<op>[\w\-]+)\(")


@dataclass
class NamedInstruction:
    """One parsed HLO instruction with buffer-level detail (ISSUE 9).

    The dsmem liveness walker (``analysis/memory_rules.py``) needs more than
    :func:`parse_instruction`'s (op, bytes) view: the instruction NAME (the
    def in the def-use chain), the operand names (the uses), the typed
    result shapes (tuple elements are separate buffers), the attribute tail
    (``index=``/``body=``/``metadata=``) and whether this is the ROOT.
    Shares the byte/shape grammar above so the HLO readers (Engine A/D
    rules, Engine E liveness) cannot drift."""

    name: str
    op: str
    result_shapes: List[tuple]   # [(dtype, dims), ...]; >1 for tuple results
    result_bytes: int            # sum over known-dtype result shapes
    operands: List[str]          # %names referenced inside the call parens
    attrs: str                   # text after the call parens (index=, body=)
    is_root: bool
    line: str


def parse_named_instruction(line: str) -> Optional[NamedInstruction]:
    """One HLO instruction line → :class:`NamedInstruction`, or None for
    non-instruction lines (headers, braces, comments)."""
    m = _NAMED_INSTR.match(line.strip())
    if not m:
        return None
    name, rest = m.group("name"), m.group("rest")
    if rest.startswith("("):
        depth = 0
        end = -1
        for i, ch in enumerate(rest):
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    end = i
                    break
        if end < 0:
            return None
        restype, tail = rest[: end + 1], rest[end + 1:]
    else:
        tm = _RESTYPE_PLAIN.match(rest)
        if not tm:
            return None
        restype, tail = rest[: tm.end()], rest[tm.end():]
    om = _OP_AFTER_TYPE.match(tail)
    if not om:
        return None
    call_start = tail.find("(")
    depth, call_end = 0, len(tail)
    for i in range(call_start, len(tail)):
        if tail[i] == "(":
            depth += 1
        elif tail[i] == ")":
            depth -= 1
            if depth == 0:
                call_end = i
                break
    shapes = _SHAPE.findall(restype)
    return NamedInstruction(
        name=name,
        op=om.group("op"),
        result_shapes=shapes,
        result_bytes=sum(
            shape_bytes(dt, dd) for dt, dd in shapes if dt in DTYPE_BYTES
        ),
        operands=re.findall(r"%([\w.\-]+)", tail[call_start:call_end]),
        attrs=tail[call_end + 1:],
        is_root=m.group("root") is not None,
        line=line,
    )


OP_NAME = re.compile(r'op_name="([^"]*)"')   # an instruction's metadata names the primitive that made it


@functools.lru_cache(maxsize=1)
def instructions_by_computation(hlo_text: str) -> Dict[str, List[NamedInstruction]]:
    """Computation name → its parsed instructions, in the text's order. (The
    backend's own config closes an instruction's line and is its longest
    part: it is cut off before the line is parsed.) The last text's answer is
    kept, for the readers to share and not to change: a compiled train step's
    census of collectives and of the optimizer's traffic each read its
    megabyte of text during set-up, a tenth of a second a parse."""
    out: Dict[str, List[NamedInstruction]] = {}
    for comp, lines in split_computations(hlo_text).items():
        for line in lines:
            if " = " in line:
                ni = parse_named_instruction(line.split(", backend_config=", 1)[0])
                if ni is not None:
                    out.setdefault(comp, []).append(ni)
    return out


def entry_computation(txt: str) -> Optional[str]:
    """Name of the ENTRY computation in ``txt`` (None if absent)."""
    m = re.search(r"^\s*ENTRY\s+%?([\w.\-]+)\s*\(", txt, re.M)
    return m.group(1) if m else None


# ---------------------------------------------------------------------------
# the collectives of a program's loops
# ---------------------------------------------------------------------------

COLLECTIVE_KINDS = ("all_gather", "reduce_scatter", "all_reduce", "all_to_all")
_COLLECTIVE_OPS = {k.replace("_", "-"): k for k in COLLECTIVE_KINDS}
_CALLED = re.compile(r"(?:calls|body|condition|to_apply)=%?([\w.\-]+)")
DOT_OPS = frozenset(("dot", "convolution", "ragged-dot"))   # what multiplies matrices; a dot is a convolution on a TPU
MOSAIC = 'custom_call_target="tpu_custom_call"'           # a Pallas kernel, in a custom-call's attributes
_MOVES = frozenset(("bitcast", "copy", "copy-start", "copy-done", "get-tuple-element", "tuple", "reshape"))
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")


@dataclass(frozen=True)
class LoopCollective:
    """One collective of a loop body, as the optimised text has it."""

    name: str            # the loop body's instruction: the collective, or the fusion that runs it
    kind: str            # one of COLLECTIVE_KINDS
    shapes: tuple        # result shapes, ((dtype, (dims...)), ...): an all-reduce of a tuple has several
    nbytes: int          # bytes of the result on one device
    op_name: str         # the metadata's op_name ("" where the compiler gave none)
    overlapped: bool     # started and awaited by separate instructions: compute may run between them
    between: int = 0     # instructions that hold a matmul or are a Mosaic kernel, from its start to its awaiting end
    carried: bool = False   # nothing of this iteration reads the result: it leaves in the loop's state, for a later one

    @property
    def ahead(self) -> bool:
        """Whether the schedule gives the collective compute to hide behind:
        an async pair with two or more products between its two ends, or
        with one where the result is ``carried``. ONE product between the
        ends of a pair whose result this iteration reads is the product just
        before the one that needs it, the scheduler's only choice, and on the
        chip it did not cover a weight's gather (``c_fc_w``'s pairs waited
        0.10 and 0.08 s of a traced 4.7 behind the attention's 1600 x 1600
        output product); a pair asked for on behalf of the NEXT iteration is
        put over whichever product the scheduler likes, and its one was long
        enough every time (under 0.001 s each). No product between (a pair
        over elementwise work alone) waited half its length; a synchronous
        collective is waited for where it stands, whoever reads it (0.15 and
        0.07 s). PERF.md section 6, PRs 40 and 51."""
        return self.overlapped and self.between >= (1 if self.carried else 2)

    def carries(self, tokens: int) -> bool:
        """Whether a result is shaped like the activations of ``tokens``
        tokens (the GLOBAL batch times the sequence): the dimensions before
        the last multiply to ``tokens``
        (``[16,1024,1600]`` and an all-to-all's ``[4,4,1024,1600]`` alike). A
        weight with as many rows as the batch has tokens reads the same."""
        return any(len(dims) >= 2 and math.prod(dims[:-1]) == tokens for _, dims in self.shapes)


def loop_collectives(hlo_text: str) -> List[LoopCollective]:
    """The all-gathers, reduce-scatters, all-reduces and all-to-alls that run
    once an iteration of a ``while`` loop of one optimised HLO module (a scan
    over layers, forward and backward; nested loops and conditionals
    included), each counted ONCE however the backend spells it:

    - a plain instruction (``all-gather``, ``all-to-all``, ...): nothing
      overlaps it;
    - an ``X-start`` / ``X-done`` pair: the start counts, with the larger
      element of its (operand, result) tuple;
    - the TPU's fused forms: a ``kCustom`` fusion named
      ``async-collective-start`` counts for the collective its computation
      holds, the ``async-collective-done`` that awaits it and the
      ``async_collective_fusion`` computation of the work that runs meanwhile
      repeat that collective and are passed over; a fusion that calls an
      ``all-reduce-scatter`` computation is the reduce-scatter (an all-reduce
      and each device's slice of it), with the fusion's result: the shard.

    Each also says where the schedule put it (the text of a compiled module is
    in scheduled order): ``between`` counts the instructions of the loop body
    that multiply matrices (a ``dot`` or ``convolution``, bare or inside a
    fusion, or a Mosaic kernel) from an async pair's start to the end that
    awaits it (a ``-done`` that takes the start; ``async-collective-done[.N]``
    for ``async-collective-start[.N]``), and ``carried`` says that the result
    reaches the body's root through moves alone (bitcasts, copies, tuples):
    it was asked for on behalf of a later iteration. See
    :attr:`LoopCollective.ahead`."""
    comps = instructions_by_computation(hlo_text)
    multiplies_memo: Dict[str, bool] = {}

    def multiplies(ni: NamedInstruction) -> bool:
        if ni.op in DOT_OPS or (ni.op == "custom-call" and MOSAIC in ni.attrs):
            return True
        return ni.op == "fusion" and any(holds_dot(c) for c in _CALLED.findall(ni.attrs))

    def holds_dot(comp: str) -> bool:
        if comp not in multiplies_memo:
            multiplies_memo[comp] = False
            multiplies_memo[comp] = any(multiplies(ni) for ni in comps.get(comp, ()))
        return multiplies_memo[comp]

    readers_memo: Dict[str, Dict[str, List[NamedInstruction]]] = {}

    def readers_in(comp: str) -> Dict[str, List[NamedInstruction]]:
        """Instruction name → the instructions of ``comp`` that take it."""
        if comp not in readers_memo:
            readers = readers_memo[comp] = {}
            for x in comps[comp]:
                for o in set(x.operands):
                    readers.setdefault(o, []).append(x)
        return readers_memo[comp]

    def placed(comp: str, start: int) -> Tuple[int, bool]:
        """(``between``, ``carried``) of the collective that instruction ``start`` of ``comp`` starts."""
        body = comps[comp]
        ni = body[start]
        end = start
        if ni.op.endswith("-start"):
            done = ni.op.removesuffix("start") + "done"
            end = next((j for j in range(start + 1, len(body))
                        if body[j].op == done and ni.name in body[j].operands), start)
        elif ni.name.startswith("async-collective-start"):
            done = ni.name.replace("start", "done", 1)
            end = next((j for j in range(start + 1, len(body)) if body[j].name == done), start)
        between = sum(multiplies(x) for x in body[start + 1:end])
        readers = readers_in(comp)
        seen, todo, carried = set(), [body[end].name], False
        while todo:
            for x in readers.get(todo.pop(), ()):
                if x.name in seen:
                    continue
                seen.add(x.name)
                if x.is_root:
                    carried = True
                elif x.op in _MOVES:
                    todo.append(x.name)
                else:
                    return between, False
        return between, carried

    def called(ni: NamedInstruction) -> List[str]:
        out = _CALLED.findall(ni.attrs)
        for group in _BRANCHES.findall(ni.attrs):
            out.extend(c.strip().lstrip("%") for c in group.split(","))
        return out

    def dims_of(shapes) -> tuple:
        return tuple((dt, tuple(int(d) for d in dd.split(",") if d)) for dt, dd in shapes)

    found: List[LoopCollective] = []
    seen: set = set()

    def collective(ni: NamedInstruction, holder: NamedInstruction, overlapped: bool, comp: str, at: int) -> None:
        kind = _COLLECTIVE_OPS.get(ni.op.removesuffix("-start"))
        if kind is None:
            return
        shapes = [s for s in ni.result_shapes if s[0] in DTYPE_BYTES and s[1]]
        if ni.op.endswith("-start") and len(shapes) > 1:   # (operand alias, result)
            shapes = [max(shapes, key=lambda s: shape_bytes(*s))]
        m = OP_NAME.search(ni.attrs) or OP_NAME.search(holder.attrs)
        found.append(LoopCollective(
            holder.name, kind, dims_of(shapes), sum(shape_bytes(*s) for s in shapes),
            m.group(1) if m else "", overlapped or ni.op.endswith("-start"),
            *placed(comp, at),
        ))

    def walk(comp: str) -> None:
        if comp in seen:
            return
        seen.add(comp)
        for at, ni in enumerate(comps.get(comp, ())):
            if ni.op != "fusion":
                collective(ni, ni, False, comp, at)
                for c in called(ni):
                    walk(c)
                continue
            inner = [c for c in called(ni) if c in comps]
            if any(c.startswith("all-reduce-scatter") for c in inner):
                m = OP_NAME.search(ni.attrs)
                found.append(LoopCollective(
                    ni.name, "reduce_scatter", dims_of(ni.result_shapes), ni.result_bytes,
                    m.group(1) if m else "", False,
                ))
            elif ni.name.startswith("async-collective-start"):
                for c in inner:
                    for held in comps[c]:
                        collective(held, ni, True, comp, at)
            # any other fusion computes (or awaits, or runs beside, a collective counted at its start)

    for instrs in comps.values():
        for ni in instrs:
            if ni.op == "while":
                body = re.search(r"body=%?([\w.\-]+)", ni.attrs)
                if body:
                    walk(body.group(1))
    return found
