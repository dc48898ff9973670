"""HLO cost / MFU analyzer: interpret a compiled step, not just time it.

PR 1 gave the runtime raw metrics; this module turns a compiled XLA program
into *answers*: what fraction of the chip's peak the step achieved (MFU),
where its flops and bytes go (matmul / attention / collective / elementwise),
and what bounds it (compute vs memory vs communication — a roofline
classification against a per-chip peak table, CPU fallback included).

Method: walk the **post-optimization HLO text** of the compiled executable
(the same source of truth ``comm/comm.py record_from_compiled`` uses for the
collective mix) and cost each instruction analytically:

- ``dot``: flops = 2 · |output| · Π(contracted dims) — exact, from the
  printed shapes and ``lhs_contracting_dims``. Categorized ``attention``
  when the instruction's metadata (op_name / source_file) points into an
  attention module, ``matmul`` otherwise.
- collectives (``all-reduce`` / ``all-gather`` / ``reduce-scatter`` /
  ``all-to-all`` / ``collective-permute``): payload bytes from the operand
  shapes (post-opt dtypes ⇒ wire precision). Async ``-start``/``-done``
  pairs are counted once and tallied as *overlappable* — the latency-hiding
  scheduler split them so compute can run between start and done; the
  ``overlap_fraction`` estimate is overlappable bytes / total collective
  bytes.
- elementwise arithmetic + reduces: 1 flop per output (resp. input) element,
  mirroring XLA's own HloCostAnalysis convention, so the parsed total stays
  comparable to ``compiled.cost_analysis()['flops']``
  (``profiling.flops_profiler.verify_against_hlo`` pins the two within 5%).

Known limits (inherited from HLO-as-text, same as bench.py's cost_analysis
caveats): a ``while`` body (gradient-accumulation scan) prints once but runs
``loop_iterations`` times — pass the trip count (the engine passes its gas)
and in-loop costs are multiplied; Pallas custom-calls report zero flops
(their cost is invisible to XLA too), so TPU flash-attention steps
under-count — the ``attention`` category still *counts* the calls.
"""

from __future__ import annotations

import math
import re
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

# ---------------------------------------------------------------------------
# per-chip peak table
# ---------------------------------------------------------------------------

# bf16 matmul peak flop/s, HBM bytes/s, and per-link ICI bytes/s by device
# kind (published TPU specs). THE peak table: benches and the comm logger read
# it through :func:`chip_peak`. Keys match ``jax.Device.device_kind``
# substrings, checked longest first so "TPU v5p" wins over "TPU v5".
PEAK_TABLE: Dict[str, Dict[str, float]] = {
    "TPU v4": dict(peak_flops=275e12, hbm_bytes_per_s=1.23e12, ici_bytes_per_s=4.8e10),
    "TPU v5 lite": dict(peak_flops=197e12, hbm_bytes_per_s=8.19e11, ici_bytes_per_s=4.0e10),
    "TPU v5e": dict(peak_flops=197e12, hbm_bytes_per_s=8.19e11, ici_bytes_per_s=4.0e10),
    "TPU v5p": dict(peak_flops=459e12, hbm_bytes_per_s=2.765e12, ici_bytes_per_s=9.0e10),
    "TPU v6e": dict(peak_flops=918e12, hbm_bytes_per_s=1.64e12, ici_bytes_per_s=4.0e10),
    "TPU v6 lite": dict(peak_flops=918e12, hbm_bytes_per_s=1.64e12, ici_bytes_per_s=4.0e10),
}

# nominal entry for the CPU test mesh only (``device_kind == "cpu"``): keeps
# MFU / roofline DEFINED there, clearly labeled estimated. An accelerator
# that is not in the table is an error, never this entry.
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
    ``source="fallback"`` so dashboards can render the MFU as an estimate; any
    other kind that is not in the table raises — a utilization computed
    against another chip's peak is worse than none.
    ``peak_flops_override`` (e.g. ``telemetry.introspection.peak_tflops``)
    replaces the flops column only.
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
# HLO text walk
# ---------------------------------------------------------------------------

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
}

_COLLECTIVE_OPS = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute",
)

# elementwise arithmetic counted at 1 flop / output element (HloCostAnalysis
# convention; transcendentals land in the same bucket here — they execute on
# the same units and the counts are dominated by dots anyway)
_ELEMENTWISE_OPS = frozenset((
    "add", "subtract", "multiply", "divide", "maximum", "minimum", "power",
    "exponential", "log", "tanh", "rsqrt", "sqrt", "negate", "abs",
    "exponential-minus-one", "log-plus-one", "logistic", "cbrt",
))

_ATTN_HINT = re.compile(r"attention|attn|flash|softmax_qk|scaled_dot", re.I)

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


def _shape_bytes(dtype: str, dims: str) -> int:
    return _numel(dims) * _DTYPE_BYTES.get(dtype, 4)


def _operand_shapes(line: str) -> List[tuple]:
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


def _dot_flops(line: str, out_dims: str) -> float:
    """2 · |out| · Π(lhs contracted dims) — exact from the printed attrs."""
    ops = _operand_shapes(line)
    if not ops:
        return 0.0
    lhs_dims = [int(d) for d in ops[0][1].split(",") if d]
    m = re.search(r"lhs_contracting_dims=\{([0-9,]*)\}", line)
    contracted = 1
    if m and lhs_dims:
        for idx in m.group(1).split(","):
            if idx and int(idx) < len(lhs_dims):
                contracted *= lhs_dims[int(idx)]
    return 2.0 * _numel(out_dims) * contracted


@dataclass
class CategoryCost:
    flops: float = 0.0
    bytes: float = 0.0
    count: int = 0

    def add(self, flops: float, nbytes: float) -> None:
        self.flops += flops
        self.bytes += nbytes
        self.count += 1

    def to_dict(self) -> Dict[str, float]:
        return {"flops": self.flops, "bytes": self.bytes, "count": self.count}


@dataclass
class HloAnalysis:
    """Per-category cost of one compiled program (per-device module)."""

    categories: Dict[str, CategoryCost] = field(default_factory=dict)
    total_flops: float = 0.0
    total_bytes: float = 0.0
    collective_bytes: float = 0.0
    overlappable_collective_bytes: float = 0.0
    loop_iterations: int = 1
    xla_flops: Optional[float] = None
    xla_bytes: Optional[float] = None

    @property
    def overlap_fraction(self) -> float:
        """Collective bytes issued as async start/done pairs (schedulable
        under compute) over all collective bytes; 1.0 when there is nothing
        to hide."""
        if self.collective_bytes <= 0:
            return 1.0
        return self.overlappable_collective_bytes / self.collective_bytes

    def to_dict(self) -> Dict[str, Any]:
        return {
            "total_flops": self.total_flops,
            "total_bytes": self.total_bytes,
            "collective_bytes": self.collective_bytes,
            "overlap_fraction": round(self.overlap_fraction, 4),
            "loop_iterations": self.loop_iterations,
            "xla_flops": self.xla_flops,
            "xla_bytes": self.xla_bytes,
            "categories": {k: v.to_dict() for k, v in self.categories.items()},
        }


_CALLED_COMPS = re.compile(r"(?:body|condition|calls|to_apply)=\{?%?([\w.\-]+)")


def _split_computations(txt: str) -> Dict[str, List[str]]:
    """Computation name → its instruction lines (HLO text is one flat file
    of ``%comp (params) -> type { ... }`` blocks plus the ENTRY block)."""
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


def _loop_computations(comps: Dict[str, List[str]]) -> set:
    """Computations that execute once PER while-loop iteration: the bodies/
    conditions named on ``while(`` instructions, closed transitively over
    the call graph (fusions/calls/reduces nested inside a loop body run per
    iteration too)."""
    refs: Dict[str, List[str]] = {
        name: [r for line in lines for r in _CALLED_COMPS.findall(line)]
        for name, lines in comps.items()
    }
    seeds = [
        r
        for lines in comps.values()
        for line in lines
        if " while(" in line or "= while(" in line
        for r in _CALLED_COMPS.findall(line)
    ]
    in_loop: set = set()
    stack = list(seeds)
    while stack:
        c = stack.pop()
        if c in in_loop:
            continue
        in_loop.add(c)
        stack.extend(refs.get(c, ()))
    return in_loop


# -- public instruction grammar (ISSUE 6) -----------------------------------
# the dslint program verifiers (analysis/hlo_rules.py) read the same HLO
# text; exporting the grammar keeps the two HLO readers from drifting

DTYPE_BYTES = _DTYPE_BYTES
shape_bytes = _shape_bytes
operand_shapes = _operand_shapes


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
        nbytes = _shape_bytes(dtype, dims) if dtype in _DTYPE_BYTES else 0
        return m.group("op"), nbytes, None
    tm = _INSTR_TUPLE.search(line)
    if tm:
        shapes = _SHAPE.findall(tm.group("shapes"))
        sizes = [
            _shape_bytes(dt, dd) for dt, dd in shapes if dt in _DTYPE_BYTES
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
    Shares the byte/shape grammar above so the three HLO readers (cost walk,
    Engine A/D rules, Engine E liveness) cannot drift."""

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
            _shape_bytes(dt, dd) for dt, dd in shapes if dt in _DTYPE_BYTES
        ),
        operands=re.findall(r"%([\w.\-]+)", tail[call_start:call_end]),
        attrs=tail[call_end + 1:],
        is_root=m.group("root") is not None,
        line=line,
    )


def split_computations(txt: str) -> Dict[str, List[str]]:
    """Public alias of the computation splitter (ISSUE 9): computation name
    → its instruction lines. The ENTRY computation's name is recoverable by
    scanning for a line starting with ``ENTRY``; see ``entry_computation``."""
    return _split_computations(txt)


def entry_computation(txt: str) -> Optional[str]:
    """Name of the ENTRY computation in ``txt`` (None if absent)."""
    m = re.search(r"^\s*ENTRY\s+%?([\w.\-]+)\s*\(", txt, re.M)
    return m.group(1) if m else None


def analyze_hlo_text(txt: str, loop_iterations: int = 1) -> HloAnalysis:
    """Walk post-optimization HLO text into a per-category cost breakdown.

    ``loop_iterations`` multiplies costs found inside ``while``-loop bodies
    (a gas scan prints its body once but executes it gas times); the caller
    knows the trip count, the text does not. Loop membership is derived
    from the while instructions' ``body=``/``condition=`` attributes, closed
    over the call graph, so fusions nested in a scan body count correctly.
    """
    ana = HloAnalysis(loop_iterations=max(1, int(loop_iterations)))
    cats = ana.categories
    for name in ("matmul", "attention", "collective", "elementwise", "other"):
        cats[name] = CategoryCost()

    comps = _split_computations(txt)
    in_loop_comps = _loop_computations(comps) if ana.loop_iterations > 1 else set()

    for comp_name, lines in comps.items():
        mult = ana.loop_iterations if comp_name in in_loop_comps else 1
        for line in lines:
            _cost_line(line, mult, ana, cats)

    ana.total_flops = sum(c.flops for c in cats.values())
    ana.total_bytes = sum(c.bytes for c in cats.values())
    return ana


def _cost_line(line: str, mult: int, ana: HloAnalysis, cats) -> None:
    """Cost one HLO instruction line into the category breakdown."""
    m = _INSTR.search(line)
    tuple_shapes = None
    if not m:
        tm = _INSTR_TUPLE.search(line)
        if not tm:
            return
        m, tuple_shapes = tm, tm.group("shapes")
    op = m.group("op")
    base_op = re.sub(r"-(start|done)$", "", op)

    if base_op in _COLLECTIVE_OPS:
        if op.endswith("-done"):
            return  # counted at -start
        # payload = largest typed buffer: async starts return an
        # (operand-alias, result) tuple whose biggest element — operand for
        # all-reduce, gathered result for all-gather — upper-bounds the wire
        # volume (same convention as comm.record_from_compiled); sync forms
        # read it off the call operands
        if tuple_shapes is not None:
            shapes = _SHAPE.findall(tuple_shapes)
        else:
            shapes = list(_operand_shapes(line))
        sizes = [
            _shape_bytes(dt, dd) for dt, dd in shapes if dt in _DTYPE_BYTES
        ]
        nbytes = (max(sizes) if sizes else 0) * mult
        cats["collective"].add(0.0, nbytes)
        ana.collective_bytes += nbytes
        if op.endswith("-start"):
            ana.overlappable_collective_bytes += nbytes
        return

    if tuple_shapes is not None:
        return  # other tuple-result ops (variadic reduce, rng) are uncosted
    dtype, dims = m.group("dtype"), m.group("dims")
    if dtype is None or dtype not in _DTYPE_BYTES:
        return
    out_bytes = _shape_bytes(dtype, dims)

    if op == "dot":
        flops = _dot_flops(line, dims) * mult
        nbytes = (
            out_bytes
            + sum(_shape_bytes(dt, dd) for dt, dd in _operand_shapes(line)
                  if dt in _DTYPE_BYTES)
        ) * mult
        cat = "attention" if _ATTN_HINT.search(line) else "matmul"
        cats[cat].add(flops, nbytes)
    elif op == "custom-call":
        cat = "attention" if _ATTN_HINT.search(line) else "other"
        # Pallas / library custom-calls: flops invisible (see module
        # docstring); count the call and its result bytes
        cats[cat].add(0.0, out_bytes * mult)
    elif op in _ELEMENTWISE_OPS:
        flops = float(_numel(dims)) * mult
        cats["elementwise"].add(flops, 2.0 * out_bytes * mult)
    elif op == "reduce":
        ops_ = _operand_shapes(line)
        in_elems = max((_numel(dd) for _, dd in ops_), default=0)
        flops = float(max(0, in_elems - _numel(dims))) * mult
        nbytes = (out_bytes + sum(
            _shape_bytes(dt, dd) for dt, dd in ops_ if dt in _DTYPE_BYTES
        )) * mult
        cats["elementwise"].add(flops, nbytes)


def analyze_compiled(compiled, loop_iterations: int = 1) -> HloAnalysis:
    """Analyze a ``jax.stages.Compiled`` (or anything with ``as_text()``);
    attaches XLA's own ``cost_analysis()`` totals for cross-checking."""
    txt = compiled.as_text() if hasattr(compiled, "as_text") else str(compiled)
    ana = analyze_hlo_text(txt, loop_iterations=loop_iterations)
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        ca = dict(ca or {})
        ana.xla_flops = float(ca.get("flops", 0.0)) or None
        ana.xla_bytes = float(ca.get("bytes accessed", 0.0)) or None
    except Exception:
        pass
    return ana


# ---------------------------------------------------------------------------
# MFU + roofline report
# ---------------------------------------------------------------------------

def step_report(
    analysis: HloAnalysis,
    duration_s: float,
    peak: Optional[PeakSpec] = None,
) -> Dict[str, Any]:
    """One measured step + one analyzed program → the introspection record.

    Everything is per-device: the analyzed module is the SPMD-partitioned
    per-device program and ``peak`` is one chip's table entry, so the MFU
    is the per-chip utilization regardless of mesh size.

    Roofline: estimated compute / memory / communication times from the
    peak table; the largest wins as ``bound``. ``comm`` additionally
    discounts collective time by the overlap fraction — fully-async
    collectives only bound the step through their unhidden remainder.
    """
    peak = peak or chip_peak()
    dur = max(float(duration_s), 1e-9)
    flops = analysis.total_flops
    nbytes = analysis.total_bytes
    mfu = flops / dur / peak.peak_flops
    t_compute = flops / peak.peak_flops
    t_memory = nbytes / peak.hbm_bytes_per_s
    unhidden = analysis.collective_bytes * (1.0 - analysis.overlap_fraction)
    t_comm = unhidden / peak.ici_bytes_per_s
    bound = max(
        (("compute", t_compute), ("memory", t_memory), ("comm", t_comm)),
        key=lambda kv: kv[1],
    )[0]
    intensity = flops / nbytes if nbytes > 0 else float("inf")
    ridge = peak.peak_flops / peak.hbm_bytes_per_s
    report = {
        "mfu": round(mfu, 9),
        "flops_per_step": flops,
        "bytes_per_step": nbytes,
        "arithmetic_intensity": round(intensity, 3) if math.isfinite(intensity) else None,
        "ridge_intensity": round(ridge, 3),
        "roofline_bound": bound,
        "est_compute_s": t_compute,
        "est_memory_s": t_memory,
        "est_comm_s": t_comm,
        "overlap_fraction": round(analysis.overlap_fraction, 4),
        "flops_per_category": {
            k: v.flops for k, v in analysis.categories.items() if v.count or v.flops
        },
        "bytes_per_category": {
            k: v.bytes for k, v in analysis.categories.items() if v.count or v.bytes
        },
        "peak": peak.to_dict(),
        "loop_iterations": analysis.loop_iterations,
    }
    if analysis.xla_flops:
        report["xla_flops"] = analysis.xla_flops
    return report


def export_to_registry(registry, report: Dict[str, Any]) -> None:
    """Fold one step report into the PR-1 metrics registry: ``step_mfu``,
    per-category flop/byte gauges, ``overlap_fraction``, and a one-hot
    ``roofline_bound{bound}`` family (the current bound reads 1)."""
    registry.gauge(
        "step_mfu", "model flops utilization of the last sampled step"
    ).set(report["mfu"])
    registry.gauge(
        "overlap_fraction",
        "collective bytes hidden under compute (HLO-schedule estimate)",
    ).set(report["overlap_fraction"])
    if report.get("arithmetic_intensity") is not None:
        registry.gauge(
            "step_arithmetic_intensity", "flops per HBM byte of the step"
        ).set(report["arithmetic_intensity"])
    gf = registry.gauge(
        "flops_per_category", "per-step flops by HLO category",
        labelnames=("category",),
    )
    for k, v in report["flops_per_category"].items():
        gf.set(v, category=k)
    gb = registry.gauge(
        "bytes_per_category", "per-step bytes by HLO category",
        labelnames=("category",),
    )
    for k, v in report["bytes_per_category"].items():
        gb.set(v, category=k)
    gr = registry.gauge(
        "roofline_bound", "roofline classification (current bound = 1)",
        labelnames=("bound",),
    )
    for b in ("compute", "memory", "comm"):
        gr.set(1.0 if report["roofline_bound"] == b else 0.0, bound=b)
