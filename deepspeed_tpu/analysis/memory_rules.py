"""Engine E: static HBM liveness — what a compiled program *costs* in bytes.

dslint's Engines A/D verify what a program *does*; this engine verifies what
it costs. The post-optimization HLO text of a compiled executable is
scheduled (``is_scheduled=true``), so a def-use live-range walk over the
ENTRY instruction sequence reconstructs the resident-bytes curve the
runtime will actually trace out — before the program ever runs, and
therefore before an OOM or a silently shrunken KV page pool can happen at
3am. ZeRO-Infinity (arXiv:2104.07857) and DeepSpeed-Inference
(arXiv:2207.00032) both stand on exact per-tier byte accounting; this
module makes that accounting a static, CI-gated property.

The buffer model (validated within 10% of ``compiled.memory_analysis()``
on the gpt2-tiny train step and both serving executables — asserted in
``tests/unit/test_memory_analysis.py``):

- every allocating instruction defines a buffer of its printed result size,
  live from its def to its last use;
- ``bitcast`` / ``reshape`` / ``get-tuple-element`` / ``optimization-barrier``
  are views, not allocations — uses of the view keep the SOURCE alive;
- ``tuple`` carries its operands per element, ``while`` updates its init
  tuple in place (XLA's in-place while), ``get-tuple-element(index=k)``
  keeps only element k alive — so a loop-carried KV-pool double-buffer is
  charged exactly once, for exactly the loop's extent;
- ``dynamic-update-slice`` (and DUS-rooted fusions) update their target
  operand in place, matching XLA's emission;
- a ``while`` additionally charges its body's internal peak while it runs
  (the while-body closure), ``conditional`` the max over its branches;
- entry parameters are charged for the whole program (they are the caller's
  resident arrays); ROOT-reachable buffers stay live to the end.

``peak_bytes`` = entry-argument bytes + the walk's peak over live internal
buffers. The live-at-peak ledger is categorized — params / kv-pool /
activations / collective-scratch / temp — so a budget failure names the
tier that grew.

Rules:

- ``hbm-over-budget``: peak above the program's committed byte budget
  (``analysis.memory`` config + the committed ``.dsmem-budgets.json``
  ledger) — the CI gate for items 2/3/5 of the roadmap.
- ``donation-missed-bytes``: an undonated entry parameter that is dead
  before the peak — aliasing it (donate_argnums) would hand its bytes back
  to the allocator and cut the peak by up to its size.
- ``oversized-collective-scratch``: collective staging buffers holding an
  outsized share of the live-at-peak bytes.
- ``padding-waste``: a tiled layout (``{...:T(8,128)...}``) whose physical
  bytes exceed the logical bytes by more than the configured ratio.
"""

from __future__ import annotations

import json
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

from ..telemetry.introspect import (
    DTYPE_BYTES,
    NamedInstruction,
    entry_computation,
    parse_named_instruction,
    shape_bytes,
    split_computations,
)
from .findings import SEVERITY_ERROR, SEVERITY_WARNING, Finding

# the ONE alias-table parser (Engine A owns it; a second copy of the
# brace-matched cut would let the two readers of the same header drift)
from .hlo_rules import _PARAM as _PARAM_DECL
from .hlo_rules import _aliased_params as _aliased_param_numbers

RULES = {
    "hbm-over-budget":
        "static peak HBM above the program's committed byte budget",
    "donation-missed-bytes":
        "undonated input dead before the peak — donating it would cut peak",
    "oversized-collective-scratch":
        "collective staging buffers hold an outsized share of peak HBM",
    "padding-waste":
        "tiled layout's physical bytes far exceed the logical bytes",
}

DEFAULT_BUDGET_NAME = ".dsmem-budgets.json"

# buffer categories in the live-at-peak ledger. "metadata" (ISSUE 10) is
# the serving control plane: integer block tables, draft-token batches and
# page maps — the device shadow of the scheduler's host-side
# refcount/prefix-index state, labeled so the ledger separates them from
# model temps.
CATEGORIES = ("params", "kv-pool", "activations", "collective-scratch",
              "temp", "metadata")

_METADATA_DTYPES = frozenset(("s8", "s16", "s32", "s64", "u8", "u16", "u32",
                              "u64", "pred"))

_COLLECTIVE_BASES = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute",
)

# view ops: zero allocation, uses keep the source buffer alive
_VIEW_OPS = frozenset((
    "bitcast", "reshape", "optimization-barrier", "get-tuple-element",
    "copy-done",
))


@dataclass
class MemoryRuleContext:
    """Declared memory expectations the compiled text is verified against."""

    program: str = "program"
    # -- hbm-over-budget ----------------------------------------------
    budget_bytes: int = 0                 # 0 = no budget check
    # -- donation-missed-bytes ----------------------------------------
    check_donation: bool = True
    donation_min_bytes: int = 1 << 16
    # -- oversized-collective-scratch ---------------------------------
    scratch_max_fraction: float = 0.25
    scratch_min_bytes: int = 1 << 20
    # -- padding-waste -------------------------------------------------
    padding_waste_min_ratio: float = 1.5
    padding_waste_min_bytes: int = 1 << 16
    # -- categorization ------------------------------------------------
    # dim strings ("L,P,KV,page,D") whose buffers are the serving KV pool
    kv_pool_dims: Sequence[str] = ()
    # dim strings of integer control-plane buffers (block tables, draft
    # batches, page maps) labeled "metadata"; only integer/pred dtypes
    # match, so a float activation sharing a dim string stays put
    metadata_dims: Sequence[str] = ()
    # dim strings of the quantized KV pool's per-page scales ([L,P,KV,2]
    # fp32, ISSUE 12) — also "metadata" (they are bookkeeping beside the
    # pool, not page payload), but FLOAT, so they get their own declared
    # list instead of widening metadata_dims' dtype guard
    scales_dims: Sequence[str] = ()
    # metadata source/op hint that marks a temp buffer as an activation
    activation_hint: str = r"models/|attention|attn|mlp|embed|transformer"


@dataclass
class LiveBuffer:
    """One buffer in the live-at-peak ledger."""

    name: str
    nbytes: int
    category: str
    line: int = 0

    def to_dict(self) -> Dict:
        return {
            "name": self.name, "bytes": self.nbytes,
            "category": self.category, "line": self.line,
        }


@dataclass
class MemoryAnalysis:
    """Static memory profile of one compiled program."""

    program: str = "program"
    args_bytes: int = 0            # entry parameters (resident for the call)
    aliased_bytes: int = 0         # donated args (aliased input->output)
    walk_peak_bytes: int = 0       # peak over internal/output buffers
    peak_line: int = 0             # 1-based HLO line of the peak instruction
    live_at_peak: List[LiveBuffer] = field(default_factory=list)
    by_category: Dict[str, int] = field(default_factory=dict)
    # undonated params dead before the peak: (name, bytes, def_line)
    donation_candidates: List[Tuple[str, int, int]] = field(
        default_factory=list
    )
    n_buffers: int = 0

    @property
    def peak_bytes(self) -> int:
        return self.args_bytes + self.walk_peak_bytes

    def to_dict(self) -> Dict:
        return {
            "program": self.program,
            "peak_bytes": self.peak_bytes,
            "args_bytes": self.args_bytes,
            "aliased_bytes": self.aliased_bytes,
            "walk_peak_bytes": self.walk_peak_bytes,
            "peak_line": self.peak_line,
            "by_category": dict(self.by_category),
            "n_buffers": self.n_buffers,
            "donation_candidates": [
                {"param": n, "bytes": b, "line": ln}
                for n, b, ln in self.donation_candidates
            ],
        }


# ---------------------------------------------------------------------------
# the liveness walk
# ---------------------------------------------------------------------------

_TYPED_OPND = re.compile(
    r"(\w+)\[([0-9,]*)\](?:\{[^}]*\})?\s+%([\w.\-]+)"
)
_META_OP = re.compile(r'op_name="([^"]*)"')
_META_SRC = re.compile(r'source_file="([^"]*)"')


def _is_dus(inst: NamedInstruction) -> bool:
    return inst.op == "dynamic-update-slice" or (
        inst.op == "fusion" and "dynamic-update-slice" in inst.name
    )


def _dus_target(inst: NamedInstruction) -> Optional[str]:
    """The operand a dynamic-update-slice updates in place: the first
    operand printed with the result's own shape."""
    if not inst.result_shapes:
        return None
    want = inst.result_shapes[0]
    for dt, dd, name in _TYPED_OPND.findall(inst.line):
        if (dt, dd) == want and name != inst.name:
            return name
    return None


def _categorize(inst: NamedInstruction, ctx: MemoryRuleContext,
                act_re, pool_dims: frozenset) -> str:
    base = re.sub(r"-(start|done)$", "", inst.op)
    if base in _COLLECTIVE_BASES:
        return "collective-scratch"
    if pool_dims and any(dd in pool_dims for _, dd in inst.result_shapes):
        return "kv-pool"
    meta_dims = frozenset(ctx.metadata_dims)
    if meta_dims and any(
        dd in meta_dims and dt in _METADATA_DTYPES
        for dt, dd in inst.result_shapes
    ):
        return "metadata"
    scl_dims = frozenset(ctx.scales_dims)
    if scl_dims and any(dd in scl_dims for _, dd in inst.result_shapes):
        return "metadata"
    if act_re is not None:
        op_m = _META_OP.search(inst.line)
        src_m = _META_SRC.search(inst.line)
        hint = (op_m.group(1) if op_m else "") + " " + \
            (src_m.group(1) if src_m else "")
        if hint.strip() and act_re.search(hint):
            return "activations"
    return "temp"


class _Walker:
    """Def-use live-range pass over one computation's scheduled lines."""

    def __init__(self, comps: Dict[str, List[str]], ctx: MemoryRuleContext,
                 memo: Dict[str, int]):
        self.comps = comps
        self.ctx = ctx
        self.memo = memo  # computation name -> internal temp peak
        self.act_re = (
            re.compile(ctx.activation_hint, re.I)
            if ctx.activation_hint else None
        )
        self.pool_dims = frozenset(ctx.kv_pool_dims)

    def comp_peak(self, cname: str) -> int:
        """Internal peak of a nested computation (while body / branch)."""
        if cname in self.memo:
            return self.memo[cname]
        self.memo[cname] = 0  # recursion guard
        peak = self.walk(self.comps.get(cname, []))[0]
        self.memo[cname] = peak
        return peak

    def walk(self, lines: Sequence[str], line_base: int = 0,
             want_ledger: bool = False):
        """→ (peak_bytes, peak_line, live_at_peak ledger, param_last_use).

        ``param_last_use`` maps entry-parameter NAME → index of its last
        use (for the donation rule); only populated on the entry walk."""
        ctx = self.ctx
        insts: List[Tuple[int, NamedInstruction]] = []
        for off, line in enumerate(lines):
            p = parse_named_instruction(line)
            if p is not None:
                insts.append((line_base + off + 1, p))

        # value model: name -> frozenset of storage roots, or a list of
        # frozensets for tuple-typed values (per-element liveness)
        val: Dict[str, object] = {}
        size: Dict[str, int] = {}
        cat: Dict[str, str] = {}
        def_line: Dict[str, int] = {}
        param_names: Dict[str, int] = {}  # name -> def line

        def _flat(v) -> set:
            if isinstance(v, list):
                out: set = set()
                for s in v:
                    out |= s
                return out
            return set(v)

        def V(n):
            return val.get(n, frozenset())

        for idx, (lineno, inst) in enumerate(insts):
            name, op = inst.name, inst.op
            if op == "parameter":
                # a param's storage is tracked (for donation liveness) but
                # never counted in the walk — it lives in args_bytes
                val[name] = frozenset((f"param:{name}",))
                param_names[name] = lineno
            elif op == "get-tuple-element" and inst.operands:
                src = V(inst.operands[0])
                mi = re.search(r"index=(\d+)", inst.attrs)
                if isinstance(src, list) and mi and \
                        int(mi.group(1)) < len(src):
                    val[name] = src[int(mi.group(1))]
                else:
                    val[name] = frozenset(_flat(src))
            elif op in _VIEW_OPS and inst.operands:
                val[name] = V(inst.operands[0])
            elif op == "tuple":
                val[name] = [frozenset(_flat(V(o))) for o in inst.operands]
            elif op == "while" and inst.operands:
                val[name] = V(inst.operands[0])  # in-place while
            elif _is_dus(inst):
                tgt = _dus_target(inst)
                if tgt is not None and not isinstance(V(tgt), list):
                    val[name] = V(tgt)  # in-place update
                else:
                    size[name] = inst.result_bytes
                    val[name] = frozenset((name,))
            else:
                size[name] = inst.result_bytes
                val[name] = frozenset((name,))
            if name in size:
                cat[name] = _categorize(inst, ctx, self.act_re,
                                        self.pool_dims)
                def_line[name] = lineno

        # loop-carried refinement: buffers flowing into a while's init tuple
        # are the activation-stack shape (saved residuals / accumulators) —
        # their defining instruction is usually a bare copy with no
        # metadata, so the hint regex can't see them
        for lineno, inst in insts:
            if inst.op != "while" or not inst.operands:
                continue
            for r in _flat(V(inst.operands[0])):
                if cat.get(r) == "temp":
                    cat[r] = "activations"

        # last use per storage root (the def-use chain's "use" side)
        last: Dict[str, int] = {}
        n = len(insts)
        for idx, (lineno, inst) in enumerate(insts):
            if inst.op == "get-tuple-element":
                use = set(_flat(V(inst.name)))  # only the picked element
            else:
                use = set()
                for o in inst.operands:
                    use |= _flat(V(o))
            for r in use:
                last[r] = idx
            if inst.is_root:
                for r in _flat(V(inst.name)) | {inst.name}:
                    last[r] = n  # outputs live to the end

        live = peak = 0
        peak_idx = -1
        live_set: set = set()
        peak_set: set = set()
        ends: Dict[int, List[str]] = {}
        for idx, (lineno, inst) in enumerate(insts):
            transient = 0
            if inst.op == "while":
                m = re.search(r"body=%?([\w.\-]+)", inst.line)
                if m:
                    transient += self.comp_peak(m.group(1))
            elif inst.op == "conditional":
                # indexed form: branch_computations={%c0, %c1, ...};
                # predicated form: true_computation=%ct, false_computation=%cf
                brs = re.findall(
                    r"branch_computations=\{([^}]*)\}", inst.line
                )
                names = re.findall(r"%?([\w.\-]+)", brs[0]) if brs else \
                    re.findall(
                        r"(?:true|false)_computation=%?([\w.\-]+)",
                        inst.line,
                    )
                transient += max(
                    (self.comp_peak(c) for c in names if c), default=0
                )
            if inst.name in size:
                live += size[inst.name]
                live_set.add(inst.name)
                ends.setdefault(last.get(inst.name, idx), []).append(
                    inst.name
                )
            if live + transient > peak:
                peak, peak_idx = live + transient, idx
                peak_set = set(live_set)
            for dead in ends.pop(idx, ()):
                live -= size[dead]
                live_set.discard(dead)

        peak_line = insts[peak_idx][0] if 0 <= peak_idx < n else 0
        ledger = []
        if want_ledger:
            ledger = [
                LiveBuffer(name=b, nbytes=size[b], category=cat[b],
                           line=def_line.get(b, 0))
                for b in sorted(peak_set, key=lambda b: -size[b])
            ]
        param_last = {
            p: last.get(f"param:{p}", -1) for p in param_names
        }
        # resolve param last-use index -> "dead before peak?" for the caller
        param_dead_before_peak = {
            p: (ix < peak_idx) for p, ix in param_last.items()
        }
        return (peak, peak_line, ledger,
                {"def_line": param_names, "dead": param_dead_before_peak})


def analyze_memory_text(
    txt: str, ctx: Optional[MemoryRuleContext] = None
) -> MemoryAnalysis:
    """Walk one post-optimization HLO module into a :class:`MemoryAnalysis`.

    The text must be the scheduled post-opt dump (``compiled.as_text()``);
    an unscheduled module still parses but the peak is then an instruction-
    order estimate rather than the compiler's schedule."""
    ctx = ctx or MemoryRuleContext()
    ana = MemoryAnalysis(program=ctx.program)
    comps = split_computations(txt)
    entry = entry_computation(txt)
    if entry is None or entry not in comps:
        return ana

    aliased_nums = _aliased_param_numbers(txt)
    pool_dims = frozenset(ctx.kv_pool_dims)

    # entry params: args_bytes + the params/kv-pool categories of the ledger
    params: Dict[str, Tuple[str, str, int, int]] = {}
    entry_lines = comps[entry]
    for lineno, line in enumerate(entry_lines, start=1):
        m = _PARAM_DECL.search(line)
        if m:
            params[_param_name(line)] = (
                m.group("dtype"), m.group("dims"),
                int(m.group("num")), lineno,
            )
    meta_dims = frozenset(ctx.metadata_dims)
    args_by_cat = {"params": 0, "kv-pool": 0, "metadata": 0}
    param_buffers: List[LiveBuffer] = []
    for pname, (dt, dd, num, lineno) in params.items():
        b = shape_bytes(dt, dd) if dt in DTYPE_BYTES else 0
        if dd in pool_dims:
            category = "kv-pool"
        elif dd in meta_dims and dt in _METADATA_DTYPES:
            category = "metadata"
        elif dd in frozenset(ctx.scales_dims):
            category = "metadata"
        else:
            category = "params"
        args_by_cat[category] += b
        param_buffers.append(LiveBuffer(pname, b, category, lineno))
        ana.args_bytes += b
        if num in aliased_nums:
            ana.aliased_bytes += b

    walker = _Walker(comps, ctx, memo={})
    peak, peak_line, ledger, pinfo = walker.walk(
        entry_lines, want_ledger=True
    )
    ana.walk_peak_bytes = peak
    ana.peak_line = peak_line
    ana.live_at_peak = (
        sorted(param_buffers, key=lambda b: -b.nbytes) + ledger
    )
    ana.n_buffers = len(ana.live_at_peak)

    by_cat = {c: 0 for c in CATEGORIES}
    by_cat["params"] = args_by_cat["params"]
    by_cat["kv-pool"] = args_by_cat["kv-pool"]
    by_cat["metadata"] = args_by_cat["metadata"]
    for buf in ledger:
        by_cat[buf.category] = by_cat.get(buf.category, 0) + buf.nbytes
    # while-body internal peaks are charged transiently at the while line
    # but have no named ENTRY buffer — fold the remainder into temp so the
    # category breakdown always sums to peak_bytes
    residual = ana.peak_bytes - sum(by_cat.values())
    if residual > 0:
        by_cat["temp"] += residual
    ana.by_category = by_cat

    if ctx.check_donation:
        for pname, (dt, dd, num, lineno) in params.items():
            if num in aliased_nums or dt not in DTYPE_BYTES:
                continue
            b = shape_bytes(dt, dd)
            if b >= ctx.donation_min_bytes and pinfo["dead"].get(pname):
                ana.donation_candidates.append((pname, b, lineno))
    return ana


def _param_name(line: str) -> str:
    m = re.match(r"\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=", line)
    return m.group(1) if m else line.strip()[:40]


# ---------------------------------------------------------------------------
# rules
# ---------------------------------------------------------------------------

def _finding(ctx, rule, severity, message, line_no=0, snippet=""):
    return Finding(
        rule=rule, severity=severity, message=message,
        path=f"hlo://{ctx.program}", line=line_no, symbol=ctx.program,
        snippet=(snippet or message)[:160], engine="mem",
    )


def rule_hbm_over_budget(
    ana: MemoryAnalysis, ctx: MemoryRuleContext
) -> List[Finding]:
    if ctx.budget_bytes <= 0 or ana.peak_bytes <= ctx.budget_bytes:
        return []
    cats = ", ".join(
        f"{k}={v / 1e6:.2f}MB" for k, v in ana.by_category.items() if v
    )
    return [_finding(
        ctx, "hbm-over-budget", SEVERITY_ERROR,
        f"static peak HBM {ana.peak_bytes / 1e6:.2f} MB exceeds the "
        f"committed budget {ctx.budget_bytes / 1e6:.2f} MB "
        f"(+{100.0 * (ana.peak_bytes - ctx.budget_bytes) / ctx.budget_bytes:.1f}%); "
        f"live at peak: {cats}",
        line_no=ana.peak_line,
    )]


def rule_donation_missed(
    ana: MemoryAnalysis, ctx: MemoryRuleContext
) -> List[Finding]:
    out = []
    for pname, b, lineno in ana.donation_candidates:
        out.append(_finding(
            ctx, "donation-missed-bytes", SEVERITY_WARNING,
            f"entry parameter %{pname} ({b / 1e6:.2f} MB) is dead before "
            "the peak and not donated — aliasing it (donate_argnums) would "
            f"cut peak HBM by up to {b / 1e6:.2f} MB",
            line_no=lineno, snippet=f"%{pname}",
        ))
    return out


def rule_oversized_collective_scratch(
    ana: MemoryAnalysis, ctx: MemoryRuleContext
) -> List[Finding]:
    scratch = ana.by_category.get("collective-scratch", 0)
    peak = max(1, ana.peak_bytes)
    if scratch < ctx.scratch_min_bytes:
        return []
    if scratch / peak <= ctx.scratch_max_fraction:
        return []
    return [_finding(
        ctx, "oversized-collective-scratch", SEVERITY_WARNING,
        f"collective staging buffers hold {scratch / 1e6:.2f} MB "
        f"({scratch / peak:.0%}) of the {peak / 1e6:.2f} MB peak — combine "
        "thresholds or bucket sizes are staging more than they hide",
        line_no=ana.peak_line,
    )]


_LAYOUT_TILED = re.compile(
    r"(?P<dtype>\w+)\[(?P<dims>[0-9,]+)\]\{(?P<perm>[0-9,]+):"
    r"(?P<tiles>[^}]*T\([^)]*\)[^}]*)\}"
)
_TILE = re.compile(r"T\(([0-9,*]+)\)")


def padded_bytes(dtype: str, dims: str, perm: str, tiles: str) -> int:
    """Physical bytes of a tiled layout: minor dims round up to the first
    tile's multiples (sub-tiles like ``(2,1)`` repack without padding
    beyond the major tile, so only ``T(...)`` is charged)."""
    sizes = [int(d) for d in dims.split(",") if d]
    order = [int(p) for p in perm.split(",") if p]
    m = _TILE.search(tiles)
    if not m or not sizes or len(order) != len(sizes):
        return shape_bytes(dtype, dims)
    tile = [t for t in m.group(1).split(",") if t and t != "*"]
    tile_sizes = [int(t) for t in tile]
    padded = list(sizes)
    # tile dims map onto the minor-most layout dims, innermost last
    for k, t in enumerate(reversed(tile_sizes)):
        if k >= len(order):
            break
        dim = order[k]  # k-th minor logical dim
        padded[dim] = -(-padded[dim] // t) * t
    n = 1
    for d in padded:
        n *= d
    return n * DTYPE_BYTES.get(dtype, 4)


def rule_padding_waste(txt: str, ctx: MemoryRuleContext) -> List[Finding]:
    out = []
    seen = set()
    for i, line in enumerate(txt.splitlines(), start=1):
        m = _LAYOUT_TILED.search(line)
        if not m:
            continue
        logical = shape_bytes(m.group("dtype"), m.group("dims"))
        physical = padded_bytes(
            m.group("dtype"), m.group("dims"), m.group("perm"),
            m.group("tiles"),
        )
        waste = physical - logical
        if logical <= 0 or waste < ctx.padding_waste_min_bytes:
            continue
        if physical / logical < ctx.padding_waste_min_ratio:
            continue
        key = (m.group("dtype"), m.group("dims"), m.group("tiles"))
        if key in seen:
            continue  # one finding per distinct padded shape
        seen.add(key)
        out.append(_finding(
            ctx, "padding-waste", SEVERITY_WARNING,
            f"{m.group('dtype')}[{m.group('dims')}] pads to "
            f"{physical / 1e6:.2f} MB physical for {logical / 1e6:.2f} MB "
            f"logical ({physical / logical:.1f}x) under tiling "
            f"{m.group('tiles').strip()} — reshape or re-layout to stop "
            "paying HBM for padding",
            line_no=i, snippet=line.strip(),
        ))
    return out


def verify_memory_text(
    txt: str, ctx: Optional[MemoryRuleContext] = None
) -> Tuple[List[Finding], MemoryAnalysis]:
    """Every Engine-E rule over one HLO module text → (findings, analysis)."""
    ctx = ctx or MemoryRuleContext()
    ana = analyze_memory_text(txt, ctx)
    findings: List[Finding] = []
    findings.extend(rule_hbm_over_budget(ana, ctx))
    findings.extend(rule_donation_missed(ana, ctx))
    findings.extend(rule_oversized_collective_scratch(ana, ctx))
    findings.extend(rule_padding_waste(txt, ctx))
    return findings, ana


def verify_memory_compiled(
    compiled, ctx: Optional[MemoryRuleContext] = None
) -> Tuple[List[Finding], MemoryAnalysis]:
    txt = compiled.as_text() if hasattr(compiled, "as_text") else str(compiled)
    return verify_memory_text(txt, ctx)


# ---------------------------------------------------------------------------
# the XLA cross-check + the committed budget ledger
# ---------------------------------------------------------------------------

def xla_peak_bytes(compiled) -> Optional[int]:
    """XLA's own accounting of the same peak: arguments + outputs − aliased
    + temp heap, from ``compiled.memory_analysis()``. None when the backend
    doesn't expose it. Engine E's estimate is pinned within 10% of this on
    the real train/serving programs (acceptance test).

    An executable deserialized from the persistent compilation cache
    reports ``alias_size_in_bytes=0`` even though its module header still
    carries the ``input_output_alias`` table — recompute the aliased bytes
    from the text in that case, or a run served from the cache would inflate the
    reference by the whole donated state."""
    try:
        ma = compiled.memory_analysis()
        if isinstance(ma, (list, tuple)):
            ma = ma[0]
        alias = int(ma.alias_size_in_bytes)
        if alias == 0 and hasattr(compiled, "as_text"):
            txt = compiled.as_text()
            nums = _aliased_param_numbers(txt)
            if nums:
                entry = entry_computation(txt)
                lines = split_computations(txt).get(entry, []) if entry else []
                for line in lines:
                    m = _PARAM_DECL.search(line)
                    if m and int(m.group("num")) in nums and \
                            m.group("dtype") in DTYPE_BYTES:
                        alias += shape_bytes(m.group("dtype"),
                                             m.group("dims"))
        return int(
            ma.argument_size_in_bytes
            + ma.output_size_in_bytes
            - alias
            + ma.temp_size_in_bytes
        )
    except Exception:
        return None


def load_budgets(path: str) -> Dict[str, int]:
    """The committed per-program budget ledger: ``{program: budget_bytes}``.
    Raises ValueError on a corrupt file (a broken ledger must not pass the
    gate vacuously)."""
    try:
        with open(path, encoding="utf-8") as fh:
            doc = json.load(fh)
    except OSError:
        return {}
    except json.JSONDecodeError as e:
        raise ValueError(f"corrupt dsmem budget file {path!r}: {e}") from e
    if not isinstance(doc, dict):
        raise ValueError(f"dsmem budget file {path!r} is not an object")
    out = {}
    for k, v in doc.items():
        if k.startswith("_"):
            continue  # comment / metadata keys
        out[str(k)] = int(v)
    return out


def find_budget_file(start: Optional[str] = None) -> Optional[str]:
    """Nearest committed budget ledger, walking upward from ``start`` (same
    walk as the dslint baseline). Without ``start`` the walk is anchored at
    the CWD; with it, the anchor wins — a dump in another checkout must
    resolve against THAT repo's ledger, not the invoking repo's."""
    if start is None and os.path.exists(DEFAULT_BUDGET_NAME):
        return DEFAULT_BUDGET_NAME
    probe = os.path.abspath(start or os.getcwd())
    if os.path.isfile(probe):
        probe = os.path.dirname(probe)
    for _ in range(6):
        cand = os.path.join(probe, DEFAULT_BUDGET_NAME)
        if os.path.exists(cand):
            return cand
        parent = os.path.dirname(probe)
        if parent == probe:
            break
        probe = parent
    return None


def resolve_budget(mcfg, program: str,
                   search_from: Optional[str] = None) -> int:
    """Budget for ``program``: the explicit ``analysis.memory.budgets``
    entry wins, then the committed ledger file, then
    ``default_budget_bytes`` (0 = no gate)."""
    budgets = dict(getattr(mcfg, "budgets", {}) or {})
    if program in budgets:
        return int(budgets[program])
    explicit = getattr(mcfg, "budget_file", "")
    if explicit and os.path.exists(explicit):
        path = explicit
    elif search_from is not None:
        # anchored lookup (CLI *.hlo dumps): the ledger nearest the dump
        # wins over the invoking repo's
        path = find_budget_file(search_from) or ""
    else:
        path = explicit or DEFAULT_BUDGET_NAME
        if not os.path.exists(path):
            path = find_budget_file() or path
    if path and os.path.exists(path):
        ledger = load_budgets(path)
        if program in ledger:
            return int(ledger[program])
    return int(getattr(mcfg, "default_budget_bytes", 0) or 0)


def headroom_pct(budget_bytes: int, peak_bytes: int) -> Optional[float]:
    """Budget headroom as a percentage (positive = under budget), None when
    no positive budget is set — the ONE definition every report shares
    (engine/serving ``memory_report()``)."""
    if not budget_bytes or budget_bytes <= 0:
        return None
    return round(100.0 * (budget_bytes - peak_bytes) / budget_bytes, 2)


def context_from_config(mcfg, program: str, **overrides) -> MemoryRuleContext:
    """Build a :class:`MemoryRuleContext` from an ``analysis.memory`` config
    section (thresholds + the resolved per-program budget)."""
    kw = dict(
        program=program,
        budget_bytes=resolve_budget(mcfg, program),
        check_donation=bool(getattr(mcfg, "check_donation", True)),
        donation_min_bytes=int(getattr(mcfg, "donation_min_bytes", 1 << 16)),
        scratch_max_fraction=float(
            getattr(mcfg, "scratch_max_fraction", 0.25)
        ),
        scratch_min_bytes=int(getattr(mcfg, "scratch_min_bytes", 1 << 20)),
        padding_waste_min_ratio=float(
            getattr(mcfg, "padding_waste_min_ratio", 1.5)
        ),
        padding_waste_min_bytes=int(
            getattr(mcfg, "padding_waste_min_bytes", 1 << 16)
        ),
    )
    kw.update(overrides)
    return MemoryRuleContext(**kw)
