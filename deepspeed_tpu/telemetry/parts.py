"""Device time by model part: named scopes in the programs, and a part table
read from each compiled program.

A trace names a device operation by its HLO instruction (``%fusion.123``) and
says nothing of what it computes. The program does: every instruction of the
optimised HLO carries ``metadata={op_name="jit(step)/transpose(jvp())/.../
dspart.mlp/dot_general"}``, and a ``jax.named_scope`` survives ``jvp``,
``transpose`` and ``checkpoint``. So the package opens ONE closed vocabulary of
scopes (:data:`PARTS`, through :func:`part` alone) where the work is traced,
and :func:`table_of` reads from a compiled program's text, for every
instruction of every computation, which part it belongs to, in which pass
(:data:`PHASES`), whether it multiplies matrices, and whether the compiler
fused it across a part boundary. A reader joins a trace's operation events to
the table by (module, instruction name): ``perfbench/program_parts.py`` for
the benchmark, :func:`dump` for an operator's own profile
(docs/OBSERVABILITY.md).

A scope is metadata: it changes no instruction. Programs register a CALLABLE
that gives their text (:func:`register`), so with no reader nothing is
rendered or parsed: the cost is the scopes' context managers at trace time and
one dict entry a program.
"""

from __future__ import annotations

import contextlib
import functools
import json
import logging
import re
import time
from typing import Callable, Dict, List, NamedTuple, Optional, Tuple

import jax

from .introspect import (
    DOT_OPS as _DOT_OPS, DTYPE_BYTES, MOSAIC as _MOSAIC, OP_NAME as _OP_NAME, instructions_by_computation,
    shape_bytes,
)

logger = logging.getLogger(__name__)

# jax leaves metadata out of its compilation-cache key, so an executable cached by a build
# whose scopes differ (the build before this module had none) would be loaded with THAT
# build's op_names and every table would read it: the names are part of the key here
jax.config.update("jax_compilation_cache_include_metadata_in_key", True)

PREFIX = "dspart."
PARTS = (
    "embed",        # token and position embeddings
    "norm",         # the residual stream's norms
    "attn.qkv",     # projections, biases, QK norm, rotary, the absorbed w_uk product
    "attn.cca",     # what CCA adds between the projections and the kernel: two short convolutions over the latent,
                    # the q-k mean, norm, temperature, rotary and the value shift (models/zaya.py)
    "attn.core",    # the attention kernel or its jnp form, and all between the projections and it
    "attn.out",     # the output projection
    "kv.write",     # token and page writes into pools and rings
    "mlp",          # a dense MLP and a shared expert
    "mlp.dense",    # a double layer's two dense FFNs, beside its expert layer (models/longcat_flash.py)
    "moe.route",    # router logits, top-k, the sort, gathers, scatters and the combine
    "moe.experts",  # the held experts' products, masked or grouped
    "ssm.proj",     # a state-space mixer's and a gated memory unit's projections and gates
    "ssm.scan",     # the short convolution and the recurrence, kernel or lax.scan
    "lin.proj",     # a linear attention's (Gated DeltaNet) projections, gates, output norm and gate
    "lin.scan",     # its short convolution and the gated delta rule, kernel or lax form
    "hc.mix",       # a multi-stream residual's mixing around a sub-block (mHC): the maps from the stream, the
                    # pre-mixed row, the write back through H_res and H_post; kernel pair or jnp (models/xing4.py)
    "head",         # final norm, logits, the loss in training
    "sample",       # sampling
    "optim",        # gradient norm and clip, AdamW, casts of masters, loss scaling
)
PHASES = ("fwd", "bwd", "recompute", "none")
_PART_SET = frozenset(PARTS)


def part(name: str):
    """The scope of one model part: ``with parts.part("mlp"): ...``. The
    innermost open scope names an instruction. A name outside :data:`PARTS`
    is refused: the readers' metrics are written against the list."""
    if name not in _PART_SET:
        raise ValueError(f"parts.part: {name!r} is not one of {PARTS}")
    return jax.named_scope(PREFIX + name)


def scoped(name: str):
    """:func:`part` as a decorator: the whole call runs under the scope
    (opened at each call, as :func:`part` is then bound)."""
    def deco(fn):
        @functools.wraps(fn)
        def run(*args, **kwargs):
            with part(name):
                return fn(*args, **kwargs)
        return run
    return deco


def unscoped():
    """No scope open, around a Pallas call the package gives no name. XLA names
    such a kernel's custom call after the innermost scope open around it (a
    flash kernel is ``closed_call``, ``checkpoint`` or ``rematted_computation``
    after the pass it runs in, the decode program's is ``decode_fn``), and the
    benchmark's patterns know it by that name: a ``dspart.*`` scope there would
    rename it. A ``custom_vjp`` call hands its scope to the kernels of both its
    rules, so the hole is opened around that call. The table gives a kernel
    with no part the part of the file its call is written in
    (:data:`KERNEL_FILES`) or, where a transform rewrote that, the part that
    the instructions around it agree on."""
    try:
        from jax._src.source_info_util import reset_name_stack
    except ImportError:   # another jax: the kernels take a scope's name, and nothing else changes
        return contextlib.nullcontext()
    return reset_name_stack()


# where an unnamed kernel's call is written -> its part (the end of the path)
KERNEL_FILES = {
    "ops/pallas/flash_attention.py": "attn.core",
    "ops/pallas/decode_attention.py": "attn.core",
    "ops/pallas/selective_scan.py": "ssm.scan",
    "ops/pallas/gated_delta.py": "lin.scan",
    "ops/pallas/hyper_connection.py": "hc.mix",
}


class Entry(NamedTuple):
    """What the table knows of one instruction."""
    part: Optional[str]             # None: no scope, and no rule gave it one
    phase: str                      # one of PHASES
    has_dot: bool                   # it, or its fused computation, multiplies matrices (or is a Mosaic kernel)
    parts_inside: Tuple[str, ...]   # distinct parts of its fused computation's instructions; > 1: mixed
    op_name: str                    # "" for an instruction the compiler inserted
    source: str                     # "file:line" of the frame that made it, "" where the text has none


# -- the op_name -------------------------------------------------------------

_PART_IN_NAME = re.compile(re.escape(PREFIX) + r"([a-z]+(?:\.[a-z]+)*)")
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_FRAME = re.compile(r"stack_frame_id=(\d+)")
_MODULE = re.compile(r"^HloModule\s+([\w.\-]+)", re.M)
_CARRIES = frozenset(("tuple", "while", "conditional", "call"))


def part_of(op_name: str) -> Optional[str]:
    """The innermost ``dspart.*`` scope of an ``op_name`` (the last: scopes
    nest left to right, inside a transform's parentheses or along the path)."""
    found = [p for p in _PART_IN_NAME.findall(op_name) if p in _PART_SET]
    return found[-1] if found else None


def phase_of(op_name: str) -> str:
    """The pass an instruction belongs to, from the wrappers around its scope:
    the forward run again under remat, the backward, the forward, or neither
    (the optimizer; every instruction of a served program)."""
    if "rematted_computation" in op_name:
        return "recompute"
    if "transpose(" in op_name:
        return "bwd"
    if "jvp(" in op_name:
        return "fwd"
    return "none"


def module_name(hlo_text: str) -> Optional[str]:
    """The ``HloModule`` name: what a trace's line of programs shows."""
    m = _MODULE.search(hlo_text)
    return m.group(1) if m else None


def _frames(hlo_text: str) -> Dict[str, str]:
    """``stack_frame_id`` → ``file:line``, from the tables a module's text
    opens with (FileNames, FileLocations, StackFrames); {} where it has none."""
    files: Dict[str, str] = {}
    locs: Dict[str, str] = {}
    frames: Dict[str, str] = {}
    section = ""
    for line in hlo_text.splitlines():
        s = line.strip()
        if s.endswith("{"):   # the first computation: the tables are over
            break
        if s in ("FileNames", "FunctionNames", "FileLocations", "StackFrames"):
            section = s
            continue
        if not s or not s[0].isdigit():
            continue
        key, _, rest = s.partition(" ")
        if section == "FileNames":
            files[key] = rest.strip('"')
        elif section == "FileLocations":
            m = re.search(r"file_name_id=(\d+).*?\bline=(\d+)", rest)
            if m:
                locs[key] = f"{files.get(m.group(1), '?')}:{m.group(2)}"
        elif section == "StackFrames":
            m = re.search(r"file_location_id=(\d+)", rest)
            if m and m.group(1) in locs:
                frames[key] = locs[m.group(1)]
    return frames


# -- the table ---------------------------------------------------------------

class _Instr(NamedTuple):
    op: str
    operands: Tuple[str, ...]
    op_name: str
    source: str
    calls: Optional[str]   # a fusion's fused computation
    own_dot: bool
    is_root: bool


def table_of(hlo_text: str) -> Dict[str, Entry]:
    """``{instruction name: Entry}`` over ALL computations of one optimised
    HLO module (loop bodies and called computations run as events of their
    own; instruction names are unique in a module).

    ``part`` and ``phase`` come from the instruction's own ``op_name`` (a
    fusion's is its root's). ``has_dot``: the instruction or its fused
    computation, nested fusions included, holds a ``dot``, a ``convolution``
    (what a dot is on a TPU), a ``ragged-dot`` or a Mosaic ``custom-call``.
    ``parts_inside``: the distinct parts of the fused computation's
    instructions: a fusion the compiler drew across a boundary is charged to
    its root's part and counted as mixed. An instruction the compiler inserted
    without an ``op_name`` (layout copies, bitcasts, ``copy-start`` / ``-done``,
    ``async-collective-done``) or under an argument's name (a copy of a
    parameter) takes part and phase of the instructions that read it, where
    those agree (its one consumer, as a rule) or, failing that, of those that
    made its operands; else ``None``. A Mosaic kernel called under
    :func:`unscoped` takes its part from :data:`KERNEL_FILES` or, where a
    transform rewrote where its call is written, from what surrounds it."""
    frames = _frames(hlo_text)
    instrs: Dict[str, _Instr] = {}
    by_comp: Dict[str, List[str]] = {}
    for comp, parsed in instructions_by_computation(hlo_text).items():
        for ni in parsed:
            m = _OP_NAME.search(ni.attrs)
            calls = _CALLS.search(ni.attrs) if ni.op == "fusion" else None
            frame = _FRAME.search(ni.attrs)
            instrs[ni.name] = _Instr(
                ni.op, tuple(ni.operands), m.group(1) if m else "",
                frames.get(frame.group(1), "") if frame else "", calls.group(1) if calls else None,
                ni.op in _DOT_OPS or (ni.op == "custom-call" and _MOSAIC in ni.attrs),
                ni.is_root,
            )
            by_comp.setdefault(comp, []).append(ni.name)

    inside_memo: Dict[str, Tuple[bool, frozenset]] = {}

    def inside(comp: str) -> Tuple[bool, frozenset]:
        """(holds a dot, the parts of its instructions), nested fusions included."""
        if comp not in inside_memo:
            inside_memo[comp] = (False, frozenset())   # a cycle cannot occur; a guard costs nothing
            dot, found = False, set()
            for name in by_comp.get(comp, ()):
                i = instrs[name]
                p = part_of(i.op_name)
                if p:
                    found.add(p)
                dot = dot or i.own_dot
                if i.calls:
                    d, f = inside(i.calls)
                    dot, found = dot or d, found | f
            inside_memo[comp] = (dot, frozenset(found))
        return inside_memo[comp]

    def own_name(name: str) -> str:
        i = instrs[name]
        if i.op_name or not i.calls:
            return i.op_name
        return next((instrs[n].op_name for n in by_comp.get(i.calls, ()) if instrs[n].is_root), "")

    def is_kernel(name: str) -> bool:
        return instrs[name].op == "custom-call" and instrs[name].own_dot

    # what each instruction says of itself. An operation's op_name is a path from the jitted
    # function to a primitive; a copy or a slice the compiler made of an ARGUMENT carries the
    # argument's name ("p['wte']"), which names no operation: it is the compiler's own
    shown = {name: own_name(name) for name in instrs}
    names = {name: op_name if "/" in op_name else "" for name, op_name in shown.items()}
    known: Dict[str, Tuple[Optional[str], str]] = {}
    for name, op_name in names.items():
        if not op_name:
            continue
        p = part_of(op_name)
        if p is None and is_kernel(name):   # called under `unscoped`: where its call is written
            src = instrs[name].source.rsplit(":", 1)[0]
            p = next((v for k, v in KERNEL_FILES.items() if src.endswith(k)), None)
        known[name] = (p, phase_of(op_name))

    consumers: Dict[str, List[str]] = {}
    for name, i in instrs.items():
        for o in set(i.operands):
            if o in instrs:
                consumers.setdefault(o, []).append(name)
    producers = {name: [o for o in i.operands if o in instrs] for name, i in instrs.items()}

    def around(name: str, through_named: bool) -> Optional[Tuple[str, str]]:
        """(part, phase) of the nearest instructions that have a part, where
        they agree on it: the consumers' or, failing that, the producers',
        walking through the instructions the compiler inserted (and, for a
        kernel, through those that name no part). A loop's state says nothing
        of who made it: the walk stops there."""
        for edges in (consumers, producers):
            found: Dict[str, str] = {}
            seen, todo = {name}, list(edges.get(name, ()))
            while todo and len(seen) < 256:
                n = todo.pop()
                if n in seen:
                    continue
                seen.add(n)
                p, ph = known.get(n, (None, "none"))
                if p is not None:
                    found.setdefault(p, ph)
                elif instrs[n].op not in _CARRIES and (through_named or not names[n]):
                    todo.extend(edges.get(n, ()))
            if len(found) == 1:
                return next(iter(found.items()))
        return None

    # what the compiler inserted takes its part from what it serves; then the kernels whose
    # call a transform moved (their phase is their own)
    known.update({
        name: around(name, through_named=False) or (None, "none")
        for name, op_name in names.items() if not op_name
    })
    for name in [n for n in instrs if is_kernel(n) and known[n][0] is None]:
        got = around(name, through_named=True)
        if got:
            known[name] = (got[0], known[name][1])

    table: Dict[str, Entry] = {}
    for name, i in instrs.items():
        dot, found = inside(i.calls) if i.calls else (False, frozenset())
        table[name] = Entry(*known[name], i.own_dot or dot, tuple(sorted(found)), shown[name], i.source)
    return table


# -- the optimizer's passes --------------------------------------------------

class OptimTraffic(NamedTuple):
    """What the instructions of part ``optim`` move, from one program's text."""
    read: int                       # bytes of their operands
    written: int                    # bytes of their results
    passes: int                     # of them, those with a result shaped like a whole leaf of the masters
    leaf_results: Tuple[Tuple[str, str, Tuple[int, ...]], ...]   # (instruction, dtype, dims) of each such result


# no traffic of their own: views, what carries other computations, and the end of an async pair
_NO_TRAFFIC = frozenset(("bitcast", "get-tuple-element", "tuple", "parameter", "constant", "after-all",
                         "partition-id", "replica-id")) | _CARRIES


def optim_traffic(hlo_text: str, leaf_shapes) -> OptimTraffic:
    """The bytes that the instructions of part ``optim`` read and write in ONE
    run of an optimised HLO module, and how many of them write a whole leaf:
    a result of exactly a leaf's dimensions (``leaf_shapes``: the shapes of
    the masters' leaves as ONE device holds them). Counted are the
    instructions that run as operations of their own (not those inside a
    fused computation); an operand counts whole, once an instruction, and an
    asynchronous copy's bytes count though it is no pass (so a leaf copied
    into fast memory ahead of the fusion that reads it counts twice). An
    update that reads each master and moment once and writes it once gives
    one such instruction a leaf; a cast of the masters, a gradient made
    float32, a leaf re-laid for the update and back each add one. Shapes and
    types only: the same on any backend, no trace needed."""
    comps = instructions_by_computation(hlo_text)
    by_name = {ni.name: ni for parsed in comps.values() for ni in parsed}
    fused = {c for parsed in comps.values() for ni in parsed if ni.op == "fusion"
             for c in _CALLS.findall(ni.attrs)}
    leaves = {tuple(int(d) for d in shape) for shape in leaf_shapes}
    table = table_of(hlo_text)
    read = written = 0
    leaf_results = []
    for comp, parsed in comps.items():
        if comp in fused:
            continue
        for ni in parsed:
            if table[ni.name].part != "optim" or ni.op in _NO_TRAFFIC or ni.op.endswith("-done"):
                continue
            shapes = ni.result_shapes
            prefetch = ni.op.endswith("-start")
            if prefetch:   # (operand alias, result, context): the result alone is written
                shapes = shapes[1:2]
            read += sum(by_name[o].result_bytes for o in set(ni.operands) if o in by_name)
            written += sum(shape_bytes(dt, dd) for dt, dd in shapes if dt in DTYPE_BYTES)
            for dt, dd in () if prefetch else shapes:   # a copy into fast memory ahead of its reader is no pass
                dims = tuple(int(d) for d in dd.split(",") if d)
                if dims in leaves:
                    leaf_results.append((ni.name, dt, dims))
    passes = len({name for name, _, _ in leaf_results})
    return OptimTraffic(read, written, passes, tuple(leaf_results))


# -- the registry ------------------------------------------------------------

_programs: Dict[str, Callable[[], Optional[str]]] = {}
_built: Dict[str, Tuple[str, Dict[str, Entry], dict]] = {}   # registered name -> (module, table, cost)


def register(name: str, text_fn: Callable[[], Optional[str]]) -> None:
    """A compiled program under the name a trace's line of programs shows
    (``jit_decode_fn``, ``jit_train_step``). ``text_fn`` gives the optimised
    HLO text when a reader asks (``lambda: exe.as_text()``), or ``None`` where
    the program is gone; it is stored uncalled. Registering a name again
    replaces the program and forgets its table."""
    _programs[name] = text_fn
    _built.pop(name, None)


def registered() -> Tuple[str, ...]:
    return tuple(_programs)


def _materialise() -> None:
    for name, text_fn in list(_programs.items()):
        if name in _built:
            continue
        t0 = time.perf_counter()
        try:
            text = text_fn()
        except Exception:   # a reader's boundary: one program's failure must not take the others' tables
            logger.exception("parts: the text of program %r could not be read", name)
            text = None
        if text is None:
            continue
        table = table_of(text)
        _built[name] = (module_name(text) or name, table, {
            "bytes": len(text), "instructions": len(table),
            "seconds": time.perf_counter() - t0,
        })


def tables() -> Dict[str, Dict[str, Entry]]:
    """``{HloModule name: table}`` of every registered program, built on the
    first call and cached (a program registered since is built then)."""
    _materialise()
    return {module: table for module, table, _ in _built.values()}


def costs() -> Dict[str, dict]:
    """What each built table cost: the bytes of the program's text, its
    instructions and the seconds to render and parse it, by HloModule name."""
    return {module: dict(cost) for module, _, cost in _built.values()}


def dump(path: str) -> None:
    """The tables as JSON (``{module: {instruction: [part, phase, has_dot,
    parts_inside, op_name, source]}}``), to lie beside a profile."""
    with open(path, "w") as f:
        json.dump({m: {k: list(e) for k, e in t.items()} for m, t in tables().items()}, f)


def clear() -> None:
    """Forget every program and table (tests)."""
    _programs.clear()
    _built.clear()
