"""Engine B: AST rules — JAX footguns visible in the Python source.

On TPU the per-step host code is as latency-critical as the compiled
program: one stray ``.item()`` in the decode loop serializes the host with
the device every step, one Python branch on a tracer turns a static program
into a recompilation storm. These are all visible in the AST, before
anything runs:

- ``host-sync-in-step``: device→host syncs (``.item()``, ``jax.device_get``,
  ``block_until_ready``, ``np.asarray(<jax expr>)``) inside *hot* functions
  (the scheduler slot loop, ``train_batch``, telemetry sampling —
  ``analysis.hot_function_patterns``).
- ``host-sync-in-traced``: the same calls inside *traced* code (jit-decorated
  or passed to ``jax.jit``/``lax.scan``/…) — there they either fail or
  silently fall out of the program.
- ``tracer-branch``: Python ``if``/``while`` on a traced value (a
  ``jnp``/``jax`` call or an ``.any()/.all()/.sum()``-style reduction in the
  test) inside traced code — retrace-per-value or ConcretizationTypeError.
- ``jnp-in-hot-loop``: ``jnp.*``/``jax.*`` device-op dispatch inside hot
  host functions — the scheduler's per-request/per-step path should hand the
  compiled executable plain numpy and let XLA do the rest.
- ``missing-donate-argnums``: ``jax.jit(<step/prefill/decode/train fn>)``
  without ``donate_argnums`` — a large-pytree program that copies instead of
  aliasing doubles its HBM footprint.
- ``unstable-cache-key``: compile-cache keys built from ``id(...)`` (unstable
  across runs and objects — cache never hits, executables pile up) or from
  unhashable literals.

Each rule can be silenced with ``# dslint: disable=<rule>`` on the flagged
line or the line above — the suppression carries the justification.
"""

from __future__ import annotations

import ast
import fnmatch
from typing import List, Optional, Sequence

from .findings import (
    SEVERITY_ERROR,
    SEVERITY_WARNING,
    Finding,
    SuppressionIndex,
    apply_suppressions,
)

RULES = {
    "host-sync-in-step":
        "device→host sync in a hot (per-step / per-request) host function",
    "host-sync-in-traced":
        "device→host sync inside traced (jit/scan) code",
    "tracer-branch":
        "Python branch on a traced value inside traced code",
    "jnp-in-hot-loop":
        "jnp/jax device-op dispatch in a hot host function",
    "missing-donate-argnums":
        "jax.jit of a step-like function without donate_argnums",
    "unstable-cache-key":
        "compile-cache keyed on id()/unhashable values",
}

DEFAULT_HOT_PATTERNS = [
    "ServingEngine.step", "ServingEngine._step", "ServingEngine.run", "ServingEngine._admit",
    "ServingEngine._finish_slot", "ServingEngine.submit",
    # ISSUE 10: chunked prefill runs once per scheduler step while a slot
    # prefills, and _start_decoding is the per-admission transition _admit
    # used to carry — both stay under the hot-path lint
    "ServingEngine._advance_chunks", "ServingEngine._launch_chunk",
    "ServingEngine._launch_alone", "ServingEngine._start_decoding",
    # ISSUE 54: _step's launches, its one fetch and the two sides of a
    # prompt's end are methods of their own — all of them the step's path
    "ServingEngine._launch", "ServingEngine._dispatch", "ServingEngine._resolve",
    "ServingEngine.settle", "ServingEngine._rows_due", "ServingEngine._arm",
    "ServingEngine._first_token",
    "ServingEngine._draft", "ServingEngine._accept_tokens",
    "*.train_batch", "*._train_batch", "*._print_cadence", "*.eval_batch",
    "*._telemetry_step", "*._watchdog_step",
    "InferenceEngine.generate",
]

DEFAULT_DONATE_PATTERNS = ["*step*", "*prefill*", "*decode*", "*train*"]

# entry points whose function-valued arguments become traced code
# (pallas_call included: an ops/pallas kernel body is traced code too — a
# host sync or value-branch inside one is exactly as fatal as under jit)
_TRACE_ENTRY = (
    "jax.jit", "jit", "pjit", "jax.pjit",
    "lax.scan", "jax.lax.scan", "lax.while_loop", "jax.lax.while_loop",
    "lax.cond", "jax.lax.cond", "lax.fori_loop", "jax.lax.fori_loop",
    "shard_map", "jax.checkpoint", "jax.remat", "checkpoint", "remat",
    "jax.vmap", "vmap", "jax.grad", "jax.value_and_grad",
    "pallas_call", "pl.pallas_call",
)

# jax.* call chains that are host-side bookkeeping, not device-op dispatch
_HOST_SIDE_JAX = (
    "jax.tree", "jax.tree_util", "jax.ShapeDtypeStruct", "jax.device_get",
    "jax.block_until_ready", "jax.profiler", "jax.monitoring", "jax.config",
    "jax.devices", "jax.local_devices", "jax.device_count",
    "jax.local_device_count", "jax.process_count", "jax.process_index",
    "jax.named_scope", "jax.debug", "jax.eval_shape", "jax.clear_caches",
    "jax.live_arrays", "jax.typeof",
)

_REDUCTION_ATTRS = ("any", "all", "sum", "max", "min", "mean", "item")


def _chain(node: ast.AST) -> str:
    """Dotted name of a Name/Attribute chain ('' when not a plain chain)."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return ""


def _is_device_chain(chain: str) -> bool:
    if not chain:
        return False
    root = chain.split(".", 1)[0]
    if root not in ("jax", "jnp"):
        return False
    return not any(
        chain == h or chain.startswith(h + ".") for h in _HOST_SIDE_JAX
    )


def _contains_device_call(node: ast.AST) -> bool:
    for sub in ast.walk(node):
        if isinstance(sub, ast.Call) and _is_device_chain(_chain(sub.func)):
            return True
    return False


def _host_sync_kind(call: ast.Call) -> Optional[str]:
    """Classify a Call as a device→host sync, or None."""
    chain = _chain(call.func)
    if chain.endswith(".item") and not call.args and not call.keywords:
        return ".item()"
    if chain.endswith("block_until_ready"):
        return "block_until_ready"
    if chain == "jax.device_get" or chain.endswith(".device_get"):
        return "jax.device_get"
    if chain in ("np.asarray", "numpy.asarray", "np.array", "numpy.array"):
        if any(_contains_device_call(a) for a in call.args):
            return f"{chain}(<jax expr>)"
    return None


class _FuncInfo:
    def __init__(self, node, qualname, traced, hot):
        self.node = node
        self.qualname = qualname
        self.traced = traced
        self.hot = hot


class _Linter:
    def __init__(self, path: str, tree: ast.Module, source: str,
                 hot_patterns: Sequence[str],
                 donate_patterns: Sequence[str]):
        self.path = path
        self.tree = tree
        self.lines = source.splitlines()
        self.hot_patterns = list(hot_patterns)
        self.donate_patterns = list(donate_patterns)
        self.findings: List[Finding] = []
        self.traced_names = self._collect_traced_names()

    # -- traced / hot classification ----------------------------------
    def _collect_traced_names(self) -> set:
        """Function names passed by name to a trace entry point anywhere in
        the module (``jax.jit(step_fn)``, ``lax.scan(body, ...)``)."""
        names = set()
        for node in ast.walk(self.tree):
            if not isinstance(node, ast.Call):
                continue
            if _chain(node.func) in _TRACE_ENTRY:
                for arg in list(node.args) + [kw.value for kw in node.keywords]:
                    if isinstance(arg, ast.Name):
                        names.add(arg.id)
        return names

    def _is_traced_def(self, node) -> bool:
        for dec in node.decorator_list:
            for sub in ast.walk(dec):
                chain = _chain(sub) if isinstance(sub, (ast.Name, ast.Attribute)) else ""
                if chain in _TRACE_ENTRY:
                    return True
        return node.name in self.traced_names

    def _is_hot(self, qualname: str, name: str) -> bool:
        return any(
            fnmatch.fnmatch(qualname, p) or fnmatch.fnmatch(name, p)
            for p in self.hot_patterns
        )

    # -- driving -------------------------------------------------------
    def run(self) -> List[Finding]:
        self._scan_block(self.tree.body, prefix="", symbol="<module>")
        return self.findings

    def _scan_block(self, stmts, prefix, symbol):
        """Module/class level: route function defs to the per-function
        checks, everything else to the everywhere-rules."""
        for stmt in stmts:
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self._handle_function(
                    stmt, f"{prefix}{stmt.name}",
                    traced=self._is_traced_def(stmt),
                )
            elif isinstance(stmt, ast.ClassDef):
                self._scan_block(stmt.body, f"{stmt.name}.", stmt.name)
            else:
                for sub in ast.walk(stmt):
                    self._check_common_node(sub, symbol)

    def _handle_function(self, fn, qualname, traced):
        # a nested def inside a hot function is a traced closure being
        # built, not itself hot host code — hot never propagates down
        hot = (not traced) and self._is_hot(qualname, fn.name)
        self._check_function(fn, qualname, traced, hot)
        for sub in self._nested_defs(fn):
            self._handle_function(
                sub, f"{qualname}.{sub.name}",
                traced=traced or self._is_traced_def(sub),
            )

    def _nested_defs(self, fn):
        """Function defs directly nested in ``fn`` (not transitively)."""
        out, stack = [], list(fn.body)
        while stack:
            node = stack.pop()
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                out.append(node)
                continue
            if isinstance(node, ast.ClassDef):
                stack.extend(node.body)
                continue
            stack.extend(ast.iter_child_nodes(node))
        return out

    # -- per-function checks ------------------------------------------
    def _check_function(self, fn, qualname, traced, hot):
        for node in self._function_nodes(fn):
            self._check_common_node(node, qualname)
            if isinstance(node, ast.Call):
                sync = _host_sync_kind(node)
                if sync and traced:
                    self._emit(
                        "host-sync-in-traced", SEVERITY_ERROR, node, qualname,
                        f"{sync} inside traced code — the sync either fails "
                        "under jit or silently leaves the program",
                    )
                elif sync and hot:
                    self._emit(
                        "host-sync-in-step", SEVERITY_ERROR, node, qualname,
                        f"{sync} in a hot per-step path serializes the host "
                        "with the device every iteration",
                    )
                elif hot and not traced:
                    chain = _chain(node.func)
                    if _is_device_chain(chain):
                        self._emit(
                            "jnp-in-hot-loop", SEVERITY_WARNING, node,
                            qualname,
                            f"{chain}() dispatches a device op from the hot "
                            "host loop — precompute, or pass numpy straight "
                            "to the compiled executable",
                        )
            if traced and isinstance(node, (ast.If, ast.While)):
                test = node.test
                if self._is_traced_value(test):
                    self._emit(
                        "tracer-branch", SEVERITY_ERROR, node, qualname,
                        "Python branch on a traced value — use lax.cond / "
                        "jnp.where (this retraces per value or raises "
                        "ConcretizationTypeError)",
                    )

    def _function_nodes(self, fn):
        """Walk a function body, NOT descending into nested defs (they are
        classified and checked separately)."""
        stack = list(fn.body)
        while stack:
            node = stack.pop()
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.ClassDef, ast.Lambda)):
                continue
            yield node
            for child in ast.iter_child_nodes(node):
                stack.append(child)

    def _is_traced_value(self, test: ast.AST) -> bool:
        for sub in ast.walk(test):
            if isinstance(sub, ast.Call):
                chain = _chain(sub.func)
                if _is_device_chain(chain):
                    return True
                if chain.split(".")[-1] in _REDUCTION_ATTRS and \
                        isinstance(sub.func, ast.Attribute):
                    return True
        return False

    # -- everywhere checks --------------------------------------------
    def _check_common_node(self, node, symbol):
        if isinstance(node, ast.Call):
            self._check_missing_donate(node, symbol)
            self._check_cache_key_call(node, symbol)
        elif isinstance(node, ast.Subscript):
            self._check_cache_key_subscript(node, symbol)

    def _check_missing_donate(self, call: ast.Call, symbol):
        if _chain(call.func) not in ("jax.jit", "jit", "pjit", "jax.pjit"):
            return
        if not call.args or not isinstance(call.args[0], ast.Name):
            return
        name = call.args[0].id
        if not any(fnmatch.fnmatch(name.lower(), p)
                   for p in self.donate_patterns):
            return
        if any(kw.arg in ("donate_argnums", "donate_argnames")
               for kw in call.keywords):
            return
        self._emit(
            "missing-donate-argnums", SEVERITY_WARNING, call, symbol,
            f"jax.jit({name}) without donate_argnums — a step-like program "
            "that copies its state instead of aliasing doubles its HBM "
            "footprint",
        )

    def _cacheish(self, node) -> bool:
        chain = _chain(node)
        return "cache" in chain.split(".")[-1].lower() if chain else False

    def _check_cache_key_subscript(self, node: ast.Subscript, symbol):
        if not self._cacheish(node.value):
            return
        key = node.slice
        if any(isinstance(s, ast.Call) and _chain(s.func) == "id"
               for s in ast.walk(key)):
            self._emit(
                "unstable-cache-key", SEVERITY_WARNING, node, symbol,
                "cache keyed on id(...) — unstable across objects/runs, the "
                "cache never hits and executables pile up",
            )
        elif isinstance(key, (ast.List, ast.Dict, ast.Set)):
            self._emit(
                "unstable-cache-key", SEVERITY_WARNING, node, symbol,
                "unhashable literal used as a cache key",
            )

    def _check_cache_key_call(self, call: ast.Call, symbol):
        if not isinstance(call.func, ast.Attribute):
            return
        if call.func.attr not in ("get", "setdefault", "pop"):
            return
        if not self._cacheish(call.func.value) or not call.args:
            return
        if any(isinstance(s, ast.Call) and _chain(s.func) == "id"
               for s in ast.walk(call.args[0])):
            self._emit(
                "unstable-cache-key", SEVERITY_WARNING, call, symbol,
                "cache keyed on id(...) — unstable across objects/runs, the "
                "cache never hits and executables pile up",
            )

    def _emit(self, rule, severity, node, symbol, message):
        line = getattr(node, "lineno", 0)
        snippet = self.lines[line - 1].strip() if 0 < line <= len(self.lines) else ""
        self.findings.append(Finding(
            rule=rule, severity=severity, message=message, path=self.path,
            line=line, symbol=symbol, snippet=snippet, engine="ast",
        ))


def lint_source(
    source: str,
    path: str = "<string>",
    hot_patterns: Optional[Sequence[str]] = None,
    donate_patterns: Optional[Sequence[str]] = None,
):
    """Lint one Python source string → (findings, suppressed_count).

    Raises SyntaxError upward — an unparseable file is the caller's problem
    to report (the CLI turns it into a usage-class error)."""
    tree = ast.parse(source, filename=path)
    linter = _Linter(
        path, tree, source,
        hot_patterns if hot_patterns is not None else DEFAULT_HOT_PATTERNS,
        donate_patterns if donate_patterns is not None else DEFAULT_DONATE_PATTERNS,
    )
    findings = linter.run()
    findings.sort(key=lambda f: (f.path, f.line, f.rule))
    # two calls on one line produce identical fingerprints — report once
    seen, unique = set(), []
    for f in findings:
        key = (f.rule, f.path, f.line)
        if key not in seen:
            seen.add(key)
            unique.append(f)
    return apply_suppressions(unique, SuppressionIndex.from_source(source))


def lint_file(path: str, hot_patterns=None, donate_patterns=None):
    with open(path, encoding="utf-8") as fh:
        return lint_source(
            fh.read(), path=path,
            hot_patterns=hot_patterns, donate_patterns=donate_patterns,
        )
