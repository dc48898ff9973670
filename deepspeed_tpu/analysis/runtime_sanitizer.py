"""Runtime concurrency sanitizer: observed schedules cross-check Engine C.

Engine C (``concurrency_rules``) reasons about locks and threads statically;
this module is the dynamic half. When enabled (the ``analysis.sanitizer``
config knob, or directly in ``dsan``-marked tests), concurrency-bearing
modules build their locks through :func:`maybe_lock` and annotate shared
attribute accesses with :func:`note_read`/:func:`note_write`. The sanitizer
then records, from REAL executions:

- the lock-acquisition order actually observed per thread (edges ``A→B``
  when ``B`` is acquired while ``A`` is held), and
- every cross-thread attribute access with the lock set held at that
  instant.

:meth:`RuntimeSanitizer.findings` converts violations into the same
:class:`~.findings.Finding` model the static engines report (engine
``"dsan"``, pseudo-path ``dsan://runtime``): an observed lock-order cycle is
a ``lock-order-cycle``, and a key written by one thread and touched by
another with disjoint held-lock sets is a ``shared-state-unlocked``. The
static graph says what *could* interleave; the sanitizer says what *did* —
a rule firing in both is a confirmed bug, one firing only statically is a
candidate for a justified waiver.

Cost: one tuple append per lock acquire and one dict update per annotated
access — and ONLY while a sanitizer is installed. When
``analysis.sanitizer`` is disabled, ``note_read``/``note_write`` are
rebound to empty no-op functions and :class:`SanitizedLock` skips its
recording branch, so the instrumented hot paths (``StepTracer.emit``, the
checkpoint writer) pay nothing but the call itself (ISSUE 9 satellite: on
the CPU the active recorder cost a third of the instrumented emit
micro-path; not measured on the chip).
"""

from __future__ import annotations

import threading
from typing import Dict, List, Optional, Set, Tuple

from .findings import SEVERITY_ERROR, Finding

RULES = {
    "shared-state-unlocked":
        "observed cross-thread access with disjoint held-lock sets",
    "lock-order-cycle":
        "observed lock-acquisition orders form a cycle",
}

_ACTIVE: Optional["RuntimeSanitizer"] = None


def enable(sanitizer: "RuntimeSanitizer") -> "RuntimeSanitizer":
    """Install ``sanitizer`` as the process-wide active recorder (and swap
    the live ``note_*`` implementations in)."""
    global _ACTIVE, note_read, note_write
    _ACTIVE = sanitizer
    note_read, note_write = _note_read_active, _note_write_active
    return sanitizer


def disable() -> None:
    """Uninstall the recorder and rebind ``note_*`` to the no-ops, so
    disabled runs pay nothing on the instrumented paths (ISSUE 9)."""
    global _ACTIVE, note_read, note_write
    _ACTIVE = None
    note_read, note_write = _note_noop, _note_noop


def active() -> Optional["RuntimeSanitizer"]:
    return _ACTIVE


def from_config(config) -> Optional["RuntimeSanitizer"]:
    """Build + install from an ``analysis.sanitizer`` config section.

    A config with ``enabled=False`` actively UNINSTALLS any process-wide
    sanitizer (the engine's config owns the global: an engine that opted
    out must not inherit a previous engine's instrumentation or keep its
    record tables alive). ``config=None`` (no section at all) leaves a
    manually ``enable()``-d sanitizer untouched."""
    if config is None:
        return None
    if not getattr(config, "enabled", False):
        disable()
        return None
    return enable(RuntimeSanitizer(
        max_events=int(getattr(config, "max_events", 65536))
    ))


def maybe_lock(name: str):
    """A lock for ``name``: instrumented under an active sanitizer, a plain
    ``threading.Lock`` otherwise (the zero-cost passthrough). A
    ``SanitizedLock`` created while enabled also stops recording the moment
    its sanitizer is uninstalled, so a long-lived lock never pins a dead
    recorder's overhead."""
    if _ACTIVE is not None:
        return _ACTIVE.lock(name)
    return threading.Lock()


def _note_noop(owner, attr: str) -> None:
    """The disabled-mode ``note_*``: an empty function — no global read,
    no branch. ``enable()``/``disable()`` rebind the module-level names."""


def _note_read_active(owner, attr: str) -> None:
    san = _ACTIVE
    if san is not None:
        san.note(owner, attr, "read")


def _note_write_active(owner, attr: str) -> None:
    san = _ACTIVE
    if san is not None:
        san.note(owner, attr, "write")


# live bindings: enable()/disable() swap these between the active
# implementations and the no-op (import the MODULE, not the function, to
# observe the swap — tracer.py and writer.py already do)
note_read = _note_noop
note_write = _note_noop


class SanitizedLock:
    """``threading.Lock`` wrapper that reports acquisition order."""

    def __init__(self, sanitizer: "RuntimeSanitizer", name: str):
        self._san = sanitizer
        self.name = name
        self._lock = threading.Lock()

    def acquire(self, blocking: bool = True, timeout: float = -1) -> bool:
        ok = self._lock.acquire(blocking, timeout)
        # record only while OUR sanitizer is still the installed one — a
        # lock that outlives its sanitizer degrades to a plain mutex
        # (ISSUE 9: no-op passthrough when analysis.sanitizer is disabled)
        if ok and _ACTIVE is self._san:
            self._san._on_acquire(self.name)
        return ok

    def release(self) -> None:
        # unconditional: _on_release only pops this lock from the thread's
        # held tuple (a no-op if acquire skipped the push), so a disable()
        # that lands mid-hold cannot strand a stale held entry that would
        # fabricate order edges after a later re-enable()
        self._san._on_release(self.name)
        self._lock.release()

    def locked(self) -> bool:
        return self._lock.locked()

    def __enter__(self) -> "SanitizedLock":
        self.acquire()
        return self

    def __exit__(self, *exc) -> bool:
        self.release()
        return False


class RuntimeSanitizer:
    """Records observed lock orders + cross-thread attribute accesses."""

    def __init__(self, max_events: int = 65536):
        self.max_events = int(max_events)
        self._mu = threading.Lock()   # guards the record tables only
        self._tls = threading.local()
        # (held, acquired) lock-name pairs actually observed
        self.order_edges: Dict[Tuple[str, str], int] = {}
        # access key → set of (thread ident, kind, frozenset(held locks))
        self.accesses: Dict[str, Set[Tuple[int, str, frozenset]]] = {}
        self.events = 0
        self.dropped = 0

    # -- recording ------------------------------------------------------
    def lock(self, name: str) -> SanitizedLock:
        return SanitizedLock(self, name)

    def _held(self) -> tuple:
        return getattr(self._tls, "held", ())

    def _on_acquire(self, name: str) -> None:
        held = self._held()
        if held:
            with self._mu:
                for h in held:
                    if h != name:
                        edge = (h, name)
                        self.order_edges[edge] = \
                            self.order_edges.get(edge, 0) + 1
        self._tls.held = held + (name,)

    def _on_release(self, name: str) -> None:
        held = list(self._held())
        for i in range(len(held) - 1, -1, -1):
            if held[i] == name:
                del held[i]
                break
        self._tls.held = tuple(held)

    def note(self, owner, attr: str, kind: str) -> None:
        key = attr if isinstance(owner, str) else \
            f"{type(owner).__name__}.{attr}"
        rec = (threading.get_ident(), kind, frozenset(self._held()))
        with self._mu:
            if self.events >= self.max_events:
                self.dropped += 1
                return
            self.events += 1
            self.accesses.setdefault(key, set()).add(rec)

    def clear(self) -> None:
        with self._mu:
            self.order_edges.clear()
            self.accesses.clear()
            self.events = 0
            self.dropped = 0

    # -- reporting ------------------------------------------------------
    def _mk(self, rule: str, message: str, symbol: str) -> Finding:
        return Finding(
            rule=rule, severity=SEVERITY_ERROR, message=message,
            path="dsan://runtime", line=0, symbol=symbol,
            snippet=message, engine="dsan",
        )

    def findings(self) -> List[Finding]:
        """Violations observed so far, as dslint Findings."""
        out: List[Finding] = []
        with self._mu:
            edges = dict(self.order_edges)
            accesses = {k: set(v) for k, v in self.accesses.items()}

        graph: Dict[str, Set[str]] = {}
        for a, b in edges:
            graph.setdefault(a, set()).add(b)
        reported: Set[frozenset] = set()
        visited: Set[str] = set()

        def dfs(node, stack, on_stack):
            for nxt in sorted(graph.get(node, ())):
                if nxt in on_stack:
                    cyc = stack[stack.index(nxt):] + [nxt]
                    key = frozenset(cyc)
                    if key not in reported:
                        reported.add(key)
                        out.append(self._mk(
                            "lock-order-cycle",
                            "observed acquisition orders form a cycle: "
                            + " -> ".join(cyc),
                            symbol=cyc[0],
                        ))
                elif nxt not in visited:
                    visited.add(nxt)
                    dfs(nxt, stack + [nxt], on_stack | {nxt})

        for start in sorted(graph):
            if start not in visited:
                visited.add(start)
                dfs(start, [start], {start})

        for key, recs in sorted(accesses.items()):
            writes = [r for r in recs if r[1] == "write"]
            if not writes:
                continue
            racy = any(
                w[0] != o[0] and not (w[2] & o[2])
                for w in writes for o in recs
            )
            if racy:
                threads = len({r[0] for r in recs})
                out.append(self._mk(
                    "shared-state-unlocked",
                    f"`{key}` touched by {threads} threads with at least "
                    "one write under disjoint lock sets — a real schedule "
                    "already reached this interleaving",
                    symbol=key,
                ))
        return out
