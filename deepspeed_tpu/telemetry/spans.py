"""Spans where the work happens, on the clock the device trace uses.

``span(name, **attrs)`` and ``phase(name, **attrs)`` are context managers that

- enter ``jax.profiler.TraceAnnotation(name, **attrs)``: whenever *any*
  profiler session is open (a benchmark's trace, ``engine.profile_step``, the
  watchdog's capture) the span and its attributes land in the same
  ``xplane.pb`` as the device operations, on one clock;
- append ``(name, t0, t1, attrs)``, stamped with ``time.perf_counter()``, to a
  process-wide bounded ring: hot-path spans to one (``snapshot``), cold-path
  phases (set-up, compilations: tens per process) to another (``phases``), so
  that a long run's steps cannot evict set-up.

Always on: no configuration, no file, no thread, no lock. One ``span()`` costs
a few microseconds with no profiler session open (PERF.md has the measurement).
Attributes known only at exit are set on the yielded object (``s.set(tokens=3)``).
The names and attributes opened by the program are listed in PERF.md section 3
and docs/OBSERVABILITY.md; they are a contract with the readers.
"""

from __future__ import annotations

import collections
import time
from typing import Any, Deque, Dict, List, Optional, Tuple

from jax.profiler import TraceAnnotation

Record = Tuple[str, float, float, Dict[str, Any]]  # name, t0, t1 (perf_counter seconds), attrs

_clock = time.perf_counter
_tracing = TraceAnnotation.is_enabled
_ring: Deque[Record] = collections.deque(maxlen=65536)    # a served window makes about 70 a second
_phases: Deque[Record] = collections.deque(maxlen=16384)  # three jit events per compiled program


class Span:
    """One open span. ``t0`` is readable inside the block, ``t1`` after it."""

    __slots__ = ("name", "attrs", "t0", "t1", "_sink", "_ann")

    def __init__(self, name: str, attrs: Dict[str, Any], sink: Deque[Record]):
        self.name = name
        self.attrs = attrs
        self._sink = sink
        self._ann = None

    def set(self, **attrs: Any) -> None:
        """Attributes that are only known at exit (tokens emitted, slots left)
        or, for the ring's record alone, shortly after it (what a later fetch
        of the same step brought: the record holds this span's own dict)."""
        self.attrs.update(attrs)
        if self._ann is not None:
            self._ann.set_metadata(**attrs)

    def elapsed(self) -> float:
        return _clock() - self.t0

    @property
    def duration(self) -> float:
        return self.t1 - self.t0

    def __enter__(self) -> "Span":
        # with no profiler session open the annotation would record nothing:
        # skipping it halves the cost of a span (a session that opens inside a
        # span misses that one span)
        if _tracing():
            self._ann = TraceAnnotation(self.name, **self.attrs)
            self._ann.__enter__()
        self.t0 = _clock()
        return self

    def __exit__(self, *exc) -> bool:
        self.t1 = _clock()
        if self._ann is not None:
            self._ann.__exit__(*exc)
            self._ann = None  # a later ``set`` reaches the record alone
        self._sink.append((self.name, self.t0, self.t1, self.attrs))
        return False


def span(name: str, **attrs: Any) -> Span:
    """A hot-path span (a step, a leaf of a step)."""
    return Span(name, attrs, _ring)


def phase(name: str, **attrs: Any) -> Span:
    """A cold-path span (set-up, a compilation), kept apart from the ring."""
    return Span(name, attrs, _phases)


def note_phase(name: str, t0: float, t1: float, **attrs: Any) -> None:
    """A phase that someone else timed (jax.monitoring's duration events)."""
    _phases.append((name, t0, t1, attrs))


def _since(recs: Deque[Record], since: Optional[float]) -> List[Record]:
    out = list(recs)
    return out if since is None else [r for r in out if r[2] >= since]


def snapshot(since: Optional[float] = None) -> List[Record]:
    """The ring, oldest first; with ``since``, the spans that ended at or after it."""
    return _since(_ring, since)


def phases(since: Optional[float] = None) -> List[Record]:
    """The cold-path records, oldest first; ``since`` as in :func:`snapshot`."""
    return _since(_phases, since)


def summary(since: Optional[float] = None) -> Dict[str, Dict[str, float]]:
    """Per span name in the ring: count, total, median and 95th percentile (seconds)."""
    by_name: Dict[str, List[float]] = collections.defaultdict(list)
    for name, t0, t1, _ in snapshot(since):
        by_name[name].append(t1 - t0)
    out = {}
    for name, durs in by_name.items():
        durs.sort()
        n = len(durs)
        out[name] = {
            "count": n, "total_s": sum(durs),
            "p50_s": durs[(n - 1) // 2], "p95_s": durs[min(n - 1, int(0.95 * n))],
        }
    return out
