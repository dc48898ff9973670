"""Compile-pipeline statistics via ``jax.monitoring`` listeners.

XLA compiles are the TPU analog of the reference's CUDA-extension JIT builds:
invisible until they eat minutes of wall clock. jax publishes them on its
monitoring bus (``/jax/core/compile/backend_compile_duration``,
``/jax/compilation_cache/cache_hit|miss`` with the persistent cache on);
this module subscribes once per process and forwards into whatever
:class:`~deepspeed_tpu.telemetry.registry.MetricsRegistry` is currently
installed — counters:

- ``jit_compiles_total``            backend-compile events
- ``jit_compile_seconds_total``     summed backend-compile wall time
- ``jit_trace_seconds_total``       summed jaxpr-trace wall time
- ``jit_cache_hits_total`` / ``jit_cache_misses_total``  persistent-cache outcome

Listeners cannot be unregistered in jax (only globally cleared), so they are
installed once and fan out to every live installed registry (a WeakSet —
compiles are process-global, so a training and an inference engine in one
process both see them, and a dropped engine's registry just falls out). With
no sink installed the callbacks are a dict lookup and an empty loop —
effectively free.

Each of the three duration events also becomes a phase in
:mod:`~deepspeed_tpu.telemetry.spans` (``ds.jit.trace`` / ``ds.jit.lower`` /
``ds.jit.compile`` with ``fun=<program name>``), once the listeners are
registered: ``spans.phases()`` then says which program compiled, and when.
"""

from __future__ import annotations

import threading
import time
import weakref

from . import spans
from .registry import MetricsRegistry

_lock = threading.Lock()
_sinks: "weakref.WeakSet[MetricsRegistry]" = weakref.WeakSet()
_listeners_registered = False


# the three duration events of jax's compile pipeline (jax._src.dispatch):
# matched exactly, and each one also becomes a named phase with ``fun=`` the
# program's name, so that a compilation inside a measured window says which
# program compiled. The backend_compile span covers the persistent-cache
# lookup too: a hit is a retrieval, not a compile (attr ``cache_hit``).
_PHASE_OF = {
    "/jax/core/compile/jaxpr_trace_duration": "ds.jit.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "ds.jit.lower",
    "/jax/core/compile/backend_compile_duration": "ds.jit.compile",
}
_cache_outcome = None  # set by the cache event that fires inside a backend_compile span


def _on_event(event: str, **kw) -> None:
    global _cache_outcome
    if "cache_hit" in event:
        name, _cache_outcome = "jit_cache_hits_total", True
    elif "cache_miss" in event:
        name, _cache_outcome = "jit_cache_misses_total", False
    else:
        return
    for reg in list(_sinks):
        reg.counter(name).inc()


def _on_duration(event: str, duration: float, **kw) -> None:
    global _cache_outcome
    phase = _PHASE_OF.get(event)
    if phase is None:
        return
    attrs = {"fun": str(kw.get("fun_name", ""))}
    if phase == "ds.jit.compile":
        if _cache_outcome is not None:
            attrs["cache_hit"] = _cache_outcome
            _cache_outcome = None
        for reg in list(_sinks):
            reg.counter("jit_compiles_total").inc()
            reg.counter("jit_compile_seconds_total").inc(duration)
    elif phase == "ds.jit.trace":
        for reg in list(_sinks):
            reg.counter("jit_trace_seconds_total").inc(duration)
    t1 = time.perf_counter()  # the event fires as the timed block exits
    spans.note_phase(phase, t1 - duration, t1, **attrs)


def listen() -> None:
    """Register the listeners (once per process) without a registry: the
    engines call this so that ``spans.phases()`` names every compilation
    whether or not telemetry is configured."""
    global _listeners_registered
    with _lock:
        if not _listeners_registered:
            import jax.monitoring as monitoring

            monitoring.register_event_listener(_on_event)
            monitoring.register_event_duration_secs_listener(_on_duration)
            _listeners_registered = True


def install(registry: MetricsRegistry) -> None:
    """Subscribe ``registry`` to the monitoring listeners (registering them
    on first call). Declares the counters eagerly so a scrape before the
    first compile still sees the families at 0."""
    listen()
    with _lock:
        for name, help in (
            ("jit_compiles_total", "XLA backend compile events"),
            ("jit_compile_seconds_total", "summed XLA backend compile wall time"),
            ("jit_trace_seconds_total", "summed jaxpr trace wall time"),
            ("jit_cache_hits_total", "persistent compilation cache hits"),
            ("jit_cache_misses_total", "persistent compilation cache misses"),
        ):
            registry.counter(name, help)
        _sinks.add(registry)


def uninstall() -> None:
    """Detach every sink (listeners stay registered but become no-ops;
    jax.monitoring offers no targeted deregistration)."""
    with _lock:
        _sinks.clear()
