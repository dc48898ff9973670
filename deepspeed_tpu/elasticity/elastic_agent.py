"""Elastic restart agent for TPU slices.

Analog of reference ``deepspeed/elasticity/elastic_agent.py`` (DSElasticAgent
:23, a torch-elastic LocalElasticAgent subclass): keep a training job alive
across membership changes by restarting from checkpoint at a compatible
scale. Torch-elastic's rendezvous does not exist on TPU; the equivalent
events are slice preemption/resize, surfaced to a single-controller JAX job
as device loss. The agent:

1. derives the compatible-batch ladder once (``compute_elastic_config``),
2. runs the user's train function,
3. on a registered failure, re-derives batch/micro-batch for the NEW chip
   count and reruns from the latest checkpoint — reference semantics
   (recovery is restart-from-checkpoint, not in-run healing).
"""

from __future__ import annotations

import threading
import time
from typing import Any, Callable, Dict, Optional, Tuple

from ..utils.logging import log_dist
from .elasticity import ElasticityError, compute_elastic_config


def choose_compatible_world_size(
    ds_config: Dict[str, Any], available: int, valid: Optional[list] = None
) -> int:
    """Largest ladder-compatible world size <= ``available`` chips.

    The restart arm of the reference's rendezvous: after losing devices a
    job re-joins at whatever compatible scale the surviving slice admits
    (DSElasticAgent re-rendezvous; our ladder fixes the effective batch so
    any compatible count converges identically). Pass ``valid`` to reuse an
    already-derived ladder."""
    if valid is None:
        _, valid = compute_elastic_config(ds_config)
    fitting = [g for g in valid if g <= available]
    if not fitting:
        raise ElasticityError(
            f"no ladder-compatible world size fits {available} available "
            f"chips (ladder: {valid})"
        )
    return max(fitting)


def make_progress_probe(get_step: Callable[[], int], stall_s: float = 300.0):
    """THE probe for an in-process :class:`DeviceMonitor`: healthy while
    ``get_step()`` advances within ``stall_s``. A chip belongs to one process
    at a time — the training process holds it, so liveness is read from
    training progress, never by opening the device from a second process
    (the reference's worker monitoring also watches the worker, not the
    device). Pass e.g. ``lambda: engine.global_steps``."""
    state = {"step": None, "t": time.monotonic()}

    def probe(_timeout_s: float) -> bool:
        step = int(get_step())
        now = time.monotonic()
        if state["step"] is None or step != state["step"]:
            state["step"], state["t"] = step, now
            return True
        return (now - state["t"]) < stall_s

    def reset() -> None:
        state["step"], state["t"] = None, time.monotonic()

    # progress can only resume once training relaunches, so the agent must
    # NOT block in _await_healthy on this probe (deadlock: progress needs
    # training, training needs _await_healthy to return) — reset and go
    probe.waitable = False
    probe.reset = reset
    return probe


class DeviceMonitor:
    """Background accelerator health watcher.

    Analog of the reference elastic agent's worker-monitoring loop
    (``DSElasticAgent`` polls worker processes and triggers restart on
    failure, elastic_agent.py:23). The monitor probes liveness on an
    interval and flips ``healthy`` on consecutive failures.

    Scope of the trip: the reference supervises worker PROCESSES it can
    kill; here ``train_fn`` runs in the agent's own process, so a trip
    cannot preempt a train_fn that is HUNG inside a blocking device call
    (no raise to catch). What the trip does do: (a) fires ``on_trip`` once
    — wire it to ``PreemptionGuard``'s checkpoint path, a process-exit, or
    an orchestrator signal for hang recovery; (b) makes the agent wait for
    recovery before RELAUNCHING after a raised failure, instead of
    crash-looping into a wedged runtime; (c) exposes ``healthy`` for
    external health endpoints."""

    def __init__(
        self,
        probe_fn: Callable[[float], bool],
        interval_s: float = 60.0,
        probe_timeout_s: float = 90.0,
        failures_to_trip: int = 2,
        on_trip: Optional[Callable[[], None]] = None,
    ):
        """``probe_fn(timeout_s) -> healthy``; in the training process build
        it with :func:`make_progress_probe` (``lambda: engine.global_steps``)."""
        self.interval_s = float(interval_s)
        self.probe_timeout_s = float(probe_timeout_s)
        self.failures_to_trip = int(failures_to_trip)
        self.probe_fn = probe_fn
        self.on_trip = on_trip
        self.consecutive_failures = 0
        self.probes = 0
        self._healthy = True
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        # probe_once is called from the background thread AND from the
        # agent's _await_healthy; serializing it keeps the trip counter
        # coherent and prevents duplicate concurrent (expensive) probes
        self._probe_lock = threading.Lock()

    @property
    def healthy(self) -> bool:
        # deliberately lock-free: a single GIL-atomic bool read on the hot
        # polling path; the probe thread's writes are serialized under
        # _probe_lock and a stale read here only delays the trip by one poll
        return self._healthy  # dslint: disable=shared-state-unlocked

    def probe_once(self) -> bool:
        with self._probe_lock:
            self.probes += 1
            ok = bool(self.probe_fn(self.probe_timeout_s))
            if ok:
                self.consecutive_failures = 0
                self._healthy = True
            else:
                self.consecutive_failures += 1
                if self.consecutive_failures >= self.failures_to_trip:
                    tripping = self._healthy
                    if tripping:
                        log_dist(
                            f"device monitor: {self.consecutive_failures} consecutive "
                            "probe failures — marking accelerator unhealthy"
                        )
                    self._healthy = False
                    if tripping and self.on_trip is not None:
                        try:
                            self.on_trip()
                        except Exception as e:
                            log_dist(f"device monitor: on_trip raised {e!r}")
            return ok

    def start(self) -> None:
        if self._thread is not None:
            if self._thread.is_alive():
                # previous loop still draining an in-flight probe (stop was
                # called with _stop set): wait it out before a fresh start
                self._thread.join(timeout=self.probe_timeout_s + 5)
            if self._thread.is_alive():
                raise RuntimeError(
                    "device monitor: previous probe loop did not exit"
                )
            self._thread = None
        self._stop.clear()

        def loop():
            while not self._stop.wait(self.interval_s):
                try:
                    self.probe_once()
                except Exception as e:  # user probe_fn raised: keep watching
                    log_dist(
                        f"device monitor: probe raised {type(e).__name__}: {e} "
                        "(counted as a failure; monitoring continues)"
                    )
                    with self._probe_lock:
                        self.consecutive_failures += 1
                        if self.consecutive_failures >= self.failures_to_trip:
                            self._healthy = False

        self._thread = threading.Thread(target=loop, daemon=True)
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5)
            if self._thread.is_alive():
                # a probe (up to probe_timeout_s) is still in flight: leave
                # the handle so a later start() can't clear _stop and revive
                # this loop alongside a fresh one
                log_dist("device monitor: stop() leaving in-flight probe to drain")
            else:
                self._thread = None


def resize_restart(
    engine_factory: Callable[[int, int, int], Any],
    ds_config: Dict[str, Any],
    ckpt_dir: str,
    world_size: int,
    tag: Optional[str] = None,
):
    """Resume training at a NEW slice size from the universal checkpoint.

    The slice-resize arm of the reference's elastic restart (DSElasticAgent
    restart + compute_elastic_config:287): the elastic ladder fixes ONE
    effective batch size across every compatible chip count, so a resize is

    1. look up ``world_size``'s micro batch on the ladder (convergence
       contract preserved: same effective batch, new micro x gas x dp split),
    2. build the engine at the new mesh geometry via ``engine_factory
       (world_size, train_batch, micro_batch)``,
    3. restore the mesh-agnostic universal checkpoint into the resized
       shardings (params AND optimizer state reshard on load).

    Returns the restored engine; training continues with an identical loss
    trajectory to an uninterrupted run (rehearsed in
    tests/unit/test_aux_subsystems.py::TestElasticResize).
    """
    batch, _, micro = compute_elastic_config(
        ds_config, world_size=world_size, return_microbatch=True
    )
    if micro is None:
        raise ElasticityError(f"no micro batch for world size {world_size}")
    engine = engine_factory(world_size, batch, micro)
    engine.load_checkpoint(ckpt_dir, tag=tag)
    log_dist(
        f"elastic resize: resumed at world_size={world_size} "
        f"batch={batch} micro={micro} from {ckpt_dir}"
    )
    return engine


class ElasticAgent:
    def __init__(
        self,
        ds_config: Dict[str, Any],
        train_fn: Callable[..., Any],
        max_restarts: int = 100,
        restart_delay_s: float = 5.0,
        retryable: Tuple[type, ...] = (RuntimeError, OSError),
        monitor: Optional[DeviceMonitor] = None,
    ):
        """``train_fn(world_size, train_batch_size, micro_batch)`` runs (and
        internally resumes from its latest checkpoint); the agent restarts it
        with recomputed batch geometry after retryable failures. A
        :class:`DeviceMonitor` (optional) runs alongside: when it trips, the
        agent waits for the accelerator to answer again before relaunching
        (rather than crash-looping into a wedged runtime)."""
        self.ds_config = ds_config
        self.train_fn = train_fn
        self.max_restarts = max_restarts
        self.restart_delay_s = restart_delay_s
        self.retryable = retryable
        self.restart_count = 0
        self.monitor = monitor

    def _current_world_size(self) -> int:
        import jax

        return jax.device_count()

    def geometry(self, world_size: int) -> Tuple[int, int, int]:
        """(world_size', train_batch, micro_batch) for the LARGEST
        ladder-compatible world size <= ``world_size`` — a post-resize chip
        count that is off-ladder (e.g. 7 of 8 chips healthy) steps down to
        the nearest compatible, and the RETURNED world size is the one to
        launch with (batch % (micro * ws') == 0 holds for it, not for the
        raw count)."""
        ws = choose_compatible_world_size(self.ds_config, world_size)
        batch, _, micro = compute_elastic_config(
            self.ds_config, world_size=ws, return_microbatch=True
        )
        if micro is None:
            raise ElasticityError(f"no micro batch for world size {ws}")
        return ws, batch, micro

    def _await_healthy(self, max_wait_s: float = 3600.0) -> None:
        """Block until the monitor reports the accelerator answering again
        (the re-rendezvous wait: no point relaunching into a dead runtime).
        Bounded: a permanently revoked slice raises instead of burning the
        allocation forever, so an orchestrator can reschedule. Progress-based
        probes (``probe.waitable = False``) skip the wait entirely — their
        signal can only recover once training relaunches — and are reset so
        the stalled window doesn't instantly re-trip."""
        if self.monitor is None:
            return
        if not getattr(self.monitor.probe_fn, "waitable", True):
            getattr(self.monitor.probe_fn, "reset", lambda: None)()
            self.monitor.consecutive_failures = 0
            self.monitor._healthy = True
            return
        deadline = time.monotonic() + max_wait_s
        while not self.monitor.probe_once():
            if time.monotonic() >= deadline:
                raise ElasticityError(
                    f"accelerator unhealthy for {max_wait_s:.0f}s "
                    "(slice revoked, not resized?) — giving up"
                )
            log_dist("elastic agent: accelerator still unhealthy; waiting")
            time.sleep(self.monitor.interval_s)

    def run(self) -> Any:
        if self.monitor is not None:
            self.monitor.start()
        try:
            while True:
                ws, batch, micro = self.geometry(self._current_world_size())
                log_dist(
                    f"elastic agent: starting at world_size={ws} "
                    f"batch={batch} micro={micro} (restart #{self.restart_count})"
                )
                try:
                    return self.train_fn(ws, batch, micro)
                except self.retryable as e:
                    self.restart_count += 1
                    if self.restart_count > self.max_restarts:
                        raise ElasticityError(
                            f"exceeded max_restarts={self.max_restarts}"
                        ) from e
                    log_dist(f"elastic agent: retryable failure {e!r}; restarting")
                    self._await_healthy()
                    time.sleep(self.restart_delay_s)
        finally:
            if self.monitor is not None:
                self.monitor.stop()
