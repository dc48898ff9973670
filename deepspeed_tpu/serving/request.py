"""Serving request lifecycle: QUEUED → RUNNING → FINISHED/TRUNCATED, or
REJECTED at the door (admission control) / TIMED_OUT while still queued.

A request is the unit the continuous-batching scheduler moves through slots
(serving/scheduler.py). ``tokens`` accumulates as the slot decodes; the
deadline fields make timeout eviction deterministic under an injected clock
(tests drive a fake clock, production uses ``time.monotonic``).
"""

from __future__ import annotations

import itertools
from dataclasses import dataclass, field
from typing import List, Optional

import numpy as np


class RequestStatus:
    QUEUED = "queued"
    RUNNING = "running"
    FINISHED = "finished"       # emitted max_new_tokens or hit EOS
    TRUNCATED = "truncated"     # deadline passed mid-decode: partial output
    TIMED_OUT = "timed_out"     # deadline passed before ever reaching a slot
    REJECTED = "rejected"       # backpressure: queue full / can never fit
    PREEMPTED = "preempted"     # graceful drain evicted it (shutdown/SIGTERM)
    FAILED = "failed"           # transient slot failure, retry budget spent

    TERMINAL = (FINISHED, TRUNCATED, TIMED_OUT, REJECTED, PREEMPTED, FAILED)


_ids = itertools.count()


@dataclass
class Request:
    """One generation request. ``prompt`` is a 1-D int array of token ids."""

    prompt: np.ndarray
    max_new_tokens: int
    seed: int = 0
    eos_token_id: Optional[int] = None
    # relative deadline (seconds from submit); None → serving config default
    deadline_s: Optional[float] = None
    # original ask when admission clamped max_new_tokens (over-long request
    # degrading to a truncated response); None = not clamped
    requested_new_tokens: Optional[int] = None

    # -- resilience (ISSUE 7) ------------------------------------------
    # transient-failure retries consumed (scheduler retry-with-backoff)
    retries: int = 0
    # earliest re-admission time after a backoff (scheduler clock domain)
    not_before: float = 0.0
    # fault injection: fail this slot transiently once it has emitted this
    # many tokens (None = healthy); set by the scheduler at admission
    stall_after: Optional[int] = None

    # -- SLO / tenancy (ISSUE 11) --------------------------------------
    # tenant is a free-form accounting dimension (per-tenant counters +
    # trace records); slo_class names a ``serving.slo.classes`` entry —
    # the scheduler resolves unknown/empty to the configured default
    tenant: str = "default"
    slo_class: str = ""
    # -- fleet (ISSUE 18) ----------------------------------------------
    # replica currently serving this request; stamped by the FleetRouter
    # at routing time and restamped on migration ("" = no fleet in play).
    # Lands in the terminal trace record so reports can group --by replica.
    replica: str = ""

    # -- prefix cache (ISSUE 10) ---------------------------------------
    # prompt tokens served from shared prefix-index pages at admission
    # (0 = cold); the tail past this point was prefilled normally
    prefix_shared_tokens: int = 0
    # a full-prefix hit forked the last prompt page copy-on-write
    cow_forked: bool = False

    # -- filled by the scheduler ---------------------------------------
    id: int = field(default_factory=lambda: next(_ids))
    status: str = RequestStatus.QUEUED
    tokens: List[int] = field(default_factory=list)
    detail: str = ""            # why rejected/truncated
    t_submit: float = 0.0
    t_admit: Optional[float] = None   # queue wait ends: slot assigned
    # set on retry rewind: the request re-entered the queue at this time,
    # so the next admission's queue wait measures from here, not from the
    # original submit (which would fold the failed attempt's service time
    # into a wait that never happened)
    t_requeue: Optional[float] = None
    t_first_token: Optional[float] = None
    # the launch number of the program that sampled the first token (the
    # ``launch`` of its ``ds.serve.*`` leaf: docs/OBSERVABILITY.md)
    first_launch: Optional[int] = None
    t_finish: Optional[float] = None
    # one wall timestamp per emitted token (parallel to ``tokens``): a
    # speculative verify step emits its accepted run at ONE instant, so the
    # entries repeat — exactly what a streaming client observes (ISSUE 11;
    # inter-token quantiles derive from these, not from the mean)
    t_emissions: List[float] = field(default_factory=list)

    @property
    def done(self) -> bool:
        return self.status in RequestStatus.TERMINAL

    @property
    def prompt_list(self) -> List[int]:
        """The prompt as a plain list, converted ONCE — the speculative
        drafter reads prompt ⊕ tokens every step, and re-running
        ``ndarray.tolist()`` per slot per step is avoidable hot-path work."""
        cached = getattr(self, "_prompt_list", None)
        if cached is None:
            cached = np.asarray(self.prompt, np.int64).tolist()
            object.__setattr__(self, "_prompt_list", cached)
        return cached

    @property
    def prompt_len(self) -> int:
        return int(np.asarray(self.prompt).shape[-1])

    @property
    def output(self) -> np.ndarray:
        """prompt + generated tokens, the ``generate()``-shaped result."""
        return np.concatenate(
            [np.asarray(self.prompt, np.int32).reshape(-1),
             np.asarray(self.tokens, np.int32)]
        )

    @property
    def ttft_s(self) -> Optional[float]:
        if self.t_first_token is None:
            return None
        return self.t_first_token - self.t_submit

    @property
    def queue_wait_s(self) -> Optional[float]:
        """Enqueue → slot assignment (admission); None while still queued
        or rejected at the door. After a retry rewind the wait measures
        from the re-queue, not the original submit."""
        if self.t_admit is None:
            return None
        return self.t_admit - (
            self.t_requeue if self.t_requeue is not None else self.t_submit
        )

    @property
    def tpot_s(self) -> Optional[float]:
        """Mean time per output token AFTER the first (decode cadence)."""
        if self.t_finish is None or self.t_first_token is None or len(self.tokens) < 2:
            return None
        return (self.t_finish - self.t_first_token) / (len(self.tokens) - 1)

    @property
    def inter_token_gaps_s(self) -> List[float]:
        """Per-token arrival deltas from the emission timestamps — the
        streaming-client view. Tokens a verify step emitted together have
        gap 0; the gap preceding an accepted run carries that step's whole
        latency. ``serving_tpot_seconds`` observes THESE (ISSUE 11), so its
        quantiles are what a client percentile-monitors, not the
        per-request mean. Delegates to the one derivation the offline
        scorer also uses, so the stats()-reproduces-trace cross-check can
        never drift."""
        from ..telemetry.request_trace import inter_token_gaps

        return inter_token_gaps(self.t_emissions)
