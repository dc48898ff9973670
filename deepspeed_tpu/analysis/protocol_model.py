"""Engine G (dsproto), pass 2 — bounded explicit-state protocol model checker.

Companion to :mod:`deepspeed_tpu.analysis.protocol_rules` (the AST ownership
lint).  Where the lint proves per-function release obligations, this module
proves the *global* serving protocol: it builds a small counting abstraction
of the scheduler — requests x lifecycle states x per-allocator free-page
counts x prefix-index refcounts — and exhaustively explores every
interleaving of the protocol events (submit / admit / prefill-complete /
disagg handoff / decode / retry-rewind / timeout-evict / prefix-evict /
drain-SIGTERM / preempt) up to a configurable state bound, checking on every
reachable state:

* **refcounts conserved and >= 0** — for each pool,
  ``free + sum(owned) + index_entries == capacity`` and no counter goes
  negative (``proto-refcount-conservation``);
* **zero leaked pages at quiescence** — when every request is terminal and
  the engine has drained, no request still owns pages or holds refs
  (``proto-page-leak``; a single-pool imbalance under disaggregation is
  classified ``proto-dual-reserve``);
* **no use-after-free** — no decode step targets a slot whose pages were
  already released (``proto-use-after-free``);
* **no write into a shared page** — a COW-mapped prefix page is never a
  write target unless it was forked first (``proto-write-shared-page``);
* **no wedge** — every non-terminal state has at least one enabled event,
  so every request eventually reaches a terminal status
  (``proto-request-wedged``).

The abstraction is exact for the quantities it tracks: admission, prefix
lookup/registration, COW forking, disaggregated dual reservation and
handoff, retry rewind, timeout eviction, LRU prefix eviction, and drain all
mirror the accounting the real ``ServingEngine`` performs against
``PageAllocator`` / ``PrefixCache``.  A violation therefore comes with a
*minimal* counterexample (BFS guarantees shortest event trace), and
:func:`replay_trace` drives that trace through the **real** engine — with an
injectable clock and a :class:`ProtocolMonitor` asserting the same
invariants against the live allocators — so counterexamples are
machine-confirmed, not speculative.

Known-bug mutations (``ProtoModelConfig.mutations``) re-introduce specific
defects into the abstract transition relation; the PR gate asserts each one
produces a counterexample and that :func:`apply_engine_mutation` makes the
same defect reproduce on the real engine under replay.
"""

from __future__ import annotations

import re
from collections import deque
from dataclasses import dataclass, field
from typing import Dict, FrozenSet, Iterator, List, Optional, Tuple

from .findings import SEVERITY_ERROR, Finding

__all__ = [
    "MODEL_RULES",
    "MUTATIONS",
    "ProtoModelConfig",
    "ProtoReport",
    "ProtoViolation",
    "ProtocolMonitor",
    "ReplayClock",
    "apply_engine_mutation",
    "default_model_configs",
    "explore",
    "model_findings",
    "replay_fleet_trace",
    "replay_trace",
]


MODEL_RULES: Dict[str, str] = {
    "proto-refcount-conservation": (
        "pool accounting violated: free + owned + index != capacity, or a "
        "refcount went negative"
    ),
    "proto-page-leak": (
        "pages still owned (or prefix refs still held) after every request "
        "reached a terminal status and the engine drained"
    ),
    "proto-use-after-free": (
        "a decode step targeted a slot whose KV pages were already released"
    ),
    "proto-write-shared-page": (
        "a prefill/decode write landed in a prefix-shared page without a "
        "COW fork"
    ),
    "proto-request-wedged": (
        "a reachable state has a non-terminal request but no enabled event "
        "(the request can never finish)"
    ),
    "proto-dual-reserve": (
        "disaggregated admission reserved on both allocators but a terminal "
        "path released only one pool"
    ),
    "proto-host-tier-bound": (
        "host-tier occupancy left the [0, host_budget] envelope: a demotion "
        "or restore miscounted the host-resident pages"
    ),
    "proto-dual-emit": (
        "a migrating (or migrated) session emitted a token on more than one "
        "replica: the source kept decoding after the payload left, or the "
        "destination decoded a slot the source still owns"
    ),
    "proto-replica-page-leak": (
        "a replica died still holding pages (or index refs) owned by "
        "sessions that no longer run there — a migration's source-side "
        "release was skipped"
    ),
}

#: Known-bug mutations for the self-test gate.  Each flips one guard in the
#: abstract transition relation; ``apply_engine_mutation`` mirrors the first
#: two on the real engine.
MUTATIONS: FrozenSet[str] = frozenset(
    {
        "drop-drain-free",    # drain preemption skips the slot's page frees
        "skip-cow-fork",      # full prefix hit maps the shared tail page writable
        "drop-handoff-free",  # disagg handoff never releases the prefill pool
        "double-free-finish", # finish releases the slot's pages twice
        "decode-after-free",  # retry rewind frees pages but keeps decoding
        "skip-queue-drain",   # drain forgets to reject the queued backlog
        "drop-host-free",     # prefix demotion copies to host but skips the
                              # device-side free (page owned by neither tier)
        "drop-migration-free",  # migrate_commit forgets the SOURCE replica's
                                # release: pages/refs leak across replica death
    }
)

# request lifecycle states of the abstraction.  _MIGRATE and _DECODE_B are
# fleet-only (ISSUE 18): a migrating session is dual-owned — source pages
# still held while the destination's reservation exists, exactly like the
# disaggregated dual-reserve window — and _DECODE_B decodes on the peer.
_NEW, _QUEUED, _PREFILL, _HANDOFF, _DECODE, _DONE, _MIGRATE, _DECODE_B = range(8)
_STATUS_NAMES = (
    "new", "queued", "prefill", "handoff", "decode", "done",
    "migrate", "decode_b",
)

# ``draining`` bitfield (plain bool pre-ISSUE-18 traces == bit 0):
_DRAIN = 1       # full drain: admissions stopped fleet-wide
_PREEMPT_A = 2   # replica A received its SIGTERM: migrating sessions out
_DEAD_A = 4      # replica A retired: nothing may touch its pools again

# request tuple layout: (status, own, d_own, sref, reg, cow, emitted, retries)
# own    -- private pages held on the prefill-side pool (sole pool when shared)
# d_own  -- private pages held on the decode pool (disaggregated only)
# sref   -- refs this request holds on prefix-index chain pages
# reg    -- pages this request registered into the index and still refs
#           (non-disagg only: the slot keeps its refs until finish)
# cow    -- 1 when the writable row maps a shared page (skip-cow-fork)


@dataclass(frozen=True)
class ProtoModelConfig:
    """Bounds for one exploration of the abstract serving protocol."""

    requests: int = 2
    slots: int = 2
    prompt_pages: int = 2      # full pages per prompt (page-aligned prompts)
    new_tokens: int = 2        # decode steps per request before finish
    disaggregated: bool = False
    prefix_cache: bool = True
    retry_max: int = 1
    allow_timeout: bool = True
    tiering: bool = False      # host-DRAM second tier for evicted prefix pages
    host_budget: int = 1       # host-tier slots (page capacity of the store)
    # fleet mode (ISSUE 18): replica A is modeled concretely (prefill pool +
    # index), replica B's pool rides the decode-pool machinery — migration
    # dual-owns a session across both exactly like dual-reserve does
    fleet: bool = False
    mutations: FrozenSet[str] = frozenset()
    max_states: int = 200_000

    def __post_init__(self) -> None:
        bad = set(self.mutations) - set(MUTATIONS)
        if bad:
            raise ValueError(f"unknown protocol mutations: {sorted(bad)}")
        if self.tiering and not self.prefix_cache:
            raise ValueError("tiering requires prefix_cache (demotion source)")
        if self.tiering and self.host_budget < 1:
            raise ValueError("tiering requires host_budget >= 1")
        if self.fleet and self.disaggregated:
            raise ValueError(
                "fleet mode reuses the decode pool as replica B; combine "
                "with disaggregated later if both are ever needed at once"
            )

    # Pools are sized so admission can transiently block (pool pressure is
    # part of the explored behaviour) but never permanently starve: enough
    # for every request in flight at once plus one resident index chain.
    @property
    def reserve_pages(self) -> int:
        """Pages a request reserves on its decode-capable pool."""
        return self.prompt_pages + 1

    @property
    def prefill_capacity(self) -> int:
        if self.disaggregated:
            return self.requests * self.prompt_pages + self.prompt_pages
        return self.requests * self.reserve_pages + self.prompt_pages

    @property
    def decode_capacity(self) -> int:
        if self.disaggregated or self.fleet:
            return self.requests * self.reserve_pages
        return 0


@dataclass(frozen=True)
class ProtoViolation:
    rule: str
    message: str
    trace: Tuple[str, ...]   # minimal counterexample event sequence


@dataclass
class ProtoReport:
    config: ProtoModelConfig
    states: int = 0
    transitions: int = 0
    complete: bool = True    # False when max_states truncated the search
    violations: List[ProtoViolation] = field(default_factory=list)

    @property
    def ok(self) -> bool:
        return not self.violations


def default_model_configs() -> Dict[str, ProtoModelConfig]:
    """The stock configurations the dslint gate explores."""
    return {
        "shared": ProtoModelConfig(disaggregated=False),
        "disaggregated": ProtoModelConfig(disaggregated=True),
        "fleet": ProtoModelConfig(fleet=True),
    }


# --------------------------------------------------------------------------
# transition relation
# --------------------------------------------------------------------------

def _initial(cfg: ProtoModelConfig):
    req = (_NEW, 0, 0, 0, 0, 0, 0, 0)
    return (
        (req,) * cfg.requests,
        cfg.prefill_capacity,
        cfg.decode_capacity,
        0,       # index_pages: full pages resident in the prefix chain
        0,       # host_pages: prefix pages demoted to the host-DRAM tier
        0,       # draining bitfield: _DRAIN | _PREEMPT_A | _DEAD_A
    )


def _ev(name: str, i: Optional[int] = None) -> str:
    return name if i is None else f"{name}(r{i})"


def _enabled(cfg: ProtoModelConfig, st) -> List[str]:
    reqs, free_p, free_d, index, host, draining = st
    P, R = cfg.prompt_pages, cfg.reserve_pages
    active = sum(1 for r in reqs if r[0] in (_PREFILL, _HANDOFF, _DECODE))
    # replica B slot pressure (fleet): a migrating session holds its B
    # reservation from migrate_begin on, so it occupies a B slot already
    b_active = sum(1 for r in reqs if r[0] in (_MIGRATE, _DECODE_B))
    out: List[str] = []
    for i, r in enumerate(reqs):
        status = r[0]
        if status == _NEW and not (draining & _DRAIN):
            out.append(_ev("submit", i))
        elif status == _QUEUED:
            if draining == 0 and active < cfg.slots:
                shared = min(index, P - 1) if cfg.prefix_cache else 0
                cow_hit = cfg.prefix_cache and index >= P
                skip_cow = cow_hit and "skip-cow-fork" in cfg.mutations
                if cfg.disaggregated:
                    p_need = P - shared - (1 if skip_cow else 0)
                    if free_p >= p_need and free_d >= R:
                        out.append(_ev("admit", i))
                else:
                    need = R - shared - (1 if skip_cow else 0)
                    if free_p >= need:
                        out.append(_ev("admit", i))
            # ISSUE 18: once replica A drains, the router lands new (and
            # re-queued) work on replica B — its own pool and slots
            if (cfg.fleet and (draining & _PREEMPT_A)
                    and not (draining & _DRAIN)
                    and free_d >= R and b_active < cfg.slots):
                out.append(_ev("admit_b", i))
        elif status == _PREFILL:
            out.append(_ev("prefill_done", i))
            if cfg.allow_timeout:
                out.append(_ev("timeout_evict", i))
            if draining & _DRAIN:
                out.append(_ev("preempt", i))
        elif status == _HANDOFF:
            out.append(_ev("handoff", i))
            if cfg.allow_timeout:
                out.append(_ev("timeout_evict", i))
            if draining & _DRAIN:
                out.append(_ev("preempt", i))
        elif status == _DECODE:
            if not (cfg.fleet and (draining & _PREEMPT_A)):
                # a preempted replica A emits NOTHING more: its sessions
                # migrate or restart — decode here would be dual-emission
                out.append(_ev("decode", i))
                if r[7] < cfg.retry_max and draining == 0:
                    out.append(_ev("retry", i))
            elif not (draining & _DEAD_A) and free_d >= R and b_active < cfg.slots:
                out.append(_ev("migrate_begin", i))
            if cfg.allow_timeout:
                out.append(_ev("timeout_evict", i))
            if draining & _DRAIN:
                out.append(_ev("preempt", i))
        elif status == _MIGRATE:
            out.append(_ev("migrate_commit", i))
            out.append(_ev("migrate_abort", i))
        elif status == _DECODE_B:
            out.append(_ev("decode_b", i))
            if cfg.allow_timeout:
                out.append(_ev("timeout_evict", i))
    if not (draining & _DRAIN):
        out.append("drain")
    if cfg.fleet and draining == 0:
        out.append("replica_preempt")
    if (cfg.fleet and (draining & _PREEMPT_A) and not (draining & _DEAD_A)
            and not any(r[0] in (_PREFILL, _HANDOFF, _DECODE, _MIGRATE)
                        for r in reqs)):
        # A may retire only once nothing still runs (or is mid-flight) there
        out.append("replica_die")
    if (index > 0 and not (draining & _DEAD_A)
            and all(r[3] == 0 and r[4] == 0 for r in reqs)):
        # With a host tier configured the LRU prefix eviction *demotes* the
        # page to host DRAM instead of dropping it (ISSUE 17); the device
        # page is freed either way.  A dead replica's index is frozen.
        out.append("demote_prefix" if cfg.tiering else "evict_prefix")
    if cfg.tiering and host > 0 and free_p > 0 and not (draining & _DEAD_A):
        out.append("restore_prefix")
    return out


def _apply(cfg: ProtoModelConfig, st, ev: str):
    """Apply ``ev`` to ``st``; return ``(next_state, violation_rule|None)``."""
    reqs, free_p, free_d, index, host, draining = st
    reqs = list(reqs)
    P, R = cfg.prompt_pages, cfg.reserve_pages
    vio: Optional[str] = None
    m = re.match(r"(\w+)(?:\(r(\d+)\))?$", ev)
    name, idx = m.group(1), (int(m.group(2)) if m.group(2) else None)

    def release(i: int, skip_free: bool = False) -> None:
        """Terminal release of everything request ``i`` holds."""
        nonlocal free_p, free_d
        s, own, d_own, sref, reg, cow, emitted, retries = reqs[i]
        # pages orphaned by a skipped handoff-free stay leaked: the slot no
        # longer records them, so no terminal path can reclaim them
        orphaned = (
            cfg.disaggregated
            and "drop-handoff-free" in cfg.mutations
            and s == _DECODE
        ) or (
            # a committed migration that skipped the source-side release left
            # the A-pool pages behind permanently: B's terminal path only
            # frees B's reservation
            cfg.fleet
            and "drop-migration-free" in cfg.mutations
            and s == _DECODE_B
        )
        if not skip_free:
            free_d += d_own
            d_own = 0
            if not orphaned:
                free_p += own
                own = sref = reg = 0
            cow = 0
        reqs[i] = (_DONE, own, d_own, sref, reg, cow, emitted, retries)

    if name == "submit":
        s = reqs[idx]
        reqs[idx] = (_QUEUED,) + s[1:]
    elif name == "admit":
        shared = min(index, P - 1) if cfg.prefix_cache else 0
        cow_hit = cfg.prefix_cache and index >= P
        skip_cow = cow_hit and "skip-cow-fork" in cfg.mutations
        sref = shared + (1 if skip_cow else 0)
        cow = 1 if skip_cow else 0
        retries = reqs[idx][7]
        if cfg.disaggregated:
            p_need = P - shared - (1 if skip_cow else 0)
            free_p -= p_need
            free_d -= R
            reqs[idx] = (_PREFILL, p_need, R, sref, 0, cow, 0, retries)
        else:
            need = R - shared - (1 if skip_cow else 0)
            free_p -= need
            reqs[idx] = (_PREFILL, need, 0, sref, 0, cow, 0, retries)
    elif name == "prefill_done":
        s, own, d_own, sref, reg, cow, emitted, retries = reqs[idx]
        if cow:
            # the tail chunk recomputes into the COW-mapped shared page
            vio = vio or "proto-write-shared-page"
            cow = 0
        if cfg.disaggregated:
            reqs[idx] = (_HANDOFF, own, d_own, sref, reg, cow, emitted, retries)
        else:
            k = max(0, P - index) if cfg.prefix_cache else 0
            k = min(k, own)        # only privately-owned pages register
            own -= k
            reg += k
            index += k
            emitted = 1
            reqs[idx] = (_DECODE, own, d_own, sref, reg, cow, emitted, retries)
            if emitted >= cfg.new_tokens:
                pre_own, pre_d = own, d_own
                release(idx)
                if "double-free-finish" in cfg.mutations:
                    free_p += pre_own
                    free_d += pre_d
    elif name == "handoff":
        s, own, d_own, sref, reg, cow, emitted, retries = reqs[idx]
        k = max(0, P - index) if cfg.prefix_cache else 0
        k = min(k, own)
        index += k
        if "drop-handoff-free" in cfg.mutations:
            # registered pages moved to the index; the rest leak with the refs
            own -= k
        else:
            # insert retains registered pages for the index, then the slot's
            # refs on the whole prefill row are dropped: request holds nothing
            free_p += own - k
            own = 0
            sref = 0
        emitted = 1
        reqs[idx] = (_DECODE, own, d_own, sref, reg, cow, emitted, retries)
    elif name == "decode":
        s, own, d_own, sref, reg, cow, emitted, retries = reqs[idx]
        if cow:
            vio = vio or "proto-write-shared-page"
            cow = 0
        if own + d_own == 0:
            # writable row holds no live private pages
            vio = vio or "proto-use-after-free"
        emitted += 1
        reqs[idx] = (s, own, d_own, sref, reg, cow, emitted, retries)
        if emitted >= cfg.new_tokens:
            pre_own, pre_d = own, d_own
            release(idx)
            if "double-free-finish" in cfg.mutations:
                free_p += pre_own
                free_d += pre_d
    elif name == "retry":
        s, own, d_own, sref, reg, cow, emitted, retries = reqs[idx]
        free_p += own
        free_d += d_own
        if "decode-after-free" in cfg.mutations:
            # rewind released the pages but forgot to vacate the slot
            reqs[idx] = (_DECODE, 0, 0, 0, 0, 0, emitted, retries + 1)
        else:
            reqs[idx] = (_QUEUED, 0, 0, 0, 0, 0, 0, retries + 1)
    elif name == "timeout_evict":
        release(idx)
    elif name == "preempt":
        release(idx, skip_free="drop-drain-free" in cfg.mutations)
    elif name == "drain":
        draining |= _DRAIN
        for i, r in enumerate(reqs):
            if r[0] in (_NEW, _QUEUED):
                if "skip-queue-drain" in cfg.mutations and r[0] == _QUEUED:
                    continue        # backlog forgotten: wedged forever
                reqs[i] = (_DONE,) + r[1:]
    elif name == "evict_prefix":
        index -= 1
        free_p += 1
    elif name == "demote_prefix":
        # LRU prefix eviction with a host tier: the page's KV moves to a
        # host slot (evicting the host LRU first when the store is full, so
        # host occupancy saturates at the budget) and the device page is
        # freed.  ``drop-host-free`` skips that free: the page is then owned
        # by neither tier and device conservation breaks.
        index -= 1
        if "drop-host-free" not in cfg.mutations:
            free_p += 1
        host = min(host + 1, cfg.host_budget)
    elif name == "restore_prefix":
        # A prefix hit on a demoted chain restores the page into a freshly
        # allocated device page and drops the host copy.
        host -= 1
        index += 1
        free_p -= 1
    elif name == "replica_preempt":
        # SIGTERM on replica A: the router marks it draining-for-retirement.
        # New admissions land on replica B; live decodes migrate or restart.
        draining |= _PREEMPT_A
    elif name == "replica_die":
        draining |= _DEAD_A
    elif name == "admit_b":
        # router re-lands a queued request on replica B (fresh restart —
        # prefix reuse on B is out of scope for the abstract model, so B
        # sessions are modeled decode-pool-only like a disaggregated row)
        retries = reqs[idx][7]
        free_d -= R
        emitted = 1
        reqs[idx] = (_DECODE_B, 0, R, 0, 0, 0, emitted, retries)
        if emitted >= cfg.new_tokens:
            release(idx)
    elif name == "migrate_begin":
        # session becomes dual-owned (like dual-reserve during handoff): A
        # still holds its pages, B's destination reservation is charged now
        s, own, d_own, sref, reg, cow, emitted, retries = reqs[idx]
        free_d -= R
        d_own += R
        reqs[idx] = (_MIGRATE, own, d_own, sref, reg, cow, emitted, retries)
    elif name == "migrate_commit":
        s, own, d_own, sref, reg, cow, emitted, retries = reqs[idx]
        if s != _MIGRATE:
            vio = vio or "proto-dual-emit"
        if "drop-migration-free" in cfg.mutations:
            # source-side release skipped: A's pages/refs stay charged to the
            # request but no slot records them — leaked across A's death
            pass
        else:
            free_p += own
            own = sref = reg = 0
        cow = 0
        reqs[idx] = (_DECODE_B, own, d_own, sref, reg, cow, emitted, retries)
    elif name == "migrate_abort":
        # crc-failed / no-capacity payload: B's reservation returns, A's
        # pages are released and the request restarts from the queue — or,
        # when the fleet already drained, fails terminally (PREEMPTED): the
        # router never requeues into a drained fleet
        s, own, d_own, sref, reg, cow, emitted, retries = reqs[idx]
        free_p += own
        free_d += d_own
        if draining & _DRAIN:
            reqs[idx] = (_DONE, 0, 0, 0, 0, 0, emitted, retries)
        else:
            reqs[idx] = (_QUEUED, 0, 0, 0, 0, 0, 0, retries)
    elif name == "decode_b":
        s, own, d_own, sref, reg, cow, emitted, retries = reqs[idx]
        if s != _DECODE_B:
            vio = vio or "proto-dual-emit"
        if d_own == 0:
            vio = vio or "proto-use-after-free"
        emitted += 1
        reqs[idx] = (s, own, d_own, sref, reg, cow, emitted, retries)
        if emitted >= cfg.new_tokens:
            release(idx)
    else:  # pragma: no cover - defensive
        raise ValueError(f"unknown event {ev!r}")

    nxt = (tuple(reqs), free_p, free_d, index, host, draining)
    return nxt, vio


def _check_state(cfg: ProtoModelConfig, st) -> Optional[Tuple[str, str]]:
    """Invariant check; returns ``(rule, message)`` or ``None``."""
    reqs, free_p, free_d, index, host, draining = st
    if free_p < 0 or free_d < 0 or index < 0:
        return (
            "proto-refcount-conservation",
            f"negative counter: free_p={free_p} free_d={free_d} index={index}",
        )
    if host < 0 or host > cfg.host_budget:
        return (
            "proto-host-tier-bound",
            f"host tier holds {host} page(s), budget {cfg.host_budget}",
        )
    if host and not cfg.tiering:
        return (
            "proto-host-tier-bound",
            f"host tier holds {host} page(s) with tiering disabled",
        )
    if any(min(r[1:6]) < 0 for r in reqs):
        return ("proto-refcount-conservation", "negative per-request counter")
    held_p = sum(r[1] for r in reqs)
    held_d = sum(r[2] for r in reqs)
    if free_p + held_p + index != cfg.prefill_capacity:
        return (
            "proto-refcount-conservation",
            f"prefill pool: free {free_p} + owned {held_p} + index {index} "
            f"!= capacity {cfg.prefill_capacity}",
        )
    if (cfg.disaggregated or cfg.fleet) and free_d + held_d != cfg.decode_capacity:
        return (
            "proto-refcount-conservation",
            f"decode pool: free {free_d} + owned {held_d} "
            f"!= capacity {cfg.decode_capacity}",
        )
    if cfg.fleet and (draining & _DEAD_A):
        # replica_die is gated on no session running (or migrating) on A, so
        # anything still charged to the A-side pools at death is leaked — a
        # migration's source-side release was skipped
        a_leak = sum(r[1] + r[3] + r[4] for r in reqs)
        if a_leak:
            return (
                "proto-replica-page-leak",
                f"replica A died holding {a_leak} page(s)/ref(s) charged to "
                f"sessions that no longer run there",
            )
    if draining and all(r[0] == _DONE for r in reqs):
        p_leak = sum(r[1] + r[3] + r[4] for r in reqs)
        d_leak = held_d
        if p_leak or d_leak:
            if cfg.disaggregated and (p_leak == 0) != (d_leak == 0):
                return (
                    "proto-dual-reserve",
                    f"one-sided release at quiescence: prefill-side leak "
                    f"{p_leak} page(s)/ref(s), decode-side {d_leak}",
                )
            return (
                "proto-page-leak",
                f"{p_leak + d_leak} page(s)/ref(s) still held at quiescence",
            )
    return None


def explore(cfg: ProtoModelConfig) -> ProtoReport:
    """BFS over the abstract protocol; shortest-trace counterexamples."""
    report = ProtoReport(config=cfg)
    init = _initial(cfg)
    parent: Dict[tuple, Optional[Tuple[tuple, str]]] = {init: None}
    q = deque([init])
    seen_rules: Dict[str, ProtoViolation] = {}

    def trace_to(st, extra: Optional[str] = None) -> Tuple[str, ...]:
        evs: List[str] = []
        cur = st
        while parent[cur] is not None:
            prev, ev = parent[cur]
            evs.append(ev)
            cur = prev
        evs.reverse()
        if extra is not None:
            evs.append(extra)
        return tuple(evs)

    def record(rule: str, message: str, trace: Tuple[str, ...]) -> None:
        if rule not in seen_rules:
            v = ProtoViolation(rule=rule, message=message, trace=trace)
            seen_rules[rule] = v
            report.violations.append(v)

    bad = _check_state(cfg, init)
    if bad:
        record(bad[0], bad[1], ())
    while q:
        if report.states >= cfg.max_states:
            report.complete = False
            break
        st = q.popleft()
        report.states += 1
        evs = _enabled(cfg, st)
        if not evs:
            if any(r[0] != _DONE for r in st[0]):
                stuck = [
                    f"r{i}:{_STATUS_NAMES[r[0]]}"
                    for i, r in enumerate(st[0])
                    if r[0] != _DONE
                ]
                record(
                    "proto-request-wedged",
                    "no enabled event but non-terminal request(s): "
                    + ", ".join(stuck),
                    trace_to(st),
                )
            continue
        for ev in evs:
            report.transitions += 1
            nxt, vio = _apply(cfg, st, ev)
            if vio:
                record(vio, MODEL_RULES[vio], trace_to(st, ev))
            bad = _check_state(cfg, nxt)
            if bad:
                record(bad[0], bad[1], trace_to(st, ev))
                continue   # don't explore past a corrupted state
            if nxt not in parent:
                parent[nxt] = (st, ev)
                q.append(nxt)
    return report


def model_findings(
    report: ProtoReport, program: str = "serving"
) -> List[Finding]:
    """Render a report's violations as standard Engine-G findings."""
    mode = "disagg" if report.config.disaggregated else "shared"
    if report.config.tiering:
        mode += "+tiered"
    out = []
    for v in report.violations:
        trace = " -> ".join(v.trace) if v.trace else "<initial state>"
        out.append(
            Finding(
                rule=v.rule,
                severity=SEVERITY_ERROR,
                message=f"[{mode}] {v.message}; counterexample: {trace}",
                path=f"model://{program}/{mode}",
                line=0,
                symbol=v.rule,
                snippet=trace,
                engine="protocol",
            )
        )
    return out


# --------------------------------------------------------------------------
# counterexample replay on the real engine
# --------------------------------------------------------------------------

class ReplayClock:
    """Injectable monotonic clock for deterministic timeout replay."""

    def __init__(self, start: float = 0.0) -> None:
        self.t = float(start)

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += float(dt)


class ProtocolMonitor:
    """Machine-checks model invariants against a live ``ServingEngine``.

    ``check_step()`` is called between engine steps: every page the next
    decode/chunk-prefill launch will write must be privately owned
    (refcount 1), and both allocators' internal accounting must be
    consistent.  ``check_quiescent()`` additionally runs the engine's own
    ``check_no_leaks``.
    """

    def __init__(self, srv, hook: bool = True) -> None:
        self.srv = srv
        self.violations: List[str] = []
        self._undo_hook = None
        if hook:
            self.install()

    def install(self) -> None:
        """Hook the chunk-prefill launch: an admit can complete its whole
        prefill inside one ``step()``, so the shared-page write-target check
        must run at the launch site, not just between steps."""
        if self._undo_hook is not None:
            return
        srv = self.srv
        orig = srv._launch_chunk
        page = srv.page_size

        def launch(slot_i, rows):
            slot = srv.slots[slot_i]
            req = slot.request
            if req is not None and slot.row is not None:
                alloc = srv.prefill_set.allocator
                lo = slot.prefill_pos // page
                hi = (
                    min(slot.prefill_pos + srv.chunk_width, req.prompt_len)
                    - 1
                ) // page
                for pi in range(lo, hi + 1):
                    self._shared_write(
                        alloc,
                        int(slot.row[0, pi]),
                        f"chunk prefill slot {slot_i}",
                    )
            return orig(slot_i, rows)

        srv._launch_chunk = launch

        def undo():
            srv._launch_chunk = orig

        self._undo_hook = undo

    def uninstall(self) -> None:
        if self._undo_hook is not None:
            self._undo_hook()
            self._undo_hook = None

    def _allocators(self):
        seen = []
        for aset in (self.srv.prefill_set, self.srv.decode_set):
            if all(a is not aset.allocator for a in seen):
                seen.append(aset.allocator)
        return seen

    def _shared_write(self, alloc, pid: int, what: str) -> None:
        if pid and alloc.refcount(pid) > 1:
            self.violations.append(
                f"proto-write-shared-page: {what} targets page {pid} "
                f"with refcount {alloc.refcount(pid)}"
            )

    def check_step(self) -> List[str]:
        srv = self.srv
        start = len(self.violations)
        for alloc in self._allocators():
            err = alloc.check_consistent()
            if err:
                self.violations.append(f"proto-refcount-conservation: {err}")
        page = srv.page_size
        spec_k = getattr(srv, "spec_k", 0) or 0
        for i, slot in enumerate(srv.slots):
            req = slot.request
            if req is None:
                continue
            if slot.prefilling and slot.row is not None:
                # next chunk writes [prefill_pos, prompt_len) through the row
                alloc = srv.prefill_set.allocator
                lo = slot.prefill_pos // page
                hi = (req.prompt_len - 1) // page
                for pi in range(lo, hi + 1):
                    self._shared_write(
                        alloc, int(slot.row[0, pi]), f"chunk prefill slot {i}"
                    )
            elif not slot.prefilling and slot.pos > 0:
                # decode/verify writes [pos, pos + spec_k] through the table
                alloc = srv.decode_set.allocator
                lo = slot.pos // page
                hi = min(
                    (slot.pos + spec_k) // page, srv.pages_per_slot - 1
                )
                for pi in range(lo, hi + 1):
                    self._shared_write(
                        alloc,
                        int(srv.table.block_tables[i, pi]),
                        f"decode slot {i}",
                    )
                live = set(srv.allocator._refs)
                used = {
                    int(p)
                    for p in srv.table.block_tables[i, : slot.pos // page + 1]
                    if int(p) != 0
                }
                dead = used - live
                if dead:
                    self.violations.append(
                        f"proto-use-after-free: decode slot {i} row maps "
                        f"freed page(s) {sorted(dead)}"
                    )
        return self.violations[start:]

    def check_quiescent(self) -> List[str]:
        start = len(self.violations)
        try:
            self.srv.check_no_leaks()
        except Exception as e:
            self.violations.append(f"proto-page-leak: {e}")
        for alloc in self._allocators():
            err = alloc.check_consistent()
            if err:
                self.violations.append(f"proto-refcount-conservation: {err}")
        return self.violations[start:]


def apply_engine_mutation(srv, name: str):
    """Re-introduce a model mutation into a live engine; returns an undo().

    Only the gate mutations are supported on the real engine:

    * ``drop-drain-free`` — preempted slots keep their pages (the drain
      path's frees are skipped), reproducing the leak the model finds;
    * ``skip-cow-fork`` — a full prefix hit maps the shared tail page into
      the writable row instead of forking it by recompute;
    * ``drop-host-free`` — prefix demotion copies the page into the host
      tier but skips the device-side free, so the page is owned by neither
      tier (needs ``serving.tiering`` enabled);
    * ``drop-migration-free`` — a migration's source-side release keeps the
      slot-table bookkeeping but skips the allocator frees, leaking the
      source replica's pages across its death (``srv`` must be a
      :class:`~deepspeed_tpu.serving.fleet.FleetRouter`).
    """
    from deepspeed_tpu.serving.request import RequestStatus

    if name == "drop-drain-free":
        orig_finish = srv._finish_slot

        def finish(slot_i, status, detail, now):
            if status == RequestStatus.PREEMPTED:
                allocs = {id(srv.allocator): srv.allocator,
                          id(srv.prefill_set.allocator):
                          srv.prefill_set.allocator}
                saved = [(a, a.free) for a in allocs.values()]
                for a, _ in saved:
                    a.free = lambda pages: None
                try:
                    return orig_finish(slot_i, status, detail, now)
                finally:
                    for a, f in saved:
                        a.free = f
            return orig_finish(slot_i, status, detail, now)

        srv._finish_slot = finish

        def undo():
            srv._finish_slot = orig_finish

        return undo

    if name == "skip-cow-fork":
        if srv.prefix_cache is None:
            raise ValueError("skip-cow-fork needs prefix_cache enabled")
        if srv.disaggregated:
            raise ValueError("skip-cow-fork replay supports shared mode only")
        cache = srv.prefix_cache
        alloc = srv.allocator
        orig_lookup = cache.lookup
        orig_alloc = alloc.alloc
        pending: List[int] = []

        def lookup(prompt):
            pages, shared_tokens, cow_page = orig_lookup(prompt)
            if cow_page is not None:
                # defeat the fork: remember the shared page; the admission
                # alloc right after this lookup gets it spliced in writable
                pending.append(cow_page)
                return pages, shared_tokens, None
            return pages, shared_tokens, cow_page

        def alloc_fn(n):
            out = orig_alloc(n)
            if pending and out:
                cow = pending.pop()
                alloc.retain([cow])
                alloc.free([out[0]])
                out[0] = cow
            return out

        cache.lookup = lookup
        alloc.alloc = alloc_fn

        def undo():
            cache.lookup = orig_lookup
            alloc.alloc = orig_alloc

        return undo

    if name == "drop-host-free":
        if getattr(srv, "tiering", None) is None:
            raise ValueError("drop-host-free needs serving.tiering enabled")
        cache = srv.prefix_cache
        alloc = cache.allocator
        orig_evict_one = cache._evict_one

        def evict_one():
            # demotion runs inside _evict_one; silence the device-side free
            # for its duration so the demoted page stays allocated
            orig_free = alloc.free
            alloc.free = lambda pages: None
            try:
                return orig_evict_one()
            finally:
                alloc.free = orig_free

        cache._evict_one = evict_one

        def undo():
            cache._evict_one = orig_evict_one

        return undo

    if name == "drop-migration-free":
        reps = getattr(srv, "replicas", None)
        if reps is None:
            raise ValueError("drop-migration-free needs a FleetRouter")
        saved = []
        for rep in reps:
            eng = rep.srv
            orig_release = eng.release_slot

            def release(slot_i, now=None, *, _eng=eng, _orig=orig_release):
                # the migration path frees the source pages via release_slot
                # right before the payload leaves; silence both allocators
                # for its duration so the bookkeeping proceeds pages-in-hand
                allocs = {id(_eng.allocator): _eng.allocator,
                          id(_eng.prefill_set.allocator):
                          _eng.prefill_set.allocator}
                frees = [(a, a.free) for a in allocs.values()]
                for a, _ in frees:
                    a.free = lambda pages: None
                try:
                    return _orig(slot_i, now=now)
                finally:
                    for a, f in frees:
                        a.free = f

            eng.release_slot = release
            saved.append((eng, orig_release))

        def undo():
            for eng, orig in saved:
                eng.release_slot = orig

        return undo

    raise ValueError(f"unsupported engine mutation: {name!r}")


_EV_RE = re.compile(r"(\w+)(?:\(r(\d+)\))?$")


def replay_trace(
    srv,
    trace,
    prompts,
    max_new_tokens: int = 2,
    clock: Optional[ReplayClock] = None,
    max_steps: int = 200,
) -> dict:
    """Drive a counterexample event trace through a real ``ServingEngine``.

    Each abstract event maps onto the concrete API (``submit`` / ``step`` /
    ``drain`` / clock advance for timeouts); a :class:`ProtocolMonitor`
    checks the model's invariants against the live allocators after every
    step and ``check_no_leaks`` at quiescence.  Returns a dict with ``ok``,
    the recorded ``violations``, and the request handles.
    """
    mon = ProtocolMonitor(srv)
    handles: Dict[int, object] = {}
    drained = False
    preempts = sum(1 for ev in trace if ev.startswith("preempt"))
    steps = 0
    for ev in trace:
        m = _EV_RE.match(ev)
        name, idx = m.group(1), (int(m.group(2)) if m.group(2) else None)
        if name == "submit":
            handles[idx] = srv.submit(
                prompts[idx % len(prompts)],
                max_new_tokens=max_new_tokens,
                seed=7 + (idx or 0),
            )
        elif name == "drain":
            srv.drain(deadline_s=0.0 if preempts else 5.0)
            drained = True
        elif name == "timeout_evict":
            if clock is not None:
                clock.advance(1e6)
            srv.step()
            steps += 1
        elif name == "demote_prefix":
            # tiered LRU eviction: force one leaf out of the index; with the
            # tier wired its KV demotes to the host store
            pc = srv.prefix_cache
            if pc is not None and len(pc):
                pc.evict(keep=len(pc) - 1)
            if getattr(srv, "tiering", None) is not None:
                srv.tiering.flush()
        elif name in ("admit", "prefill_done", "handoff", "decode", "retry",
                      "preempt", "evict_prefix", "restore_prefix"):
            if not drained:
                srv.step()
                steps += 1
        mon.check_step()
    # settle: run the engine to quiescence, then drain and leak-check
    while not drained and steps < max_steps and any(
        s.request is not None for s in srv.slots
    ):
        srv.step()
        steps += 1
        mon.check_step()
    if not drained:
        srv.drain(deadline_s=5.0)
    mon.check_quiescent()
    return {
        "ok": not mon.violations,
        "violations": list(mon.violations),
        "steps": steps,
        "handles": handles,
    }


def replay_fleet_trace(
    fleet,
    trace,
    prompts,
    max_new_tokens: int = 2,
    clock: Optional[ReplayClock] = None,
    max_steps: int = 300,
) -> dict:
    """Drive a fleet-model counterexample through a real ``FleetRouter``.

    Replica events map onto the router API (``replica_preempt`` triggers
    :meth:`FleetRouter.preempt` on the first live replica; migration and
    replica-B events advance the fleet), with one :class:`ProtocolMonitor`
    per replica.  A leak the retirement path detects (``check_no_leaks``
    raising inside :meth:`FleetRouter.step`) is recorded as
    ``proto-replica-page-leak`` rather than propagated, so a mutated fleet
    replays red instead of crashing the harness.
    """
    monitors = {rep.rid: ProtocolMonitor(rep.srv) for rep in fleet.replicas}
    violations: List[str] = []
    handles: Dict[int, object] = {}
    drained = False
    steps = 0

    def step_all() -> None:
        nonlocal steps
        try:
            fleet.step()
        except Exception as e:  # retirement leak-check tripping mid-step
            violations.append(f"proto-replica-page-leak: {e}")
        steps += 1
        for rep in fleet.replicas:
            if rep.alive:
                monitors[rep.rid].check_step()

    for ev in trace:
        m = _EV_RE.match(ev)
        name, idx = m.group(1), (int(m.group(2)) if m.group(2) else None)
        if name == "submit":
            handles[idx] = fleet.submit(
                prompts[idx % len(prompts)],
                max_new_tokens=max_new_tokens,
                seed=7 + (idx or 0),
            )
        elif name == "replica_preempt":
            # the abstract model preempts "the" replica running work; pick
            # the most-loaded live replica so the victim actually holds the
            # trace's sessions (mirrors the router's default victim policy)
            alive = fleet.alive()
            if alive:
                victim = max(alive, key=fleet._load)
                fleet.preempt(victim.rid)
        elif name == "drain":
            try:
                fleet.drain(deadline_s=5.0)
            except Exception as e:
                violations.append(f"proto-replica-page-leak: {e}")
            drained = True
        elif name == "timeout_evict":
            if clock is not None:
                clock.advance(1e6)
            step_all()
        elif name in ("admit", "prefill_done", "handoff", "decode", "retry",
                      "preempt", "admit_b", "migrate_begin", "migrate_commit",
                      "migrate_abort", "decode_b", "replica_die",
                      "evict_prefix", "demote_prefix", "restore_prefix"):
            if not drained:
                step_all()
    # settle: run the fleet to quiescence, then drain and leak-check every
    # replica — the dead ones included; a retired replica must hold nothing
    while (not drained and steps < max_steps
           and any(rep.srv.queue or any(s.request is not None
                                        for s in rep.srv.slots)
                   for rep in fleet.alive())):
        step_all()
    if not drained:
        try:
            fleet.drain(deadline_s=5.0)
        except Exception as e:
            violations.append(f"proto-replica-page-leak: {e}")
    for rep in fleet.replicas:
        try:
            rep.srv.check_no_leaks()
        except Exception as e:
            violations.append(f"proto-replica-page-leak: {e}")
    violations.extend(
        v for mon in monitors.values() for v in mon.violations
    )
    return {
        "ok": not violations,
        "violations": violations,
        "steps": steps,
        "handles": handles,
    }
