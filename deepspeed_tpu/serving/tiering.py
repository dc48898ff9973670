"""Tiered KV cache: a host-DRAM second tier for cold pages (ISSUE 17).

ZeRO-Infinity's overlap-the-slow-tier pattern (PAPERS.md 2104.07857 — the
same shape as DeepSpeed's ``runtime/swap_tensor/async_swapper.py`` and the
AsyncCheckpointWriter here) applied to the serving page pool: HBM holds only
the *hot* working set, and evicted prefix pages spill to pinned host numpy
buffers instead of being dropped. A later prompt that re-hits the demoted
prefix restores the page device-side (one compiled width-1 scatter program)
instead of recomputing it — a cold prefix hit becomes a warm-from-host hit.

Layout: the host store mirrors the device pool's ``[L, P, KV, page, D]``
layout page-for-page (``P`` is the host budget), with the per-page scale
sidecar ``[L, P, KV, 2]`` when the pool is int8 — codes+scales spill as-is,
so PR-12's 0.50x byte halving carries straight to the host tier.

Overlap: ``demote_begin`` only *dispatches* the device-side page slice (an
async read on the compute stream, ordered before any later program can
overwrite the freed page) and hands the arrays to a background worker
thread; the ``jax.device_get`` host sync happens off the step path. Restores
run synchronously at admission (the slot is about to decode through those
pages) and are depth-bounded per step by ``serving.tiering.prefetch_depth``.

Integrity: every spilled buffer carries a CRC32 (``serving.tiering.crc``);
a mismatch on restore is treated as a cold miss — the entry is dropped and
the scheduler recomputes the prefix — never as silent corruption.

Ownership across tiers is machine-checked: the heat ledger grows
demote/restore/host-drop events (``D``/``U``/``V``), Engine G's abstract
model grows an owned-by-host state with a two-tier conservation invariant,
and ``ServingEngine.check_no_leaks`` reconciles ledger handles against the
live store. ``policy_victim_key`` below is the SINGLE definition of spill
victim order — the live engine, the PrefixCache leaf choice and the
``replay_live_tier`` cross-check all rank through it, and it mirrors the
PR-16 what-if simulator (``telemetry.kv_heat._simulate_policy``) exactly,
which is what makes ``tools/kv_heat.py --policy`` diffs meaningful.
"""

from __future__ import annotations

import threading
import time
import zlib
from collections import OrderedDict
from typing import Any, Dict, List, Optional, Sequence, Set, Tuple

import numpy as np

from ..telemetry.tracer import StepTracer

# mirror of telemetry.kv_heat.SPILL_POLICIES (kept literal: runtime.config
# validates against this without importing the telemetry plane)
TIERING_POLICIES = ("idle_lru", "prefix_aware", "slot_priority")


class HostTierError(RuntimeError):
    """Host-tier protocol violation (duplicate key, reserve past budget)."""


def policy_victim_key(policy: str, p: int, led: Any, now: float):
    """Spill-victim sort key for page ``p`` under ``policy`` — bit-identical
    to the PR-16 what-if simulator's ``victim_key`` so live behaviour and
    offline prediction rank victims the same way (ties break on page id).

    ``led`` is a :class:`telemetry.kv_heat.KVHeatLedger` (or anything with
    ``page_last``/``prefix_pages``/``owner``/``sessions``)."""
    age = now - led.page_last.get(p, now)
    if policy == "idle_lru":
        return (-age, p)
    if policy == "prefix_aware":
        # non-prefix pages first (False < True), then oldest
        return (p in led.prefix_pages, -age, p)
    # slot_priority: pages of live recently-active sessions last
    slot = led.owner.get(p)
    ss = led.sessions.get(slot) if slot is not None else None
    sess_last = ss["last"] if ss is not None else -float("inf")
    return (ss is not None, sess_last, -age, p)


class _HostEntry:
    __slots__ = ("slot", "hid", "origin_page", "crc_k", "crc_v", "crc_s",
                 "ready", "failed")

    def __init__(self, slot: int, hid: int, origin_page: int):
        self.slot = slot
        self.hid = hid
        self.origin_page = origin_page
        self.crc_k = 0
        self.crc_v = 0
        self.crc_s = 0
        self.ready = threading.Event()
        self.failed = False


class HostPageStore:
    """Pinned host buffers holding spilled KV pages, keyed by prefix key.

    ``budget_pages`` host slots of ``[L, KV, page, D]`` codes x2 (+ the
    ``[L, KV, 2]`` scale sidecar when quantized). Entry order (an
    ``OrderedDict``) is spill order — the host tier's own LRU, evicted via
    :meth:`drop_lru` when a demotion finds the store full.

    Thread contract: ``reserve``/``drop``/``get``/bookkeeping run on the
    scheduler thread; ``fill``/``abandon`` run on the spill worker. The
    per-entry ``ready`` event is the only cross-thread handshake — ``drop``
    and ``get`` wait on it before touching the buffer slot, so a slot is
    never recycled under an in-flight fill."""

    def __init__(self, budget_pages: int, *, n_layer: int, n_kv_head: int,
                 page_size: int, head_dim: int, dtype: Any,
                 quantized: bool = False, crc: bool = True):
        if budget_pages <= 0:
            raise HostTierError(
                f"HostPageStore needs a positive page budget, got {budget_pages}"
            )
        self.budget_pages = int(budget_pages)
        self.quantized = bool(quantized)
        self.crc = bool(crc)
        dt = np.dtype(dtype)
        shape = (n_layer, self.budget_pages, n_kv_head, page_size, head_dim)
        # host mirrors of the device pool layout ([L, P, KV, page, D])
        self.k_codes = np.zeros(shape, dt)
        self.v_codes = np.zeros(shape, dt)
        self.scales = (
            np.zeros((n_layer, self.budget_pages, n_kv_head, 2), np.float32)
            if self.quantized else None
        )
        self._free: List[int] = list(range(self.budget_pages - 1, -1, -1))
        self._entries: "OrderedDict[Any, _HostEntry]" = OrderedDict()
        self._by_hid: Dict[int, _HostEntry] = {}
        self._hid = 0
        self.crc_failures = 0

    # -- capacity ------------------------------------------------------

    def __contains__(self, key: Any) -> bool:
        return key in self._entries

    def __len__(self) -> int:
        return len(self._entries)

    @property
    def free_slots(self) -> int:
        return len(self._free)

    @property
    def page_bytes(self) -> int:
        """Host bytes per spilled page (codes x2 + scale sidecar)."""
        per = self.k_codes.nbytes + self.v_codes.nbytes
        if self.scales is not None:
            per += self.scales.nbytes
        return per // self.budget_pages

    def host_bytes(self) -> int:
        """Full pinned-buffer footprint (allocated up front, not per-entry)."""
        return self.page_bytes * self.budget_pages

    def used_bytes(self) -> int:
        return self.page_bytes * len(self._entries)

    def handles(self) -> Set[int]:
        """Live host handles — what the heat ledger reconciles against."""
        return set(self._by_hid)

    # -- spill side ----------------------------------------------------

    def reserve(self, key: Any, origin_page: int) -> int:
        """Claim a host slot for ``key``; returns the host handle. The
        buffer contents arrive later via :meth:`fill` (worker thread)."""
        if key in self._entries:
            raise HostTierError(f"host tier already holds key {key!r}")
        if not self._free:
            raise HostTierError("host tier full (evict before reserving)")
        self._hid += 1
        ent = _HostEntry(self._free.pop(), self._hid, int(origin_page))
        self._entries[key] = ent
        self._by_hid[ent.hid] = ent
        return ent.hid

    def fill(self, hid: int, k: Any, v: Any,
             scales: Optional[Any] = None) -> None:
        """Worker-side: copy the fetched page into the reserved slot."""
        ent = self._by_hid.get(int(hid))
        if ent is None:  # dropped while the fill was in flight
            return
        try:
            k = np.asarray(k, self.k_codes.dtype)
            v = np.asarray(v, self.v_codes.dtype)
            self.k_codes[:, ent.slot] = k
            self.v_codes[:, ent.slot] = v
            if self.scales is not None:
                self.scales[:, ent.slot] = np.asarray(scales, np.float32)
            if self.crc:
                ent.crc_k = zlib.crc32(self.k_codes[:, ent.slot].tobytes())
                ent.crc_v = zlib.crc32(self.v_codes[:, ent.slot].tobytes())
                if self.scales is not None:
                    ent.crc_s = zlib.crc32(self.scales[:, ent.slot].tobytes())
        except Exception:
            ent.failed = True
        finally:
            ent.ready.set()

    def put(self, key: Any, origin_page: int, k: Any, v: Any,
            scales: Optional[Any] = None) -> int:
        """Synchronous reserve+fill (tests, replay cross-check)."""
        hid = self.reserve(key, origin_page)
        self.fill(hid, k, v, scales)
        return hid

    def abandon(self, hid: int) -> None:
        """Worker-side: mark an in-flight fill failed (device fetch threw)
        so a waiting ``get``/``drop`` can't hang on the ready event."""
        ent = self._by_hid.get(int(hid))
        if ent is not None:
            ent.failed = True
            ent.ready.set()

    # -- restore side --------------------------------------------------

    def get(self, key: Any) -> Optional[Tuple[np.ndarray, np.ndarray,
                                              Optional[np.ndarray]]]:
        """Page payload for ``key``, or None on miss / failed fill / CRC
        mismatch (the entry is dropped — the caller recomputes)."""
        ent = self._entries.get(key)
        if ent is None:
            return None
        ent.ready.wait()
        bad = ent.failed
        if not bad and self.crc:
            bad = (
                zlib.crc32(self.k_codes[:, ent.slot].tobytes()) != ent.crc_k
                or zlib.crc32(self.v_codes[:, ent.slot].tobytes()) != ent.crc_v
                or (self.scales is not None and
                    zlib.crc32(self.scales[:, ent.slot].tobytes()) != ent.crc_s)
            )
            if bad:
                self.crc_failures += 1
        if bad:
            self.drop(key)
            return None
        k = self.k_codes[:, ent.slot]
        v = self.v_codes[:, ent.slot]
        s = self.scales[:, ent.slot] if self.scales is not None else None
        return k, v, s

    def drop(self, key: Any) -> Optional[int]:
        """Forget ``key`` and recycle its slot; returns the host handle
        (None on miss). Waits out any in-flight fill first — the slot must
        not be handed to a new reservation under a concurrent write."""
        ent = self._entries.pop(key, None)
        if ent is None:
            return None
        ent.ready.wait(timeout=30.0)
        self._by_hid.pop(ent.hid, None)
        self._free.append(ent.slot)
        return ent.hid

    def drop_lru(self) -> Optional[Tuple[Any, int]]:
        """Evict the oldest (first-spilled) entry: ``(key, hid)`` or None."""
        if not self._entries:
            return None
        key = next(iter(self._entries))
        return key, self.drop(key)

    def clear(self) -> None:
        for key in list(self._entries):
            self.drop(key)

    def check_consistent(self) -> None:
        """Slot bookkeeping invariants (free list + entries partition the
        budget; hid index agrees). Raises AssertionError on violation."""
        used = {e.slot for e in self._entries.values()}
        assert len(used) == len(self._entries), "host slot double-booked"
        assert used.isdisjoint(self._free), "host slot both free and used"
        assert len(used) + len(self._free) == self.budget_pages, (
            f"host slots leaked: {len(used)} used + {len(self._free)} free "
            f"!= {self.budget_pages}"
        )
        assert {e.hid for e in self._entries.values()} == set(self._by_hid), (
            "host hid index out of sync"
        )


class KVTieringEngine:
    """Spill/restore engine between one device pool and a HostPageStore.

    Owns the background spill worker (the async_swapper pattern: the
    scheduler thread only dispatches device-side page slices and queues
    them; the worker does the blocking ``jax.device_get`` and the host
    copy). The scheduler wires ``demote_begin`` in as the PrefixCache's
    ``demote_sink`` and ``select_leaf`` as its ``victim_order``, binds the
    compiled width-1 restore program via :meth:`bind_restore_exec`, and
    drives restores from admission (``ServingEngine._tier_prefetch``)."""

    def __init__(self, store: HostPageStore, pset: Any, *,
                 policy: str = "idle_lru", prefetch_depth: int = 4,
                 clock=time.monotonic):
        if policy not in TIERING_POLICIES:
            raise HostTierError(
                f"unknown tiering policy {policy!r}; pick from {TIERING_POLICIES}"
            )
        self.store = store
        self.pset = pset
        self.policy = policy
        self.prefetch_depth = int(prefetch_depth)
        self.clock = clock
        # wired by ServingEngine.attach_heat / _ensure_compiled
        self.ledger: Optional[Any] = None
        self._restore_exec = None
        # ISSUE 18 satellite: device-index residency predicate (the
        # scheduler wires ``prefix_cache._entries.__contains__``) — lets
        # the tier drop host entries whose parent chain link left BOTH
        # tiers instead of waiting for host-LRU to age them out. None
        # (standalone/fuzz construction) disables the eager sweep.
        self.device_resident = None
        # counters (stats()["kv_tiering"])
        self.spills = 0
        self.restores = 0
        self.restore_misses = 0
        self.host_evictions = 0
        self.orphan_drops = 0
        self.spilled_bytes = 0
        self.restored_bytes = 0
        # async spill worker: scheduler enqueues (hid, device arrays);
        # worker device_gets + fills off the step path
        self._lock = StepTracer._new_lock()
        self._queue: List[Tuple[int, Any, Any, Any]] = []
        self._wake = threading.Event()
        self._idle = threading.Event()
        self._idle.set()
        self._closed = False
        self._worker = threading.Thread(
            target=self._spill_loop, name="kv-tier-spill", daemon=True
        )
        self._worker.start()

    # -- worker --------------------------------------------------------

    def _spill_loop(self) -> None:
        import jax  # local: worker thread only ever host-syncs

        while True:
            self._wake.wait()
            with self._lock:
                if self._closed and not self._queue:
                    return
                batch, self._queue = self._queue, []
                self._wake.clear()
            for hid, k_dev, v_dev, s_dev in batch:
                try:
                    k = np.asarray(jax.device_get(k_dev))
                    v = np.asarray(jax.device_get(v_dev))
                    s = (np.asarray(jax.device_get(s_dev))
                         if s_dev is not None else None)
                    self.store.fill(hid, k, v, s)
                except Exception:
                    self.store.abandon(hid)
            with self._lock:
                if not self._queue:
                    self._idle.set()

    def flush(self, timeout: float = 30.0) -> None:
        """Block until every queued spill has landed in the host store."""
        self._wake.set()
        self._idle.wait(timeout)

    def close(self) -> None:
        with self._lock:
            self._closed = True
        self._wake.set()
        self._worker.join(timeout=5.0)

    # -- spill side ----------------------------------------------------

    def select_leaf(self, leaves: Sequence[Tuple[Any, int]]):
        """PrefixCache ``victim_order`` hook: rank evictable leaves by the
        configured policy's victim key (heat-blind before attach_heat)."""
        if not leaves:
            return None
        led = self.ledger
        if led is None:
            return leaves[0]
        now = float(self.clock())
        return min(
            leaves,
            key=lambda kp: policy_victim_key(self.policy, kp[1], led, now),
        )

    def demote_begin(self, key: Any, pid: int) -> Optional[int]:
        """PrefixCache ``demote_sink`` hook: snapshot device page ``pid``
        toward the host tier and return the host handle (None if the key is
        already host-held). Called BEFORE the caller frees the device page:
        the ledger ``D`` event lands before the F/E pair, so no trace
        prefix ever shows the page in neither tier, and the device-side
        slice is dispatched before any later program can overwrite the
        about-to-be-freed page (single-stream ordering)."""
        if key in self.store:
            return None
        while self.store.free_slots == 0:
            dropped = self.store.drop_lru()
            if dropped is None:
                return None
            self.host_evictions += 1
            if self.ledger is not None:
                self.ledger.host_drop(dropped[1])
        # eager orphan sweep BEFORE the D event lands: host-LRU above (or
        # an earlier non-demoting device eviction) may have severed a
        # chain link, and the lockstep trace pin requires any resulting V
        # events to precede D, never split a D→F→E triple. ``key`` itself
        # is mid-demotion (already popped from the device index, not yet
        # reserved here) — treat it as resident so its own host children
        # survive the sweep.
        self.drop_orphans(keep=key)
        # async read of the page column; device_get happens on the worker
        k_dev, v_dev, s_dev = self.pset.page_column(pid)
        hid = self.store.reserve(key, pid)
        with self._lock:
            self._queue.append((hid, k_dev, v_dev, s_dev))
            self._idle.clear()
        self._wake.set()
        self.spills += 1
        self.spilled_bytes += self.store.page_bytes
        if self.ledger is not None:
            self.ledger.demote(pid, hid)
        return hid

    def drop_orphans(self, keep: Any = None) -> int:
        """Eagerly drop host entries whose parent chain link left BOTH
        tiers (ISSUE 18 satellite, closing the PR-17 documented edge): a
        chained-hash key is only reachable through its parent, so once the
        parent is neither device-resident nor host-held the entry can
        never be restored — before this sweep it squatted in the host
        budget until LRU aged it out. Each drop emits a ledger ``V`` event
        exactly like a host-LRU eviction. Runs to a fixpoint (dropping an
        orphan may orphan its own host-held children). ``keep`` names a
        key that is mid-transition (being reserved right now) and counts
        as resident. Returns the number of entries dropped; no-ops when no
        ``device_resident`` predicate is wired (standalone fuzz rigs) —
        reachability is unknowable without the device index."""
        if self.device_resident is None:
            return 0
        dropped_n = 0
        changed = True
        while changed:
            changed = False
            for key in list(self.store._entries):
                parent = key[0] if isinstance(key, tuple) and key else None
                # only proper chain parents are links: tuples. Roots
                # (parent None) and foreign key shapes (replay_live_tier
                # uses ("page", p) ids) have nothing to sever.
                if not isinstance(parent, tuple) or parent == keep:
                    continue
                if parent in self.store or self.device_resident(parent):
                    continue
                hid = self.store.drop(key)
                if hid is not None:
                    self.orphan_drops += 1
                    dropped_n += 1
                    changed = True
                    if self.ledger is not None:
                        self.ledger.host_drop(hid)
        return dropped_n

    # -- restore side --------------------------------------------------

    def bind_restore_exec(self, fn) -> None:
        """Install the compiled width-1 restore program
        (``serving_kv_restore``): ``(pools..., k, v[, s], dst) -> pools``."""
        self._restore_exec = fn

    def restore(self, key: Any, pid: int) -> bool:
        """Copy ``key``'s host page back into freshly allocated device page
        ``pid``. False on cold miss (absent / failed / CRC mismatch) — the
        caller recomputes the prefix instead."""
        payload = self.store.get(key)  # waits out an in-flight spill
        if payload is None:
            self.restore_misses += 1
            # a CRC-mismatch drop inside get() severs the chain below
            # ``key`` — sweep its now-unreachable host descendants
            self.drop_orphans()
            return False
        if self._restore_exec is None:
            raise HostTierError("restore program not bound (call verify path "
                                "through ServingEngine)")
        k, v, s = payload
        # [L, KV, page, D] -> packed width-1 [L, 1, KV, page, D]
        pk = np.ascontiguousarray(k)[:, None]
        pv = np.ascontiguousarray(v)[:, None]
        dst = np.array([pid], np.int32)
        packed = [pk, pv] + ([np.ascontiguousarray(s)[:, None]] if s is not None else [])
        (self.pset.cache,) = self._restore_exec(self.pset.cache, *packed, dst)
        hid = self.store.drop(key)  # exactly-one-tier: host copy retires
        self.restores += 1
        self.restored_bytes += self.store.page_bytes
        if self.ledger is not None and hid is not None:
            self.ledger.restore_up(hid, pid)
        return True

    # -- audit ---------------------------------------------------------

    def check_consistent(self, prefix_cache: Optional[Any] = None
                         ) -> Optional[str]:
        """Cross-tier invariants; returns a one-line mismatch or None."""
        try:
            self.store.check_consistent()
        except AssertionError as e:
            return str(e)
        if self.ledger is not None:
            got = self.store.handles()
            want = self.ledger.host_handles
            if got != want:
                return (f"host handles diverge: store={sorted(got)} "
                        f"ledger={sorted(want)}")
        if prefix_cache is not None:
            both = [k for k in prefix_cache._entries if k in self.store]
            if both:
                return f"keys in BOTH tiers (device index + host): {both[:4]}"
        return None

    def stats(self) -> Dict[str, Any]:
        return {
            "policy": self.policy,
            "host_budget_pages": self.store.budget_pages,
            "host_pages": len(self.store),
            "host_bytes": self.store.host_bytes(),
            "host_used_bytes": self.store.used_bytes(),
            "spills": self.spills,
            "restores": self.restores,
            "restore_misses": self.restore_misses,
            "host_evictions": self.host_evictions,
            "orphan_drops": self.orphan_drops,
            "crc_failures": self.store.crc_failures,
            "spilled_bytes": self.spilled_bytes,
            "restored_bytes": self.restored_bytes,
        }


def replay_live_tier(
    records: Sequence[Dict[str, Any]],
    pool: str,
    policy: str = "idle_lru",
    resident_fraction: float = 0.5,
) -> Dict[str, Any]:
    """Satellite 1: replay a recorded heat trace against the LIVE tier
    implementation — victims ranked by :func:`policy_victim_key`, every
    spill/restore flowing through a real :class:`HostPageStore` (synthetic
    page payloads, CRC verified on every restore) — and return the same
    stats dict as ``telemetry.kv_heat.evaluate_spill_policies`` so
    ``tools/kv_heat.py --policy`` can diff predicted vs actual field by
    field. Any divergence means the simulator and the engine no longer
    agree on victim order or residency accounting."""
    from ..telemetry.kv_heat import KVHeatError, replay_heat

    if policy not in TIERING_POLICIES:
        raise HostTierError(
            f"unknown tiering policy {policy!r}; pick from {TIERING_POLICIES}"
        )
    meta = next(
        (r for r in records
         if r.get("kind") == "kv_heat_meta" and r.get("pool") == pool),
        None,
    )
    if meta is None:
        raise KVHeatError(f"pool {pool!r}: no kv_heat_meta record in trace")
    capacity = int(meta["capacity"])
    page_bytes = int(meta.get("page_bytes") or 0)
    cap = max(1, int(capacity * float(resident_fraction)))

    store = HostPageStore(
        max(1, capacity), n_layer=1, n_kv_head=1, page_size=4, head_dim=2,
        dtype=np.float32, crc=True,
    )

    def spill(p: int) -> None:
        store.put(("page", p), p,
                  np.full((1, 1, 4, 2), float(p), np.float32),
                  np.full((1, 1, 4, 2), float(p) + 0.5, np.float32))

    def unspill(p: int) -> bool:
        payload = store.get(("page", p))
        if payload is None:
            return False
        k, v, _ = payload
        ok = (float(k[0, 0, 0, 0]) == float(p)
              and float(v[0, 0, 0, 0]) == float(p) + 0.5)
        store.drop(("page", p))
        return ok

    resident: Set[int] = set()
    spilled: Set[int] = set()
    stats = {"spills": 0, "restore_stalls": 0, "restored_pages": 0}

    def make_room(n: int, led, now: float, pinned: Set[int]) -> None:
        while len(resident) + n > cap:
            candidates = [p for p in resident if p not in pinned]
            if not candidates:
                break
            victim = min(
                candidates,
                key=lambda p: policy_victim_key(policy, p, led, now),
            )
            resident.discard(victim)
            spilled.add(victim)
            spill(victim)
            stats["spills"] += 1

    def admit(pages: Sequence[int], led, now: float) -> None:
        pages = [int(p) for p in pages]
        new = [p for p in pages if p not in resident]
        if not new:
            return
        make_room(len(new), led, now, pinned=set(pages))
        for p in new:
            if p in spilled:
                spilled.discard(p)
                unspill(p)
            resident.add(p)

    def require(pages: Sequence[int], led, now: float) -> int:
        need = [int(p) for p in pages if int(p) in spilled]
        if not need:
            return 0
        make_room(len(need), led, now, pinned={int(p) for p in pages})
        for p in need:
            spilled.discard(p)
            if not unspill(p):
                raise HostTierError(f"live-tier restore lost page {p}")
            resident.add(p)
        return len(need)

    def on_event(ev: Tuple, led) -> None:
        op = ev[0]
        now = float(ev[1])
        if op == "A":
            admit(ev[2], led, now)
        elif op == "B":
            admit([p for p, _c in ev[2]], led, now)
        elif op in ("R", "H"):
            n = require(ev[2], led, now)
            if n:
                stats["restore_stalls"] += 1
                stats["restored_pages"] += n
        elif op == "F":
            for p in ev[2]:
                p = int(p)
                if p not in led.refs:  # final free: page left the pool
                    resident.discard(p)
                    if p in spilled:
                        spilled.discard(p)
                        store.drop(("page", p))
        elif op == "touch":
            _, t, _step, batch = ev
            sess = led.sessions
            stalls = 0
            for slot, wp, n_pages in batch:
                ss = sess.get(slot)
                if ss is not None and "pages" in ss:
                    pages = ss["pages"][: int(n_pages)]
                else:
                    pages = [int(wp)]
                n = require(pages, led, float(t))
                if n:
                    stalls += 1
                    stats["restored_pages"] += n
            stats["restore_stalls"] += stalls
        elif op == "S":
            ss = led.sessions.get(int(ev[2]))
            if ss is not None:
                ss["pages"] = [int(p) for p in ev[5]]
            admit(ev[5], led, now)

    replay_heat(records, pool, on_event=on_event)
    store.check_consistent()
    return {
        "spills": stats["spills"],
        "spilled_bytes": stats["spills"] * page_bytes,
        "restore_stalls": stats["restore_stalls"],
        "restored_pages": stats["restored_pages"],
        "restored_bytes": stats["restored_pages"] * page_bytes,
    }
