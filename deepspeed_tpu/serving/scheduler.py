"""Continuous-batching scheduler: slot-based decode over the paged KV pool.

The serving answer to DeepSpeed-Inference's throughput story (PAPERS.md
2207.00032) under XLA's static-shape constraint (2605.25645): instead of one
static batch per ``generate`` call, a fixed array of ``max_slots`` decode
slots advances through ONE compiled decode-shaped program per step, while
finished sequences vacate their slot mid-flight and queued requests are
admitted into free slots via prefill-insertions (ONE compiled prefill
program: the chunk program where ``serving.prefill_chunk_tokens`` is set,
else the whole-prompt program; a prompt's length selects nothing). A fixed,
config-derived set of executables exists for the
lifetime of the engine — ``ServingEngine.executables``, exact-checked by
``verify()`` — because every input shape is a function of the ``serving``
config alone:

- tokens/seq_lens/keys: ``[max_slots]`` — inactive slots ride along pointed
  at the scratch page (their compute is garbage nobody reads; all ops are
  row-independent, so active slots are unaffected).
- prompts: right-padded to the static prefill (or chunk) width, true length
  traced.
- the KV cache: a paged pool + per-slot block tables (serving/kv_cache.py),
  so sequence length never appears in any array shape.

Serving hot-path shapes (ISSUE 10), all off by default and all preserving
the token streams:

- **Self-speculative decode** (``serving.speculative``): the scheduler
  proposes ``k`` draft tokens per slot host-side (prompt-lookup n-grams over
  prompt+output) and ONE ``paged_verify_step`` program replaces the decode
  step, scoring all k+1 positions per slot per step and accepting the
  longest matching prefix — decode is memory-bound (PR-5 roofline), so the
  extra verified tokens are nearly free and an accepted draft advances a
  slot several tokens per step. Greedy-only; the emitted stream is
  BIT-identical to sequential decode (tested), rejected-draft K/V rolls
  back by being overwritten before anything attends it.
- **Shared-prefix KV reuse** (``serving.prefix_cache``): full prompt pages
  register in a chained-hash index after prefill; later prompts map the
  matching page-aligned prefix into their block table (refcounted pages)
  and prefill only the tail through the chunk program. A full-prefix hit
  copy-on-write-forks the last page (recomputed privately — the shared
  original is never written) and collapses TTFT to roughly one chunk step.
- **Chunked prefill** (``serving.prefill_chunk_tokens``): EVERY prompt
  prefills in fixed-width page-aligned chunks, ONE chunk per scheduler step,
  so a long prompt no longer stalls co-resident decode slots for its whole
  width (TPOT invariance, tested), and a prompt that fits one chunk takes
  one call of the chunk program, its first chunk and its last (``whole=1``
  on the call's ``ds.serve.launch`` leaf). Such an engine builds TWO model
  programs, the decode step and the chunk program: the whole-prompt program
  (``max_prompt_len`` rows wide) is neither compiled nor called. It stays
  the cold prompts' program where ``prefill_chunk_tokens`` is 0, with or
  without a prefix cache (whose one-page chunks carry the tails of hits
  only).

One step in flight (ISSUE 54): a call of :meth:`ServingEngine.step` launches
step n+1 before it reads step n's tokens. The sampled token stays on the
device as the next step's input (the programs' ``prev`` / ``src`` operands),
the host's tables advance at the launch (their dispatched side: ``seq_lens``,
the keys, ``_Slot.sent``) and what the host has read is kept apart (the
emitted side: ``_Slot.pos`` / ``step``, ``req.tokens``), a stop by count gives
a slot no further row and a stop the tokens decide is seen one step late, its
row launched ahead dropped. The host's turn between two programs lies under
the program that runs. A server whose next rows wait for its tokens
(speculation, a disaggregated handoff) launches nothing ahead, through the
same loop.

Robustness: admission control (queue-depth + KV-page budget) rejects at the
door; per-request deadlines evict mid-flight to a TRUNCATED response; an
over-long ask is clamped at submit. A stuck or runaway request can therefore
never wedge the batch — the invariant the timeout tests pin down.

Resilience (ISSUE 7): :meth:`drain` is the graceful-shutdown path (stop
admission, finish in-flight up to ``serving.drain_deadline_s``, evict the
rest as PREEMPTED — slots and KV pages always reclaimed); transiently
failed slots (fault-injected stalls today, real slot faults tomorrow)
re-enqueue their request with exponential backoff up to
``serving.retry_max`` times before going terminal FAILED.

Determinism: slot ``b``'s token stream is bit-identical to a sequential
``generate`` of the same request (see serving/model.py for why), which the
token-equivalence test asserts for mixed-length streams.
"""

from __future__ import annotations

import time
import weakref
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Deque, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..moe.expert_share import experts_streamed
from ..ops.pallas import grouped_experts
from ..ops.pallas.decode_attention import paged_walk_steps
from ..ops.pallas.latent_attention import latent_walk_steps
from ..telemetry import parts, spans
from ..telemetry.registry import MetricsRegistry
from ..telemetry.request_trace import LATENCY_BUCKETS, RequestTracer
from ..utils.logging import log_dist
from . import model as smodel
from .kv_cache import (
    SCRATCH_PAGE,
    PageAllocatorError,
    PrefixCache,
    SlotTable,
    pages_for,
    pool_bytes,
    refusals,
    scales_bytes,
)
from .placement import Placement, ProgramSet
from .request import Request, RequestStatus
from .tiering import HostPageStore, KVTieringEngine

# TTFT/TPOT/queue-wait histogram buckets (seconds): sub-ms CPU-sim steps
# through multi-second queue waits. Defined in telemetry/request_trace.py so
# trace-derived quantiles (tools/request_trace.py) interpolate over the SAME
# bounds as these histograms and reproduce stats() exactly (ISSUE 11).


def _host_prng_key(seed: int) -> np.ndarray:
    """``jax.random.PRNGKey(seed)``'s raw [2]-uint32 data, built host-side.

    The admission path needs the key only as numpy input to the compiled
    prefill program; materializing it through ``jax.random.PRNGKey`` +
    ``np.asarray`` dispatched a device op and a device→host sync per
    admission (dslint ``host-sync-in-step``). For the default threefry2x32
    impl and an int32-range non-negative seed — every realistic request
    seed — the key is just ``[0, seed]``, identically under x64 on or off:
    bit-parity with ``generate`` at zero device round-trips. Anything else
    (negative / >= 2**31 seeds are canonicalized by jax in x64-dependent
    ways, other PRNG impls lay keys out differently) takes the exact jax
    path rather than guessing."""
    if (
        jax.config.jax_default_prng_impl == "threefry2x32"
        and 0 <= seed < 2**31
    ):
        return np.array([0, seed], np.uint32)
    return np.asarray(jax.random.PRNGKey(seed))


def _tokens_of(tokens, prev, src):
    """The decode rows' tokens inside a step program: the host's ``tokens``,
    and for a row whose last token the host has not read (``src >= 0``: the
    step that sampled it is still in flight) that place of ``prev``, the
    token output ``[slots + 1]`` of that step (a slot's own row, or the last
    place where the prompt's final chunk rode it)."""
    return jnp.where(src >= 0, prev[jnp.maximum(src, 0)], tokens)


def gather_fn(cache, src):
    """Page columns ``src`` of the paged pools, packed ``[L, n, KV, page, D]``
    a pool (an int8 cache's scales ride along): what crosses placements,
    engines or tiers."""
    return tuple(x[:, src] for x in (cache.k, cache.v, cache.scales) if x is not None)


def scatter_fn(cache, *packed):
    """``packed = (*payloads, dst)``: :func:`gather_fn`'s payloads into page
    columns ``dst`` of the paged pools → ``(cache,)``."""
    *packed, dst = packed
    fields = [f for f in ("k", "v", "scales") if getattr(cache, f) is not None]
    return (cache._replace(**{
        f: getattr(cache, f).at[:, dst].set(x) for f, x in zip(fields, packed)
    }),)


def restore_fn(cache, *packed):
    """:func:`scatter_fn` under the name the host tier's program has in a trace."""
    return scatter_fn(cache, *packed)


@dataclass
class _Slot:
    request: Optional[Request] = None
    pages: List[int] = field(default_factory=list)  # full row: shared + private
    pos: int = 0    # tokens currently in this slot's cache (the emitted side: what the host has read)
    step: int = 0   # decode steps completed (read back: the emitted side)
    # decode rows launched (the dispatched side: ``step`` of them are read
    # back, one more while a step is in flight)
    sent: int = 0
    # the first token is the last place of the step in flight: the prompt's
    # final chunk rode it, and the slot's rows are launched from there on
    first_due: bool = False
    # the slot was handed on with this residency's LAST row in flight (a stop
    # by count is known at the launch): the step's read finishes the request
    handed: bool = False
    keys: Optional[np.ndarray] = None  # [max_new-1, 2] u32 decode sampling keys
    # -- ISSUE 10: chunked prefill + prefix sharing --------------------
    # True while the prompt is still prefilling chunk-by-chunk; the main
    # slot-table row stays scratch (the batched decode must not touch this
    # slot's real pages) and ``row`` below carries the real block table
    prefilling: bool = False
    prefill_pos: int = 0               # prompt tokens prefilled so far
    row: Optional[np.ndarray] = None   # [1, pages_per_slot] real block table
    shared_pages: int = 0              # leading row entries mapped from the index
    # -- ISSUE 14: disaggregated placements ----------------------------
    # prompt pages on the PREFILL placement's pool (shared + private);
    # freed right after the gather→scatter handoff into ``pages``
    prefill_pages: List[int] = field(default_factory=list)
    # the in-flight first-token device array of a dispatched prefill —
    # the decode placement polls ``.is_ready()`` instead of blocking, so
    # decode batches never wait on another core-set's prefill compute
    pending_tok: Optional[Any] = None
    # the launch number of the program that samples this slot's first token
    # (a whole prefill, the prompt's last chunk): what the leaf that hands
    # the token out names in ``firsts``, and the request's ``first_launch``
    first_launch: int = 0
    # the expert layers' token counts of this prompt's chunk calls that rode
    # no decode step (device arrays until its last chunk's token is fetched)
    # and the prompt tokens those calls advanced
    moe_counts: List[Any] = field(default_factory=list)
    moe_tokens: int = 0


@dataclass
class _Flight:
    """A step that is launched and not read: what :meth:`ServingEngine._resolve`
    emits from once the host has its tokens."""
    out: Any        # the program's results after the pools, on the device: the tokens, the expert loads
    rows: List[tuple]   # (slot index, the _Slot that held it at the launch) of the rows launched
    t0: float       # the dispatch leaf's opening, on the engine's clock
    launch: int = 0   # the step program's launch number (``flight`` on the leaves that read it)
    drafts: dict = field(default_factory=dict)   # a verify step's, by slot
    started: Optional[tuple] = None   # (slot index, _Slot) whose prompt's last chunk rode this step
    rode: int = 0   # prompt tokens of the chunk that rode


class ServingEngine:
    """Continuous-batching front end over an :class:`InferenceEngine`.

    Construct via ``InferenceEngine.serve()`` (or directly); drive with
    :meth:`submit` + :meth:`step`, or :meth:`run` to drain. ``clock`` is
    injectable for deterministic timeout tests.

    Concurrency contract (ISSUE 8 dsan audit): this engine is
    **single-threaded by design** — ``submit``/``step``/``drain``/``stats``
    all mutate ``queue``/``slots``/``completed`` and the stats counters
    with no lock, and must run on the one scheduler thread. ``drain`` is
    the cooperative shutdown path: the PreemptionGuard's SIGTERM handler
    only sets a flag, and the scheduler thread calls ``drain`` at the next
    step boundary (never from the signal frame). A future multi-threaded
    front end must put a lock around ``submit`` and the ``completed``
    ledger before relaxing this — Engine C will flag the first thread this
    module grows that touches them."""

    def __init__(self, engine, config=None, clock=time.monotonic, fault_injector=None,
                 tracer=None, heat_tracer=None, journal=None):
        from ..runtime.config import ServingConfig

        if config is None:
            config = ServingConfig()
        elif isinstance(config, dict):
            config = ServingConfig.from_dict(config)
        self.config = config
        self.engine = engine
        self.clock = clock
        # request-lifecycle tracing (ISSUE 11): explicit tracer wins, else
        # the owning engine's telemetry plane provides one
        # (telemetry.request_trace), else tracing is off (zero overhead —
        # every hook is one None check)
        self.tracer: Optional[RequestTracer] = (
            tracer if tracer is not None
            else getattr(getattr(engine, "telemetry", None), "request_tracer", None)
        )
        # resilience (ISSUE 7): deterministic fault injection + drain state
        self.fault_injector = (
            fault_injector
            if fault_injector is not None
            else getattr(engine, "fault_injector", None)
        )
        self._draining = False
        self._admissions = 0  # 1-based admission ordinal (stall injection)
        mcfg = engine.model_config
        if not hasattr(mcfg, "serving_family"):
            raise ValueError(
                "ServingEngine serves a model whose config gives the paged "
                "programs its pieces (serving_family(): the gpt2 family, "
                "including injected HF GPT-2, exaone_moe, mistral4, "
                "longcat_flash, phi4flash, zaya, qwen3_next, xing4 and ling3); got "
                f"{type(mcfg).__name__}"
            )
        int8_pages = bool(config.kv_cache_dtype) and jnp.dtype(config.kv_cache_dtype) == jnp.dtype(jnp.int8)
        if int8_pages and hasattr(mcfg, "per_head_cache"):
            # an int8 page carries one scale a cached head: a family that would
            # cache PAIRS of heads keeps a head a published head instead
            mcfg = mcfg.per_head_cache()
        self.model_config = mcfg
        fam = self.family = mcfg.serving_family()
        # what a family holds besides paged per-head pages (window rings, a
        # latent pool, recurrent state, carried rows: ``kv_cache.STATE_KINDS``)
        # the mechanisms that move, share, shard or re-code pages do not handle
        plc_ = getattr(config, "placement", None)
        for on, what in (
            (getattr(getattr(config, "prefix_cache", None), "enabled", False),
             "serving.prefix_cache"),
            (getattr(getattr(config, "tiering", None), "enabled", False),
             "serving.tiering"),
            (int8_pages, "serving.kv_cache_dtype=int8"),
            (plc_ is not None and max(
                int(getattr(plc_, k, 0) or 0)
                for k in ("tp", "decode_tp", "prefill_tp")) > 1,
             "serving.placement.tp > 1"),
            (plc_ is not None and bool(getattr(plc_, "disaggregate", False)),
             "serving.placement.disaggregate"),
            (bool(getattr(getattr(config, "speculative", None), "enabled", False)),
             "serving.speculative"),
        ):
            if on:
                self._refuse_unhandled(what)

        page = int(config.page_size)
        self.page_size = page
        # static prefill width: max_prompt_len rounded up to whole pages
        self.prefill_pages = pages_for(config.max_prompt_len, page)
        self.prefill_width = self.prefill_pages * page
        self.max_total_len = min(
            int(config.max_prompt_len) + int(config.max_new_tokens),
            int(mcfg.n_positions),
        )
        if self.prefill_width > mcfg.n_positions:
            raise ValueError(
                f"serving.max_prompt_len (page-rounded to {self.prefill_width}) "
                f"exceeds the model's n_positions={mcfg.n_positions}"
            )
        self.pages_per_slot = pages_for(self.max_total_len, page)

        self.cache_dtype = (
            jnp.dtype(config.kv_cache_dtype).type if config.kv_cache_dtype
            else engine.dtype
        )
        self.max_slots = int(config.max_slots)
        pcfg = getattr(config, "prefix_cache", None)
        self.prefix_enabled = bool(pcfg and pcfg.enabled)
        cw = int(getattr(config, "prefill_chunk_tokens", 0) or 0)
        # the ONE fact that says which program prefills a cold prompt, for
        # admission and for the program set alike: the chunk program (every
        # cold prompt, whatever its length; no whole-prompt program is built)
        # or, with no ``prefill_chunk_tokens``, the whole-prompt program
        self._chunk_cold = cw > 0
        if cw > 0:
            self.chunk_width = pages_for(cw, page) * page
        elif self.prefix_enabled:
            # prefix-hit tails always run through the chunk program
            self.chunk_width = page
        else:
            self.chunk_width = 0
        if self.chunk_width > self.prefill_width:
            self.chunk_width = self.prefill_width
        # a window layer's ring: the window before a program's first query,
        # the tokens one call writes (a chunk, a verify step's drafts, one
        # token) and a page of slack for where in a page the window starts
        spec_ = getattr(config, "speculative", None)
        self.ring_pages = (
            pages_for(
                max(fam.windows) + max(
                    self.chunk_width,
                    int(spec_.k) + 1 if spec_ is not None and spec_.enabled else 1,
                ), page,
            ) + 1
            if any(fam.windows) else 0
        )

        # -- ISSUE 14: placements + program sets ---------------------------
        # Every program compiles FOR a placement (mesh slice + spec table);
        # each placement owns its pools, allocator and placed params as one
        # ProgramSet. Default: one shared single-device placement — the
        # pre-ISSUE-14 engine, byte-for-byte.
        plc = getattr(config, "placement", None)
        tp = int(getattr(plc, "tp", 1) or 1) if plc is not None else 1
        self.disaggregated = bool(getattr(plc, "disaggregate", False)) if plc is not None else False
        decode_tp = (int(getattr(plc, "decode_tp", 0) or 0) or tp) if plc is not None else tp
        prefill_tp = (int(getattr(plc, "prefill_tp", 0) or 0) or tp) if plc is not None else tp
        if not self.disaggregated:
            decode_tp = prefill_tp = tp
        self.tp = tp
        if max(decode_tp, prefill_tp) > 1 and getattr(engine, "quantized", False):
            raise ValueError(
                "serving.placement.tp > 1 requires unquantized weights (the "
                "rank-major QKV permute operates on the plain injected tree); "
                "int8 KV pages (serving.kv_cache_dtype) shard fine"
            )
        all_devices = jax.devices()
        # ISSUE 18: a fleet offsets each replica's device window so replicas
        # own disjoint core-sets — replica i serves from
        # devices[base : base + decode_tp (+ prefill_tp)]
        base = int(getattr(plc, "device_base", 0) or 0) if plc is not None else 0
        devices = all_devices[base:]
        n_dev = decode_tp + (prefill_tp if self.disaggregated else 0)
        if n_dev > len(devices):
            raise ValueError(
                f"serving.placement needs {n_dev} devices "
                f"(decode_tp={decode_tp}"
                + (f" + prefill_tp={prefill_tp}" if self.disaggregated else "")
                + (f" from device_base={base}" if base else "")
                + f"), only {len(devices)} visible"
            )
        self.decode_placement = Placement(
            "decode" if self.disaggregated else "shared",
            devices[:decode_tp], decode_tp,
        )
        self.decode_placement.local_model_config(mcfg)  # fail fast on divisibility
        # int8 KV pages (ISSUE 12): pools store codes, kv_scales carries the
        # per-(layer, page, kv-head) block scales beside them — every page-id
        # mechanism (refcounted sharing, COW fork, prefix eviction) moves the
        # scale with the page for free. At tp > 1 the pools (and scales)
        # shard 1/tp over the KV-head axis; page ids stay global.
        self.decode_set = ProgramSet(
            self.decode_placement, mcfg, int(config.num_pages), page,
            self.cache_dtype, engine.params,
            ring_slots=self.max_slots, ring_pages=self.ring_pages,
        )
        if self.disaggregated:
            # the prefill pool only ever holds PROMPT pages (decode-side
            # reservations are always private copies): auto-size it to
            # max_slots concurrent prompts + prefix-index headroom + scratch
            pnp = int(getattr(plc, "prefill_num_pages", 0) or 0)
            if pnp <= 0:
                pnp = min(
                    int(config.num_pages),
                    2 * self.max_slots * self.prefill_pages + 1,
                )
            self.prefill_placement = Placement(
                "prefill", devices[decode_tp:decode_tp + prefill_tp], prefill_tp,
            )
            self.prefill_placement.local_model_config(mcfg)
            self.prefill_set = ProgramSet(
                self.prefill_placement, mcfg, pnp, page,
                self.cache_dtype, engine.params,
            )
        else:
            self.prefill_placement = self.decode_placement
            self.prefill_set = self.decode_set
            if decode_tp == 1 and self.decode_placement.device is jax.devices()[0]:
                # the engine's own arrays, but for a leaf the placement laid
                # out anew (``Placement.shard_params``): hand the tree back,
                # or the table would lie on the device twice
                engine.params = self.decode_set.params
        self.quantized = self.decode_set.quantized
        if self.pages_per_slot > self.decode_set.allocator.capacity:
            raise ValueError(
                f"serving.num_pages={config.num_pages} cannot hold even one "
                f"max-size request ({self.pages_per_slot} pages of {page} "
                "tokens; page 0 is scratch)"
            )
        if self.disaggregated and self.prefill_pages > self.prefill_set.allocator.capacity:
            raise ValueError(
                f"serving.placement.prefill_num_pages={self.prefill_set.num_pages} "
                f"cannot hold one max-size prompt ({self.prefill_pages} pages)"
            )
        self.table = SlotTable(self.max_slots, self.pages_per_slot)
        # the decode rows of a chunk call that rides no decode step: all idle
        self._idle_table = SlotTable(self.max_slots, self.pages_per_slot)
        self.slots: List[_Slot] = [_Slot() for _ in range(self.max_slots)]
        self.queue: Deque[Request] = deque()
        self.completed: List[Request] = []
        self._sampling = float(config.temperature) > 0.0

        # -- ISSUE 11: SLO classes + per-tenant accounting -----------------
        self._slo = getattr(config, "slo", None)
        self._slo_enabled = bool(self._slo and self._slo.classes)
        # class -> [met, evaluated]; tenant -> accounting dict
        self._slo_counts: dict = {}
        self.tenants: dict = {}
        # per-ENGINE terminal-status counts: the tracer ledger and the
        # registry counter are both telemetry-plane-scoped, so two engines
        # sharing one plane would report each other's requests through
        # either — stats()["by_status"] must stay this engine's own
        self._status_counts: dict = {}
        self._slo_good_tokens = 0
        self._t_first_submit: Optional[float] = None
        self._backoff_pending = False  # a retry is (possibly) in its window

        # -- ISSUE 10: speculative decode / prefix cache / chunked prefill --
        self.spec = getattr(config, "speculative", None)
        self.spec_enabled = bool(self.spec and self.spec.enabled)
        self.spec_k = int(self.spec.k) if self.spec_enabled else 0
        self.spec_ngram = int(self.spec.ngram) if self.spec_enabled else 2
        if self.spec_enabled and self._sampling:
            raise ValueError(
                "serving.speculative requires temperature == 0 (greedy)"
            )
        # the prefix index lives beside the pool prefill WRITES: under
        # disaggregation that is the prefill placement's pool — the chunk
        # program attends shared pages there, and decode-side pages are
        # always private copies (COW never triggers on the decode pool)
        self.prefix_cache: Optional[PrefixCache] = (
            PrefixCache(self.prefill_set.allocator, page,
                        max_pages=int(pcfg.max_pages) if pcfg else 0)
            if self.prefix_enabled else None
        )
        # -- ISSUE 17: host-DRAM second tier for cold prefix pages ---------
        # The prefix index holds the only cross-request pages, so demotion
        # tiers on the PREFILL placement's pool (which IS the decode pool in
        # shared mode): evicted leaves spill to pinned host buffers instead
        # of dropping, and a later prompt re-hitting the chain restores them
        # through one compiled width-1 scatter (serving_kv_restore).
        tcfg = getattr(config, "tiering", None)
        self.tiering_enabled = bool(
            tcfg and tcfg.enabled and self.prefix_cache is not None
        )
        self.tiering: Optional[KVTieringEngine] = None
        if self.tiering_enabled:
            budget = int(tcfg.host_budget_pages) or self.prefill_set.allocator.capacity
            store = HostPageStore(
                budget,
                n_layer=self.decode_set.n_layer,
                n_kv_head=self.prefill_set.n_kv_head,  # GLOBAL layout: device_get unshards
                page_size=page,
                head_dim=self.prefill_set.head_dim,  # the CACHED head's, as the family gives both
                dtype=self.cache_dtype,
                quantized=self.quantized,
                crc=bool(tcfg.crc),
            )
            self.tiering = KVTieringEngine(
                store, self.prefill_set,
                policy=str(tcfg.policy),
                prefetch_depth=int(tcfg.prefetch_depth),
                clock=self.clock,
            )
            self.prefix_cache.demote_sink = self.tiering
            self.prefix_cache.victim_order = self.tiering.select_leaf
            # ISSUE 18 satellite: the tier needs device-index residency to
            # eagerly drop host entries whose parent chain link left BOTH
            # tiers (otherwise unreachable until host-LRU ages them out)
            self.tiering.device_resident = (
                self.prefix_cache._entries.__contains__
            )
        # -- telemetry (PR-1 registry when the engine carries one) ---------
        self.metrics: MetricsRegistry = (
            engine.telemetry.registry if getattr(engine, "telemetry", None)
            else MetricsRegistry()
        )
        m = self.metrics
        self._h_ttft = m.histogram(
            "serving_ttft_seconds", "submit → first token", buckets=LATENCY_BUCKETS
        )
        self._h_tpot = m.histogram(
            "serving_tpot_seconds",
            "inter-token emission latency, streaming-client view (one "
            "observation per gap; a speculative accepted run lands at one "
            "instant, so its intra-run gaps are 0)",
            buckets=LATENCY_BUCKETS,
        )
        self._h_qwait = m.histogram(
            "serving_queue_wait_seconds", "submit → slot admission",
            buckets=LATENCY_BUCKETS,
        )
        self._h_step = m.histogram(
            "serving_decode_step_seconds", "one batched decode step",
            buckets=LATENCY_BUCKETS,
        )
        self._c_requests = m.counter(
            "serving_requests_total", "requests by terminal status",
            labelnames=("status",),
        )
        self._c_tokens = m.counter("serving_tokens_total", "generated tokens")
        self._c_prefills = m.counter("serving_prefills_total", "prefill insertions")
        self._c_steps = m.counter("serving_decode_steps_total", "batched decode steps")
        # occupancy and mean context are their quotients with the step counter
        self._c_slot_steps = m.counter(
            "serving_decode_slot_steps_total", "active slots summed over decode steps"
        )
        self._c_attended = m.counter(
            "serving_attended_tokens_total",
            "context tokens attended, summed over active slots and decode steps",
        )
        # the paged attention kernels of both families walk the items a call
        # owns: a live slot's (query block, page block) pairs and one item an
        # idle slot. Their quotient is how much of the rectangular grid a
        # call's lengths and idle rows skip
        self._c_walk_steps = m.counter(
            "serving_attn_walk_steps_total",
            "grid steps of the paged attention kernels' calls, one paged "
            "layer's: the items the calls own, by the kernel's name in a "
            "trace (0 where the jnp fallback runs)",
            labelnames=("program",),
        )
        self._c_rect_steps = m.counter(
            "serving_attn_rect_steps_total",
            "what the same calls' rectangles take: slots x head or query "
            "blocks x the table's page blocks (serving_paged_grid_steps' terms)",
            labelnames=("program",),
        )
        self._attn_kernel = False   # set by _set_census_gauges: the programs call the paged attention kernels
        self._c_timeouts = m.counter(
            "serving_timeout_evictions_total",
            "requests evicted mid-flight by deadline",
        )
        self._g_queue = m.gauge("serving_queue_depth", "waiting requests")
        self._g_util = m.gauge(
            "serving_slot_utilization", "active slots / max_slots"
        )
        self._g_pages = m.gauge("serving_kv_pages_in_use", "allocated KV pages")
        self._g_occ = m.gauge(
            "serving_kv_page_occupancy", "allocated / allocatable KV pages"
        )
        self._g_quant = m.gauge(
            "serving_latency_quantile_seconds",
            "TTFT/TPOT/decode-step quantiles estimated from the histograms",
            labelnames=("metric", "q"),
        )
        self._c_stragglers = m.counter(
            "serving_stragglers_total",
            "requests flagged resident in a slot far beyond their decode budget",
        )
        self._c_drained = m.counter(
            "serving_drained_requests_total",
            "requests preempted by a graceful drain (queued + in-flight)",
        )
        self._c_retries = m.counter(
            "serving_retried_requests_total",
            "transient slot failures re-enqueued with backoff",
        )
        # -- ISSUE 10 instruments ------------------------------------------
        self._h_accept = m.histogram(
            "serving_spec_accept_length",
            "tokens emitted per slot per speculative verify step "
            "(1 = no draft accepted, k+1 = full accept)",
            buckets=tuple(float(i) for i in range(1, max(2, self.spec_k) + 2)),
        )
        self._c_spec_steps = m.counter(
            "serving_spec_steps_total", "batched speculative verify steps"
        )
        self._c_spec_drafted = m.counter(
            "serving_spec_drafted_total", "draft tokens proposed (host-side)"
        )
        self._c_spec_accepted = m.counter(
            "serving_spec_accepted_total", "draft tokens accepted by verify"
        )
        self._c_prefix_hits = m.counter(
            "serving_prefix_hits_total",
            "prefix-cache admission lookups by outcome",
            labelnames=("kind",),  # full | partial | miss
        )
        self._g_prefix_rate = m.gauge(
            "serving_prefix_hit_rate", "lookups that mapped >= 1 shared page"
        )
        self._c_pages_reused = m.counter(
            "serving_prefix_pages_reused_total",
            "KV pages mapped from the prefix index instead of prefilled",
        )
        self._g_pages_shared = m.gauge(
            "serving_kv_pages_shared", "in-use pages with refcount > 1"
        )
        self._c_cow = m.counter(
            "serving_kv_cow_forks_total",
            "shared pages forked copy-on-write at a full-prefix hit",
        )
        self._c_chunks = m.counter(
            "serving_chunk_prefills_total",
            "prompt chunks advanced (chunk program invocations: those that "
            "carried a decode step's rows and those that did not)",
        )
        self._c_rows_skipped = m.counter(
            "serve_prefill_rows_skipped_total",
            "prompt rows that left the stream behind the family's stop_after "
            "sub-block (a family whose last sub-blocks write no state runs "
            "them for the sampled row alone)",
        )
        self._c_chunks_rode = m.counter(
            "serving_chunks_rode_total",
            "prompt chunks that rode a decode step's dispatch: one call of "
            "the chunk program carried the chunk and the step's decode rows, "
            "so the step streamed the weights once (over "
            "serving_chunk_prefills_total: how often a chunk found a step)",
        )
        self._g_index_pages = m.gauge(
            "serving_prefix_index_pages", "pages held live by the prefix index"
        )
        # -- ISSUE 11: SLO / goodput / per-tenant instruments --------------
        self._g_slo = m.gauge(
            "serving_slo_attainment",
            "SLO-met / SLO-evaluated terminal requests per class",
            labelnames=("slo_class",),
        )
        self._g_goodput = m.gauge(
            "serving_goodput_tokens_per_sec",
            "tokens from SLO-met requests per second — over the trailing "
            "serving.slo.goodput_window_s window when set, else over the "
            "whole span since first submit (PR-11 behavior)",
        )
        # -- ISSUE 20: journal-visible SLO counters + windowed goodput -----
        # the monotone per-class counters the burn-rate engine windows over
        # (the _slo_counts dict is invisible to the metrics journal)
        self._c_slo_eval = m.counter(
            "serving_slo_evaluated_total",
            "SLO-evaluated terminal requests per class",
            labelnames=("slo_class",),
        )
        self._c_slo_met = m.counter(
            "serving_slo_met_total",
            "SLO-met terminal requests per class",
            labelnames=("slo_class",),
        )
        self._c_good_tokens = m.counter(
            "serving_slo_good_tokens_total",
            "tokens generated by SLO-met requests (windowed goodput source)",
        )
        self._goodput_window_s = float(
            getattr(self._slo, "goodput_window_s", 0.0) or 0.0
        )
        # ring-buffer fallback when no journal is attached: (t, tokens) of
        # each SLO-met completion, trimmed to the window on read
        self._good_events: Deque[tuple] = deque()
        self._c_tenant_requests = m.counter(
            "serving_tenant_requests_total",
            "terminal requests by tenant and status (tenant cardinality is "
            "the caller's responsibility)",
            labelnames=("tenant", "status"),
        )
        self._c_tenant_tokens = m.counter(
            "serving_tenant_tokens_total", "generated tokens by tenant",
            labelnames=("tenant",),
        )
        # -- is the pool met in the layout the paged programs work in ------
        self._g_relayout = m.gauge(
            "serving_pool_relayout_ops",
            "copy / slice / transpose instructions of a compiled serving "
            "program whose result is one or more whole layers of a KV pool "
            "(0 = the pool keeps one layout from the program's entry to its "
            "kernels and back)",
            labelnames=("program",),
        )
        self._g_weight_relayout = m.gauge(
            "serving_weight_relayout_bytes",
            "bytes of the results of the copy / slice / transpose instructions "
            "of a compiled serving program that are a whole parameter leaf of "
            "1 MB or more (per device; 0 = the program reads every weight "
            "where the placement laid it)",
            labelnames=("program",),
        )
        self._g_temp_bytes = m.gauge(
            "serving_program_temp_bytes",
            "HLO temp bytes of a compiled serving program (per device)",
            labelnames=("program",),
        )
        self._g_grid_steps = m.gauge(
            "serving_paged_grid_steps",
            "the bound on the grid steps of one call of each paged attention "
            "kernel of a compiled serving program (the chunk program has two): "
            "slots x head blocks x page blocks, what a call of full slots "
            "takes; a call walks the items it owns "
            "(serving_attn_walk_steps_total; 0 = the program calls no such kernel)",
            labelnames=("program",),
        )
        # -- two kinds of KV state, and the experts a chip's share holds ----
        self._g_kv_bytes = m.gauge(
            "serving_kv_bytes",
            "K+V bytes held on the device by class: paged (the pools under "
            "the block tables, the layers that read their whole context), "
            "latent (a latent family's one pool under the block tables, in "
            "paged's place) and window (the per-slot rings of the "
            "sliding-window layers, which do not grow with context)",
            labelnames=("class",),
        )
        self._g_kv_row_bytes = m.gauge(
            "serving_kv_row_bytes",
            "bytes one token costs one layer of the paged cache as it is "
            "stored (K and V of every kv-head, or a latent family's one row "
            "with its lane padding)",
        )
        self._g_ring_pages = m.gauge(
            "serving_window_pages_per_slot",
            "pages of one slot's ring in the window pools (0 = the model has "
            "no sliding-window layer)",
        )
        self._g_carry_bytes = m.gauge(
            "serving_attn_carry_bytes",
            "bytes of the rows the attentions of a family carry from call to "
            "call under their paged K and V (slots x attention sub-blocks x "
            "carry_width; 0 = the model's attentions carry none)",
        )
        self._g_lin_state_bytes = m.gauge(
            "serving_lin_state_bytes",
            "bytes of the matrix states the linear attentions of a family keep "
            "(slots x linear-attention sub-blocks x value heads x dk x dv, "
            "float32; a decode step reads and writes every live slot's; 0 = "
            "the model has no such sub-block)",
        )
        self._g_hc_row_bytes = m.gauge(
            "serving_hc_row_bytes",
            "bytes of one token's row of a multi-stream residual as the "
            "programs carry it (streams x hidden x the compute type's size: "
            "what one pass of the mixing moves a row; 0 = the model's "
            "residual is one stream)",
        )
        self._g_experts_held = m.gauge(
            "serving_moe_experts_held",
            "routed experts of a layer held on this chip (0 = no expert layer)",
        )
        self._c_moe_held = m.counter(
            "serving_moe_pairs_held_total",
            "token-expert pairs whose expert is held here (computed), over "
            "decode steps and chunk calls",
        )
        self._c_moe_routed = m.counter(
            "serving_moe_pairs_routed_total",
            "token-expert pairs routed (tokens x experts a token x expert "
            "layers), over decode steps and chunk calls",
        )
        self._c_moe_streamed = m.counter(
            "serving_moe_experts_streamed_total",
            "held experts whose matrices an expert layer's products read: "
            "those some token picked where the grouped kernel runs, every "
            "held expert in the masked form; over decode steps and chunk calls",
        )
        self._c_moe_zero = m.counter(
            "serving_moe_pairs_zero_total",
            "token-expert pairs that chose an identity (zero-compute) expert: "
            "no matrices, the token itself, on whichever chip serves it",
        )
        self._c_moe_group_rows = m.counter(
            "serving_moe_group_rows_total",
            "rows whose kept routing groups include one this chip holds "
            "experts of, summed over the expert layers, over decode steps "
            "and chunk calls (a router with a group limit; else 0)",
        )
        self._c_moe_rows = m.counter(
            "serving_moe_rows_total",
            "rows a group-limited router routed, summed over the expert "
            "layers: what serving_moe_group_rows_total is a share of",
        )
        # -- ISSUE 14: TP sharding + disaggregation instruments ------------
        self._g_tp_coll = m.gauge(
            "serving_tp_collective_bytes",
            "per-invocation all-reduce payload of a TP-sharded serving "
            "program (2 psums/layer over the [batch, width, n_embd] partial "
            "products; 0 = program not TP-sharded)",
            labelnames=("program",),
        )
        self._c_handoffs = m.counter(
            "serving_kv_handoffs_total",
            "prefill→decode KV page handoffs (disaggregated placements)",
        )
        self._c_handoff_bytes = m.counter(
            "serving_kv_handoff_bytes_total",
            "logical KV bytes moved prefill→decode by page handoffs",
        )
        self._h_handoff = m.histogram(
            "serving_kv_handoff_seconds",
            "one gather → device_put → scatter KV handoff, dispatch to "
            "installed",
            buckets=LATENCY_BUCKETS,
        )
        # anomaly watchdog (ISSUE 5): shared with the owning engine's
        # telemetry when present — straggler trips land in the same trace
        self.watchdog = (
            engine.telemetry.watchdog if getattr(engine, "telemetry", None)
            else None
        )
        self._ema_step_s = 0.0  # EWMA decode-step latency (straggler budget)
        self._t_read = float("-inf")  # when the last step was read (a step launched ahead is timed from there)
        self._step_count = 0
        # -- one step in flight while the host reads the step before it ----
        # the launched step the host has not read (``_resolve`` reads it)
        self._flight: Optional[_Flight] = None
        # calls of a compiled serving program so far: each takes the next
        # number (:meth:`_launch_attrs`) and its leaf span carries it
        self._launches = 0
        # whether the next step's rows are known without the tokens in flight:
        # a verify step's accepted count moves ``seq_lens``, and a handoff
        # between placements is polled between two steps
        self._ahead_ok = not (self.spec_enabled or self.disaggregated)
        self._chunk_sp = None   # this call's ds.serve.chunk leaf
        # (loads, tokens) of the prompts that finished prefilling, until a chunk leaf reports them
        self._moe_done: list = []
        self._c_ahead = m.counter(
            "serving_steps_ahead_total",
            "decode dispatches launched with the step before still in flight "
            "(over serving_decode_steps_total: how often the host's turn lay "
            "under a program)",
        )
        self._c_dropped = m.counter(
            "serving_rows_dropped_total",
            "decode rows computed and dropped: launched ahead for a slot that "
            "the step before ended (EOS, a stall, a deadline, a drain)",
        )

        # -- ISSUE 16: page-lifetime / session-heat tracing ----------------
        # explicit tracer wins, else the engine's telemetry plane provides
        # one (telemetry.kv_heat), else the plane is off — every hook is
        # one None check
        self._heat = None            # the KVHeatTracer
        self._heat_decode = None     # decode/shared pool ledger
        self._heat_prefill = None    # prefill pool ledger (aliases in shared)
        ht = (
            heat_tracer if heat_tracer is not None
            else getattr(getattr(engine, "telemetry", None), "kv_heat_tracer", None)
        )
        if ht is not None:
            self.attach_heat(ht)

        # -- ISSUE 20: metrics time-series journal -------------------------
        # explicit journal wins, else the engine's telemetry plane provides
        # one (telemetry.timeseries); the step path pays one None check
        self._journal = None
        mj = (
            journal if journal is not None
            else getattr(getattr(engine, "telemetry", None), "metrics_journal", None)
        )
        if mj is not None:
            self.attach_journal(mj)

        self._prefill_exec = None
        self._decode_exec = None
        self._moe_kernel = False  # whether the compiled programs hold the grouped expert kernel
        self._verify_exec = None
        self._chunk_exec = None
        self._gather_exec = None
        self._scatter_exec = None
        self._restore_exec = None
        # ISSUE 18: full-row migration transport (compiled on first use —
        # only fleets ever migrate, so solo engines never pay the compile)
        self._migrate_gather_exec = None
        self._migrate_scatter_exec = None
        self.executables: List[Any] = []
        # program name -> {"exe", "pset", "kind"[, "fn"]} (built by _ensure_compiled;
        # verify() derives per-program local shapes and aliasing from it)
        self._program_info: dict = {}
        log_dist(
            f"ServingEngine: slots={self.max_slots} page={page} "
            f"pages={config.num_pages} (pool "
            f"{self.decode_set.cache_bytes()['pages'] / 1e6:.1f} MB"
            + (
                f" + {scales_bytes(self.decode_set.n_layer, int(config.num_pages), self.decode_set.n_kv_head) / 1e6:.2f} MB scales"
                if self.quantized else ""
            )
            + (
                f" + {self.decode_set.cache_bytes()['window'] / 1e6:.1f} MB in "
                f"{self.ring_pages}-page window rings"
                if self.ring_pages else ""
            )
            + f", [{self.decode_set.local_pool_dims()}] a device"
            + f") prefill_width={self.prefill_width} dtype={np.dtype(self.cache_dtype).name} "
            f"spec_k={self.spec_k if self.spec_enabled else 0} "
            f"prefix_cache={self.prefix_enabled} chunk={self.chunk_width} "
            f"tp={self.tp}"
            + (
                f" disaggregated(prefill={self.prefill_placement!r}, "
                f"decode={self.decode_placement!r}, "
                f"prefill_pages={self.prefill_set.num_pages})"
                if self.disaggregated else ""
            )
        )

    # -- back-compat pool/allocator views (the decode placement owns the
    # main pool; pre-ISSUE-14 callers and tests read these directly) -------
    @property
    def k_pool(self):
        return self.decode_set.cache.k

    @property
    def v_pool(self):
        return self.decode_set.cache.v

    @property
    def kv_scales(self):
        return self.decode_set.cache.scales

    @property
    def allocator(self):
        return self.decode_set.allocator

    def _refuse_unhandled(self, mechanism: str) -> None:
        """Raise where ``mechanism`` does not handle a kind of per-slot state
        this model's family holds (``kv_cache.refusals``)."""
        why = refusals(self.family, mechanism, type(self.model_config).__name__)
        if why:
            raise ValueError(f"{mechanism} is not available for a model with " + "; and with ".join(why))

    @property
    def expected_executables(self) -> int:
        """The static-shapes contract (Engine A ``exact`` budget): ONE
        decode-shaped program (the speculative verify step REPLACES the plain
        decode step when enabled — never both), the whole-prompt prefill
        program unless the engine chunks its cold prompts
        (``serving.prefill_chunk_tokens``: the chunk program then prefills
        every prompt), the chunk-prefill program when chunking or the prefix
        cache needs it, and — under disaggregated placements (ISSUE 14) — the
        KV-handoff gather + scatter pair; the host tier (ISSUE 17) adds the
        width-1 ``serving_kv_restore`` scatter."""
        return (
            1 + (0 if self._chunk_cold else 1) + (1 if self.chunk_width > 0 else 0)
            + (2 if self.disaggregated else 0)
            + (1 if self.tiering_enabled else 0)
        )

    # ------------------------------------------------------------------
    # ISSUE 16: page-lifetime / session-heat tracing
    # ------------------------------------------------------------------
    def attach_heat(self, tracer) -> None:
        """Attach a :class:`~deepspeed_tpu.telemetry.kv_heat.KVHeatTracer`:
        one ledger per placement pool, seeded from the allocator's CURRENT
        refcount table (attaching mid-run — e.g. after warm-up — must
        reconcile from the first event), hooks installed on
        the allocator(s) and the prefix index, derived gauges bound to this
        engine's registry. Idempotent for the same tracer."""
        if tracer is self._heat:
            return
        tracer.bind_registry(self.metrics)
        ds = self.decode_set
        page_b = pool_bytes(
            ds.n_layer, 1, ds.n_kv_head, self.page_size, ds.head_dim,
            np.dtype(self.cache_dtype).itemsize, pools=ds.kv_pools,
        )
        now = self.clock()
        alloc = self.decode_set.allocator
        led = tracer.pool(
            self.decode_placement.name, alloc.capacity,
            page_size=self.page_size, page_bytes=page_b, clock=self.clock,
        )
        prefix_held = (
            [int(p) for p in self.prefix_cache.held_pages]
            if self.prefix_cache is not None and not self.disaggregated else []
        )
        led.seed(alloc.refcounts(), prefix_held, now)
        alloc.heat = led
        self._heat_decode = led
        if self.disaggregated:
            palloc = self.prefill_set.allocator
            pled = tracer.pool(
                self.prefill_placement.name, palloc.capacity,
                page_size=self.page_size, page_bytes=page_b, clock=self.clock,
            )
            pled.seed(
                palloc.refcounts(),
                [int(p) for p in self.prefix_cache.held_pages]
                if self.prefix_cache is not None else [],
                now,
            )
            palloc.heat = pled
            self._heat_prefill = pled
        else:
            self._heat_prefill = led
        if self.prefix_cache is not None:
            # the index lives on the prefill placement's pool
            self.prefix_cache.heat = self._heat_prefill
        if self.tiering is not None:
            # the tier spills/restores prefill-pool pages: its D/U/V events
            # and policy victim keys read the same ledger
            self.tiering.ledger = self._heat_prefill
        self._heat = tracer

    def detach_heat(self) -> None:
        """Uninstall every heat hook (the tracer and its records survive —
        this only stops further recording on this engine)."""
        self.decode_set.allocator.heat = None
        self.prefill_set.allocator.heat = None
        if self.prefix_cache is not None:
            self.prefix_cache.heat = None
        if self.tiering is not None:
            self.tiering.ledger = None
        self._heat = None
        self._heat_decode = None
        self._heat_prefill = None

    # ------------------------------------------------------------------
    # ISSUE 20: metrics time-series journal
    # ------------------------------------------------------------------
    def attach_journal(self, journal) -> None:
        """Attach a :class:`~deepspeed_tpu.telemetry.timeseries.MetricsJournal`:
        bind it to this engine's registry and injectable clock (replayed
        timestamps stay virtual) and snapshot on the step cadence.
        Idempotent for the same journal."""
        if journal is self._journal:
            return
        journal.bind(self.metrics, clock=self.clock)
        self._journal = journal

    def detach_journal(self) -> None:
        """Stop snapshotting (the journal and its file survive)."""
        self._journal = None

    def _goodput_now(self, now: float) -> tuple:
        """(windowed, cumulative) goodput in tokens/s. Cumulative is the
        PR-11 whole-span number; windowed divides the trailing
        ``goodput_window_s`` of SLO-met tokens — journal ``increase()``
        when attached, the ring-buffer fallback when not — by the
        *effective* window (capped at the span, so a young engine is not
        under-reported). With no window configured both are the span
        number."""
        if self._t_first_submit is None:
            return 0.0, 0.0
        span = max(now - self._t_first_submit, 1e-12)
        cumulative = self._slo_good_tokens / span
        w = self._goodput_window_s
        if w <= 0.0:
            return cumulative, cumulative
        if self._journal is not None and self._journal.last_t is not None:
            good = self._journal.increase(
                "serving_slo_good_tokens_total", now - w, now
            )
            # snapshots trail the live counter by up to interval_s: fold
            # in the not-yet-journaled tail (those completions are by
            # definition the freshest, so they belong in any window)
            live = self._c_good_tokens.value()
            latest = self._journal.latest("serving_slo_good_tokens_total")
            good += live - (latest if latest is not None else 0.0)
        else:
            ring = self._good_events
            while ring and ring[0][0] < now - w:
                ring.popleft()
            good = float(sum(tok for _t, tok in ring))
        eff = min(w, span)
        return good / max(eff, 1e-12), cumulative

    def draft_index_bytes(self) -> int:
        """Host bytes held by live slots' incremental n-gram drafter state
        (ISSUE 16 satellite: the host-metadata budget) — the context list
        plus the n-gram → position index built by :meth:`_draft`."""
        import sys as _sys

        total = 0
        for slot in self.slots:
            req = slot.request
            st = getattr(req, "_draft_state", None) if req is not None else None
            if not st:
                continue
            ctx, index, _watermark = st
            total += _sys.getsizeof(ctx) + 28 * len(ctx)
            total += _sys.getsizeof(index)
            # per entry: the n-token tuple key + one int position value
            total += len(index) * (28 * (self.spec_ngram + 1) + 56)
        return total

    def host_metadata_breakdown(self) -> dict:
        """The host-side (RSS, not HBM) metadata ledger: prefix-index
        structures, per-request drafter indexes, heat-ledger mirrors —
        budgeted next to the device pools in :meth:`memory_report`."""
        prefix_b = (
            self.prefix_cache.host_metadata_bytes()
            if self.prefix_cache is not None else 0
        )
        draft_b = self.draft_index_bytes()
        heat_b = self._heat.ledger_bytes() if self._heat is not None else 0
        tier_b = (
            self.tiering.store.host_bytes() if self.tiering is not None else 0
        )
        return {
            "prefix_index_bytes": prefix_b,
            "draft_index_bytes": draft_b,
            "heat_ledger_bytes": heat_b,
            "kv_host_tier_bytes": tier_b,
            "total_bytes": prefix_b + draft_b + heat_b + tier_b,
        }

    # ------------------------------------------------------------------
    # compilation: a fixed feature-derived program set, ahead-of-time
    # ------------------------------------------------------------------
    def _ensure_compiled(self) -> None:
        if self._program_info:
            return
        with spans.phase("ds.init.programs", what="serving") as ph:
            self._compile_programs()
            ph.set(**self._set_census_gauges())

    def _compile_programs(self) -> None:
        sc = self.config
        temp, tk, top_p = float(sc.temperature), int(sc.top_k), float(sc.top_p)
        S = jax.ShapeDtypeStruct
        i32, u32 = jnp.int32, jnp.uint32

        # Every program is ``fn(params, cache, *host operands)`` and gives
        # the cache back first (``kv_cache.Cache``: one donated value, which
        # kinds of state it holds is the family's affair and the programs').
        # Each program is built FOR a placement (ISSUE 14): it traces with
        # that placement's LOCAL model config (n_embd/n_head divided by tp)
        # and psums its row-parallel partials over the tp axis.
        # The prefill and chunk programs of a family that keeps state a slot
        # take the slot as their last host operand (:meth:`_slot_operand`).
        # The chunk program is the MIXED step: one prefilling slot's chunk
        # and every slot's decode row through the weights once. Its host
        # operands are the decode step's six, then the chunk's.
        ring = self.ring_pages

        def make_fns(cfg, tp_axis):
            def prefill_fn(params, cache, ids, plen, page_ids, key, slot=None):
                return smodel.paged_prefill(
                    cfg, params, ids, plen, cache, page_ids, key,
                    temperature=temp, top_k=tk, top_p=top_p,
                    tp_axis=tp_axis, slot=slot, ring=ring,
                )

            def decode_fn(params, cache, tokens, seq_lens, bt, keys, prev, src):
                cache, nxt, *counts = smodel.paged_decode_step(
                    cfg, params, _tokens_of(tokens, prev, src), seq_lens, cache, bt, keys,
                    temperature=temp, top_k=tk, top_p=top_p, tp_axis=tp_axis, ring=ring,
                )
                # the chunk program's shape: either's tokens are the next
                # call's ``prev``, whichever program that is
                return (cache, jnp.pad(nxt, (0, 1)), *counts)

            def verify_fn(params, cache, tokens, seq_lens, bt):
                return smodel.paged_verify_step(
                    cfg, params, tokens, seq_lens, cache, bt, tp_axis=tp_axis, ring=ring,
                )

            # named for what a trace's readers find it by: a chunk program
            # and the program a decode dispatch launches
            def chunk_decode_fn(params, cache, tokens, seq_lens, bt, keys, prev, src,
                                ids, start, plen, page_ids, bt_row, key, slot=None):
                return smodel.paged_mixed_step(
                    cfg, params, _tokens_of(tokens, prev, src), seq_lens, ids, start, plen,
                    cache, bt, page_ids, bt_row, keys, key,
                    temperature=temp, top_k=tk, top_p=top_p,
                    tp_axis=tp_axis, slot=slot, ring=ring,
                )

            return prefill_fn, decode_fn, verify_fn, chunk_decode_fn

        # AOT: lower + compile ONCE with the config-derived static shapes;
        # the compiled objects reject any other shape, enforcing the
        # executable-count contract structurally (pools — and the scales
        # pool under int8 — are donated: the cache never exists twice,
        # per device). At tp > 1 the function body runs under shard_map:
        # pools/params enter with their placement specs, host operands
        # replicate, and donation threads through the outer jit so XLA
        # aliases the per-device pool shards.
        # what a program returns after the pools: the token(s) and, for a
        # family with expert layers, the tokens each held expert got
        n_results = 2 if self.family.sparse_layers else 1
        slot_sds = (S((), i32),) if self._slot_operand(0) else ()
        # the decode rows' operands: tokens, lengths, tables, keys from the
        # host, then the token output of the step before (on the device while
        # that step is in flight) and each row's place in it
        rows_sds = (
            S((self.max_slots,), i32), S((self.max_slots,), i32),
            S((self.max_slots, self.pages_per_slot), i32),
            S((self.max_slots, 2), u32),
            S((self.max_slots + 1,), i32), S((self.max_slots,), i32),
        )

        def compile_for(pset, fn, host_sds):
            rep = pset.placement.rep_spec()
            return pset.aot(
                fn, host_sds, (rep,) * len(host_sds), (rep,) * n_results,
                with_params=True,
            )

        d_cfg = self.decode_placement.local_model_config(self.model_config)
        p_cfg = self.prefill_placement.local_model_config(self.model_config)
        p_fns = make_fns(p_cfg, self.prefill_placement.tp_axis)
        d_fns = (
            p_fns if self.prefill_placement is self.decode_placement
            else make_fns(d_cfg, self.decode_placement.tp_axis)
        )
        sfx = "_int8" if self.quantized else ""
        info: dict = {}

        self.executables = []
        if not self._chunk_cold:
            # the whole-prompt program, where cold prompts have no other
            self._prefill_exec = compile_for(self.prefill_set, p_fns[0], (
                S((1, self.prefill_width), i32), S((), i32),
                S((self.prefill_pages,), i32), S((2,), u32), *slot_sds,
            ))
            info[f"serving_prefill{sfx}{self.prefill_placement.suffix()}"] = {
                "exe": self._prefill_exec, "pset": self.prefill_set,
                "kind": "prefill", "fn": p_fns[0].__name__,
            }
            self.executables.append(self._prefill_exec)
        # the verify step REPLACES the decode step when speculation is on:
        # exactly one decode-shaped program ever advances the batch
        if self.spec_enabled:
            self._verify_exec = compile_for(self.decode_set, d_fns[2], (
                S((self.max_slots, self.spec_k + 1), i32),
                S((self.max_slots,), i32),
                S((self.max_slots, self.pages_per_slot), i32),
            ))
            info[f"serving_verify{sfx}{self.decode_placement.suffix()}"] = {
                "exe": self._verify_exec, "pset": self.decode_set,
                "kind": "verify", "fn": d_fns[2].__name__,
            }
            self.executables.append(self._verify_exec)
        else:
            self._decode_exec = compile_for(self.decode_set, d_fns[1], rows_sds)
            info[f"serving_decode{sfx}{self.decode_placement.suffix()}"] = {
                "exe": self._decode_exec, "pset": self.decode_set,
                "kind": "decode", "fn": d_fns[1].__name__,
            }
            self.executables.append(self._decode_exec)
        if self.chunk_width > 0:
            self._chunk_exec = compile_for(self.prefill_set, p_fns[3], rows_sds + (
                S((1, self.chunk_width), i32), S((), i32), S((), i32),
                S((self.chunk_width // self.page_size,), i32),
                S((1, self.pages_per_slot), i32), S((2,), u32), *slot_sds,
            ))
            info[f"serving_chunk_prefill{sfx}{self.prefill_placement.suffix()}"] = {
                "exe": self._chunk_exec, "pset": self.prefill_set,
                "kind": "chunk", "fn": p_fns[3].__name__,
            }
            self.executables.append(self._chunk_exec)

        if self.disaggregated:
            self._compile_handoff(info, sfx, S((self.prefill_pages,), i32))

        if self.tiering_enabled:
            self._compile_restore(info, sfx, S((1,), i32))

        self._program_info = info
        self._register_parts()
        self._set_collective_gauges()

    def _register_parts(self) -> None:
        """Each compiled program for ``telemetry.parts``, under the name a
        trace's line of programs shows (``jit_decode_fn``, ...). A callable,
        called when a reader asks: no text is rendered here. (The engine is
        held weakly: the registry must not keep its pools on the device.)"""
        me = weakref.ref(self)

        def text_of(key):
            def text():
                eng = me()
                rec = None if eng is None else eng._program_info.get(key)
                return None if rec is None else rec["exe"].as_text()
            return text

        for key, rec in self._program_info.items():
            if "fn" in rec:   # the programs that run the model: the page movers hold no part
                parts.register("jit_" + rec["fn"], text_of(key))

    def _compile_handoff(self, info: dict, sfx: str, src_sds) -> None:
        """The disaggregated KV handoff pair (ISSUE 14): ``gather`` packs a
        finished prompt's pages out of the prefill pool ([L, n, KV, page, D]
        per pool, scales ride along under int8); the packed buffers cross
        placements via ``jax.device_put``; ``scatter`` writes them into the
        decode pool's pages (the cache donated — the decode cache never exists
        twice). Page-id lists are scratch-padded to the static
        ``prefill_pages`` width, so the pair compiles once; duplicate pad
        indices all target scratch page 0, which no active slot reads."""
        pp, dp = self.prefill_placement, self.decode_placement
        pset, dset = self.prefill_set, self.decode_set

        # gather: the prefill cache is READ, not donated — the prompt pages
        # stay live for the prefix index until the host frees them
        self._gather_exec = pset.aot(
            gather_fn, (src_sds,), (pp.rep_spec(),), pset.packed_specs(),
            returns_cache=False, donate=False,
        )
        info[f"serving_kv_gather{sfx}{pp.suffix()}"] = {
            "exe": self._gather_exec, "pset": pset, "kind": "gather",
        }
        self.executables.append(self._gather_exec)

        # scatter: the decode cache donated
        self._scatter_exec = dset.aot(
            scatter_fn, dset.packed_sds(self.prefill_pages) + (src_sds,),
            dset.packed_specs() + (dp.rep_spec(),),
        )
        info[f"serving_kv_scatter{sfx}{dp.suffix()}"] = {
            "exe": self._scatter_exec, "pset": dset, "kind": "scatter",
        }
        self.executables.append(self._scatter_exec)

    def _compile_restore(self, info: dict, sfx: str, dst_sds) -> None:
        """The host-tier restore program (ISSUE 17): a width-1 scatter into
        the PREFILL placement's pool (where the prefix index lives) —
        ``(cache, packed_k, packed_v[, packed_s], dst) -> cache`` with
        the cache donated, so a restore rewrites exactly one page column in
        place. The packed operands arrive as host numpy straight out of the
        :class:`HostPageStore` buffers (the ``device_put`` leg of the
        async_swapper pattern rides the program's own operand transfer)."""
        pp, pset = self.prefill_placement, self.prefill_set
        self._restore_exec = pset.aot(
            restore_fn, pset.packed_sds(1) + (dst_sds,),
            pset.packed_specs() + (pp.rep_spec(),),
        )
        info[f"serving_kv_restore{sfx}{pp.suffix()}"] = {
            "exe": self._restore_exec, "pset": pset, "kind": "restore",
        }
        self.executables.append(self._restore_exec)
        self.tiering.bind_restore_exec(self._restore_exec)

    def _set_census_gauges(self) -> dict:
        """Per compiled program: how many pool-layer-sized copies, slices
        and transposes its optimised HLO holds, its temp bytes, the whole
        weight leaves it copies for another order (``weight_relayout``, keyed
        ``<program>=<instructions>/<bytes>``; the gauge holds the bytes), and
        the grid steps of one call of each of its paged attention kernels, as
        gauges and (returned) as the attrs of the ``ds.init.programs`` phase:
        ``relayout_ops`` / ``temp_bytes`` / ``grid_steps`` keyed
        ``<program>=<n>``."""
        from ..ops.attention import (
            latent_attention_grid_steps,
            paged_attention_grid_steps,
        )

        # (slots, query tokens a slot) of a program's attention kernel calls;
        # the verify step attends as k+1 single-token calls, the chunk
        # program calls both kernels a layer
        rows = (self.max_slots, None)
        shapes = {
            "decode": (rows,), "verify": (rows,),
            "chunk": ((1, self.chunk_width), rows),
        }
        relayout, temp, steps, w_relayout = {}, {}, {}, {}
        for name, rec in self._program_info.items():
            pset = rec["pset"]
            relayout[name], temp[name], w_ops, w_bytes = pset.program_census(name, rec["exe"])
            w_relayout[name] = f"{w_ops}/{w_bytes}"
            self._g_weight_relayout.set(w_bytes, program=name)
            steps[name] = 0
            for B, T in shapes.get(rec["kind"], ()):
                steps[name] += latent_attention_grid_steps(
                    self.model_config.attn_impl, B, self.family.n_head,
                    pset.page_size, pset.head_dim, pset.cache.k.dtype.itemsize,
                    self.pages_per_slot, T or 1,
                ) if pset.cache.latent else paged_attention_grid_steps(
                    self.model_config.attn_impl, B, pset.local_kv_heads(),
                    pset.page_size, pset.head_dim, pset.cache.k.dtype.itemsize,
                    self.pages_per_slot, T,
                    rep=self.family.n_head // self.family.n_kv_head,
                )
            self._g_relayout.set(relayout[name], program=name)
            self._g_temp_bytes.set(temp[name], program=name)
            self._g_grid_steps.set(steps[name], program=name)
        self._attn_kernel = any(steps.values())
        ds = self.decode_set
        by = ds.cache_bytes()
        kv_bytes = {
            "latent" if ds.cache.latent else "paged": by["pages"],
            "window": by["window"],
            **({"state": by["state"]} if ds.cache.rec is not None else {}),
            **({"carry": by["carry"]} if ds.cache.carry is not None else {}),
        }
        for cls, n in kv_bytes.items():
            self._g_kv_bytes.set(n, **{"class": cls})
        # what one token costs one layer of the cache, as it is stored
        row_bytes = (
            ds.kv_pools * ds.n_kv_head * ds.head_dim
            * jnp.dtype(ds.cache.k.dtype).itemsize
        )
        self._g_kv_row_bytes.set(row_bytes)
        self._g_ring_pages.set(self.ring_pages)
        self._g_carry_bytes.set(by["carry"])
        self._g_lin_state_bytes.set(by["lin_state"])
        self._g_experts_held.set(self.family.experts_held)
        hc_row_bytes = getattr(self.family, "stream_row_width", 0) * np.dtype(self.engine.dtype).itemsize
        self._g_hc_row_bytes.set(hc_row_bytes)
        # the expert layers' form, as compiled: a program names the kernel or
        # not (read off the step program, which every engine has: the form
        # hangs on the experts' shapes, which are every program's)
        step_exec = self._verify_exec if self.spec_enabled else self._decode_exec
        self._moe_kernel = bool(self.family.sparse_layers) and (
            grouped_experts.KERNEL_NAME in step_exec.as_text()
        )
        attrs = {
            key: " ".join(f"{k}={v}" for k, v in got.items())
            for key, got in (("relayout_ops", relayout), ("weight_relayout", w_relayout),
                             ("temp_bytes", temp), ("grid_steps", steps), ("kv_bytes", kv_bytes))
        }
        attrs.update(window_pages_per_slot=self.ring_pages,
                     moe_experts_held=self.family.experts_held,
                     kv_row_bytes=row_bytes)
        if ds.cache.carry is not None:
            attrs["carry_rows"] = by["carry"]
        if by["lin_state"]:
            attrs["lin_state_bytes"] = by["lin_state"]
        if hc_row_bytes:
            attrs["hc_row_bytes"] = hc_row_bytes
        return attrs

    def _moe_attrs(self, counts: np.ndarray, n_tokens: int) -> dict:
        """Span attributes from the expert layers' ``[calls x sparse layers,
        experts_held]`` token counts of one decode step or of a prompt's
        chunk calls, ``n_tokens`` real tokens in all; the registry's pair
        counters move with them. A family whose router has identity columns
        reports one entry more a layer, the pairs that chose one: a third kind
        beside the held and the routed."""
        fam = self.family
        attrs = {}
        if getattr(fam, "zero_experts", 0):
            counts, zero = counts[:, :-1], int(counts[:, -1].sum())
            self._c_moe_zero.inc(zero)
            attrs["moe_pairs_zero"] = zero
        if getattr(fam, "expert_groups", 1) > 1:
            # a group-limited router's last entry: the rows that kept a group held here
            counts, kept = counts[:, :-1], int(counts[:, -1].sum())
            rows = int(n_tokens) * len(fam.sparse_layers)
            self._c_moe_group_rows.inc(kept)
            self._c_moe_rows.inc(rows)
            attrs.update(group_rows=kept, rows=rows)
        held = int(counts.sum())
        routed = int(n_tokens) * fam.experts_per_token * len(fam.sparse_layers)
        streamed = experts_streamed(counts, self._moe_kernel)
        self._c_moe_held.inc(held)
        self._c_moe_routed.inc(routed)
        self._c_moe_streamed.inc(streamed)
        return {
            "moe_pairs_held": held, "moe_pairs_routed": routed,
            "moe_load_max": int(counts.max()),
            "moe_experts_hit": int((counts > 0).sum()),
            "moe_experts_streamed": streamed, **attrs,
        }

    def _set_collective_gauges(self) -> None:
        """Static per-invocation all-reduce payload of each TP program: the
        head-parallel design psums the [B, width, n_embd] partial product
        twice per layer (attention out-proj + MLP down-proj), identically
        in every program — the analytical truth Engine D's order check
        verifies structurally."""
        mc = self.model_config
        it = np.dtype(self.engine.dtype).itemsize
        widths = {
            "prefill": (1, self.prefill_width),
            "decode": (self.max_slots, 1),
            "verify": (self.max_slots, self.spec_k + 1),
            "chunk": (1, self.chunk_width + self.max_slots),
        }
        for name, rec in self._program_info.items():
            bs = widths.get(rec["kind"])
            tp_n = rec["pset"].placement.tp
            nbytes = (
                2 * mc.n_layer * bs[0] * bs[1] * mc.n_embd * it
                if bs is not None and tp_n > 1 else 0
            )
            self._g_tp_coll.set(nbytes, program=name)

    # ------------------------------------------------------------------
    # admission control
    # ------------------------------------------------------------------
    def submit(
        self,
        prompt,
        max_new_tokens: Optional[int] = None,
        seed: int = 0,
        eos_token_id: Optional[int] = None,
        deadline_s: Optional[float] = None,
        tenant: str = "default",
        slo_class: Optional[str] = None,
    ) -> Request:
        """Enqueue one request. Backpressure REJECTS at the door (queue depth,
        or a prompt that can never fit); an over-long ``max_new_tokens`` is
        clamped and the response marked TRUNCATED at finish. ``tenant`` is a
        free-form accounting dimension; ``slo_class`` names a
        ``serving.slo.classes`` entry (unknown/None → the configured
        default — SLO accounting is observability, never admission
        control)."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        mnt = int(self.config.max_new_tokens if max_new_tokens is None else max_new_tokens)
        req = Request(
            prompt=prompt, max_new_tokens=mnt, seed=int(seed),
            eos_token_id=eos_token_id, deadline_s=deadline_s,
            tenant=str(tenant),
            slo_class=(
                self._slo.resolve_class(slo_class) if self._slo_enabled
                else (slo_class or "")
            ),
        )
        req.t_submit = self.clock()
        if self._t_first_submit is None:
            self._t_first_submit = req.t_submit
        if self.tracer is not None:
            self.tracer.submit(req, req.t_submit)
        plen = req.prompt_len
        if plen < 1 or plen > int(self.config.max_prompt_len):
            return self._reject(
                req, f"prompt length {plen} outside [1, {self.config.max_prompt_len}]"
            )
        if mnt < 1:
            return self._reject(req, f"max_new_tokens {mnt} < 1")
        cap = min(int(self.config.max_new_tokens), self.max_total_len - plen)
        if cap < 1:
            return self._reject(req, f"prompt length {plen} leaves no decode budget")
        if mnt > cap:
            # degrade, don't wedge: the response will be truncated at cap
            req.requested_new_tokens = mnt
            req.max_new_tokens = cap
            req.detail = f"max_new_tokens clamped {mnt} -> {cap}"
        if self._draining:
            return self._reject(req, "engine draining (admission stopped)",
                                cause="draining")
        if len(self.queue) >= int(self.config.max_queue_depth):
            return self._reject(req, f"queue full ({self.config.max_queue_depth})",
                                cause="queue_depth")
        self.queue.append(req)
        self._g_queue.set(len(self.queue))
        return req

    def _reject(self, req: Request, why: str, cause: str = "invalid") -> Request:
        req.status = RequestStatus.REJECTED
        req.detail = why
        req.t_finish = self.clock()
        self._c_requests.inc(status=RequestStatus.REJECTED)
        if self.tracer is not None:
            self.tracer.event(req, "reject", req.t_finish, cause=cause)
        self._req_terminal(req, req.t_finish)
        self.completed.append(req)
        return req

    def _deadline(self, req: Request) -> Optional[float]:
        d = req.deadline_s
        if d is None:
            d = float(self.config.default_deadline_s) or None
        return None if d is None else req.t_submit + d

    # ------------------------------------------------------------------
    # the scheduler loop
    # ------------------------------------------------------------------
    def step(self) -> int:
        """One scheduler iteration: evict deadline-passed work, admit queued
        requests into free slots (prefill insertion), advance every active
        slot one token. Returns the number of active slots after the step."""
        self._ensure_compiled()
        with spans.span(
            "ds.serve.step", step=self._step_count, queue=len(self.queue)
        ) as sp:
            n_active = self._step()
            sp.set(active=n_active)
        return n_active

    def _step(self) -> int:
        """The body of :meth:`step`, tiled by leaf spans (``ds.serve.*``; the
        names and attributes are listed in PERF.md section 3): no statement
        that can take more than a few microseconds is outside one, and every
        place that blocks on the device has a leaf whose name ends in
        ``.wait``.

        One step program is in flight while the host reads the step before
        it: a call LAUNCHES the next step (admission, chunks, the decode
        dispatch, all from the dispatched side of the tables) and then reads
        the one the call before launched, emits its tokens and keeps house
        while the device runs. A server whose next rows depend on the tokens
        it has not read (speculative verification: the accepted count moves
        ``seq_lens``; a disaggregated handoff) launches nothing ahead and
        reads its step in the call that launched it, through the same loop."""
        nxt = self._launch()
        if self._flight is None:
            self._flight, nxt = nxt, None
            if self._flight is not None and self._ahead_ok:
                # nothing was in flight (an empty server's first rows): fill
                # the pipe, so that this call too reads a step's tokens
                rows = self._rows_due()
                if rows:
                    nxt = self._dispatch(rows)
        flight, self._flight = self._flight, nxt
        if flight is not None:
            self._resolve(flight)
        elif self._moe_done:
            self._report_chunk_loads()

        with spans.span("ds.serve.housekeep") as hk:
            # which of its four chores a long one ran (the span's attrs, set at exit)
            scanned = pumped = 0
            # straggler detection (ISSUE 5 watchdog): a request resident in a
            # slot far beyond its expected decode budget (straggler_factor x
            # max_new_tokens x EMA step time) is flagged once — a wedged or
            # pathologically slow request surfaces instead of silently holding
            # a slot. Slots advance in lockstep, so residence time is the only
            # per-request axis that can straggle.
            if self.watchdog is not None and self._ema_step_s > 0.0:
                factor = float(getattr(self.watchdog.config, "straggler_factor", 3.0))
                now = self.clock()
                for slot in self.slots:
                    req = slot.request
                    if req is None or req.t_first_token is None:
                        continue
                    scanned += 1
                    budget = factor * max(1, req.max_new_tokens) * self._ema_step_s
                    elapsed = now - req.t_first_token
                    if elapsed > budget and self.watchdog.observe_straggler(
                        self._step_count, req.id,
                        f"slot residence {elapsed:.3f}s > {budget:.3f}s "
                        f"({len(req.tokens)}/{req.max_new_tokens} tokens)",
                    ):
                        self._c_stragglers.inc()

            n_active = sum(1 for s in self.slots if s.request is not None)
            self._g_queue.set(len(self.queue))
            self._g_util.set(n_active / self.max_slots)
            self._g_pages.set(self.allocator.pages_in_use)
            self._g_occ.set(self.allocator.pages_in_use / self.allocator.capacity)
            self._g_pages_shared.set(self.allocator.pages_shared)
            if self.prefix_cache is not None:
                self._g_index_pages.set(len(self.prefix_cache))
            if self.tiering is not None:
                pumped = self._tier_pump()
            refresh = bool(self._step_count and self._step_count % 32 == 0)
            if refresh:
                self.stats()  # refresh the quantile gauges for textfile scrapes
            journaled = self._journal is not None and self._journal.maybe_snapshot(self.clock())
            hk.set(stats=int(refresh), journal=int(journaled), pump=pumped, stragglers=scanned)
        return n_active

    def settle(self) -> None:
        """Read the step in flight, if one is: its tokens are emitted and the
        slots it finishes are freed, as the next :meth:`step` would have. What
        ends or moves a slot from outside :meth:`step` calls this first."""
        flight, self._flight = self._flight, None
        if flight is not None:
            self._chunk_sp = None   # a call's leaf takes no attribute after the call
            self._resolve(flight)

    def _launch_attrs(self, kind: str, rows: int, tokens: int) -> dict:
        """The next launch number and what its program carries: the
        attributes, from its entry, of the leaf span that makes ONE call of a
        compiled serving program (``ds.serve.decode.dispatch`` where the step
        program is the leaf's only call, else a ``ds.serve.launch`` of the
        call's own nested in the leaf that is there). ``kind``: ``plain``
        (the decode program), ``mixed`` (the chunk program with decode rows),
        ``chunk`` (with none), ``prefill`` (the whole-prompt program, which
        only an engine that chunks no cold prompt has), ``verify``; ``rows``:
        decode rows carried; ``tokens``: prompt tokens carried (a chunk call
        that carries a whole prompt, first chunk and last in one, adds
        ``whole=1``). A trace's reader joins the runtime's launch inside the leaf
        to the number, and the leaves that read the program's outputs name it
        (``flight``, ``firsts``, a synchronous wait's ``launch``):
        docs/OBSERVABILITY.md."""
        self._launches += 1
        return {"launch": self._launches, "kind": kind, "rows": rows, "tokens": tokens}

    def _rows_due(self) -> List[int]:
        """The slots the next decode launch has a row for: past their prompt,
        and short of their count by what the host has read AND what is in
        flight. A stop by count is known at the launch; a stop the tokens
        decide is seen when they are read, a step late."""
        return [
            i for i, s in enumerate(self.slots)
            if s.request is not None and not s.prefilling
            and self._tokens_out(s) < s.request.max_new_tokens
        ]

    @staticmethod
    def _tokens_out(slot: _Slot) -> int:
        """The tokens of a slot's request that are sampled or will be by what
        is launched: those the host has read and those still on the device
        (the first, where its chunk rode the step in flight; a row's)."""
        return len(slot.request.tokens) + slot.first_due + slot.sent - slot.step

    def _hand_on_spent_slot(self, now: float) -> Optional[int]:
        """Free a slot whose request has its LAST row in the step in flight (a
        stop by count is known at the launch, so nothing more is launched for
        it) for the queue's next request, a call before that step is read:
        the slot sits out no step between two requests (without it the
        document cell's slots read 89.9% active where the synchronous loop
        read 91.4 and this one reads 91.1: PERF.md section 6, PR 54). The
        pages go back now (what the next owner writes is launched behind
        the row that still reads and writes them); the request keeps its
        place in the step in flight and is finished where its last token is
        read, later in this call. → the slot, or ``None`` where no slot is
        spent."""
        for i, held in (self._flight.rows if self._flight is not None else ()):
            if self.slots[i] is not held or self._tokens_out(held) < held.request.max_new_tokens:
                continue
            held.handed = True
            if self._heat_decode is not None:
                # the row's touch, before the session ends: the page the
                # row in flight writes and the prefix it attends, under the
                # number its step gets when it is read (the next one read)
                pos_after = held.pos + 1
                self._heat_decode.touch_step(now, self._step_count + 1, [(
                    i, int(self.table.block_tables[i, (pos_after - 1) // self.page_size]),
                    pages_for(pos_after, self.page_size),
                )])
            self._vacate(i, now)
            return i
        return None

    def _vacate(self, slot_i: int, now: float) -> None:
        """The slot's side of every end of a residency: the heat session
        closed, the pages back to their pools, the table's row cleared, a
        fresh slot in its place (by which a row of the old residency still
        in flight is known for dropped)."""
        slot = self.slots[slot_i]
        if self._heat_decode is not None:
            self._heat_decode.session_end(now, slot_i)
        self.allocator.free(slot.pages)
        if slot.prefill_pages:
            # ended mid-prefill (timeout / preempt / stall) before the handoff
            # could free the prefill-side reservation
            self.prefill_set.allocator.free(slot.prefill_pages)
        self.table.clear(slot_i)
        self.slots[slot_i] = _Slot()

    def _launch(self) -> Optional[_Flight]:
        """A call's launches: admission (and, in an engine that chunks no
        cold prompt, its whole prefills), the prefilling slots' chunks and the
        decode dispatch, from what the host knows without the tokens of the
        step in flight. → the step launched, if the call had a decode row."""
        with spans.span("ds.serve.admit") as sp:
            admitted, blocked = 0, ""
            now = self.clock()

            # 1. timeout eviction — a request past its deadline degrades to a
            # truncated response; its slot and pages are reclaimed immediately
            # (a row of its in the step in flight is computed and dropped)
            for i, slot in enumerate(self.slots):
                if slot.request is None:
                    continue
                dl = self._deadline(slot.request)
                if dl is not None and now > dl:
                    self._c_timeouts.inc()
                    self._finish_slot(i, RequestStatus.TRUNCATED, "deadline exceeded", now)
            if self.queue:
                keep: Deque[Request] = deque()
                for req in self.queue:
                    dl = self._deadline(req)
                    if dl is not None and now > dl:
                        req.status = RequestStatus.TIMED_OUT
                        req.detail = "deadline exceeded while queued"
                        req.t_finish = now
                        self._c_requests.inc(status=RequestStatus.TIMED_OUT)
                        self._req_terminal(req, now)
                        self.completed.append(req)
                    else:
                        keep.append(req)
                self.queue = keep

            # queue-wait attribution (ISSUE 11): requests sitting out a retry
            # backoff window are waiting on themselves, not on capacity — note
            # it once per scheduler step so the trace can split queue wait by
            # cause (the admission loop below attributes the capacity causes).
            # _backoff_pending gates the queue scan: retries are rare and a
            # deep queue would otherwise pay the walk every step
            if self.tracer is not None and self._backoff_pending:
                waiting = False
                for r in self.queue:
                    if r.not_before > now:
                        self.tracer.note_wait(r, "backoff")
                        waiting = True
                if not waiting:
                    self._backoff_pending = False

            # 2. prefill insertions: FIFO admission into free slots, gated by the
            # KV-page budget (head-of-line blocks until draining slots free
            # pages). The page need is net of prefix-index pages the prompt can
            # map (ISSUE 10 — shared pages cost nothing), and under pool
            # pressure the index yields cold entries to live traffic before the
            # head of line blocks. A drain stops admission entirely; a retried
            # request still inside its backoff window (not_before) is passed
            # over, not a head-of-line blocker.
            while self.queue and not self._draining:
                free = next(
                    (i for i, s in enumerate(self.slots) if s.request is None), None
                )
                if free is None:
                    free = self._hand_on_spent_slot(now)
                if free is None:
                    # all slots busy: the ready head of line waited this step
                    # on slot capacity (queue depth, in SLO terms). The ready
                    # scan only serves that attribution — skip it untraced
                    if self.tracer is not None:
                        idx = next(
                            (j for j, r in enumerate(self.queue)
                             if r.not_before <= now),
                            None,
                        )
                        if idx is not None:
                            self.tracer.note_wait(self.queue[idx], "no_free_slot")
                    blocked = "no_free_slot"
                    break
                idx = next(
                    (j for j, r in enumerate(self.queue) if r.not_before <= now),
                    None,
                )
                if idx is None:
                    blocked = "backoff"
                    break
                req = self.queue[idx]
                # ISSUE 17: before costing the reservation, restore any of the
                # prompt's demoted prefix pages from the host tier (each restore
                # turns a would-be recompute page into a mapped hit, shrinking
                # `need` below). Depth-bounded per step — a long host-held chain
                # keeps the request queued with a kv_restore wait and continues
                # next step rather than absorbing unbounded device_put work.
                if self.tiering is not None and self._tier_prefetch(req, now):
                    if self.tracer is not None:
                        self.tracer.note_wait(req, "kv_restore")
                    blocked = "kv_restore"
                    break
                # under disaggregation BOTH placements gate admission: the
                # decode pool must hold the full private reservation, the
                # prefill pool the prompt pages net of prefix hits. The index
                # holds prefill-side pages, so eviction only relieves that side.
                need = self._pages_needed(req)
                p_alloc = self.prefill_set.allocator
                p_need = (
                    self._prefill_pages_needed(req) if self.disaggregated else need
                )
                if need > self.allocator.free_pages or (
                    self.disaggregated and p_need > p_alloc.free_pages
                ):
                    if self.prefix_cache is not None and len(self.prefix_cache):
                        self.prefix_cache.evict(need_free=p_need)
                        self._g_index_pages.set(len(self.prefix_cache))
                        # eviction may have dropped the very pages the probe
                        # counted as mappable — recompute, or _admit could
                        # allocate past the pool
                        need = self._pages_needed(req)
                        if self.disaggregated:
                            p_need = self._prefill_pages_needed(req)
                    if need > self.allocator.free_pages or (
                        self.disaggregated and p_need > p_alloc.free_pages
                    ):
                        if self.tracer is not None:
                            self.tracer.note_wait(req, "page_budget")
                        blocked = "page_budget"
                        break
                del self.queue[idx]
                self._admit(free, req)
                admitted += 1
            sp.set(admitted=admitted, blocked=blocked)
            pre = [
                i for i, s in enumerate(self.slots)
                if s.request is not None and s.prefilling and s.pending_tok is None
            ]

        # 2b. chunked prefill (ISSUE 10): every PREFILLING slot advances ONE
        # chunk, then the decode batch below still runs — a long prompt pays
        # out its prefill across steps instead of stalling co-resident
        # decodes for its whole width. A slot whose first token is already
        # on the device (pending_tok) is past its last chunk — it waits on the
        # step's fetch (or the handoff phase below), not on more chunks.
        # Where a decode step follows on the same placement, the FIRST
        # prefilling slot's chunk RIDES its dispatch (phase 3: one call of
        # the chunk program carries the chunk and the decode rows, so the
        # step streams the weights once); every other chunk is a call of
        # that program with no decode row: here where this phase waits for
        # it (a prompt's last chunk, with no step in flight), else under that
        # dispatch, ahead of the call that carries the rider
        # (`_advance_chunks` says why).
        rider, unwaited = None, []
        self._chunk_sp = None
        if pre:
            self._chunk_sp, rider, unwaited = self._advance_chunks(pre)

        # 2c. disaggregated handoff completion (ISSUE 14): a slot whose
        # prefill placement has sampled the first token moves its prompt KV
        # into the decode pool and joins the decode batch. Readiness is
        # polled (is_ready) so a long prefill never stalls the decode
        # batch below — UNLESS nothing is decoding, in which case blocking
        # is free and avoids spinning run()'s step budget dry.
        if self.disaggregated:
            pend = [
                i for i, s in enumerate(self.slots)
                if s.request is not None and s.pending_tok is not None
            ]
            if pend:
                with spans.span("ds.serve.handoff") as sp:
                    done = 0
                    force = not any(
                        s.request is not None and not s.prefilling
                        for s in self.slots
                    )
                    for i in pend:
                        arr = self.slots[i].pending_tok
                        ready = getattr(arr, "is_ready", None)
                        if force or ready is None or ready():
                            self._complete_handoff(i)
                            done += 1
                            force = False  # a decode-active slot now exists
                    sp.set(slots=done)

        # 3. one batched decode (or speculative verify) step for every slot
        # that is past prefill and short of its count
        rows = self._rows_due()
        return self._dispatch(rows, rider, unwaited) if rows else None

    def _dispatch(self, active: List[int], rider: Optional[int] = None,
                  unwaited=()) -> _Flight:
        """The ``ds.serve.decode.dispatch`` leaf: launch one step program with
        a row for each slot of ``active`` (and ``rider``'s chunk, after the
        ``unwaited`` slots' calls) and move the dispatched side of the tables
        on. With a step in flight (``ahead`` 1) a row whose last token that
        step samples takes it from the step's output on the device."""
        before = self._flight
        # the step program is this leaf's one call, and the leaf carries its
        # number; a chunk that rides (and the calls ahead of it) has a leaf each
        own = {} if rider is not None else self._launch_attrs(
            "verify" if self.spec_enabled else "plain", len(active), 0)
        with spans.span(
            "ds.serve.decode.dispatch", active=len(active), ahead=int(before is not None), **own
        ) as sp:
            t0 = self.clock()
            # tokens the queries attend (each slot's cached context and the
            # token this step writes) and the pages those contexts hold
            lens = self.table.seq_lens[active]
            attended = int(lens.sum()) + len(active)
            if self.ring_pages:
                # what a layer reads, averaged over the layers: a window
                # layer reads its window of a context, not the context
                # (of a family of several kinds the sub-blocks that
                # attend: a cross layer reads its source's whole context,
                # a state-space mixer and a memory unit no key)
                ws = self.family.windows
                attended = int(sum(
                    (np.minimum(lens + 1, w).sum() if w else attended)
                    for k, w in zip(smodel.sub_block_kinds(self.family), ws) if k in ("attn", "cross")
                ) / len(ws))
            sp.set(
                attended=attended,
                pages=int((lens // self.page_size).sum()) + len(active),
            )
            self._c_slot_steps.inc(len(active))
            self._c_attended.inc(attended)
            prev = src = None
            if before is not None:
                self._c_ahead.inc()
                prev = self._token_of(before.out)
                src = np.full((self.max_slots,), -1, np.int32)
                for i in active:
                    s = self.slots[i]
                    if s.first_due:
                        src[i] = self.max_slots
                    elif s.sent > s.step:
                        src[i] = i
            rows = self.table.rows(active, prev, src)
            walk, rect = self._count_walk(
                rows[1], self.spec_k + 1 if self.spec_enabled else None, rows[2][:, 0] != SCRATCH_PAGE
            )
            sp.set(walk_steps=walk, rect_steps=rect)
            flight = _Flight(None, [(i, self.slots[i]) for i in active], t0)
            # the AOT executable takes the numpy slot tables directly — a
            # jnp.asarray wrapper here would dispatch extra device ops
            # per decode step (dslint jnp-in-hot-loop)
            if self.spec_enabled:
                T = self.spec_k + 1
                vt = np.zeros((self.max_slots, T), np.int32)
                vt[:, 0] = self.table.tokens
                for i in active:
                    d = self._draft(self.slots[i].request)
                    flight.drafts[i] = d
                    vt[i, 1:] = d
                flight.out = self.decode_set.call(self._verify_exec, vt, rows[1], rows[2])
                self._c_spec_steps.inc()
                self._c_spec_drafted.inc(self.spec_k * len(active))
            elif rider is not None:
                # the further prefilling slots' calls that nothing waits
                # for, then the chunk program with the step's own rows:
                # the slots' tokens come back with the chunk's, in one fetch
                for i in unwaited:
                    self._launch_alone(i)
                flight.rode = self._chunk_reach(rider)[0]
                flight.out, last = self._launch_chunk(rider, rows, len(active))
                self._c_chunks_rode.inc()
                if last:
                    # the chunk that rode was its prompt's last: the first
                    # token is this step's last place, and the slot's rows
                    # are launched from the next step on
                    flight.started = (rider, self.slots[rider])
                    self.slots[rider].first_due = True
                    self._arm(rider)
            else:
                flight.out = self.decode_set.call(self._decode_exec, *rows)
            flight.launch = self._launches   # the step program is the leaf's last call
            # the dispatched side moves on at the launch: the next step's
            # lengths and keys are known without this one's tokens (a verify
            # step's lengths move by what it accepts, when it is read)
            for i in active:
                s = self.slots[i]
                s.sent += 1
                if s.keys is not None and s.sent < len(s.keys):
                    self.table.keys[i] = s.keys[s.sent]
            if not self.spec_enabled:
                self.table.seq_lens[active] += 1
        return flight

    def _resolve(self, flight: _Flight) -> None:
        """Read a launched step and emit it: the ONE place a step waits for
        the device. The fetch is of the step program's own outputs, the tokens
        with the expert loads that ride them, and with them the first tokens
        that a whole prefill or a prompt's last chunk left on its slot while
        this step was in flight (programs queued before the step launched
        after it: the fetch waits for neither more nor less than the host
        needs). A row whose slot was ended since the launch (a stop the tokens
        decided, a stall, a deadline, a drain) is dropped and counted."""
        # the ONE deliberate sync of the slot loop: the scheduler must
        # read the sampled tokens to retire/advance slots (with them, a
        # prompt's earlier calls' expert loads where its last chunk rode)
        with spans.span("ds.serve.decode.wait", flight=flight.launch):
            rider, started = flight.started or (None, None)
            if started is not None and self.slots[rider] is not started:
                started = None   # ended since: its first token is nobody's
            late = [] if self.disaggregated else [
                i for i, s in enumerate(self.slots)
                if s.request is not None and s.pending_tok is not None
            ]
            out_np, alone_np, late_np = jax.device_get((  # dslint: disable=host-sync-in-step
                flight.out, list(started.moe_counts) if started else [],
                [(self.slots[i].pending_tok, list(self.slots[i].moe_counts)) for i in late],
            ))
        moe_np = None
        if self.family.sparse_layers:
            out_np, moe_np = out_np  # the expert loads rode the same fetch
        active = flight.rows
        with spans.span("ds.serve.emit", flight=flight.launch) as sp:
            if started is not None or late:
                # the programs whose FIRST token this leaf hands out: the
                # step's own where a prompt's last chunk rode it, and the
                # prefill or last chunk that left its token on a slot
                firsts = [flight.launch] if started is not None else []
                firsts += [self.slots[i].first_launch for i in late]
                sp.set(firsts=",".join(map(str, firsts)))
            if moe_np is not None:
                sp.set(**self._moe_attrs(
                    moe_np,
                    len(active) * (self.spec_k + 1 if self.spec_enabled else 1)
                    + flight.rode,
                ))
            n_emit = n_fin = dropped = 0
            now = self.clock()
            # one step's time: from its launch, or from the read of the step
            # before where it was launched behind that (it ran behind it on
            # the device too), to its read
            dt = now - max(flight.t0, self._t_read)
            self._t_read = now
            self._h_step.observe(dt)
            self._c_steps.inc()
            self._step_count += 1
            self._ema_step_s = (
                dt if self._ema_step_s == 0.0
                else 0.8 * self._ema_step_s + 0.2 * dt
            )
            # pass 1 — tokens + trace events for EVERY slot, batched into
            # ONE tracer ingestion (one lock round-trip per step, not per
            # slot), and ingested BEFORE any retirement below can fold a
            # finishing request's buffer into its terminal record
            emitted: list = []
            ev_batch: list = []
            heat_batch: list = []
            heat = self._heat_decode  # ISSUE 16: decode-pool heat ledger
            page = self.page_size
            for i, held in active:
                slot = held
                if self.slots[i] is not held and not held.handed:
                    dropped += 1   # the slot was ended since the launch
                    continue
                req = slot.request
                if self.spec_enabled:
                    toks = self._accept_tokens(req, flight.drafts[i], out_np[i])
                else:
                    toks = [int(out_np[i])]
                req.tokens.extend(toks)
                n_emit += len(toks)
                if heat is not None and not slot.handed:
                    # the step's KV write landed in the page holding the last
                    # emitted position; the attended set is the slot's
                    # block-table prefix (leanest columnar shape — offline
                    # expansion rides the session's S-event page list)
                    pos_after = slot.pos + len(toks)
                    heat_batch.append((
                        i, int(self.table.block_tables[i, (pos_after - 1) // page]),
                        pages_for(pos_after, page),
                    ))
                # one emission timestamp per token: an accepted speculative
                # run lands at ONE instant — the streaming-client truth the
                # TPOT quantiles derive from (ISSUE 11)
                req.t_emissions.extend([now] * len(toks))
                if self.tracer is not None:
                    ev_batch.append((req.id, {
                        "e": "verify", "t": now, "step": self._step_count,
                        "slot": i, "emitted": len(toks),
                        "drafted": self.spec_k, "accepted": len(toks) - 1,
                        "total": len(req.tokens),
                    } if self.spec_enabled else (
                        # plain decode: the lean columnar series (emitted
                        # is always 1) — this line runs for every slot of
                        # every step the engine ever takes
                        now, self._step_count, i,
                    )))
                emitted.append((i, slot, toks))
            if ev_batch:
                if self.spec_enabled:
                    self.tracer.step_events(ev_batch)
                else:
                    self.tracer.decode_events(ev_batch)
            if heat_batch:
                heat.touch_step(now, self._step_count, heat_batch)
            # pass 2 — advance/retire the slots (the emitted side; the
            # lengths and keys moved on when the row was launched)
            for i, slot, toks in emitted:
                req = slot.request
                slot.pos += len(toks)
                slot.step += 1
                if slot.handed:
                    # the slot is its next request's since this row's launch
                    self._close_request(req, RequestStatus.FINISHED, "", now)
                    self._req_terminal(req, now)
                    self.completed.append(req)
                    n_fin += 1
                    continue
                if self.spec_enabled:
                    self.table.seq_lens[i] = slot.pos
                self.table.tokens[i] = toks[-1]
                if len(req.tokens) >= req.max_new_tokens or (
                    req.eos_token_id is not None
                    and toks[-1] == req.eos_token_id
                ):
                    self._finish_slot(i, RequestStatus.FINISHED, "", now)
                    n_fin += 1
                elif req.stall_after is not None and len(req.tokens) >= req.stall_after:
                    # injected transient slot failure (ISSUE 7): evict and
                    # route through the retry-with-backoff path
                    self._fail_slot(i, "injected slot stall", now)
            if started is not None:
                # the chunk that rode was its prompt's last: the first
                # token is this step's, decoding started with the next
                if alone_np:
                    self._moe_done.append((np.concatenate(alone_np), started.moe_tokens))
                started.moe_counts, started.moe_tokens = [], 0
                self._first_token(rider, int(out_np[-1]), self.clock())
            for i, (tok_np, counts) in zip(late, late_np):
                # a whole prefill's [1] or a chunk call's [slots + 1]: the prompt's last
                slot = self.slots[i]
                if counts:
                    self._moe_done.append((np.concatenate(counts), slot.moe_tokens))
                slot.moe_counts, slot.moe_tokens, slot.pending_tok = [], 0, None
                self._start_decoding(i, int(tok_np[-1]))
            if dropped:
                self._c_dropped.inc(dropped)
            if self._moe_done:
                self._report_chunk_loads()
            sp.set(tokens=n_emit, finished=n_fin)

    def _report_chunk_loads(self) -> None:
        """The prompts whose last chunk's token this call read report their
        chunk calls' expert loads on the call's ``ds.serve.chunk`` leaf: it is
        closed, and its record takes attributes until the step ends. (Read
        outside a call that has one, they wait for the next.)"""
        if self._chunk_sp is not None:
            self._chunk_sp.set(**self._moe_report(self._moe_done))
            self._moe_done = []

    def _pages_needed(self, req: Request) -> int:
        """Net new DECODE-pool pages an admission must allocate: the
        request's full reservation minus pages the prefix index can map
        (non-counting probe — the admission gate runs this every step while
        a request heads the queue). Under disaggregation the decode
        reservation is ALL private (shared prompt KV is scattered into it
        by the handoff), so nothing nets out."""
        total = pages_for(req.prompt_len + req.max_new_tokens, self.page_size)
        if self.prefix_cache is None or self.disaggregated:
            return total
        return total - self.prefix_cache.probe(req.prompt)

    def _prefill_pages_needed(self, req: Request) -> int:
        """Prefill-pool pages a disaggregated admission must allocate: the
        PROMPT's pages net of prefix-index hits (the index lives on the
        prefill placement — that is where admissions compute)."""
        pp = pages_for(req.prompt_len, self.page_size)
        if self.prefix_cache is None:
            return pp
        return pp - self.prefix_cache.probe(req.prompt)

    # ------------------------------------------------------------------
    # ISSUE 17: host-tier restore prefetch + background spill pump
    # ------------------------------------------------------------------
    def _tier_prefetch(self, req: Request, now: float) -> bool:
        """Walk ``req``'s prefix chain root→leaf and restore every link the
        host tier holds back into freshly allocated prefill-pool pages (the
        ``serving_kv_restore`` program), re-adopting each into the index so
        the admission probe right after maps it as a plain hit. Returns
        True when the restore budget (``tiering.prefetch_depth``) ran out
        with host-held links remaining — the caller keeps the request
        queued under a ``kv_restore`` wait and continues next step.

        Miss semantics: a broken chain, a CRC-failed buffer, or an
        exhausted pool all just stop the walk — the un-restored tail
        re-prefills through the normal (chunked) path, bit-identically."""
        pc = self.prefix_cache
        tier = self.tiering
        palloc = self.prefill_set.allocator
        restored = 0
        for key in pc.chain_keys(req.prompt):
            if key in pc._entries:
                continue  # already device-resident
            if key not in tier.store:
                break  # chain broken here: cold from this link on
            if restored >= tier.prefetch_depth:
                return True  # budget spent, host still holds links
            try:
                pids = palloc.alloc(1)
            except PageAllocatorError:
                break  # pool pressure: the relief-valve path takes over
            t0 = self.clock()
            if not tier.restore(key, pids[0]):
                palloc.free(pids)  # cold miss (CRC/failed fill): recompute
                break
            pc.adopt(key, pids[0])
            restored += 1
            if self.tracer is not None:
                t1 = self.clock()
                self.tracer.event(
                    req, "kv_restore", t1, page=int(pids[0]),
                    bytes=tier.store.page_bytes, dur_s=t1 - t0,
                )
        return False

    def _tier_pump(self) -> int:
        """Keep free-page headroom by demoting cold index leaves to host
        BEFORE admissions hit the relief valve: when the prefill pool's
        free list drops under 1/8 capacity, evict (= demote, the sink is
        wired) enough LRU leaves to climb back. The device-side snapshot
        is dispatched here; the blocking device→host copy runs on the
        spill worker — the step path never waits on host DMA. → the pages
        it moved."""
        pc = self.prefix_cache
        if pc is None or not len(pc):
            return 0
        palloc = self.prefill_set.allocator
        low = max(1, palloc.capacity // 8)
        if palloc.free_pages >= low:
            return 0
        held = len(pc)
        pc.evict(need_free=low)
        self._g_index_pages.set(len(pc))
        return held - len(pc)

    def _draft(self, req: Request) -> np.ndarray:
        """Host-side prompt-lookup draft (ISSUE 10): the continuation of the
        most recent PRIOR occurrence of the context's last ``ngram`` tokens,
        padded with the last token. The ngram→position map is maintained
        incrementally on the request (only positions that appeared since the
        previous step get indexed), so drafting costs O(tokens appended) per
        step instead of rescanning the whole context; a retry rewind
        (``req.tokens`` reset) shrinks the context and rebuilds it. A bad
        draft costs nothing extra — the verify step's shape is fixed — so
        the fallback is deliberately dumb."""
        k, n = self.spec_k, self.spec_ngram
        prompt = req.prompt_list
        L = len(prompt) + len(req.tokens)
        st = getattr(req, "_draft_state", None)
        if st is None or len(st[0]) > L:
            st = ([], {}, [0])  # (ctx copy, ngram→most-recent start, watermark)
            object.__setattr__(req, "_draft_state", st)
        ctx, index, cur = st
        if len(ctx) < L:
            grown = len(ctx)
            ctx.extend(prompt[grown:] if grown < len(prompt) else [])
            ctx.extend(req.tokens[len(ctx) - len(prompt):])
        # index every ngram start strictly before the target position L-n —
        # latest write wins, so a lookup is exactly the backward scan's
        # "most recent prior occurrence"
        for s in range(cur[0], L - n):
            index[tuple(ctx[s:s + n])] = s
        cur[0] = max(cur[0], L - n)
        last = ctx[-1]
        if L >= n + 1:
            s = index.get(tuple(ctx[L - n:]))
            if s is not None:
                cont = ctx[s + n:s + n + k]
                return np.asarray((cont + [last] * k)[:k], np.int32)
        return np.full((k,), last, np.int32)

    def _accept_tokens(self, req: Request, draft: np.ndarray,
                       greedy: np.ndarray) -> List[int]:
        """The speculative accept rule: ``greedy[t]`` is the argmax token
        after the prefix ⊕ draft[:t], so drafts are accepted while
        ``draft[t] == greedy[t]`` and the step emits the accepted drafts
        plus one bonus token — exactly the sequential greedy stream,
        truncated to the remaining budget and at EOS."""
        n_acc = 0
        while n_acc < self.spec_k and int(draft[n_acc]) == int(greedy[n_acc]):
            n_acc += 1
        emit = min(n_acc + 1, req.max_new_tokens - len(req.tokens))
        toks = [int(t) for t in greedy[:emit]]
        if req.eos_token_id is not None and req.eos_token_id in toks:
            toks = toks[: toks.index(req.eos_token_id) + 1]
        self._c_spec_accepted.inc(len(toks) - 1)
        self._h_accept.observe(len(toks))
        return toks

    def _admit(self, slot_i: int, req: Request) -> None:
        self._admissions += 1
        # queue wait ends here: the request owns a slot
        req.t_admit = self.clock()
        qw = req.queue_wait_s
        if qw is not None:
            self._h_qwait.observe(qw)
        if (
            req.stall_after is None
            and self.fault_injector is not None
            and self.fault_injector.fire("serving_stall", self._admissions)
        ):
            # fail once the request is mid-decode — the interesting point:
            # pages held, tokens emitted, retry must rewind all of it
            req.stall_after = max(1, req.max_new_tokens // 2)
        page = self.page_size
        total = pages_for(req.prompt_len + req.max_new_tokens, page)

        # prefix-cache lookup (ISSUE 10): map every indexed full page of the
        # prompt instead of recomputing it. A full-prefix hit additionally
        # finds the LAST prompt page indexed — that page is copy-on-write
        # forked (a fresh private page, filled by recomputing its tokens
        # through the chunk program) because the slot's own decode writes
        # continue into its page-aligned neighborhood; the shared original
        # stays immutable for every other holder.
        shared: List[int] = []
        shared_tokens = 0
        cow_page = None
        if self.prefix_cache is not None:
            shared, shared_tokens, cow_page = self.prefix_cache.lookup(req.prompt)
            kind = (
                "full" if cow_page is not None
                else ("partial" if shared else "miss")
            )
            self._c_prefix_hits.inc(kind=kind)
            pc = self.prefix_cache
            lookups = pc.hits_full + pc.hits_partial + pc.misses
            if lookups:
                self._g_prefix_rate.set(
                    (pc.hits_full + pc.hits_partial) / lookups
                )
            if shared:
                # refcounts live with the pool that holds the pages: the
                # prefill allocator under disaggregation (aliases the
                # decode allocator in shared mode)
                self.prefill_set.allocator.retain(shared)
                self._c_pages_reused.inc(len(shared))
            if cow_page is not None:
                self.prefill_set.allocator.cow_forks_total += 1
                self._c_cow.inc()
        p_priv: List[int] = []
        try:
            if self.disaggregated:
                # two reservations: prompt pages on the prefill placement
                # (shared + private — the handoff reads and then frees the
                # private ones), the FULL reservation as private pages on the
                # decode placement (the handoff scatters the prompt KV in)
                p_priv = self.prefill_set.allocator.alloc(
                    pages_for(req.prompt_len, page) - len(shared)
                )
                prefill_pages = shared + p_priv
                pages = self.allocator.alloc(total)
            else:
                prefill_pages = []
                pages = shared + self.allocator.alloc(total - len(shared))
        except PageAllocatorError as e:
            # dual-reserve rollback: a raising alloc must not strand the
            # prefix retains or the other pool's reservation — the admission
            # either holds everything it needs or holds nothing (one free
            # call so the rollback itself has no partial-release edge)
            rollback = p_priv + shared
            if rollback:
                self.prefill_set.allocator.free(rollback)
            self._retry_or_fail(
                req, f"admission reservation failed: {e}", self.clock()
            )
            return
        slot = self.slots[slot_i]
        slot.request = req
        slot.pages = pages
        slot.prefill_pages = prefill_pages
        slot.pending_tok = None
        slot.moe_counts, slot.moe_tokens = [], 0
        slot.pos = 0
        slot.step = slot.sent = 0
        slot.keys = None
        slot.shared_pages = len(shared)
        slot.row = None
        slot.prefilling = False
        req.prefix_shared_tokens = shared_tokens
        req.cow_forked = cow_page is not None
        if self._heat_decode is not None:
            # session owner map (ISSUE 16): the FULL decode reservation is
            # taken here — no decode-time growth — so the S event's
            # block-table-ordered page list is the slot's complete footprint
            self._heat_decode.session_start(
                req.t_admit, slot_i, req.id, req.tenant, pages
            )
        if self.tracer is not None:
            self.tracer.event(
                req, "admit", req.t_admit, step=self._step_count,
                slot=slot_i, queue_wait_s=qw, pages=total,
                shared_pages=len(shared), shared_tokens=shared_tokens,
                prefix_kind=(
                    ("full" if cow_page is not None
                     else ("partial" if shared else "miss"))
                    if self.prefix_cache is not None else None
                ),
                retries=req.retries,
            )

        # which program prefills: the chunk program every prompt of an engine
        # that chunks cold prompts (one no longer than a chunk as ONE chunk,
        # its first and its last) and the tail behind a prefix hit; the
        # whole-prompt program the cold prompts of an engine that does not.
        # A prompt's length selects nothing.
        if self.chunk_width > 0 and (shared_tokens > 0 or self._chunk_cold):
            # chunked tail prefill: the real block table lives on the slot;
            # the main table row stays scratch so the batched decode's
            # rides-along write for this slot cannot touch real (possibly
            # shared) pages mid-prefill. Under disaggregation the chunk
            # program runs on the PREFILL placement, so the row addresses
            # the prefill pool's pages.
            row = np.full((1, self.pages_per_slot), 0, np.int32)
            src = prefill_pages if self.disaggregated else pages
            row[0, : len(src)] = src
            slot.row = row
            slot.prefilling = True
            slot.prefill_pos = shared_tokens
            req.status = RequestStatus.RUNNING
            return

        ids = np.zeros((1, self.prefill_width), np.int32)
        ids[0, : req.prompt_len] = req.prompt
        # host-built key + plain numpy operands: the compiled prefill does
        # its own device_put, so admission dispatches exactly one program
        key0 = _host_prng_key(req.seed)
        pset = self.prefill_set
        if self.disaggregated:
            # whole prefill on the PREFILL placement: page ids address the
            # prefill pool, and the sampled first token stays ON DEVICE
            # (slot.pending_tok) — admission never blocks the decode batch;
            # step phase 2c syncs it and completes the handoff
            page_ids = np.zeros((self.prefill_pages,), np.int32)
            page_ids[: len(prefill_pages)] = prefill_pages
            with spans.span("ds.serve.launch", **self._launch_attrs("prefill", 0, req.prompt_len)):
                first = self._token_of(pset.call(
                    self._prefill_exec, ids, np.asarray(req.prompt_len, np.int32), page_ids, key0,
                ))
            self._c_prefills.inc()
            slot.pending_tok, slot.first_launch = first, self._launches
            slot.prefilling = True
            slot.prefill_pos = req.prompt_len
            req.status = RequestStatus.RUNNING
            if self.tracer is not None:
                self.tracer.event(
                    req, "prefill", self.clock(), step=self._step_count,
                    slot=slot_i, width=self.prefill_width,
                    prompt_len=req.prompt_len,
                )
            return

        # the slot's real block table lives on the slot until it decodes: the
        # main table's row stays scratch, so that a decode step launched while
        # the first token is still on the device cannot write this slot's pages
        slot.row = np.zeros((1, self.pages_per_slot), np.int32)
        slot.row[0, : len(pages)] = pages
        with spans.span("ds.serve.launch", **self._launch_attrs("prefill", 0, req.prompt_len)):
            first = self._token_of(pset.call(
                self._prefill_exec, ids, np.asarray(req.prompt_len, np.int32),
                slot.row[0, : self.prefill_pages], key0,
                *self._slot_operand(slot_i),
            ))
        self._c_prefills.inc()
        slot.first_launch = self._launches
        if self._flight is not None:
            # a step is in flight: the token stays on the slot, as under
            # disaggregation, and is read with that step's fetch (`_resolve`)
            slot.pending_tok = first
            slot.prefilling = True
            slot.prefill_pos = req.prompt_len
            req.status = RequestStatus.RUNNING
        else:
            # deliberate sync: TTFT is defined by the first token reaching the
            # host, and an at-admission EOS must retire the slot before decode
            with spans.span("ds.serve.prefill.wait", launch=slot.first_launch):
                tok0 = int(jax.device_get(first)[0])  # dslint: disable=host-sync-in-step
        if self.tracer is not None:
            self.tracer.event(
                req, "prefill", self.clock(), step=self._step_count,
                slot=slot_i, width=self.prefill_width,
                prompt_len=req.prompt_len,
            )
        if slot.pending_tok is None:
            self._start_decoding(slot_i, tok0)

    def _slot_operand(self, slot_i: int) -> tuple:
        """The last host operand of the prefill and chunk programs of a
        family that keeps per-slot state there: the slot, whose ring a window
        layer writes, whose carried rows an attention reads and leaves and
        whose recurrent state a mixer starts from and leaves."""
        return (np.asarray(slot_i, np.int32),) if self.decode_set.cache.per_slot else ()

    def _token_of(self, out):
        """The sampled token of a prefill program's results (a family with
        expert layers returns their loads after it; the whole-prompt program's
        are not reported)."""
        return out[0] if self.family.sparse_layers else out

    def _launch_chunk(self, slot_i: int, rows: tuple, carried: int = 0):
        """The next chunk of a PREFILLING slot's prompt through the chunk
        program, beside the decode ``rows`` (a step's own, ``carried`` of
        them: the chunk rides its dispatch; the idle table's: a call with no
        decode row), under a ``ds.serve.launch`` leaf of its own. → (the
        call's results after the pools, on the device: the tokens ``[slots +
        1]``, the chunk's last, and for a family with expert layers their
        loads; whether that was the prompt's last chunk)."""
        slot = self.slots[slot_i]
        req = slot.request
        C = self.chunk_width
        page = self.page_size
        start = slot.prefill_pos
        ids = np.zeros((1, C), np.int32)
        seg = req.prompt[start: start + C]
        ids[0, : len(seg)] = seg
        p0 = start // page
        n_cp = C // page
        page_ids = np.zeros((n_cp,), np.int32)  # scratch-padded
        avail = slot.row[0, p0: p0 + n_cp]
        page_ids[: len(avail)] = avail
        key0 = _host_prng_key(req.seed)
        pset = self.prefill_set
        attrs = self._launch_attrs("mixed" if carried else "chunk", carried, len(seg))
        if self._chunk_is_whole(slot_i):
            attrs["whole"] = 1   # the prompt's first chunk and its last: the one call of its prefill
        with spans.span("ds.serve.launch", **attrs):
            out = pset.call(
                self._chunk_exec, *rows, ids, np.asarray(start, np.int32),
                np.asarray(req.prompt_len, np.int32), page_ids, slot.row, key0,
                *self._slot_operand(slot_i),
            )
        self._c_chunks.inc()
        slot.prefill_pos = start + C
        final = slot.prefill_pos >= req.prompt_len
        if final:
            self._c_prefills.inc()
            slot.first_launch = self._launches
        if self.tracer is not None:
            self.tracer.event(
                req, "prefill_chunk", self.clock(), step=self._step_count,
                slot=slot_i, start=start, width=C, final=final,
            )
        return out, final

    def _chunk_reach(self, slot_i: int) -> tuple:
        """(prompt tokens the next chunk of a PREFILLING slot advances, key
        rows their queries read: the context before the chunk for each of
        them and the causal triangle inside it)."""
        slot = self.slots[slot_i]
        t = min(self.chunk_width, slot.request.prompt_len - slot.prefill_pos)
        return t, t * slot.prefill_pos + t * (t + 1) // 2

    def _count_walk(self, base, T: Optional[int] = None, live=None, chunk: bool = False) -> tuple:
        """One call of a program's paged attention kernel whose slots' queries
        start at ``base`` (``T`` of them a slot; None: the decode step's one;
        ``chunk``: the chunk kernel's), the slots that are not ``live``
        idle: → (the items the call owns, the steps of its rectangle), one
        paged layer's (every such layer of the call walks the same; a window
        layer's ring is not counted), by the rule the kernel's wrapper walks
        by. Where the programs call the kernel, both are added to the
        counters under its name in a trace."""
        if self.decode_set.cache.latent:
            kernel = "mla_paged_" + ("chunk" if chunk else "decode" if T is None else "verify")
            walk, rect = latent_walk_steps(
                base, self.family.n_head, self.page_size, T or 1, self.pages_per_slot
            )
        else:
            kernel = "chunk_fn" if chunk else "decode_fn" if T is None else "verify_fn"
            pset = self.decode_set
            shape = (
                pset.local_kv_heads(), pset.page_size, pset.head_dim,
                pset.cache.k.dtype.itemsize, self.pages_per_slot,
            )
            if chunk:
                rep = self.family.n_head // self.family.n_kv_head
                walk, rect = paged_walk_steps(base, live, *shape, T, rep)
            else:
                # the verify step attends its T queries as T one-token calls
                walk, rect = map(sum, zip(*(
                    paged_walk_steps(np.asarray(base) + t, live, *shape) for t in range(T or 1)
                )))
        if self._attn_kernel:
            self._c_walk_steps.inc(walk, program=kernel)
            self._c_rect_steps.inc(rect, program=kernel)
        return walk, rect

    def _chunk_is_last(self, slot_i: int) -> bool:
        """Whether the next chunk of a PREFILLING slot is its prompt's last."""
        slot = self.slots[slot_i]
        return slot.prefill_pos + self.chunk_width >= slot.request.prompt_len

    def _chunk_is_whole(self, slot_i: int) -> bool:
        """Whether the next chunk of a PREFILLING slot is its whole prompt:
        its first chunk and its last, the one call of a prompt no longer than
        a chunk."""
        return self.slots[slot_i].prefill_pos == 0 and self._chunk_is_last(slot_i)

    def _moe_report(self, prompts: list) -> dict:
        """``ds.serve.chunk``'s expert attributes for the prompts that
        finished prefilling: (loads ``[calls x sparse layers, experts_held]``
        of a prompt's chunk calls that rode no decode step, the tokens those
        advanced) each. A call that rode is in its step's ``ds.serve.emit``."""
        counts = np.concatenate([c for c, _ in prompts])
        return dict(
            moe_calls=len(counts) // len(self.family.sparse_layers),
            **self._moe_attrs(counts, sum(n for _, n in prompts)),
        )

    def _launch_alone(self, slot_i: int):
        """:meth:`_launch_chunk` with no decode row (the idle table's rows):
        the call's expert loads wait on the slot, on the device, for the
        prompt's last chunk. → (the call's tokens on the device, whether that
        was the prompt's last chunk)."""
        slot = self.slots[slot_i]
        t = self._chunk_reach(slot_i)[0]
        out, final = self._launch_chunk(slot_i, self._idle_table.rows())
        if self.family.sparse_layers:
            out, counts = out
            slot.moe_counts.append(counts)
            slot.moe_tokens += t
        return out, final

    def _advance_chunks(self, pre: list):
        """The ``ds.serve.chunk`` leaf of a step, for the prefilling slots
        ``pre``: where the call has a decode dispatch to come on the same
        placement, the first of them is the ``rider``, whose chunk that
        dispatch will carry (the leaf counts its tokens and key rows beside
        the others'); one call of the chunk program with no decode row for
        each of the others. ``whole``: how many of those chunks are a whole
        prompt (one no longer than a chunk: its one call).
        On a prompt's final chunk the sampled token becomes the request's
        first token and the slot joins the decode batch: here, where nothing
        is in flight and this leaf waits for it; else the token stays on the
        slot (``pending_tok``) and the step's fetch reads it. → (the span,
        closed; the rider; the slots whose call the dispatch leaf
        launches). (loads, tokens) of the prompts that finished go to
        ``_moe_done``, and :meth:`_step` reports them on the span once the
        step's fetch is in, which may bring more (a prompt whose last chunk
        rode, or was not waited for): the ring's record takes attributes
        until the step ends.

        Which calls the dispatch leaf launches, ahead of the one that carries
        the ``rider``: those nothing here waits for (not a prompt's last
        chunk). A call this leaf waits for has ended before the dispatch
        opens. Every call, here or there, has a ``ds.serve.launch`` leaf of
        its own with its launch number (:meth:`_launch_attrs`), so a trace's
        reader knows each program by its number, wherever it was launched."""
        with spans.span("ds.serve.chunk") as sp:
            rider = pre[0] if self._ahead_ok and self._rows_due() else None
            alone = pre[rider is not None:]
            sp.set(chunks=len(alone), rode=int(rider is not None))
            n_tok, attended = self._chunk_reach(rider) if rider is not None else (0, 0)
            finals = int(rider is not None and self._chunk_is_last(rider))
            whole = 0
            for i in alone + ([rider] if rider is not None else []):
                self._count_walk([self.slots[i].prefill_pos], self.chunk_width, chunk=True)
                whole += self._chunk_is_whole(i)
            unwaited = []
            for i in alone:
                slot = self.slots[i]
                t, att = self._chunk_reach(i)
                n_tok += t
                attended += att
                finals += self._chunk_is_last(i)
                if rider is not None and slot.prefill_pos + t < slot.request.prompt_len:
                    unwaited.append(i)
                    continue
                out, final = self._launch_alone(i)
                if not final:
                    continue  # more chunks; the decode batch advances meanwhile
                if self.disaggregated or self._flight is not None:
                    # the final chunk's sample stays on device: step phase 2c
                    # syncs it and hands the prompt KV off to the decode
                    # placement, or (a step in flight) that step's fetch
                    # reads it (`_resolve`)
                    slot.pending_tok = out
                    continue
                # deliberate sync, as in _admit: the final chunk's sample is
                # the request's first token
                with spans.span("ds.serve.chunk.wait", launch=slot.first_launch):
                    tok_np, *counts = jax.device_get((out, *slot.moe_counts))  # dslint: disable=host-sync-in-step
                if counts:
                    self._moe_done.append((np.concatenate(counts), slot.moe_tokens))
                slot.moe_counts, slot.moe_tokens = [], 0
                self._start_decoding(i, int(tok_np[-1]))
            sp.set(tokens=n_tok, attended=attended, whole=whole)
            if getattr(self.family, "kinds", None):
                # rows through the sub-blocks up to the family's stop_after,
                # and through those behind it: a final chunk's sampled row
                stops = getattr(self.family, "stop_after", None) is not None
                sp.set(rows_self=n_tok, rows_cross=finals if stops else n_tok)
                if stops:
                    self._c_rows_skipped.inc(n_tok - finals)
        return sp, rider, unwaited

    def _complete_handoff(self, slot_i: int) -> None:
        """Finish a disaggregated prefill (ISSUE 14): read the pending first
        token, move the prompt KV from the prefill placement's pool into
        the slot's private decode-pool reservation (gather on the prefill
        mesh → ``device_put`` across placements → scatter donating the
        decode pools), register the prompt in the prefix index (PREFILL-side
        pages — the index serves admissions, which compute there), free the
        prefill-side private pages, and join the decode batch.

        Prefill-terminal requests (``max_new_tokens == 1`` or EOS on the
        first token) skip the copy entirely — they finish without ever
        decoding, so their KV has no business on the decode placement."""
        slot = self.slots[slot_i]
        req = slot.request
        # phase 2c only calls here once the array is ready (or nothing is
        # decoding, so blocking costs no batch progress)
        # a whole prefill's [1] or a chunk call's [slots + 1]: the prompt's last
        with spans.span("ds.serve.handoff.wait", launch=slot.first_launch):
            tok0 = int(jax.device_get(slot.pending_tok)[-1])  # dslint: disable=host-sync-in-step
        slot.pending_tok = None
        if req.max_new_tokens == 1 or (
            req.eos_token_id is not None and tok0 == req.eos_token_id
        ):
            # prefill-terminal request: the first token is also the last,
            # so the decode placement never needs this prompt's KV — skip
            # the cross-placement copy; index + free stay prefill-side
            if self.prefix_cache is not None:
                self.prefix_cache.insert(req.prompt, slot.prefill_pages)
                self._g_index_pages.set(len(self.prefix_cache))
            self.prefill_set.allocator.free(slot.prefill_pages)
            slot.prefill_pages = []
            self._start_decoding(slot_i, tok0)
            return
        t0 = self.clock()
        n = len(slot.prefill_pages)
        # scratch-pad both id lists to the compiled static width; duplicate
        # pad entries all hit scratch page 0, which no live slot reads
        src = np.zeros((self.prefill_pages,), np.int32)
        src[:n] = slot.prefill_pages
        dst = np.zeros((self.prefill_pages,), np.int32)
        dst[:n] = slot.pages[:n]
        pset, dset = self.prefill_set, self.decode_set
        packed = self._gather_exec(pset.cache, src)
        moved = tuple(dset.placement.pull_pool(x) for x in packed)
        out = (dset.cache,) = self._scatter_exec(dset.cache, *moved, dst)
        # sync for latency truth: the handoff gauge must cover the actual
        # copy, not its async dispatch
        with spans.span("ds.serve.handoff.wait"):
            jax.block_until_ready(out)  # dslint: disable=host-sync-in-step
        now = self.clock()
        nbytes = sum(int(x.nbytes) for x in packed)
        self._c_handoffs.inc()
        self._c_handoff_bytes.inc(nbytes)
        self._h_handoff.observe(now - t0)
        if self.tracer is not None:
            self.tracer.event(
                req, "kv_handoff", now, step=self._step_count, slot=slot_i,
                pages=n, bytes=nbytes, latency_s=now - t0,
            )
        # prefix insert BEFORE freeing: insert retains the prompt's full
        # pages, so the private non-full tail is the only thing released
        if self.prefix_cache is not None:
            self.prefix_cache.insert(req.prompt, slot.prefill_pages)
            self._g_index_pages.set(len(self.prefix_cache))
        pset.allocator.free(slot.prefill_pages)
        slot.prefill_pages = []
        self._start_decoding(slot_i, tok0)

    def _start_decoding(self, slot_i: int, tok0: int) -> None:
        """Shared post-prefill transition, where the host has the first token
        in hand: both sides of it, :meth:`_arm` and :meth:`_first_token`."""
        now = self.clock()
        self._arm(slot_i)
        self._first_token(slot_i, tok0, now)

    def _arm(self, slot_i: int) -> None:
        """The dispatched side of a prompt's end: install the real block
        table (if the prefill kept it on the slot), set the length and arm
        the sampling keys. From here on the slot's decode rows can be
        launched, with the first token in the host's table or still on the
        device (``first_due``)."""
        slot = self.slots[slot_i]
        req = slot.request
        if self.disaggregated:
            # the slot decodes against its private decode-pool reservation;
            # whatever row the prefill used addressed the OTHER pool
            self.table.assign(slot_i, slot.pages)
            slot.prefilling = False
            slot.row = None
        elif slot.row is not None:
            self.table.block_tables[slot_i, :] = slot.row[0]
            slot.prefilling = False
            slot.row = None
        slot.pos = req.prompt_len
        self.table.seq_lens[slot_i] = slot.pos
        if self._sampling and req.max_new_tokens > 1:
            # the EXACT key sequence of gpt2.generate for this request:
            # step t consumes split(fold_in(PRNGKey(seed), 1), N-1)[t-1].
            # fold_in/split ARE the jax PRNG — reimplementing threefry on
            # the host would fork the bit-parity contract, so the sampling
            # path keeps one device round-trip per admission (waived below)
            key1 = jax.random.fold_in(  # dslint: disable=jnp-in-hot-loop
                jax.random.PRNGKey(req.seed), 1
            )
            # dslint: disable=jnp-in-hot-loop
            keys = jax.random.split(key1, req.max_new_tokens - 1)
            with spans.span("ds.serve.keys.wait"):
                slot.keys = np.asarray(keys)  # dslint: disable=host-sync-in-step
            self.table.keys[slot_i] = slot.keys[0]

    def _first_token(self, slot_i: int, tok0: int, now: float) -> None:
        """The emitted side of a prompt's end, at ``now``, where the host got
        the token: record TTFT, register the prompt's full pages in the
        prefix index, and handle an immediate EOS / single-token ask."""
        slot = self.slots[slot_i]
        req = slot.request
        slot.first_due = False
        req.status = RequestStatus.RUNNING
        # TTFT = the first SAMPLED token reaching the host. Under chunked
        # prefill that is the LAST chunk's sample (earlier chunks emit
        # nothing a client could stream) — the ISSUE 11 pin.
        req.t_first_token = now
        req.first_launch = slot.first_launch
        self._h_ttft.observe(now - req.t_submit)
        req.tokens.append(tok0)
        req.t_emissions.append(now)
        if self.tracer is not None:
            self.tracer.event(
                req, "first_token", now, step=self._step_count, slot=slot_i,
                ttft_s=now - req.t_submit,
            )
        self.table.tokens[slot_i] = tok0
        if self.prefix_cache is not None and not self.disaggregated:
            # disaggregated: _complete_handoff already indexed the
            # PREFILL-side pages — slot.pages here are decode-pool ids
            self.prefix_cache.insert(req.prompt, slot.pages)
            self._g_index_pages.set(len(self.prefix_cache))
        if req.max_new_tokens == 1 or (
            req.eos_token_id is not None and tok0 == req.eos_token_id
        ):
            self._finish_slot(slot_i, RequestStatus.FINISHED, "", now)

    def _finish_slot(self, slot_i: int, status: str, detail: str, now: float) -> None:
        slot = self.slots[slot_i]
        req = slot.request
        self._close_request(req, status, detail, now)
        self._vacate(slot_i, now)
        self._req_terminal(req, now)
        self.completed.append(req)

    def _close_request(self, req: Request, status: str, detail: str, now: float) -> None:
        """The request's side of a finish: its status, its stamp, the gaps
        between its tokens and the counters."""
        stopped_on_eos = (
            req.eos_token_id is not None
            and bool(req.tokens)
            and req.tokens[-1] == req.eos_token_id
        )
        if (
            req.requested_new_tokens is not None
            and status == RequestStatus.FINISHED
            and not stopped_on_eos
        ):
            # the clamp actually bit: the decode budget ran out short of the
            # original ask. An EOS stop is a complete response even when the
            # ask was clamped.
            status = RequestStatus.TRUNCATED
        req.status = status
        if detail:
            req.detail = detail
        req.t_finish = now
        # ISSUE 11 fix: observe per-emission inter-token gaps, not the
        # per-request mean — a speculative verify step emits k+1 tokens at
        # one instant, and a streaming client's p99 sees those 0-gaps plus
        # the full step latency before the run, not a flattering average
        for gap in req.inter_token_gaps_s:
            self._h_tpot.observe(gap)
        self._c_requests.inc(status=status)
        self._c_tokens.inc(len(req.tokens))

    def _slo_verdict(self, req: Request) -> Optional[dict]:
        """The request's SLO outcome against its class targets, or None
        when no SLO accounting applies (no classes configured, or the
        class declares no targets). Only a FINISHED request can meet its
        SLO; a missing TPOT measurement (< 2 tokens) passes that axis."""
        if not self._slo_enabled:
            return None
        t = self._slo.targets(req.slo_class)
        if t["ttft_target_s"] <= 0 and t["tpot_target_s"] <= 0:
            return None
        met = req.status == RequestStatus.FINISHED
        if met and t["ttft_target_s"] > 0:
            met = req.ttft_s is not None and req.ttft_s <= t["ttft_target_s"]
        if met and t["tpot_target_s"] > 0:
            tp = req.tpot_s
            met = tp is None or tp <= t["tpot_target_s"]
        return {"class": req.slo_class, **t, "met": bool(met)}

    def _req_terminal(self, req: Request, now: float) -> None:
        """Every terminal transition funnels here (ISSUE 11): the SLO
        verdict + goodput ledger, per-tenant accounting, and the trace
        record. ``req.t_finish`` is already set."""
        self._status_counts[req.status] = (
            self._status_counts.get(req.status, 0) + 1
        )
        verdict = self._slo_verdict(req)
        if verdict is not None:
            cnt = self._slo_counts.setdefault(req.slo_class, [0, 0])
            cnt[1] += 1
            self._c_slo_eval.inc(slo_class=req.slo_class)
            if verdict["met"]:
                cnt[0] += 1
                self._slo_good_tokens += len(req.tokens)
                self._c_slo_met.inc(slo_class=req.slo_class)
                if req.tokens:
                    self._c_good_tokens.inc(len(req.tokens))
                    if self._goodput_window_s > 0.0:
                        self._good_events.append((now, len(req.tokens)))
            self._g_slo.set(cnt[0] / cnt[1], slo_class=req.slo_class)
        ten = self.tenants.setdefault(req.tenant, {
            "requests": 0, "tokens": 0, "slo_met": 0, "slo_evaluated": 0,
        })
        ten["requests"] += 1
        ten["tokens"] += len(req.tokens)
        if verdict is not None:
            ten["slo_evaluated"] += 1
            ten["slo_met"] += int(verdict["met"])
        self._c_tenant_requests.inc(tenant=req.tenant, status=req.status)
        if req.tokens:
            self._c_tenant_tokens.inc(len(req.tokens), tenant=req.tenant)
        if self.tracer is not None:
            self.tracer.finish(
                req, req.t_finish if req.t_finish is not None else now,
                slo=verdict,
            )

    def _fail_slot(self, slot_i: int, why: str, now: float) -> None:
        """Transient slot failure (ISSUE 7): reclaim the slot and pages
        immediately, then either re-enqueue the request with exponential
        backoff (``serving.retry_max`` budget — generation restarts from
        scratch, the evicted KV is gone) or finish it terminal FAILED."""
        req = self.slots[slot_i].request
        self._vacate(slot_i, now)
        self._retry_or_fail(req, why, now)

    def _retry_or_fail(self, req: Request, why: str, now: float) -> None:
        """Requeue-with-backoff or terminal-FAIL a request whose pages and
        slot (if any) are already reclaimed. Shared by transient slot
        failures and admission-reservation failures; deliberately performs
        no allocator operations."""
        retry_max = int(getattr(self.config, "retry_max", 0))
        if not self._draining and req.retries < retry_max:
            req.retries += 1
            req.stall_after = None  # the injected fault is one-shot
            req.tokens = []
            # the retry regenerates from scratch — drop the incremental
            # drafter index built over the discarded output, and the
            # emission/admission timeline with it (queue wait and TPOT are
            # re-measured from the re-admission)
            object.__setattr__(req, "_draft_state", None)
            req.status = RequestStatus.QUEUED
            req.t_first_token = req.first_launch = None
            req.t_admit = None
            req.t_requeue = now
            req.t_emissions = []
            req.not_before = now + float(
                getattr(self.config, "retry_backoff_s", 0.05)
            ) * (2 ** (req.retries - 1))
            req.detail = f"retry {req.retries}/{retry_max}: {why}"
            self._c_retries.inc()
            self._backoff_pending = True
            if self.tracer is not None:
                self.tracer.event(
                    req, "retry", now, cause=why, retries=req.retries,
                    not_before=req.not_before,
                )
            self.queue.append(req)
            self._g_queue.set(len(self.queue))
        else:
            req.status = RequestStatus.FAILED
            req.detail = why if req.retries == 0 else (
                f"{why} (retry budget {retry_max} spent)"
            )
            req.t_finish = now
            self._c_requests.inc(status=RequestStatus.FAILED)
            self._req_terminal(req, now)
            self.completed.append(req)

    def drain(self, deadline_s: Optional[float] = None) -> dict:
        """Graceful shutdown (ISSUE 7): stop admission, let in-flight
        requests finish inside the deadline (``serving.drain_deadline_s``
        default), then evict whatever remains as PREEMPTED — every slot
        empty and every KV page back on the free list when this returns
        (asserted via :meth:`check_no_leaks`). Queued requests that never
        reached a slot are preempted immediately: starting new work inside
        a shutdown window is how drains overrun.

        Idempotent and terminal for this engine instance — ``submit`` after
        ``drain`` rejects with "engine draining"."""
        self._draining = True
        # the step in flight is computed: read it, so that no token of it is
        # lost and the slots it finishes count as finished
        self.settle()
        start = self.clock()
        deadline = start + float(
            self.config.drain_deadline_s if deadline_s is None else deadline_s
        )
        preempted = 0
        while self.queue:
            req = self.queue.popleft()
            req.status = RequestStatus.PREEMPTED
            req.detail = "drained before admission"
            req.t_finish = start
            self._c_requests.inc(status=RequestStatus.PREEMPTED)
            self._c_drained.inc()
            self._req_terminal(req, start)
            self.completed.append(req)
            preempted += 1
        finished = 0
        while any(s.request is not None for s in self.slots) and self.clock() < deadline:
            before = {id(s.request) for s in self.slots if s.request is not None}
            self.step()
            finished += sum(
                1 for x in before
                if x not in {id(s.request) for s in self.slots if s.request is not None}
            )
        self.settle()   # what the last step launched ahead
        now = self.clock()
        deadline_hit = False
        for i, s in enumerate(self.slots):
            if s.request is not None:
                deadline_hit = True
                self._c_drained.inc()
                self._finish_slot(i, RequestStatus.PREEMPTED, "drained at deadline", now)
                preempted += 1
        self._g_queue.set(0)
        self._g_util.set(0.0)
        self._g_pages.set(self.allocator.pages_in_use)
        if self.tiering is not None:
            # land every in-flight spill before callers audit the tiers
            self.tiering.flush()
        if self.tracer is not None:
            # every request is terminal now — make the records durable
            self.tracer.flush()
        log_dist(
            f"serving drain complete in {now - start:.3f}s: "
            f"{finished} finished in-flight, {preempted} preempted"
        )
        return {
            "duration_s": now - start,
            "finished_in_flight": finished,
            "preempted": preempted,
            "deadline_hit": deadline_hit,
        }

    def run(self, max_steps: Optional[int] = None) -> List[Request]:
        """Drive :meth:`step` until queue and slots drain; returns every
        request completed during the run (in completion order). ``max_steps``
        bounds the loop; the default budget covers the worst case, so hitting
        it means a scheduler bug — raise rather than wedge."""
        if max_steps is None:
            budget = sum(
                r.max_new_tokens for r in self.queue
            ) + sum(
                s.request.max_new_tokens for s in self.slots if s.request is not None
            )
            n_reqs = len(self.queue) + sum(
                1 for s in self.slots if s.request is not None
            )
            # chunked prefill consumes steps without emitting tokens
            chunks_per_req = (
                -(-self.prefill_width // self.chunk_width)
                if self.chunk_width else 0
            )
            max_steps = 2 * budget + n_reqs * chunks_per_req + len(self.queue) + 16
        start = len(self.completed)
        for _ in range(max_steps):
            if not self.queue and all(s.request is None for s in self.slots):
                break
            self.step()
        else:
            raise RuntimeError(
                f"ServingEngine.run: no drain within {max_steps} steps "
                f"(queue={len(self.queue)}, "
                f"active={sum(1 for s in self.slots if s.request)})"
            )
        self.settle()   # rows launched ahead for a slot that a late stop ended
        return self.completed[start:]

    # ------------------------------------------------------------------
    # ISSUE 18: live session migration (fleet replica -> peer replica)
    # ------------------------------------------------------------------
    def _ensure_migration_programs(self) -> None:
        """Compile the full-row migration transport pair on first use:
        ``serving_kv_gather`` packs a slot's whole page row out of the
        decode pool ([L, pages_per_slot, KV, page, D] per pool, int8
        scales ride along); ``serving_kv_scatter`` writes a packed row
        into the DESTINATION engine's decode pool (pools donated). Page-id
        lists are scratch-padded to the static ``pages_per_slot`` width —
        pad entries all target scratch page 0, which no live slot reads —
        so each side compiles exactly once per engine."""
        if self._migrate_gather_exec is not None:
            return
        self._refuse_unhandled("session migration")
        self._ensure_compiled()
        dp, dset = self.decode_placement, self.decode_set
        W = self.pages_per_slot
        ids_sds = jax.ShapeDtypeStruct((W,), jnp.int32)
        # gather: decode pools READ, not donated — the source row stays
        # live until the peer's adoption is validated (crc), so a corrupt
        # payload never costs the conversation more than a requeue
        self._migrate_gather_exec = dset.aot(
            gather_fn, (ids_sds,), (dp.rep_spec(),), dset.packed_specs(),
            returns_cache=False, donate=False,
        )
        self._migrate_scatter_exec = dset.aot(
            scatter_fn, dset.packed_sds(W) + (ids_sds,),
            dset.packed_specs() + (dp.rep_spec(),),
        )

    def export_session(self, slot_i: int):
        """Serialize slot ``slot_i``'s live decode session for migration
        (ISSUE 18): ``(client_state, arrays)`` — the JSON-able request +
        slot state, and the KV page row (+ sampling keys) as host numpy,
        gathered through ``serving_kv_gather``. The caller wraps both in
        the PR-7 crc-checked manifest, transfers, and the peer rebuilds the
        slot with :meth:`adopt_session`. The slot itself is untouched —
        pair with :meth:`release_slot` once the payload is written."""
        self.settle()   # the session that moves holds every token computed for it
        slot = self.slots[slot_i]
        req = slot.request
        if req is None:
            raise ValueError(f"slot {slot_i} is empty")
        if slot.prefilling or slot.pending_tok is not None:
            raise ValueError(
                f"slot {slot_i} is still prefilling — nothing emitted yet; "
                "requeue it instead of migrating"
            )
        self._ensure_migration_programs()
        n = len(slot.pages)
        ids = np.zeros((self.pages_per_slot,), np.int32)
        ids[:n] = np.asarray(slot.pages, np.int32)
        dset = self.decode_set
        packed = self._migrate_gather_exec(dset.cache, ids)
        packed_np = [np.asarray(x) for x in jax.device_get(packed)]  # dslint: disable=host-sync-in-step
        arrays = {"k_pages": packed_np[0], "v_pages": packed_np[1]}
        if self.quantized:
            arrays["kv_scales"] = packed_np[2]
        if slot.keys is not None:
            arrays["keys"] = np.asarray(slot.keys)
        state = {
            "kind": "migration",
            "id": int(req.id),
            "prompt": [int(t) for t in req.prompt_list],
            "tokens": [int(t) for t in req.tokens],
            "seed": int(req.seed),
            "max_new_tokens": int(req.max_new_tokens),
            "requested_new_tokens": req.requested_new_tokens,
            "eos_token_id": req.eos_token_id,
            "deadline_s": req.deadline_s,
            "retries": int(req.retries),
            "tenant": req.tenant,
            "slo_class": req.slo_class,
            "prefix_shared_tokens": int(req.prefix_shared_tokens),
            "cow_forked": bool(req.cow_forked),
            "t_submit": req.t_submit,
            "t_admit": req.t_admit,
            "t_requeue": req.t_requeue,
            "t_first_token": req.t_first_token,
            "t_emissions": [float(t) for t in req.t_emissions],
            "pos": int(slot.pos),
            "step": int(slot.step),
            "n_pages": n,
            "last_token": int(self.table.tokens[slot_i]),
        }
        return state, arrays

    def release_slot(self, slot_i: int, now: Optional[float] = None):
        """Free a migrated-out session's slot WITHOUT terminal accounting
        (ISSUE 18): pages back to the allocator(s), table row cleared, the
        request handed back to the caller still RUNNING — it finishes on
        the peer replica. The source can never emit for this session again
        (its slot is gone), which is the concrete form of the model's
        no-dual-emission invariant. A row of the slot's in the step in
        flight is computed and dropped when that step is read: the request
        leaves with the tokens it has, and no token is emitted here after."""
        slot = self.slots[slot_i]
        req = slot.request
        if req is None:
            raise ValueError(f"slot {slot_i} is empty")
        self._vacate(slot_i, self.clock() if now is None else now)
        return req

    def adopt_session(self, state: dict, arrays: dict, request=None):
        """Rebuild a migrated decode session from a validated payload
        (ISSUE 18): allocate a private page row, scatter the KV through
        ``serving_kv_scatter``, and resume decoding exactly where the
        source stopped — greedy/speculative streams continue BIT-identical
        (the drafter index rebuilds deterministically from prompt+tokens;
        sampling keys ride the payload). Returns the live request, or
        ``None`` when this engine cannot host it (no free slot / pages) —
        the router requeues elsewhere. ``request`` re-binds the original
        in-process handle; omitted, the request is rebuilt from
        ``client_state`` (the cross-process path)."""
        if self._draining:
            return None
        slot_i = next(
            (i for i, s in enumerate(self.slots) if s.request is None), None
        )
        if slot_i is None:
            return None
        n = int(state["n_pages"])
        if n > self.pages_per_slot:
            raise ValueError(
                f"migration payload needs {n} pages/slot, this engine "
                f"holds {self.pages_per_slot}"
            )
        self._ensure_migration_programs()
        if n > self.allocator.free_pages and self.prefix_cache is not None \
                and not self.disaggregated:
            self.prefix_cache.evict(need_free=n)
            self._g_index_pages.set(len(self.prefix_cache))
        try:
            pages = self.allocator.alloc(n)
        except PageAllocatorError:
            return None
        if request is not None:
            req = request
            if int(req.id) != int(state["id"]):
                self.allocator.free(pages)
                raise ValueError(
                    f"migration payload id {state['id']} does not match "
                    f"request {req.id}"
                )
        else:
            req = Request(
                prompt=np.asarray(state["prompt"], np.int32),
                max_new_tokens=int(state["max_new_tokens"]),
                seed=int(state["seed"]),
                eos_token_id=state["eos_token_id"],
                deadline_s=state["deadline_s"],
                tenant=state["tenant"],
                slo_class=state["slo_class"],
            )
            req.id = int(state["id"])
            req.requested_new_tokens = state["requested_new_tokens"]
            req.retries = int(state["retries"])
            req.prefix_shared_tokens = int(state["prefix_shared_tokens"])
            req.cow_forked = bool(state["cow_forked"])
            req.t_submit = state["t_submit"]
            req.t_admit = state["t_admit"]
            req.t_requeue = state["t_requeue"]
            req.t_first_token = state["t_first_token"]
        req.tokens = [int(t) for t in state["tokens"]]
        req.t_emissions = [float(t) for t in state["t_emissions"]]
        req.status = RequestStatus.RUNNING
        # the incremental n-gram drafter index rebuilds deterministically
        # from prompt + tokens on the first _draft() here
        object.__setattr__(req, "_draft_state", None)
        dst = np.zeros((self.pages_per_slot,), np.int32)
        dst[:n] = np.asarray(pages, np.int32)
        dset = self.decode_set
        args = [arrays["k_pages"], arrays["v_pages"]]
        if self.quantized:
            args.append(arrays["kv_scales"])
        (dset.cache,) = self._migrate_scatter_exec(dset.cache, *args, dst)
        slot = self.slots[slot_i]
        slot.request = req
        slot.pages = list(pages)
        slot.pos = int(state["pos"])
        # the source read its step in flight before the export: the
        # dispatched side of the session stands where its emitted side does
        slot.step = slot.sent = int(state["step"])
        slot.prefilling = False
        keys = arrays.get("keys")
        if keys is not None:
            slot.keys = np.asarray(keys)
        self.table.assign(slot_i, slot.pages)
        self.table.seq_lens[slot_i] = slot.pos
        self.table.tokens[slot_i] = int(state["last_token"])
        if slot.keys is not None and slot.step < len(slot.keys):
            self.table.keys[slot_i] = slot.keys[slot.step]
        return req

    def takeover_queue(self) -> List[Request]:
        """Hand the whole waiting queue to the caller (ISSUE 18): the
        router reroutes a draining replica's backlog to peers instead of
        preempting it. The requests stay QUEUED; this engine forgets them."""
        out = list(self.queue)
        self.queue.clear()
        self._g_queue.set(0)
        return out

    def adopt_request(self, req: Request) -> bool:
        """Enqueue a request rerouted from a peer replica (ISSUE 18).
        Validation already ran at the original submit (identical configs
        across a fleet); only the live gates apply here. False = this
        engine cannot take it (draining / queue full)."""
        if self._draining:
            return False
        if len(self.queue) >= int(self.config.max_queue_depth):
            return False
        self.queue.append(req)
        self._g_queue.set(len(self.queue))
        return True

    def slo_snapshot(self) -> dict:
        """Cheap PR-11 goodput/attainment snapshot for fleet routing and
        backpressure (ISSUE 18) — the gauges' source numbers without the
        full ``stats()`` quantile sweep."""
        met = sum(c[0] for c in self._slo_counts.values())
        evaluated = sum(c[1] for c in self._slo_counts.values())
        now = self.clock()
        span = (
            now - self._t_first_submit
            if self._t_first_submit is not None else 0.0
        )
        windowed, cumulative = self._goodput_now(now)
        return {
            "good_tokens": int(self._slo_good_tokens),
            "met": int(met),
            "evaluated": int(evaluated),
            "attainment": (met / evaluated) if evaluated else None,
            # windowed when goodput_window_s is set (ISSUE 20) — fleet
            # routing then reacts to the recent past, not the whole run
            "goodput_tokens_per_sec": windowed,
            "goodput_cumulative_tokens_per_sec": cumulative,
            "span_s": span,
        }

    # ------------------------------------------------------------------
    def executable_names(self) -> List[tuple]:
        """→ [(name, compiled)] for the engine's program set (compiling on
        first use). The names key the dsmem budget ledger and the analysis
        reports; int8 pools suffix them ``_int8`` so the quantized programs
        carry their OWN (lower) budget pins — the halved pool is the point,
        and sharing the full-precision pins would let a lost quantization
        regress silently inside the old headroom. TP placements suffix
        further (``_tp2``): a sharded program's per-device peak is a
        different artifact, with its own pin (ISSUE 14)."""
        self._ensure_compiled()
        return [(name, rec["exe"]) for name, rec in self._program_info.items()]

    def verify(self, analysis_config=None) -> list:
        """Full analysis-plane verification of the serving program set.

        Engine F FIRST and PRE-compile (ISSUE 14): each placement's
        sharding-spec table is checked against the real param tree and the
        placement's mesh axes — a broken table (dead regex, rank mismatch,
        large replicated leaf) returns findings before any ``shard_map``
        traces with it. Then Engine A per program: EXACTLY
        ``analysis.max_serving_programs`` executables (``static-shapes``;
        0 = auto — :attr:`expected_executables`), the KV pools donated AND
        actually aliased input→output with their per-DEVICE shapes
        (``donation-honored`` — at tp>1 the HLO is the local program), no
        fp32 upcasts (``no-fp32-upcast``); the handoff gather is the one
        deliberate exception (its source pool must stay live for the
        prefix index). Engine D checks the cross-program collective order;
        Engine E the per-device HBM peaks against the ledger. Engine G
        (ISSUE 15) closes the pass: the page-ownership dataflow lint over
        the serving sources plus the bounded protocol model checker in this
        engine's mode (shared vs disaggregated), whose violations carry
        minimal counterexample traces. Returns findings; empty = clean."""
        from ..runtime.config import AnalysisConfig
        from .. import analysis as dsa

        acfg = analysis_config or AnalysisConfig()
        if isinstance(acfg, dict):
            acfg = AnalysisConfig.from_dict(acfg)
        if not acfg.enabled:
            return []

        # Engine F (ISSUE 14 satellite): pre-compile sharding-spec gate.
        # An explicit analysis.sharding.rules table overrides the committed
        # GPT2_SERVING_RULES for the check; tp=1 placements with no
        # explicit table carry no mesh to shard and are skipped (the
        # committed table is inert there, exactly as before ISSUE 14).
        findings: list = []
        scfg = getattr(acfg, "sharding", None)
        if scfg is not None and getattr(scfg, "enabled", True):
            from ..analysis import sharding_rules as dsspec

            cfg_rules = dsspec.rules_from_config(scfg)
            placements = [self.decode_placement]
            if self.prefill_placement is not self.decode_placement:
                placements.append(self.prefill_placement)
            for plc in placements:
                if plc.tp == 1 and not cfg_rules:
                    continue
                fctx = dsspec.ShardingRuleContext(
                    program=f"serving_params_{plc.name}{plc.suffix()}",
                    mesh_axes=plc.mesh_axes,
                    replicated_min_bytes=scfg.replicated_min_bytes,
                )
                findings.extend(dsspec.verify_spec_table(
                    cfg_rules if cfg_rules else plc.rules,
                    self.engine.params, fctx,
                ))
            if findings:
                # fail BEFORE compile: shard_map must never trace a table
                # Engine F rejects
                return findings

        self._ensure_compiled()
        pool_dt = dsa.hlo_dtype(np.dtype(self.cache_dtype))
        expected_dtype = pool_dt if pool_dt in ("bf16", "f16") else None
        ctx = dsa.RuleContext(program="serving")
        budget = int(getattr(acfg, "max_serving_programs", 0) or 0)
        findings.extend(dsa.check_program_budget(
            len(self.executables), budget or self.expected_executables,
            ctx, exact=True,
        ))
        texts = {}
        for name, rec in self._program_info.items():
            pset, kind = rec["pset"], rec["kind"]
            texts[name] = rec["exe"].as_text()
            if kind == "gather":
                # gather READS the prefill pool (pages stay live for the
                # prefix index) — demanding aliasing here would be wrong
                expect_aliased = []
            else:
                # both pools share one per-device shape: demand two aliased
                # params; int8 pools additionally demand the donated scales
                # pool aliased (a copied scales buffer is small, but an
                # unaliased donation means XLA round-trips it every step)
                expect_aliased = [(pool_dt, pset.local_pool_dims())] * 2
                if self.quantized:
                    expect_aliased.append(("f32", pset.local_scales_dims()))
            pctx = dsa.RuleContext(
                program=name,
                expect_aliased_shapes=expect_aliased,
                expected_dtype=expected_dtype,
                upcast_allow=acfg.upcast_allow,
                allgather_min_bytes=acfg.allgather_min_bytes,
            )
            findings.extend(dsa.verify_hlo_text(texts[name], pctx))
        # Engine D (ISSUE 8): every executable runs on one engine — channel
        # uniqueness + start/done pairing per program, and (ROADMAP item 2,
        # landed: ISSUE 14) the TP-sharded prefill/decode pair must agree
        # on per-group collective order or concurrent slots desync
        findings.extend(dsa.verify_program_set(texts))
        # Engine E (ISSUE 9): static HBM liveness per executable against
        # the committed budgets — the KV page pool is the dominant
        # consumer, so a doubled pool or a lost donation fails the gate
        # here before it OOMs under load. At tp>1 the dims fed to the
        # categorizer are the per-DEVICE pool/packed shapes — the peaks
        # (and their ``_tp2`` ledger pins) are per-device quantities.
        # check_donation=False: serving weights are shared across every
        # call by design (only the pools are donated, already aliased).
        mcfg = getattr(acfg, "memory", None)
        if mcfg is not None and getattr(mcfg, "enabled", True):
            from ..analysis import memory_rules as dsmem

            self._memory_analyses = {}
            self._memory_cfg = mcfg
            for name, rec in self._program_info.items():
                pset, kind = rec["pset"], rec["kind"]
                kv_dims = list(pset.kv_pool_dims())
                scl = (pset.local_scales_dims(),) if self.quantized else ()
                if kind in ("gather", "scatter"):
                    kv_dims.append(pset.packed_dims(self.prefill_pages))
                    if self.quantized:
                        scl = scl + (
                            pset.packed_scales_dims(self.prefill_pages),
                        )
                ectx = dsmem.context_from_config(
                    mcfg, name,
                    check_donation=False,
                    kv_pool_dims=tuple(kv_dims),
                    metadata_dims=self._metadata_dims(),
                    scales_dims=scl,
                )
                mem_findings, ana = dsmem.verify_memory_text(
                    texts[name], ectx
                )
                findings.extend(mem_findings)
                self._memory_analyses[name] = ana
        # Engine G (ISSUE 15): the serving-protocol plane. The ownership
        # lint re-audits the serving sources this engine is running, and
        # the bounded model checker explores the abstract protocol in THIS
        # engine's mode (shared vs disaggregated page pools) — a violation
        # carries a minimal counterexample trace replayable via
        # analysis.protocol_model.replay_trace.
        pcfg = getattr(acfg, "protocol", None)
        if pcfg is not None and getattr(pcfg, "enabled", True):
            import os as _os

            from ..analysis import protocol_model as dsproto
            from ..analysis import protocol_rules as dsprot

            if getattr(pcfg, "lint", True):
                serving_dir = _os.path.dirname(_os.path.abspath(__file__))
                for fname in sorted(_os.listdir(serving_dir)):
                    if fname.endswith(".py"):
                        got, _w = dsprot.check_file(
                            _os.path.join(serving_dir, fname)
                        )
                        findings.extend(got)
            if getattr(pcfg, "model", True):
                mcfg = dsproto.ProtoModelConfig(
                    requests=int(getattr(pcfg, "requests", 2)),
                    slots=min(self.max_slots,
                              int(getattr(pcfg, "requests", 2))),
                    prompt_pages=int(getattr(pcfg, "prompt_pages", 2)),
                    new_tokens=int(getattr(pcfg, "new_tokens", 2)),
                    disaggregated=self.disaggregated,
                    prefix_cache=self.prefix_cache is not None,
                    retry_max=int(getattr(pcfg, "retry_max", 1)),
                    max_states=int(getattr(pcfg, "max_states", 200_000)),
                    tiering=self.tiering is not None,
                    host_budget=min(
                        self.tiering.store.budget_pages, 2
                    ) if self.tiering is not None else 1,
                )
                findings.extend(
                    dsproto.model_findings(dsproto.explore(mcfg))
                )
        return findings

    def _metadata_dims(self) -> tuple:
        """HLO dim strings of the serving control-plane buffers (block
        tables, draft-token batches, chunk page maps) so Engine E's ledger
        labels them ``metadata`` instead of ``temp`` — they are the device
        shadow of the host-side refcount/prefix-index state."""
        dims = {
            f"{self.max_slots},{self.pages_per_slot}",  # block tables
            f"1,{self.pages_per_slot}",                 # chunk table row
            f"{self.prefill_pages}",                    # prefill page ids
        }
        if self.chunk_width:
            dims.add(f"{self.chunk_width // self.page_size}")  # chunk pages
        if self.spec_enabled:
            dims.add(f"{self.max_slots},{self.spec_k + 1}")    # draft batch
        return tuple(sorted(dims))

    def memory_report(self) -> dict:
        """The dsmem (Engine E) profile of both serving executables: peak
        HBM, budget + headroom, KV page-pool bytes. Compiles + verifies on
        first use."""
        if not getattr(self, "_memory_analyses", None):
            self.verify()
        from ..analysis import memory_rules as dsmem
        from ..runtime.config import AnalysisConfig

        mcfg = getattr(self, "_memory_cfg", None) or AnalysisConfig().memory
        host_meta = (
            self.prefix_cache.host_metadata_bytes()
            if self.prefix_cache is not None else 0
        )
        scl_bytes = (
            scales_bytes(self.decode_set.n_layer, int(self.config.num_pages),
                         self.decode_set.n_kv_head)
            if self.quantized else 0
        )
        # ISSUE 16 satellite: the full host-RSS metadata ledger (prefix
        # index + drafter indexes + heat ledgers), budgeted beside HBM
        host_breakdown = self.host_metadata_breakdown()
        out = {}
        for name, ana in (self._memory_analyses or {}).items():
            budget = dsmem.resolve_budget(mcfg, name)
            rec = ana.to_dict()
            rec["budget_bytes"] = budget
            rec["headroom_pct"] = dsmem.headroom_pct(budget, ana.peak_bytes)
            rec["kv_pool_bytes"] = ana.by_category.get("kv-pool", 0)
            # device control-plane buffers (block tables / draft batches)
            # plus the host-side refcount & prefix-index footprint they
            # shadow (ISSUE 10)
            rec["metadata_bytes"] = ana.by_category.get("metadata", 0)
            rec["host_metadata_bytes"] = host_meta
            rec["host_metadata"] = dict(host_breakdown)
            # int8 pools (ISSUE 12): quantized payload + scales reported
            # SEPARATELY — the pool entry is codes only, the scales live
            # under metadata (where Engine E categorizes them)
            rec["kv_cache_dtype"] = np.dtype(self.cache_dtype).name
            rec["kv_scales_bytes"] = scl_bytes
            out[name] = rec
        return out

    def stats(self) -> dict:
        """p50/p95/p99 + mean/count summaries of TTFT, TPOT and decode-step
        latency, estimated from the existing histograms (the same
        ``histogram_quantile`` interpolation Prometheus applies), plus
        current load. Also refreshes the
        ``serving_latency_quantile_seconds{metric,q}`` gauges so the
        telemetry textfile export carries the summaries."""
        out: dict = {}
        for name, hist in (
            ("ttft", self._h_ttft), ("tpot", self._h_tpot),
            ("decode_step", self._h_step), ("queue_wait", self._h_qwait),
        ):
            total, n = hist.stats()
            entry = {"count": n, "mean_s": (total / n) if n else None}
            for q, label in ((0.5, "p50"), (0.95, "p95"), (0.99, "p99")):
                v = hist.quantile(q)
                entry[f"{label}_s"] = v
                if v is not None:
                    self._g_quant.set(v, metric=name, q=label)
            out[name] = entry
        out["queue_depth"] = len(self.queue)
        out["active_slots"] = sum(1 for s in self.slots if s.request is not None)
        out["kv_pages_in_use"] = self.allocator.pages_in_use
        out["completed"] = len(self.completed)
        out["decode_steps"] = self._step_count
        out["stragglers"] = int(self._c_stragglers.value())
        out["drained"] = int(self._c_drained.value())
        out["retried"] = int(self._c_retries.value())
        out["draining"] = self._draining
        # -- ISSUE 10: sharing / speculation / chunking invariant counters --
        # -- ISSUE 11: per-terminal-status counts + SLO/goodput/tenancy ----
        # engine-local (every terminal path funnels _req_terminal): the
        # tracer ledger and registry counters are telemetry-plane-scoped
        # and would mix engines sharing one plane
        out["by_status"] = dict(self._status_counts)
        now = self.clock()
        if self._slo_enabled and self._t_first_submit is not None:
            windowed, cumulative = self._goodput_now(now)
            self._g_goodput.set(windowed)
            out["slo"] = {
                "goodput_tokens_per_sec": windowed,
                "goodput_cumulative_tokens_per_sec": cumulative,
                "goodput_window_s": self._goodput_window_s,
                "classes": {
                    cls: {
                        "met": met, "evaluated": ev,
                        "attainment": (met / ev) if ev else None,
                    }
                    for cls, (met, ev) in sorted(self._slo_counts.items())
                },
            }
        if self.tenants:
            out["tenants"] = {t: dict(v) for t, v in sorted(self.tenants.items())}
        if self.tracer is not None:
            out["request_trace"] = {
                "path": self.tracer.file_path,
                "records": self.tracer.records_emitted,
                "live": self.tracer.live_requests,
                "rotations": self.tracer.rotations,
                "events_dropped": self.tracer.events_dropped,
                "records_lost": self.tracer.records_lost,
            }
            if self.tracer.encode_error is not None:
                out["request_trace"]["encode_error"] = self.tracer.encode_error
        # ISSUE 16: heat-plane health + the host-metadata budget
        out["host_metadata"] = self.host_metadata_breakdown()
        if self._heat is not None:
            self._heat.refresh_gauges(now)
            out["kv_heat"] = {
                "path": self._heat.file_path,
                "records": self._heat.records_emitted,
                "rotations": self._heat.rotations,
                "records_lost": self._heat.records_lost,
                "ledger_bytes": self._heat.ledger_bytes(),
                "pools": {
                    name: led.occupancy(now, self._heat.idle_thresholds_s)
                    for name, led in self._heat.ledgers.items()
                },
            }
            if self._heat.encode_error is not None:
                out["kv_heat"]["encode_error"] = self._heat.encode_error
        # ISSUE 20: time-series journal health
        if self._journal is not None:
            out["timeseries"] = {
                "path": self._journal.file_path,
                "snapshots": self._journal.snapshots,
                "records": self._journal.records_emitted,
                "rotations": self._journal.rotations,
                "last_t": self._journal.last_t,
            }
            if self._journal.encode_error is not None:
                out["timeseries"]["encode_error"] = self._journal.encode_error
        out["kv_pages_shared"] = self.allocator.pages_shared
        out["kv_cow_forks"] = self.allocator.cow_forks_total
        # ISSUE 12: the pool's storage dtype + its HBM split (codes vs
        # scales) — the ops surface for "how much cache does this engine
        # actually hold per byte"
        ds = self.decode_set
        out["kv_cache_dtype"] = np.dtype(self.cache_dtype).name
        out["kv_pool_bytes"] = pool_bytes(
            ds.n_layer, int(self.config.num_pages), ds.n_kv_head,
            self.page_size, ds.head_dim,
            np.dtype(self.cache_dtype).itemsize, pools=ds.kv_pools,
        )
        out["kv_window_bytes"] = ds.cache_bytes()["window"]
        out["kv_scales_bytes"] = (
            scales_bytes(ds.n_layer, int(self.config.num_pages), ds.n_kv_head)
            if self.quantized else 0
        )
        # ISSUE 14: where the programs run and what each device holds —
        # per-device pool bytes drop 1/tp, the whole point of the axis
        psets = {self.decode_set.placement.name: self.decode_set}
        psets[self.prefill_set.placement.name] = self.prefill_set
        per_device = {name: {k: n // ps.placement.tp for k, n in ps.cache_bytes().items()} for name, ps in psets.items()}
        out["placement"] = {
            "tp": self.tp,
            "disaggregated": self.disaggregated,
            "placements": {
                name: {
                    "tp": ps.placement.tp,
                    "devices": [
                        str(getattr(d, "id", d)) for d in ps.placement.devices
                    ],
                    "num_pages": ps.num_pages,
                    "pages_in_use": ps.allocator.pages_in_use,
                    "per_device_pool_bytes": per_device[name]["pages"],
                    "per_device_scales_bytes": per_device[name]["scales"],
                }
                for name, ps in psets.items()
            },
        }
        if self.disaggregated:
            out["kv_handoffs"] = int(self._c_handoffs.value())
            out["kv_handoff_bytes"] = int(self._c_handoff_bytes.value())
            total, n = self._h_handoff.stats()
            out["kv_handoff_latency_mean_s"] = (total / n) if n else None
        out["chunk_prefills"] = int(self._c_chunks.value())
        # steps launched with the step before in flight, and rows launched
        # ahead for a slot that a late stop ended (computed and dropped)
        out["steps_ahead"] = int(self._c_ahead.value())
        out["rows_dropped"] = int(self._c_dropped.value())
        out["group_rows"] = int(self._c_moe_group_rows.value())
        if self.prefix_cache is not None:
            pc = self.prefix_cache
            lookups = pc.hits_full + pc.hits_partial + pc.misses
            out["prefix_index_pages"] = len(pc)
            out["prefix_hits_full"] = pc.hits_full
            out["prefix_hits_partial"] = pc.hits_partial
            out["prefix_misses"] = pc.misses
            out["prefix_evictions"] = pc.evictions
            out["prefix_hit_rate"] = (
                (pc.hits_full + pc.hits_partial) / lookups if lookups else None
            )
            out["prefix_host_metadata_bytes"] = pc.host_metadata_bytes()
            out["prefix_demotions"] = pc.demotions
            out["prefix_adoptions"] = pc.adoptions
        # ISSUE 17: host-tier sizes + spill/restore traffic
        if self.tiering is not None:
            out["kv_tiering"] = {"enabled": True, **self.tiering.stats()}
        if self.spec_enabled:
            total, n = self._h_accept.stats()
            out["spec_steps"] = int(self._c_spec_steps.value())
            out["spec_drafted"] = int(self._c_spec_drafted.value())
            out["spec_accepted"] = int(self._c_spec_accepted.value())
            out["spec_accept_len_mean"] = (total / n) if n else None
        return out

    def release_prefix_cache(self) -> int:
        """Drop every prefix-index reference (teardown / tests): after this,
        a drained engine's allocator is fully free. → pages released."""
        if self.prefix_cache is None:
            return 0
        n = self.prefix_cache.clear()
        self._g_index_pages.set(len(self.prefix_cache))
        self._g_pages_shared.set(self.allocator.pages_shared)
        return n

    def check_no_leaks(self) -> None:
        """Drain invariant: every page either back on the free list or held
        by EXACTLY the prefix index (refcount 1), every slot empty, every
        block-table entry pointing at scratch. Under disaggregation the
        index lives on the PREFILL allocator; the decode pool must drain
        completely — a page left there means a handoff leaked its
        reservation."""
        self.settle()
        held = self.prefix_cache.held_pages if self.prefix_cache else None
        if self.disaggregated:
            self.prefill_set.allocator.check_no_leaks(allowed=held)
            self.decode_set.allocator.check_no_leaks(allowed=None)
        else:
            self.allocator.check_no_leaks(allowed=held)
        assert all(s.request is None for s in self.slots)
        assert all(not s.prefill_pages for s in self.slots)
        assert (self.table.block_tables == 0).all()
        assert (self.table.seq_lens == 0).all()
        if self.tiering is not None:
            # ISSUE 17: the host tier must be internally consistent, agree
            # with the heat ledger's handle mirror, and never hold a key
            # the device index also holds (exactly-one-tier)
            self.tiering.flush()
            err = self.tiering.check_consistent(self.prefix_cache)
            assert err is None, f"host tier inconsistent at drain: {err}"
