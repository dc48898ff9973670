"""Paged KV cache: a fixed pool of fixed-size pages + a free-list allocator.

The serving-side answer to XLA's static-shape constraint (PAPERS.md
2605.25645): a dense per-request cache ``[B, prompt+new, H, D]`` either
recompiles per length or pads every sequence to the worst case. Here ONE
preallocated HBM pool ``[L, P, H, page, D]`` is carved into pages; each
in-flight sequence owns a list of pages (its *block table* row), so wildly
different lengths share the pool with at most ``page_size - 1`` wasted slots
per sequence — the vLLM PagedAttention idea, expressed with TPU-native
layouts (the page dim sits where Mosaic wants its sublane axis, see
``ops/pallas/decode_attention.paged_decode_attention``).

Page 0 is a permanently-reserved scratch page: inactive slots and the padded
tail of block-table rows point at it, so every compiled gather/scatter index
is valid without masking, and garbage writes land somewhere no active slot
ever reads. The paged kernels take a page's ``[KV, page, D]`` run — all
kv-heads, contiguous in this layout — with one DMA and walk a row only as
far as the slot's own last page (the decode step) or the page the chunk
reaches (the multi-token kernel), so the padded tail costs them nothing;
the jnp fallbacks still gather it and mask.

On a TPU the pool of a head narrower than 128 lanes is STORED with its page
axis split (:func:`pool_stored_shape`), which keeps the device's
default layout row-major; the programs see the ``[L, P, KV, page, D]``
view (:func:`viewed`), and the kernels take that whole view plus a layer index.
Such a pool is padded tiles, twice its values at 64 lanes, and the kernels'
DMAs move the padding (PERF.md, PR 56: the same keys as pairs of heads in 128
lanes cost the one-token kernel 0.58 of the time). Since PR 56 no served
configuration stores one: GPT-2's 64-wide heads reach this module as PAIRS,
``ceil(H / 2)`` kv-heads of 128 lanes (``models/gpt2.GPT2Family``; the
``Family`` notes in ``serving/model.py``), and only an int8 cache of such a
model, which keeps a head and a scale a published head, is still split.
"""

from __future__ import annotations

from collections import OrderedDict
from typing import Any, Callable, Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

SCRATCH_PAGE = 0  # reserved: never allocated, absorbs inactive-slot writes


class PageAllocatorError(RuntimeError):
    pass


class PageAllocator:
    """Refcounted free-list allocator over pages ``1..num_pages-1`` (0 =
    scratch).

    LIFO reuse (a freshly-freed page is the next handed out) keeps the hot
    working set small. ``alloc`` is all-or-nothing and hands out pages at
    refcount 1; ``retain`` adds a reference (a second slot, or the prefix
    index, mapping an existing page — ISSUE 10 shared-prefix reuse);
    ``free`` drops one reference and returns the page to the free list only
    at refcount 0. Double-frees, foreign ids, and retaining a free page all
    raise — the invariants the drain/sharing tests assert.
    """

    def __init__(self, num_pages: int):
        if num_pages < 2:
            raise ValueError(f"num_pages must be >= 2 (page 0 is scratch), got {num_pages}")
        self.num_pages = int(num_pages)
        self._free: List[int] = list(range(self.num_pages - 1, 0, -1))
        self._refs: Dict[int, int] = {}  # page -> refcount (in-use pages only)
        self.cow_forks_total = 0  # bumped by the scheduler's COW path
        # ISSUE 16: optional KVHeatLedger — hooks fire AFTER each mutation
        # (one None check when heat tracing is off)
        self.heat = None

    @property
    def capacity(self) -> int:
        """Allocatable pages (excludes the scratch page)."""
        return self.num_pages - 1

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def pages_in_use(self) -> int:
        return len(self._refs)

    @property
    def pages_shared(self) -> int:
        """In-use pages referenced by more than one holder."""
        return sum(1 for c in self._refs.values() if c > 1)

    def refcount(self, page: int) -> int:
        return self._refs.get(int(page), 0)

    def refcounts(self) -> Dict[int, int]:
        """Snapshot of the full page → refcount table (heat-ledger seeding
        and the lockstep reconcile read it; callers get a copy)."""
        return dict(self._refs)

    def alloc(self, n: int) -> List[int]:
        if n < 0:
            raise ValueError(f"alloc({n})")
        if n > len(self._free):
            raise PageAllocatorError(
                f"KV pool exhausted: need {n} pages, {len(self._free)} free "
                f"of {self.capacity}"
            )
        pages = [self._free.pop() for _ in range(n)]
        for p in pages:
            self._refs[p] = 1
        if self.heat is not None:
            self.heat.alloc(pages)
        return pages

    def retain(self, pages: Sequence[int]) -> None:
        """Add one reference per page (sharing an already-allocated page)."""
        for p in pages:
            p = int(p)
            if p == SCRATCH_PAGE:
                raise PageAllocatorError("cannot retain the scratch page")
            if p not in self._refs:
                raise PageAllocatorError(f"retain of free/foreign page {p}")
        for p in pages:
            self._refs[int(p)] += 1
        if self.heat is not None:
            self.heat.retain(pages)

    def free(self, pages: Sequence[int]) -> None:
        """Drop one reference per page; a page returns to the free list only
        when its LAST holder frees it."""
        for p in pages:
            p = int(p)
            if p == SCRATCH_PAGE:
                raise PageAllocatorError("cannot free the scratch page")
            if p not in self._refs:
                raise PageAllocatorError(f"double free / foreign page {p}")
        for p in pages:
            p = int(p)
            self._refs[p] -= 1
            if self._refs[p] == 0:
                del self._refs[p]
                self._free.append(p)
        if self.heat is not None:
            self.heat.free(pages)

    def check_consistent(self) -> Optional[str]:
        """Validate the allocator's internal accounting (Engine G monitor).

        Returns ``None`` when healthy, else a one-line description of the
        corruption.  Unlike :meth:`check_no_leaks` this holds at ANY point
        in the protocol, not just at quiescence: the free list and the
        refcount table must partition the pool exactly."""
        fset = set(self._free)
        if len(fset) != len(self._free):
            dups = sorted(p for p in fset if self._free.count(p) > 1)
            return f"free list has duplicate pages: {dups[:4]}"
        if SCRATCH_PAGE in fset or SCRATCH_PAGE in self._refs:
            return "scratch page entered the pool"
        overlap = fset & set(self._refs)
        if overlap:
            return f"pages both free and in use: {sorted(overlap)[:4]}"
        bad = sorted(p for p, c in self._refs.items() if c < 1)
        if bad:
            return f"pages with non-positive refcounts: {bad[:4]}"
        if len(fset) + len(self._refs) != self.capacity:
            return (
                f"page conservation violated: {len(fset)} free + "
                f"{len(self._refs)} in use != capacity {self.capacity}"
            )
        oob = sorted(
            p for p in fset | set(self._refs) if not 1 <= p < self.num_pages
        )
        if oob:
            return f"page ids out of range: {oob[:4]}"
        return None

    def check_no_leaks(self, allowed: Optional[Sequence[int]] = None) -> None:
        """Raise unless every in-use page is in ``allowed`` (default: none) —
        and every allowed page holds EXACTLY one reference (the holder that
        declared it, e.g. the prefix index after all slots drained)."""
        err = self.check_consistent()
        if err:
            raise PageAllocatorError(f"allocator state corrupt: {err}")
        allowed_set = {int(p) for p in (allowed or ())}
        leaked = sorted(p for p in self._refs if p not in allowed_set)
        if leaked:
            raise PageAllocatorError(f"leaked pages: {leaked}")
        over = sorted(
            (p, c) for p, c in self._refs.items() if c != 1
        )
        if over:
            raise PageAllocatorError(
                f"pages with nonzero extra refcounts at drain: {over}"
            )


class SlotTable:
    """Host-side view of the per-slot block tables + sequence lengths.

    The np arrays are the EXACT inputs of the compiled decode step — the
    scheduler mutates them in place (admission writes a row, finish clears
    it) and hands them to the executable each step; shapes never change, so
    the step never retraces.
    """

    def __init__(self, max_slots: int, pages_per_slot: int):
        self.max_slots = int(max_slots)
        self.pages_per_slot = int(pages_per_slot)
        self.block_tables = np.full((max_slots, pages_per_slot), SCRATCH_PAGE, np.int32)
        self.seq_lens = np.zeros((max_slots,), np.int32)
        self.tokens = np.zeros((max_slots,), np.int32)
        self.keys = np.zeros((max_slots, 2), np.uint32)
        # a call none of whose rows takes its token from the step before
        self._no_prev = np.zeros((max_slots + 1,), np.int32)
        self._no_src = np.full((max_slots,), -1, np.int32)

    def rows(self, live=None, prev=None, src=None) -> tuple:
        """The decode rows' host operands, in program order, as arrays of
        this call's own: the table moves on at the launch (``seq_lens``, the
        keys), while the program that took them may still be queued.

        ``live``: the slots that get a row; every other row goes idle (the
        scratch table, length 0), as a table nobody assigns to is a step's
        worth of idle rows. ``prev`` and ``src``: where a row's token is
        still on the device, the token output ``[slots + 1]`` of the step
        before and the row's place in it (``-1``: ``tokens`` holds it)."""
        tokens, seq_lens, bt, keys = (
            a.copy() for a in (self.tokens, self.seq_lens, self.block_tables, self.keys)
        )
        if live is not None:
            idle = np.ones((self.max_slots,), bool)
            idle[live] = False
            seq_lens[idle] = 0
            bt[idle] = SCRATCH_PAGE
        return (
            tokens, seq_lens, bt, keys,
            self._no_prev if prev is None else prev,
            self._no_src if src is None else src,
        )

    def assign(self, slot: int, pages: List[int]) -> None:
        if len(pages) > self.pages_per_slot:
            raise ValueError(
                f"slot {slot}: {len(pages)} pages > table width {self.pages_per_slot}"
            )
        row = self.block_tables[slot]
        row[:] = SCRATCH_PAGE
        row[: len(pages)] = pages

    def clear(self, slot: int) -> None:
        self.block_tables[slot, :] = SCRATCH_PAGE
        self.seq_lens[slot] = 0
        self.tokens[slot] = 0
        self.keys[slot, :] = 0


def pages_for(tokens: int, page_size: int) -> int:
    """Pages needed to hold ``tokens`` cache entries."""
    return -(-int(tokens) // int(page_size))


PAGE_GROUP = 64  # the longest a stored page axis may be (see pool_stored_shape)


class PoolLayoutError(RuntimeError):
    """A KV pool is not, or a compiled program does not keep it, in the
    layout the paged kernels read: raised at set-up, never inside a run."""


def _page_axes(num_pages: int) -> tuple:
    """``num_pages`` as a product of axes no longer than ``PAGE_GROUP``,
    largest divisors last; a factor that has no such divisor stays whole."""
    axes = []
    while num_pages > PAGE_GROUP:
        g = max(g for g in range(1, PAGE_GROUP + 1) if num_pages % g == 0)
        if g == 1:
            break
        axes.append(g)
        num_pages //= g
    return (num_pages, *reversed(axes))


def pool_stored_shape(n_layer: int, num_pages: int, n_kv_head: int,
                      page_size: int, head_dim: int, dtype: Any,
                      latent: bool = False) -> tuple:
    """The shape the K and V pools are STORED with on the device:
    ``[L, P, KV, page, D]``, or, where the paged kernels run (a TPU, a page
    shape they take) on a head that does not fill the 128 lanes, the same
    elements in the same order with the page axis split into axes of at
    most ``PAGE_GROUP``, ``[L, P // g, g, KV, page, D]`` (512 pages:
    ``[L, 8, 64, ...]``; 8192: ``[L, 2, 64, 64, ...]``). Every program sees
    the 5-D view (:func:`viewed`); only ``placement.ProgramSet.aot`` handles
    the stored shape.

    Why. The TPU's default layout of an array whose minor dimension does not
    fill the lanes puts an axis LONGER than that dimension in its place:
    ``bf16[L, 512, KV, 16, 64]`` gets the PAGE index minor-most (it saves
    padding 64 lanes to 128), where the paged kernels' page blocks and the
    whole-page writes work row-major, and every program then re-lays a
    layer, or both whole pools, out around each kernel call. With no axis
    longer than the head the default layout is row-major, the 5-D view of it
    is a bitcast, and nothing between two kernels changes it. (Compiled for
    a described v5e, ``tests/unit/ops/test_mosaic_compile.py``: at D = 64 an
    axis of 65 to 8192 is moved, whichever it is, L and KV included, and
    none of 64 or less; a head of 128 or 256 is row-major at any length.)
    That is the compiler's choice and not a contract, so
    ``ProgramSet`` checks what it got and raises :class:`PoolLayoutError`
    where a pool came out otherwise (a ``num_pages`` with a prime factor
    over ``PAGE_GROUP``, more than 64 layers or kv-heads at D = 64). The
    bytes are the padded tiles (twice the values at D = 64; a 128-wide head
    is left alone and pads nothing). The layout is not pinned with
    ``jax.experimental.layout`` instead, because this libtpu's executables
    lose a custom result layout when they are loaded from the compilation
    cache, so that every warm run fails; nor is the head stored 128 wide,
    because every reader of a page, on the device and on the host, would
    then carry the padding (PERF.md section 6, PR 29).

    A third case, a LATENT pool (``latent``: one row a token, ``head_dim``
    wide, that is neither 64 nor whole lane tiles: 320). Its rows are kept
    padded to whole 128-lane tiles, ``[L, P, 1, page, 384]``, where the latent
    kernels run, and that IS the programs' view: they write the row with
    zeros behind it and pad the query with zeros. Why not 320: compiled for
    the described v5e, ``bf16[6, P, 1, page, 320]`` comes out with the page
    INDEX minor-most at page 16 and the page's ROW axis minor-most at page
    128 (either saves the padding), so no split of the page axis helps once
    the page itself is a lane tile long; row-major at 320 the tiles would
    pad every row to 384 lanes anyway, so the bytes are the same 768 a row,
    and three whole lane tiles are what the kernels' products contract over.
    A minor dimension of whole tiles is row-major at any length, so
    :class:`PoolLayoutError` has only that to check. Off the TPU the row
    stays ``head_dim`` wide."""
    from ..ops.pallas.decode_attention import paged_page_ok

    shape = (n_layer, num_pages, n_kv_head, page_size, head_dim)
    if latent:
        from ..ops.pallas.latent_attention import latent_attention_ok

        lanes = -(-head_dim // 128) * 128
        if latent_attention_ok(page_size, lanes, jnp.dtype(dtype).itemsize):
            return (*shape[:4], lanes)
        return shape
    if head_dim % 128 == 0 or not paged_page_ok(
        page_size, head_dim, jnp.dtype(dtype).itemsize
    ):
        return shape
    return (n_layer, *_page_axes(num_pages), *shape[2:])


class Cache(NamedTuple):
    """The served cache of one placement, as ONE value (docs/SERVING.md, "The
    cache"): ``placement.ProgramSet`` owns it, every served program takes it as
    one donated argument and gives it back as its first result. A field a
    family has no use for is ``None``, which a jitted program flattens into
    nothing: the buffers a program takes and returns are the fields that are
    there, in this order."""

    k: Any                # the paged K pool [L, P, KV, page, D]; a latent family's ONE pool [L, P, 1, page, W]
    v: Any = None         # the paged V pool; None: a latent family
    scales: Any = None    # [L, P, KV, 2] float32, an int8 cache's scale a page and head (K, V)
    win_k: Any = None     # a window layer's K ring [Lw, 1 + slots * ring, KV, page, D]
    win_v: Any = None
    rec: Any = None       # a recurrent sub-block's state a slot, float32: [Ls, slots, N, d_inner] or [Ll, slots, Hv, dk, dv]
    conv: Any = None      # its convolution's last K - 1 input rows [Ls, slots, K - 1, channels]
    carry: Any = None     # the rows an attention carries from call to call [La, slots, carry_width]

    @property
    def latent(self) -> bool:
        return self.v is None

    @property
    def per_slot(self) -> bool:
        """Whether a slot owns a part of it outright (a ring, a recurrent
        state, carried rows): the prefill and chunk programs then take the
        slot as their last host operand."""
        return any(x is not None for x in (self.win_k, self.rec, self.carry))


PAGED_FIELDS = ("k", "v", "win_k", "win_v")   # the pools of pages: [L, P, KV, page, D], the page axis maybe stored split


def viewed(cache: Cache) -> Cache:
    """``cache`` with every pool of pages as the ``[L, P, KV, page, D]`` view
    the programs work on, from its :func:`pool_stored_shape` (a bitcast, or
    the pool itself)."""
    return cache._replace(**{
        f: x.reshape(x.shape[0], -1, *x.shape[-3:])
        for f in PAGED_FIELDS if (x := getattr(cache, f)) is not None
    })


def stored_as(cache: Cache, stored: Cache) -> Cache:
    """:func:`viewed`'s inverse: ``cache`` with its pools of pages in the
    shapes ``stored`` has them in."""
    return cache._replace(**{
        f: x.reshape(getattr(stored, f).shape)
        for f in PAGED_FIELDS if (x := getattr(cache, f)) is not None
    })


# ---------------------------------------------------------------------------
# the kinds of per-slot state a family may hold besides paged per-head pages
# ---------------------------------------------------------------------------

MECHANISMS = (
    "serving.prefix_cache", "serving.tiering", "serving.kv_cache_dtype=int8",
    "serving.placement.tp > 1", "serving.placement.disaggregate",
    "serving.speculative", "session migration",
)
# What moves, shares, shards or re-codes pages knows K and V pools of per-head
# pages only, so none of it handles another kind. A draft is another matter:
# a rejected draft's rows are overwritten where they lie in pages and rings,
# so only a state that cannot be rolled back refuses one.
_NONE_HANDLES = frozenset(MECHANISMS)
_ROLLED_BACK = _NONE_HANDLES - {"serving.speculative"}


def _recurrent(fam) -> bool:
    return bool({"ssm", "lin"} & set(getattr(fam, "kinds", None) or ()))


def _state_what(fam) -> str:
    """What a recurrent state is, for the refusals: its bytes a slot and
    sub-block differ by 6x between the two."""
    if "lin" in (getattr(fam, "kinds", None) or ()):
        return ("a linear-attention layer's matrix state a value head "
                f"({4 * int(np.prod(fam.lin_state)) / 1e6:.1f} MB a slot and layer)")
    return "a state-space layer's scan state"


class StateKind(NamedTuple):
    holds: Callable[[Any], bool]   # whether a family holds it
    unhandled: frozenset           # the mechanisms that do not handle it
    admission: str                 # why, where the engine is built ({name}: the model's; {state}: _state_what)
    migration: str                 # why, where a session would move


# in the order the refusals name them
STATE_KINDS = (
    # a fixed-size recurrent state a slot (a state-space mixer's, a linear
    # attention's matrix a value head): no page, cannot be cut at a prefix
    StateKind(
        _recurrent, _NONE_HANDLES,
        "recurrent state ({name}): {state} and convolution rows live in a "
        "per-slot pool beside the paged pool, which this mechanism does not handle",
        "recurrent state: the transport moves a slot's paged row, and "
        "{state} and convolution rows would stay behind",
    ),
    # under paged K and V, the rows before a call's first
    # (serving/model._qkv_carried): a cached prefix's pages without the rows
    # at its end would serve a wrong first token silently
    StateKind(
        lambda fam: bool(getattr(fam, "carry_width", 0)), _NONE_HANDLES,
        "carried attention rows ({name}): the queries, keys and values of a "
        "call's first rows need the rows before them, which live in a per-slot "
        "pool beside the paged pool; this mechanism does not handle it (pages "
        "without the rows at their end would serve a wrong token)",
        "carried attention rows: the transport moves a slot's paged row, and "
        "the rows its next token's queries, keys and values need would stay behind",
    ),
    StateKind(
        lambda fam: any(fam.windows), _ROLLED_BACK,
        "sliding-window layers ({name}): a window layer's KV lives in a "
        "per-slot ring beside the paged pool, which this mechanism does not handle",
        "sliding-window layers: the transport moves a slot's paged row, and "
        "its window rings would stay behind",
    ),
    StateKind(
        lambda fam: fam.kv_pools == 1, _ROLLED_BACK,
        "a latent KV pool ({name}): its cache is one pool of one row a token "
        "that every head reads, with no V pool and no head axis, which this "
        "mechanism does not handle",
        "a latent KV pool: the transport packs a K and a V pool's page columns",
    ),
)


def refusals(fam, mechanism: str, name: str = "") -> List[str]:
    """Why ``mechanism`` (one of :data:`MECHANISMS`) does not serve the family
    ``fam``: a sentence a kind of state it holds that the mechanism does not
    handle, every one by name (a family may hold more than one: a recurrent
    state beside a latent pool); empty where it serves. ``name``: the model's,
    as the admission sentences cite it."""
    if mechanism not in MECHANISMS:
        raise ValueError(f"unknown mechanism {mechanism!r}")
    return [
        (kind.migration if mechanism == "session migration" else kind.admission)
        .format(name=name, state=_state_what(fam))
        for kind in STATE_KINDS if kind.holds(fam) and mechanism in kind.unhandled
    ]


def init_pools(
    n_layer: int,
    num_pages: int,
    n_kv_head: int,
    page_size: int,
    head_dim: int,
    dtype: Any = jnp.bfloat16,
    pools: int = 2,
):
    """The shared K and V pools, zeros in :func:`pool_stored_shape`
    (``[L, P, KV, page, D]`` but for a narrow head on a TPU), plus the
    per-page scales pool — ``(k_pool, v_pool, scales)``. ``pools = 1``: a
    latent family's ONE pool (``n_kv_head`` 1, ``head_dim`` the cached row)
    and no V pool: ``(pool, None, None)``.

    Layout is kernel-native: per layer the pool is ``[P, KV, page, D]``, whose
    trailing ``(page, D)`` dims are exactly one Mosaic block — the paged
    kernel DMAs page ``block_table[b, j]`` without any transpose.

    Quantized pools (ISSUE 12, ``serving.kv_cache_dtype = "int8"``): K/V are
    stored as int8 codes and ``scales`` is ``[L, P, KV, 2]`` fp32 — one
    symmetric block scale per (layer, page, kv-head) for K (index 0) and V
    (index 1), living BESIDE the pool so every page-id mechanism (refcounted
    sharing, COW fork-by-recompute, prefix-index eviction) carries the scale
    for free: sharing a page shares its scale row, and a recomputed fork
    rewrites its own. Zero-initialized: a never-written page dequantizes to
    exact zeros. Full-precision pools return ``scales = None``."""
    shape = pool_stored_shape(
        n_layer, num_pages, n_kv_head, page_size, head_dim, dtype,
        **({"latent": True} if pools == 1 else {}),
    )
    if pools == 1:
        return jnp.zeros(shape, dtype), None, None
    scales = (
        jnp.zeros((n_layer, num_pages, n_kv_head, 2), jnp.float32)
        if jnp.dtype(dtype) == jnp.dtype(jnp.int8) else None
    )
    return jnp.zeros(shape, dtype), jnp.zeros(shape, dtype), scales


def pool_bytes(
    n_layer: int, num_pages: int, n_kv_head: int, page_size: int, head_dim: int,
    itemsize: int = 2, pools: int = 2,
) -> int:
    """HBM footprint of K+V pools (sizing aid for the ``serving`` config);
    ``itemsize = 1`` for int8 pages, ``pools = 1`` for a latent pool (with
    ``head_dim`` the row as it is stored). Scales are accounted separately
    (:func:`scales_bytes`) — they are metadata, not page payload."""
    return pools * n_layer * num_pages * n_kv_head * page_size * head_dim * itemsize


def scales_bytes(n_layer: int, num_pages: int, n_kv_head: int) -> int:
    """HBM footprint of the quantized pools' per-page scales
    (``[L, P, KV, 2]`` fp32) — reported under Engine E's ``metadata``
    category, beside the host-side refcount/prefix-index bytes."""
    return n_layer * num_pages * n_kv_head * 2 * 4


# ---------------------------------------------------------------------------
# shared-prefix index (ISSUE 10)
# ---------------------------------------------------------------------------


class PrefixCache:
    """Chained-hash index over FULL prompt pages: hash(parent, page tokens)
    → pool page holding that page's K/V.

    The production shape this serves: millions of users sharing system
    prompts. After a prompt prefills, each full page of it is registered
    here (the index ``retain``s the page, so it outlives the request); a
    later prompt walks its own pages through the chain and maps every
    matching page into its block table instead of recomputing it. Sharing
    is deterministic-by-construction — the same tokens at the same
    positions produce bit-identical K/V, so a mapped page IS the page
    prefill would have written.

    Only pages strictly before the prompt's last token are ever returned by
    :meth:`lookup` (``(plen-1)//page`` cap): the tail always re-runs through
    the model so the first sampled token has logits, and a full-prefix hit
    (prompt == an indexed chain, page-aligned) is handled by the scheduler's
    copy-on-write path instead.

    Eviction: LRU among LEAF entries only (an interior page stays as long
    as any longer chain extends it — evicting a parent would orphan its
    descendants). ``max_pages`` bounds the held set; the scheduler also
    evicts on pool pressure.
    """

    def __init__(self, allocator: PageAllocator, page_size: int,
                 max_pages: int = 0):
        self.allocator = allocator
        self.page_size = int(page_size)
        self.max_pages = int(max_pages)
        # key -> page id; OrderedDict gives LRU order (move_to_end on hit)
        self._entries: "OrderedDict[Tuple, int]" = OrderedDict()
        self._children: Dict[Tuple, int] = {}  # key -> # direct extensions
        self._parent: Dict[Tuple, Optional[Tuple]] = {}
        self.hits_full = 0
        self.hits_partial = 0
        self.misses = 0
        self.evictions = 0
        # ISSUE 16: optional KVHeatLedger (register/hit/evict hooks)
        self.heat = None
        # ISSUE 17: host-tier hooks. ``demote_sink`` (a KVTieringEngine)
        # receives (key, pid) BEFORE an evicted leaf's device page frees —
        # the page moves to the host tier instead of vanishing.
        # ``victim_order`` ranks the evictable leaves ([(key, pid)] → the
        # chosen pair) under the configured spill policy; None keeps the
        # plain LRU order.
        self.demote_sink = None
        self.victim_order = None
        self.demotions = 0
        self.adoptions = 0

    def __len__(self) -> int:
        return len(self._entries)

    @property
    def held_pages(self) -> List[int]:
        return list(self._entries.values())

    @staticmethod
    def _key(parent: Optional[Tuple], tokens: np.ndarray) -> Tuple:
        return (parent, tuple(int(t) for t in tokens))

    def lookup(self, prompt: np.ndarray) -> Tuple[List[int], int, Optional[int]]:
        """→ (shared page ids, shared token count, cow_page).

        The shared pages are the longest indexed page-aligned prefix of
        ``prompt``, capped so the last prompt token always stays in the tail
        (its logits must be recomputed). ``cow_page``: when the prompt is
        exactly page-aligned and the index also holds its LAST page (a
        full-prefix hit), that page's id — the scheduler copy-on-write-forks
        it instead of re-prefilling the tail, collapsing TTFT to one decode
        step."""
        plen = int(np.asarray(prompt).shape[-1])
        page = self.page_size
        limit = max(0, (plen - 1) // page)  # last token never shared
        pages: List[int] = []
        parent: Optional[Tuple] = None
        for j in range(limit):
            key = self._key(parent, prompt[j * page:(j + 1) * page])
            pid = self._entries.get(key)
            if pid is None:
                break
            self._entries.move_to_end(key)
            pages.append(pid)
            parent = key
        cow_page: Optional[int] = None
        # a full hit needs mappable pages to be worth anything: a one-page
        # prompt (limit == 0) has nothing to reuse — the tail IS the prompt —
        # so it reports a plain miss rather than a phantom COW fork
        if pages and len(pages) == limit and plen % page == 0:
            key = self._key(parent, prompt[limit * page: plen])
            pid = self._entries.get(key)
            if pid is not None:
                self._entries.move_to_end(key)
                cow_page = pid
        if cow_page is not None:
            self.hits_full += 1
        elif pages:
            self.hits_partial += 1
        else:
            self.misses += 1
        if self.heat is not None and (pages or cow_page is not None):
            hit_pages = pages + ([cow_page] if cow_page is not None else [])
            self.heat.hit(hit_pages, "full" if cow_page is not None else "partial")
        return pages, len(pages) * page, cow_page

    def probe(self, prompt: np.ndarray) -> int:
        """Non-mutating :meth:`lookup`: how many pages a lookup would map
        right now (no hit/miss counters, no LRU refresh) — the admission
        gate calls this every step while a request heads the queue."""
        plen = int(np.asarray(prompt).shape[-1])
        page = self.page_size
        limit = max(0, (plen - 1) // page)
        parent: Optional[Tuple] = None
        n = 0
        for j in range(limit):
            key = self._key(parent, prompt[j * page:(j + 1) * page])
            if key not in self._entries:
                break
            n += 1
            parent = key
        return n

    def insert(self, prompt: np.ndarray, pages: Sequence[int],
               n_tokens: Optional[int] = None) -> int:
        """Register the full pages of ``prompt`` (whose K/V lives in
        ``pages``, the slot's block-table prefix). Pages already indexed are
        refreshed; new ones are ``retain``ed by the index. Returns the
        number of newly indexed pages."""
        page = self.page_size
        plen = int(np.asarray(prompt).shape[-1]) if n_tokens is None else int(n_tokens)
        n_full = min(plen // page, len(pages))
        parent: Optional[Tuple] = None
        added = 0
        new_pages: List[int] = []
        for j in range(n_full):
            key = self._key(parent, prompt[j * page:(j + 1) * page])
            if key in self._entries:
                self._entries.move_to_end(key)
            else:
                pid = int(pages[j])
                self.allocator.retain([pid])
                self._entries[key] = pid
                self._parent[key] = parent
                self._children[key] = 0
                if parent is not None:
                    self._children[parent] += 1
                added += 1
                new_pages.append(pid)
            parent = key
        if self.heat is not None and new_pages:
            self.heat.register(new_pages)
        if self.max_pages > 0:
            self.evict(keep=self.max_pages)
        return added

    def _evict_one(self) -> bool:
        """Release one evictable LEAF entry — the LRU one, unless a
        ``victim_order`` policy reranks the candidates. → False if none.

        ISSUE 17 demotion: when a ``demote_sink`` is wired and the index
        holds the page's LAST reference (a still-shared page stays
        device-live with its other holder — duplicating it host-side would
        fork ownership), the sink snapshots the page to the host tier
        FIRST. Ordering is load-bearing for the cross-tier ledger: the
        sink's D event lands before the F/E pair below, so no trace prefix
        ever shows the page in neither tier (satellite 2, pinned by the
        lockstep-fuzz test)."""
        leaves = [(key, pid) for key, pid in self._entries.items()
                  if self._children.get(key, 0) == 0]
        if not leaves:
            return False
        if self.victim_order is not None:
            key, pid = self.victim_order(leaves)
        else:
            key, pid = leaves[0]  # insertion(/recency) order = LRU
        self._entries.pop(key)
        parent = self._parent.pop(key)
        self._children.pop(key, None)
        if parent is not None and parent in self._children:
            self._children[parent] -= 1
        demoted = False
        if self.demote_sink is not None and self.allocator.refcount(pid) == 1:
            if self.demote_sink.demote_begin(key, pid) is not None:
                self.demotions += 1
                demoted = True
        self.allocator.free([pid])
        if self.heat is not None:
            self.heat.evict(pid)
        self.evictions += 1
        if (not demoted and self.demote_sink is not None
                and hasattr(self.demote_sink, "drop_orphans")):
            # ISSUE 18 satellite: the key left the device index WITHOUT
            # reaching the host tier (shared page, or the sink declined) —
            # any host-held children just became unreachable; drop them
            # now (ledger V events) instead of squatting until host-LRU.
            # Safe after the F/E pair: the pin only fixes D→F→E adjacency.
            self.demote_sink.drop_orphans()
        return True

    def adopt(self, key: Tuple, pid: int) -> None:
        """Re-insert a host-restored page under its original chain ``key``
        (ISSUE 17 restore path). The caller hands over a freshly allocated
        refcount-1 page whose K/V was just device_put from the host tier —
        ownership transfers to the index (no extra retain), exactly undoing
        what demotion's free released. The parent link must already be
        resident (restores walk the chain root→leaf)."""
        parent = key[0]
        if key in self._entries:
            raise PageAllocatorError(f"prefix key already resident: {key!r}")
        if parent is not None and parent not in self._entries:
            raise PageAllocatorError(
                "adopt out of chain order: parent key not resident"
            )
        self._entries[key] = int(pid)
        self._parent[key] = parent
        self._children[key] = 0
        if parent is not None:
            self._children[parent] += 1
        if self.heat is not None:
            self.heat.register([int(pid)])
        self.adoptions += 1

    def chain_keys(self, prompt: np.ndarray) -> List[Tuple]:
        """The prompt's full chain keys root→leaf (same ``(plen-1)//page``
        cap as :meth:`lookup`), resident or not — the restore prefetch
        walks this list checking each tier."""
        plen = int(np.asarray(prompt).shape[-1])
        page = self.page_size
        limit = max(0, (plen - 1) // page)
        keys: List[Tuple] = []
        parent: Optional[Tuple] = None
        for j in range(limit):
            key = self._key(parent, prompt[j * page:(j + 1) * page])
            keys.append(key)
            parent = key
        return keys

    def evict(self, keep: Optional[int] = None, need_free: int = 0) -> int:
        """Evict LRU leaves until the index holds ≤ ``keep`` entries (when
        given) and the allocator has ≥ ``need_free`` free pages (when
        given) — each independent goal stops mattering once met, so a
        pure ``need_free`` call frees only as much as pool pressure
        demands instead of dumping the cache. An evicted page only frees
        if the index held its last reference. → entries evicted."""
        n = 0
        while self._entries:
            over_cap = keep is not None and len(self._entries) > keep
            starved = need_free > 0 and self.allocator.free_pages < need_free
            if not (over_cap or starved):
                break
            if not self._evict_one():
                break
            n += 1
        return n

    def clear(self) -> int:
        """Release every index reference (teardown / leak accounting)."""
        return self.evict(keep=0)

    def host_metadata_bytes(self) -> int:
        """Rough host-side footprint of the index structures (Engine E's
        ledger reports it alongside the HLO-derived device categories)."""
        import sys

        total = sys.getsizeof(self._entries)
        for key in self._entries:
            total += sys.getsizeof(key) + 2 * len(key[1] or ()) * 28
        return total
