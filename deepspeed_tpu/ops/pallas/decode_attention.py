"""Pallas decode-attention kernel: one query step against a KV cache.

Analog of the reference's fused inference attention (``softmax_context`` with
``layer_past``: ``csrc/transformer/inference/csrc/pt_binding.cpp:1323``-region,
``ops/transformer/inference/transformer_inference.py:231``): at decode time
the hot op is q·K^T → masked softmax → ·V over the cache, with the valid
length ``pos`` known only at runtime. The XLA fallback materializes the
[B,H,1,Smax] score tensor in HBM; this kernel streams K/V blocks through
VMEM with an online softmax, writing only the [B,H,D] output.

Grid: one program per (batch, head). ``pos`` arrives as a scalar-prefetch
operand so the same compiled kernel serves every decode step (no recompile
as the cache fills); keys at positions > pos are masked, not skipped —
compute is bounded by Smax, the usual TPU static-shape tradeoff.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...telemetry import parts

S_BLOCK = 512  # cache rows per online-softmax tile


def _decode_kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, *, sm_scale: float,
                   s_max: int, s_block: int):
    pos = pos_ref[0]
    D = q_ref.shape[-1]
    # dots take the cache's storage dtype with f32 accumulation (bf16
    # products are exact in the accumulator; skips two full-block VPU
    # upcast passes per tile); scores/softmax state stay f32
    q = q_ref[...].reshape(1, D)
    n_blocks = s_max // s_block

    def body(j, carry):
        m_prev, l_prev, acc = carry
        k = k_ref[0, 0, pl.dslice(j * s_block, s_block), :]
        v = v_ref[0, 0, pl.dslice(j * s_block, s_block), :]
        s = jnp.dot(k, q.T, preferred_element_type=jnp.float32) * sm_scale  # [S,1]
        idx = jax.lax.broadcasted_iota(jnp.int32, (s_block, 1), 0) + j * s_block
        s = jnp.where(idx <= pos, s, -1e30)
        m_cur = jnp.maximum(m_prev, jnp.max(s))
        corr = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur)  # [S,1] f32
        l_cur = l_prev * corr + jnp.sum(p)
        acc = acc * corr + jnp.dot(
            p.astype(v.dtype).T, v, preferred_element_type=jnp.float32
        )
        return m_cur, l_cur, acc

    init = (
        jnp.float32(-1e30),
        jnp.float32(0.0),
        jnp.zeros((1, D), jnp.float32),
    )
    m, l, acc = jax.lax.fori_loop(0, n_blocks, body, init)
    o_ref[...] = (acc / jnp.maximum(l, 1e-30)).reshape(o_ref.shape).astype(o_ref.dtype)


def decode_attention(
    q: jnp.ndarray,  # [B, H, D] current-step queries
    k_cache: jnp.ndarray,  # [B, Smax, KV, D]; KV == H or H % KV == 0 (GQA)
    v_cache: jnp.ndarray,  # [B, Smax, KV, D]
    pos: jnp.ndarray,  # i32: highest valid cache index (inclusive)
    sm_scale: Optional[float] = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Single-token cached attention → [B, H, D].

    GQA (KV < H): each q head's program reads its group's cache column via
    a divided head index map — the cache stays at KV heads, never repeated
    (the memory saving that motivates GQA serving)."""
    from .flash_attention import validate_kv_heads

    B, H, D = q.shape
    S = k_cache.shape[1]
    rep = validate_kv_heads(H, k_cache, v_cache)
    s_block = S if S < S_BLOCK else S_BLOCK
    assert S % s_block == 0, f"cache length {S} not a multiple of {s_block}"
    scale = sm_scale if sm_scale is not None else 1.0 / (D**0.5)

    kernel = functools.partial(
        _decode_kernel, sm_scale=float(scale), s_max=S, s_block=s_block
    )
    # Mosaic requires every block's trailing two dims to be (8,128)-divisible
    # or equal to the array's; [B,Smax,KV,D] caches with a (1,S,1,D) block
    # violate that whenever KV>1, so the kernel consumes a [B,KV,S,D] view
    # (trailing (S,D) block == array dims) and q/o gain a singleton row.
    k_t = jnp.swapaxes(k_cache, 1, 2)
    v_t = jnp.swapaxes(v_cache, 1, 2)
    q4 = q.reshape(B, H, 1, D)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, H),
            in_specs=[
                pl.BlockSpec((1, 1, 1, D), lambda b, h, pos: (b, h, 0, 0)),
                pl.BlockSpec((1, 1, S, D), lambda b, h, pos: (b, h // rep, 0, 0)),
                pl.BlockSpec((1, 1, S, D), lambda b, h, pos: (b, h // rep, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, 1, D), lambda b, h, pos: (b, h, 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, 1, D), q.dtype),
        interpret=interpret,
    )(jnp.asarray(pos, jnp.int32).reshape(1), q4, k_t, v_t)
    return out.reshape(B, H, D)


# ---------------------------------------------------------------------------
# paged variant: K/V live in a shared page pool, gathered through a per-slot
# block table (the serving subsystem's cache layout, serving/kv_cache.py).
# Reference analog: vLLM's paged_attention kernel — but expressed TPU-natively:
# the gather IS the BlockSpec index map (the scalar-prefetched block table
# names the pool page each page input DMAs into VMEM, all kv-heads of the
# page in one contiguous run), so no dense copy of the cache ever
# materializes, and the map stops at the slot's own last page.
# ---------------------------------------------------------------------------


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _page_tile_bytes(page: int, D: int, itemsize: int) -> int:
    """One head's ``[page, D]`` page as VMEM holds it: padded to its tile."""
    return _round_up(page, max(1, 32 // itemsize)) * _round_up(D, 128) * itemsize


# The most pages one grid step of the paged decode kernel takes. Each is an
# input of the call for K and one for V, with an index map of its own to
# trace and lower: 16 pages a step where 4 cost ZAYA's two programs 1.05 s of
# set-up on the chip's host (PERF.md, PR 50), about 20 ms a page input. 32 is
# what the widest served call carries (K-EXAONE's 512 keys of 16-key pages).
PAGE_INPUTS = 32


def paged_decode_blocks(KV: int, page: int, D: int, itemsize: int = 2,
                        n_pages: Optional[int] = None):
    """(kv-heads, pages) one grid step of the paged decode kernel holds, from
    the shapes alone: K and V of the block, double-buffered, at their padded
    VMEM tile sizes, stay inside ``VMEM_RESIDENT_BYTES``. All heads of a page
    when they fit (then as many pages as fit, a power of two, at most
    ``PAGE_INPUTS`` and the table's width: few heads of long pages take the
    pages their bytes leave room for, not a count of keys); else the largest
    divisor of ``KV`` whose single page fits. ``None`` when one head's page
    does not."""
    from .flash_attention import VMEM_RESIDENT_BYTES

    tile = _page_tile_bytes(page, D, itemsize)
    fit = VMEM_RESIDENT_BYTES // (4 * tile)  # (head, page) tiles: K, V x 2 buffers
    if fit < 1:
        return None
    if fit < KV:
        return max(h for h in range(1, fit + 1) if KV % h == 0), 1
    cap = min(fit // KV, PAGE_INPUTS, n_pages or PAGE_INPUTS)
    return KV, 1 << (cap.bit_length() - 1)


# What one grid step of the multi-token kernel may count in VMEM. The count
# (:func:`paged_multitoken_blocks`) takes every temporary at its padded size
# and all of them live at once, so it runs ahead of what Mosaic allocates:
# steps counted at 16-18 MiB compile under the default 16 MiB scoped limit
# (``tests/unit/ops/test_mosaic_compile.py``), and the call asks for no more.
PAGED_MULTITOKEN_VMEM_BYTES = 18 * 1024 * 1024


def paged_multitoken_blocks(KV: int, page: int, D: int, T: int,
                            itemsize: int = 2, n_pages: Optional[int] = None,
                            rep: int = 1):
    """(kv-heads, pages) one grid step of the multi-token paged kernel holds,
    from the shapes alone. It starts from :func:`paged_decode_blocks`' pair
    and cuts the pages so that a head's ``[rep * T, G * page]`` score tile
    keeps about 8 x ``S_BLOCK`` elements: ``S_BLOCK`` keys for a sublane
    tile of rows (the verify shape: at 16-key pages the decode kernel's
    block), 128 keys, one lane tile, from 32 rows on (a chunk computes, and
    masks, no further past its reach than that). Then the step has to fit
    ``PAGED_MULTITOKEN_VMEM_BYTES``: beside the K and V page buffers a head
    costs its query and output blocks (double-buffered), the float32
    (m, l, acc) scratch, the block's K and V joined in the query's type and
    the float32 scores and probabilities.
    Fewer pages first (down to the lane tile), then fewer heads a step (the
    largest divisor of ``KV`` that fits). ``None`` when one head does not."""
    decode = paged_decode_blocks(KV, page, D, itemsize, n_pages)
    if decode is None:
        return None
    lanes, qsize = _round_up(D, 128), max(2, itemsize)  # int8 codes meet a bf16 q
    rows = _round_up(rep * T, 8)

    def step_bytes(HB, G):
        keys = _round_up(G * page, 128)
        return HB * (
            4 * _round_up(rep * T, 32 // qsize) * lanes * qsize  # q, o x 2
            + rows * (lanes + 2 * 128) * 4  # acc, m, l
            + 4 * G * _page_tile_bytes(page, D, itemsize)  # K, V x 2
            + 2 * G * page * lanes * qsize  # the block's K and V, joined
            + rows * keys * (8 + qsize)  # s, p and p in V's type
        )

    HB, G = decode
    floor = max(1, min(G, 128 // page))  # pages of one lane tile of keys
    G = max(floor, min(G, S_BLOCK * 8 // rows // page))
    G = 1 << (G.bit_length() - 1)
    while G > floor and step_bytes(HB, G) > PAGED_MULTITOKEN_VMEM_BYTES:
        G //= 2
    return next(
        ((hb, G) for hb in range(HB, 0, -1)
         if HB % hb == 0 and step_bytes(hb, G) <= PAGED_MULTITOKEN_VMEM_BYTES),
        None,
    )


def _pool_dims(pool, layer: Optional[int]):
    """(KV, page) of a ``[P, KV, page, D]`` pool or, with a static ``layer``,
    of a whole ``[L, P, KV, page, D]`` one."""
    if pool.ndim != (4 if layer is None else 5):
        raise ValueError(
            f"paged pool of rank {pool.ndim} with layer={layer}: a "
            "[P, KV, page, D] pool takes no layer, a [L, P, KV, page, D] one "
            "needs it"
        )
    return pool.shape[-3], pool.shape[-2]


def _pool_block_spec(block, index_map, layer):
    """The BlockSpec of one pool page block. With ``layer`` the pool is the
    whole ``[L, P, KV, page, D]`` array and the layer a squeezed leading block
    index, so the kernel body sees the same ``[1, HB, page, D]`` ref. A
    ``layer`` that is not a Python int is an operand: the call's LAST
    scalar-prefetched one (``[1]`` i32), read here."""
    if layer is None:
        return pl.BlockSpec(block, index_map)
    if isinstance(layer, int):
        return pl.BlockSpec((None, *block), lambda *a: (layer, *index_map(*a)))
    return pl.BlockSpec((None, *block), lambda *a: (a[-1][0], *index_map(*a)))


def _layer_operand(layer) -> tuple:
    """The scalar-prefetch operand of a ``layer`` that is an operand."""
    if layer is None or isinstance(layer, int):
        return ()
    return (jnp.asarray(layer, jnp.int32).reshape(1),)


def own_blocks(at, reach: int, GP: int, n_blk: int, xp=jnp):
    """Page blocks of ``GP`` keys that queries starting at ``at`` and
    ``reach`` tokens long own: block 0 up to the one that holds the last
    token's own key, inside the table's ``n_blk``. The ONE walk rule of both
    paged kernel families (``latent_attention.latent_walk`` states it a query
    block); with ``xp=np`` the scheduler's counters reckon by it too."""
    return xp.minimum((at + (reach - 1)) // GP, n_blk - 1) + 1


def items_of_rows(starts, ends, per_row, n: int):
    """``per_row`` ``[K, rows]`` spread over the ``n`` items of a walk in which
    row ``r`` owns items ``starts[r] .. ends[r] - 1`` → ``[K, n]`` (zeros
    past the last item). Compares and sums over ``[items, rows]``, no search
    and no loop: a call may sit in a conditional a layer, where nothing folds."""
    s = jnp.arange(n, dtype=jnp.int32)
    mine = (s[:, None] >= starts[None, :]) & (s[:, None] < ends[None, :])   # [items, rows]: one row an item
    return s, jnp.where(mine[None], per_row[:, None, :], 0).sum(-1).astype(jnp.int32)


def item_pages(block_tables, table, blk, last, G: int):
    """The ``G`` pages the inputs hold in each item: block ``blk`` of the
    table row that starts at ``table`` (in the flattened table) and is owned
    up to page ``last``. Past ``last``, the page the input held a block ago
    (an unchanged index fetches nothing) or, in block 0, the row's first
    pages. ONE gather from the table."""
    e = blk[:, None] * G + jnp.arange(G, dtype=jnp.int32)[None, :]
    e = jnp.clip(jnp.where(e > last[:, None], e - G, e), 0, last[:, None])
    return block_tables.reshape(-1)[table[:, None] + e]


def paged_walk_steps(at, live, KV: int, page: int, D: int, itemsize: int,
                     n_pages: int, T: Optional[int] = None, rep: int = 1):
    """(grid steps one call of the paged attention kernel takes, steps of the
    rectangle ``slots x head blocks x page blocks`` that bounds them: what
    full slots take) for slots whose queries start at ``at`` ``[B]``, the
    ``live`` ones (``[B]`` bool; None: all) owning their blocks and the others
    one item each; ``T`` None is the one-token kernel. Host arithmetic, by
    the wrappers' own rules."""
    import numpy as np

    blocks = (
        paged_decode_blocks(KV, page, D, itemsize, n_pages) if T is None
        else paged_multitoken_blocks(KV, page, D, T, itemsize, n_pages, rep)
    )
    if blocks is None:  # a page no kernel takes
        return 0, 0
    HB, G = blocks
    n_blk = -(-n_pages // G)
    own = own_blocks(np.asarray(at, np.int64), T or 1, G * page, n_blk, xp=np)
    if live is not None:
        own = np.where(live, own, 1)
    return int(own.sum()) * (KV // HB), own.size * (KV // HB) * n_blk


def _paged_kernel(row_ref, home_ref, blk_ref, at_ref, pages_ref, *rest,
                  sm_scale: float, G: int, n_blk: int, T: int = 1,
                  rep: int = 1, quantized: bool = False,
                  windowed: bool = False, layer_operand: bool = False):
    """Online-softmax accumulation over one slot's pages for ``T`` query
    tokens of the slot (1: the decode step; more: chunked prefill and the
    verify shape), ``G`` pages and ``HB`` kv-heads (all of them, unless a
    step of all does not fit VMEM) to a grid step.

    The grid is the call's ITEMS (:func:`_walk_items`), sequential: step
    ``s`` holds page block ``blk[s]`` of grid row ``row[s]`` (slot ``row //
    nhb``, head block ``row % nhb``), a row's blocks in a row. The (m, l, acc)
    scratch persists across a row's items, reset at its block 0 and emitted
    at its LAST OWN block. Query ``t`` of the row's slot sits at position
    ``at[s] + t`` and attends keys at positions ``<= at[s] + t``, so the last
    own block is the one the tokens reach, ``(at + T - 1) // (G * page)``:
    every step computes, and the next item's pages arrive under it. An IDLE
    row's one item has ``at[s]`` -1: its inputs name what the item before
    held, so nothing is fetched, and the step writes the row's output zeros
    (rows feed shared reductions in the families with expert layers).
    ``rest`` holds the block's ``G`` K pages and ``G`` V pages, ``[1, HB,
    page, D]`` each. A page ref past the slot's last page holds one of the
    slot's earlier pages; its scores are masked and its probabilities are
    exactly 0.

    The arithmetic is batched over heads: ``s[h, r, p]`` and ``acc[h, r, d]``
    are one ``dot_general`` each over ``[HB, G * page, D]``, q viewed as
    ``[HB, rep * T, D]`` (row ``g * T + t`` is query head ``g`` of the group,
    token ``t``) so a GQA group reads its single pool column.

    The mask only where it can bite: with more than a sublane tile of query
    rows, a block whose last key is ``<= at`` is visible to every query and
    takes a branch that builds none; only the blocks that overlap ``at ..
    at + T - 1`` do (the last one alone, where chunks start on block
    multiples). With a tile of rows or fewer the scores are a few registers
    and one masked branch, with the emit inside it, is cheaper than a second
    copy of the body and a third branch a step.

    ``quantized`` (ISSUE 12): K/V pages are int8 codes (exact in the query's
    float type) and one more input carries the block's K and V scales per
    key column, ``[2, HB, 1, G * page]``; scores and probabilities are scaled
    in VMEM, so the HBM read per page stays the halved code bytes.

    ``windowed``: one more prefetched operand ``lo`` (an item's) bounds the
    keys from below: query ``t`` attends keys ``lo + t <= key <= at + t`` (a
    sliding window; the caller's table starts at the page that holds ``lo``,
    so the walk starts there and block 0 is an own block).

    ``layer_operand``: one more prefetched operand follows, the pool's layer,
    which the pools' index maps read and the body does not. ``row``, ``home``
    and ``pages`` are the index maps' too."""
    lo_ref = None
    if windowed:
        lo_ref, *rest = rest
    if layer_operand:
        rest = rest[1:]
    q_ref, *rest = rest
    k_refs, v_refs, rest = rest[:G], rest[G:2 * G], rest[2 * G:]
    if quantized:
        sc_ref, *rest = rest
    o_ref, m_ref, l_ref, acc_ref = rest
    step = pl.program_id(0)
    j, at = blk_ref[step], at_ref[step]
    lo = lo_ref[step] if windowed else None
    live = at >= 0
    GP = G * k_refs[0].shape[2]
    last_blk = jax.lax.div(at + (T - 1), GP)
    if T > 1:  # a chunk may reach past the table; a decode position does not
        last_blk = jnp.minimum(last_blk, n_blk - 1)

    @pl.when(live & (j == 0))
    def _reset():
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(jnp.logical_not(live))
    def _idle():
        o_ref[...] = jnp.zeros_like(o_ref)

    def emit():
        o_ref[0, 0] = (
            acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        ).astype(o_ref.dtype)

    def update(masked: bool, emits: bool = False):
        q = q_ref[0, 0]  # [HB, rep * T, D]
        k = jnp.concatenate([r[0] for r in k_refs], axis=1)  # [HB, GP, D]
        v = jnp.concatenate([r[0] for r in v_refs], axis=1)
        if quantized:
            k, v = k.astype(q.dtype), v.astype(q.dtype)
        # dots take the pool's storage dtype with f32 accumulation (bf16
        # products are exact in the accumulator); scores/softmax state f32
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        ) * sm_scale  # [HB, rep * T, GP]
        if quantized:
            s = s * sc_ref[0, 0, 0, 0]
        if masked:
            key = jax.lax.broadcasted_iota(jnp.int32, (1, 1, GP), 2) + j * GP
            t = 0
            if T > 1:
                t = jax.lax.broadcasted_iota(jnp.int32, (1, rep * T, 1), 1)
                for _ in range(1, rep):  # row g * T + t -> t, with no division
                    t = jnp.where(t >= T, t - T, t)
            seen = key <= at + t
            if windowed:
                seen = seen & (key >= lo + t)
            s = jnp.where(seen, s, -1e30)
        m_prev, l_prev = m_ref[...], l_ref[...]  # [HB, rep * T, 1]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        corr = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur)
        m_ref[...] = m_cur
        l_ref[...] = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        if quantized:
            sv = sc_ref[0, 0, 1, 0]
            if masked:
                # a padded table entry's scale row may hold anything: 0 * it too
                sv = jnp.where(key <= at + (T - 1), sv, 0.0)
            p = p * sv
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )  # [HB, rep * T, D]
        if emits:
            pl.when(j == last_blk)(emit)

    if rep * T <= 8:
        # the decode step's form: one branch a grid step, the emit inside it
        pl.when(live)(functools.partial(update, True, emits=True))
    else:
        whole = (j + 1) * GP - 1 <= at  # every key visible to every query
        if windowed:
            whole = whole & (j * GP >= lo + (T - 1))
        pl.when(live & whole)(functools.partial(update, False))
        pl.when(live & jnp.logical_not(whole))(functools.partial(update, True))
        pl.when(live & (j == last_blk))(emit)


def _walk_items(block_tables, at, live, T: int, G: int, page: int, nhb: int):
    """The call's items in the order the grid walks them, padded to the
    rectangle ``B * nhb * n_blk`` (what a call of full slots owns): a LIVE
    grid row (a slot's head block) owns the blocks :func:`own_blocks` counts
    for its slot's ``at`` ``[B]``, an IDLE one (``live`` ``[B]`` false; None:
    every slot is live) ONE item. → per item its grid ``row``; the row
    ``home`` whose query and pages its inputs hold; the page block ``blk``;
    ``at`` of its slot, -1 for an idle row's; the ``G`` ``pages`` (flat:
    :func:`item_pages`); and ``[1]`` the count of real items. An idle row's
    ``home`` is the nearest live row before it, or for leading idle rows the
    first live one, and its item names that row's nearest block (its last
    own, or block 0): the inputs hold already what the item names, or fetch it
    ONCE for the live item too."""
    B, n_pages = block_tables.shape
    n_blk = -(-n_pages // G)
    slot = jnp.arange(B, dtype=jnp.int32)
    live = jnp.ones((B,), bool) if live is None else live
    own = jnp.where(live, own_blocks(at, T, G * page, n_blk), 1)            # [B]
    before = jax.lax.cummax(jnp.where(live, slot, -1))                      # a live slot's: itself
    host = jnp.where(before >= 0, before, jnp.argmax(live)).astype(jnp.int32)
    per_slot = jnp.stack([                                                  # of an item's slot:
        slot, host, jnp.where(live, at, -1),
        jnp.where(live | (before < 0), 0, own[host] - 1),                   # the block an idle row's item names
        jnp.where(before >= 0, nhb - 1, 0),                                 # ... and the head block
        jnp.minimum((at[host] + (T - 1)) // page, n_pages - 1),             # the last page its host reaches
    ])
    hb = jnp.tile(jnp.arange(nhb, dtype=jnp.int32), B)                      # rows: a slot's head blocks in a row
    own = jnp.repeat(own, nhb)
    ends = jnp.cumsum(own)
    starts = ends - own
    s, (b, host, at, blk, host_hb, last, hb, first) = items_of_rows(
        starts, ends, jnp.concatenate([jnp.repeat(per_slot, nhb, axis=1), hb[None], starts[None]]),
        B * nhb * n_blk,
    )
    row = b * nhb + hb
    home = jnp.where(at >= 0, row, host * nhb + host_hb)
    blk = jnp.minimum(blk + s - first, n_blk - 1)
    pages = item_pages(block_tables, host * n_pages, blk, last, G)
    return row, home, blk, at, pages.reshape(-1), ends[-1:].astype(jnp.int32)


def _paged_call(kernel, q5, k_pool, v_pool, block_tables, at, live, T: int,
                HB: int, G: int, scales, layer: Optional[int], interpret: bool,
                lo=None, name: Optional[str] = None):
    """The ``pallas_call`` of :func:`_paged_kernel`, for both wrappers: a grid
    as long as the call's own walk (:func:`_walk_items`: its bound is a value
    of the call) over ``q5`` ``[B, nhb, HB, R, D]`` (``R`` query rows a
    kv-head: ``rep`` for the decode step, ``rep * T`` for T tokens), the
    ``G`` K and ``G`` V page inputs under the items' pages, an int8 pool's
    scales per key column, and the (m, l, acc) scratch. ``at`` ([B]) is what
    the kernel masks by and what a slot's own blocks follow from, ``live``
    ([B] bool, or None) the slots that hold a request. ``lo`` ([B], for a
    ``windowed`` kernel) is prefetched an item, after the pages. ``name`` is
    the call's name in a trace; without one it takes the name of the jitted
    function that holds it (``decode_fn``, ``verify_fn``), which is what a
    program that holds both kernels cannot leave to chance."""
    B, nhb, _, R, D = q5.shape
    page = k_pool.shape[-2]
    n_pages = block_tables.shape[1]
    n_blk, GP = -(-n_pages // G), G * page
    block_tables = jnp.asarray(block_tables, jnp.int32)
    row, home, blk, at_s, pages, n_items = _walk_items(
        block_tables, at, live, T, G, page, nhb
    )

    def rows(r):  # grid row -> (slot, head block)
        return (r, 0) if nhb == 1 else (jax.lax.div(r, nhb), jax.lax.rem(r, nhb))

    def page_spec(g):
        def index_map(s, row, home, blk, at, pages, *_):
            return pages[s * G + g], rows(home[s])[1], 0, 0

        return _pool_block_spec((1, HB, page, D), index_map, layer)

    block = (1, 1, HB, R, D)
    q_spec = pl.BlockSpec(block, lambda s, row, home, *_: (*rows(home[s]), 0, 0, 0))
    o_spec = pl.BlockSpec(block, lambda s, row, *_: (*rows(row[s]), 0, 0, 0))
    page_specs = [page_spec(g) for g in range(G)]
    in_specs = [q_spec] + page_specs + page_specs
    operands = [q5] + [k_pool] * G + [v_pool] * G
    if scales is not None:
        # per key column of each page block: [B, n_blk, 2, nhb, HB, 1, GP];
        # an idle row's item keeps the index of the item before
        st = jnp.asarray(scales, jnp.float32)[block_tables]  # [B, n, KV, 2]
        st = jnp.pad(st, ((0, 0), (0, n_blk * G - n_pages), (0, 0), (0, 0)))
        st = jnp.repeat(st, page, axis=1).reshape(B, n_blk, GP, nhb, HB, 2)
        operands.append(st.transpose(0, 1, 5, 3, 4, 2)[..., None, :])

        def scale_map(s, row, home, blk, *_):
            b, hb = rows(home[s])
            return b, blk[s], 0, hb, 0, 0, 0

        in_specs.append(pl.BlockSpec((1, 1, 2, 1, HB, 1, GP), scale_map))
    prefetched = (row, home, blk, at_s, pages)
    if lo is not None:
        prefetched += (jnp.asarray(lo, jnp.int32)[rows(row)[0]],)
    prefetched += _layer_operand(layer)
    with parts.unscoped():
        return pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                # the items' five vectors (+ their windows' lower bounds)
                # (+ the layer, where it is an operand)
                num_scalar_prefetch=len(prefetched),
                grid=(n_items[0],),
                in_specs=in_specs,
                out_specs=o_spec,
                scratch_shapes=[
                    pltpu.VMEM((HB, R, 1), jnp.float32),  # running max
                    pltpu.VMEM((HB, R, 1), jnp.float32),  # running denominator
                    pltpu.VMEM((HB, R, D), jnp.float32),  # output accumulator
                ],
            ),
            out_shape=jax.ShapeDtypeStruct(q5.shape, q5.dtype),
            interpret=interpret,
            **({} if name is None else {"name": name}),
        )(*prefetched, *operands)


# A pool of this many layers or more has its kernels' calls, one a layer of a
# program, share one traced and lowered kernel (:func:`_for_all_layers`). The
# jitted twin costs a trace of its own, 0.25-0.5 s on the chip's host where a
# layer's call traced and lowered in place costs about 0.1 s (PERF.md, PR 35:
# 48 layers 11.2 -> 1.8 s off the chip; five layers of two kinds lost 2 s).
SHARED_FROM_LAYERS = 8


def _shares_kernel(pool, layer, name) -> bool:
    """Whether this call goes through its jitted twin: a named call (XLA
    names an unnamed kernel's custom call after the innermost jitted
    function, so it would be lost to a trace's readers) with a static layer
    into a pool deep enough for the twin to pay."""
    return (
        name is not None and isinstance(layer, int)
        and pool.shape[0] >= SHARED_FROM_LAYERS
    )


@functools.partial(jax.jit, static_argnames=("fn", "sm_scale", "interpret", "name"))
def _for_all_layers(fn, q, k_pool, v_pool, block_tables, at, layer, scales, lo,
                    live, *, sm_scale, interpret, name):
    """``fn`` (one of the two wrappers below) with the layer an OPERAND, under
    a jit of its own: a program's calls, one a layer, then share one traced
    and one lowered kernel, which is most of what a layer costs a served
    program's set-up (ROADMAP S9)."""
    return fn(q, k_pool, v_pool, block_tables, at, sm_scale, interpret, scales,
              layer, lo, name, live)


def paged_decode_attention(
    q: jnp.ndarray,  # [B, H, D] current-step queries (one per serving slot)
    k_pool: jnp.ndarray,  # [P, KV, page, D] shared page pool, or [L, P, ...]
    v_pool: jnp.ndarray,  # the same shape
    block_tables: jnp.ndarray,  # [B, n_pages] i32 pool-page ids per slot
    pos: jnp.ndarray,  # [B] i32: highest valid cache index per slot (inclusive)
    sm_scale: Optional[float] = None,
    interpret: bool = False,
    scales: Optional[jnp.ndarray] = None,  # [P, KV, 2] f32 for int8 pools
    layer=None,  # the layer of a [L, P, KV, page, D] pool: static, or a traced i32
    lo: Optional[jnp.ndarray] = None,  # [B] i32: lowest key index attended (a window)
    name: Optional[str] = None,  # the call's name in a trace (:func:`_paged_call`)
    live: Optional[jnp.ndarray] = None,  # [B] bool: the slots that hold a request (None: all)
) -> jnp.ndarray:
    """Single-token attention against a PAGED cache → [B, H, D].

    ``lo`` bounds the keys from below (``lo[b] <= key <= pos[b]``): a sliding
    window, whose caller hands a table that starts at the page holding
    ``lo[b]`` with ``pos`` and ``lo`` counted from that page's first key, so
    that the walk starts where the window does.

    With ``layer`` the pools are the serving engine's whole ``[L, P, KV, page,
    D]`` arrays and the layer is one more (squeezed) block index: the caller
    never slices ``pool[l]``, which XLA would materialise, a layer of padded
    tiles per kernel call. ``scales`` stays that layer's ``[P, KV, 2]``.

    Each slot's logical cache is ``block_tables[b]``'s pages concatenated up
    to ``pos[b]``; the kernel walks them ``G`` pages at a time with all
    kv-heads in one step (:func:`paged_decode_blocks` picks both from the
    shapes). Each of the ``G`` page inputs is the same pool under its own
    index map: the scalar-prefetched items name the page, one DMA brings
    its whole ``[KV, page, D]`` run, and the walk stops at the slot's own
    last block (:func:`_walk_items`), so table entries past ``pos[b] //
    page`` are never read and the next slot's first block is fetched under
    this slot's last. A slot that ``live`` says holds no request owns one
    item that fetches nothing; its output rows are zeros. GQA (KV < H) reads the
    group's pool column once for its ``rep`` query heads. ``scales``
    (ISSUE 12): int8 pools are served by the same kernel;
    the slots' per-page scale rows are gathered through the table into
    per-key columns here (a few hundred KB beside the halved code bytes)
    and applied to the scores and the probabilities in VMEM.

    A call with a ``name`` and a static ``layer`` into a deep pool shares its
    kernel with the program's other layers (:func:`_shares_kernel`)."""
    if _shares_kernel(k_pool, layer, name):
        return _for_all_layers(
            paged_decode_attention, q, k_pool, v_pool, block_tables, pos,
            jnp.int32(layer), scales, lo, live, sm_scale=sm_scale,
            interpret=interpret, name=name,
        )
    B, H, D = q.shape
    KV, page = _pool_dims(k_pool, layer)
    n_pages = block_tables.shape[1]
    if H % KV != 0:
        raise ValueError(f"q heads {H} must divide by KV heads {KV}")
    rep = H // KV
    scale = sm_scale if sm_scale is not None else 1.0 / (D**0.5)
    quantized = scales is not None
    blocks = paged_decode_blocks(KV, page, D, k_pool.dtype.itemsize, n_pages)
    if blocks is None:
        raise ValueError(
            f"paged_decode_attention: one [{page}, {D}] page of one head "
            "does not fit the kernel's VMEM budget"
        )
    HB, G = blocks
    nhb = KV // HB
    pos = jnp.asarray(pos, jnp.int32)
    kernel = functools.partial(
        _paged_kernel, sm_scale=float(scale), G=G, n_blk=-(-n_pages // G), T=1,
        rep=rep, quantized=quantized, windowed=lo is not None,
        layer_operand=bool(_layer_operand(layer)),
    )
    out = _paged_call(
        kernel, q.reshape(B, nhb, HB, rep, D), k_pool, v_pool, block_tables,
        pos, live, 1, HB, G, scales, layer, interpret, lo, name,
    )
    return out.reshape(B, H, D)


def paged_multitoken_attention(
    q: jnp.ndarray,  # [B, T, H, D] T query tokens per slot
    k_pool: jnp.ndarray,  # [P, KV, page, D] shared page pool, or [L, P, ...]
    v_pool: jnp.ndarray,  # the same shape
    block_tables: jnp.ndarray,  # [B, n_pages] i32 pool-page ids per slot
    base: jnp.ndarray,  # [B] i32: query t of slot b sits at position base[b]+t
    sm_scale: Optional[float] = None,
    interpret: bool = False,
    scales: Optional[jnp.ndarray] = None,  # [P, KV, 2] f32 for int8 pools
    layer=None,  # the layer of a [L, P, KV, page, D] pool: static, or a traced i32
    lo: Optional[jnp.ndarray] = None,  # [B] i32: query t attends keys >= lo[b] + t
    name: Optional[str] = None,  # the call's name in a trace (:func:`_paged_call`)
    live: Optional[jnp.ndarray] = None,  # [B] bool: the slots that hold a request (None: all)
) -> jnp.ndarray:
    """T-token causal attention against a PAGED cache → [B, T, H, D].
    ``lo`` as in :func:`paged_decode_attention`, moving with the query.

    Serves chunked prefill (T = chunk width, base = chunk start) and the
    verify shape (T = k+1 drafted tokens, base = per-slot cached length) —
    the chunk's own K/V must already be scattered into the pool
    (update-then-attend, as in the single-token decode step). The plan is
    :func:`paged_decode_attention`'s: all kv-heads and ``G`` pages to a grid
    step (:func:`paged_multitoken_blocks` picks both from the shapes), the
    walk ending at the page the chunk reaches, so table entries past
    ``(base[b] + T - 1) // page`` are never read; GQA, int8 ``scales``,
    ``layer`` and ``live`` as there. The queries go in head-major, ``[B, KV, rep * T,
    D]``, which is what the head-batched ``dot_general`` takes: the two
    ``[T, H] <-> [H, T]`` transposes around the call stay (0.4 MB each at
    the served shape). ``name`` as in :func:`paged_decode_attention`."""
    if _shares_kernel(k_pool, layer, name):
        return _for_all_layers(
            paged_multitoken_attention, q, k_pool, v_pool, block_tables, base,
            jnp.int32(layer), scales, lo, live, sm_scale=sm_scale,
            interpret=interpret, name=name,
        )
    B, T, H, D = q.shape
    KV, page = _pool_dims(k_pool, layer)
    n_pages = block_tables.shape[1]
    if H % KV != 0:
        raise ValueError(f"q heads {H} must divide by KV heads {KV}")
    rep = H // KV
    scale = sm_scale if sm_scale is not None else 1.0 / (D**0.5)
    blocks = paged_multitoken_blocks(
        KV, page, D, T, k_pool.dtype.itemsize, n_pages, rep
    )
    if blocks is None:
        raise ValueError(
            f"paged_multitoken_attention: {rep * T} query rows against one "
            f"[{page}, {D}] page of one head do not fit the kernel's VMEM "
            "budget"
        )
    HB, G = blocks
    nhb = KV // HB
    base = jnp.asarray(base, jnp.int32)
    kernel = functools.partial(
        _paged_kernel, sm_scale=float(scale), G=G, n_blk=-(-n_pages // G), T=T,
        rep=rep, quantized=scales is not None, windowed=lo is not None,
        layer_operand=bool(_layer_operand(layer)),
    )
    q5 = q.reshape(B, T, nhb, HB, rep, D).transpose(0, 2, 3, 4, 1, 5)
    out = _paged_call(
        kernel, q5.reshape(B, nhb, HB, rep * T, D), k_pool, v_pool,
        block_tables, base, live, T, HB, G, scales, layer, interpret, lo, name,
    )
    out = out.reshape(B, nhb, HB, rep, T, D).transpose(0, 4, 1, 2, 3, 5)
    return out.reshape(B, T, H, D)


def _token_write_kernel(pidx_ref, poff_ref, *rest):
    """One slot's K page and V page (a block of kv-heads of them) with the
    rows of the slot's new tokens replaced; the other rows pass through. The
    step of token ``t`` brings the page ``pidx[b, t]`` and writes EVERY token
    of the slot that lands on that page, so the steps of one page, which
    follow one another, each leave the whole result: the pipeline fetches and
    writes back a block only when its index changes. (Where the layer is an
    operand, its prefetched ref comes third and only the index maps read it.)"""
    k_new_ref, v_new_ref, k_ref, v_ref, k_out_ref, v_out_ref = rest[-6:]
    b, t = pl.program_id(0), pl.program_id(2)
    page = k_out_ref.shape[-2]
    row = jax.lax.broadcasted_iota(jnp.int32, (1, page, 1), 1)
    here_page = pidx_ref[b, t]
    k, v = k_ref[0], v_ref[0]
    for u in range(k_new_ref.shape[1]):  # in order: a later token wins
        here = row == jnp.where(pidx_ref[b, u] == here_page, poff_ref[b, u], -1)
        k = jnp.where(here, k_new_ref[0, u], k)
        v = jnp.where(here, v_new_ref[0, u], v)
    k_out_ref[0] = k
    v_out_ref[0] = v


def paged_token_write_blocks(KV: int, page: int, D: int, itemsize: int = 2,
                             T: int = 1) -> Optional[int]:
    """kv-heads a grid step of :func:`paged_token_write` holds: the largest
    divisor of ``KV`` whose K and V page, in and out and double-buffered,
    with the ``T`` new rows of each (a tile a row), fit the VMEM budget.
    ``None`` when a single head's do not."""
    from .flash_attention import VMEM_RESIDENT_BYTES

    per_head = (
        8 * _page_tile_bytes(page, D, itemsize)
        + 4 * T * _page_tile_bytes(1, D, itemsize)
    )
    fit = VMEM_RESIDENT_BYTES // per_head
    return max((h for h in range(1, KV + 1) if KV % h == 0 and h <= fit),
               default=None)


def paged_token_write(
    k_pool: jnp.ndarray,  # [L, P, KV, page, D], updated in place (donate it)
    v_pool: jnp.ndarray,  # the same
    layer,  # static, or (under the jitted twin) a traced i32
    pidx: jnp.ndarray,  # [B] or [B, T] i32 page of each new token
    poff: jnp.ndarray,  # the same shape: its offset in that page
    k_vals: jnp.ndarray,  # [B, KV, D] or [B, T, KV, D] the new tokens' K
    v_vals: jnp.ndarray,  # the same shape: their V
    interpret: bool = False,
    shared: bool = False,  # a program's calls, one a layer, share one kernel
):
    """The decode step's one-token write into both pools (or the verify
    step's ``T`` tokens a slot) as ONE device operation → ``(k_pool,
    v_pool)``: a grid step brings a slot's current K and V page, replaces the
    new tokens' rows and puts the pages back; the pools are aliased, so
    nothing else moves. Same elements, same values as ``pool.at[layer,
    pidx[b, t], :, poff[b, t]].set(vals[b, t])`` for every ``(b, t)`` in
    order. A slot's tokens sit at consecutive positions, so the steps of one
    page follow one another (the kernel counts on it); slots never share the
    page they write but for the scratch page, which idle slots and drafts
    past a slot's row share and nothing reads. (A scatter asks the TPU for
    the page index minor-most and so re-lays the pool out; sixteen
    ``dynamic_update_slice`` a layer and pool do the same in place in thirty
    device operations, and a traced run has to write every one of them out.)
    ``shared``: into a deep pool the call goes through a jitted twin with the
    layer an operand (:func:`_shares_kernel`; the kernel has its name)."""
    if shared and _shares_kernel(k_pool, layer, "kv_token_write"):
        return _token_write_for_all_layers(
            k_pool, v_pool, jnp.int32(layer), pidx, poff, k_vals, v_vals, interpret
        )
    KV, page, D = k_pool.shape[2:]
    if pidx.ndim == 1:
        pidx, poff = pidx[:, None], poff[:, None]
        k_vals, v_vals = k_vals[:, None], v_vals[:, None]
    B, T = pidx.shape
    HB = paged_token_write_blocks(KV, page, D, k_pool.dtype.itemsize, T)
    if HB is None:
        raise ValueError(
            f"paged_token_write: a [{page}, {D}] page of {k_pool.dtype.name} "
            "does not fit VMEM"
        )
    block = _pool_block_spec(
        (1, HB, page, D),
        lambda b, hb, t, pidx, poff, *_: (pidx[b, t], hb, 0, 0), layer,
    )
    new = pl.BlockSpec(
        (1, T, HB, 1, D), lambda b, hb, t, pidx, poff, *_: (b, 0, hb, 0, 0)
    )
    prefetched = (
        jnp.asarray(pidx, jnp.int32), jnp.asarray(poff, jnp.int32),
        *_layer_operand(layer),
    )
    pool_shape = jax.ShapeDtypeStruct(k_pool.shape, k_pool.dtype)
    return pl.pallas_call(
        _token_write_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetched),
            grid=(B, KV // HB, T),
            in_specs=[new, new, block, block],
            out_specs=[block, block],
        ),
        out_shape=[pool_shape, pool_shape],
        # the pools, after the prefetched operands and the two new rows
        input_output_aliases={len(prefetched) + 2: 0, len(prefetched) + 3: 1},
        # its own name in a trace: the roofline readers find the attention
        # kernels by the name of the function that holds them (decode_fn)
        name="kv_token_write",
        interpret=interpret,
    )(
        *prefetched,
        k_vals.astype(k_pool.dtype).reshape(B, T, KV, 1, D),
        v_vals.astype(v_pool.dtype).reshape(B, T, KV, 1, D),
        k_pool, v_pool,
    )


_token_write_for_all_layers = jax.jit(paged_token_write, static_argnums=(7,))


def paged_token_write_ok(KV: int, page: int, D: int, itemsize: int = 2,
                         T: int = 1) -> bool:
    """Gate for :func:`paged_token_write`: the page rule, and a head block
    :func:`paged_token_write_blocks` can place in VMEM."""
    return (
        paged_page_ok(page, D, itemsize)
        and paged_token_write_blocks(KV, page, D, itemsize, T) is not None
    )


def paged_page_ok(page: int, D: int, itemsize: int) -> bool:
    """What both paged kernels ask of a page: TPU backend, lane-friendly head
    dim, sublane-aligned page length."""
    sublane = max(1, 32 // max(1, itemsize))
    return (
        jax.default_backend() == "tpu"
        and D % 64 == 0
        and page % sublane == 0
    )


def paged_decode_attention_ok(
    KV: int, page: int, D: int, itemsize: int = 2
) -> bool:
    """Trace-time gate for the paged decode kernel: the page rule, and a
    block :func:`paged_decode_blocks` can place in VMEM (``KV`` is the pool's
    own head count: a tensor-parallel shard passes its ``KV / tp``)."""
    return (
        paged_page_ok(page, D, itemsize)
        and paged_decode_blocks(KV, page, D, itemsize) is not None
    )


def paged_multitoken_attention_ok(
    KV: int, page: int, D: int, T: int, itemsize: int = 2, rep: int = 1
) -> bool:
    """Gate for the multi-token paged kernel: the page rule, and a block
    :func:`paged_multitoken_blocks` can place in VMEM (``KV`` the pool's own
    head count, ``rep`` query heads to each)."""
    return (
        paged_page_ok(page, D, itemsize)
        and paged_multitoken_blocks(KV, page, D, T, itemsize, rep=rep)
        is not None
    )


def decode_attention_ok(S: int, D: int, itemsize: int = 2) -> bool:
    """Trace-time gate mirroring ops.attention._pallas_ok: TPU backend,
    lane-friendly head dim, and the K+V slabs of one (batch, head) program
    fitting the kernel's VMEM budget (per-program cost is B/H independent)."""
    from .flash_attention import VMEM_RESIDENT_BYTES

    return (
        jax.default_backend() == "tpu"
        and D % 64 == 0
        and (S < S_BLOCK or S % S_BLOCK == 0)
        and 2 * S * D * itemsize <= VMEM_RESIDENT_BYTES
    )
