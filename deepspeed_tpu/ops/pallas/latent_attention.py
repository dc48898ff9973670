"""Pallas kernels over a LATENT paged cache (multi-head latent attention).

A latent family caches ONE row a token a layer, ``[c | rot(kr)]``, that all
query heads share: the keys are the whole row, the values its first
``v_width`` lanes. Served absorbed (``models/mistral4.py``), attention is
multi-query with ``H`` heads on that row:

    s[t, h, j] = sm_scale * qa[t, h] . row[j]            (over the row's W lanes)
    o[t, h]    = softmax_j(s) row[j, :v_width]           causal: j <= base + t

so a page is read ONCE for scores and values, where the per-head kernels of
``decode_attention.py`` read a K page and a V page for each kv-head.

:func:`latent_paged_attention` serves the decode step (``T`` = 1: a slot's 32
heads are the rows of one step, pages walked ``G`` at a time) and the chunk
program (``T`` = the chunk: ``TQ`` tokens x ``H`` heads are the rows of a
step). The grid walks the (slot, query block, page block) pairs the call owns
and no other: a list of items whose length is a value of the call.
:func:`latent_token_write` is the decode step's write into the one pool. The
pool is ``[L, P, 1, page, W]`` (or ``[P, 1, page, W]``) with ``W`` a whole
number of 128-lane tiles where the kernels run
(``serving/kv_cache.pool_stored_shape`` says why): the lanes past the
family's row are zeros and the query is zero there too.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .decode_attention import _pool_block_spec, _pool_dims, item_pages, items_of_rows, own_blocks

DECODE_KEYS = 2048   # keys a grid step of the decode shape holds (32 rows: the page DMAs are the step)
CHUNK_KEYS = 512     # ... and of the chunk shape
CHUNK_ROWS = 1024    # query rows (tokens x heads) a grid step of the chunk shape holds
# What a step may count in VMEM: the chunk shape's score and probability tiles
# ([CHUNK_ROWS, CHUNK_KEYS] float32 twice and once in the pool's type) beside
# its query, output, accumulator and page buffers come to about 11 MiB.
LATENT_VMEM_BYTES = 32 * 1024 * 1024


def latent_blocks(H: int, page: int, T: int, n_pages: int):
    """(query tokens, pages) one grid step holds, from the shapes alone: as
    many tokens as keep ``TQ * H`` rows within ``CHUNK_ROWS`` (a divisor of
    ``T``), and pages up to ``DECODE_KEYS`` keys where the rows are a quarter of
    that or fewer, ``CHUNK_KEYS`` beyond (a power of two, at most the table)."""
    TQ = max(t for t in range(1, min(T, max(1, CHUNK_ROWS // H)) + 1) if T % t == 0)
    keys = DECODE_KEYS if TQ * H <= CHUNK_ROWS // 4 else CHUNK_KEYS
    G = max(1, min(keys // page, n_pages))
    return TQ, 1 << (G.bit_length() - 1)


def latent_walk(base, T: int, TQ: int, GP: int, n_blk: int, xp=jnp):
    """Page blocks each (slot, query block) of a call owns, ``[B, T // TQ]``:
    ``decode_attention.own_blocks``, the ONE walk rule of both paged kernel
    families, for a query block's ``TQ`` tokens (``base`` ``[B]``). The wrapper
    and, with ``xp=np``, the scheduler's counters reckon by it."""
    at = base[:, None] + TQ * xp.arange(T // TQ)[None, :]
    return own_blocks(at, TQ, GP, n_blk, xp)


def latent_walk_steps(base, H: int, page: int, T: int, n_pages: int):
    """(grid steps one kernel call takes, steps of the rectangle ``slots x
    query blocks x page blocks`` that bounds them: what full slots take) for
    slots whose queries start at ``base`` ``[B]``; host arithmetic."""
    TQ, G = latent_blocks(H, page, T, n_pages)
    n_blk = -(-n_pages // G)
    own = latent_walk(np.asarray(base, np.int64), T, TQ, G * page, n_blk, xp=np)
    return int(own.sum()), own.size * n_blk


def _latent_kernel(_row_ref, blk_ref, at_ref, _page_ref, q_ref, *rest, sm_scale: float,
                   G: int, TQ: int, H: int, v_width: int, n_blk: int):
    """Online softmax over one slot's pages for the ``TQ`` query tokens of one
    query block (rows ``t * H + h``). The grid is the call's ITEMS, one a
    (slot, query block, page block) the call owns, a query block's page blocks
    in a row: step ``s`` holds block ``blk[s]`` for the query block whose first
    token sits at ``at[s]``. The (m, l, acc) scratch persists over a query
    block's items, reset at its block 0 and emitted at the last block it
    reaches; every step computes, so the next item's pages, and the next query
    block's queries, arrive under a computed step. A block every query of the
    step sees whole takes the branch that builds no mask."""
    k_refs, (o_ref, m_ref, l_ref, acc_ref) = rest[:G], rest[G:]
    step = pl.program_id(0)
    j, at = blk_ref[step], at_ref[step]          # the page block; position of the step's first query
    GP = G * k_refs[0].shape[1]
    last_blk = jnp.minimum(jax.lax.div(at + (TQ - 1), GP), n_blk - 1)

    @pl.when(j == 0)
    def _reset():
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def update(masked: bool):
        q = q_ref[0]                                             # [TQ * H, W]
        k = jnp.concatenate([r[0] for r in k_refs], axis=0)      # [GP, W]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * sm_scale                                             # [TQ * H, GP]
        if masked:
            key = jax.lax.broadcasted_iota(jnp.int32, (1, GP), 1) + j * GP
            t = 0
            if TQ > 1:
                row = jax.lax.broadcasted_iota(jnp.int32, (TQ * H, 1), 0)
                t = (
                    jax.lax.shift_right_logical(row, H.bit_length() - 1)
                    if H & (H - 1) == 0 else jax.lax.div(row, H)
                )
            s = jnp.where(key <= at + t, s, -1e30)
        m_prev, l_prev = m_ref[...], l_ref[...]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        corr = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur)
        m_ref[...] = m_cur
        l_ref[...] = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jnp.dot(
            p.astype(k.dtype), k[:, :v_width], preferred_element_type=jnp.float32
        )

    whole = (j + 1) * GP - 1 <= at
    pl.when(whole)(functools.partial(update, False))
    pl.when(jnp.logical_not(whole))(functools.partial(update, True))

    @pl.when(j == last_blk)
    def _emit():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def _walk_items(block_tables, base, T: int, TQ: int, G: int, page: int):
    """The call's items in the order the grid walks them, padded to the
    rectangle ``B * nq * n_blk`` (what a call of full slots owns): per item
    the row ``b * nq + i`` of its (slot, query block), its page block, the
    position of the query block's first token and the ``G`` pages the inputs
    hold (past the last page the query block reaches, the page the input held
    a block ago, so nothing is fetched; in block 0 the slot's first pages),
    and ``[1]`` the count of real items. Compares and sums over ``[items,
    rows]`` and ONE gather from the table, no search and no loop: the decode
    shape's call sits in a conditional a layer, where nothing folds.
    The two steps are ``decode_attention``'s, which the per-head kernels'
    walk takes too (``items_of_rows``: a row's values spread over its items;
    ``item_pages``: the pages an item's inputs hold): ONE walk for both kernel
    families, whose rows differ (a query block here, a head block there)."""
    B, n_pages = block_tables.shape
    nq, n_blk = T // TQ, -(-n_pages // G)
    own = latent_walk(base, T, TQ, G * page, n_blk).reshape(-1)             # [B * nq]
    ends = jnp.cumsum(own)
    starts = ends - own
    r = jnp.arange(B * nq, dtype=jnp.int32)
    at = jnp.repeat(base, nq) + (r % nq) * TQ
    per_row = jnp.stack([                                                   # of an item's (slot, query block):
        r, starts, at,                                                      # its row, its first item, its first query
        jnp.minimum((at + (TQ - 1)) // page, n_pages - 1),                  # the last page it reaches
        (r // nq) * n_pages,                                                # where its slot's table row starts
    ])
    s, (row, first, at, last, table) = items_of_rows(starts, ends, per_row, B * nq * n_blk)
    blk = jnp.minimum(s - first, n_blk - 1)
    pages = item_pages(block_tables, table, blk, last, G)
    return row, blk, at, pages.reshape(-1), ends[-1:].astype(jnp.int32)


def latent_paged_attention(
    q: jnp.ndarray,             # [B, T, H, W] absorbed queries, zero past the family's row
    pool: jnp.ndarray,          # [P, 1, page, W] latent page pool, or [L, P, 1, page, W]
    block_tables: jnp.ndarray,  # [B, n_pages] i32 pool-page ids per slot
    base: jnp.ndarray,          # [B] i32: query t of slot b sits at position base[b] + t
    v_width: int,               # the row's first lanes that are the values
    sm_scale: float,
    interpret: bool = False,
    layer: Optional[int] = None,
    name: Optional[str] = None,
) -> jnp.ndarray:
    """``T``-token causal attention against a latent paged cache → ``[B, T,
    H, v_width]``; the tokens' own rows must already be in the pool
    (update-then-attend). ``T`` = 1 is the decode step (``base`` the slot's
    cached length: one query block a slot), more the chunk program. The grid
    is as long as the call's own walk (:func:`_walk_items`): its bound is a
    value of the call. ``name`` is the kernel's name in a trace (the roofline
    readers find it by that)."""
    B, T, H, W = q.shape
    one, page = _pool_dims(pool, layer)
    if one != 1 or pool.shape[-1] != W:
        raise ValueError(
            f"latent_paged_attention: a latent pool has one row a token of the "
            f"query's width {W}; got {list(pool.shape)}"
        )
    n_pages = block_tables.shape[1]
    TQ, G = latent_blocks(H, page, T, n_pages)
    nq, n_blk = T // TQ, -(-n_pages // G)
    row, blk, at, pages, n_items = _walk_items(
        jnp.asarray(block_tables, jnp.int32), jnp.asarray(base, jnp.int32), T, TQ, G, page
    )

    def page_spec(g):
        return _pool_block_spec(
            (1, None, page, W), lambda s, row, blk, at, pages: (pages[s * G + g], 0, 0, 0), layer
        )

    def qo_spec(width):
        return pl.BlockSpec((1, TQ * H, width), lambda s, row, blk, at, pages: (row[s], 0, 0))

    kernel = functools.partial(
        _latent_kernel, sm_scale=float(sm_scale), G=G, TQ=TQ, H=H, v_width=int(v_width), n_blk=n_blk
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n_items[0],),
            in_specs=[qo_spec(W)] + [page_spec(g) for g in range(G)],
            out_specs=qo_spec(v_width),
            scratch_shapes=[
                pltpu.VMEM((TQ * H, 1), jnp.float32),
                pltpu.VMEM((TQ * H, 1), jnp.float32),
                pltpu.VMEM((TQ * H, v_width), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B * nq, TQ * H, v_width), q.dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=LATENT_VMEM_BYTES),
        name=name,
        interpret=interpret,
    )(row, blk, at, pages, q.reshape(B * nq, TQ * H, W), *([pool] * G))
    return out.reshape(B, T, H, v_width)


def _latent_write_kernel(pidx_ref, poff_ref, new_ref, page_ref, out_ref):
    """One slot's current page with the rows of the slot's new tokens
    replaced (``decode_attention._token_write_kernel``, one pool)."""
    b, t = pl.program_id(0), pl.program_id(1)
    page = out_ref.shape[-2]
    row = jax.lax.broadcasted_iota(jnp.int32, (page, 1), 0)
    here_page = pidx_ref[b, t]
    x = page_ref[0]
    for u in range(new_ref.shape[1]):  # in order: a later token wins
        here = row == jnp.where(pidx_ref[b, u] == here_page, poff_ref[b, u], -1)
        x = jnp.where(here, new_ref[0, u], x)
    out_ref[0] = x


def latent_token_write(
    pool: jnp.ndarray,   # [L, P, 1, page, W], updated in place (donate it)
    layer: int,          # static
    pidx: jnp.ndarray,   # [B] or [B, T] i32 page of each new token
    poff: jnp.ndarray,   # the same shape: its offset in that page
    rows: jnp.ndarray,   # [B, 1, W] or [B, T, 1, W] the new tokens' rows
    interpret: bool = False,
) -> jnp.ndarray:
    """The decode step's one-token write into the latent pool (or ``T``
    tokens a slot) as ONE device operation: ``decode_attention.
    paged_token_write``'s plan on one pool. Same elements, same values as
    ``pool.at[layer, pidx[b, t], 0, poff[b, t]].set(rows[b, t, 0])``."""
    page, W = pool.shape[3:]
    if pidx.ndim == 1:
        pidx, poff, rows = pidx[:, None], poff[:, None], rows[:, None]
    B, T = pidx.shape
    block = pl.BlockSpec(
        (None, 1, None, page, W), lambda b, t, pidx, poff: (layer, pidx[b, t], 0, 0, 0)
    )
    new = pl.BlockSpec((1, T, 1, W), lambda b, t, pidx, poff: (b, 0, 0, 0))
    return pl.pallas_call(
        _latent_write_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(B, T), in_specs=[new, block], out_specs=block,
        ),
        out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        input_output_aliases={3: 0},  # the pool, after the two tables and the new rows
        name="kv_token_write",
        interpret=interpret,
    )(
        jnp.asarray(pidx, jnp.int32), jnp.asarray(poff, jnp.int32),
        rows.astype(pool.dtype).reshape(B, T, 1, W), pool,
    )


def latent_attention_ok(page: int, W: int, itemsize: int = 2) -> bool:
    """Trace-time gate for the three kernels here: a TPU, whole lane tiles a
    row, a sublane-aligned page."""
    return (
        jax.default_backend() == "tpu"
        and W % 128 == 0
        and page % max(1, 32 // max(1, itemsize)) == 0
    )
