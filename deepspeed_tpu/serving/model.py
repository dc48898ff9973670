"""Static-shape serving programs over the paged KV pool.

Compiled-once programs over a model FAMILY's own building blocks, so that
for the gpt2 family serving is BIT-IDENTICAL to per-request ``generate``.
The programs hold what every family shares (the pool writes, the paged
attention, the sampling); the model's module gives the rest through
``cfg.serving_family()`` (:class:`Family` below: ``models/gpt2.GPT2Family``,
``models/exaone_moe.ExaoneFamily``, ``models/mistral4.Mistral4Family``,
``models/longcat_flash.LongcatFlashFamily``,
``models/phi4flash.Phi4FlashFamily``, ``models/zaya.ZayaFamily``,
``models/qwen3_next.Qwen3NextFamily``, ``models/xing4.Xing4Family``). A sub-block is of one of five KINDS
(``fam.kinds``; without it every one is the first): an attention that writes
its own K/V (``attn``), a state-space mixer over a per-slot recurrent state
(``ssm``: the third kind of state beside pages and rings, :func:`_ssm_block`),
a linear attention over a per-slot MATRIX state a head (``lin``: the same kind
of state, another shape and recurrence, :func:`_lin_block`), and two that keep
nothing: a cross-attention that reads the pages another
sub-block wrote (``cross``) and a gated memory unit that reads another's scan
output of the same token (``gmu``). The programs:

- :func:`paged_prefill` — one request's prompt (right-padded to the static
  prefill width) through the model, K/V written page-granularly into the
  slot's pool pages, first token sampled at the true last prompt position.
- :func:`paged_decode_step` — one token for EVERY slot: scatter the new K/V
  into each slot's current page, attend through the block table
  (``ops.attention.paged_cached_attention``), sample per-slot with per-slot
  keys. All shapes are functions of the serving config only — finished
  sequences vacating slots and new prompts arriving never retrace.
- :func:`paged_mixed_step` — the chunk program: one chunk of ONE slot's
  incremental prefill and one token for every decoding slot, both sets of
  rows through every weight once and parted for attention alone (a chunk
  that finds no decode step to ride takes it with idle decode rows);
  :func:`paged_verify_step` is the decode step's speculative twin.
- :func:`generate_padded` — the bucket-padded analog of ``gpt2.generate``
  for the offline ``InferenceEngine.generate`` path: prompt length is a
  TRACED scalar, so every length in a bucket reuses one executable.

Why bit-identical: every op is row-independent across batch/slots, padded
key positions contribute exact zeros through the masked softmax
(``exp(-1e30 - m)`` underflows to 0.0), and garbage K/V at positions beyond
a slot's length is either masked or overwritten by the decode write before
that position is ever attended. The attention lines below deliberately
mirror ``gpt2._attention_cached`` (same einsums, same casts, same mask
compare) so the two paths cannot drift.

Why the layer loop is UNROLLED (ISSUE 10 perf fix): scanning the pools as
``lax.scan`` xs/ys stacks a freshly-written FULL pool as the scan output —
every program call paid O(pool bytes) of copy traffic even with donation
(~170 ms/step at a 151 MB pool, linear in ``num_pages``). With a static
python loop the pools are plain dataflow values updated by per-layer
scatters into donated buffers: per-call cost scales with the pages
actually touched, not the pool (38x at gpt2-tiny on the CPU), which is the
whole point of paging. n_layer is static and small, so the unroll's
compile-time cost is bounded; the arithmetic per layer is unchanged, so
token streams are unaffected (the equivalence tests pin this).
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..models import gpt2
from ..models.gpt2 import KVCache
from ..ops.quantizer import (
    dequantize_kv_pages,
    kv_page_scale,
    quantize_kv_pages,
    quantize_kv_token,
)
from ..ops.sampling import sample_logits
from ..telemetry import parts

PyTree = Any


# ---------------------------------------------------------------------------
# int8 KV pages (ISSUE 12): pool write helpers shared by all four programs.
#
# ``scales`` is the [L, P, KV, 2] per-page scales pool (None = full-precision
# pools, every path below reduces to the historical scatter). The scale
# discipline that keeps the PR-10 equivalence contracts intact under
# quantization: a page's scale is ESTABLISHED exactly once — by the
# whole-page write that fills it (prefill / chunk-prefill / COW recompute)
# or by the token write at offset 0 — and FROZEN until the page is refilled
# from offset 0 again. Later token writes code against the frozen scale, so
# a write never re-codes earlier positions: scattering T draft tokens then
# attending (the verify step) produces bit-identical pool state to writing
# them one step at a time (the decode step), which is what makes the
# speculative stream provably equal to sequential int8 decode. Rejected
# drafts re-write from the accept point next step; a re-write at offset 0
# re-establishes the scale, and every stale position is overwritten before
# anything attends it — exactly the bf16 rollback-by-overwrite argument.
# ---------------------------------------------------------------------------


@parts.scoped("kv.write")
def _write_pool_pages(pool, scales, l, page_ids, chunks, sidx):
    """Whole-page scatter: ``chunks [n_pp, KV, page, D]`` (compute precision)
    into layer ``l``'s pages; quantize-at-write when the pool is int8.
    ``sidx``: 0 = K scales, 1 = V. → (pool, scales, attend_chunks) where
    ``attend_chunks`` is what attention must read for these tokens — the
    dequantized codes when quantized (the cache serves DEQUANTIZED values;
    prefill attending the exact pre-quantization values would make the
    first token inconsistent with every later read of the same pages)."""
    if scales is None:
        return pool.at[l, page_ids].set(chunks.astype(pool.dtype)), None, chunks
    codes, s = quantize_kv_pages(chunks)
    pool = pool.at[l, page_ids].set(codes)
    scales = scales.at[l, page_ids, :, sidx].set(s)
    return pool, scales, dequantize_kv_pages(codes, s)


@parts.scoped("kv.write")
def _scatter_tokens(k_pool, v_pool, l, pidx, poff, k_vals, v_vals, shared=False):
    """``k_vals`` / ``v_vals [..., KV, D]`` to (layer ``l``, page
    ``pidx[...]``, every kv head, offset ``poff[...]``) of the ``[L, P, KV,
    page, D]`` pools → ``(k_pool, v_pool)``; ``pidx`` / ``poff`` are ``[B]``
    (the decode step) or ``[B, T]`` (the verify step).

    Where the paged kernels run it is one Pallas call for both pools
    (``paged_token_write``: a slot's pages in, the new rows replaced, the
    pages out, the pools aliased), which asks no layout of the pools, so
    that they stay as the kernels read them between two of them. Elsewhere
    it is a scatter whose kv-head axis is INDEXED (an iota), not sliced, so
    that its update window is the minor dim D alone (with ``pool.at[l, pidx,
    :, poff]`` the window (KV, D) straddles the page dim). On a TPU a scatter
    wants the page index minor-most and XLA re-lays a layer or the whole
    pool out around every kernel for it: ``ProgramSet.program_census``
    refuses such a program. The indices are always in range (an idle slot or
    an out-of-budget draft points at the scratch page); where two tokens
    name the same element, which only happens on the scratch page, which one
    stays is not defined. Same elements, same values either way. ``shared``:
    the kernel's calls of a program, one a layer, may share one traced and
    lowered kernel (the layer an operand; the mixed step asks for it, the
    pool's depth decides)."""
    from ..ops.pallas.decode_attention import (
        paged_token_write,
        paged_token_write_ok,
    )

    KV, D = k_vals.shape[-2:]
    T = 1 if pidx.ndim == 1 else pidx.shape[1]
    if paged_token_write_ok(KV, k_pool.shape[3], D, k_pool.dtype.itemsize, T):
        return paged_token_write(
            k_pool, v_pool, l, pidx, poff, k_vals, v_vals, shared=shared
        )
    at = (l, pidx[..., None], jnp.arange(KV), poff[..., None])
    return (
        k_pool.at[at].set(k_vals.astype(k_pool.dtype)),
        v_pool.at[at].set(v_vals.astype(v_pool.dtype)),
    )


@parts.scoped("kv.write")
def _token_codes(scales, l, pidx, poff, vals, sidx):
    """What a one-token write stores for ``vals [B, KV, D]`` → ``(codes,
    scales)``: the values themselves, or, for an int8 pool, their codes
    under the page's scale. Offset 0 establishes that scale from this token;
    any other offset codes against the frozen scale."""
    if scales is None:
        return vals, None
    s_old = scales[l, pidx, :, sidx]                       # [B, KV]
    s = jnp.where((poff == 0)[:, None], kv_page_scale(vals), s_old)
    return quantize_kv_token(vals, s), scales.at[l, pidx, :, sidx].set(s)


def _write_pool_tokens(cache, l, pidx, poff, k_vals, v_vals, shared=False):
    """One-token write: ``k_vals`` / ``v_vals [B, KV, D]`` to (layer ``l``,
    page ``pidx[b]``, offset ``poff[b]``) of both paged pools, quantized at
    write when they are int8 → the cache."""
    k_vals, scales = _token_codes(cache.scales, l, pidx, poff, k_vals, 0)
    v_vals, scales = _token_codes(scales, l, pidx, poff, v_vals, 1)
    k_pool, v_pool = _scatter_tokens(
        cache.k, cache.v, l, pidx, poff, k_vals, v_vals, shared
    )
    return cache._replace(k=k_pool, v=v_pool, scales=scales)


def _write_cache_pages(cache, l, page_ids, k_c, v_c, dtype=None):
    """:func:`_write_pool_pages` for a chunk's K and V ``[1, S, KV, D]`` (cast
    to ``dtype`` where one is given) into layer ``l`` of the paged pools →
    ``(cache, k_att, v_att)``, what attention must read of either."""
    page = cache.k.shape[3]
    cast = (lambda x: x) if dtype is None else (lambda x: x.astype(dtype))
    k_pool, scales, k_att = _write_pool_pages(cache.k, cache.scales, l, page_ids, _page_chunks(cast(k_c), page), 0)
    v_pool, scales, v_att = _write_pool_pages(cache.v, scales, l, page_ids, _page_chunks(cast(v_c), page), 1)
    return cache._replace(k=k_pool, v=v_pool, scales=scales), k_att, v_att


def _gather_dense(k_pool_l, v_pool_l, block_tables, scales_l=None):
    """Gather each slot's pages into the dense ``[B, n, page, KV, D]`` view
    the jnp attention branches consume, dequantizing int8 pools through
    ``scales_l [P, KV, 2]``. Delegates to the dispatcher fallbacks' own
    gather (``ops.attention.gather_pool_pages``) so the serving-model jnp
    branches and the ops fallbacks can never disagree on the scale
    layout."""
    from ..ops.attention import gather_pool_pages

    kd, vd = gather_pool_pages(k_pool_l, v_pool_l, block_tables, scales_l)
    return jnp.swapaxes(kd, 2, 3), jnp.swapaxes(vd, 2, 3)


# ---------------------------------------------------------------------------
# what the programs ask of a model
# ---------------------------------------------------------------------------

class Family:
    """The protocol ``cfg.serving_family()`` returns (documentation; the
    families do not inherit from it). Shapes: ``h`` is the residual stream
    ``[B, S, E]``; ``positions`` is ``[B, S]`` or, one row, ``[S]``
    (``embed`` also takes ``[B]`` ids and positions, the decode step's,
    and then gives ``[B, 1, E]``). The stream's WIDTH is the family's: the
    programs pass ``h`` from ``embed`` through ``qkv`` and the rest of every
    sub-block to ``logits`` and never read its last axis. A family whose
    residual is several streams a token (Xing4.0's four, mHC) gives ``[B, S,
    n E]``, the streams side by side on the lanes, reads it through its
    pre-map in ``qkv``, owns the rest of the layer in ``after_attention`` and
    sums the streams in ``logits``; it states ``stream_row_width`` (``n E``:
    the gauge ``serving_hc_row_bytes``).

    - ``n_layer, n_head, n_kv_head, head_dim, vocab_size, n_positions,
      attn_impl``: geometry. ``n_layer`` counts the CACHED SUB-BLOCKS: what
      the programs loop over, the pool's layers and the kernels' layer index.
      For a model whose layer is one attention and one MLP that is its depth;
      a family whose layer holds two attentions (LongCat-Flash's double
      layer) gives twice its depth, ``layer(params, l)`` the l-th sub-block's
      weights, and ties the sub-blocks together through ``after_attention``.
    - ``kv_pools, v_width``: what a program needs to know of the cache (the
      fields of ``kv_cache.Cache``: docs/SERVING.md, "The cache"). 2: a
      K and a V pool of ``n_kv_head`` heads ``head_dim`` wide (``v_width`` is
      ``head_dim``). 1: a LATENT family, ONE pool of one row a token
      (``n_kv_head`` 1, ``head_dim`` the row's width) that every query head
      reads, whose first ``v_width`` lanes are the values; the cache then
      has ``v = None``, ``qkv`` gives the absorbed query ``[B,S,H,
      head_dim]``, the row ``[B,S,1,head_dim]`` and ``None``, ``attn_out``
      takes ``[B,S,H*v_width]``, the family states its ``sm_scale``, and the
      whole-prompt program attends per head through ``qkv_expanded(lp, h,
      positions, l) -> q, k, v, row`` and ``attn_out_expanded``.
    - ``windows``: per layer, how many keys a query reads, itself included
      (a sliding window, whose K/V live in the slot's RING of the window
      pools), or 0: every key before it (K/V paged under the block table).
    - ``sm_scale`` (optional for a two-pool family; a latent one states it):
      the scores' scale where it is not ``1 / sqrt(head_dim)``. A family whose
      cached head is a PAIR of published heads (``[k1 | k2]`` under the
      zero-padded queries ``[q1 | 0]``, ``[0 | q2]``: ``n_head`` is then ``2 *
      n_kv_head`` and the programs' ``rep`` 2) scales by the published head's
      width and takes the pairs apart in ``attn_out``: Phi-4-mini-flash's
      differential attention combines the two products; GPT-2, whose 64-wide
      heads are cached two to the 128 lanes (``models/gpt2.GPT2Family``: the
      rule is ``2 * head_dim == 128``, read from the config's shape), keeps
      head 2p's first half and head 2p + 1's second. The zeros add exactly 0,
      so the results are the published model's. An ODD head count gets one
      zero head behind the last, in the activations only (the projections
      stay as published and ``attn_out`` drops it again), and under TP a rank
      pairs its own heads: the pools' kv-heads are ``tp x`` a rank's pairs
      (``placement.ProgramSet`` builds its family from the per-rank config).
      An int8 cache carries ONE scale a page and cached head, so it is served
      a head a published head (``ServingEngine`` asks the config for
      ``per_head_cache()`` where it has one): a pair under one scale would
      quantise the quieter of its two heads coarser than it is today.
    - ``kinds`` (optional): per sub-block ``"attn"`` (the default: everything
      above), ``"ssm"``, ``"gmu"`` or ``"cross"``. With it the family gives
      ``sources`` (``{l: the sub-block a "cross" or "gmu" sub-block reads}``:
      the K/V pages of an ``"attn"`` one, which get no second write, or the
      scan output ``s`` of an ``"ssm"`` one, which travels in ``carry`` as
      ``{source: s}``), ``ssm_state`` (``(N, d_inner)``: a slot's scan state
      an ``"ssm"`` sub-block, float32), ``ssm_conv`` (the convolution's taps:
      ``ssm_conv - 1`` rows of ``d_inner`` are carried), ``ssm_impl``, the
      pieces ``ssm_in(lp, h) -> xs, z``, ``ssm_consts(lp) -> A [N, d_inner], D,
      w_conv, b_conv``, ``ssm_dt(lp, c) -> dt, B, C``, ``ssm_out(lp, s, z,
      tp_axis)``, ``gmu(lp, h, m, tp_axis)``, ``q_cross(lp, h, positions, l) ->
      q``, and ``stop_after``: ``None``, or the last sub-block a PROMPT row
      runs. Behind it only rows whose logits are sampled go on (a prompt's
      last row, the decode rows): the sub-blocks there write no state.
      ``windows`` is 0 for a sub-block that is no ``"attn"``.
      A ``"lin"`` sub-block (a linear attention under the gated delta rule,
      ``ops/pallas/gated_delta.py``; a family has ``"ssm"`` or ``"lin"``
      sub-blocks, not both) asks for ``lin_state`` (``(Hv, dk, dv)``: a slot's
      state a sub-block, float32, a matrix a value head), ``lin_conv`` (``(K,
      channels)``: the convolution's taps and the channels it runs over, ``K -
      1`` rows of which are carried), ``lin_impl``, and the pieces
      ``lin_in(lp, h) -> m [..., channels], rest`` (the norm and the
      projections; ``rest`` is whatever else of the row the later pieces
      need), ``lin_taps(lp) -> w_conv [channels, K]`` (no bias),
      ``lin_gates(lp, c, rest) -> q, k [..., Hk, dk], v [..., Hv, dv], g, beta
      [..., Hv]`` in float32 (``q``, ``k`` as the rule reads them) and
      ``lin_out(lp, o [..., Hv, dv], rest, tp_axis)``. ``g``'s RANK is the
      rule: ``[..., Hv]`` one decay a head (Gated DeltaNet), ``[..., Hv, dk]``
      a decay a key channel (KDA); :func:`_lin_block` and the kernels' entries
      branch on it at trace time, and a family of the second kind states
      ``lin_g_min``, the lower bound of its decays (the chunk kernel's form
      rests on it). ``"lin"`` sub-blocks beside ``kv_pools == 1`` are served
      (Ling-3.0-flash: ``cache.rec`` and ``cache.conv`` beside ONE latent
      pool; the pools' layers count each kind alone, :func:`_kv_homes`).
    - ``prefill_block``: 0, or the query rows the whole-prompt program
      attends at a time (where ``[H, Sp, Sp]`` scores would not fit).
    - ``sparse_layers`` / ``experts_held``: the layers (sub-blocks) that
      report the tokens each held expert got, and how many experts that is
      (every call's held products are one grouped kernel on a TPU, the
      masked einsums elsewhere; ``moe/expert_share.py``).
      ``zero_experts``: the router's identity columns, 0 for none; where it
      has some, a report is ``[experts_held + 1]``, the last entry the pairs
      that chose one of them. ``expert_groups`` (optional): > 1 where the
      router keeps some of that many groups a token; a report is then
      ``[experts_held + 1]`` too, the last entry the ROWS that kept a group
      this chip holds experts of (``group_rows`` on the leaves that carry
      the loads).
    - ``embed(params, ids, positions) -> h``
    - ``row_gathered`` (optional): the paths of the leaves ``embed`` gathers
      rows of. ``Placement.shard_params`` lays each row-major on the device,
      once, where the device's own order for its shape is another (a v5e's
      for a table whose rows are not whole lane tiles: GPT-2 XL's 1 600-wide
      ``wte`` and ``wpe``), and the programs take the leaf as it lies; else
      every program copies the whole table to gather its few rows
      (``serving_weight_relayout_bytes`` counts such copies, in any family).
    - ``layer(params, l) -> lp``: sub-block ``l``'s weights. Every program
      calls it once a sub-block and hands the SAME ``lp`` to ``qkv`` (or
      ``qkv_expanded``) and then to the rest of the sub-block, so a family
      that makes ``lp`` anew in each call (a dict of this call's own) may
      leave in it what its ``qkv`` computed and its ``after_attention``
      needs: the HAND-OVER. Xing4.0's attention sub-block computes its three
      maps from ``h`` in ``qkv`` and writes the attention's output back
      through two of them in ``after_attention`` (``lp["handed"]``); the
      programs know nothing of it.
    - ``qkv(lp, h, positions, l) -> q [B,S,H,D], k, v [B,S,KV,D]``: the
      norm before attention, the projections and whatever the family does
      to a head before it is cached (QK norm, rotary positions).
    - ``carry_width`` (optional; 0 or absent: none): an ``"attn"`` sub-block
      that CARRIES ROWS, the fourth kind of per-slot state (pages, rings,
      scan state, and rows under a paged layer). Its q, k and v of a call's
      first rows need rows of the call before (ZAYA's CCA: two kernel-2
      convolutions over the latent and a value shifted by one token), so the
      programs keep ``carry_width`` values a slot and ``"attn"`` sub-block in
      ``cache.carry`` (``[attn sub-blocks, slots, carry_width]`` in the
      cache's type; :func:`_qkv_carried`) and the
      family gives ``qkv`` in two pieces: ``attn_in(lp, h) -> p [B,S,Wp]``,
      what a row needs of the weights alone (the norm, the projections), and
      ``attn_mix(lp, p, prev [B, carry_width], positions, l) -> q, k, v, nxt
      [B,S,carry_width]``: each batch row a sequence that ``prev`` precedes
      (zeros: a request's start), ``nxt[:, t]`` what a sequence ending with
      row ``t`` hands to its next call. K and V are the FINISHED heads: the
      paged kernels read them as any grouped-query cache. Every program
      branches on it at TRACE time: a family that states none traces what it
      traced without.
    - ``attn_out(lp, o [B,S,H*D], tp_axis) -> [B,S,E]``
    - ``mlp(lp, h, l, valid, tp_axis) -> ([B,S,E], counts [experts_held] |
      None)``: the norm before it and the MLP or expert layer; ``valid``
      broadcasts against ``[B, S]`` (the rows that are real tokens).
    - ``logits(params, h [..., E]) -> [..., vocab]``: final norm and head.
    - ``after_attention(lp, h, o, l, valid, tp_axis, carry, attn_out) -> h,
      carry, counts`` (optional): the family OWNS the combination. Without
      it the rest of a sub-block is :func:`_after_attention`'s ``h + attn_out``
      then ``h + mlp``. With it the family takes the attention's output ``o``
      (through ``attn_out``: its own, or the one the program names, as the
      whole-prompt program of a latent family does) and whatever it carried
      from the sub-blocks before (``carry``: None at sub-block 0, any pytree
      after), and gives the stream, what it carries on, and its report or
      None. LongCat-Flash's shortcut: the expert layer reads sub-block 0's
      post-attention norm and is added after sub-block 1's dense FFN.
    """


def sub_block_kinds(fam) -> tuple:
    """Each sub-block's kind: the family's ``kinds``, or all ``"attn"``."""
    return getattr(fam, "kinds", None) or ("attn",) * len(fam.windows)


def _kv_homes(fam):
    """Per sub-block ``(windowed, index)``: an attention's index among the
    window pools' layers or among the paged pools', a state-space mixer's
    among the recurrent state's; a cross-attention has its source's home
    (and writes nothing there), a gated memory unit none."""
    homes, n_win, n_paged, n_ssm = [], 0, 0, 0
    for l, (kind, w) in enumerate(zip(sub_block_kinds(fam), fam.windows)):
        if kind == "attn":
            homes.append((bool(w), n_win if w else n_paged))
            n_win, n_paged = n_win + bool(w), n_paged + (not w)
        elif kind in ("ssm", "lin"):
            homes.append((False, n_ssm))
            n_ssm += 1
        else:
            homes.append(homes[fam.sources[l]] if kind == "cross" else (False, -1))
    return homes


def pool_layers(fam) -> tuple:
    """``(paged, window, recurrent)``: how many layers each kind of per-slot
    state has for this family."""
    kinds = sub_block_kinds(fam)
    attn = [w for k, w in zip(kinds, fam.windows) if k == "attn"]
    return sum(1 for w in attn if not w), sum(1 for w in attn if w), kinds.count("ssm") + kinds.count("lin")


def _sm_scale(fam, D: int):
    """The scores' scale of a two-pool family: its own, or ``1 / sqrt(D)``."""
    s = getattr(fam, "sm_scale", None)
    return 1.0 / np.sqrt(D) if s is None else s


def ring_page_ids(slot, pages, ring: int):
    """Window-pool page of a slot's logical page ``pages``: slot ``b`` owns
    pages ``1 + b * ring .. (b + 1) * ring`` and logical page ``j`` lives in
    the ``j % ring``-th of them (page 0 is scratch, as in the paged pools)."""
    return 1 + slot * ring + pages % ring


def _window_view(slots, pos0, window: int, page: int, ring: int):
    """What a window layer's attention reads for slots whose first query sits
    at ``pos0 [B]``: → (``table [B, ring]`` the ring's pages in position
    order from the page that holds the first key the window reaches, the
    position ``off [B]`` of that page's first key, ``lo [B]`` the first key
    query 0 reads, counted from ``off``; it may be negative, and query ``t``
    reads from ``lo + t``)."""
    lo = pos0 - (window - 1)
    first = jnp.maximum(lo, 0) // page
    table = ring_page_ids(
        slots[:, None], first[:, None] + jnp.arange(ring)[None, :], ring
    )
    return table, first * page, lo - first * page


def _after_attention(fam, lp, h, o, l, valid, tp_axis, counts, carry=None, attn_out=None):
    """The rest of sub-block ``l`` in every program → ``(h, carry)``: the
    attention output into the residual stream (through ``attn_out``: the
    family's, unless a latent family attended per head), then the MLP or
    expert layer, whose held experts' token counts (if it reports any) join
    ``counts``. A family that owns the combination (``after_attention``, the
    :class:`Family` notes) does all of that itself and may hand a ``carry``
    to its next sub-block; for the others it passes through as it came (a
    family of several ``kinds`` keeps its sources' scan outputs there)."""
    own = getattr(fam, "after_attention", None)
    if own is not None:
        h, carry, c = own(lp, h, o, l, valid, tp_axis, carry, attn_out)
        if c is not None:
            counts.append(c)
        return h, carry
    with parts.part("attn.out"):  # the residual adds go with the part whose output they take in
        h = h + (attn_out or fam.attn_out)(lp, o, tp_axis)
    with parts.part("mlp"):
        m, c = fam.mlp(lp, h, l, valid, tp_axis)
        if c is not None:
            counts.append(c)
        return h + m, carry


def _window_views(fam, slots, pos0, page: int, ring: int):
    """:func:`_window_view` for each distinct window of the family's layers."""
    return {
        w: _window_view(slots, pos0, w, page, ring)
        for w in sorted(set(fam.windows) - {0})
    }


def _result(cache, token, counts):
    """A program's results: the cache, the token(s) and, for a family with
    expert layers, the tokens each held expert got ``[sparse layers,
    experts_held]`` (one entry more a layer where the router has identity
    columns: the pairs that chose one)."""
    return (cache, token) + ((jnp.stack(counts),) if counts else ())


# ---------------------------------------------------------------------------
# a latent family's one pool (``fam.kv_pools == 1``): the row a token that
# every head reads. The pool's lanes may be more than the row's (whole lane
# tiles where the kernels run, ``kv_cache.pool_stored_shape``): the row is
# written with zeros behind it and the query padded with zeros.
# ---------------------------------------------------------------------------

def _pad_lanes(x, lanes: int):
    """``x [..., w]`` with zeros up to ``lanes`` (itself when it has them)."""
    w = x.shape[-1]
    return x if w == lanes else jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, lanes - w)])


@parts.scoped("kv.write")
def _latent_write_pages(pool, l, page_ids, rows):
    """Whole-page scatter of a prompt chunk's rows ``[1, S, 1, w]`` into
    layer ``l``'s pages (the write of :func:`_write_pool_pages`, one pool)."""
    rows = _pad_lanes(rows, pool.shape[-1]).astype(pool.dtype)
    return pool.at[l, page_ids].set(_page_chunks(rows, pool.shape[3]))


@parts.scoped("kv.write")
def _latent_write_tokens(pool, l, pidx, poff, rows):
    """``rows [B, 1, w]`` (the decode step) or ``[B, T, 1, w]`` to (layer
    ``l``, page ``pidx``, offset ``poff``) of the latent pool: one Pallas
    call where the latent kernels run (``latent_token_write``), else the
    scatter of :func:`_scatter_tokens`."""
    from ..ops.pallas.latent_attention import latent_attention_ok, latent_token_write

    rows = _pad_lanes(rows, pool.shape[-1])
    if latent_attention_ok(pool.shape[3], pool.shape[4], pool.dtype.itemsize):
        return latent_token_write(pool, l, pidx, poff, rows)
    at = (l, pidx[..., None], jnp.arange(1), poff[..., None])
    return pool.at[at].set(rows.astype(pool.dtype))


def _unless_idle(live, attend, shape, dtype):
    """``attend()`` where ``live`` (a traced bool: a row of the call is real),
    else zeros of its shape, which nothing reads: a call of the mixed step
    that rode no decode step skips its decode rows' reads."""
    return lax.cond(live, attend, lambda: jnp.zeros(shape, dtype))


@parts.scoped("attn.core")
def _attend_latent(fam, q, pool, l, block_tables, base, name, live=None):
    """The absorbed queries ``q [B, T, H, w]`` against layer ``l`` of the
    (already updated) latent pool → ``[B, T, H * v_width]``; ``live`` as in
    :func:`_attend_decode_shaped`."""
    from ..ops.attention import latent_paged_cached_attention

    B, T, H, _ = q.shape
    if live is not None:
        return _unless_idle(
            live, lambda: _attend_latent(fam, q, pool, l, block_tables, base, name),
            (B, T, H * fam.v_width), q.dtype,
        )
    o = latent_paged_cached_attention(
        _pad_lanes(q, pool.shape[-1]), pool, block_tables, base, fam.v_width,
        impl=fam.attn_impl, sm_scale=fam.sm_scale, layer=l, name=name,
    )
    return o.reshape(B, T, H * fam.v_width).astype(q.dtype)


# ---------------------------------------------------------------------------
# the sub-blocks that are no attention of their own (``fam.kinds``): a
# state-space mixer over the recurrent state pools, a gated memory unit and a
# cross-attention over what other sub-blocks made
#
# ``cache.rec [Ls, slots, N, d_inner]`` float32 is the scan state a slot and
# "ssm" sub-block (the channels on the lanes: ``ops/pallas/selective_scan.py``;
# a family of linear attentions: ``[Ll, slots, Hv, dk, dv]``, :func:`_lin_block`)
# and ``cache.conv [Ls, slots, K - 1, d_inner]`` the convolution's last inputs. A request's first rows start from zeros whatever the slot
# held; a row that is padding, or an idle slot's, moves neither: its ``dt`` is
# 0 (``exp(0) h + 0``) and its convolution rows are not shifted.
# ---------------------------------------------------------------------------

def _passed(lp, a, tp_axis):
    """``attn_out`` of a mixer whose output is already projected."""
    return a


def _conv_carried(w_conv, b_conv, rows, conv, li, C: int, chunk, real):
    """The short causal convolution of a recurrent sub-block over ``rows [C +
    B, d]`` (the chunk's ``C``, then a row a slot) behind the rows its pool
    ``conv [Ls, slots, K - 1, d]`` carries → (``c [C + B, d]``, the pool).
    ``chunk = (slot, fresh, n_real)``: the chunk's slot takes the ``K - 1``
    rows before its row ``n_real`` (zeros in front where ``fresh``: the
    request's first rows); a decoding slot's are shifted by its one row, an
    idle one's stay."""
    from ..ops.pallas.selective_scan import conv_rows

    K = w_conv.shape[-1]
    cs = []
    if C:
        slot, fresh, n_real = chunk
        prev = jnp.where(fresh, jnp.zeros((), conv.dtype), conv[li, slot])
        c, full = conv_rows(w_conv, b_conv, rows[:C], prev)
        # the next call's: the K - 1 rows before row ``n_real``
        conv = conv.at[li, slot].set(
            lax.dynamic_slice_in_dim(full, n_real, K - 1, 0).astype(conv.dtype)
        )
        cs.append(c)
    if real is not None:
        prev = conv[li]
        c, full = conv_rows(w_conv, b_conv, rows[C:, None], prev)
        conv = conv.at[li].set(
            jnp.where(real[:, None, None], full[:, 1:].astype(conv.dtype), prev)
        )
        cs.append(c[:, 0])
    return (cs[0] if len(cs) == 1 else jnp.concatenate(cs)), conv


def _ssm_block(fam, lp, h, cache, li, C: int = 0, chunk=None, real=None):
    """The state-space mixer of one sub-block (``li``-th of ``cache.rec``)
    over the rows of ``h`` (``[1, C + B, E]``, or the decode step's ``[B, 1,
    E]``): the first ``C`` rows are ONE slot's chunk, ``chunk = (slot, start,
    n_real)`` (rows from ``n_real`` on are padding; ``start`` 0: the request's
    first rows), the others a row a slot, ``real [B]`` the slots that hold a
    decoding request. Both sets of rows go through the projections once and
    part for the convolution and the scan alone. → (the mixer's output, shaped
    like ``h``; the scan's ``s [..., d_inner]`` before the gate, float32:
    what a gated memory unit reads; the cache)."""
    from ..ops.pallas.selective_scan import scan_rows, scan_step

    ssm, conv = cache.rec, cache.conv
    xs, z = fam.ssm_in(lp, h)
    rows = xs.reshape(-1, xs.shape[-1])
    A, D, w_conv, b_conv = fam.ssm_consts(lp)
    impl = getattr(fam, "ssm_impl", "auto")
    with parts.part("ssm.scan"):
        if C:
            slot, start, n_real = chunk
            fresh = start == 0
        c, conv = _conv_carried(w_conv, b_conv, rows, conv, li, C, C and (slot, fresh, n_real), real)
    dt, Bm, Cm = fam.ssm_dt(lp, c)
    with parts.part("ssm.scan"):
        x = c.astype(jnp.float32)
        ss = []
        if C:
            keep = (jnp.arange(C) < n_real)[:, None]
            s, h1 = scan_rows(
                x[:C], jnp.where(keep, dt[:C], 0.0), Bm[:C], Cm[:C], A, D,
                jnp.where(fresh, 0.0, ssm[li, slot]), impl=impl,
            )
            ssm = ssm.at[li, slot].set(h1)
            ss.append(s)
        if real is not None:
            s, ssm = scan_step(
                x[C:], jnp.where(real[:, None], dt[C:], 0.0), Bm[C:], Cm[C:],
                A, D, ssm, li, impl=impl,
            )
            ss.append(s)
        s = (ss[0] if len(ss) == 1 else jnp.concatenate(ss)).reshape(xs.shape)
    return fam.ssm_out(lp, s, z), s, cache._replace(rec=ssm, conv=conv)


def _lin_block(fam, lp, h, cache, li, C: int = 0, chunk=None, real=None):
    """The linear attention of one sub-block (``li``-th of ``cache.rec [Ll,
    slots, Hv, dk, dv]`` float32 and ``cache.conv [Ll, slots, K - 1,
    channels]``) over the rows of ``h`` as :func:`_ssm_block` takes them: the
    first ``C`` ONE slot's chunk, ``chunk = (slot, start, n_real)``, the others
    a row a slot, ``real [B]`` the slots that decode. Both sets of rows go
    through the family's projections once and part for the convolution and the
    gated delta rule alone (``ops/pallas/gated_delta.py``: the chunk's rows in
    sub-chunks from the slot's carried state, zeros at ``start`` 0; a row a
    LIVE slot against the pool in place). A row that is padding has ``g`` and
    ``beta`` 0 and moves nothing. → (the mixer's output, shaped like ``h``;
    the cache)."""
    from ..ops.pallas import gated_delta

    lin, conv = cache.rec, cache.conv
    m, rest = fam.lin_in(lp, h)
    impl = getattr(fam, "lin_impl", "auto")
    with parts.part("lin.scan"):
        if C:
            slot, start, n_real = chunk
            fresh = start == 0
        c, conv = _conv_carried(
            fam.lin_taps(lp), jnp.zeros((), jnp.float32), m.reshape(-1, m.shape[-1]), conv, li,
            C, C and (slot, fresh, n_real), real,
        )
    q, k, v, g, beta = fam.lin_gates(lp, c, jax.tree.map(lambda x: x.reshape(-1, x.shape[-1]), rest))
    with parts.part("lin.scan"):
        os = []
        if C:
            keep = (jnp.arange(C) < n_real)[:, None]
            o, s1 = gated_delta.chunk_rows(
                q[:C], k[:C], v[:C], jnp.where(keep[..., None] if g.ndim == 3 else keep, g[:C], 0.0),   # g [..., Hv, dk]: a decay a channel
                jnp.where(keep, beta[:C], 0.0),
                jnp.where(fresh, 0.0, lin[li, slot]), impl=impl, g_min=getattr(fam, "lin_g_min", None),
            )
            lin = lin.at[li, slot].set(s1)
            os.append(o)
        if real is not None:
            o, lin = gated_delta.step(q[C:], k[C:], v[C:], g[C:], beta[C:], lin, li, real, impl=impl)
            os.append(o)
        o = os[0] if len(os) == 1 else jnp.concatenate(os)
    return fam.lin_out(lp, o.reshape(*h.shape[:-1], *o.shape[1:]), rest), cache._replace(rec=lin, conv=conv)


def _mixer_without_kv(fam, lp, h, l, li, positions, carry, cache, tp_axis, rows, attend):
    """The mixer of a sub-block that writes no K/V, by its kind → (its output
    ``[..., E]``, projected: :func:`_after_attention` takes it with
    :func:`_passed`; ``carry``; the cache). ``rows``: :func:`_ssm_block`'s
    ``(C, chunk, real)`` for this program's rows. ``attend(q, li)``: how this
    program's rows read the pages of a cross layer's source."""
    kind = fam.kinds[l]
    if kind == "lin":
        a, cache = _lin_block(fam, lp, h, cache, li, *rows)
        return a, carry, cache
    if kind == "ssm":
        a, s, cache = _ssm_block(fam, lp, h, cache, li, *rows)
        return a, {**(carry or {}), l: s}, cache     # what its gated memory units will read
    if kind == "gmu":
        return fam.gmu(lp, h, carry[fam.sources[l]], tp_axis), carry, cache
    with parts.part("attn.qkv"):
        q = fam.q_cross(lp, h, positions, l)
    o = attend(q, li)
    with parts.part("attn.out"):
        return fam.attn_out(lp, o, tp_axis), carry, cache


# ---------------------------------------------------------------------------
# an attention that carries rows (``fam.carry_width``): K and V paged like any
# other, and ``carry_width`` values a slot and sub-block from call to call
#
# ``cache.carry [La, slots, carry_width]`` (the cache's type): the whole-prompt program
# takes a slot's rows at the prompt's TRUE length, not the bucket's; the chunk
# program hands them from chunk to chunk and starts a request from zeros
# whatever the slot held; a decode row reads and shifts its slot's; an idle
# slot's row, or padding, moves nothing.
# ---------------------------------------------------------------------------

def _qkv_carried(fam, lp, h, positions, l, cache, C: int = 0, chunk=None, real=None):
    """``fam.qkv`` for sub-block ``l`` of a family that carries rows
    (``cache.carry``, whose layers are the ``"attn"`` sub-blocks in order), over the
    rows of ``h`` as :func:`_ssm_block` takes them: the first ``C`` ONE slot's
    chunk, ``chunk = (slot, start, n_real)``, the others a row a slot, ``real
    [B]`` the slots that decode. The projections see all rows once
    (``attn_in``); the chunk and the decode rows part for ``attn_mix`` alone.
    → ``(q, k, v, cache)``, q, k and v laid out like ``h``."""
    rows, ai = cache.carry, sub_block_kinds(fam)[:l].count("attn")
    with parts.part("attn.qkv"):
        p = fam.attn_in(lp, h)
    qkv = None
    if C:
        slot, start, n_real = chunk
        prev = jnp.where(start == 0, jnp.zeros((), rows.dtype), rows[ai, slot])
        *qkv, nxt = fam.attn_mix(lp, p[:, :C], prev[None], positions[..., :C], l)
        # the next call's: what the chunk's last REAL row hands on
        last = lax.dynamic_index_in_dim(nxt[0], jnp.maximum(n_real - 1, 0), 0, keepdims=False)
        rows = rows.at[ai, slot].set(jnp.where(n_real > 0, last.astype(rows.dtype), prev))
        p, positions = jnp.swapaxes(p[:, C:], 0, 1), positions[C:, None]
    if real is not None:
        prev = rows[ai]
        *qkv_d, nxt = fam.attn_mix(lp, p, prev, positions, l)
        rows = rows.at[ai].set(jnp.where(real[:, None], nxt[:, 0].astype(rows.dtype), prev))
        qkv = qkv_d if qkv is None else [
            jnp.concatenate([c, jnp.swapaxes(d, 0, 1)], axis=1) for c, d in zip(qkv, qkv_d)
        ]
    return (*qkv, cache._replace(carry=rows))


# ---------------------------------------------------------------------------
# paged prefill (one request into one slot's pages)
# ---------------------------------------------------------------------------

def _page_chunks(x, page: int):
    """``[1, S, KV, D]`` → ``[S // page, KV, page, D]`` rows of a pool."""
    _, S, KV, D = x.shape
    return jnp.swapaxes(x[0].reshape(S // page, page, KV, D), 1, 2)


@parts.scoped("attn.core")
def _attend_prompt_blocked(q, k, v, window: int, block: int, sm_scale=None):
    """Causal attention of a whole prompt chunk, ``block`` query rows at a
    time, so that the scores alive are ``[H, block, keys]``: all ``Sp`` keys
    on a full layer, a band of ``block + window`` on a window layer. q ``[1,
    Sp, H, D]``, k / v ``[1, Sp, KV, D]`` (v may be another width) → ``[1,
    Sp, H * Dv]``."""
    _, Sp, H, D = q.shape
    KV = k.shape[2]
    rep = H // KV
    scale = 1.0 / np.sqrt(D) if sm_scale is None else sm_scale
    pad = window if window else 0
    span = block + pad if window else Sp
    kp = jnp.pad(k[0], ((pad, 0), (0, 0), (0, 0)))
    vp = jnp.pad(v[0], ((pad, 0), (0, 0), (0, 0)))

    def rows(i):
        qi = lax.dynamic_slice_in_dim(q[0], i * block, block, 0).reshape(block, KV, rep, D)
        start = i * block if window else 0          # in the padded keys
        ki = lax.dynamic_slice_in_dim(kp, start, span, 0)
        vi = lax.dynamic_slice_in_dim(vp, start, span, 0)
        s = jnp.einsum("sgrd,tgd->grst", qi, ki, preferred_element_type=jnp.float32) * scale
        qpos = i * block + jnp.arange(block)[:, None]
        kpos = start - pad + jnp.arange(span)[None, :]
        seen = (kpos <= qpos) & (kpos >= 0)
        if window:
            seen = seen & (kpos > qpos - window)
        p = jax.nn.softmax(jnp.where(seen, s, -1e30), axis=-1).astype(vi.dtype)
        o = jnp.einsum("grst,tgd->sgrd", p, vi, preferred_element_type=jnp.float32)
        return o.reshape(block, H * v.shape[-1]).astype(q.dtype)

    return lax.map(rows, jnp.arange(Sp // block)).reshape(1, Sp, H * v.shape[-1])


def _attention_prefill_paged(fam, q, k_c, v_c, cache, page_ids, l):
    """Causal self-attention over the prompt chunk; K/V written to layer
    ``l``'s pages of the FULL pool (quantized at write when the cache has
    ``scales`` — the attention then reads the DEQUANTIZED chunk back, so the
    first sampled token is consistent with every later read of the same
    pages). → ``(o [B, Sp, H * D], cache)``.

    The chunk starts at position 0 of a fresh slot, so "the cache" IS the
    chunk — the dense causal einsum here is exactly ``_attention_cached``'s
    prefill path with ``pos = 0`` and ``Smax = Sp``."""
    B, Sp, H, D = q.shape
    KV = k_c.shape[2]

    # page-granular scatter: [Sp,KV,D] → [n_pp, KV, page, D] rows of the pool.
    # Whole pages are overwritten — a slot's pages are fresh at admission and
    # padded/garbage positions are masked until the decode write claims them;
    # padded page_ids point at the scratch page.
    cache, k_att, v_att = _write_cache_pages(cache, l, page_ids, k_c, v_c)
    if cache.scales is not None:
        # [n_pp, KV, page, D] dequantized → the [B, Sp, KV, D] chunk view
        k_c = jnp.swapaxes(k_att, 1, 2).reshape(B, Sp, KV, D)
        v_c = jnp.swapaxes(v_att, 1, 2).reshape(B, Sp, KV, D)
    if fam.prefill_block:
        o = _attend_prompt_blocked(
            q, k_c, v_c, 0, math.gcd(Sp, fam.prefill_block), getattr(fam, "sm_scale", None)
        )
        return o, cache

    scale = _sm_scale(fam, D)
    with parts.part("attn.core"):
        # a K head's group of ``H // KV`` query heads (1, or a pair family's 2)
        scores = jnp.einsum(
            "bsgrd,btgd->bgrst", q.astype(jnp.float32).reshape(B, Sp, KV, H // KV, D),
            k_c.astype(jnp.float32),
        ) * scale
        j_idx = jnp.arange(Sp)
        i_idx = jnp.arange(Sp)
        mask = j_idx[None, :] <= i_idx[:, None]
        scores = jnp.where(mask, scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1).astype(v_c.dtype)
        o = jnp.einsum("bgrst,btgd->bsgrd", probs, v_c)
        # H*D == E at TP=1; under the TP shard_map H is the per-rank head count
        # and the row-parallel projection restores the full embed dim
        return o.reshape(B, Sp, H * D).astype(q.dtype), cache


def _attention_prefill_window(fam, q, k_c, v_c, cache, li, slot, prompt_len,
                              window: int, ring: int):
    """A window layer of the whole-prompt program: a band of ``window`` keys
    a query, and of the prompt's pages only the ring's worth that a later
    query can still reach is written (ring page ``r`` takes the LAST logical
    page ``<=`` the prompt's last that lives in it; a ring page no prompt
    page lives in yet takes page 0's rows, which the decode writes replace
    before anything reads them). → ``(o, cache)``."""
    k_win, v_win = cache.win_k, cache.win_v
    Sp = q.shape[1]
    page = k_win.shape[3]
    n_last = (prompt_len - 1) // page
    r = jnp.arange(ring)
    src = jnp.clip(n_last - (n_last - r) % ring, 0, Sp // page - 1)
    ids = ring_page_ids(slot, r, ring)
    with parts.part("kv.write"):
        k_win = k_win.at[li, ids].set(_page_chunks(k_c, page)[src].astype(k_win.dtype))
        v_win = v_win.at[li, ids].set(_page_chunks(v_c, page)[src].astype(v_win.dtype))
    block = math.gcd(Sp, fam.prefill_block or Sp)
    return _attend_prompt_blocked(
        q, k_c, v_c, window, block, getattr(fam, "sm_scale", None)
    ), cache._replace(win_k=k_win, win_v=v_win)


def paged_prefill(
    cfg,                      # the model's config (``serving_family()``)
    params: PyTree,
    input_ids: jnp.ndarray,   # [1, Sp] right-padded to the static prefill width
    prompt_len: jnp.ndarray,  # traced i32: true prompt length
    cache,                    # ``kv_cache.Cache``, its pools of pages [L, P, KV, page, D]
    page_ids: jnp.ndarray,    # [Sp // page] i32 slot pages (scratch-padded)
    rng: jnp.ndarray,         # PRNGKey for the first sampled token
    temperature: float = 0.0,
    top_k: int = 0,
    top_p: float = 1.0,
    tp_axis: str = None,  # named mesh axis under the TP shard_map (ISSUE 14)
    slot: jnp.ndarray = None,  # traced i32: the slot, whose ring, state and carried rows are written
    ring: int = 0,        # static: pages of one slot's ring
):
    """→ (cache, first_token [1]) and, for a family with expert layers, its
    expert counts (:func:`_result`). Where the family states ``stop_after``,
    only the prompt's last row runs the sub-blocks behind it."""
    fam = cfg.serving_family()
    B, Sp = input_ids.shape
    positions = jnp.arange(Sp)
    with parts.part("embed"):
        h = fam.embed(params, input_ids, positions)
    valid = (positions < prompt_len) if fam.sparse_layers else None
    counts, carry = [], None
    kinds, stop = sub_block_kinds(fam), getattr(fam, "stop_after", None)
    carried = getattr(fam, "carry_width", 0)   # an attention that carries rows: ``cache.carry``
    kv_of = {}       # a cross layer's source: the prompt's K and V there
    stopped = False  # the stream is the prompt's last row alone

    def cross(q, li):
        """A cross layer's rows against its source's K/V: the one row left
        behind the stop reads the pages just written, a whole prompt the
        source's K and V of this call."""
        if stopped:
            return _attend_decode_shaped(
                fam, q, cache.k, cache.v, li, page_ids[None, :],
                jnp.reshape(prompt_len - 1, (1,)), q.dtype,
            )
        return _attend_prompt_blocked(
            q, *kv_of[li], 0, math.gcd(Sp, fam.prefill_block), fam.sm_scale
        )

    for l, (windowed, li) in enumerate(_kv_homes(fam)):
        lp = fam.layer(params, l)
        if kinds[l] != "attn":
            a, carry, cache = _mixer_without_kv(
                fam, lp, h, l, li, positions, carry, cache, tp_axis,
                (Sp, (slot, jnp.zeros((), jnp.int32), prompt_len), None), cross,
            )
            h, carry = _after_attention(fam, lp, h, a, l, valid, tp_axis, counts, carry, _passed)
            continue
        if fam.kv_pools == 1:
            # a latent family: the row into the one pool, attention per head
            # (expanded) in query blocks, its output straight into ``wo``
            with parts.part("attn.qkv"):
                q, k_, v, row = fam.qkv_expanded(lp, h, positions, l)
            cache = cache._replace(k=_latent_write_pages(cache.k, li, page_ids, row))
            o = _attend_prompt_blocked(
                q, k_, v, 0, math.gcd(Sp, fam.prefill_block), fam.sm_scale
            )
            h, carry = _after_attention(
                fam, lp, h, o, l, valid, tp_axis, counts, carry, fam.attn_out_expanded
            )
            continue
        if carried:
            q, k_, v, cache = _qkv_carried(
                fam, lp, h, positions, l, cache, Sp, (slot, jnp.zeros((), jnp.int32), prompt_len)
            )
        else:
            with parts.part("attn.qkv"):
                q, k_, v = fam.qkv(lp, h, positions, l)
        if windowed:
            o, cache = _attention_prefill_window(
                fam, q, k_, v, cache, li, slot, prompt_len, fam.windows[l], ring
            )
        else:
            pool_dt = h.dtype if cache.scales is not None else cache.k.dtype
            o, cache = _attention_prefill_paged(
                fam, q, k_.astype(pool_dt), v.astype(pool_dt), cache, page_ids, li
            )
            if l in getattr(fam, "sources", {}).values():
                kv_of[li] = (k_.astype(pool_dt), v.astype(pool_dt))   # by its paged home, as its readers find it
        h, carry = _after_attention(fam, lp, h, o, l, valid, tp_axis, counts, carry)
        if l == stop:
            # the sub-blocks behind write no state: only the row that is
            # sampled goes on (with its row of what the sources handed on)
            last = lambda x: lax.dynamic_slice_in_dim(x, prompt_len - 1, 1, 1)  # noqa: E731
            h, carry, positions, stopped = last(h), jax.tree.map(last, carry), prompt_len - 1 + jnp.arange(1), True

    with parts.part("head"):
        # [B, E] at the true last prompt position
        h_last = h[:, 0] if stopped else jnp.take(h, prompt_len - 1, axis=1)
        logits = fam.logits(params, h_last)
    with parts.part("sample"):
        first = sample_logits(logits, rng, temperature, top_k, top_p)
    return _result(cache, first, counts)


# ---------------------------------------------------------------------------
# paged decode step (one token for every slot)
# ---------------------------------------------------------------------------

@parts.scoped("attn.core")
def _attend_decode_shaped(fam, q, k_pool, v_pool, l, block_tables, pos,
                          out_dtype, scales_l=None, lo=None, name=None,
                          live=None, real=None):
    """ONE query token per slot against layer ``l`` of the paged cache →
    [B, 1, E]. The kernel takes the whole pools and the layer as a block
    index; only the ``jnp`` branch slices the layer out.

    The decode step's attention, factored so the speculative verify step
    can attend each of its T queries through EXACTLY this code — same
    shapes, same XLA reduction trees, same bits (ISSUE 10). ``scales_l``
    (= ``scales[l]``, [P, KV, 2]) dequantizes an int8 pool in the read
    path (ISSUE 12). ``lo`` (a window layer: the pools are the ring pools,
    the table and ``pos`` the ring's view, :func:`_window_view`) bounds the
    keys from below. ``name``: the kernel call's name in a trace, for a
    program that holds more kernels than this one; a named call into a deep
    pool shares its traced and lowered kernel with the program's other
    layers (``ops/pallas/decode_attention._shares_kernel``). ``live``:
    :func:`_unless_idle`'s, where the caller has one. ``real`` ([B] bool):
    the rows whose slot holds a decoding request, where the caller has
    observed them; the kernel walks only those rows' pages and leaves the
    others' output zeros."""
    B, S, H, D = q.shape  # S == 1
    E = H * D
    if live is not None:
        return _unless_idle(
            live,
            lambda: _attend_decode_shaped(
                fam, q, k_pool, v_pool, l, block_tables, pos, out_dtype,
                scales_l, lo, name, real=real,
            ),
            (B, S, E), out_dtype,
        )
    scale = _sm_scale(fam, D)
    if fam.attn_impl in ("auto", "pallas") or lo is not None:
        from ..ops.attention import paged_cached_attention

        o1 = paged_cached_attention(
            q[:, 0], k_pool, v_pool, block_tables, pos,
            impl=fam.attn_impl, sm_scale=scale, scales=scales_l, layer=l,
            lo=lo, name=name, live=real,
        )
        return o1.reshape(B, 1, E).astype(out_dtype)

    # jnp impl: gather the slot's pages into the dense view and run the exact
    # dense einsum of _attention_cached's decode path, with a per-slot mask.
    # NOT deduplicated into paged_cached_attention's jnp fallback on purpose:
    # that fallback mirrors cached_attention (f32 probs·V einsum), while an
    # attn_impl="jnp" config's generate decodes through _attention_cached's
    # own branch (probs cast to the CACHE dtype before the V einsum) — for
    # bf16 caches the two round differently, and serving must match whichever
    # path generate takes for the model's impl, bit for bit.
    kd, vd = _gather_dense(k_pool[l], v_pool[l], block_tables, scales_l)
    kd, vd = kd.reshape(B, -1, H, D), vd.reshape(B, -1, H, D)
    Smax = kd.shape[1]
    scores = jnp.einsum(
        "bshd,bthd->bhst", q.astype(jnp.float32), kd.astype(jnp.float32)
    ) * scale
    mask = jnp.arange(Smax)[None, :] <= pos[:, None]  # [B, Smax]
    scores = jnp.where(mask[:, None, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(vd.dtype)
    o = jnp.einsum("bhst,bthd->bshd", probs, vd)
    return o.reshape(B, S, E).astype(out_dtype)


class _RingWrites:
    """Where the decode and verify steps' tokens land in the window pools
    and what each query reads there, from ``seq_lens`` alone: the ring is
    statically the slot's. A slot that holds no decoding request (its table
    row is scratch: idle, or mid-prefill with its real row on the slot)
    writes to the scratch page, as it does in the paged pools; its ring may
    hold a prefill in flight."""

    def __init__(self, fam, seq_lens, block_tables, page: int, ring: int, T: int):
        B = seq_lens.shape[0]
        slots = jnp.arange(B, dtype=jnp.int32)
        pos = seq_lens[:, None] + jnp.arange(T)[None, :]               # [B, T]
        self.real = block_tables[:, 0] != 0
        self.pidx = jnp.where(self.real[:, None], ring_page_ids(slots[:, None], pos // page, ring), 0)
        self.poff = pos % page
        self.views = _window_views(fam, slots, seq_lens, page, ring)


def _attention_step_window(fam, q, k_c, v_c, cache, li, base, rw, window: int,
                           name=None, live=None):
    """A window layer of the decode (T = 1) or verify step: the T tokens'
    K/V into the slots' rings, then query ``t`` against the ring's view as a
    decode-shaped call, the keys bounded below. → ``(o [B, T, H * D], cache)``."""
    k_win, v_win = cache.win_k, cache.win_v
    T = q.shape[1]
    pidx, poff = (rw.pidx[:, 0], rw.poff[:, 0]) if T == 1 else (rw.pidx, rw.poff)
    k_new, v_new = (k_c[:, 0], v_c[:, 0]) if T == 1 else (k_c, v_c)
    k_win, v_win = _scatter_tokens(
        k_win, v_win, li, pidx, poff, k_new.astype(k_win.dtype), v_new.astype(v_win.dtype),
        shared=name is not None,
    )
    table, off, lo = rw.views[window]
    o = [
        _attend_decode_shaped(
            fam, q[:, t:t + 1], k_win, v_win, li, table, base + t - off,
            q.dtype, None, lo + t, name, live, rw.real,
        )
        for t in range(T)
    ]
    return (o[0] if T == 1 else jnp.concatenate(o, axis=1)), cache._replace(win_k=k_win, win_v=v_win)


def _attention_decode_paged(fam, q, k_c, v_c, cache, block_tables,
                            pos, pidx, poff, l, name=None, live=None, real=None):
    """One-token attention per slot against its paged cache (layer ``l`` of
    the FULL pool) → ``(o [B, 1, H * D], cache)``.

    ``pos[b]`` = tokens already cached for slot b (the new token's position);
    new K/V scatters to (page ``pidx[b]``, offset ``poff[b]``) before the
    gather, mirroring ``_attention_cached``'s update-then-attend order."""
    # [B,KV,D] values to (l, pidx[b], :, poff[b], :) — advanced indices around
    # the head slice put the batch dim first, matching the value layout.
    # Inactive slots target the scratch page.
    cache = _write_pool_tokens(
        cache, l, pidx, poff, k_c[:, 0], v_c[:, 0], shared=name is not None
    )
    o = _attend_decode_shaped(
        fam, q, cache.k, cache.v, l, block_tables, pos, q.dtype,
        cache.scales[l] if cache.scales is not None else None, name=name,
        live=live, real=real,
    )
    return o, cache


@parts.scoped("sample")
def _sample_slots(logits, keys, temperature, top_k, top_p):
    """One token a slot from ``logits [B, V]`` under per-slot ``keys [B, 2]``."""
    if not temperature or temperature <= 0.0:
        return jnp.argmax(logits.astype(jnp.float32), axis=-1)
    # per-slot keys: each row samples exactly as its own B=1 generate (vmap of
    # the PRNG is semantics-preserving, so slot b's draw equals the sequential
    # request's draw with the same key)
    return jax.vmap(
        lambda lg, kk: sample_logits(lg[None, :], kk, temperature, top_k, top_p)[0]
    )(logits, keys)


def paged_decode_step(
    cfg,
    params: PyTree,
    tokens: jnp.ndarray,        # [B] i32 last emitted token per slot
    seq_lens: jnp.ndarray,      # [B] i32 tokens already cached per slot
    cache,                      # ``kv_cache.Cache``, its pools of pages [L, P, KV, page, D]
    block_tables: jnp.ndarray,  # [B, n_pages] i32
    keys: jnp.ndarray,          # [B, 2] u32 per-slot sampling keys
    temperature: float = 0.0,
    top_k: int = 0,
    top_p: float = 1.0,
    tp_axis: str = None,  # named mesh axis under the TP shard_map (ISSUE 14)
    ring: int = 0,
):
    """→ (cache, next_tokens [B]) and expert counts as in :func:`_result`."""
    fam = cfg.serving_family()
    B = tokens.shape[0]
    page = cache.k.shape[3]
    # rows gathered by [B] indices, then the token axis: gathered by [B, 1]
    # ones the position table is copied whole and re-laid out every step
    with parts.part("embed"):
        h = fam.embed(params, tokens, seq_lens)
    positions = seq_lens[:, None]
    pidx = jnp.take_along_axis(
        block_tables, (seq_lens // page)[:, None], axis=1
    )[:, 0]
    poff = seq_lens % page
    rw = _RingWrites(fam, seq_lens, block_tables, page, ring, 1) if cache.win_k is not None else None
    real = block_tables[:, 0] != 0  # a slot that decodes holds a page; page 0 is scratch
    valid = real[:, None] if fam.sparse_layers else None
    counts, carry = [], None
    kinds, carried = sub_block_kinds(fam), getattr(fam, "carry_width", 0)

    for l, (windowed, li) in enumerate(_kv_homes(fam)):
        lp = fam.layer(params, l)
        if kinds[l] != "attn":
            a, carry, cache = _mixer_without_kv(
                fam, lp, h, l, li, positions, carry, cache, tp_axis,
                (0, None, real),
                lambda q, li: _attend_decode_shaped(
                    fam, q, cache.k, cache.v, li, block_tables, seq_lens, q.dtype,
                    real=real,
                ),
            )
            h, carry = _after_attention(fam, lp, h, a, l, valid, tp_axis, counts, carry, _passed)
            continue
        if carried:
            q, k_, v, cache = _qkv_carried(fam, lp, h, positions, l, cache, real=real)
        else:
            with parts.part("attn.qkv"):
                q, k_, v = fam.qkv(lp, h, positions, l)
        if fam.kv_pools == 1:
            cache = cache._replace(k=_latent_write_tokens(cache.k, li, pidx, poff, k_[:, 0]))
            o = _attend_latent(fam, q, cache.k, li, block_tables, seq_lens, "mla_paged_decode")
        elif windowed:
            o, cache = _attention_step_window(
                fam, q, k_, v, cache, li, seq_lens, rw, fam.windows[l]
            )
        else:
            pool_dt = h.dtype if cache.scales is not None else cache.k.dtype
            # named (the name the program's jit would give them anyway), so that
            # a deep pool's layers share ONE traced kernel each: 48 traces of a
            # kernel with 32 page inputs are 3 s of set-up (PERF.md, PR 56)
            o, cache = _attention_decode_paged(
                fam, q, k_.astype(pool_dt), v.astype(pool_dt), cache,
                block_tables, seq_lens, pidx, poff, li, name="decode_fn", real=real,
            )
        h, carry = _after_attention(fam, lp, h, o, l, valid, tp_axis, counts, carry)

    with parts.part("head"):
        logits = fam.logits(params, h[:, -1])
    nxt = _sample_slots(logits, keys, temperature, top_k, top_p)
    return _result(cache, nxt, counts)


# ---------------------------------------------------------------------------
# multi-token programs (ISSUE 10): speculative verify + chunked prefill.
#
# Both process T tokens per slot in ONE pass with the update-then-attend
# order of the decode step: scatter the T tokens' K/V into the pool, then
# attend with the causal per-query mask idx <= base + t. The batched
# matmuls (QKV, MLP, logits — where the decode step's memory-boundness
# leaves the MXU idle) are row-independent across the query dim, so each
# row's bits equal the single-token step's. Attention is the one op where
# the query count changes a REDUCTION shape (the softmax normalizer), and
# XLA's reduction tree — hence the low-order bits — depends on that shape;
# the verify step therefore attends its T queries as T unrolled
# single-token calls (exact decode-step shapes → exact decode-step bits,
# the property the greedy-equivalence contract rests on), while chunked
# prefill keeps the batched form and pins token-level identity in tests
# (chunking reorders prefill arithmetic at the ulp level by nature —
# trading bit-exact hidden states for not stalling the decode batch).
# ---------------------------------------------------------------------------


@parts.scoped("attn.core")
def _attend_multitoken_paged(fam, q, k_pool, v_pool, l, block_tables, base,
                             scales_l=None, lo=None, name=None):
    """Batched attention tail of the chunk-prefill program: q [B,T,H,D]
    against layer ``l`` of the (already updated) paged cache, masked per
    query; the pools arrive whole, as in ``_attend_decode_shaped``. The
    caller applies the output projection. ``scales_l`` dequantizes an int8
    pool (ISSUE 12); ``lo`` and ``name`` as in ``_attend_decode_shaped``.

    Dispatch mirrors ``_attention_decode_paged`` branch for branch; see the
    block comment above for why this form is token-identical but not
    bit-identical across chunking boundaries."""
    B, T, H, D = q.shape
    scale = _sm_scale(fam, D)
    if fam.attn_impl in ("auto", "pallas") or lo is not None:
        from ..ops.attention import paged_multitoken_cached_attention

        o = paged_multitoken_cached_attention(
            q, k_pool, v_pool, block_tables, base,
            impl=fam.attn_impl, sm_scale=scale, scales=scales_l, layer=l,
            lo=lo, name=name,
        )
        return o.reshape(B, T, H * D).astype(q.dtype)

    # jnp impl: dense gather + the exact einsum/cast structure of
    # _attention_decode_paged's jnp branch, extended to T query rows (see
    # that branch for why this is NOT deduplicated into the dispatcher)
    kd, vd = _gather_dense(k_pool[l], v_pool[l], block_tables, scales_l)
    kd, vd = kd.reshape(B, -1, H, D), vd.reshape(B, -1, H, D)
    Smax = kd.shape[1]
    scores = jnp.einsum(
        "bshd,bthd->bhst", q.astype(jnp.float32), kd.astype(jnp.float32)
    ) * scale
    mask = (
        jnp.arange(Smax)[None, None, :]
        <= base[:, None, None] + jnp.arange(T)[None, :, None]
    )  # [B, T, Smax]
    scores = jnp.where(mask[:, None, :, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(vd.dtype)
    o = jnp.einsum("bhst,bthd->bshd", probs, vd)
    # H*D == E at TP=1; the per-rank head slice under the TP shard_map
    return o.reshape(B, T, H * D).astype(q.dtype)


def _attention_verify_paged(fam, q, k_c, v_c, cache, block_tables,
                            base, pidx, poff, l, real=None):
    """T-token attention per slot: scatter every token's K/V to layer ``l``
    at (``pidx[b,t]``, ``poff[b,t]``), then attend query t at position
    ``base + t`` through the block table. Out-of-budget positions arrive
    with ``pidx`` already routed to the scratch page (see
    :func:`_verify_write_targets`). → ``(o [B, T, H * D], cache)``.

    The T attention calls are UNROLLED single-token ``_attend_decode_shaped``
    invocations — identical shapes to the decode step, hence identical bits;
    query t's mask (``idx <= base + t``) hides the already-scattered K/V of
    queries > t exactly as it hides any other stale cache content, so
    scatter-all-then-attend equals the sequential write-attend interleaving
    bit for bit. The QKV matmul before and the projection after stay batched
    over T — the arithmetic-intensity win speculation exists for."""
    T = q.shape[1]
    if cache.scales is None:
        # [B,T,KV,D] values to (l, pidx[b,t], :, poff[b,t], :), one write
        k_pool, v_pool = _scatter_tokens(cache.k, cache.v, l, pidx, poff, k_c, v_c)
        cache = cache._replace(k=k_pool, v=v_pool)
    else:
        # quantized pools write the T tokens in sequence: a token landing at
        # a page's offset 0 establishes the page's scale, and the tokens
        # after it IN THE SAME STEP must code against that scale — exactly
        # the order the sequential decode steps would have written them, so
        # the pool state (codes AND scales) is bit-identical to spec-off
        # int8 decode
        for t in range(T):
            cache = _write_pool_tokens(
                cache, l, pidx[:, t], poff[:, t], k_c[:, t], v_c[:, t]
            )
    scales_l = cache.scales[l] if cache.scales is not None else None
    o = jnp.concatenate(
        [
            _attend_decode_shaped(
                fam, q[:, t:t + 1], cache.k, cache.v, l, block_tables,
                base + t, q.dtype, scales_l, real=real,
            )
            for t in range(T)
        ],
        axis=1,
    )
    return o, cache


def _verify_write_targets(seq_lens, block_tables, page: int, T: int):
    """→ (pidx [B,T], poff [B,T]) write targets for tokens at positions
    ``seq_lens + t``. Positions past the block-table row (a draft running
    past the slot's reservation — the scheduler never emits those tokens)
    route to the scratch page instead of clamping into a REAL page, which
    would corrupt live cache entries."""
    B, W = block_tables.shape
    pos = seq_lens[:, None] + jnp.arange(T)[None, :]  # [B, T]
    page_i = pos // page
    safe = page_i < W
    gathered = jnp.take_along_axis(
        block_tables, jnp.minimum(page_i, W - 1), axis=1
    )
    pidx = jnp.where(safe, gathered, 0)  # 0 = scratch page
    return pidx, pos % page


def paged_verify_step(
    cfg,
    params: PyTree,
    tokens: jnp.ndarray,        # [B, T] col 0 = last emitted, cols 1.. = drafts
    seq_lens: jnp.ndarray,      # [B] i32 tokens already cached per slot
    cache,                      # ``kv_cache.Cache``, its pools of pages [L, P, KV, page, D]
    block_tables: jnp.ndarray,  # [B, W] i32
    tp_axis: str = None,  # named mesh axis under the TP shard_map (ISSUE 14)
    ring: int = 0,
):
    """Self-speculative verify (ISSUE 10): score T = k+1 tokens per slot in
    one forward pass → (cache, greedy [B, T]) and expert counts as in
    :func:`_result`.

    ``greedy[b, t]`` is the argmax next token after prefix ⊕ tokens[b, :t+1]
    — i.e. exactly what ``paged_decode_step`` would emit at that point. The
    host accepts the longest prefix where ``tokens[b, t+1] == greedy[b, t]``
    and emits ``greedy[b, :accepted+1]``: the output stream is bit-identical
    to sequential decode, drafts only change how many tokens one step
    yields. Rejected drafts leave K/V at positions past the accepted length;
    the next step's T-token scatter overwrites every such position before
    anything attends it (``new_base = base + accepted + 1 <= base + T``), so
    rollback is by construction, not by copy. (In a ring a rejected draft
    lands on the page of a position ``ring`` pages back, which no query of
    this step or a later one reaches.)"""
    fam = cfg.serving_family()
    if set(sub_block_kinds(fam)) != {"attn"} or getattr(fam, "carry_width", 0):
        # a rejected draft's rows cannot be taken back out of a recurrent state, nor out of carried rows
        raise NotImplementedError("the verify step serves families whose sub-blocks are all attentions that carry no rows")
    B, T = tokens.shape
    page = cache.k.shape[3]
    # clamp garbage positions (past the decode budget) into the embedding
    # table; their queries are never emitted and their writes go to scratch
    positions = jnp.minimum(
        seq_lens[:, None] + jnp.arange(T)[None, :], fam.n_positions - 1
    )
    with parts.part("embed"):
        h = fam.embed(params, tokens, positions)
    pidx, poff = _verify_write_targets(seq_lens, block_tables, page, T)
    rw = _RingWrites(fam, seq_lens, block_tables, page, ring, T) if cache.win_k is not None else None
    real = block_tables[:, 0] != 0
    valid = real[:, None] if fam.sparse_layers else None
    counts, carry = [], None

    for l, (windowed, li) in enumerate(_kv_homes(fam)):
        lp = fam.layer(params, l)
        with parts.part("attn.qkv"):
            q, k_, v = fam.qkv(lp, h, positions, l)
        if fam.kv_pools == 1:
            # one batched call: a latent family holds no bit-for-bit contract
            # with a per-request generate for T single-token calls to keep
            cache = cache._replace(k=_latent_write_tokens(cache.k, li, pidx, poff, k_))
            o = _attend_latent(fam, q, cache.k, li, block_tables, seq_lens, "mla_paged_verify")
        elif windowed:
            o, cache = _attention_step_window(
                fam, q, k_, v, cache, li, seq_lens, rw, fam.windows[l]
            )
        else:
            pool_dt = h.dtype if cache.scales is not None else cache.k.dtype
            o, cache = _attention_verify_paged(
                fam, q, k_.astype(pool_dt), v.astype(pool_dt), cache,
                block_tables, seq_lens, pidx, poff, li, real,
            )
        h, carry = _after_attention(fam, lp, h, o, l, valid, tp_axis, counts, carry)

    with parts.part("head"):
        logits = fam.logits(params, h)
    with parts.part("sample"):
        greedy = jnp.argmax(logits.astype(jnp.float32), axis=-1).astype(jnp.int32)
    return _result(cache, greedy, counts)


# A call of the mixed step whose decode rows are ALL idle (a chunk that rode no
# decode step) skips those rows' attention reads from this many slots on. On
# the chip (PERF.md, PR 35) the one-token kernel walks idle rows at about half
# a microsecond a grid step, slots x page blocks of them (33 us a layer at 8
# slots, 240 at 64, 420 at 48 slots of 13 blocks), and a conditional costs
# about 10 us a layer in every call that does carry rows: most calls.
SKIP_IDLE_READS_FROM_SLOTS = 16


def paged_mixed_step(
    cfg,
    params: PyTree,
    tokens: jnp.ndarray,        # [B] i32 last emitted token per slot
    seq_lens: jnp.ndarray,      # [B] i32 tokens already cached per slot
    input_ids: jnp.ndarray,     # [1, C] one chunk, right-padded past the prompt
    start: jnp.ndarray,         # traced i32: absolute position of input_ids[0, 0]
    prompt_len: jnp.ndarray,    # traced i32: the request's true prompt length
    cache,                      # ``kv_cache.Cache``, its pools of pages [L, P, KV, page, D]
    block_tables: jnp.ndarray,  # [B, W] i32: the decode rows' tables
    page_ids: jnp.ndarray,      # [C // page] i32: THIS chunk's slot pages
    chunk_row: jnp.ndarray,     # [1, W] i32: the prefilling slot's full table row
    keys: jnp.ndarray,          # [B, 2] u32 per-slot sampling keys
    rng: jnp.ndarray,           # PRNGKey for the prompt's first sampled token
    temperature: float = 0.0,
    top_k: int = 0,
    top_p: float = 1.0,
    tp_axis: str = None,  # named mesh axis under the TP shard_map (ISSUE 14)
    slot: jnp.ndarray = None,  # traced i32: the prefilling slot, whose ring, state and carried rows are written
    ring: int = 0,
):
    """One chunk of ONE slot's incremental prefill (ISSUE 10) and one token
    for every decoding slot, through every weight ONCE → (cache, tokens [B +
    1]: the slots' next tokens, then the chunk's) and expert counts as in
    :func:`_result` (ONE count of the call's real tokens a held expert: the
    chunk's and the active slots').

    The ``C`` chunk rows and the ``B`` decode rows are one ``[1, C + B, E]``
    activation through the norms, ``fam.qkv``, the output projection, the MLP
    or expert layer and the head: a step that carries both streams the weights
    once, and both sets of rows are far below the rows at which the matmuls
    stop being bound by that stream. They part for attention alone, each to
    the code its own program runs: the decode rows to
    :func:`paged_decode_step`'s write and one-token kernel under
    ``block_tables``, the chunk's to the page-granular write and the
    multi-token kernel under ``chunk_row``. The kernels carry the names they
    have in those programs' traces (``decode_fn``, ``chunk_fn``; a latent
    family's ``mla_paged_decode``, ``mla_paged_chunk``).

    The chunk: positions ``start .. start+C-1`` attend the slot's cached
    prefix (``< start``: earlier chunks or shared prefix pages) plus causal
    intra-chunk, K/V written page-granularly to ``page_ids`` (page-aligned
    because C is a page multiple; pages the chunk overruns are scratch-padded
    by the scheduler; an int8 pool's fresh pages are quantized here, their
    own scales written: the COW fork-by-recompute path rides this program). A
    window layer writes the chunk's pages into the slot's ring (whose
    ``ring`` pages hold the window before the chunk, the chunk and a page of
    slack, so no page a query of this chunk reads is overwritten) and reads
    the ring's view. Its token is sampled at the true last prompt position
    and only meaningful on the final chunk. Chunking reorders prefill
    arithmetic at the ulp level by nature (token identity is pinned in
    tests).

    The decode rows: as in :func:`paged_decode_step`. The prefilling slot's
    own decode row is an idle one (its table row is scratch while it
    prefills), so it writes to the scratch page of the pools and of the
    rings (:class:`_RingWrites`) and its token is discarded. ``B`` may be 0
    (:func:`paged_chunk_prefill`); a call whose rows are all idle is what a
    chunk takes when it has no decode step to ride, and where the slots are
    many it skips their reads (``SKIP_IDLE_READS_FROM_SLOTS``).

    A family of several ``kinds``: a state-space sub-block takes the chunk's
    rows from the slot's carried state (zeros at ``start`` 0) and the decode
    rows each from its slot's (:func:`_ssm_block`). Behind ``fam.stop_after``
    the chunk is ONE row, the one whose logits are sampled (the prompt's last
    where it falls in this chunk): the sub-blocks there write nothing a later
    call reads, so the other prompt rows have no business in them. A cross
    layer then reads its source's pages for that row and the decode rows in
    one decode-shaped call (the chunk's row under ``chunk_row``). An attention
    that carries rows (``fam.carry_width``) takes the chunk's from the slot's
    (zeros at ``start`` 0), leaves what the chunk's last real row hands on, and
    shifts each decoding slot's by its one row (:func:`_qkv_carried`)."""
    fam = cfg.serving_family()
    B, C = tokens.shape[0], input_ids.shape[1]
    page = cache.k.shape[3]
    # chunk rows first: they start at row 0 and the decode rows at a page
    # multiple, whole tiles both
    c_pos = jnp.minimum(start + jnp.arange(C), fam.n_positions - 1)
    positions = jnp.concatenate([c_pos, seq_lens])
    with parts.part("embed"):
        h = fam.embed(params, jnp.concatenate([input_ids[0], tokens])[None], positions)
    base = jnp.reshape(start, (1,))
    pidx = jnp.take_along_axis(block_tables, (seq_lens // page)[:, None], axis=1)[:, 0]
    poff = seq_lens % page
    real = block_tables[:, 0] != 0  # a slot that decodes holds a page; page 0 is scratch
    valid = jnp.concatenate([c_pos < prompt_len, real]) if fam.sparse_layers else None
    live = jnp.any(real) if B >= SKIP_IDLE_READS_FROM_SLOTS else None
    counts, carry = [], None
    kinds, stop = sub_block_kinds(fam), getattr(fam, "stop_after", None)
    carried = getattr(fam, "carry_width", 0)
    # the chunk's row that is sampled (its last real one, on a prompt's final chunk)
    idx = jnp.clip(prompt_len - 1 - start, 0, C - 1)
    n_real = jnp.clip(prompt_len - start, 0, C)
    Cs, stopped = C, False   # chunk rows in the stream: C, or the sampled one behind ``stop_after``
    rw = None
    if cache.win_k is not None:
        ring_ids = ring_page_ids(slot, start // page + jnp.arange(C // page), ring)
        views = _window_views(fam, jnp.reshape(slot, (1,)), base, page, ring)
        rw = _RingWrites(fam, seq_lens, block_tables, page, ring, 1) if B else None

    def part(x):
        """``[1, C + B, ...]`` → the chunk's ``[1, C, ...]`` and the decode
        rows as their own program has them, ``[B, 1, ...]``."""
        return x[:, :Cs], jnp.swapaxes(x[:, Cs:], 0, 1)

    def cross(q, li):
        """A cross layer's rows against its source's pages, which this call
        has already written."""
        if stopped:
            # the chunk's one row beside the decode rows, its table row
            # beside theirs: one call of the one-token kernel
            o = _attend_decode_shaped(
                fam, jnp.swapaxes(q, 0, 1), cache.k, cache.v, li,
                jnp.concatenate([chunk_row, block_tables]),
                jnp.concatenate([base + idx, seq_lens]), q.dtype, name="decode_fn",
                live=None if live is None else live | (prompt_len <= start + C),
                real=jnp.concatenate([jnp.ones((1,), bool), real]),
            )
            return jnp.swapaxes(o, 0, 1)
        qc, qd = part(q)
        o = _attend_multitoken_paged(fam, qc, cache.k, cache.v, li, chunk_row, base, name="chunk_fn")
        if not B:
            return o
        od = _attend_decode_shaped(
            fam, qd, cache.k, cache.v, li, block_tables, seq_lens, q.dtype,
            name="decode_fn", live=live, real=real,
        )
        return jnp.concatenate([o, jnp.swapaxes(od, 0, 1)], axis=1)

    for l, (windowed, li) in enumerate(_kv_homes(fam)):
        lp = fam.layer(params, l)
        if kinds[l] != "attn":
            a, carry, cache = _mixer_without_kv(
                fam, lp, h, l, li, positions, carry, cache, tp_axis,
                (Cs, (slot, start, n_real), real if B else None), cross,
            )
            h, carry = _after_attention(fam, lp, h, a, l, valid, tp_axis, counts, carry, _passed)
            continue
        if carried:
            q, k_, v, cache = _qkv_carried(
                fam, lp, h, positions, l, cache, C, (slot, start, n_real), real if B else None
            )
        else:
            with parts.part("attn.qkv"):
                q, k_, v = fam.qkv(lp, h, positions, l)
        (qc, qd), (kc, kd) = part(q), part(k_)
        od = None
        if fam.kv_pools == 1:
            cache = cache._replace(k=_latent_write_pages(cache.k, li, page_ids, kc))
            if B:
                cache = cache._replace(k=_latent_write_tokens(cache.k, li, pidx, poff, kd[:, 0]))
                od = _attend_latent(
                    fam, qd, cache.k, li, block_tables, seq_lens, "mla_paged_decode", live
                )
            oc = _attend_latent(fam, qc, cache.k, li, chunk_row, base, "mla_paged_chunk")
        elif windowed:
            vc, vd = part(v)
            k_win, v_win = cache.win_k, cache.win_v
            with parts.part("kv.write"):
                k_win = k_win.at[li, ring_ids].set(_page_chunks(kc, page).astype(k_win.dtype))
                v_win = v_win.at[li, ring_ids].set(_page_chunks(vc, page).astype(v_win.dtype))
            cache = cache._replace(win_k=k_win, win_v=v_win)
            if B:
                od, cache = _attention_step_window(
                    fam, qd, kd, vd, cache, li, seq_lens, rw, fam.windows[l], "decode_fn", live
                )
            table, off, lo = views[fam.windows[l]]
            oc = _attend_multitoken_paged(
                fam, qc, cache.win_k, cache.win_v, li, table, base - off, None, lo, "chunk_fn"
            )
        else:
            vc, vd = part(v)
            pool_dt = h.dtype if cache.scales is not None else cache.k.dtype
            # page-granular scatter, exactly paged_prefill's write (quantized
            # at write when the pool is int8; the attention below reads the
            # pool, so it sees the dequantized codes either way)
            cache, _, _ = _write_cache_pages(cache, li, page_ids, kc, vc, pool_dt)
            if B:
                od, cache = _attention_decode_paged(
                    fam, qd, kd.astype(pool_dt), vd.astype(pool_dt), cache,
                    block_tables, seq_lens, pidx, poff, li, "decode_fn", live, real,
                )
            oc = _attend_multitoken_paged(
                fam, qc, cache.k, cache.v, li, chunk_row, base,
                cache.scales[li] if cache.scales is not None else None, name="chunk_fn",
            )
        o = oc if od is None else jnp.concatenate([oc, jnp.swapaxes(od, 0, 1)], axis=1)
        h, carry = _after_attention(fam, lp, h, o, l, valid, tp_axis, counts, carry)
        if l == stop:
            # of the chunk only the sampled row goes on, with its row of
            # what the sources handed on
            sampled = lambda x: jnp.concatenate(  # noqa: E731
                [lax.dynamic_slice_in_dim(x, idx, 1, 1), x[:, C:]], axis=1
            )
            h, carry, positions = sampled(h), jax.tree.map(sampled, carry), sampled(positions[None])[0]
            Cs, stopped = 1, True

    # one pass of the head: the chunk's true last prompt position (when it
    # falls inside this chunk) and the decode rows
    with parts.part("head"):
        logits = fam.logits(
            params, h[0] if stopped
            else jnp.concatenate([jnp.take(h[0], idx[None], axis=0), h[0, C:]])
        )
    with parts.part("sample"):
        first = sample_logits(logits[:1], rng, temperature, top_k, top_p)
        if B:
            nxt = _sample_slots(logits[1:], keys, temperature, top_k, top_p)
            first = jnp.concatenate([nxt, first.astype(nxt.dtype)])
    return _result(cache, first, counts)


def paged_chunk_prefill(
    cfg, params: PyTree, input_ids, start, prompt_len, cache,
    page_ids, block_tables, rng, **kw,
):
    """One chunk through the model with no decode row beside it → (cache,
    token [1], ...): :func:`paged_mixed_step` at ``B`` = 0, its
    operands in the chunk's own order (``block_tables [1, W]`` is the
    prefilling slot's row). No engine compiles this shape (a chunk that has
    no decode step to ride takes the mixed program with idle rows); it is
    what the tests hold the mixed step's chunk rows against."""
    none = jnp.zeros((0,), jnp.int32)
    return paged_mixed_step(
        cfg, params, none, none, input_ids, start, prompt_len, cache,
        jnp.zeros((0, block_tables.shape[1]), jnp.int32), page_ids, block_tables,
        jnp.zeros((0, 2), jnp.uint32), rng, **kw,
    )


# ---------------------------------------------------------------------------
# bucket-padded offline generate (InferenceEngine.generate satellite)
# ---------------------------------------------------------------------------

def generate_padded(
    cfg: gpt2.GPT2Config,
    params: PyTree,
    input_ids: jnp.ndarray,   # [B, Sb] right-padded to the bucket length
    prompt_len: jnp.ndarray,  # traced i32: true prompt length
    max_new_tokens: int,
    temperature: float = 0.0,
    rng=None,
    cache_dtype=jnp.bfloat16,
    top_k: int = 0,
    top_p: float = 1.0,
) -> jnp.ndarray:
    """``gpt2.generate`` with a traced prompt length: one executable serves
    every prompt length in the bucket. Prefill runs on the padded chunk
    (garbage K/V past ``prompt_len`` is masked until the decode writes
    overwrite it), the head reads the true last prompt position, and the
    decode scan is ``gpt2.generate``'s own. Returns [B, max_new_tokens],
    bit-identical to the unpadded path."""
    B, Sb = input_ids.shape
    max_len = Sb + max_new_tokens
    if max_len > cfg.n_positions:
        raise ValueError(
            f"bucket ({Sb}) + max_new_tokens ({max_new_tokens}) exceeds "
            f"n_positions={cfg.n_positions}"
        )
    if rng is None:
        rng = jax.random.PRNGKey(0)

    cache = gpt2.init_cache(cfg, B, max_len, dtype=cache_dtype)
    logits, cache = gpt2.forward_cached(
        cfg, params, input_ids, cache, logits_at=prompt_len - 1
    )
    # rewind pos to the true length: decode overwrites the padded garbage
    cache = KVCache(k=cache.k, v=cache.v, pos=jnp.asarray(prompt_len, jnp.int32))

    def sample(lg, key):
        return sample_logits(lg, key, temperature, top_k, top_p)

    first = sample(logits, rng)
    if max_new_tokens == 1:
        return first[:, None]

    def step(carry, key):
        token, cache = carry
        lg, cache = gpt2.forward_cached(
            cfg, params, token[:, None].astype(input_ids.dtype), cache
        )
        nxt = sample(lg, key)
        return (nxt, cache), token

    keys = jax.random.split(jax.random.fold_in(rng, 1), max_new_tokens - 1)
    (last, _), tokens = lax.scan(step, (first, cache), keys)
    return jnp.concatenate([jnp.moveaxis(tokens, 0, 1), last[:, None]], axis=1)
