"""Static-shape serving programs over the paged KV pool.

Three compiled-once programs built from the gpt2 family's own building
blocks (``models/gpt2``) so serving is BIT-IDENTICAL to per-request
``generate``:

- :func:`paged_prefill` — one request's prompt (right-padded to the static
  prefill width) through the model, K/V written page-granularly into the
  slot's pool pages, first token sampled at the true last prompt position.
- :func:`paged_decode_step` — one token for EVERY slot: scatter the new K/V
  into each slot's current page, attend through the block table
  (``ops.attention.paged_cached_attention``), sample per-slot with per-slot
  keys. All shapes are functions of the serving config only — finished
  sequences vacating slots and new prompts arriving never retrace.
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

from typing import Any, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..models import gpt2
from ..models.gpt2 import GPT2Config, KVCache, _mlp
from ..ops.layer_norm import layer_norm_inference as _layer_norm
from ..ops.quantizer import (
    dequantize_kv_pages,
    kv_page_scale,
    quantize_kv_pages,
    quantize_kv_token,
)
from ..ops.quantizer import maybe_dequantize as _deq
from ..ops.sampling import sample_logits

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


def _scatter_tokens(k_pool, v_pool, l, pidx, poff, k_vals, v_vals):
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
    stays is not defined. Same elements, same values either way."""
    from ..ops.pallas.decode_attention import (
        paged_token_write,
        paged_token_write_ok,
    )

    KV, D = k_vals.shape[-2:]
    T = 1 if pidx.ndim == 1 else pidx.shape[1]
    if paged_token_write_ok(KV, k_pool.shape[3], D, k_pool.dtype.itemsize, T):
        return paged_token_write(k_pool, v_pool, l, pidx, poff, k_vals, v_vals)
    at = (l, pidx[..., None], jnp.arange(KV), poff[..., None])
    return (
        k_pool.at[at].set(k_vals.astype(k_pool.dtype)),
        v_pool.at[at].set(v_vals.astype(v_pool.dtype)),
    )


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


def _write_pool_tokens(k_pool, v_pool, scales, l, pidx, poff, k_vals, v_vals):
    """One-token write: ``k_vals`` / ``v_vals [B, KV, D]`` to (layer ``l``,
    page ``pidx[b]``, offset ``poff[b]``) of both pools, quantized at write
    when they are int8 → ``(k_pool, v_pool, scales)``."""
    k_vals, scales = _token_codes(scales, l, pidx, poff, k_vals, 0)
    v_vals, scales = _token_codes(scales, l, pidx, poff, v_vals, 1)
    k_pool, v_pool = _scatter_tokens(k_pool, v_pool, l, pidx, poff, k_vals, v_vals)
    return k_pool, v_pool, scales


def _proj(o, w, b, dtype, tp_axis=None):
    """Output projection shared by every attention variant. Under the TP
    ``shard_map`` (ISSUE 14) ``w`` is the row-parallel slice — the partial
    product is psum-reduced over ``tp_axis`` BEFORE the replicated bias is
    added once (adding per-rank biases would count ``b`` tp times). With
    ``tp_axis=None`` this is the exact historical ``o @ w + b`` graph, so
    the TP=1 program set stays byte-identical."""
    out = o @ _deq(w, dtype)
    if tp_axis is not None:
        out = lax.psum(out, tp_axis)
    return out + b


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
# paged prefill (one request into one slot's pages)
# ---------------------------------------------------------------------------

def _layer_params(params: PyTree, l: int) -> PyTree:
    """Layer ``l``'s slice of the stacked block params (static index — XLA
    folds the slices into their consumers)."""
    return jax.tree_util.tree_map(lambda x: x[l], params["blocks"])


def _attention_prefill_paged(cfg, lp, h, k_pool, v_pool, page_ids, l,
                             scales=None, tp_axis=None):
    """Causal self-attention over the prompt chunk; K/V written to layer
    ``l``'s pages of the FULL pool (quantized at write when ``scales`` is
    given — the attention then reads the DEQUANTIZED chunk back, so the
    first sampled token is consistent with every later read of the same
    pages).

    The chunk starts at position 0 of a fresh slot, so "the cache" IS the
    chunk — the dense causal einsum here is exactly ``_attention_cached``'s
    prefill path with ``pos = 0`` and ``Smax = Sp``."""
    B, Sp, E = h.shape
    H, D = cfg.n_head, cfg.head_dim
    page = k_pool.shape[3]
    qkv = h @ _deq(lp["c_attn_w"], h.dtype) + lp["c_attn_b"]
    q, k_, v = jnp.split(qkv, 3, axis=-1)
    q = q.reshape(B, Sp, H, D)
    pool_dt = h.dtype if scales is not None else k_pool.dtype
    k_c = k_.reshape(B, Sp, H, D).astype(pool_dt)
    v_c = v.reshape(B, Sp, H, D).astype(pool_dt)

    # page-granular scatter: [Sp,H,D] → [n_pp, H, page, D] rows of the pool.
    # Whole pages are overwritten — a slot's pages are fresh at admission and
    # padded/garbage positions are masked until the decode write claims them;
    # padded page_ids point at the scratch page.
    n_pp = Sp // page
    chunks = jnp.swapaxes(k_c[0].reshape(n_pp, page, H, D), 1, 2)
    k_pool, scales, k_att = _write_pool_pages(
        k_pool, scales, l, page_ids, chunks, 0
    )
    chunks_v = jnp.swapaxes(v_c[0].reshape(n_pp, page, H, D), 1, 2)
    v_pool, scales, v_att = _write_pool_pages(
        v_pool, scales, l, page_ids, chunks_v, 1
    )
    if scales is not None:
        # [n_pp, KV, page, D] dequantized → the [B, Sp, H, D] chunk view
        k_c = jnp.swapaxes(k_att, 1, 2).reshape(B, Sp, H, D)
        v_c = jnp.swapaxes(v_att, 1, 2).reshape(B, Sp, H, D)

    scale = 1.0 / np.sqrt(D)
    scores = jnp.einsum(
        "bshd,bthd->bhst", q.astype(jnp.float32), k_c.astype(jnp.float32)
    ) * scale
    j_idx = jnp.arange(Sp)
    i_idx = jnp.arange(Sp)
    mask = j_idx[None, :] <= i_idx[:, None]
    scores = jnp.where(mask[None, None, :, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(v_c.dtype)
    o = jnp.einsum("bhst,bthd->bshd", probs, v_c)
    # H*D == E at TP=1; under the TP shard_map H is the per-rank head count
    # and the row-parallel projection restores the full embed dim
    o = o.reshape(B, Sp, H * D).astype(h.dtype)
    return (
        _proj(o, lp["c_proj_w"], lp["c_proj_b"], h.dtype, tp_axis),
        k_pool, v_pool, scales,
    )


def paged_prefill(
    cfg: GPT2Config,
    params: PyTree,
    input_ids: jnp.ndarray,   # [1, Sp] right-padded to the static prefill width
    prompt_len: jnp.ndarray,  # traced i32: true prompt length
    k_pool: jnp.ndarray,      # [L, P, KV, page, D]
    v_pool: jnp.ndarray,
    page_ids: jnp.ndarray,    # [Sp // page] i32 slot pages (scratch-padded)
    rng: jnp.ndarray,         # PRNGKey for the first sampled token
    temperature: float = 0.0,
    top_k: int = 0,
    top_p: float = 1.0,
    scales: jnp.ndarray = None,  # [L, P, KV, 2] when the pool is int8
    tp_axis: str = None,  # named mesh axis under the TP shard_map (ISSUE 14)
):
    """→ (k_pool, v_pool, first_token [1]), with ``scales`` threaded between
    the pools and the token when the pool is quantized (ISSUE 12)."""
    B, Sp = input_ids.shape
    eps = cfg.layer_norm_epsilon
    positions = jnp.arange(Sp)
    h = params["wte"][input_ids] + params["wpe"][positions][None, :, :]

    for l in range(cfg.n_layer):
        lp = _layer_params(params, l)
        a, k_pool, v_pool, scales = _attention_prefill_paged(
            cfg, lp["attn"],
            _layer_norm(h, lp["ln_1"]["scale"], lp["ln_1"]["bias"], eps),
            k_pool, v_pool, page_ids, l, scales, tp_axis,
        )
        h = h + a
        m, _aux = _mlp(
            cfg, lp["mlp"],
            _layer_norm(h, lp["ln_2"]["scale"], lp["ln_2"]["bias"], eps),
            False, None, tp_axis=tp_axis,
        )
        h = h + m

    h_last = jnp.take(h, prompt_len - 1, axis=1)  # [B, E] true last prompt pos
    h_last = _layer_norm(h_last, params["ln_f"]["scale"], params["ln_f"]["bias"], eps)
    logits = (h_last @ params["wte"].T)[..., : cfg.vocab_size]
    first = sample_logits(logits, rng, temperature, top_k, top_p)
    if scales is not None:
        return k_pool, v_pool, scales, first
    return k_pool, v_pool, first


# ---------------------------------------------------------------------------
# paged decode step (one token for every slot)
# ---------------------------------------------------------------------------

def _attend_decode_shaped(cfg, q, k_pool, v_pool, l, block_tables, pos,
                          out_dtype, scales_l=None):
    """ONE query token per slot against layer ``l`` of the paged cache →
    [B, 1, E]. The kernel takes the whole pools and the layer as a block
    index; only the ``jnp`` branch slices the layer out.

    The decode step's attention, factored so the speculative verify step
    can attend each of its T queries through EXACTLY this code — same
    shapes, same XLA reduction trees, same bits (ISSUE 10). ``scales_l``
    (= ``scales[l]``, [P, KV, 2]) dequantizes an int8 pool in the read
    path (ISSUE 12)."""
    B, S, H, D = q.shape  # S == 1
    E = H * D
    scale = 1.0 / np.sqrt(D)
    if cfg.attn_impl in ("auto", "pallas"):
        from ..ops.attention import paged_cached_attention

        o1 = paged_cached_attention(
            q[:, 0], k_pool, v_pool, block_tables, pos,
            impl=cfg.attn_impl, sm_scale=scale, scales=scales_l, layer=l,
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


def _attention_decode_paged(cfg, lp, h, k_pool, v_pool, block_tables,
                            pos, pidx, poff, l, scales=None, tp_axis=None):
    """One-token attention per slot against its paged cache (layer ``l`` of
    the FULL pool).

    ``pos[b]`` = tokens already cached for slot b (the new token's position);
    new K/V scatters to (page ``pidx[b]``, offset ``poff[b]``) before the
    gather, mirroring ``_attention_cached``'s update-then-attend order."""
    B, S, E = h.shape  # S == 1
    H, D = cfg.n_head, cfg.head_dim
    qkv = h @ _deq(lp["c_attn_w"], h.dtype) + lp["c_attn_b"]
    q, k_, v = jnp.split(qkv, 3, axis=-1)
    q = q.reshape(B, S, H, D)
    pool_dt = h.dtype if scales is not None else k_pool.dtype
    k_c = k_.reshape(B, S, H, D).astype(pool_dt)
    v_c = v.reshape(B, S, H, D).astype(pool_dt)

    # [B,H,D] values to (l, pidx[b], :, poff[b], :) — advanced indices around
    # the head slice put the batch dim first, matching the value layout.
    # Inactive slots target the scratch page.
    k_pool, v_pool, scales = _write_pool_tokens(
        k_pool, v_pool, scales, l, pidx, poff, k_c[:, 0], v_c[:, 0]
    )

    o = _attend_decode_shaped(
        cfg, q, k_pool, v_pool, l, block_tables, pos, h.dtype,
        scales[l] if scales is not None else None,
    )
    return (
        _proj(o, lp["c_proj_w"], lp["c_proj_b"], h.dtype, tp_axis),
        k_pool, v_pool, scales,
    )


def paged_decode_step(
    cfg: GPT2Config,
    params: PyTree,
    tokens: jnp.ndarray,        # [B] i32 last emitted token per slot
    seq_lens: jnp.ndarray,      # [B] i32 tokens already cached per slot
    k_pool: jnp.ndarray,        # [L, P, KV, page, D]
    v_pool: jnp.ndarray,
    block_tables: jnp.ndarray,  # [B, n_pages] i32
    keys: jnp.ndarray,          # [B, 2] u32 per-slot sampling keys
    temperature: float = 0.0,
    top_k: int = 0,
    top_p: float = 1.0,
    scales: jnp.ndarray = None,  # [L, P, KV, 2] when the pool is int8
    tp_axis: str = None,  # named mesh axis under the TP shard_map (ISSUE 14)
):
    """→ (k_pool, v_pool, next_tokens [B]); ``scales`` threaded through and
    returned before the tokens when the pool is quantized."""
    B = tokens.shape[0]
    page = k_pool.shape[3]
    eps = cfg.layer_norm_epsilon
    h = params["wte"][tokens][:, None, :] + params["wpe"][seq_lens][:, None, :]
    pidx = jnp.take_along_axis(
        block_tables, (seq_lens // page)[:, None], axis=1
    )[:, 0]
    poff = seq_lens % page

    for l in range(cfg.n_layer):
        lp = _layer_params(params, l)
        a, k_pool, v_pool, scales = _attention_decode_paged(
            cfg, lp["attn"],
            _layer_norm(h, lp["ln_1"]["scale"], lp["ln_1"]["bias"], eps),
            k_pool, v_pool, block_tables, seq_lens, pidx, poff, l, scales,
            tp_axis,
        )
        h = h + a
        m, _aux = _mlp(
            cfg, lp["mlp"],
            _layer_norm(h, lp["ln_2"]["scale"], lp["ln_2"]["bias"], eps),
            False, None, tp_axis=tp_axis,
        )
        h = h + m

    h_last = _layer_norm(
        h[:, -1], params["ln_f"]["scale"], params["ln_f"]["bias"], eps
    )
    logits = (h_last @ params["wte"].T)[..., : cfg.vocab_size]
    if not temperature or temperature <= 0.0:
        nxt = jnp.argmax(logits.astype(jnp.float32), axis=-1)
    else:
        # per-slot keys: each row samples exactly as its own B=1 generate
        # (vmap of the PRNG is semantics-preserving, so slot b's draw equals
        # the sequential request's draw with the same key)
        nxt = jax.vmap(
            lambda lg, kk: sample_logits(
                lg[None, :], kk, temperature, top_k, top_p
            )[0]
        )(logits, keys)
    if scales is not None:
        return k_pool, v_pool, scales, nxt
    return k_pool, v_pool, nxt


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


def _attend_multitoken_paged(cfg, h, q, k_pool, v_pool, l,
                             block_tables, base, scales_l=None):
    """Batched attention tail of the chunk-prefill program: q [B,T,H,D]
    against layer ``l`` of the (already updated) paged cache, masked per
    query; the pools arrive whole, as in ``_attend_decode_shaped``. The
    caller applies the output projection. ``scales_l`` dequantizes an int8
    pool (ISSUE 12).

    Dispatch mirrors ``_attention_decode_paged`` branch for branch; see the
    block comment above for why this form is token-identical but not
    bit-identical across chunking boundaries."""
    B, T, E = h.shape
    H, D = cfg.n_head, cfg.head_dim
    scale = 1.0 / np.sqrt(D)
    if cfg.attn_impl in ("auto", "pallas"):
        from ..ops.attention import paged_multitoken_cached_attention

        o = paged_multitoken_cached_attention(
            q, k_pool, v_pool, block_tables, base,
            impl=cfg.attn_impl, sm_scale=scale, scales=scales_l, layer=l,
        )
        return o.reshape(B, T, H * D).astype(h.dtype)

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
    return o.reshape(B, T, H * D).astype(h.dtype)


def _attention_verify_paged(cfg, lp, h, k_pool, v_pool, block_tables,
                            base, pidx, poff, l, scales=None, tp_axis=None):
    """T-token attention per slot: scatter every token's K/V to layer ``l``
    at (``pidx[b,t]``, ``poff[b,t]``), then attend query t at position
    ``base + t`` through the block table. Out-of-budget positions arrive
    with ``pidx`` already routed to the scratch page (see
    :func:`_verify_write_targets`).

    The T attention calls are UNROLLED single-token ``_attend_decode_shaped``
    invocations — identical shapes to the decode step, hence identical bits;
    query t's mask (``idx <= base + t``) hides the already-scattered K/V of
    queries > t exactly as it hides any other stale cache content, so
    scatter-all-then-attend equals the sequential write-attend interleaving
    bit for bit. The QKV matmul above and projection below stay batched over
    T — the arithmetic-intensity win speculation exists for."""
    B, T, E = h.shape
    H, D = cfg.n_head, cfg.head_dim
    qkv = h @ _deq(lp["c_attn_w"], h.dtype) + lp["c_attn_b"]
    q, k_, v = jnp.split(qkv, 3, axis=-1)
    q = q.reshape(B, T, H, D)
    pool_dt = h.dtype if scales is not None else k_pool.dtype
    k_c = k_.reshape(B, T, H, D).astype(pool_dt)
    v_c = v.reshape(B, T, H, D).astype(pool_dt)
    if scales is None:
        # [B,T,H,D] values to (l, pidx[b,t], :, poff[b,t], :), one write
        k_pool, v_pool = _scatter_tokens(k_pool, v_pool, l, pidx, poff, k_c, v_c)
    else:
        # quantized pools write the T tokens in sequence: a token landing at
        # a page's offset 0 establishes the page's scale, and the tokens
        # after it IN THE SAME STEP must code against that scale — exactly
        # the order the sequential decode steps would have written them, so
        # the pool state (codes AND scales) is bit-identical to spec-off
        # int8 decode
        for t in range(T):
            k_pool, v_pool, scales = _write_pool_tokens(
                k_pool, v_pool, scales, l, pidx[:, t], poff[:, t],
                k_c[:, t], v_c[:, t],
            )
    scales_l = scales[l] if scales is not None else None
    o = jnp.concatenate(
        [
            _attend_decode_shaped(
                cfg, q[:, t:t + 1], k_pool, v_pool, l, block_tables,
                base + t, h.dtype, scales_l,
            )
            for t in range(T)
        ],
        axis=1,
    )
    return (
        _proj(o, lp["c_proj_w"], lp["c_proj_b"], h.dtype, tp_axis),
        k_pool, v_pool, scales,
    )


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
    cfg: GPT2Config,
    params: PyTree,
    tokens: jnp.ndarray,        # [B, T] col 0 = last emitted, cols 1.. = drafts
    seq_lens: jnp.ndarray,      # [B] i32 tokens already cached per slot
    k_pool: jnp.ndarray,        # [L, P, KV, page, D]
    v_pool: jnp.ndarray,
    block_tables: jnp.ndarray,  # [B, W] i32
    scales: jnp.ndarray = None,  # [L, P, KV, 2] when the pool is int8
    tp_axis: str = None,  # named mesh axis under the TP shard_map (ISSUE 14)
):
    """Self-speculative verify (ISSUE 10): score T = k+1 tokens per slot in
    one forward pass → (k_pool, v_pool, greedy [B, T]); ``scales`` threaded
    and returned before ``greedy`` when the pool is quantized.

    ``greedy[b, t]`` is the argmax next token after prefix ⊕ tokens[b, :t+1]
    — i.e. exactly what ``paged_decode_step`` would emit at that point. The
    host accepts the longest prefix where ``tokens[b, t+1] == greedy[b, t]``
    and emits ``greedy[b, :accepted+1]``: the output stream is bit-identical
    to sequential decode, drafts only change how many tokens one step
    yields. Rejected drafts leave K/V at positions past the accepted length;
    the next step's T-token scatter overwrites every such position before
    anything attends it (``new_base = base + accepted + 1 <= base + T``), so
    rollback is by construction, not by copy."""
    B, T = tokens.shape
    page = k_pool.shape[3]
    eps = cfg.layer_norm_epsilon
    # clamp garbage positions (past the decode budget) into the embedding
    # table; their queries are never emitted and their writes go to scratch
    positions = jnp.minimum(
        seq_lens[:, None] + jnp.arange(T)[None, :], cfg.n_positions - 1
    )
    h = params["wte"][tokens] + params["wpe"][positions]
    pidx, poff = _verify_write_targets(seq_lens, block_tables, page, T)

    for l in range(cfg.n_layer):
        lp = _layer_params(params, l)
        a, k_pool, v_pool, scales = _attention_verify_paged(
            cfg, lp["attn"],
            _layer_norm(h, lp["ln_1"]["scale"], lp["ln_1"]["bias"], eps),
            k_pool, v_pool, block_tables, seq_lens, pidx, poff, l, scales,
            tp_axis,
        )
        h = h + a
        m, _aux = _mlp(
            cfg, lp["mlp"],
            _layer_norm(h, lp["ln_2"]["scale"], lp["ln_2"]["bias"], eps),
            False, None, tp_axis=tp_axis,
        )
        h = h + m

    h = _layer_norm(h, params["ln_f"]["scale"], params["ln_f"]["bias"], eps)
    logits = (h @ params["wte"].T)[..., : cfg.vocab_size]
    greedy = jnp.argmax(logits.astype(jnp.float32), axis=-1).astype(jnp.int32)
    if scales is not None:
        return k_pool, v_pool, scales, greedy
    return k_pool, v_pool, greedy


def paged_chunk_prefill(
    cfg: GPT2Config,
    params: PyTree,
    input_ids: jnp.ndarray,   # [1, C] one chunk, right-padded past the prompt
    start: jnp.ndarray,       # traced i32: absolute position of input_ids[0, 0]
    prompt_len: jnp.ndarray,  # traced i32: the request's true prompt length
    k_pool: jnp.ndarray,      # [L, P, KV, page, D]
    v_pool: jnp.ndarray,
    page_ids: jnp.ndarray,    # [C // page] i32: THIS chunk's slot pages
    block_tables: jnp.ndarray,  # [1, W] i32: the slot's full table row
    rng: jnp.ndarray,         # PRNGKey for the first sampled token
    temperature: float = 0.0,
    top_k: int = 0,
    top_p: float = 1.0,
    scales: jnp.ndarray = None,  # [L, P, KV, 2] when the pool is int8
    tp_axis: str = None,  # named mesh axis under the TP shard_map (ISSUE 14)
):
    """One chunk of an incremental prefill (ISSUE 10) → (k_pool, v_pool,
    token [1]); ``scales`` threaded and returned before the token when the
    pool is quantized (the COW fork-by-recompute path rides this program —
    the fresh private page is REQUANTIZED here, its own scale written,
    while the shared original's codes and scale row are never touched).

    Positions ``start .. start+C-1`` run through the model attending the
    slot's cached prefix (``< start`` — earlier chunks or shared prefix
    pages) plus causal intra-chunk, K/V written page-granularly to
    ``page_ids`` (page-aligned because C is a page multiple; pages the
    chunk overruns are scratch-padded by the scheduler). The returned token
    is sampled at the true last prompt position and is only meaningful on
    the final chunk — earlier chunks' samples are discarded host-side.
    Long prompts stop stalling decode: the scheduler interleaves one chunk
    per step with the batched decode of other slots."""
    B, C = input_ids.shape
    page = k_pool.shape[3]
    n_cp = C // page
    eps = cfg.layer_norm_epsilon
    positions = jnp.minimum(start + jnp.arange(C), cfg.n_positions - 1)
    h = params["wte"][input_ids] + params["wpe"][positions][None, :, :]
    base = jnp.reshape(start, (1,))

    for l in range(cfg.n_layer):
        lp = _layer_params(params, l)
        hn = _layer_norm(h, lp["ln_1"]["scale"], lp["ln_1"]["bias"], eps)
        qkv = hn @ _deq(lp["attn"]["c_attn_w"], hn.dtype) + lp["attn"]["c_attn_b"]
        q, k_, v = jnp.split(qkv, 3, axis=-1)
        H, D = cfg.n_head, cfg.head_dim
        q = q.reshape(B, C, H, D)
        pool_dt = hn.dtype if scales is not None else k_pool.dtype
        k_c = k_.reshape(B, C, H, D).astype(pool_dt)
        v_c = v.reshape(B, C, H, D).astype(pool_dt)
        # page-granular scatter, exactly paged_prefill's write (quantized at
        # write when the pool is int8; the attention below reads the pool,
        # so it sees the dequantized codes either way)
        k_pool, scales, _ = _write_pool_pages(
            k_pool, scales, l, page_ids,
            jnp.swapaxes(k_c[0].reshape(n_cp, page, H, D), 1, 2), 0,
        )
        v_pool, scales, _ = _write_pool_pages(
            v_pool, scales, l, page_ids,
            jnp.swapaxes(v_c[0].reshape(n_cp, page, H, D), 1, 2), 1,
        )
        o = _attend_multitoken_paged(
            cfg, hn, q, k_pool, v_pool, l, block_tables, base,
            scales[l] if scales is not None else None,
        )
        a = _proj(o, lp["attn"]["c_proj_w"], lp["attn"]["c_proj_b"],
                  hn.dtype, tp_axis)
        h = h + a
        m, _aux = _mlp(
            cfg, lp["mlp"],
            _layer_norm(h, lp["ln_2"]["scale"], lp["ln_2"]["bias"], eps),
            False, None, tp_axis=tp_axis,
        )
        h = h + m

    # the true last prompt position, when it falls inside this chunk
    idx = jnp.clip(prompt_len - 1 - start, 0, C - 1)
    h_last = jnp.take(h, idx, axis=1)  # [B, E]
    h_last = _layer_norm(h_last, params["ln_f"]["scale"], params["ln_f"]["bias"], eps)
    logits = (h_last @ params["wte"].T)[..., : cfg.vocab_size]
    first = sample_logits(logits, rng, temperature, top_k, top_p)
    if scales is not None:
        return k_pool, v_pool, scales, first
    return k_pool, v_pool, first


# ---------------------------------------------------------------------------
# bucket-padded offline generate (InferenceEngine.generate satellite)
# ---------------------------------------------------------------------------

def generate_padded(
    cfg: GPT2Config,
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
