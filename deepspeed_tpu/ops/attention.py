"""Attention dispatch: Pallas flash kernel on TPU, jnp reference elsewhere.

The capability analog of the reference's fused transformer kernels
(``csrc/transformer/ds_transformer_cuda.cpp`` softmax/attention pieces): the
FLOPs-heavy attention inner loop runs as a hand-written TPU kernel
(``deepspeed_tpu/ops/pallas/flash_attention.py``) when shapes allow, with a
pure-XLA path elsewhere that still fuses well (MXU einsums + f32 softmax).
Under ``impl="auto"`` the shape gate (``flash_ok``, ``*_attention_ok``) alone
decides; a kernel that fails to lower or compile raises — nothing here
catches it and substitutes the jnp path.

Layout convention here is [B, S, H, D] (batch, seq, heads, head_dim).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec


def _on_each_device(kernel, q, k, v, *replicated):
    """``kernel(q, k, v, *replicated)`` on [B,S,H,D] operands, run per device.

    GSPMD cannot partition a Mosaic custom call ("Mosaic kernels cannot be
    automatically partitioned"), so inside a jit over a multi-device mesh the
    kernel must sit in a ``shard_map``: batch over ``dp`` and heads over
    ``tp`` where they divide, every other axis replicated. The mesh is the
    ambient one (``jax.set_mesh`` — ``DeepSpeedEngine`` sets it around its
    jitted steps). With no ambient mesh, a single device, or when the caller
    is already inside a manual region (ring attention, the serving TP
    programs) the kernel is called directly."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or mesh.size == 1 or mesh.manual_axes:
        return kernel(q, k, v, *replicated)
    size = dict(zip(mesh.axis_names, mesh.axis_sizes))
    dp = "dp" if size.get("dp", 1) > 1 and q.shape[0] % size["dp"] == 0 else None
    tp = (
        "tp" if size.get("tp", 1) > 1
        and all(x.shape[2] % size["tp"] == 0 for x in (q, k, v)) else None
    )
    spec = PartitionSpec(dp, None, tp, None)
    return jax.shard_map(
        kernel,
        in_specs=(spec, spec, spec) + (PartitionSpec(),) * len(replicated),
        out_specs=spec, check_vma=False,
    )(q, k, v, *replicated)


def causal_attention_jnp(q, k, v, sm_scale: Optional[float] = None):
    """Reference implementation: [B,S,H,D] → [B,S,H,D], causal, f32 softmax.
    Accepts GQA k/v ([B,S,KV,D], H % KV == 0) by repeating — a fallback
    path, so the materialized repeat is acceptable. Exactly the window<=0
    case of :func:`causal_attention_windowed_jnp` (one masked-softmax
    reference to keep in sync, not two)."""
    return causal_attention_windowed_jnp(q, k, v, 0, sm_scale)


def _pallas_ok(q) -> bool:
    B, S, H, D = q.shape
    if jax.default_backend() not in ("tpu",):
        return False
    # the shape rule lives in ONE place (pallas/flash_attention.flash_ok) so
    # this dispatcher can never disagree with the kernel's own checks. Within
    # the whole-K/V VMEM budget the resident kernels serve; past it,
    # flash_attention streams K/V through the KV-blocked grid variant. The
    # ring (sp) dispatcher keeps the stricter per-shard bound (ring_flash_ok).
    from .pallas.flash_attention import flash_ok

    return flash_ok(S, D)


# public name for model code deciding whether the kernel path will engage
# (e.g. the decoder zoo's GQA prefill keeps its no-repeat grouped einsum
# off-TPU instead of the jnp fallback's materialized repeat)
pallas_attention_ok = _pallas_ok


def cached_attention(q, k_cache, v_cache, pos, impl: str = "auto", sm_scale: Optional[float] = None):
    """Single-token decode attention against a KV cache: q [B,H,D],
    caches [B,Smax,KV,D] (KV == H, or H % KV == 0 for GQA),
    pos = highest valid index → [B,H,D].

    Dispatch mirrors :func:`causal_attention`: the Pallas online-softmax
    decode kernel on TPU (reference softmax_context fused inference kernel),
    jnp path elsewhere, chosen by the shape gate alone. The jnp GQA path is a
    grouped einsum — the cache is never repeated on either path.
    """
    from .pallas.flash_attention import validate_kv_heads

    B, H, D = q.shape
    S, KV = k_cache.shape[1], k_cache.shape[2]
    validate_kv_heads(H, k_cache, v_cache)
    if impl in ("auto", "pallas"):
        from .pallas.decode_attention import decode_attention, decode_attention_ok

        if impl == "pallas" or decode_attention_ok(S, D, k_cache.dtype.itemsize):
            return decode_attention(q, k_cache, v_cache, pos, sm_scale=sm_scale)
    elif impl != "jnp":
        raise ValueError(f"unknown attention impl {impl}")
    scale = sm_scale if sm_scale is not None else 1.0 / (D**0.5)
    mask = jnp.arange(S)[None, None, :] <= pos
    # one grouped form covers MHA too (rep == 1): no duplicated math
    rep = H // KV
    qg = q.reshape(B, KV, rep, D)
    scores = jnp.einsum(
        "bgrd,bsgd->bgrs", qg.astype(jnp.float32), k_cache.astype(jnp.float32)
    ) * scale
    probs = jax.nn.softmax(jnp.where(mask[:, :, None], scores, -1e30), axis=-1)
    o = jnp.einsum("bgrs,bsgd->bgrd", probs, v_cache.astype(jnp.float32))
    return o.reshape(B, H, D).astype(q.dtype)


def gather_pool_pages(k_pool, v_pool, block_tables, scales=None):
    """Gather each slot's pages ([B,n,KV,page,D]) and, for int8 pools,
    dequantize them through ``scales [P,KV,2]`` (per-page per-kv-head block
    scales, K at index 0 / V at 1 — ISSUE 12). Pure data movement when
    ``scales`` is None. The ONE dense-view gather both the serving-model
    jnp branches and the dispatcher fallbacks below share — a scale-layout
    change lands everywhere or nowhere."""
    kd = k_pool[block_tables]
    vd = v_pool[block_tables]
    if scales is not None:
        st = scales[block_tables]  # [B, n, KV, 2]
        kd = kd.astype(jnp.float32) * st[..., 0][..., None, None]
        vd = vd.astype(jnp.float32) * st[..., 1][..., None, None]
    return kd, vd


def _paged_kernel_taken(impl: str, ok) -> bool:
    """The paged dispatchers' rule, and the gauge's: ``"pallas"`` forces the
    kernel, ``"auto"`` asks its gate ``ok()``, ``"jnp"`` never takes it."""
    if impl not in ("auto", "pallas", "jnp"):
        raise ValueError(f"unknown attention impl {impl}")
    return impl == "pallas" or (impl == "auto" and ok())


def _live_rows(o, live):
    """``o [B, ...]`` with the rows of slots that are not ``live`` ([B] bool;
    None: all are) zeros, as the paged kernels leave them."""
    if live is None:
        return o
    return jnp.where(live.reshape((-1,) + (1,) * (o.ndim - 1)), o, 0)


def paged_cached_attention(
    q, k_pool, v_pool, block_tables, pos, impl: str = "auto",
    sm_scale: Optional[float] = None, scales=None, layer=None, lo=None,
    name: Optional[str] = None, live=None,
):
    """Single-token decode attention against a PAGED KV cache (the serving
    subsystem's layout): q [B,H,D], pools [P,KV,page,D] (KV == H or
    H % KV == 0), block_tables [B,n] i32 pool-page ids per slot, pos [B] i32
    per-slot highest valid index (inclusive) → [B,H,D]. ``scales``
    [P,KV,2] dequantizes int8 pools (ISSUE 12) — required iff the pool
    dtype is int8. With a static ``layer`` the pools are the serving
    engine's whole [L,P,KV,page,D] arrays: the kernel indexes the layer
    itself (no ``pool[l]`` slice for XLA to materialise), the fallback
    slices it. ``lo`` [B] i32 bounds the keys from below (a sliding window:
    ``lo[b] <= key <= pos[b]``, both counted from the table's first key).
    ``name`` is the kernel call's name in a trace (else that of the jitted
    function that holds it). ``live`` [B] bool names the slots that hold a
    request (None: all): an idle slot's row reads nothing and comes out zeros.

    Dispatch mirrors :func:`cached_attention`: the Pallas paged kernel on TPU
    (the block-table gather IS the kernel's index maps — no dense copy, no
    page past a slot's own length; int8 codes are scaled INSIDE the kernel,
    so HBM traffic is the halved code bytes), and a pure-jnp fallback that
    gathers the slot's pages into a dense view and runs the exact grouped
    einsum of :func:`cached_attention` with a per-slot mask, so the two
    paths agree with the dense cache."""
    B, H, D = q.shape
    KV, page = k_pool.shape[-3:-1]
    if H % KV != 0:
        raise ValueError(f"q heads {H} must divide by KV heads {KV}")
    if (scales is None) == (k_pool.dtype == jnp.int8):
        raise ValueError(
            "paged_cached_attention: scales must be given exactly when the "
            f"pool is int8 (pool dtype {k_pool.dtype}, scales "
            f"{'given' if scales is not None else 'missing'})"
        )
    from .pallas.decode_attention import (
        paged_decode_attention,
        paged_decode_attention_ok,
    )

    if _paged_kernel_taken(impl, lambda: paged_decode_attention_ok(
        KV, page, D, k_pool.dtype.itemsize
    )):
        return paged_decode_attention(
            q, k_pool, v_pool, block_tables, pos, sm_scale=sm_scale,
            scales=scales, layer=layer, lo=lo, name=name, live=live,
        )
    if layer is not None:
        k_pool, v_pool = k_pool[layer], v_pool[layer]
    # gather [B,n,KV,page,D] → logical [B,T,KV,D] per slot (pure data
    # movement; int8 pools dequantize here), then the same grouped math as
    # cached_attention's fallback
    kd, vd = gather_pool_pages(k_pool, v_pool, block_tables, scales)
    kd = jnp.swapaxes(kd, 2, 3).reshape(B, -1, KV, D)
    vd = jnp.swapaxes(vd, 2, 3).reshape(B, -1, KV, D)
    scale = sm_scale if sm_scale is not None else 1.0 / (D**0.5)
    S = kd.shape[1]
    mask = jnp.arange(S)[None, None, :] <= pos[:, None, None]  # [B,1,S]
    if lo is not None:
        mask = mask & (jnp.arange(S)[None, None, :] >= lo[:, None, None])
    rep = H // KV
    qg = q.reshape(B, KV, rep, D)
    scores = jnp.einsum(
        "bgrd,bsgd->bgrs", qg.astype(jnp.float32), kd.astype(jnp.float32)
    ) * scale
    probs = jax.nn.softmax(jnp.where(mask[:, :, None], scores, -1e30), axis=-1)
    o = jnp.einsum("bgrs,bsgd->bgrd", probs, vd.astype(jnp.float32))
    return _live_rows(o.reshape(B, H, D).astype(q.dtype), live)


def paged_multitoken_cached_attention(
    q, k_pool, v_pool, block_tables, base, impl: str = "auto",
    sm_scale: Optional[float] = None, scales=None, layer=None, lo=None,
    name: Optional[str] = None, live=None,
):
    """T-token causal decode attention against a PAGED KV cache (ISSUE 10:
    the speculative verify step and chunked prefill): q [B,T,H,D], pools
    [P,KV,page,D], block_tables [B,n] i32, base [B] i32 — query t of slot b
    sits at absolute position ``base[b] + t`` and attends keys ``<= base[b]
    + t`` → [B,T,H,D]. The chunk's own K/V must already be scattered into
    the pool (update-then-attend, exactly like the single-token step).
    ``layer`` and ``live`` as in :func:`paged_cached_attention`; with ``lo``
    [B] query t attends only keys ``>= lo[b] + t``.

    Dispatch mirrors :func:`paged_cached_attention`: the multitoken Pallas
    kernel on TPU, and a pure-jnp fallback whose T == 1 slice is the exact
    grouped einsum of the single-token fallback (same casts, same masked
    softmax) so the verify step's first query agrees with the decode step
    bit for bit."""
    B, T, H, D = q.shape
    KV, page = k_pool.shape[-3:-1]
    if H % KV != 0:
        raise ValueError(f"q heads {H} must divide by KV heads {KV}")
    if (scales is None) == (k_pool.dtype == jnp.int8):
        raise ValueError(
            "paged_multitoken_cached_attention: scales must be given "
            f"exactly when the pool is int8 (pool dtype {k_pool.dtype})"
        )
    from .pallas.decode_attention import (
        paged_multitoken_attention,
        paged_multitoken_attention_ok,
    )

    if _paged_kernel_taken(impl, lambda: paged_multitoken_attention_ok(
        KV, page, D, T, k_pool.dtype.itemsize, H // KV
    )):
        return paged_multitoken_attention(
            q, k_pool, v_pool, block_tables, base, sm_scale=sm_scale,
            scales=scales, layer=layer, lo=lo, name=name, live=live,
        )
    if layer is not None:
        k_pool, v_pool = k_pool[layer], v_pool[layer]
    kd, vd = gather_pool_pages(k_pool, v_pool, block_tables, scales)
    kd = jnp.swapaxes(kd, 2, 3).reshape(B, -1, KV, D)
    vd = jnp.swapaxes(vd, 2, 3).reshape(B, -1, KV, D)
    scale = sm_scale if sm_scale is not None else 1.0 / (D**0.5)
    S = kd.shape[1]
    # [B, T, S]: key j visible to query t iff j <= base + t
    mask = (
        jnp.arange(S)[None, None, :]
        <= base[:, None, None] + jnp.arange(T)[None, :, None]
    )
    if lo is not None:
        mask = mask & (
            jnp.arange(S)[None, None, :]
            >= lo[:, None, None] + jnp.arange(T)[None, :, None]
        )
    rep = H // KV
    qg = q.reshape(B, T, KV, rep, D)
    scores = jnp.einsum(
        "btgrd,bsgd->btgrs", qg.astype(jnp.float32), kd.astype(jnp.float32)
    ) * scale
    probs = jax.nn.softmax(
        jnp.where(mask[:, :, None, None, :], scores, -1e30), axis=-1
    )
    o = jnp.einsum("btgrs,bsgd->btgrd", probs, vd.astype(jnp.float32))
    return _live_rows(o.reshape(B, T, H, D).astype(q.dtype), live)


def paged_attention_grid_steps(
    impl: str, B: int, KV: int, page: int, D: int, itemsize: int,
    n_pages: int, T: Optional[int] = None, rep: int = 1,
) -> int:
    """The STATIC BOUND on the grid steps of ONE call of the paged attention
    kernel that :func:`paged_cached_attention` (``T`` None) or
    :func:`paged_multitoken_cached_attention` dispatches these shapes to
    under ``impl``: slots x head blocks x page blocks, by the dispatchers'
    own rule and the kernels' own block rules (``rep`` query heads to a
    kv-head widen a multi-token step's rows); what a call of full slots
    takes. A call walks only the items it owns
    (``decode_attention.paged_walk_steps`` reckons them from its lengths and
    its live slots). 0 where the jnp fallback runs."""
    from .pallas import decode_attention as da

    if T is None:
        ok = lambda: da.paged_decode_attention_ok(KV, page, D, itemsize)
        blocks = da.paged_decode_blocks(KV, page, D, itemsize, n_pages)
    else:
        ok = lambda: da.paged_multitoken_attention_ok(
            KV, page, D, T, itemsize, rep
        )
        blocks = da.paged_multitoken_blocks(
            KV, page, D, T, itemsize, n_pages, rep
        )
    if blocks is None or not _paged_kernel_taken(impl, ok):
        return 0
    HB, G = blocks
    return B * (KV // HB) * -(-n_pages // G)


def traced_flash_plan(reset: bool = False) -> dict:
    """Under which plan the resident flash forward last traced in this
    process runs: its q block ``bq``, its inner width ``bk``, and the block
    pairs of ONE forward call it masks (``masked``: the causal edge crosses
    them) and does not (``plain``), from the kernels' own block rule
    (``flash_plan``). All 0 where only the jnp path was traced. ``reset``
    forgets it first: whoever reports a program resets, traces, reads."""
    import sys

    fa = sys.modules.get(f"{__package__}.pallas.flash_attention")
    plan = {} if fa is None else fa.traced_plan
    if reset:
        plan.clear()
    return {key: plan.get(key, 0) for key in ("bq", "bk", "masked", "plain")}


def windowed_attention_ok(q) -> bool:
    """Whether sliding-window causal attention will ride the Pallas kernels
    for this shape: the ordinary dispatch gate plus the resident-kernel
    bound (windows are not implemented in the grid variant). The shape rule
    is windowed_flash_ok — shared with the kernel's own checks so the two
    gates can never disagree."""
    B, S, H, D = q.shape
    from .pallas.flash_attention import windowed_flash_ok

    if jax.default_backend() not in ("tpu",):
        return False
    return windowed_flash_ok(S, D, q.dtype.itemsize)


def causal_attention_windowed_jnp(q, k, v, window, sm_scale: Optional[float] = None):
    """Sliding-window reference path: key j visible to query i iff
    i - window < j <= i; ``window`` may be a traced i32 scalar (<=0 =
    global). GQA k/v accepted by repeating (fallback path).
    The unwindowed :func:`causal_attention_jnp` is the window<=0 case."""
    B, S, H, D = q.shape
    if k.shape[2] != H:
        rep = H // k.shape[2]
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    scale = sm_scale if sm_scale is not None else 1.0 / (D**0.5)
    logits = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * scale
    i = jnp.arange(S)[:, None]
    j = jnp.arange(S)[None, :]
    win = jnp.asarray(window, jnp.int32)
    keep = (j <= i) & ((win <= 0) | (j > i - win))
    logits = jnp.where(keep[None, None], logits, jnp.float32(-1e30))
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def causal_attention(q, k, v, impl: str = "auto", sm_scale: Optional[float] = None,
                     window=None):
    if impl == "jnp":
        if window is not None:
            return causal_attention_windowed_jnp(q, k, v, window, sm_scale)
        return causal_attention_jnp(q, k, v, sm_scale)
    if impl in ("auto", "pallas"):
        ok = windowed_attention_ok(q) if window is not None else _pallas_ok(q)
        if impl == "pallas" or ok:
            from .pallas.flash_attention import flash_attention

            # a (possibly traced) window rides along as a replicated operand
            win = () if window is None else (jnp.asarray(window, jnp.int32),)
            return _on_each_device(
                lambda q, k, v, *w: flash_attention(
                    q, k, v, causal=True, sm_scale=sm_scale,
                    window=w[0] if w else None,
                ),
                q, k, v, *win,
            )
        if window is not None:
            return causal_attention_windowed_jnp(q, k, v, window, sm_scale)
        return causal_attention_jnp(q, k, v, sm_scale)
    raise ValueError(f"unknown attention impl {impl}")


def bidirectional_attention_jnp(q, k, v, mask=None, sm_scale: Optional[float] = None):
    """Encoder attention: [B,S,H,D] -> [B,S,H,D], optional padding ``mask``
    [B,S] (1 = attend), f32 softmax."""
    B, S, H, D = q.shape
    scale = sm_scale if sm_scale is not None else 1.0 / (D**0.5)
    logits = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * scale
    if mask is not None:
        logits = jnp.where(mask[:, None, None, :].astype(bool), logits, jnp.float32(-1e30))
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def bidirectional_attention(
    q, k, v, mask=None, impl: str = "auto", sm_scale: Optional[float] = None
):
    """Non-causal dispatcher with the same gate-decides contract as
    :func:`causal_attention`. The Pallas flash kernel serves the unmasked
    case; a padding mask routes to the jnp path (the kernel has no mask
    input — masked encoder batches are typically short enough that the
    materialized [S,S] is cheap)."""
    if impl == "jnp" or mask is not None:
        return bidirectional_attention_jnp(q, k, v, mask, sm_scale)
    if impl in ("auto", "pallas"):
        if impl == "pallas" or _pallas_ok(q):
            from .pallas.flash_attention import flash_attention

            return _on_each_device(
                functools.partial(flash_attention, causal=False, sm_scale=sm_scale),
                q, k, v,
            )
        return bidirectional_attention_jnp(q, k, v, None, sm_scale)
    raise ValueError(f"unknown attention impl {impl}")


def latent_paged_cached_attention(
    q, pool, block_tables, base, v_width: int, impl: str = "auto",
    sm_scale: float = 1.0, layer=None, name: Optional[str] = None,
):
    """Causal attention of ``T`` query tokens a slot against a LATENT paged
    cache: q [B,T,H,W] (absorbed queries), pool [P,1,page,W] or, with a static
    ``layer``, [L,P,1,page,W]: one row a token that every head reads, keys
    the whole row, values its first ``v_width`` lanes; query t of slot b sits
    at ``base[b] + t`` → [B,T,H,v_width]. ``T`` = 1 is the decode step. The
    tokens' own rows must already be in the pool.

    Dispatch as :func:`paged_cached_attention`: the latent Pallas kernel on a
    TPU (a page read once for scores and values), else a jnp fallback that
    gathers the slot's pages and runs the same masked softmax in float32."""
    B, T, H, W = q.shape
    page = pool.shape[-2]
    from .pallas.latent_attention import latent_attention_ok, latent_paged_attention

    if _paged_kernel_taken(impl, lambda: latent_attention_ok(page, W, pool.dtype.itemsize)):
        return latent_paged_attention(
            q, pool, block_tables, base, v_width, sm_scale, layer=layer, name=name
        )
    if layer is not None:
        pool = pool[layer]
    kd = pool[block_tables].reshape(B, -1, W).astype(jnp.float32)     # [B, S, W]
    S = kd.shape[1]
    seen = (
        jnp.arange(S)[None, None, :]
        <= base[:, None, None] + jnp.arange(T)[None, :, None]
    )  # [B, T, S]
    s = jnp.einsum("bthw,bsw->bths", q.astype(jnp.float32), kd) * sm_scale
    p = jax.nn.softmax(jnp.where(seen[:, :, None, :], s, -1e30), axis=-1)
    return jnp.einsum("bths,bsv->bthv", p, kd[..., :v_width]).astype(q.dtype)


def latent_attention_grid_steps(
    impl: str, B: int, H: int, page: int, W: int, itemsize: int, n_pages: int, T: int = 1,
) -> int:
    """The STATIC BOUND on the grid steps of ONE call of the latent attention
    kernel that :func:`latent_paged_cached_attention` dispatches these shapes
    to under ``impl``: slots x query blocks x page blocks, by the dispatcher's
    own rule and the kernel's own block rule; what a call of full slots takes.
    A call walks only the pairs it owns (``latent_attention.latent_walk_steps``
    reckons them from its lengths). 0 where the jnp fallback runs."""
    from .pallas.latent_attention import latent_attention_ok, latent_blocks

    if not _paged_kernel_taken(impl, lambda: latent_attention_ok(page, W, itemsize)):
        return 0
    TQ, G = latent_blocks(H, page, T, n_pages)
    return B * (T // TQ) * -(-n_pages // G)
