"""Flash attention as a Pallas TPU kernel (fwd + bwd), causal.

TPU-native replacement for the attention core of the reference's fused
transformer kernels (``csrc/transformer/ds_transformer_cuda.cpp`` — attention
score softmax/dropout fused ops; ``softmax_kernels.cu``): one VMEM-resident
online-softmax kernel instead of materializing the [S,S] score matrix in HBM.

Layout: inputs [B, S, H, D]; internally processed as [B*H, S, D].
D may be 64/128/256 (sub-128 head dims are lane-padded by Mosaic).

Block sizes: the resident kernels take theirs from the shape
(:func:`flash_plan`: the q block of a grid step and the width of one inner
iteration over the partner blocks, as measured on the chip), and mask only
the block pairs the causal edge (or a window's trailing edge) crosses: the
pairs wholly inside the band run the same math with no mask. The KV-blocked
grid variant keeps BQ=BK=128 and masks every live pair.

Backward follows the standard flash recomputation: forward also emits the
per-row logsumexp; dq and dk/dv are computed by two kernels that recompute
P = exp(S - lse) blockwise, using delta = rowsum(dO * O).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...telemetry import parts

# Block sizes of the KV-blocked grid variant, the granule every plan of
# flash_plan is a multiple of, and what flash_ok asks S to divide by.
BQ = 128
BK = 128
NUM_LANES = 128  # lse/delta carry a broadcast 128-lane trailing dim (Mosaic
                 # requires >=(8,128)-tileable blocks; same layout as the
                 # official jax TPU flash kernel)
NEG_INF = -1e30


def _mask(s, keep):
    """Scores with the pairs ``keep`` ([bq, bk] bool) drops set to NEG_INF;
    ``keep`` None = a pair wholly inside the band, no mask."""
    return s if keep is None else jnp.where(keep, s, NEG_INF)


def _grid_keep(q_block, k_block):
    """Causal keep of one 128 x 128 pair of the grid variant (key position
    <= query position), which masks every live pair."""
    row = q_block * BQ + jax.lax.broadcasted_iota(jnp.int32, (BQ, BK), 0)
    col = k_block * BK + jax.lax.broadcasted_iota(jnp.int32, (BQ, BK), 1)
    return row >= col


# ---- shared per-block math (one copy for the resident AND grid kernels) ----
#
# Dots take q/k/v/do in their STORAGE dtype with an f32 accumulator: bf16
# inputs then ride the MXU's native bf16 path (4x the f32 matmul rate on
# v4/v5) and the products are still exact in the f32 accumulator, so QK^T
# and dp are bit-identical to an upcast-first formulation. sm_scale is
# applied to the f32 scores AFTER the dot (matches ops.attention's jnp
# reference; exact for any scale, where pre-scaling a bf16 q would round).
# The second GEMM of each pass casts its f32 left operand (p / ds) down to
# the storage dtype — the standard flash-kernel precision contract.
# ``keep`` is the pair's mask (None: none needed), see _mask.

def _online_softmax_step(q, k, v, carry, keep, sm_scale):
    """One K/V block of the online-softmax forward.
    carry = (acc [BQ,D], m [BQ,1], l [BQ,1]) in f32."""
    acc, m_prev, l_prev = carry
    s = _mask(jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * sm_scale, keep)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc = acc * alpha + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    return acc, m_new, l_new


def _dq_block(q, k, v, do, lse, delta, keep, sm_scale):
    """One K/V block's contribution to dq (unscaled: caller multiplies the
    accumulated dq by sm_scale once). lse/delta [BQ,1] f32."""
    s = _mask(jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * sm_scale, keep)
    p = jnp.exp(s - lse)
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    ds = p * (dp - delta)
    return jax.lax.dot_general(
        ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def _dkv_block(q, k, v, do, lse, delta, keep, sm_scale):
    """One Q block's contributions to (dk, dv); dk unscaled (caller applies
    sm_scale once at finalize)."""
    s = _mask(jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * sm_scale, keep)
    p = jnp.exp(s - lse)  # [BQ, BK] f32
    dv = jax.lax.dot_general(
        p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    ds = p * (dp - delta)
    dk = jax.lax.dot_general(
        ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    return dk, dv


def _joint_bwd_block(q, k, v, do, lse, delta, keep, sm_scale):
    """One (q,k) block pair's contributions to (dq, dk, dv) from a SINGLE
    recompute of s/p/dp/ds — the fused-backward building block. The split
    dq/dkv kernels each recompute QK^T, exp, dp and ds for every pair; this
    shares them (7 MXU dots -> 5 per pair, softmax VPU work halved).
    dq/dk returned unscaled (caller applies sm_scale once)."""
    s = _mask(jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * sm_scale, keep)
    p = jnp.exp(s - lse)  # [BQ, BK] f32
    dv = jax.lax.dot_general(
        p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    ds = p * (dp - delta)
    ds_c = ds.astype(q.dtype)
    dq = jax.lax.dot_general(
        ds_c, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    dk = jax.lax.dot_general(
        ds_c, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    return dq, dk, dv


# ---- which pairs a resident kernel walks, and which of them it masks -------
#
# A resident kernel takes a piece of one side (``rows`` rows of q from
# ``row0``, or ``cols`` keys from ``col0``) and walks the other side in
# blocks. THE bounds of that walk, for any sizes, in one place: the live
# range [lo, hi) (blocks wholly outside the band are never visited) is cut
# at a and b into the blocks the window's trailing edge crosses, the blocks
# wholly inside the band, which need no mask, and the blocks the causal edge
# crosses. A walk is its three ranges ``(start, stop, masked, trips)``;
# ``trips`` is stop - start where it is known as the kernel is traced (the
# causal edge of a piece crosses a fixed number of blocks), so that the range
# becomes straight-line code, which the chip runs far faster than a loop of
# a trip count it has to read (PERF.md section 6, PR 33). Where the piece's
# position is a Python int (a grid step that takes the whole sequence) every
# bound is. ``win``: None = no window operand (no window arithmetic is traced
# at all); else the traced i32 scalar, 0 = global. Key j is visible to row i
# iff i - window < j <= i.

_NO_WINDOW = 1 << 30  # a window no sequence reaches (GRID_KERNEL_MAX_SEQ < it)
_UNROLL_MAX = 8  # longest range written out as straight-line code


def _static(*xs) -> bool:
    return all(isinstance(x, int) for x in xs)


def _min(a, b):
    return min(a, b) if _static(a, b) else jnp.minimum(a, b)


def _edge_trips(piece: int, block: int, win):
    """Blocks of ``block`` the causal edge of a ``piece`` crosses, where the
    one divides the other and no window can clip the range."""
    if win is not None:
        return None
    if piece % block == 0:
        return piece // block
    return 1 if block % piece == 0 else None


def _k_walk(row0, rows: int, num_k_blocks: int, bk: int, win):
    """The walk over k blocks of ``bk`` for q rows [row0, row0 + rows):
    [lo, a) cross the window's trailing edge, [a, b) are unmasked, [b, hi)
    cross the causal edge."""
    # k blocks the piece attends into: up to its newest row
    hi = _min(pl.cdiv(row0 + rows, bk), num_k_blocks)
    # k blocks whose newest key the piece's OLDEST row already sees
    b = _min((row0 + 1) // bk, hi)
    lo = a = 0
    if win is not None:
        # first k block the oldest row can see: its oldest visible key is
        # row0 - window + 1
        lo = jnp.where(win > 0, jnp.maximum(0, (row0 - win + 1) // bk), 0)
        # first k block whose oldest key the piece's NEWEST row still sees
        a = jnp.where(win > 0, jnp.maximum(0, pl.cdiv(row0 + rows - win, bk)), 0)
        # a window narrower than a block: a > b, and every live pair is masked
        a = jnp.minimum(a, hi)
        b = jnp.maximum(a, b)
    return ((lo, a, True, None), (a, b, False, None),
            (b, hi, True, _edge_trips(rows, bk, win)))


def _q_walk(col0, cols: int, num_q_blocks: int, bq: int, win):
    """The walk over q blocks of ``bq`` for keys [col0, col0 + cols):
    [lo, a) cross the causal edge, [a, b) are unmasked, [b, hi) cross the
    window's trailing edge."""
    # first q block that can attend to the piece's oldest key
    lo = col0 // bq
    # first q block whose oldest row sees the piece's NEWEST key
    a = _min(pl.cdiv(col0 + cols - 1, bq), num_q_blocks)
    b = hi = num_q_blocks
    if win is not None:
        # one-past-last q block that can see the piece: its newest key
        # (col0 + cols - 1) is visible to rows up to key + window - 1
        hi = jnp.where(
            win > 0,
            jnp.minimum(num_q_blocks, (col0 + cols + win - 2) // bq + 1),
            num_q_blocks,
        )
        # one-past-last q block whose NEWEST row still sees the piece's
        # oldest key: (i+1)*bq - 1 - window < col0
        b = jnp.where(win > 0, jnp.minimum((col0 + win) // bq, hi), hi)
        a = jnp.minimum(a, hi)
        b = jnp.maximum(a, b)
    return ((lo, a, True, _edge_trips(cols, bq, win)), (a, b, False, None),
            (b, hi, True, None))


def _whole_walk(num_blocks: int):
    """The walk of a kernel that is not causal: every block, none masked."""
    return ((0, num_blocks, False, None),)


def _walk(ranges, pair, carry):
    """Run ``pair(j, carry, masked)`` over the ranges of a walk."""
    for start, stop, masked, trips in ranges:
        if _static(start, stop):
            trips = max(stop - start, 0)
        if trips is not None and trips <= _UNROLL_MAX:
            for t in range(trips):
                carry = pair(start + t, carry, masked)
        else:
            carry = jax.lax.fori_loop(
                start, stop, functools.partial(pair, masked=masked), carry
            )
    return carry


def _pair_diff(rows: int, cols: int):
    """row - col inside a pair, made once a grid step for all its masks."""
    return (jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 0)
            - jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 1))


def _edge_keep(diff, off, win):
    """The mask of a pair an edge crosses. ``diff`` is _pair_diff, ``off``
    the pair's first column less its first row, so row - col of the sequence
    is diff - off: keep iff 0 <= diff - off < window. Selects what the one
    mask of every pair selected before the walk was split."""
    keep = diff >= off
    if win is not None:
        keep = keep & (diff < off + jnp.where(win > 0, win, _NO_WINDOW))
    return keep


def plan_pairs(S: int, bq: int, bk: int, causal: bool = True) -> tuple:
    """(masked, plain) pairs one head's forward walks under a plan with no
    window: pieces of min(bq, bk) rows against blocks of bk keys, told apart
    by the positions themselves, not by the walk's bounds (the tests hold
    the two against each other)."""
    rows = min(bq, bk)
    masked = plain = 0
    for row0 in range(0, S, rows):
        for col0 in range(0, S, bk):
            if not causal or col0 + bk - 1 <= row0:
                plain += 1
            elif col0 <= row0 + rows - 1:
                masked += 1
    return masked, plain


def _row_pieces(win_ref, q_ref, *, seq_len: int, bk: int, causal: bool, windowed: bool):
    """A grid step over ``bq`` rows of q, as the forward, the dq and the
    fused backward kernels take it: pieces of min(bq, bk) rows, each with
    its slice of the step's blocks, its walk over the k blocks and
    ``keep(j, masked)``, the mask of its pair with k block j (None where the
    pair needs none). A step that takes the whole sequence knows every
    bound as it is traced."""
    bq = q_ref.shape[1]
    rows = min(bq, bk)
    base = 0 if bq == seq_len else pl.program_id(1) * bq
    win = win_ref[0] if windowed else None  # i32 scalar; 0 = global
    nk = seq_len // bk
    diff = _pair_diff(rows, bk) if causal else None
    for r in range(0, bq, rows):
        row0 = base + r

        def keep(j, masked, row0=row0):
            return _edge_keep(diff, j * bk - row0, win) if masked else None

        walk = _k_walk(row0, rows, nk, bk, win) if causal else _whole_walk(nk)
        yield slice(r, r + rows), walk, keep


# This kernel keeps the full per-(batch,head) K/V (fwd, dq) or Q/dO (dkv) block
# resident in VMEM (~16 MB/core). Budget for the largest such array; beyond it
# callers must shard the sequence (ring attention over the sp axis).
VMEM_RESIDENT_BYTES = 4 * 1024 * 1024


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(win_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, *, sm_scale: float,
                bk: int, **step):
    """One grid step: ``bq`` rows of one head's q against its whole K/V,
    in pieces (_row_pieces) that each walk the k blocks they see."""
    for rows, walk, keep in _row_pieces(win_ref, q_ref, bk=bk, **step):
        q = q_ref[0, rows, :]  # storage dtype (bf16 dots ride the native MXU path)

        def pair(j, carry, masked):
            k = k_ref[0, pl.ds(j * bk, bk), :]  # [bk, D]
            v = v_ref[0, pl.ds(j * bk, bk), :]
            return _online_softmax_step(q, k, v, carry, keep(j, masked), sm_scale)

        n = q.shape[0]
        acc0 = jnp.zeros(q.shape, jnp.float32)
        m0 = jnp.full((n, 1), NEG_INF, jnp.float32)
        l0 = jnp.zeros((n, 1), jnp.float32)
        acc, m, l = _walk(walk, pair, (acc0, m0, l0))
        l = jnp.maximum(l, 1e-30)
        o_ref[0, rows, :] = (acc / l).astype(o_ref.dtype)
        lse_ref[0, rows, :] = jax.lax.broadcast_in_dim(
            (m + jnp.log(l))[:, 0], (n, NUM_LANES), (0,)
        )


def _win_arr(window) -> jnp.ndarray:
    """Scalar-prefetch operand for the resident kernels (i32[1]; 0=global)."""
    return jnp.asarray(0 if window is None else window, jnp.int32).reshape(1)


def _fwd(q3, k3, v3, sm_scale: float, causal: bool, interpret: bool = False, kv_rep: int = 1, window=None):
    """q3: [BH, S, D], k3/v3: [BH // kv_rep, S, D] → (o [BH,S,D], lse).

    ``kv_rep`` > 1 is grouped-query attention: the flattened batch dim packs
    q heads group-major (bh = (b*KV + g)*rep + r), so the K/V index maps
    simply divide by rep — every q head in a group reads the SAME K/V block
    and the repeated cache is never materialized.

    ``window`` (i32 scalar, traced OK; None/0 = global): sliding-window
    causal attention — key j visible to query i iff i-window < j <= i. Rides
    a scalar-prefetch operand so one compiled kernel serves every per-layer
    window (GPT-Neo alternating local/global layers under one lax.scan)."""
    BH, S, D = q3.shape
    bq, bk = flash_plan(S, D, q3.dtype.itemsize, kv_rep, causal=causal)
    _note_plan(S, BH, bq, bk, causal)
    kernel = functools.partial(
        _fwd_kernel, sm_scale=sm_scale, causal=causal, seq_len=S, bk=bk,
        windowed=window is not None,
    )
    head_idx = lambda b, i, w: (b, i, 0)  # noqa: E731
    kv_idx = lambda b, i, w: (b // kv_rep, 0, 0)  # noqa: E731
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(BH, S // bq),
            in_specs=[
                pl.BlockSpec((1, bq, D), head_idx),
                pl.BlockSpec((1, S, D), kv_idx),
                pl.BlockSpec((1, S, D), kv_idx),
            ],
            out_specs=[
                pl.BlockSpec((1, bq, D), head_idx),
                pl.BlockSpec((1, bq, NUM_LANES), head_idx),
            ],
        ),
        interpret=interpret,
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, D), q3.dtype),
            jax.ShapeDtypeStruct((BH, S, NUM_LANES), jnp.float32),
        ],
    )(_win_arr(window), q3, k3, v3)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _bwd_dq_kernel(win_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   *, sm_scale, bk, **step):
    for rows, walk, keep in _row_pieces(win_ref, q_ref, bk=bk, **step):
        q = q_ref[0, rows, :]
        do = do_ref[0, rows, :]
        # load full lanes, slice the VALUE: a width-1 lane slice in the ref
        # indexer is a Mosaic hazard; the value slice is free (lanes broadcast)
        lse = lse_ref[0, rows, :][:, 0:1]  # [rows, 1]
        delta = delta_ref[0, rows, :][:, 0:1]

        def pair(j, dq, masked):
            k = k_ref[0, pl.ds(j * bk, bk), :]
            v = v_ref[0, pl.ds(j * bk, bk), :]
            return dq + _dq_block(q, k, v, do, lse, delta, keep(j, masked), sm_scale)

        dq = _walk(walk, pair, jnp.zeros(q.shape, jnp.float32))
        dq_ref[0, rows, :] = (dq * sm_scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(win_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
                    *, sm_scale, causal, seq_len, bq, windowed):
    """One grid step: one block of ``bk`` keys against the head's whole
    q/dO, walked in blocks of ``bq`` rows (the plan's piece)."""
    bk, D = k_ref.shape[1:]
    col0 = 0 if bk == seq_len else pl.program_id(1) * bk
    win = win_ref[0] if windowed else None
    k = k_ref[0]  # [bk, D]
    v = v_ref[0]
    nq = seq_len // bq
    diff = _pair_diff(bq, bk) if causal else None

    def pair(i, carry, masked):
        dk, dv = carry
        q = q_ref[0, pl.ds(i * bq, bq), :]
        do = do_ref[0, pl.ds(i * bq, bq), :]
        # dynamic sublane slice at full lanes, then slice the value (the
        # combined dynamic-sublane + width-1-lane ref slice is a Mosaic hazard)
        lse = lse_ref[0, pl.ds(i * bq, bq), :][:, 0:1]  # [bq, 1]
        delta = delta_ref[0, pl.ds(i * bq, bq), :][:, 0:1]
        keep = _edge_keep(diff, col0 - i * bq, win) if masked else None
        dkc, dvc = _dkv_block(q, k, v, do, lse, delta, keep, sm_scale)
        return dk + dkc, dv + dvc

    dk0 = jnp.zeros((bk, D), jnp.float32)
    dv0 = jnp.zeros((bk, D), jnp.float32)
    walk = _q_walk(col0, bk, nq, bq, win) if causal else _whole_walk(nq)
    dk, dv = _walk(walk, pair, (dk0, dv0))
    dk_ref[0] = (dk * sm_scale).astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


# Fused-backward VMEM budget per element of [S,D]: K + V (bf16, resident,
# 2+2 B) + whole-sequence dk/dv f32 scratch (4+4 B) + the revisited dk/dv
# output blocks (2+2 B bf16 MHA; 4+4 B f32 when GQA stages per-q-head
# grads) = 16 B (20 B GQA). 8 MB keeps the kernel comfortably inside VMEM
# next to the per-block operands; larger resident shapes fall back to the
# split dq/dkv kernels.
FUSED_BWD_BYTES = 8 * 1024 * 1024


def _fused_bwd_ok(S: int, D: int, kv_rep: int = 1) -> bool:
    per_elem = 20 if kv_rep > 1 else 16
    return S * D * per_elem <= FUSED_BWD_BYTES


def _bwd_fused_kernel(win_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      dq_ref, dk_ref, dv_ref, dk_acc, dv_acc,
                      *, sm_scale, bk, **step):
    """dq + dk + dv in ONE pass over the (q,k) block pairs (resident shapes):
    dk/dv accumulate in whole-sequence VMEM f32 scratch across the
    sequential q-block grid dimension and are written once at the last q
    step. Each pair's s/p/dp/ds are computed once (_joint_bwd_block) instead
    of once per split kernel."""
    qi = pl.program_id(1)

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    for rows, walk, keep in _row_pieces(win_ref, q_ref, bk=bk, **step):
        q = q_ref[0, rows, :]
        do = do_ref[0, rows, :]
        # load full lanes, slice the VALUE (width-1 lane ref slices are a
        # Mosaic hazard — same pattern as the split kernels)
        lse = lse_ref[0, rows, :][:, 0:1]
        delta = delta_ref[0, rows, :][:, 0:1]

        def pair(j, dq, masked):
            k = k_ref[0, pl.ds(j * bk, bk), :]
            v = v_ref[0, pl.ds(j * bk, bk), :]
            dqc, dkc, dvc = _joint_bwd_block(
                q, k, v, do, lse, delta, keep(j, masked), sm_scale
            )
            dk_acc[pl.ds(j * bk, bk), :] = dk_acc[pl.ds(j * bk, bk), :] + dkc
            dv_acc[pl.ds(j * bk, bk), :] = dv_acc[pl.ds(j * bk, bk), :] + dvc
            return dq + dqc

        dq = _walk(walk, pair, jnp.zeros(q.shape, jnp.float32))
        dq_ref[0, rows, :] = (dq * sm_scale).astype(dq_ref.dtype)

    @pl.when(qi == pl.num_programs(1) - 1)
    def _finalize():
        dk_ref[0] = (dk_acc[...] * sm_scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _bwd_fused(q3, k3, v3, delta, lse, do3, sm_scale, causal, interpret, kv_rep, window):
    BH, S, D = q3.shape
    bq, bk = flash_plan(S, D, q3.dtype.itemsize, kv_rep, backward=True, causal=causal)
    head_idx = lambda b, i, w: (b, i, 0)  # noqa: E731
    kv_idx = lambda b, i, w: (b // kv_rep, 0, 0)  # noqa: E731
    # dk/dv staged PER Q HEAD (b, not b//kv_rep): under GQA the group is
    # summed outside in f32 so the storage rounding happens exactly once
    dkv_idx = lambda b, i, w: (b, 0, 0)  # noqa: E731
    dkv_shape = jax.ShapeDtypeStruct((BH, S, D), jnp.float32 if kv_rep > 1 else q3.dtype)
    return pl.pallas_call(
        functools.partial(
            _bwd_fused_kernel, sm_scale=sm_scale, causal=causal,
            seq_len=S, bk=bk, windowed=window is not None,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(BH, S // bq),
            in_specs=[
                pl.BlockSpec((1, bq, D), head_idx),
                pl.BlockSpec((1, S, D), kv_idx),
                pl.BlockSpec((1, S, D), kv_idx),
                pl.BlockSpec((1, bq, D), head_idx),
                pl.BlockSpec((1, bq, NUM_LANES), head_idx),
                pl.BlockSpec((1, bq, NUM_LANES), head_idx),
            ],
            out_specs=[
                pl.BlockSpec((1, bq, D), head_idx),
                pl.BlockSpec((1, S, D), dkv_idx),
                pl.BlockSpec((1, S, D), dkv_idx),
            ],
            scratch_shapes=[
                pltpu.VMEM((S, D), jnp.float32),
                pltpu.VMEM((S, D), jnp.float32),
            ],
        ),
        interpret=interpret,
        out_shape=[jax.ShapeDtypeStruct((BH, S, D), q3.dtype), dkv_shape, dkv_shape],
    )(_win_arr(window), q3, k3, v3, do3, lse, delta)


def _bwd(q3, k3, v3, o3, lse, do3, sm_scale: float, causal: bool, interpret: bool = False, kv_rep: int = 1, window=None):
    """Grads for _fwd. With ``kv_rep`` > 1 (GQA) the dk/dv kernels run at
    per-q-head resolution ([BH,S,D], each reading its group's K/V block via
    the divided index map); the caller sums the rep axis to get the true
    [BH//rep, S, D] K/V grads (gradient of a shared tensor accumulates over
    the q heads sharing it).

    Deliberate tradeoff: the per-q-head f32 staging transiently costs
    rep x 4 bytes over the final dk/dv footprint. It buys exactly-once
    rounding AND keeps the (batch*head) grid dimension parallel —
    accumulating the group inside the kernel would force sequential
    output-block revisiting over that dimension. dk/dv are layer-local
    transients, so the peak coexists with one layer's backward only;
    revisit if profiles show it matters at rep >= 8."""
    BH, S, D = q3.shape
    delta = jnp.sum(do3.astype(jnp.float32) * o3.astype(jnp.float32), axis=-1)  # [BH,S]
    delta = jnp.broadcast_to(delta[..., None], (BH, S, NUM_LANES))

    full = lambda b, i, w: (b, 0, 0)
    kv_full = lambda b, i, w: (b // kv_rep, 0, 0)
    if _fused_bwd_ok(S, D, kv_rep):
        dq, dk, dv = _bwd_fused(
            q3, k3, v3, delta, lse, do3, sm_scale, causal, interpret, kv_rep, window
        )
        if kv_rep > 1:
            dk = dk.reshape(BH // kv_rep, kv_rep, S, D).sum(axis=1).astype(k3.dtype)
            dv = dv.reshape(BH // kv_rep, kv_rep, S, D).sum(axis=1).astype(v3.dtype)
        return dq, dk, dv
    bq, bk = flash_plan(S, D, q3.dtype.itemsize, kv_rep, backward=True, causal=causal)
    win = _win_arr(window)
    static = dict(sm_scale=sm_scale, causal=causal, seq_len=S, windowed=window is not None)
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, bk=bk, **static),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(BH, S // bq),
            in_specs=[
                pl.BlockSpec((1, bq, D), lambda b, i, w: (b, i, 0)),
                pl.BlockSpec((1, S, D), kv_full),
                pl.BlockSpec((1, S, D), kv_full),
                pl.BlockSpec((1, bq, D), lambda b, i, w: (b, i, 0)),
                pl.BlockSpec((1, bq, NUM_LANES), lambda b, i, w: (b, i, 0)),
                pl.BlockSpec((1, bq, NUM_LANES), lambda b, i, w: (b, i, 0)),
            ],
            out_specs=pl.BlockSpec((1, bq, D), lambda b, i, w: (b, i, 0)),
        ),
        interpret=interpret,
        out_shape=jax.ShapeDtypeStruct((BH, S, D), q3.dtype),
    )(win, q3, k3, v3, do3, lse, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, bq=min(bq, bk), **static),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(BH, S // bk),
            in_specs=[
                pl.BlockSpec((1, S, D), full),
                pl.BlockSpec((1, bk, D), lambda b, i, w: (b // kv_rep, i, 0)),
                pl.BlockSpec((1, bk, D), lambda b, i, w: (b // kv_rep, i, 0)),
                pl.BlockSpec((1, S, D), full),
                pl.BlockSpec((1, S, NUM_LANES), full),
                pl.BlockSpec((1, S, NUM_LANES), full),
            ],
            out_specs=[
                pl.BlockSpec((1, bk, D), lambda b, i, w: (b, i, 0)),
                pl.BlockSpec((1, bk, D), lambda b, i, w: (b, i, 0)),
            ],
        ),
        interpret=interpret,
        out_shape=[
            # GQA: per-q-head grads stay f32 so the rep-axis sum below
            # rounds to the storage dtype exactly once (like the MHA path)
            jax.ShapeDtypeStruct((BH, S, D), jnp.float32 if kv_rep > 1 else q3.dtype),
            jax.ShapeDtypeStruct((BH, S, D), jnp.float32 if kv_rep > 1 else q3.dtype),
        ],
    )(win, q3, k3, v3, do3, lse, delta)
    if kv_rep > 1:
        dk = dk.reshape(BH // kv_rep, kv_rep, S, D).sum(axis=1).astype(k3.dtype)
        dv = dv.reshape(BH // kv_rep, kv_rep, S, D).sum(axis=1).astype(v3.dtype)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# KV-blocked (grid) variant: K/V stream block-by-block through the grid's
# innermost dimension with the online-softmax state carried in VMEM scratch,
# so nothing sequence-length-sized is ever VMEM-resident. Removes the
# whole-K/V budget bound of the kernels above: single-device sequence length
# is then limited by HBM (q/k/v/o + the [BH,S,128] lse), not VMEM. Same
# math, same outputs, same custom-VJP structure.
# ---------------------------------------------------------------------------

# HBM-level ceiling for the grid variant: the broadcast-lane lse residual is
# [B*H, S, 128] f32 (plus a same-sized delta in backward), so the bookkeeping
# itself gets large past ~256k tokens per device.
GRID_KERNEL_MAX_SEQ = 128 * 2048

_GRID_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary")
)


def _causal_block_live(qi, ki):
    """True when k block ki intersects the causal triangle of q block qi."""
    return ki * BK <= qi * BQ + (BQ - 1)


def _kv_index_causal(b, i, j):
    """K/V index map for causal fwd/dq grids: dead steps (past the triangle)
    clamp to the last live block, so their iteration revisits the resident
    block instead of DMAing K/V it will never use."""
    return (b, jnp.minimum(j, (i * BQ + BQ - 1) // BK), 0)


def _q_index_causal(b, j, i):
    """Q-side index map for the causal dkv grid: steps before the first live
    q block clamp up to it (same DMA-elision trick, from below)."""
    return (b, jnp.maximum(i, (j * BK) // BQ), 0)


def _fwd_grid_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
    *, sm_scale: float, causal: bool, num_k_blocks: int,
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        carry = (acc_ref[...], m_ref[:, 0:1], l_ref[:, 0:1])
        keep = _grid_keep(qi, ki) if causal else None
        acc, m_new, l_new = _online_softmax_step(q, k, v, carry, keep, sm_scale)
        acc_ref[...] = acc
        m_ref[...] = jax.lax.broadcast_in_dim(m_new[:, 0], m_ref.shape, (0,))
        l_ref[...] = jax.lax.broadcast_in_dim(l_new[:, 0], l_ref.shape, (0,))

    if causal:
        @pl.when(_causal_block_live(qi, ki))
        def _():
            compute()
    else:
        compute()

    @pl.when(ki == num_k_blocks - 1)
    def _finalize():
        l = jnp.maximum(l_ref[:, 0:1], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)
        lse_ref[0] = m_ref[...] + jnp.log(jnp.maximum(l_ref[...], 1e-30))


def _fwd_grid(q3, k3, v3, sm_scale: float, causal: bool, interpret: bool = False, kv_rep: int = 1):
    BH, S, D = q3.shape
    nq, nk = S // BQ, S // BK
    kernel = functools.partial(
        _fwd_grid_kernel, sm_scale=sm_scale, causal=causal, num_k_blocks=nk
    )
    if causal:
        kv_idx = lambda b, i, j: _kv_index_causal(b // kv_rep, i, j)
    else:
        kv_idx = lambda b, i, j: (b // kv_rep, j, 0)
    o, lse = pl.pallas_call(
        kernel,
        grid=(BH, nq, nk),
        interpret=interpret,
        in_specs=[
            pl.BlockSpec((1, BQ, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, BK, D), kv_idx),
            pl.BlockSpec((1, BK, D), kv_idx),
        ],
        out_specs=[
            pl.BlockSpec((1, BQ, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, BQ, NUM_LANES), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, D), q3.dtype),
            jax.ShapeDtypeStruct((BH, S, NUM_LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((BQ, D), jnp.float32),
            pltpu.VMEM((BQ, NUM_LANES), jnp.float32),
            pltpu.VMEM((BQ, NUM_LANES), jnp.float32),
        ],
        compiler_params=_GRID_PARAMS,
    )(q3, k3, v3)
    return o, lse


def _bwd_dq_grid_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_acc,
    *, sm_scale: float, causal: bool, num_k_blocks: int,
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    def compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0, :, 0:1]
        delta = delta_ref[0, :, 0:1]
        keep = _grid_keep(qi, ki) if causal else None
        dq_acc[...] = dq_acc[...] + _dq_block(q, k, v, do, lse, delta, keep, sm_scale)

    if causal:
        @pl.when(_causal_block_live(qi, ki))
        def _():
            compute()
    else:
        compute()

    @pl.when(ki == num_k_blocks - 1)
    def _finalize():
        dq_ref[0] = (dq_acc[...] * sm_scale).astype(dq_ref.dtype)


def _bwd_dkv_grid_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
    dk_acc, dv_acc, *, sm_scale: float, causal: bool, num_q_blocks: int,
):
    ki = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0, :, 0:1]
        delta = delta_ref[0, :, 0:1]
        keep = _grid_keep(qi, ki) if causal else None
        dkc, dvc = _dkv_block(q, k, v, do, lse, delta, keep, sm_scale)
        dk_acc[...] = dk_acc[...] + dkc
        dv_acc[...] = dv_acc[...] + dvc

    if causal:
        @pl.when(_causal_block_live(qi, ki))
        def _():
            compute()
    else:
        compute()

    @pl.when(qi == num_q_blocks - 1)
    def _finalize():
        dk_ref[0] = (dk_acc[...] * sm_scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _bwd_grid(q3, k3, v3, o3, lse, do3, sm_scale: float, causal: bool, interpret: bool = False, kv_rep: int = 1):
    BH, S, D = q3.shape
    nq, nk = S // BQ, S // BK
    delta = jnp.sum(do3.astype(jnp.float32) * o3.astype(jnp.float32), axis=-1)
    delta = jnp.broadcast_to(delta[..., None], (BH, S, NUM_LANES))

    if causal:
        kv_idx = lambda b, i, j: _kv_index_causal(b // kv_rep, i, j)
    else:
        kv_idx = lambda b, i, j: (b // kv_rep, j, 0)
    dq = pl.pallas_call(
        functools.partial(
            _bwd_dq_grid_kernel, sm_scale=sm_scale, causal=causal, num_k_blocks=nk
        ),
        grid=(BH, nq, nk),
        interpret=interpret,
        in_specs=[
            pl.BlockSpec((1, BQ, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, BK, D), kv_idx),
            pl.BlockSpec((1, BK, D), kv_idx),
            pl.BlockSpec((1, BQ, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, BQ, NUM_LANES), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, BQ, NUM_LANES), lambda b, i, j: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, BQ, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, D), q3.dtype),
        scratch_shapes=[pltpu.VMEM((BQ, D), jnp.float32)],
        compiler_params=_GRID_PARAMS,
    )(q3, k3, v3, do3, lse, delta)

    q_idx = _q_index_causal if causal else (lambda b, j, i: (b, i, 0))
    dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_dkv_grid_kernel, sm_scale=sm_scale, causal=causal, num_q_blocks=nq
        ),
        grid=(BH, nk, nq),
        interpret=interpret,
        in_specs=[
            pl.BlockSpec((1, BQ, D), q_idx),
            pl.BlockSpec((1, BK, D), lambda b, j, i: (b // kv_rep, j, 0)),
            pl.BlockSpec((1, BK, D), lambda b, j, i: (b // kv_rep, j, 0)),
            pl.BlockSpec((1, BQ, D), q_idx),
            pl.BlockSpec((1, BQ, NUM_LANES), q_idx),
            pl.BlockSpec((1, BQ, NUM_LANES), q_idx),
        ],
        out_specs=[
            pl.BlockSpec((1, BK, D), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, BK, D), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            # GQA: f32 per-q-head grads, one rounding after the rep sum
            jax.ShapeDtypeStruct((BH, S, D), jnp.float32 if kv_rep > 1 else q3.dtype),
            jax.ShapeDtypeStruct((BH, S, D), jnp.float32 if kv_rep > 1 else q3.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((BK, D), jnp.float32),
            pltpu.VMEM((BK, D), jnp.float32),
        ],
        compiler_params=_GRID_PARAMS,
    )(q3, k3, v3, do3, lse, delta)
    if kv_rep > 1:
        dk = dk.reshape(BH // kv_rep, kv_rep, S, D).sum(axis=1).astype(k3.dtype)
        dv = dv.reshape(BH // kv_rep, kv_rep, S, D).sum(axis=1).astype(v3.dtype)
    return dq, dk, dv


def resident_ok(S: int, D: int, itemsize: int) -> bool:
    """THE resident-vs-grid split: whether one (batch, head)'s K or V slab
    fits the whole-K/V VMEM budget. Shared by the auto dispatchers and any
    telemetry that reports which variant served a shape."""
    return S * D * itemsize <= VMEM_RESIDENT_BYTES


# What the census of the resident kernels found (v5e, bf16, D 64 and 128,
# S 512 to 4096; PERF.md section 6, PR 33): a pair's time is loop and grid
# overhead before it is arithmetic, so the widest inner iteration measured
# (512 keys) wins at every shape, and a grid step that takes a head's whole
# sequence, whose walk is then straight-line code, beats one of 512 rows by
# a fifth to a quarter. That holds while the walk is at most
# PLAN_WHOLE_PAIRS pairs: written out longer it outgrows the kernel's stack.
PLAN_WIDTHS = (512, 256)
PLAN_WHOLE_PAIRS = 10
# The VMEM a v5e kernel is allowed (Mosaic's scoped limit).
PLAN_VMEM_BYTES = 16 * 1024 * 1024


def plan_vmem_bytes(S: int, D: int, itemsize: int, kv_rep: int, bq: int,
                    bk: int, backward: bool) -> int:
    """VMEM a resident kernel needs under a plan: the [rows, bk] f32 tiles
    alive in a pair (forward s and p; fused backward s, p, dp and ds), two
    buffers each of K and V, of the step's q rows and of what comes and goes
    with them, and in the backward the two [S, D] f32 accumulators and two
    buffers each of the dk and dv they are written to. A reckoning from
    shapes that errs high where it was held against the chip's compiler,
    which has the last word: tests/unit/ops/test_mosaic_compile.py asks it."""
    tile = min(bq, bk) * bk * 4
    kv = 2 * S * D * itemsize
    row = D * itemsize      # one row of q, o, dO or dq
    stat = NUM_LANES * 4    # one row of lse or delta
    if not backward:
        return 2 * tile + 2 * (kv + bq * (2 * row + stat))
    staged = 4 if kv_rep > 1 else itemsize
    accumulators = 2 * S * max(D, NUM_LANES) * 4
    return (4 * tile + accumulators
            + 2 * (kv + bq * (3 * row + 2 * stat) + 2 * S * D * staged))


def flash_plan(S: int, D: int, itemsize: int, kv_rep: int = 1,
               backward: bool = False, causal: bool = True) -> tuple:
    """THE block rule of the resident kernels: (q rows of a grid step,
    width of one inner iteration over k) for a shape. The widest measured
    width that divides S, under a grid step of the whole sequence where its
    walk is short enough and fits, else of one piece; 128 x 128, what every
    shape ran before, where the chip was not asked (other head dims, f32
    operands, sequences under 512, the split backward)."""
    if D not in (64, 128) or itemsize != 2 or S < 512:
        return BQ, BK
    if backward and not _fused_bwd_ok(S, D, kv_rep):
        return BQ, BK  # the split dq / dkv kernels were not in the census
    for bk in PLAN_WIDTHS:
        if S % bk:
            continue
        fits = lambda bq: plan_vmem_bytes(
            S, D, itemsize, kv_rep, bq, bk, backward) <= PLAN_VMEM_BYTES
        if sum(plan_pairs(S, S, bk, causal)) <= PLAN_WHOLE_PAIRS and fits(S):
            return S, bk
        if fits(bk):
            return bk, bk
    return BQ, BK


# The plan of the last resident forward traced in this process, for whoever
# reports what a compiled step runs (runtime/engine.py copies it into the
# ``ds.init.programs`` phase): empty while only the jnp path was traced.
traced_plan: dict = {}


def _note_plan(S: int, BH: int, bq: int, bk: int, causal: bool) -> None:
    masked, plain = plan_pairs(S, bq, bk, causal)
    traced_plan.update(bq=bq, bk=bk, masked=BH * masked, plain=BH * plain)


def _fwd_auto(q3, k3, v3, sm_scale: float, causal: bool, interpret: bool = False, kv_rep: int = 1, window=None):
    """Resident kernels inside the whole-K/V VMEM budget, grid variant past
    it — the one dispatch point shared by flash_attention AND the ring(sp)
    per-block compute. Sliding windows ride the resident kernels only
    (callers gate via windowed_flash_ok)."""
    BH, S, D = q3.shape
    if resident_ok(S, D, q3.dtype.itemsize):
        return _fwd(q3, k3, v3, sm_scale, causal, interpret, kv_rep, window)
    if window is not None:
        raise NotImplementedError(
            "windowed attention requires the resident kernels (shape past "
            "the VMEM budget); silently dropping the window would compute "
            "global attention"
        )
    return _fwd_grid(q3, k3, v3, sm_scale, causal, interpret, kv_rep)


def _bwd_auto(q3, k3, v3, o3, lse, do3, sm_scale: float, causal: bool, interpret: bool = False, kv_rep: int = 1, window=None):
    BH, S, D = q3.shape
    if resident_ok(S, D, q3.dtype.itemsize):
        return _bwd(q3, k3, v3, o3, lse, do3, sm_scale, causal, interpret, kv_rep, window)
    if window is not None:
        raise NotImplementedError(
            "windowed attention requires the resident kernels (shape past "
            "the VMEM budget); silently dropping the window would compute "
            "global attention"
        )
    return _bwd_grid(q3, k3, v3, o3, lse, do3, sm_scale, causal, interpret, kv_rep)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash_grid(q3, k3, v3, sm_scale: float, causal: bool, interpret: bool):
    o, _ = _fwd_grid(q3, k3, v3, sm_scale, causal, interpret)
    return o


def _flash_grid_fwd_rule(q3, k3, v3, sm_scale, causal, interpret):
    o, lse = _fwd_grid(q3, k3, v3, sm_scale, causal, interpret)
    return o, (q3, k3, v3, o, lse)


def _flash_grid_bwd_rule(sm_scale, causal, interpret, res, do3):
    q3, k3, v3, o3, lse = res
    dq, dk, dv = _bwd_grid(q3, k3, v3, o3, lse, do3, sm_scale, causal, interpret)
    return dq, dk, dv


_flash_grid.defvjp(_flash_grid_fwd_rule, _flash_grid_bwd_rule)


# ---------------------------------------------------------------------------
# public API with custom VJP
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _flash(q3, k3, v3, window, sm_scale: float, causal: bool, interpret: bool, kv_rep: int = 1):
    """``window``: i32[1] (may be traced; [0] = global). Rides the primal
    argument list because a traced value cannot be a nondiff argnum; its
    cotangent is float0 (integer dtype)."""
    o, _ = _fwd_auto(q3, k3, v3, sm_scale, causal, interpret, kv_rep, window)
    return o


def _flash_fwd_rule(q3, k3, v3, window, sm_scale, causal, interpret, kv_rep=1):
    o, lse = _fwd_auto(q3, k3, v3, sm_scale, causal, interpret, kv_rep, window)
    return o, (q3, k3, v3, o, lse, window)


def _flash_bwd_rule(sm_scale, causal, interpret, kv_rep, res, do3):
    q3, k3, v3, o3, lse, window = res
    dq, dk, dv = _bwd_auto(q3, k3, v3, o3, lse, do3, sm_scale, causal, interpret, kv_rep, window)
    # integer-dtype primal → float0 cotangent (None when no window was passed)
    win_ct = None if window is None else np.zeros((1,), jax.dtypes.float0)
    return dq, dk, dv, win_ct


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def validate_kv_heads(H: int, k, v) -> int:
    """THE kv-head rule (one copy; decode + dispatch share it): K/V head
    counts must match and divide the q head count. Returns rep = H // KV."""
    KV = k.shape[-2]
    if v.shape[-2] != KV or H % KV != 0:
        raise ValueError(
            f"kv heads ({KV}/{v.shape[-2]}) must match and divide q heads ({H})"
        )
    return H // KV


def flash_ok(S: int, D: int) -> bool:
    """THE shape predicate for single-device flash dispatch: tiling-legal and
    within the grid kernel's ceiling. One copy, used by the ops dispatchers,
    so they can never disagree with flash_attention's own checks (the ring
    path adds its per-shard VMEM bound on top via ring_flash_ok)."""
    return S % BQ == 0 and S % BK == 0 and D % 64 == 0 and S <= GRID_KERNEL_MAX_SEQ


def windowed_flash_ok(S: int, D: int, itemsize: int = 2) -> bool:
    """Whether a sliding-window sequence can ride the kernels: windows are
    implemented in the resident variant only (the grid variant's static
    index maps cannot elide a traced window's dead blocks)."""
    return flash_ok(S, D) and resident_ok(S, D, itemsize)


def flash_attention(q, k, v, causal: bool = True, sm_scale: Optional[float] = None,
                    interpret: bool = False, window=None):
    """[B,S,H,D] flash attention (causal by default). S must be a multiple of
    128. Sequences within the whole-K/V VMEM budget use the resident kernels
    (fewer grid steps, chip-validated first); longer sequences stream K/V
    block-by-block through the grid variant, whose only length bound is HBM.

    Grouped-query attention: ``k``/``v`` may carry fewer heads than ``q``
    ([B,S,KV,D] with H % KV == 0). The kernels read each group's shared K/V
    block through a divided batch index map — the repeated cache is never
    materialized in HBM or VMEM, and dk/dv accumulate over the group.

    ``window`` (int or traced i32 scalar; None/0 = global): sliding-window
    causal attention — key j visible to query i iff i-window < j <= i
    (Mistral sliding_window / GPT-Neo local-layer semantics). The loop
    bounds skip blocks wholly outside the band, so FLOPs scale with
    S*window, not S^2; requires ``causal`` and the resident kernels
    (gate with windowed_flash_ok)."""
    B, S, H, D = q.shape
    rep = validate_kv_heads(H, k, v)
    if S % BQ != 0 or S % BK != 0:
        raise ValueError(f"seq {S} must be a multiple of {BQ}/{BK}")
    if S > GRID_KERNEL_MAX_SEQ:
        raise ValueError(
            f"seq {S} exceeds the grid kernel's bookkeeping ceiling "
            f"({GRID_KERNEL_MAX_SEQ}): the [B*H, S, 128] f32 lse/delta "
            "residuals dominate HBM past it — shard the sequence (sp axis / "
            "ring attention) instead"
        )
    if window is not None:
        if not causal:
            raise ValueError("window requires causal attention")
        if not resident_ok(S, D, q.dtype.itemsize):
            raise ValueError(
                f"windowed attention needs the resident kernels "
                f"(S*D*itemsize <= {VMEM_RESIDENT_BYTES}); got S={S} D={D}"
            )
    scale = sm_scale if sm_scale is not None else 1.0 / (D**0.5)

    win = None if window is None else _win_arr(window)
    def to3(x):
        nh = x.shape[2]
        return x.transpose(0, 2, 1, 3).reshape(B * nh, S, D)

    # batch-major flattening makes bh = (b*KV + g)*rep + r for q and
    # b*KV + g for k/v, so bh // rep recovers the kv row exactly
    q3, k3, v3 = to3(q), to3(k), to3(v)
    # the kernels keep the names XLA gives them (a trace's readers know them by those): the
    # custom_vjp call carries its scope into the forward and the backward kernels alike
    with parts.unscoped():
        o3 = _flash(q3, k3, v3, win, float(scale), bool(causal), bool(interpret), rep)
    return o3.reshape(B, H, S, D).transpose(0, 2, 1, 3)
