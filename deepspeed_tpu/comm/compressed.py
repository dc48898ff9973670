"""Compressed gradient collectives: block-scaled low-precision reduce.

The reference DeepSpeed spends most of its scaling budget on gradient
communication (ZeRO's reduce-scatter / allreduce over NCCL). EQuARX
(arXiv:2506.17615) shows a quantized allreduce inside XLA recovers 1.4-2x
collective throughput with negligible quality loss; this module is that idea
as a first-class layer over ``jax.lax`` collectives, block-scaled int8 / fp8
(e4m3):

    quantize per block -> all_to_all low-precision -> dequantize+reduce
    -> requantize -> all_gather low-precision -> dequantize

Two-stage, like the reference's NcclBackend.compressed_allreduce (nccl.py:51):
rank r "serves" chunk r — it receives every rank's r-th chunk, reduces in
fp32, recompresses, and broadcasts the result. Wire volume per collective is
``n * 1 + (n/block) * 4`` bytes instead of ``4n`` (≈3.9x less at block 256).

Error feedback: quantization error is *returned to the caller* so it can be
carried into the next step (per-leaf residuals in ``TrainState.comm_error``)
— compensated compression preserves convergence where plain rounding biases
it (the error-feedback algebra of 1-bit Adam under a milder quantizer).

Bucketing: :func:`build_bucket_plan` packs gradient leaves into size-capped
flat buckets (``zero_optimization.reduce_bucket_size``), each reduced by an
INDEPENDENT collective — giving XLA's latency-hiding scheduler separate ops
to overlap with backward compute (T3, arXiv:2401.16677) instead of one fused
tree-allreduce that walls the step.

Accounting: every compressed collective records (logical fp32 bytes, actual
wire bytes) at trace time — into the module registry (:func:`records_by_axis`,
always on; the telemetry plane's source of truth) and into the shared
``CommsLogger`` when enabled (wire/ratio columns in ``log_summary``).
"""

from __future__ import annotations

from contextlib import contextmanager
from dataclasses import dataclass
from typing import Any, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

PyTree = Any

METHODS = ("int8", "fp8")

# quantization range per method: int8 symmetric [-127, 127]; fp8 e4m3 has
# max finite 448 (we scale amax onto it, mantissa rounding does the rest)
_INT8_QMAX = 127.0
_FP8_QMAX = 448.0


# ---------------------------------------------------------------------------
# block-scaled quantizers
# ---------------------------------------------------------------------------

def _quantize_exact(xb: jnp.ndarray, method: str) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Core block codec: ``[..., block]`` fp32 -> (payload, scale ``[..., 1]``).
    Scale = amax/qmax per block (zero blocks get scale 1 so the payload is
    exactly zero). The ONE place the scale/round/clip rule lives — the grad
    collectives, the weight quantizer (``ops/quantizer``) and the KV page
    codec all route here."""
    amax = jnp.max(jnp.abs(xb), axis=-1, keepdims=True)
    qmax = _INT8_QMAX if method == "int8" else _FP8_QMAX
    scale = jnp.where(amax > 0, amax / qmax, 1.0)
    y = xb / scale
    if method == "int8":
        q = jnp.clip(jnp.round(y), -_INT8_QMAX, _INT8_QMAX).astype(jnp.int8)
    else:
        q = y.astype(jnp.float8_e4m3fn)
    return q, scale


def quantize_blocks(x: jnp.ndarray, method: str = "int8", block: int = 256) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """fp32 ``[..., n]`` -> (payload ``[..., n]`` int8/fp8, scales
    ``[..., ceil(n/block)]`` fp32).

    When ``n % block == 0`` — every hot caller: the grad buckets are padded
    to the collective multiple, and KV pages are exact multiples by
    construction (``block = page * head_dim``) — this is a pure reshape, no
    copy. A trailing remainder is quantized as one short block with its own
    scale (head reshaped + tail sliced in place — never a padded copy of
    the whole array)."""
    if method not in METHODS:
        raise ValueError(f"unknown compression method {method!r}; use one of {METHODS}")
    n = x.shape[-1]
    rem = n % block
    if rem == 0:
        xb = x.reshape(x.shape[:-1] + (n // block, block)).astype(jnp.float32)
        q, scale = _quantize_exact(xb, method)
        return q.reshape(x.shape), scale.reshape(x.shape[:-1] + (n // block,))
    head = n - rem
    hb = x[..., :head].reshape(x.shape[:-1] + (head // block, block)).astype(jnp.float32)
    q_h, s_h = _quantize_exact(hb, method)
    q_t, s_t = _quantize_exact(x[..., head:].astype(jnp.float32), method)
    q = jnp.concatenate([q_h.reshape(x.shape[:-1] + (head,)), q_t], axis=-1)
    s = jnp.concatenate([s_h.reshape(x.shape[:-1] + (head // block,)),
                         s_t.reshape(x.shape[:-1] + (1,))], axis=-1)
    return q, s


def dequantize_blocks(payload: jnp.ndarray, scales: jnp.ndarray, block: int = 256) -> jnp.ndarray:
    """Inverse of :func:`quantize_blocks`: low-precision payload -> fp32.
    Mirrors its remainder handling (the tail is one short block)."""
    n = payload.shape[-1]
    rem = n % block
    if rem == 0:
        pb = payload.reshape(payload.shape[:-1] + (n // block, block)).astype(jnp.float32)
        out = pb * scales[..., None]
        return out.reshape(payload.shape)
    head = n - rem
    hb = payload[..., :head].reshape(
        payload.shape[:-1] + (head // block, block)
    ).astype(jnp.float32)
    out_h = (hb * scales[..., : head // block, None]).reshape(
        payload.shape[:-1] + (head,)
    )
    out_t = payload[..., head:].astype(jnp.float32) * scales[..., -1:]
    return jnp.concatenate([out_h, out_t], axis=-1)


def wire_bytes(n: int, method: str = "int8", block: int = 256) -> int:
    """Actual bytes on the wire for ``n`` compressed elements: 1-byte payload
    plus one fp32 scale per (possibly short trailing) block."""
    return n + (-(-n // block)) * 4


# ---------------------------------------------------------------------------
# trace-time compression accounting
# ---------------------------------------------------------------------------

# {(op, axis): {count, logical_bytes, wire_bytes}} — recorded at trace time
# (shapes are static under jit, so this is the exact per-compiled-step mix)
_records: Dict[Tuple[str, str], Dict[str, float]] = {}
_suspended = False


def _record_compressed(op: str, axis, logical: int, wire: int) -> None:
    if _suspended:
        return
    rec = _records.setdefault(
        (op, str(axis)), {"count": 0, "logical_bytes": 0, "wire_bytes": 0}
    )
    rec["count"] += 1
    rec["logical_bytes"] += logical
    rec["wire_bytes"] += wire
    # fold into the shared comms logger (wire/ratio columns) when enabled
    from .comm import comms_logger

    comms_logger.append(op, axis, logical, wire_bytes=wire)


@contextmanager
def suspend_records():
    """Silence trace-time recording while DELIBERATELY re-tracing an
    already-accounted program (the engine's comms accounting ``.lower()``) —
    otherwise every re-trace duplicates the compressed ops' rows in the
    shared CommsLogger and this registry."""
    global _suspended
    prev, _suspended = _suspended, True
    try:
        yield
    finally:
        _suspended = prev


def reset_records() -> None:
    _records.clear()


def records() -> Dict[Tuple[str, str], Dict[str, float]]:
    return {k: dict(v) for k, v in _records.items()}


def records_by_axis() -> Dict[str, Dict[str, float]]:
    """Per-axis {logical_bytes, wire_bytes, ratio} aggregate of everything
    recorded so far. NOTE: like the CommsLogger wrappers, records accrue on
    every trace — deliberately re-lowering the same program
    (``Compiled``-based accounting) inflates the absolute
    byte totals, though the ratio survives. The engine's per-step numbers
    (``_compression_stats``) are therefore derived analytically from the
    bucket plan instead of from this registry."""
    out: Dict[str, Dict[str, float]] = {}
    for (_, axis), rec in _records.items():
        agg = out.setdefault(axis, {"logical_bytes": 0, "wire_bytes": 0})
        agg["logical_bytes"] += rec["logical_bytes"]
        agg["wire_bytes"] += rec["wire_bytes"]
    for agg in out.values():
        agg["ratio"] = (
            agg["logical_bytes"] / agg["wire_bytes"] if agg["wire_bytes"] else 1.0
        )
    return out


# ---------------------------------------------------------------------------
# compressed collectives (call inside shard_map with the axis in scope)
# ---------------------------------------------------------------------------

def compressed_all_reduce(
    x: jnp.ndarray,
    axis_name: str,
    world: int,
    method: str = "int8",
    block: int = 256,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Mean of ``x`` across ``axis_name`` with both transfer stages in low
    precision; returns ``(mean, residual)``.

    ``x``: ``[n]`` flat fp32, ``n % (world * block) == 0`` (caller pads —
    see :func:`build_bucket_plan`). ``residual`` is the local quantization
    error in units of ``x``: feed it back by adding it to next step's input
    (error-feedback / compensated compression). It is rank-divergent — carry
    it per-rank (e.g. a ``[world, ...]`` buffer sharded over the axis).
    """
    n = x.shape[0]
    assert n % world == 0 and (n // world) % block == 0, (n, world, block)
    chunk = n // world

    # -- stage A (reduce-scatter shape): quantize, route chunks to servers --
    q, s = quantize_blocks(x, method, block)
    local_deq = dequantize_blocks(q, s, block)
    worker_err = x - local_deq

    _record_compressed("all_to_all", axis_name, 4 * n, wire_bytes(n, method, block))
    q_r = lax.all_to_all(q.reshape(world, chunk), axis_name, split_axis=0, concat_axis=0, tiled=False)
    s_r = lax.all_to_all(
        s.reshape(world, chunk // block), axis_name, split_axis=0, concat_axis=0, tiled=False
    )

    # -- server side: dequantize every rank's contribution, reduce in fp32 --
    vals = dequantize_blocks(q_r, s_r, block)  # [world, chunk] fp32
    reduced = jnp.sum(vals, axis=0) / world  # [chunk] — the mean's r-th chunk

    # -- stage B (broadcast shape): recompress the served chunk, all-gather --
    q2, s2 = quantize_blocks(reduced, method, block)
    server_err = reduced - dequantize_blocks(q2, s2, block)
    _record_compressed("all_gather", axis_name, 4 * chunk, wire_bytes(chunk, method, block))
    all_q = lax.all_gather(q2, axis_name, axis=0, tiled=False)  # [world, chunk]
    all_s = lax.all_gather(s2, axis_name, axis=0, tiled=False)
    mean = dequantize_blocks(all_q, all_s, block).reshape(n)

    # residual: own worker error, plus the served chunk's stage-B error
    # scaled by world (next step's reduction divides by world, so carrying
    # world*e_B recovers e_B exactly once, on this rank)
    rank = lax.axis_index(axis_name)
    residual = worker_err + lax.dynamic_update_slice(
        jnp.zeros_like(x), world * server_err, (rank * chunk,)
    )
    return mean, residual


def compressed_reduce_scatter(
    x: jnp.ndarray,
    axis_name: str,
    world: int,
    method: str = "int8",
    block: int = 256,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Stage A only: mean-reduce ``x`` across ``axis_name`` and keep this
    rank's ``[n/world]`` chunk (the ZeRO ``grad_spec`` reduce-scatter in low
    precision). Returns ``(chunk_mean, residual)`` with ``residual`` the
    full-length worker error (stage-B error does not exist here — the chunk
    stays fp32 on its owner)."""
    n = x.shape[0]
    assert n % world == 0 and (n // world) % block == 0, (n, world, block)
    chunk = n // world

    q, s = quantize_blocks(x, method, block)
    residual = x - dequantize_blocks(q, s, block)

    _record_compressed("all_to_all", axis_name, 4 * n, wire_bytes(n, method, block))
    q_r = lax.all_to_all(q.reshape(world, chunk), axis_name, split_axis=0, concat_axis=0, tiled=False)
    s_r = lax.all_to_all(
        s.reshape(world, chunk // block), axis_name, split_axis=0, concat_axis=0, tiled=False
    )
    vals = dequantize_blocks(q_r, s_r, block)
    return jnp.sum(vals, axis=0) / world, residual


def compressed_all_gather(
    x: jnp.ndarray,
    axis_name: str,
    world: int,
    method: str = "int8",
    block: int = 256,
) -> jnp.ndarray:
    """Low-precision all-gather (ISSUE 12): replicate every rank's ``[n]``
    shard across ``axis_name`` with the payload on the wire as int8/fp8 +
    per-block scales — the ZeRO-3 param all-gather's wire format
    (``runtime/zero/partitioning.gather_full_compressed``). Returns the
    gathered ``[world * n]`` fp32 array.

    Unlike the reduce collectives there is NO error-feedback residual: a
    gather is pure data movement, not an accumulating reduction — the
    quantization error is a one-shot, per-element bounded rounding (the
    round-trip tests pin it), and every rank dequantizes the SAME codes, so
    the gathered copy is bit-identical across ranks (the property a
    replicated param tree must keep).

    Ledger convention (module-wide, PR-2): logical bytes are
    fp32-NORMALIZED (4 per element) regardless of the source dtype —
    against a bf16 baseline the true reduction is ~half the recorded
    ratio."""
    n = x.shape[0]
    q, s = quantize_blocks(x.astype(jnp.float32), method, block)
    _record_compressed("all_gather", axis_name, 4 * n, wire_bytes(n, method, block))
    all_q = lax.all_gather(q, axis_name, axis=0, tiled=False)  # [world, n]
    all_s = lax.all_gather(s, axis_name, axis=0, tiled=False)
    return dequantize_blocks(all_q, all_s, block).reshape(world * n)


def compressed_all_to_all(
    x: jnp.ndarray,
    axis_name: str,
    world: int,
    method: str = "int8",
    block: int = 256,
) -> jnp.ndarray:
    """Low-precision all-to-all (ISSUE 12): rank r's chunk ``x[r]`` travels
    to rank r as int8/fp8 + per-chunk block scales — the MoE expert
    all-to-all's wire format (``moe/sharded_moe.moe_mlp_ep``). ``x`` is
    ``[world, chunk]``; returns the exchanged ``[world, chunk]`` fp32.

    Like the gather, this is pure data movement: no reduction, no error
    feedback — the parity tests bound the one-shot rounding against the
    uncompressed exchange. ``chunk`` need not divide ``block`` (the codec's
    trailing-remainder path covers ragged expert capacities). Logical
    bytes in the ledger are fp32-normalized, as everywhere in this
    module."""
    w, chunk = x.shape
    assert w == world, (w, world)
    q, s = quantize_blocks(x.astype(jnp.float32), method, block)
    _record_compressed(
        "all_to_all", axis_name, 4 * world * chunk,
        world * wire_bytes(chunk, method, block),
    )
    q_r = lax.all_to_all(q, axis_name, split_axis=0, concat_axis=0, tiled=False)
    s_r = lax.all_to_all(s, axis_name, split_axis=0, concat_axis=0, tiled=False)
    return dequantize_blocks(q_r, s_r, block)


# ---------------------------------------------------------------------------
# bucket plan: leaves -> size-capped flat buckets (independent collectives)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class BucketPlan:
    """Static grouping of flat leaf sizes into size-capped buckets.

    ``entries[b]`` is a list of ``(leaf_index, offset, size)`` rows: leaf
    ``leaf_index`` occupies ``bucket[b][offset:offset+size]``. ``padded[b]``
    is the bucket length after rounding up to ``multiple`` (zero-padded —
    exact under sum reductions). Leaves are never split across buckets; a
    leaf larger than the cap gets a bucket of its own (the reference splits
    flat buffers instead; leaf-aligned buckets keep the unflatten free)."""

    entries: Tuple[Tuple[Tuple[int, int, int], ...], ...]
    padded: Tuple[int, ...]
    multiple: int
    cap_elems: int

    @property
    def num_buckets(self) -> int:
        return len(self.entries)


def build_bucket_plan(
    sizes: Sequence[int],
    bucket_bytes: int,
    itemsize: int = 4,
    multiple: int = 1,
) -> BucketPlan:
    """Greedily pack leaf sizes (in flatten order) into buckets of at most
    ``bucket_bytes`` (``zero_optimization.reduce_bucket_size`` semantics),
    each padded up to ``multiple`` elements (axis divisibility for the
    collective: ``world * block`` for compressed reduces, the dp size for
    flat-sharded constraints)."""
    cap_elems = max(1, int(bucket_bytes) // max(1, itemsize))
    buckets: List[List[Tuple[int, int, int]]] = []
    cur: List[Tuple[int, int, int]] = []
    cur_n = 0
    for i, size in enumerate(sizes):
        size = int(size)
        if cur and cur_n + size > cap_elems:
            buckets.append(cur)
            cur, cur_n = [], 0
        cur.append((i, cur_n, size))
        cur_n += size
    if cur:
        buckets.append(cur)
    padded = tuple(
        int(-(-sum(e[2] for e in b) // multiple) * multiple) for b in buckets
    )
    return BucketPlan(
        entries=tuple(tuple(b) for b in buckets),
        padded=padded,
        multiple=int(multiple),
        cap_elems=cap_elems,
    )


def flatten_to_buckets(leaves: Sequence[jnp.ndarray], plan: BucketPlan, dtype=None) -> List[jnp.ndarray]:
    """Leaves (flatten order) -> list of flat zero-padded bucket arrays."""
    out = []
    for rows, pad_n in zip(plan.entries, plan.padded):
        parts = [leaves[i].reshape(-1) for i, _, _ in rows]
        flat = jnp.concatenate(parts) if len(parts) > 1 else parts[0]
        if dtype is not None:
            flat = flat.astype(dtype)
        if flat.shape[0] < pad_n:
            flat = jnp.pad(flat, (0, pad_n - flat.shape[0]))
        out.append(flat)
    return out


def unflatten_from_buckets(
    buckets: Sequence[jnp.ndarray], plan: BucketPlan, shapes: Sequence[Tuple[int, ...]]
) -> List[jnp.ndarray]:
    """Inverse of :func:`flatten_to_buckets` (padding dropped)."""
    leaves: List[Any] = [None] * len(shapes)
    for flat, rows in zip(buckets, plan.entries):
        for i, off, size in rows:
            leaves[i] = flat[off:off + size].reshape(shapes[i])
    assert all(l is not None for l in leaves), "plan does not cover all leaves"
    return leaves


def leaf_sizes(tree: PyTree) -> List[int]:
    """Flat element counts of a pytree's leaves, in flatten order."""
    return [int(np.prod(l.shape)) if getattr(l, "shape", ()) else 1 for l in jax.tree.leaves(tree)]
