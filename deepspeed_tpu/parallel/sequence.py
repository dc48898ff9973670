"""Sequence/context parallelism: ring attention + Ulysses over the ``sp`` axis.

The reference snapshot has NO sequence parallelism (SURVEY.md §5 long-context:
no ring/Ulysses hits in ``deepspeed/``); its long-sequence story is sparse
attention + partitioned activation checkpointing. This module fills that gap
natively — on TPU a sequence axis is just another mesh axis and both schemes
map directly onto ICI collectives:

- **Ulysses** (all-to-all, DeepSpeed-Ulysses style): activations arrive
  sharded over sequence; one ``all_to_all`` re-shards heads over ``sp`` and
  gathers the full sequence per head-group, dense attention runs locally, a
  second ``all_to_all`` restores the sequence sharding. Communication volume
  is O(B·S·E/n) per call — rides ICI.
- **Ring attention** (blockwise, ppermute): K/V blocks rotate around the
  ``sp`` ring while each device keeps its Q shard; online-softmax (flash
  style) accumulation makes the result exact. Memory per device is O(S/n);
  communication is overlapped with the per-block attention matmuls by XLA
  (each ppermute is independent of the current block's compute).

Both are exact (match dense causal attention bit-for-bit up to f32 softmax
reassociation) and are verified against the dense path in
``tests/unit/test_sequence_parallel.py``.

Layout convention: [B, S, H, D], sequence sharded over ``sp``, batch over
``dp``, heads optionally over ``tp``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

_NEG_INF = -1e30


# ---------------------------------------------------------------------------
# ring attention (per-device function, runs under shard_map)
# ---------------------------------------------------------------------------

def _ring_attention_local(q, k, v, *, axis_name: str, sm_scale: Optional[float], causal: bool):
    """Exact blockwise attention with K/V rotating over the ``axis_name`` ring.

    q, k, v: [B, S_loc, H, D] — this device's sequence shard.
    Returns [B, S_loc, H, D].
    """
    n = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    B, S, H, D = q.shape
    scale = sm_scale if sm_scale is not None else 1.0 / (D**0.5)

    q_pos = idx * S + jnp.arange(S)  # global positions of local queries

    # online-softmax accumulators (f32)
    o0 = jnp.zeros((B, H, S, D), jnp.float32)
    m0 = jnp.full((B, H, S), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, H, S), jnp.float32)

    # each step processes the K/V block originating from device (idx + step) % n;
    # blocks move "backwards" around the ring so device idx sees src idx, idx+1, …
    perm = [(j, (j - 1) % n) for j in range(n)]

    # remat: without it, backward through the scan stores every ring step's
    # [B,H,S_loc,S_loc] probability block (O(n·S_loc²) residuals — the full
    # attention matrix, defeating the point of ring attention). Recomputing
    # one block pair per step bounds residuals to the carries.
    @jax.checkpoint
    def step(carry, step_i):
        o, m, l, k_blk, v_blk = carry
        src = (idx + step_i) % n

        # NOTE: for causal attention, blocks with src > idx are fully masked,
        # but skipping them cannot shorten the step — the ppermute chains each
        # step to the busiest device (device n-1 always attends). Balancing
        # needs a zigzag Q layout, not a per-step branch; until then the mask
        # handles it.
        k_pos = src * S + jnp.arange(S)
        logits = jnp.einsum(
            "bqhd,bkhd->bhqk", q, k_blk, preferred_element_type=jnp.float32
        ) * scale
        if causal:
            mask = q_pos[:, None] >= k_pos[None, :]
            logits = jnp.where(mask[None, None], logits, _NEG_INF)
        blk_max = jnp.max(logits, axis=-1)
        m_new = jnp.maximum(m, blk_max)
        # guard: fully-masked rows keep m == -inf; exp(-inf - -inf) would be NaN
        safe_m = jnp.where(m_new <= _NEG_INF, 0.0, m_new)
        p = jnp.exp(logits - safe_m[..., None])
        p = jnp.where(logits <= _NEG_INF, 0.0, p)
        alpha = jnp.where(m <= _NEG_INF, 0.0, jnp.exp(m - safe_m))
        l = l * alpha + jnp.sum(p, axis=-1)
        pv = jnp.einsum(
            "bhqk,bkhd->bhqd", p.astype(v_blk.dtype), v_blk,
            preferred_element_type=jnp.float32,
        )
        o = o * alpha[..., None] + pv
        m = m_new
        # rotate K/V to the next device; independent of this block's compute,
        # so XLA overlaps the ppermute with the matmuls above
        k_blk = lax.ppermute(k_blk, axis_name, perm)
        v_blk = lax.ppermute(v_blk, axis_name, perm)
        return (o, m, l, k_blk, v_blk), None

    (o, m, l, _, _), _ = lax.scan(step, (o0, m0, l0, k, v), jnp.arange(n))
    o = o / jnp.maximum(l, 1e-30)[..., None]
    return jnp.transpose(o, (0, 2, 1, 3)).astype(q.dtype)  # [B,S,H,D]


# ---------------------------------------------------------------------------
# Ulysses attention (per-device function, runs under shard_map)
# ---------------------------------------------------------------------------

def _ulysses_local(q, k, v, *, axis_name: str, sm_scale: Optional[float], causal: bool):
    """All-to-all seq↔head re-sharding around a dense local attention.

    q, k, v: [B, S_loc, H_loc, D]. Requires H_loc % sp == 0.
    """
    n = lax.psum(1, axis_name)
    B, S, H, D = q.shape
    assert H % n == 0, f"Ulysses needs heads per device ({H}) divisible by sp ({n})"

    def seq_to_heads(x):
        # [B, S_loc, H, D] → [B, S_full, H/n, D]
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1, tiled=True)

    def heads_to_seq(x):
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2, tiled=True)

    q, k, v = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
    Sf = S * n
    if causal:
        # Pallas flash path on TPU: O(S_full) memory per device. The jnp
        # fallback (non-TPU, or shapes the kernel rejects) still materializes
        # the [B, H/n, S_full, S_full] logits — at that point prefer ring.
        from ..ops.attention import causal_attention

        o = causal_attention(q, k, v, sm_scale=sm_scale)
    else:
        from ..ops.attention import _pallas_ok

        if _pallas_ok(q):
            from ..ops.pallas.flash_attention import flash_attention

            o = flash_attention(q, k, v, causal=False, sm_scale=sm_scale)
        else:
            scale = sm_scale if sm_scale is not None else 1.0 / (D**0.5)
            logits = jnp.einsum(
                "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
            ) * scale
            probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
            o = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
    return heads_to_seq(o)


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------

def sequence_parallel_attention(
    q,
    k,
    v,
    mesh: Mesh,
    impl: str = "ring",  # "ring" | "ring_flash" | "ulysses"
    causal: bool = True,
    sm_scale: Optional[float] = None,
    dp_axis: str = "dp",
    sp_axis: str = "sp",
    tp_axis: str = "tp",
    interpret: bool = False,
):
    """Sequence-parallel exact attention over a named mesh.

    Inputs [B, S, H, D] logically; S sharded over ``sp_axis``, B over
    ``dp_axis``, H over ``tp_axis`` (any axis absent from the mesh degrades to
    replicated). Output has the same sharding as q.

    ``impl="ring"`` auto-upgrades each ring step's blockwise compute to the
    Pallas flash kernels on TPU when the shard shapes allow
    (ops/pallas/ring_flash_attention.py); ``"ring_flash"`` forces that path
    (with ``interpret=True`` it runs on CPU for tests).
    """
    if impl not in ("ring", "ring_flash", "ulysses"):
        raise ValueError(f"unknown sequence-parallel impl {impl}")
    if mesh.shape.get("pp", 1) > 1 and mesh.shape.get(sp_axis, 1) > 1:
        raise NotImplementedError(
            "sequence-parallel attention (ring/ulysses) cannot run inside a "
            "pipeline-parallel stage: the sp shard_map would nest inside the "
            "pp shard_map. Use pp with attn_impl='flash'/'jnp', or drop pp."
        )
    axes = mesh.axis_names
    dp = dp_axis if dp_axis in axes else None
    sp = sp_axis if sp_axis in axes else None
    tp = tp_axis if tp_axis in axes else None
    if sp is None or mesh.shape.get(sp, 1) == 1:
        # no sequence axis — fall back to plain dense attention
        from ..ops.attention import causal_attention_jnp

        assert causal, "non-causal fallback not wired"
        return causal_attention_jnp(q, k, v, sm_scale)

    sp_size = mesh.shape[sp]
    tp_size = mesh.shape.get(tp, 1) if tp else 1
    heads_local = q.shape[2] // tp_size
    if impl == "ulysses" and heads_local % sp_size != 0:
        from ..utils.logging import warning_once

        warning_once(
            f"Ulysses needs local heads ({heads_local}) divisible by sp ({sp_size}); "
            "falling back to ring attention"
        )
        impl = "ring"
    if impl == "ring":
        # auto-upgrade the ring's inner blockwise compute to the flash
        # kernels when each device's shard is tile-aligned and within the
        # grid kernel's ceiling (past the whole-K/V VMEM budget the inner
        # compute streams K/V through the KV-blocked grid variant)
        from ..ops.pallas.ring_flash_attention import ring_flash_ok

        s_loc = q.shape[1] // sp_size
        if jax.default_backend() == "tpu" and ring_flash_ok(
            s_loc, q.shape[3], q.dtype.itemsize
        ):
            impl = "ring_flash"
    spec = P(dp, sp, tp, None)
    if impl == "ring_flash":
        from ..ops.pallas.ring_flash_attention import ring_flash_attention

        fn = functools.partial(
            ring_flash_attention, axis_name=sp, sm_scale=sm_scale,
            causal=causal, interpret=interpret,
        )
    else:
        local = _ring_attention_local if impl == "ring" else _ulysses_local
        fn = functools.partial(local, axis_name=sp, sm_scale=sm_scale, causal=causal)
    return shard_map(
        fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec, check_vma=False
    )(q, k, v)


def shard_sequence(batch, mesh: Mesh, seq_dim: int = 1, dp_axis: str = "dp", sp_axis: str = "sp"):
    """Device-put a host batch with the sequence dim over ``sp`` (and batch
    over ``dp``) — the input-side hook for long-context training."""
    from jax.sharding import NamedSharding

    def put(x):
        spec = [None] * x.ndim
        if dp_axis in mesh.axis_names:
            spec[0] = dp_axis
        if x.ndim > seq_dim and sp_axis in mesh.axis_names:
            spec[seq_dim] = sp_axis
        return jax.device_put(x, NamedSharding(mesh, P(*spec)))

    return jax.tree.map(put, batch)
