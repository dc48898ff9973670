"""Fused AdamW as a Pallas TPU kernel over flat parameter shards.

Capability analog of reference ``csrc/adam/multi_tensor_adam.cu:163`` +
``ops/adam/fused_adam.py:15`` (multi-tensor-apply fused CUDA Adam). Under XLA
the optax update already fuses into the train step, so this kernel exists to
answer SURVEY §2.7's own question — "Pallas fused optimizer kernel over flat
param shards (or jax.jit fused update — **measure**)" — with a measurement.
Measured on the chip at PR 45 (``PERF.md`` section 6, PR 45): on one leaf of
the one-chip training cell (``f32[16,1600,6400]`` masters and moments, a bf16
gradient, one v5e) XLA's fused optax update takes 6.47 ms, 659 GB/s over the
26 bytes a parameter it moves (7.00 ms with the bf16 compute copy as a fourth
result), and :func:`fused_adamw_flat` 25.1 ms, 170 GB/s, at 8, 64 and 128 rows
a grid step alike: what it loses is not in the kernel's body but around it
(each operand is ravelled to ``[rows, 1024]`` and back, which on a TPU is a
re-laying of the whole array, and nothing is updated in place). In the compiled
step XLA's fusions move 17.8 GB a step where one read and one write of every
state leaf is 17.2 GB, so the kernel is not wired in: optax stays the
optimizer, and this file the answer to the survey's question.

Design: the update is purely elementwise and HBM-bandwidth-bound (reads
p,g,m,v + writes p,m,v = 28 B/param fp32). The kernel streams 2D tiles
through VMEM; hyperparameters arrive as a small traced vector so lr changes
never recompile. Bias correction follows optax/AdamW (mhat = m/(1-b1^t)).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 1024  # last-dim tile (multiple of the 128-lane VPU width)
ROWS = 8  # sublane tile rows per grid step


def _adam_kernel(scal_ref, p_ref, g_ref, m_ref, v_ref, op_ref, om_ref, ov_ref):
    lr = scal_ref[0]
    b1 = scal_ref[1]
    b2 = scal_ref[2]
    eps = scal_ref[3]
    wd = scal_ref[4]
    bc1 = scal_ref[5]  # 1 - b1**t
    bc2 = scal_ref[6]  # 1 - b2**t
    g = g_ref[...].astype(jnp.float32)
    m = b1 * m_ref[...] + (1.0 - b1) * g
    v = b2 * v_ref[...] + (1.0 - b2) * g * g
    update = (m / bc1) / (jnp.sqrt(v / bc2) + eps)
    p = p_ref[...]
    op_ref[...] = p - lr * (update + wd * p)
    om_ref[...] = m
    ov_ref[...] = v


def fused_adamw_flat(
    p: jnp.ndarray,
    g: jnp.ndarray,
    m: jnp.ndarray,
    v: jnp.ndarray,
    step: jnp.ndarray,
    lr,
    betas: Tuple[float, float] = (0.9, 0.999),
    eps: float = 1e-8,
    weight_decay: float = 0.0,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One AdamW step on a flat fp32 shard. Returns (p', m', v').

    ``step`` is the 1-based step count (traced i32/f32); ``lr`` may be traced.
    Grads may be bf16 (upcast in-kernel, the multi-tensor-apply behavior).
    """
    assert p.ndim == 1, "flat shards only (ravel the leaf)"
    n = p.shape[0]
    b1, b2 = float(betas[0]), float(betas[1])
    t = step.astype(jnp.float32)
    scal = jnp.stack(
        [
            jnp.asarray(lr, jnp.float32),
            jnp.float32(b1),
            jnp.float32(b2),
            jnp.float32(eps),
            jnp.float32(weight_decay),
            1.0 - jnp.float32(b1) ** t,
            1.0 - jnp.float32(b2) ** t,
        ]
    )

    tile = ROWS * LANES
    n_pad = (-n) % tile
    if n_pad:
        pad = lambda x: jnp.pad(x, (0, n_pad))
        p, g, m, v = pad(p), pad(g), pad(m), pad(v)
    rows = (n + n_pad) // LANES
    shape2d = (rows, LANES)
    p2, g2, m2, v2 = (x.reshape(shape2d) for x in (p, g, m, v))

    grid = (rows // ROWS,)
    block = pl.BlockSpec((ROWS, LANES), lambda i: (i, 0))
    scal_spec = pl.BlockSpec((7,), lambda i: (0,))
    out_shape = [jax.ShapeDtypeStruct(shape2d, jnp.float32)] * 3
    op, om, ov = pl.pallas_call(
        _adam_kernel,
        grid=grid,
        in_specs=[scal_spec, block, block, block, block],
        out_specs=[block, block, block],
        out_shape=out_shape,
        interpret=interpret,
    )(scal, p2, g2, m2, v2)
    unpad = lambda x: x.reshape(-1)[:n]
    return unpad(op), unpad(om), unpad(ov)


def fused_adamw_tree(params, grads, mu, nu, step, lr, betas=(0.9, 0.999), eps=1e-8, weight_decay=0.0, interpret=False):
    """Multi-tensor apply over a pytree: each leaf raveled through the kernel
    (the reference chunks many tensors into one launch; here each leaf is one
    pallas_call and XLA schedules them back-to-back)."""
    flat_p, tree = jax.tree.flatten(params)
    flat_g = jax.tree.leaves(grads)
    flat_m = jax.tree.leaves(mu)
    flat_v = jax.tree.leaves(nu)
    new_p, new_m, new_v = [], [], []
    for p, g, m, v in zip(flat_p, flat_g, flat_m, flat_v):
        sh = p.shape
        op, om, ov = fused_adamw_flat(
            p.reshape(-1), g.reshape(-1), m.reshape(-1), v.reshape(-1),
            step, lr, betas, eps, weight_decay, interpret=interpret,
        )
        new_p.append(op.reshape(sh))
        new_m.append(om.reshape(sh))
        new_v.append(ov.reshape(sh))
    unflat = functools.partial(jax.tree.unflatten, tree)
    return unflat(new_p), unflat(new_m), unflat(new_v)


def _lamb_stage1_kernel(scal_ref, p_ref, g_ref, m_ref, v_ref, u_ref, om_ref, ov_ref):
    b1 = scal_ref[0]
    b2 = scal_ref[1]
    eps = scal_ref[2]
    wd = scal_ref[3]
    bc1 = scal_ref[4]
    bc2 = scal_ref[5]
    g = g_ref[...].astype(jnp.float32)
    m = b1 * m_ref[...] + (1.0 - b1) * g
    v = b2 * v_ref[...] + (1.0 - b2) * g * g
    u_ref[...] = (m / bc1) / (jnp.sqrt(v / bc2) + eps) + wd * p_ref[...]
    om_ref[...] = m
    ov_ref[...] = v


def fused_lamb_flat(
    p: jnp.ndarray,
    g: jnp.ndarray,
    m: jnp.ndarray,
    v: jnp.ndarray,
    step: jnp.ndarray,
    lr,
    betas: Tuple[float, float] = (0.9, 0.999),
    eps: float = 1e-6,
    weight_decay: float = 0.0,
    min_trust: float = 0.01,
    max_trust: float = 10.0,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One LAMB step on a flat fp32 shard (reference
    ``csrc/lamb/fused_lamb_cuda_kernel.cu``: elementwise stage computing the
    Adam-style update direction runs in the kernel; the trust-ratio norms are
    tree-level reductions XLA already fuses, then the final scaled apply is a
    trivial fused axpy). Returns (p', m', v')."""
    assert p.ndim == 1
    n = p.shape[0]
    b1, b2 = float(betas[0]), float(betas[1])
    t = step.astype(jnp.float32)
    scal = jnp.stack(
        [
            jnp.float32(b1),
            jnp.float32(b2),
            jnp.float32(eps),
            jnp.float32(weight_decay),
            1.0 - jnp.float32(b1) ** t,
            1.0 - jnp.float32(b2) ** t,
        ]
    )
    tile = ROWS * LANES
    n_pad = (-n) % tile
    pg, gg, mg, vg = (jnp.pad(x, (0, n_pad)) if n_pad else x for x in (p, g, m, v))
    rows = (n + n_pad) // LANES
    shape2d = (rows, LANES)
    p2, g2, m2, v2 = (x.reshape(shape2d) for x in (pg, gg, mg, vg))
    grid = (rows // ROWS,)
    block = pl.BlockSpec((ROWS, LANES), lambda i: (i, 0))
    scal_spec = pl.BlockSpec((6,), lambda i: (0,))
    u2, om, ov = pl.pallas_call(
        _lamb_stage1_kernel,
        grid=grid,
        in_specs=[scal_spec, block, block, block, block],
        out_specs=[block, block, block],
        out_shape=[jax.ShapeDtypeStruct(shape2d, jnp.float32)] * 3,
        interpret=interpret,
    )(scal, p2, g2, m2, v2)
    unpad = lambda x: x.reshape(-1)[:n]
    u = unpad(u2)
    # trust ratio (XLA reductions; reference computes these with a two-pass
    # block reduction in the CUDA kernel)
    p_norm = jnp.linalg.norm(p)
    u_norm = jnp.linalg.norm(u)
    trust = jnp.where(
        (p_norm > 0.0) & (u_norm > 0.0),
        jnp.clip(p_norm / u_norm, min_trust, max_trust),
        1.0,
    )
    new_p = p - jnp.asarray(lr, jnp.float32) * trust * u
    return new_p, unpad(om), unpad(ov)
