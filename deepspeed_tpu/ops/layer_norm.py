"""Layer norm with fp32 statistics — the one shared implementation.

The reference's fused LN kernels accumulate mean/variance in fp32 regardless
of the activation dtype (``csrc/transformer/normalize_kernels.cu``); doing the
statistics in fp16 overflows the variance/rsqrt chain. Every model family
(gpt2/decoder/bert) routes through this helper so the numerics cannot drift
apart between copies.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax


def layer_norm(x, scale, bias, eps):
    xf = x.astype(jnp.float32)
    m = jnp.mean(xf, axis=-1, keepdims=True)
    v = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - m) * lax.rsqrt(v + eps)
    return (y * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(x.dtype)


def layer_norm_inference(x, scale, bias, eps):
    """:func:`layer_norm`'s arithmetic with the mean taken once, for programs
    that are never differentiated (the serving programs). ``jnp.var`` takes
    its own mean under a jit of its own, which XLA's CSE does not see through:
    a norm of ``layer_norm`` is five small device operations behind the
    matmul that feeds it, this one three, and a decode step is little but
    small operations (17 a layer with it, 21 without). Same bits on the CPU
    (``tests/unit/ops/test_layer_norm.py``); training keeps ``layer_norm``,
    whose backward pass XLA fuses differently."""
    xf = x.astype(jnp.float32)
    c = xf - jnp.mean(xf, axis=-1, keepdims=True)
    v = jnp.mean(c * c, axis=-1, keepdims=True)
    y = c * lax.rsqrt(v + eps)
    return (y * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(x.dtype)


def rms_norm(x, scale, eps):
    """RMSNorm (no mean subtraction, no bias) with fp32 statistics — the
    LLaMA-family normalization."""
    xf = x.astype(jnp.float32)
    y = xf * lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)
