"""The mixing of a MULTI-STREAM residual (mHC: manifold-constrained
hyper-connections, arXiv 2512.24880) around one sub-block ``F``. A token's
residual is ``X`` in ``R^{n x E}`` (``n`` streams, here the row ``[X_0 | ... |
X_{n-1}]``, ``n E`` lanes); the sub-block has ``phi [2n + n^2, n E]`` (the
published ``[n E, 2n + n^2]``, held transposed: lane-dense), ``b [2n + n^2]``
and the three gains ``a = (a_pre, a_post, a_res)``. In float32:

    xh          = vec(X) / sqrt(mean(vec(X)^2) + eps)         over all n E values
    [p | q | r] = xh phi^T                                    n, n, n^2 columns
    H_pre  = sigmoid(a_pre p + b_pre)                         [n]
    H_post = 2 sigmoid(a_post q + b_post)                     [n]
    M      = exp(clip(a_res mat(r) + b_res, lo, hi))          [n, n], row-major
    iters times:  M <- M / colsum(M);  M <- M / rowsum(M)     H_res = M
    u      = sum_i H_pre[i] X[i]                              what F reads
    X'[i]  = sum_j H_res[i, j] X[j] + H_post[i] y             y = F(norm(u))

Two entries, one before ``F`` and one after it, each ONE pass over the
stream; each a Pallas kernel on a TPU and the same equations in ``jax.numpy``
elsewhere (:func:`kernel_runs` is the one rule; the ``jnp`` forms are the CPU
path and the kernels' oracle):

- :func:`hc_pre`: ``X [T, n E]`` → ``u [T, E]`` (the stream's type) and the
  maps ``[T, 2n + n^2]`` float32 (``H_pre | H_post | H_res`` row-major), which
  the sub-block's :func:`hc_post` takes. One read of the row: the statistic,
  the projection (the stream's values are exact in its type, so one MXU pass
  with float32 accumulation IS the float32 product), the sigmoids, the clamp,
  the exponential and the rounds in VMEM. The rounds run with the TOKENS ON THE
  LANES (the projection is taken transposed, ``phi x^T``): the ``n^2`` entries
  of ``M`` are ``n^2`` rows ``[1, rows]``, a round is elementwise adds,
  ``2n`` reciprocals and multiplies of whole rows, no shuffle; the finished
  maps go back to tokens-on-sublanes through one product with the identity.
- :func:`hc_post`: ``X``, ``y [T, E]``, the maps → ``X' [T, n E]``, one read
  and one write of the row, in place (the stream is aliased).

A program's calls, two a sub-block, share ONE traced and ONE lowered kernel
each (a jit of their own: the shapes of all sub-blocks are the same). In the
served programs compiled for a v5e the stream never leaves the chip's fast
memory between the kernels (the compiler's memory-space assignment; PERF.md,
PR 57): they are bound by the vector unit and the rounds' latency, not by HBM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from ...telemetry import parts

PRE_KERNEL = "hc_pre"             # the names a trace shows the kernels under
POST_KERNEL = "hc_post"
ROWS = 64                         # rows of the stream a grid step holds
LANES = 512                       # lanes of the row taken at a time inside a step
VMEM_LIMIT = 48 * 2**20           # a step's rows in and out, twice each: 8.3 MB at 64 rows of 4 x 3584 bf16

_HI = lax.Precision.HIGHEST


def kernel_runs(E: int, impl: str = "auto") -> bool:
    """Whether the Pallas kernels run for streams ``E`` wide: on a TPU, or
    where ``impl`` is ``"pallas"`` (compiled for a described chip) or
    ``"interpret"`` (the tests, on the CPU), for whole lane tiles; never where
    it is ``"jnp"``."""
    if impl == "jnp" or E % 128:
        return False
    return impl in ("pallas", "interpret") or jax.default_backend() == "tpu"


# ---------------------------------------------------------------------------
# the equations in jax.numpy
# ---------------------------------------------------------------------------

def sinkhorn(M, iters: int):
    """``M [..., n, n]`` positive → ``iters`` rounds of columns then rows."""
    for _ in range(iters):
        M = M / jnp.sum(M, axis=-2, keepdims=True)
        M = M / jnp.sum(M, axis=-1, keepdims=True)
    return M


def maps_jnp(x, phi, a, b, n: int, eps: float, iters: int, clamp):
    """``x [..., n E]`` → the maps ``[..., 2n + n^2]`` float32."""
    f32 = jnp.float32
    xf = x.astype(f32)
    inv = lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    z = jnp.einsum("...e,ke->...k", xf, phi.astype(f32), precision=_HI) * inv
    a, b = a.astype(f32), b.astype(f32)
    pre = jax.nn.sigmoid(a[0] * z[..., :n] + b[:n])
    post = 2.0 * jax.nn.sigmoid(a[1] * z[..., n:2 * n] + b[n:2 * n])
    R = jnp.clip(a[2] * z[..., 2 * n:] + b[2 * n:], clamp[0], clamp[1])
    M = sinkhorn(jnp.exp(R).reshape(*R.shape[:-1], n, n), iters)
    return jnp.concatenate([pre, post, M.reshape(R.shape)], axis=-1)


def pre_jnp(x, maps, n: int):
    """``u = sum_i H_pre[i] X[i]`` in float32 → ``[..., E]`` in ``x``'s type."""
    xs = x.reshape(*x.shape[:-1], n, -1).astype(jnp.float32)
    return jnp.sum(maps[..., :n, None] * xs, axis=-2).astype(x.dtype)


def post_jnp(x, y, maps, n: int):
    """``X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y`` in float32 → ``x``'s
    shape and type."""
    xs = x.reshape(*x.shape[:-1], n, -1).astype(jnp.float32)
    res = maps[..., 2 * n:].reshape(*maps.shape[:-1], n, n)
    out = jnp.einsum("...ij,...je->...ie", res, xs, precision=_HI)
    out = out + maps[..., n:2 * n, None] * y.astype(jnp.float32)[..., None, :]
    return out.reshape(x.shape).astype(x.dtype)


# ---------------------------------------------------------------------------
# the kernels
# ---------------------------------------------------------------------------

def _chunks(width: int):
    step = LANES if width % LANES == 0 else 128
    return [(c, step) for c in range(0, width, step)]


def _dot_t(a, b):
    """``a [M, K] . b [N, K]^T`` → ``[M, N]`` float32; a float32 pair at full
    precision, a narrower pair exactly (its products are exact in float32)."""
    return lax.dot_general(a, b, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
                           precision=_HI if a.dtype == jnp.float32 else None)


def _pre_kernel(x_ref, phi_ref, ab_ref, u_ref, maps_ref, zt_ref, *, T, n, eps, iters, clamp):
    f32 = jnp.float32
    R, nE = x_ref.shape
    E, K = nE // n, 2 * n + n * n
    # one read of the rows: the projection, transposed (tokens on the lanes), and the squares
    zt = jnp.zeros((K, R), f32)
    sq = jnp.zeros((R, 128), f32)
    for c, w in _chunks(nE):
        xc = x_ref[:, c:c + w]
        zt = zt + _dot_t(phi_ref[:, c:c + w].astype(xc.dtype), xc)
        xf = xc.astype(f32)
        xf = xf * xf
        for t in range(0, w, 128):
            sq = sq + xf[:, t:t + 128]
    ssq = _dot_t(jnp.ones((8, 128), f32), sq)[:1]                        # [1, R]: the rows' sums, on the lanes
    zt_ref[...] = zt * lax.rsqrt(ssq / nE + eps)
    ab = ab_ref[...].astype(f32)                                          # [K, 2]: the gain and the bias of each column
    row = lambda k: zt_ref[k:k + 1, :] * ab[k:k + 1, 0:1] + ab[k:k + 1, 1:2]   # noqa: E731
    for k in range(n):
        zt_ref[k:k + 1, :] = jax.nn.sigmoid(row(k))
        zt_ref[n + k:n + k + 1, :] = 2.0 * jax.nn.sigmoid(row(n + k))
    M = [[jnp.exp(jnp.clip(row(2 * n + i * n + j), clamp[0], clamp[1])) for j in range(n)] for i in range(n)]
    for _ in range(iters):
        for j in range(n):
            s = M[0][j]
            for i in range(1, n):
                s = s + M[i][j]
            s = 1.0 / s
            for i in range(n):
                M[i][j] = M[i][j] * s
        for i in range(n):
            s = M[i][0]
            for j in range(1, n):
                s = s + M[i][j]
            s = 1.0 / s
            for j in range(n):
                M[i][j] = M[i][j] * s
    for i in range(n):
        for j in range(n):
            k = 2 * n + i * n + j
            zt_ref[k:k + 1, :] = M[i][j]
    if T % R:   # the last step's rows beyond the array are garbage: kept out of the product below
        from jax.experimental import pallas as pl

        real = pl.program_id(0) * R + lax.broadcasted_iota(jnp.int32, (K, R), 1) < T
        zt_ref[...] = jnp.where(real, zt_ref[...], 0.0)
    # back to tokens on the sublanes: a product with the identity (exact)
    eye = (lax.broadcasted_iota(jnp.int32, (R, R), 0) == lax.broadcasted_iota(jnp.int32, (R, R), 1)).astype(f32)
    maps = lax.dot_general(eye, zt_ref[...], (((1,), (1,)), ((), ())), preferred_element_type=f32, precision=_HI)
    maps_ref[...] = maps
    for c, w in _chunks(E):
        u = maps[:, 0:1] * x_ref[:, c:c + w].astype(f32)
        for i in range(1, n):
            u = u + maps[:, i:i + 1] * x_ref[:, i * E + c:i * E + c + w].astype(f32)
        u_ref[:, c:c + w] = u.astype(u_ref.dtype)


def _post_kernel(x_ref, y_ref, maps_ref, o_ref, *, n):
    f32 = jnp.float32
    E = y_ref.shape[1]
    maps = maps_ref[...]
    for c, w in _chunks(E):
        xs = [x_ref[:, j * E + c:j * E + c + w].astype(f32) for j in range(n)]
        y = y_ref[:, c:c + w].astype(f32)
        for i in range(n):
            o = maps[:, n + i:n + i + 1] * y
            for j in range(n):
                k = 2 * n + i * n + j
                o = o + maps[:, k:k + 1] * xs[j]
            o_ref[:, i * E + c:i * E + c + w] = o.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("n", "eps", "iters", "clamp", "impl"))
def _pre(x, phi, a, b, *, n, eps, iters, clamp, impl):
    with parts.part("hc.mix"):
        T, nE = x.shape
        if not kernel_runs(nE // n, impl):
            maps = maps_jnp(x, phi, a, b, n, eps, iters, clamp)
            return pre_jnp(x, maps, n), maps
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        E, K, R = nE // n, 2 * n + n * n, min(T, ROWS)
        gains = jnp.concatenate([jnp.broadcast_to(a[i], (w,)) for i, w in enumerate((n, n, n * n))])
        ab = jnp.stack([gains, b.astype(a.dtype)], axis=1)               # [K, 2]: each column's gain and bias
        rows = lambda w: pl.BlockSpec((R, w), lambda t: (t, 0))  # noqa: E731
        whole = lambda s: pl.BlockSpec(s, lambda t: (0, 0))  # noqa: E731
        return pl.pallas_call(
            functools.partial(_pre_kernel, T=T, n=n, eps=eps, iters=iters, clamp=clamp),
            grid=(pl.cdiv(T, R),),
            in_specs=[rows(nE), whole(phi.shape), whole((K, 2))],
            out_specs=[rows(E), rows(K)],
            out_shape=[jax.ShapeDtypeStruct((T, E), x.dtype), jax.ShapeDtypeStruct((T, K), jnp.float32)],
            scratch_shapes=[pltpu.VMEM((K, R), jnp.float32)],
            compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",), vmem_limit_bytes=VMEM_LIMIT),
            interpret=impl == "interpret", name=PRE_KERNEL,
        )(x, phi, ab)


@functools.partial(jax.jit, static_argnames=("n", "impl"))
def _post(x, y, maps, *, n, impl):
    with parts.part("hc.mix"):
        T, nE = x.shape
        if not kernel_runs(nE // n, impl):
            return post_jnp(x, y, maps, n)
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        R = min(T, ROWS)
        rows = lambda w: pl.BlockSpec((R, w), lambda t: (t, 0))  # noqa: E731
        return pl.pallas_call(
            functools.partial(_post_kernel, n=n),
            grid=(pl.cdiv(T, R),),
            in_specs=[rows(nE), rows(nE // n), rows(maps.shape[1])],
            out_specs=rows(nE),
            out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
            input_output_aliases={0: 0},
            compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",), vmem_limit_bytes=VMEM_LIMIT),
            interpret=impl == "interpret", name=POST_KERNEL,
        )(x, y, maps)


def hc_pre(x, phi, a, b, *, n: int, eps: float, iters: int, clamp=(-30.0, 30.0), impl: str = "auto"):
    """``x [..., n E]`` (the stream), ``phi [2n + n^2, n E]``, ``a [3]``, ``b
    [2n + n^2]`` → (``u [..., E]`` in ``x``'s type: the pre-mixed row; the maps
    ``[..., 2n + n^2]`` float32, for :func:`hc_post`)."""
    u, maps = _pre(x.reshape(-1, x.shape[-1]), phi, a, b, n=n, eps=float(eps), iters=int(iters),
                   clamp=(float(clamp[0]), float(clamp[1])), impl=impl)
    return u.reshape(*x.shape[:-1], -1), maps.reshape(*x.shape[:-1], -1)


def hc_post(x, y, maps, *, n: int, impl: str = "auto"):
    """``x [..., n E]``, the sub-block's output ``y [..., E]`` and
    :func:`hc_pre`'s maps → the stream behind the sub-block, ``x``'s shape."""
    out = _post(x.reshape(-1, x.shape[-1]), y.reshape(-1, y.shape[-1]), maps.reshape(-1, maps.shape[-1]),
                n=n, impl=impl)
    return out.reshape(x.shape)
