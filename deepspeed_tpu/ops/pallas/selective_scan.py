"""The selective scan of a state-space (Mamba) layer: a recurrence, the one
kind of kernel the attention files do not hold.

    h_t = exp(dt_t (x) A) . h_{t-1} + (dt_t . x_t) (x) B_t        h [N, d]
    s_t = C_t h_t + D . x_t                                       s [d]

``d`` is the layer's inner width (the channels, independent of each other),
``N`` its state size. Everything here is float32: the exponentials and the
state must be (a bfloat16 state forgets what 4 096 decode steps wrote into
it), and the chip's vector unit has no other arithmetic.

The state is kept ``[N, d]``: the channels on the lanes, the ``N`` state
values of a channel down the sublanes, so that a step is elementwise over
whole vector registers and ``s`` a sum over sublanes. (``[d, N]``, as the
layer's papers write it, would use 16 lanes of 128.) ``B_t`` and ``C_t`` are
then columns; the kernels take them as ``[rows, 2N, 1]`` and broadcast along
the lanes.

Two entries, each a Pallas kernel on a TPU and a ``lax.scan`` / plain
elementwise step elsewhere (:func:`kernel_runs` is the one rule; a kernel that
fails on the chip raises, nothing falls back):

- :func:`scan_rows`: a CHUNK of one slot's rows from a carried state → the
  rows' ``s`` and the state after the last row. A row whose ``dt`` is 0 leaves
  the state as it was (``exp(0) = 1``, and it adds ``0 . x``): that is how the
  padding behind a prompt's last token is kept out of the state.
- :func:`scan_step`: ONE row for each of many slots against layer ``layer`` of
  the whole ``[L, slots, N, d]`` state pool, which the kernel takes where it
  lies and gives back aliased (a slice of a layer out of the pool and back
  would move the layer twice a step). An idle slot's row has ``dt`` 0.

:func:`conv_rows` is the short causal depthwise convolution in front of the
scan, in plain ``jnp`` (four multiply-adds a value).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

CHUNK_KERNEL = "ssm_scan_chunk"   # the names a trace shows the kernels under
STEP_KERNEL = "ssm_scan_step"
ROW_BLOCK = 64                    # rows of a chunk a grid step holds
SLOT_BLOCK = 8                    # slots a grid step of the step kernel holds


def _lane_block(d: int) -> int:
    """Channels a grid step holds: the most of 512, 256, 128 that divides ``d``."""
    return next((b for b in (512, 256, 128) if d % b == 0), 0)


def kernel_runs(d: int, impl: str = "auto") -> bool:
    """Whether the Pallas kernels run for an inner width ``d``: on a TPU, or
    where ``impl`` is ``"pallas"`` (compiled for a described chip) or
    ``"interpret"`` (the tests, on the CPU), for whole lane tiles of channels;
    never where it is ``"jnp"``."""
    if impl == "jnp" or not _lane_block(d):
        return False
    return impl in ("pallas", "interpret") or jax.default_backend() == "tpu"


def conv_rows(w, b, xs, prev):
    """The causal depthwise convolution and its SiLU: ``xs [..., T, d]`` behind
    the ``K - 1`` rows before them ``prev [..., K-1, d]`` (zeros at a
    sequence's start), taps ``w [d, K]`` (tap ``K-1`` meets the row itself),
    bias ``b [d]`` → (``c [..., T, d]`` in ``xs``'s type, the rows it read
    ``[..., T+K-1, d]``: the last ``K - 1`` REAL ones of those are the next
    call's ``prev``)."""
    K, T = w.shape[-1], xs.shape[-2]
    full = jnp.concatenate([prev.astype(xs.dtype), xs], axis=-2)
    acc = b.astype(jnp.float32)
    for k in range(K):
        acc = acc + w[:, k].astype(jnp.float32) * lax.slice_in_dim(
            full, k, k + T, axis=full.ndim - 2
        ).astype(jnp.float32)
    return jax.nn.silu(acc).astype(xs.dtype), full


def _step(h, x, dt, b, c, A, D):
    """One row: ``h [N, d]``, ``x``, ``dt [1, d]``, ``b``, ``c [N, 1]`` →
    (``h``, ``s [1, d]``). The kernels and the fallbacks share these lines."""
    h = jnp.exp(dt * A) * h + (dt * x) * b
    return h, jnp.sum(h * c, axis=0, keepdims=True) + D * x


# ---------------------------------------------------------------------------
# a chunk of one slot's rows
# ---------------------------------------------------------------------------

def _scan_rows_jnp(x, dt, Bm, Cm, A, D, h0):
    def one(h, row):
        xt, dtt, bt, ct = row
        h, s = _step(h, xt[None], dtt[None], bt[:, None], ct[:, None], A, D[None])
        return h, s[0]

    h1, s = lax.scan(one, h0, (x, dt, Bm, Cm))
    return s, h1


def _chunk_kernel(x_ref, dt_ref, bc_ref, a_ref, d_ref, h0_ref, s_ref, h_ref, *, rows, N):
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(1) == 0)
    def _():
        h_ref[...] = h0_ref[...]

    A, D = a_ref[...], d_ref[...]

    def one(t, h):
        bc = bc_ref[t]                                   # [2N, 1]
        h, s = _step(h, x_ref[pl.ds(t, 1), :], dt_ref[pl.ds(t, 1), :], bc[:N], bc[N:], A, D)
        s_ref[pl.ds(t, 1), :] = s
        return h

    h_ref[...] = lax.fori_loop(0, rows, one, h_ref[...])


def scan_rows(x, dt, Bm, Cm, A, D, h0, *, impl: str = "auto"):
    """``x``, ``dt [T, d]``, ``Bm``, ``Cm [T, N]``, ``A [N, d]`` (negative),
    ``D [d]``, the carried state ``h0 [N, d]``, all float32 → (``s [T, d]``,
    the state after row ``T - 1``). ``T`` is any count: the kernel pads it to
    whole row blocks with rows of ``dt`` 0."""
    T, d = x.shape
    N = A.shape[0]
    if not kernel_runs(d, impl):
        return _scan_rows_jnp(x, dt, Bm, Cm, A, D, h0)
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows = min(ROW_BLOCK, -(-T // 8) * 8)
    Tp = -(-T // rows) * rows
    bc = jnp.concatenate([Bm, Cm], axis=-1)[:, :, None]            # [T, 2N, 1]
    if Tp != T:
        x, dt, bc = (jnp.pad(a, [(0, Tp - T)] + [(0, 0)] * (a.ndim - 1)) for a in (x, dt, bc))
    db = _lane_block(d)
    by_rows = pl.BlockSpec((rows, db), lambda j, t: (t, j))
    by_lanes = lambda n: pl.BlockSpec((n, db), lambda j, t: (0, j))  # noqa: E731
    s, h1 = pl.pallas_call(
        functools.partial(_chunk_kernel, rows=rows, N=N),
        grid=(d // db, Tp // rows),
        in_specs=[
            by_rows, by_rows, pl.BlockSpec((rows, 2 * N, 1), lambda j, t: (t, 0, 0)),
            by_lanes(N), by_lanes(1), by_lanes(N),
        ],
        out_specs=[by_rows, by_lanes(N)],
        out_shape=[jax.ShapeDtypeStruct((Tp, d), jnp.float32), jax.ShapeDtypeStruct((N, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary")),
        interpret=impl == "interpret", name=CHUNK_KERNEL,
    )(x, dt, bc, A, D[None], h0)
    return s[:T], h1


# ---------------------------------------------------------------------------
# one row for each slot, against a layer of the state pool
# ---------------------------------------------------------------------------

def _step_kernel(x_ref, dt_ref, bc_ref, a_ref, d_ref, pool_ref, s_ref, out_ref, *, slots, N):
    A, D = a_ref[...], d_ref[...]
    for i in range(slots):
        bc = bc_ref[i]
        h, s = _step(pool_ref[0, i], x_ref[i:i + 1, :], dt_ref[i:i + 1, :], bc[:N], bc[N:], A, D)
        out_ref[0, i] = h
        s_ref[i:i + 1, :] = s


def scan_step(x, dt, Bm, Cm, A, D, pool, layer: int, *, impl: str = "auto"):
    """``x``, ``dt [B, d]``, ``Bm``, ``Cm [B, N]``, ``A [N, d]``, ``D [d]``,
    the whole state pool ``[L, B, N, d]`` (row ``b`` is slot ``b``'s), all
    float32 → (``s [B, d]``, the pool with layer ``layer`` advanced)."""
    B, d = x.shape
    N = A.shape[0]
    if not kernel_runs(d, impl):
        bc = jnp.stack([Bm, Cm], axis=1)[..., None]                 # [B, 2, N, 1]
        h, s = jax.vmap(
            lambda h, xt, dtt, bct: _step(h, xt[None], dtt[None], bct[0], bct[1], A, D[None])
        )(pool[layer], x, dt, bc)
        return s[:, 0], pool.at[layer].set(h)
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    slots = SLOT_BLOCK if B % SLOT_BLOCK == 0 else B
    db = _lane_block(d)
    bc = jnp.concatenate([Bm, Cm], axis=-1)[:, :, None]
    by_slots = pl.BlockSpec((slots, db), lambda i, j: (i, j))
    by_lanes = lambda n: pl.BlockSpec((n, db), lambda i, j: (0, j))  # noqa: E731
    in_pool = pl.BlockSpec((1, slots, N, db), lambda i, j: (layer, i, 0, j))
    s, pool = pl.pallas_call(
        functools.partial(_step_kernel, slots=slots, N=N),
        grid=(B // slots, d // db),
        in_specs=[
            by_slots, by_slots, pl.BlockSpec((slots, 2 * N, 1), lambda i, j: (i, 0, 0)),
            by_lanes(N), by_lanes(1), in_pool,
        ],
        out_specs=[by_slots, in_pool],
        out_shape=[jax.ShapeDtypeStruct((B, d), jnp.float32), jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel")),
        interpret=impl == "interpret", name=STEP_KERNEL,
    )(x, dt, bc, A, D[None], pool)
    return s, pool
