"""One Pallas kernel: the held experts' gated FFNs over the (token, expert)
pairs sorted by expert.

The pairs whose expert is held are laid out group by group, each group padded
to a whole number of ROW TILES of ``tm`` rows (:func:`tile_plan`). The grid
walks the live tiles alone (its first bound is the live tile count, a value
of the call) and, inside a tile, the experts' inner width ``F`` in blocks of
``bf`` columns:

    x        = u[token of each of the tile's rows]        one-hot rows times u: exact
    g, v     = x w_gate[e][:, block], x w_up[e][:, block]  float32
    a        = (silu(g) * v * weight of the row's pair)    rounded once
    acc     += a w_down[e][block, :]                       float32, over the blocks
    y[token] += acc, rounded once                          float32, at the last block

A tile names its expert ``e`` through a scalar-prefetched map, so a hit
expert's three matrices are streamed once, in blocks of megabytes, over that
expert's rows (once a tile where its rows outgrow one tile), an unhit
expert's are never read, and a call in which no pair is held runs no grid
step. The matrices are read where they lie, ``[n, E, F]`` twice and ``[n, F,
E]``: no copy, no other layout. ``u`` and the result ``y [T, E]`` are held
in VMEM whole (a call's rows are bounded by the caller:
``moe/expert_share.block_rows``, from :func:`max_rows`): the gather into pair order and the sum of a
token's pairs happen in the kernel, so nothing but the weights and ``u`` is
read and nothing but ``y`` written, once each, and no array of the call has
the static ``T x top_k`` pair rows but three index vectors.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TILE_ROWS = (16, 128)   # a row tile: whole bf16 sublane tiles; at 128 rows the products take half the weights' stream time, at 256 all of it
VMEM_BYTES = 110 << 20  # what one call may ask of the core's 128 MiB (Mosaic took 110 MiB on the chip; the program's own kernels ask 32)
ROWS_BYTES = 28 << 20   # u and y (float32) of one call, both whole in VMEM: 1 194 rows of 4 096, 796 of 6 144
KERNEL_NAME = "moe_experts_w_gate_up_down"   # the operands' names: the trace's readers find the experts' time by them


def row_tile(pairs: int, columns: int) -> int:
    """Rows of a tile from the call's static shapes: the power of two that
    holds twice the pairs a held expert expects (``pairs`` routed over
    ``columns`` router columns), within :data:`TILE_ROWS`."""
    want = max(1, -(-2 * pairs // columns))
    return min(max(1 << (want - 1).bit_length(), TILE_ROWS[0]), TILE_ROWS[1])


def max_rows(E: int, itemsize: int) -> int:
    """The most token rows one call holds (:data:`ROWS_BYTES`)."""
    return ROWS_BYTES // (E * (itemsize + 4))


def vmem_bytes(T: int, tm: int, E: int, bf: int, itemsize: int) -> int:
    """What the kernel asks of VMEM with ``bf`` columns a block: the three
    weight blocks twice (the pipeline's two buffers), ``u`` and ``y``
    (float32) once, the tile's rows, its accumulator and its picked rows in
    float32, a block's products in float32, and an eighth over."""
    blocks = 2 * 3 * E * bf * itemsize + T * E * (itemsize + 4)
    rows = tm * E * (itemsize + 4 + 4) + tm * (T * itemsize + 4 * bf * 4)
    return (blocks + rows) * 9 // 8


def f_block(T: int, tm: int, E: int, F: int, itemsize: int) -> int:
    """Columns of ``F`` a grid step takes: the most 128-lane tiles that divide
    ``F`` and keep the call within :data:`VMEM_BYTES` (all of ``F`` where it
    has no such divisor: a small layer). The fewer rows a call holds, the
    larger its weight blocks: 12.6 MB at 64 and 320 rows of 6 144, 8.4 MB at
    1 072 of 4 096, 6.3 MB in a whole-prompt program's 768-row blocks of 6 144."""
    fits = [b for b in range(128, F + 1, 128) if F % b == 0 and vmem_bytes(T, tm, E, b, itemsize) <= VMEM_BYTES]
    return max(fits) if fits else F


def tile_plan(group, n: int, tm: int):
    """The tile map of ``group [P]`` int32 (each pair's held expert ``0 .. n
    - 1``, or ``n``: not held): → ``order [P]`` (the pairs sorted by group,
    stable) and, over the ``P // tm + n`` tiles there can be (every group
    padded to whole tiles): ``tile_expert``, ``tile_rows`` (the rows of the
    tile that hold a pair; 0 past the live tiles), ``tile_first`` (where in
    ``order`` the tile's first pair stands), and ``n_live [1]``."""
    P = group.shape[0]
    order = jnp.argsort(group, stable=True).astype(jnp.int32)
    sizes = jnp.sum(group[:, None] == jnp.arange(n)[None, :], axis=0, dtype=jnp.int32)
    tiles = (sizes + tm - 1) // tm
    ends = jnp.cumsum(tiles)
    i = jnp.arange(P // tm + n, dtype=jnp.int32)
    e = jnp.minimum(jnp.sum(i[:, None] >= ends[None, :], axis=1, dtype=jnp.int32), n - 1)
    within = (i - (ends - tiles)[e]) * tm                   # rows of the group before this tile
    rows = jnp.where(i < ends[-1], jnp.clip(sizes[e] - within, 0, tm), 0)
    return order, e, rows, (jnp.cumsum(sizes) - sizes)[e] + within, ends[-1:]


def _kernel(te_ref, rows_ref, tok_ref, pick_ref, wt_ref, u_ref, wg_ref, wu_ref, wd_ref, y_ref, x_ref, acc_ref):
    """Grid ``(live tiles, F // bf)``. At a tile's block 0 its rows are picked
    out of ``u`` (one-hot rows times ``u`` on the MXU: exact; a row past the
    tile's pairs, token -1, is zeros) and its accumulator reset; every block
    adds its part; at the last block each of the tile's pairs, rounded once,
    is added to its token's row of ``y`` in float32 (``y`` stays in VMEM over
    the whole grid: zeroed at the first step, written back after the last)."""
    i, j = pl.program_id(0), pl.program_id(1)
    tm = x_ref.shape[0]

    @pl.when((i == 0) & (j == 0))
    def _zero():
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(j == 0)
    def _gather():
        u = u_ref[...]
        token = jax.lax.broadcasted_iota(jnp.int32, (tm, u.shape[0]), 1)
        x_ref[...] = jnp.dot(
            (pick_ref[...] == token).astype(u.dtype), u, preferred_element_type=jnp.float32
        ).astype(x_ref.dtype)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]
    g = jnp.dot(x, wg_ref[...], preferred_element_type=jnp.float32)
    v = jnp.dot(x, wu_ref[...], preferred_element_type=jnp.float32)
    a = (jax.nn.silu(g) * v * wt_ref[...]).astype(x.dtype)
    acc_ref[...] += jnp.dot(a, wd_ref[...], preferred_element_type=jnp.float32)

    @pl.when(j == pl.num_programs(1) - 1)
    def _combine():
        def add(r, c):
            t = pl.ds(tok_ref[i * tm + r], 1)
            y_ref[t, :] += acc_ref[pl.ds(r, 1), :].astype(x_ref.dtype).astype(jnp.float32)
            return c

        jax.lax.fori_loop(0, rows_ref[i], add, 0)


def grouped_expert_ffn(u, tile_expert, tile_rows, row_token, n_live, row_weight,
                       w_gate, w_up, w_down, tm: int, interpret: bool = False):
    """``u [T, E]``; per tile its expert and how many of its rows hold a pair
    (they lead), per padded row its token (-1: no pair) and its pair's weight
    (``[tiles, tm]``; float32 weights), ``n_live [1]`` the tiles to run →
    ``[T, E]`` float32: ``sum over a token's held pairs of weight *
    FFN_e(u[token])``, each pair rounded to ``u``'s type before the sum. With
    ``n_live`` 0 no step runs and nothing is written: the caller knows."""
    T, E = u.shape
    F = w_gate.shape[2]
    n_tiles = tile_expert.shape[0]
    itemsize = jnp.dtype(w_gate.dtype).itemsize
    bf = f_block(T, tm, E, F, itemsize)
    tokens = row_token.reshape(n_tiles * tm)   # twice below: scalars for the combine's row index, a column for the one-hot

    def spec(block, index, **kw):
        return pl.BlockSpec(block, lambda i, j, te, rows, tok: index(i, j, te), **kw)

    whole = dict(pipeline_mode=pl.Buffered(1))   # one block for the whole grid: one buffer
    return pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n_live[0], F // bf),
            in_specs=[
                spec((tm, 1), lambda i, j, te: (i, 0)),
                spec((tm, 1), lambda i, j, te: (i, 0)),
                spec((T, E), lambda i, j, te: (0, 0), **whole),
                spec((None, E, bf), lambda i, j, te: (te[i], 0, j)),
                spec((None, E, bf), lambda i, j, te: (te[i], 0, j)),
                spec((None, bf, E), lambda i, j, te: (te[i], j, 0)),
            ],
            out_specs=spec((T, E), lambda i, j, te: (0, 0), **whole),
            scratch_shapes=[pltpu.VMEM((tm, E), u.dtype), pltpu.VMEM((tm, E), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((T, E), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem_bytes(T, tm, E, bf, itemsize),
        ),
        name=KERNEL_NAME,
        interpret=interpret,
    )(
        tile_expert, tile_rows, tokens, tokens.reshape(-1, 1), row_weight.reshape(-1, 1),
        u, w_gate, w_up, w_down,
    )


def grouped_experts_ok(E: int, F: int) -> bool:
    """Trace-time gate: a TPU, and whole lane tiles in both widths."""
    return jax.default_backend() == "tpu" and E % 128 == 0 and F % 128 == 0
