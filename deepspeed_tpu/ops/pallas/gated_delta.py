"""The gated delta rule of a linear-attention (Gated DeltaNet) layer: a
recurrence over a MATRIX-valued state a head, which ``selective_scan.py``'s
diagonal one does not hold. A value head's state ``S [dk, dv]`` (float32, keys
down the sublanes, values on the lanes), a token's ``q``, ``k [dk]`` (unit
length, ``q`` scaled), ``v [dv]``, decay ``g <= 0`` and ``beta`` in (0, 1):

    S   <- exp(g) S
    d   =  beta (v - S^T k)        the delta rule: what S holds under k is taken out
    S   <- S + k d^T               a rank-one correction that READ the state
    o   =  S^T q

Everything is float32 at full precision (``HIGHEST``: a single bfloat16 pass
over the state is the state kept in bfloat16). A row whose ``g`` and ``beta``
are 0 moves nothing and reads ``S^T q``: that is how padding and idle slots
are kept out of the state.

The decay is ONE scalar a head (``g [..., Hv]``: Gated DeltaNet, Qwen3-Next) or
a VECTOR over the key channels (``g [..., Hv, dk]``: Kimi Delta Attention,
arXiv:2510.26692; ``S <- diag(exp(g)) S``, a decay a row of ``S``). Every
entry branches on ``g``'s RANK at trace time: the scalar rule lowers to the
kernels ``gdn_step`` / ``gdn_chunk`` as it always did, the vector rule to
``kda_step`` / ``kda_chunk``.

Two entries, each a Pallas kernel on a TPU and the same lines under ``vmap`` /
``lax.scan`` elsewhere (:func:`kernel_runs` is the one rule; a kernel that
fails on the chip raises, nothing falls back). :func:`recurrence` is the
token-by-token form both are held to.

- :func:`chunk_rows`: a CHUNK of one slot's rows from a carried state, in
  sub-chunks of :data:`SUB` rows (the published ``chunk_gated_delta_rule``'s
  form; :func:`_sub_chunk` has the algebra). Sequential over sub-chunks, a
  value head a grid row. Under the scalar rule every decay is the exponential
  of a DIFFERENCE that is ``<= 0``. Under the vector rule the decay sits
  INSIDE the sum over channels (``sum_c k_ic k_jc exp(G_ic - G_jc)``), which is
  a matrix product only about a reference row ``r``: ``(K . exp(G - G_r)) (K .
  exp(G_r - G))^T``. The rule there (:func:`_sub_chunk_channels`): inside a
  diagonal block of :data:`BLOCK` rows ``r`` is the block's FIRST row, so the
  left exponents are ``<= 0`` and the right ones at most ``(BLOCK - 1) x |the
  decays' lower bound|``; every factor that reaches across blocks, and the
  state's, takes the LATER block's first row (or the row itself), where both
  exponents are ``<= 0``. No exponent passes :data:`EXP_ROOM` (float32 holds
  e^88.7): a family states its decays' lower bound (``g_min``) and
  :func:`chunk_rows` refuses one the block cannot hold.
- :func:`step`: ONE row for each of many slots against layer ``layer`` of the
  whole ``[L, slots, Hv, dk, dv]`` state pool, which the kernel takes where it
  lies and gives back aliased. Only LIVE slots are visited: the grid walks a
  scalar-prefetched order of the slots that hold a request, and its steps
  beyond them stay on the last one's block, which moves no byte.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

CHUNK_KERNEL = "gdn_chunk"        # the names a trace shows the kernels under
STEP_KERNEL = "gdn_step"
KDA_CHUNK_KERNEL = "kda_chunk"    # ... and the rule with a decay a key channel
KDA_STEP_KERNEL = "kda_step"
SUB = 64                          # rows of a sub-chunk
BLOCK = 16                        # rows of a diagonal block of the vector rule's sub-chunk
EXP_ROOM = 85.0                   # the largest exponent the vector rule's chunk may form: BLOCK x |g_min| stays under it
VMEM_LIMIT = 48 * 2**20           # a slot's state in and out, twice each (double buffers): 8 MB at 32 heads of 128 x 128

_HI = lax.Precision.HIGHEST
_dot = functools.partial(jnp.dot, precision=_HI, preferred_element_type=jnp.float32)


def kernel_runs(dk: int, dv: int, impl: str = "auto") -> bool:
    """Whether the Pallas kernels run for heads of ``dk`` keys and ``dv``
    values: on a TPU, or where ``impl`` is ``"pallas"`` (compiled for a
    described chip) or ``"interpret"`` (the tests, on the CPU), for whole lane
    tiles; never where it is ``"jnp"``."""
    if impl == "jnp" or dk % 128 or dv % 128:
        return False
    return impl in ("pallas", "interpret") or jax.default_backend() == "tpu"


def recurrence(q, k, v, g, beta, S0):
    """The equations above, token by token: ``q``, ``k [T, Hv, dk]`` (already
    one a VALUE head), ``v [T, Hv, dv]``, ``g``, ``beta [T, Hv]``, ``S0 [Hv,
    dk, dv]``, float32 → (``o [T, Hv, dv]``, the state after row ``T - 1``).
    ``g [T, Hv, dk]``: a decay a key channel."""
    def one(S, row):
        qt, kt, vt, gt, bt = row
        S = S * (jnp.exp(gt)[:, :, None] if gt.ndim == 2 else jnp.exp(gt)[:, None, None])
        d = bt[:, None] * (vt - jnp.einsum("hk,hkv->hv", kt, S, precision=_HI))
        S = S + kt[:, :, None] * d[:, None, :]
        return S, jnp.einsum("hk,hkv->hv", qt, S, precision=_HI)

    S1, o = lax.scan(one, S0, (q, k, v, g, beta))
    return o, S1


def _repeat(x, Hv: int):
    """``x [..., Hk, dk]`` → ``[..., Hv, dk]``: value head ``h`` reads key head
    ``h // (Hv / Hk)``."""
    return jnp.repeat(x, Hv // x.shape[-2], axis=-2)


# ---------------------------------------------------------------------------
# a chunk of one slot's rows
# ---------------------------------------------------------------------------

def _sub_chunk(q, k, kT, v, g, beta, S):
    """One value head over one sub-chunk of ``c`` rows: ``q``, ``k [c, dk]``,
    ``kT [dk, c]`` (``k`` again, transposed), ``v [c, dv]``, ``g``, ``beta [1,
    c]``, ``S [dk, dv]`` → (``o [c, dv]``, the state after the last row). With
    ``G`` the running sum of ``g`` and ``M_ij = exp(G_i - G_j)`` for ``i >= j``:

        (I + tril((diag(beta) K K^T) . M, -1)) [W_v | W_k] = diag(beta) [V | K . exp(G)]
        V' = W_v - W_k S                           every row's correction ``d``
        o  = (Q . exp(G)) S + tril((Q K^T) . M) V'
        S <- exp(G_last) S + (K . exp(G_last - G))^T V'

    Plain two-dimensional products, masks from iotas, rows broadcast down the
    sublanes; what has to lie along the OTHER axis (``G_i`` and ``beta_i``
    beside row ``i``) is made there by a product with ones, so the kernel and
    the ``vmap`` fallback share these lines. The unit-lower-triangular system
    is solved by its inverse, built block by block (the inverse of ``[[A, 0],
    [C, B]]`` is ``[[A', 0], [-B' C A', B']]``: six doublings from 1 to 64,
    each two products; no power of the triangle is ever formed, so nothing
    cancels)."""
    c, dk = k.shape
    dv = v.shape[1]
    f32 = jnp.float32
    i, j = lax.broadcasted_iota(jnp.int32, (c, c), 0), lax.broadcasted_iota(jnp.int32, (c, c), 1)
    low = (j <= i).astype(f32)                       # L_im: m <= i
    eye = (j == i).astype(f32)
    strict = (i > j).astype(f32)                     # as [m, j]: m > j
    ones = jnp.ones((c, max(dk, dv, c)), f32)
    gl = low * g                                     # [c, c]: g_m for m <= i
    G = _dot(gl, ones[:, :dk])                       # G_i on every lane of row i
    D = _dot(gl, strict)                             # G_i - G_j for i >= j (0 above the diagonal)
    M = low * jnp.exp(D)
    b = _dot(eye * beta, ones)                       # beta_i on every lane of row i
    A = strict * b[:, :c] * _dot(k, kT) * M
    X, half = eye, 1
    while half < c:
        off = ((i // half) % 2 == 1) & (j // half == i // half - 1)     # the lower-left block of each pair
        X, half = X - _dot(_dot(X, A * off.astype(f32)), X), 2 * half
    eG = jnp.exp(G)
    Vn = _dot(X, b[:, :dv] * v) - _dot(_dot(X, b[:, :dk] * k * eG), S)
    o = _dot(q * eG, S) + _dot(low * _dot(q, kT) * M, Vn)
    rest = _dot(jnp.broadcast_to(g, (8, c)), strict)[:1]               # [1, c]: G_last - G_i
    G_last = _dot(jnp.broadcast_to(g, (dk, c)), ones[:, :dv])           # on every element of [dk, dv]
    return o, jnp.exp(G_last) * S + _dot(kT * jnp.exp(rest), Vn)


def _sub_chunk_channels(q, k, kT, v, g, gT, beta, S):
    """:func:`_sub_chunk` with a decay a key channel: ``g [c, dk]``, ``gT [dk,
    c]`` (``g`` again, transposed), the rest as there. With ``G`` the running
    sum of ``g`` down the rows, a channel at a time, and ``P(a, b)_ij = sum_c
    a_ic b_jc exp(G_ic - G_jc)`` for ``i >= j``:

        (I + tril(diag(beta) P(K, K), -1)) [W_v | W_k] = diag(beta) [V | K . exp(G)]
        V' = W_v - W_k S
        o  = (Q . exp(G)) S + tril(P(Q, K)) V'
        S <- diag(exp(G_last)) S + (K . exp(G_last - G))^T V'

    ``P`` is a product about a reference row (the module's notes): row ``i``
    of block ``I`` (``BLOCK`` rows) is taken about the block's first row ``r_I``,
    ``a_i . exp(G_i - G_rI)``, exponents ``<= 0``; the columns it meets are
    ``b_j . exp(G_rI - G_j)``: ``<= 0`` for the blocks before, in ``[0, (BLOCK
    - 1) |g_min|]`` inside the block, and masked (exponent 0, under a zero of
    the triangle) behind it. One ``[BLOCK, dk] x [dk, c]`` product a block and
    operand; the rest is :func:`_sub_chunk`'s."""
    c, dk = k.shape
    dv = v.shape[1]
    f32 = jnp.float32
    i, j = lax.broadcasted_iota(jnp.int32, (c, c), 0), lax.broadcasted_iota(jnp.int32, (c, c), 1)
    low = (j <= i).astype(f32)
    eye = (j == i).astype(f32)
    strict = (i > j).astype(f32)
    ones = jnp.ones((c, max(dk, dv, c)), f32)
    G = _dot(low, g)                                 # [c, dk]: G_i, a channel a lane
    GT = _dot(gT, (i <= j).astype(f32))              # [dk, c]: the same, a row a lane
    first = (j == (i // BLOCK) * BLOCK).astype(f32)  # [i, m]: m is the first row of i's block
    lead = jnp.exp(G - _dot(first, G))               # exp(G_i - G_rI), <= 0
    kd, qd = k * lead, q * lead
    col = lax.broadcasted_iota(jnp.int32, (dk, c), 1)
    KK, QK = [], []
    for I in range(c // BLOCK):
        ref = _dot(GT, (i == I * BLOCK).astype(f32))                   # [dk, c]: G_rI on every lane
        ku = kT * jnp.exp(jnp.where(col < (I + 1) * BLOCK, ref - GT, 0.0))   # K_j . exp(G_rI - G_j), transposed
        rows = slice(I * BLOCK, (I + 1) * BLOCK)
        KK.append(_dot(kd[rows], ku))
        QK.append(_dot(qd[rows], ku))
    KK, QK = jnp.concatenate(KK, axis=0), jnp.concatenate(QK, axis=0)
    b = _dot(eye * beta, ones)                       # beta_i on every lane of row i
    A = strict * b[:, :c] * KK
    X, half = eye, 1
    while half < c:
        off = ((i // half) % 2 == 1) & (j // half == i // half - 1)     # the lower-left block of each pair
        X, half = X - _dot(_dot(X, A * off.astype(f32)), X), 2 * half
    eG = jnp.exp(G)
    Vn = _dot(X, b[:, :dv] * v) - _dot(_dot(X, b[:, :dk] * k * eG), S)
    o = _dot(q * eG, S) + _dot(low * QK, Vn)
    last = (i == c - 1).astype(f32)                                     # [m, j]: m is the last row
    rest = _dot(GT, last) - GT                                          # [dk, c]: G_last - G_j, <= 0
    G_last = _dot(GT, jnp.broadcast_to(last[:, :1], (c, dv)))           # [dk, dv]: a channel's on its row
    return o, jnp.exp(G_last) * S + _dot(kT * jnp.exp(rest), Vn)


def _chunk_kernel(q_ref, k_ref, kT_ref, v_ref, gb_ref, s0_ref, o_ref, s_ref):
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(1) == 0)
    def _():
        s_ref[...] = s0_ref[...]

    gb = gb_ref[0, 0]                                                   # [2, c]
    o, S = _sub_chunk(q_ref[0, 0], k_ref[0, 0], kT_ref[0, 0], v_ref[0, 0], gb[:1], gb[1:], s_ref[0])
    o_ref[0, 0] = o
    s_ref[0] = S


def _kda_chunk_kernel(q_ref, k_ref, kT_ref, v_ref, g_ref, gT_ref, b_ref, s0_ref, o_ref, s_ref):
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(1) == 0)
    def _():
        s_ref[...] = s0_ref[...]

    o, S = _sub_chunk_channels(q_ref[0, 0], k_ref[0, 0], kT_ref[0, 0], v_ref[0, 0], g_ref[0, 0], gT_ref[0, 0],
                               b_ref[0, 0], s_ref[0])
    o_ref[0, 0] = o
    s_ref[0] = S


def holds_decay(g_min) -> None:
    """Refuses, by name, a vector rule whose decays' lower bound the chunk's
    diagonal block cannot hold (the module's notes): ``BLOCK x |g_min|`` is the
    largest exponent :func:`_sub_chunk_channels` may form."""
    if g_min is None:
        raise ValueError(
            f"{KDA_CHUNK_KERNEL}: a decay a key channel needs its lower bound (g_min, e.g. kda_lower_bound): "
            "the chunk's diagonal blocks form exp(G_r - G_j), which only a bounded decay keeps inside float32"
        )
    if BLOCK * abs(float(g_min)) > EXP_ROOM:
        raise ValueError(
            f"{KDA_CHUNK_KERNEL}: a decay's lower bound of {g_min} a token over a diagonal block of {BLOCK} rows is "
            f"an exponent of {BLOCK * abs(float(g_min)):g}, over the {EXP_ROOM:g} this kernel keeps inside float32"
        )


def _by_head(a, n: int):
    """``a [T, H, ...]`` padded to ``n`` whole sub-chunks (rows of zeros: ``g``
    and ``beta`` 0) → head-major, a sub-chunk a block: ``[H, n, SUB, ...]``."""
    a = jnp.pad(a, [(0, n * SUB - a.shape[0])] + [(0, 0)] * (a.ndim - 1))
    return jnp.moveaxis(a.reshape(n, SUB, *a.shape[1:]), 2, 0)


def _chunk_rows_channels(q, k, v, g, beta, S0, impl: str):
    """:func:`chunk_rows` under a decay a key channel, ``g [T, Hv, dk]``."""
    T, Hk, dk = q.shape
    Hv, dv = v.shape[1:]
    n, r = -(-T // SUB), Hv // Hk
    by_head = lambda a: _by_head(a, n)  # noqa: E731
    qh, kh, vh, gh = by_head(q), by_head(k), by_head(v), by_head(g)        # [H, n, SUB, d]
    kT, gT = jnp.swapaxes(kh, 2, 3), jnp.swapaxes(gh, 2, 3)
    bh = by_head(beta)[:, :, None, :]                                      # [Hv, n, 1, SUB]
    if not kernel_runs(dk, dv, impl):
        rep = lambda a: jnp.repeat(a, r, axis=0)  # noqa: E731

        def sub(S, xs):
            o, S = jax.vmap(_sub_chunk_channels)(*xs, S)
            return S, o

        xs = tuple(jnp.moveaxis(a, 1, 0) for a in (rep(qh), rep(kh), rep(kT), vh, gh, gT, bh))
        S1, o = lax.scan(sub, S0, xs)                                       # o [n, Hv, SUB, dv]
        return jnp.moveaxis(o, 1, 2).reshape(n * SUB, Hv, dv)[:T], S1
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    key = lambda a, b: pl.BlockSpec((1, 1, a, b), lambda h, t: (h // r, t, 0, 0))  # noqa: E731
    val = lambda a, b: pl.BlockSpec((1, 1, a, b), lambda h, t: (h, t, 0, 0))  # noqa: E731
    state = pl.BlockSpec((1, dk, dv), lambda h, t: (h, 0, 0))
    o, S1 = pl.pallas_call(
        _kda_chunk_kernel,
        grid=(Hv, n),
        in_specs=[key(SUB, dk), key(SUB, dk), key(dk, SUB), val(SUB, dv), val(SUB, dk), val(dk, SUB), val(1, SUB), state],
        out_specs=[val(SUB, dv), state],
        out_shape=[jax.ShapeDtypeStruct((Hv, n, SUB, dv), jnp.float32), jax.ShapeDtypeStruct(S0.shape, jnp.float32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary")),
        interpret=impl == "interpret", name=KDA_CHUNK_KERNEL,
    )(qh, kh, kT, vh, gh, gT, bh, S0)
    return jnp.moveaxis(o, 0, 2).reshape(n * SUB, Hv, dv)[:T], S1


def chunk_rows(q, k, v, g, beta, S0, *, impl: str = "auto", g_min=None):
    """``q``, ``k [T, Hk, dk]``, ``v [T, Hv, dv]``, ``g``, ``beta [T, Hv]``,
    the carried state ``S0 [Hv, dk, dv]``, all float32 → (``o [T, Hv, dv]``,
    the state after row ``T - 1``). ``T`` is any count: it is padded to whole
    sub-chunks with rows of ``g`` and ``beta`` 0. ``g [T, Hv, dk]``: a decay a
    key channel, every one of them in ``[g_min, 0]`` (:func:`holds_decay`;
    the scalar rule reads no bound)."""
    if g.ndim == 3:
        holds_decay(g_min)
        return _chunk_rows_channels(q, k, v, g, beta, S0, impl)
    T, Hk, dk = q.shape
    Hv, dv = v.shape[1:]
    n = -(-T // SUB)
    by_head = lambda a: _by_head(a, n)  # noqa: E731
    qh, kh, vh = by_head(q), by_head(k), by_head(v)
    kT = jnp.swapaxes(kh, 2, 3)
    gb = jnp.stack([by_head(g), by_head(beta)], axis=2)                 # [Hv, n, 2, SUB]
    if not kernel_runs(dk, dv, impl):
        r = Hv // Hk
        rep = lambda a: jnp.repeat(a, r, axis=0)  # noqa: E731

        def sub(S, xs):
            o, S = jax.vmap(lambda qq, kk, kt, vv, gg, s: _sub_chunk(qq, kk, kt, vv, gg[:1], gg[1:], s))(*xs, S)
            return S, o

        xs = tuple(jnp.moveaxis(a, 1, 0) for a in (rep(qh), rep(kh), rep(kT), vh, gb))
        S1, o = lax.scan(sub, S0, xs)                                   # o [n, Hv, SUB, dv]
        return jnp.moveaxis(o, 1, 2).reshape(n * SUB, Hv, dv)[:T], S1
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    r = Hv // Hk
    key = lambda a, b: pl.BlockSpec((1, 1, a, b), lambda h, t: (h // r, t, 0, 0))  # noqa: E731
    val = lambda a, b: pl.BlockSpec((1, 1, a, b), lambda h, t: (h, t, 0, 0))  # noqa: E731
    state = pl.BlockSpec((1, dk, dv), lambda h, t: (h, 0, 0))
    o, S1 = pl.pallas_call(
        _chunk_kernel,
        grid=(Hv, n),
        in_specs=[key(SUB, dk), key(SUB, dk), key(dk, SUB), val(SUB, dv), val(2, SUB), state],
        out_specs=[val(SUB, dv), state],
        out_shape=[jax.ShapeDtypeStruct((Hv, n, SUB, dv), jnp.float32), jax.ShapeDtypeStruct(S0.shape, jnp.float32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary")),
        interpret=impl == "interpret", name=CHUNK_KERNEL,
    )(qh, kh, kT, vh, gb, S0)
    return jnp.moveaxis(o, 0, 2).reshape(n * SUB, Hv, dv)[:T], S1


# ---------------------------------------------------------------------------
# one row for each live slot, against a layer of the state pool
# ---------------------------------------------------------------------------

def _one_step(S, qc, kc, v, eg, beta):
    """One value head, one row: ``S [dk, dv]``, ``qc``, ``kc [dk, 1]`` (columns),
    ``v``, ``eg = exp(g)``, ``beta [1, dv]`` (rows, the two scalars on every
    lane) → (``S``, ``o [1, dv]``). The recurrence read off the state BEFORE
    its decay, so that the state is passed over once: ``(eg S)^T k = eg (S^T
    k)`` and ``S_new^T q = eg (S^T q) + (k . q) d``. The kernel and the
    fallback share these lines."""
    kS = jnp.sum(S * kc, axis=0, keepdims=True)
    qS = jnp.sum(S * qc, axis=0, keepdims=True)
    d = beta * (v - eg * kS)
    return eg * S + kc * d, eg * qS + jnp.sum(qc * kc, axis=0, keepdims=True) * d


def _live_order(live):
    """``live [B]`` bool → (the grid's walk ``[B]`` int32: the live slots first,
    in order, its steps beyond them on the last one's block, which moves no
    byte; how many are live)."""
    n_live = jnp.sum(live, dtype=jnp.int32)
    order = jnp.argsort(~live, stable=True).astype(jnp.int32)
    return jnp.where(jnp.arange(live.shape[0]) < n_live, order, order[jnp.maximum(n_live - 1, 0)]), n_live


def _one_step_channels(S, qc, kc, egc, v, beta):
    """:func:`_one_step` with a decay a key channel: ``egc = exp(g) [dk, 1]``, a
    column like ``qc`` and ``kc``: ``(diag(eg) S)^T k = S^T (eg . k)``."""
    kS = jnp.sum(S * (egc * kc), axis=0, keepdims=True)
    qS = jnp.sum(S * (egc * qc), axis=0, keepdims=True)
    d = beta * (v - kS)
    return egc * S + kc * d, qS + jnp.sum(qc * kc, axis=0, keepdims=True) * d


def _kda_step_kernel(order_ref, live_ref, qkg_ref, v_ref, beta_ref, pool_ref, o_ref, out_ref, *, Hv, Hk):
    from jax.experimental import pallas as pl

    i = pl.program_id(0)

    @pl.when(i < live_ref[0])
    def _():
        qkg = qkg_ref[0]                                                # [dk, 2 Hk + Hv]: q's heads, k's, then exp(g)'s
        for h in range(Hv):
            j = h // (Hv // Hk)
            S, o = _one_step_channels(
                pool_ref[0, 0, h], qkg[:, j:j + 1], qkg[:, Hk + j:Hk + j + 1], qkg[:, 2 * Hk + h:2 * Hk + h + 1],
                v_ref[0, h:h + 1, :], beta_ref[0, h:h + 1, :],
            )
            out_ref[0, 0, h] = S
            o_ref[0, h:h + 1, :] = o

    @pl.when(live_ref[0] == 0)
    def _():      # no live slot: the one block the grid sits on goes back as it came
        out_ref[...] = pool_ref[...]
        o_ref[...] = jnp.zeros_like(o_ref)


def _step_channels(q, k, v, g, beta, pool, layer: int, live, impl: str):
    """:func:`step` under a decay a key channel, ``g [B, Hv, dk]``: ``exp(g)``
    travels beside ``q`` and ``k``, keys down the sublanes."""
    B, Hk, dk = q.shape
    Hv, dv = v.shape[1:]
    bb = jnp.broadcast_to(beta[:, :, None], (B, Hv, dv))
    qkg = jnp.swapaxes(jnp.concatenate([q, k, jnp.exp(g)], axis=1), 1, 2)   # [B, dk, 2 Hk + Hv]
    if not kernel_runs(dk, dv, impl):
        r = Hv // Hk
        heads = jax.vmap(_one_step_channels)
        col = lambda a, n: jnp.repeat(jnp.swapaxes(a, 0, 1), n, axis=0)[:, :, None]  # noqa: E731  [dk, H] -> [H n, dk, 1]
        S, o = jax.vmap(lambda S, x, vv, b_: heads(
            S, col(x[:, :Hk], r), col(x[:, Hk:2 * Hk], r), col(x[:, 2 * Hk:], 1), vv[:, None], b_[:, None]))(
            pool[layer], qkg, v, bb)
        keep = live[:, None, None, None]
        return jnp.where(live[:, None, None], o[:, :, 0], 0.0), pool.at[layer].set(jnp.where(keep, S, pool[layer]))
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    order, n_live = _live_order(live)
    row = lambda a, b: pl.BlockSpec((1, a, b), lambda i, order, n: (order[i], 0, 0))  # noqa: E731
    in_pool = pl.BlockSpec((1, 1, Hv, dk, dv), lambda i, order, n: (layer, order[i], 0, 0, 0))
    o, pool = pl.pallas_call(
        functools.partial(_kda_step_kernel, Hv=Hv, Hk=Hk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(B,),
            in_specs=[row(dk, 2 * Hk + Hv), row(Hv, dv), row(Hv, dv), in_pool],
            out_specs=[row(Hv, dv), in_pool],
        ),
        out_shape=[jax.ShapeDtypeStruct((B, Hv, dv), jnp.float32), jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",), vmem_limit_bytes=VMEM_LIMIT),
        interpret=impl == "interpret", name=KDA_STEP_KERNEL,
    )(order, n_live[None], qkg, v, bb, pool)
    return jnp.where(live[:, None, None], o, 0.0), pool


def _step_kernel(order_ref, live_ref, qk_ref, v_ref, eg_ref, beta_ref, pool_ref, o_ref, out_ref, *, Hv, Hk):
    from jax.experimental import pallas as pl

    i = pl.program_id(0)

    @pl.when(i < live_ref[0])
    def _():
        qk = qk_ref[0]                                                  # [dk, 2 Hk]: q's heads, then k's
        for h in range(Hv):
            j = h // (Hv // Hk)
            S, o = _one_step(
                pool_ref[0, 0, h], qk[:, j:j + 1], qk[:, Hk + j:Hk + j + 1],
                v_ref[0, h:h + 1, :], eg_ref[0, h:h + 1, :], beta_ref[0, h:h + 1, :],
            )
            out_ref[0, 0, h] = S
            o_ref[0, h:h + 1, :] = o

    @pl.when(live_ref[0] == 0)
    def _():      # no live slot: the one block the grid sits on goes back as it came
        out_ref[...] = pool_ref[...]
        o_ref[...] = jnp.zeros_like(o_ref)


def step(q, k, v, g, beta, pool, layer: int, live, *, impl: str = "auto"):
    """``q``, ``k [B, Hk, dk]``, ``v [B, Hv, dv]``, ``g``, ``beta [B, Hv]``
    (float32), the whole state pool ``[L, B, Hv, dk, dv]`` (row ``b`` is slot
    ``b``'s), ``live [B]`` bool: the slots that hold a decoding request →
    (``o [B, Hv, dv]``, 0 for the others; the pool with the live slots of
    layer ``layer`` advanced, the others untouched). ``g [B, Hv, dk]``: a decay
    a key channel."""
    if g.ndim == 3:
        return _step_channels(q, k, v, g, beta, pool, layer, live, impl)
    B, Hk, dk = q.shape
    Hv, dv = v.shape[1:]
    eg = jnp.broadcast_to(jnp.exp(g)[:, :, None], (B, Hv, dv))
    bb = jnp.broadcast_to(beta[:, :, None], (B, Hv, dv))
    # keys down the sublanes, heads on the lanes: what the state's columns meet
    qk = jnp.swapaxes(jnp.concatenate([q, k], axis=1), 1, 2)            # [B, dk, 2 Hk]
    if not kernel_runs(dk, dv, impl):
        r = Hv // Hk
        heads = jax.vmap(_one_step)
        col = lambda a: jnp.repeat(jnp.swapaxes(a, 0, 1), r, axis=0)[:, :, None]  # noqa: E731  [dk, Hk] -> [Hv, dk, 1]
        S, o = jax.vmap(lambda S, x, vv, e, b_: heads(S, col(x[:, :Hk]), col(x[:, Hk:]), vv[:, None], e[:, None], b_[:, None]))(
            pool[layer], qk, v, eg, bb)
        keep = live[:, None, None, None]
        return jnp.where(live[:, None, None], o[:, :, 0], 0.0), pool.at[layer].set(jnp.where(keep, S, pool[layer]))
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    order, n_live = _live_order(live)
    row = lambda a, b: pl.BlockSpec((1, a, b), lambda i, order, n: (order[i], 0, 0))  # noqa: E731
    in_pool = pl.BlockSpec((1, 1, Hv, dk, dv), lambda i, order, n: (layer, order[i], 0, 0, 0))
    o, pool = pl.pallas_call(
        functools.partial(_step_kernel, Hv=Hv, Hk=Hk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(B,),
            in_specs=[row(dk, 2 * Hk), row(Hv, dv), row(Hv, dv), row(Hv, dv), in_pool],
            out_specs=[row(Hv, dv), in_pool],
        ),
        out_shape=[jax.ShapeDtypeStruct((B, Hv, dv), jnp.float32), jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",), vmem_limit_bytes=VMEM_LIMIT),
        interpret=impl == "interpret", name=STEP_KERNEL,
    )(order, n_live[None], qk, v, eg, bb, pool)
    return jnp.where(live[:, None, None], o, 0.0), pool
