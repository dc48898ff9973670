"""An expert layer that is told which experts it holds.

Under expert parallelism a chip holds ``n_held`` of a layer's ``n_experts``
routed experts and every chip holds what all tokens take alike: the shared
expert, where the model has one, and the IDENTITY experts, where the router has
such columns (zero-compute experts: ``n_zero`` columns behind the real ones,
whose "expert" gives the token back). The layer here is that chip's part, with
no exchange: it routes every token over ALL the published columns (the router
keeps its full width), forms the token's weights over the ``top_k`` it
selected, and computes what its own experts give for the tokens routed to
them, plus the shared expert and the identity term once. What the absent
experts would have added is left out; nothing stands in for the other chips.

    s     = sigmoid(u W_r)  |  softmax(u W_r)   float32, over all n_experts + n_zero columns
    sel   = top_k(s + b)                        the bias only selects
    w_e   = scale * s_e / sum_{sel} s           for e in sel   (``norm_topk``)
          | scale * s_e                                        (not renormalised)
    y     = sum_{e in sel, e < n_experts, e held} w_e FFN_e(u)
          + FFN_shared(u)                       where ``lp`` has ``shared``
          + (sum_{e in sel, e >= n_experts} w_e) u             the identity columns

Two scorings, one :func:`route`: ``sigmoid`` renormalised over the picks with a
shared expert (K-EXAONE, Mistral Small 4) and ``softmax`` over 512 + 256
columns, not renormalised, no shared expert (LongCat-Flash). The counts the
layer reports are the tokens each held expert got and, with identity columns,
the pairs that chose one of those (the last entry).

No capacity: a held expert computes every token routed to it. Two forms of
the same sum. A family that takes the grouped form says from how many rows a
call on (``expert_share_layer``'s ``grouped_from``, a static property of the
family: ``GROUPED_MIN_ROWS`` for the top-4 family this was measured for; 0,
the default, keeps every call of a family masked, as the top-8 family's
accepted cell was measured); the switch is then by the call's static row
count alone:

- MASKED (:func:`held_experts`), for a decode step's rows and a short chunk:
  the products run over all held experts at once with the weights of the
  unselected pairs zero (``[n_held, T, F]``). At 64 tokens x 8 of 128 every
  held expert is hit by some token of the batch anyway, so its weights are
  streamed either way, and a chunk of 256 tokens pays 16 x the products a
  grouped form would need, which the MXU has room for beside that stream
  (PERF.md, PR 32, has both forms measured).
- GROUPED (:func:`held_experts_grouped`), for calls of many rows: the
  (token, expert) pairs whose expert is held, sorted by expert, multiplied
  group by group (``lax.ragged_dot``: a held expert's weights meet only the
  rows routed to it). At 1 024 rows x 4 of 128 with 16 held the masked form
  multiplies 32 x the pairs there are (PERF.md, PR 34, has both measured).
  The pair budget is the static ``T x top_k`` (every token may pick held
  experts only); the pairs whose expert is absent (or an identity column) sort
  behind the held groups, belong to no group and carry weight 0. No pair is
  dropped.

A third routing, LongCat-Flash's 12 of 768 with 16 of 512 held (E 6144, F
2048): one selected pair in 48 meets a held expert, so the grouped form's
static budget is 48 times the pairs there are and the masked form multiplies
every row by every held expert. On the chip (PERF.md, PR 41;
``perfbench/tools/micro_longcat_flash.py``), masked / grouped, a layer: 2.43 /
2.49 ms at 64 rows (22 held pairs of 768, 11 experts hit), 3.20 / 3.70 ms at
320, 7.89 / 8.08 ms at 1 024 (283 of 12 288): ``ragged_dot`` pays for the
rows outside every group, so the grouped form wins nowhere and
``models/longcat_flash.LongcatFlashFamily.grouped_from`` is 0. Either form
streams all 16 held experts; a product that reads only the experts hit is
ROADMAP S12.

The sum of all the shares' routed parts, with the shared part and the identity
term once, is the uncut layer (``tests/unit/test_expert_share.py``).
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..telemetry import parts

_HI = jax.lax.Precision.HIGHEST


class ExpertShare(NamedTuple):
    """Which experts of ``n_experts`` live here: chip ``index`` of ``chips``
    holds experts ``index * n_held .. (index + 1) * n_held - 1``. ``n_zero``:
    the router's identity columns ``n_experts .. n_experts + n_zero - 1``,
    which no chip holds matrices for and every chip computes for its tokens."""
    n_experts: int
    chips: int = 1
    index: int = 0
    n_zero: int = 0

    @property
    def n_held(self) -> int:
        return self.n_experts // self.chips

    def held_ids(self):
        return self.index * self.n_held + jnp.arange(self.n_held)


SCORINGS = {"sigmoid": jax.nn.sigmoid, "softmax": lambda x: jax.nn.softmax(x, axis=-1)}


def route(u, router_w, bias, top_k: int, scale: float, norm_topk: bool = True,
          scoring: str = "sigmoid"):
    """``u [T, E]`` → (``idx [T, k]`` int32 over all the router's columns, ``w
    [T, k]`` float32: ``scale`` times the picked scores, renormalised over the
    picks with ``norm_topk``). Scores (``scoring``: :data:`SCORINGS`) in
    float32 at full precision: the selection is discrete, and a bf16 pass
    would flip near-ties that the float32 reference keeps."""
    s = SCORINGS[scoring](jnp.dot(
        u.astype(jnp.float32), router_w.astype(jnp.float32), precision=_HI
    ))
    _, idx = jax.lax.top_k(s + bias.astype(jnp.float32), top_k)
    picked = jnp.take_along_axis(s, idx, axis=-1)
    if norm_topk:
        picked = picked / jnp.sum(picked, axis=-1, keepdims=True)
    return idx, picked * scale


def held_weights(idx, w, share: ExpertShare):
    """The pairs whose expert is not held are dropped here, after the weights
    were formed over all ``k``: → ``[T, n_held]`` float32, 0 where the token
    did not select that held expert."""
    hit = idx[:, :, None] == share.held_ids()[None, None, :]      # [T, k, n]
    return jnp.sum(jnp.where(hit, w[:, :, None], 0.0), axis=1)


def held_hits(idx, share: ExpertShare):
    """``[T, n_held]`` bool: the token selected that held expert. From the
    selection itself, not from the weight: a softmax score may round to 0."""
    return jnp.any(idx[:, :, None] == share.held_ids()[None, None, :], axis=1)


def zero_weights(idx, w, share: ExpertShare):
    """The identity columns' part of a token's weights: → (``[T]`` float32,
    the sum of its picks' weights there; ``[T]`` int32, how many picks)."""
    zero = idx >= share.n_experts
    return jnp.sum(jnp.where(zero, w, 0.0), axis=1), jnp.sum(zero, axis=1, dtype=jnp.int32)


def gated_ffn(u, w_gate, w_up, w_down):
    """``w_down (silu(w_gate u) * w_up u)`` for one expert (the shared one,
    or a dense layer's MLP)."""
    return (jax.nn.silu(u @ w_gate) * (u @ w_up)) @ w_down


def held_experts(u, wh, w_gate, w_up, w_down):
    """``sum_e wh[t, e] FFN_e(u[t])`` over the held experts: ``u [T, E]``,
    ``wh [T, n]`` float32, ``w_gate`` / ``w_up [n, E, F]``, ``w_down [n, F,
    E]``. The pair's weight meets the float32 activation before its one
    rounding to the products' type."""
    g = jnp.einsum("te,nef->ntf", u, w_gate)
    v = jnp.einsum("te,nef->ntf", u, w_up)
    a = jax.nn.silu(g.astype(jnp.float32)) * v.astype(jnp.float32) * wh.T[:, :, None]
    return jnp.einsum("ntf,nfe->te", a.astype(u.dtype), w_down)


GROUPED_MIN_ROWS = 512     # where the grouped form wins at top-4 of 128, 16 held (PERF.md, PR 34)
GROUPED_BLOCK_ROWS = 4096  # the grouped form takes this many rows at a time (a whole-prompt program's temporaries)


def grouped_rows(T: int, top_k: int, grouped_from: int) -> int:
    """Pair rows a call of ``T`` token rows hands the grouped products
    (padding included: the static budget), 0 where the masked form runs."""
    return T * top_k if grouped_from and T >= grouped_from else 0


def held_experts_grouped(u, idx, w, share: ExpertShare, w_gate, w_up, w_down):
    """:func:`held_experts`' sum over the pairs themselves: ``idx`` / ``w [T,
    k]`` as :func:`route` gives them. The ``T x k`` pairs are sorted by held
    expert (absent experts' pairs last, outside every group), each group's
    rows meet its expert's weights once, and a pair's product is weighted in
    float32 before its one rounding, as in the masked form."""
    T, k = idx.shape
    n = share.n_held
    with parts.part("moe.route"):   # the sort and the gather
        local = idx - share.index * n
        group = jnp.where((local >= 0) & (local < n), local, n).reshape(T * k)
        order = jnp.argsort(group, stable=True)                        # pair rows, by group
        sizes = jnp.sum(group[:, None] == jnp.arange(n)[None, :], axis=0, dtype=jnp.int32)
        in_group = (jnp.arange(T * k) < jnp.sum(sizes))[:, None]       # rows past the groups: nothing
        x = u[order // k]                                              # [T * k, E]
    with parts.part("moe.experts"):
        g = jax.lax.ragged_dot(x, w_gate, sizes)
        v = jax.lax.ragged_dot(x, w_up, sizes)
        a = jax.nn.silu(g.astype(jnp.float32)) * v.astype(jnp.float32) * w.reshape(T * k)[order][:, None]
        a = jnp.where(in_group, a, 0.0).astype(u.dtype)
        y = jnp.where(in_group, jax.lax.ragged_dot(a, w_down, sizes), 0)
    with parts.part("moe.route"):   # the scatter back and the combine
        # back to the pairs' own order, then a token's k pairs summed in float32
        y = y[jnp.argsort(order)].reshape(T, k, -1)
        return jnp.sum(y.astype(jnp.float32), axis=1).astype(u.dtype)


def _routed(u, lp, share, top_k, scale, norm_topk, grouped_from, scoring):
    """→ (the held experts' part for ``u [T, E]`` with the identity term;
    what says which pairs are held, ``[T, n_held]``: the weights ``wh`` under
    sigmoid scores (positive: a selected pair's weight is), the selection
    itself under softmax (:func:`held_hits`); ``zero [T]`` int32: a token's
    picks among the identity columns, or None)."""
    with parts.part("moe.route"):
        idx, w = route(u, lp["router"], lp["bias"], top_k, scale, norm_topk, scoring)
        wh = held_weights(idx, w, share)
        held = wh if scoring == "sigmoid" else held_hits(idx, share)
    ex = lp["experts"]
    if grouped_rows(u.shape[0], top_k, grouped_from):
        y = held_experts_grouped(u, idx, w, share, ex["w_gate"], ex["w_up"], ex["w_down"])
    else:
        with parts.part("moe.experts"):
            y = held_experts(u, wh, ex["w_gate"], ex["w_up"], ex["w_down"])
    if not share.n_zero:
        return y, held, None
    with parts.part("moe.route"):   # the identity experts: the token itself, weighted in float32
        wz, zero = zero_weights(idx, w, share)
        y = (y.astype(jnp.float32) + wz[:, None] * u.astype(jnp.float32)).astype(u.dtype)
    return y, held, zero


def expert_share_layer(lp, u, share: ExpertShare, top_k: int, scale: float,
                       norm_topk: bool = True, valid: Optional[jnp.ndarray] = None,
                       grouped_from: int = 0, scoring: str = "sigmoid"):
    """``u [T, E]`` → (``y [T, E]``, ``counts [n_held]`` int32: the tokens
    each held expert got, and with ``share.n_zero`` one entry more, the pairs
    that chose an identity column; with ``valid [T]`` only those rows count,
    e.g. the slots that hold a request). ``lp``: ``router [E, n_experts +
    n_zero]``, ``bias`` as wide, ``experts`` and, where the model has a shared
    expert, ``shared``, each with ``w_gate, w_up, w_down``. ``grouped_from``:
    calls of that many rows or more take the grouped form (0: none does)."""
    T = u.shape[0]
    if grouped_from and T > GROUPED_BLOCK_ROWS and T % GROUPED_BLOCK_ROWS == 0:
        y, held, zero = jax.lax.map(
            lambda ub: _routed(ub, lp, share, top_k, scale, norm_topk, grouped_from, scoring),
            u.reshape(-1, GROUPED_BLOCK_ROWS, u.shape[1]),
        )
        y, held = y.reshape(T, -1), held.reshape(T, -1)
        zero = None if zero is None else zero.reshape(T)
    else:
        y, held, zero = _routed(u, lp, share, top_k, scale, norm_topk, grouped_from, scoring)
    if "shared" in lp:
        sh = lp["shared"]
        y = y + gated_ffn(u, sh["w_gate"], sh["w_up"], sh["w_down"])
    with parts.part("moe.route"):   # the load count
        got = held > 0.0 if scoring == "sigmoid" else held
        if valid is not None:
            got = got & valid[:, None]
        counts = jnp.sum(got, axis=0, dtype=jnp.int32)
        if zero is None:
            return y, counts
        if valid is not None:
            zero = jnp.where(valid, zero, 0)
        return y, jnp.concatenate([counts, jnp.sum(zero, dtype=jnp.int32)[None]])
