"""An expert layer that is told which experts it holds.

Under expert parallelism a chip holds ``n_held`` of a layer's ``n_experts``
routed experts and every chip holds the shared expert. The layer here is that
chip's part, with no exchange: it routes every token over ALL the published
experts (the router keeps its full width), forms the token's weights over the
``top_k`` it selected, and computes what its own experts give for the tokens
routed to them, plus the shared expert once. What the absent experts would
have added is left out; nothing stands in for the other chips.

    s     = sigmoid(u W_r)                      float32, over all n_experts
    sel   = top_k(s + b)                        the bias only selects
    w_e   = scale * s_e / sum_{sel} s           for e in sel
    y     = sum_{e in sel, e held} w_e FFN_e(u) + FFN_shared(u)

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
  experts only); the pairs whose expert is absent sort behind the held
  groups, belong to no group and carry weight 0. No pair is dropped.

The sum of all the shares' routed parts and the shared part once is the uncut
layer (``tests/unit/test_expert_share.py``).
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..telemetry import parts

_HI = jax.lax.Precision.HIGHEST


class ExpertShare(NamedTuple):
    """Which experts of ``n_experts`` live here: chip ``index`` of ``chips``
    holds experts ``index * n_held .. (index + 1) * n_held - 1``."""
    n_experts: int
    chips: int = 1
    index: int = 0

    @property
    def n_held(self) -> int:
        return self.n_experts // self.chips

    def held_ids(self):
        return self.index * self.n_held + jnp.arange(self.n_held)


def route(u, router_w, bias, top_k: int, scale: float, norm_topk: bool = True):
    """``u [T, E]`` → (``idx [T, k]`` int32 over all experts, ``w [T, k]``
    float32). Scores in float32 at full precision: the selection is discrete,
    and a bf16 pass would flip near-ties that the float32 reference keeps."""
    s = jax.nn.sigmoid(jnp.dot(
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


def _routed(u, lp, share, top_k, scale, norm_topk, grouped_from):
    """→ (the held experts' part for ``u [T, E]``, ``wh [T, n_held]``)."""
    with parts.part("moe.route"):
        idx, w = route(u, lp["router"], lp["bias"], top_k, scale, norm_topk)
        wh = held_weights(idx, w, share)
    ex = lp["experts"]
    if grouped_rows(u.shape[0], top_k, grouped_from):
        return held_experts_grouped(u, idx, w, share, ex["w_gate"], ex["w_up"], ex["w_down"]), wh
    with parts.part("moe.experts"):
        return held_experts(u, wh, ex["w_gate"], ex["w_up"], ex["w_down"]), wh


def expert_share_layer(lp, u, share: ExpertShare, top_k: int, scale: float,
                       norm_topk: bool = True, valid: Optional[jnp.ndarray] = None,
                       grouped_from: int = 0):
    """``u [T, E]`` → (``y [T, E]``, ``counts [n_held]`` int32: the tokens
    each held expert got; with ``valid [T]`` only those rows count, e.g. the
    slots that hold a request). ``lp``: ``router [E, n_experts]``, ``bias
    [n_experts]``, ``experts`` and ``shared`` with ``w_gate, w_up, w_down``.
    ``grouped_from``: calls of that many rows or more take the grouped form
    (0: none does)."""
    T = u.shape[0]
    if grouped_from and T > GROUPED_BLOCK_ROWS and T % GROUPED_BLOCK_ROWS == 0:
        y, wh = jax.lax.map(
            lambda ub: _routed(ub, lp, share, top_k, scale, norm_topk, grouped_from),
            u.reshape(-1, GROUPED_BLOCK_ROWS, u.shape[1]),
        )
        y, wh = y.reshape(T, -1), wh.reshape(T, -1)
    else:
        y, wh = _routed(u, lp, share, top_k, scale, norm_topk, grouped_from)
    sh = lp["shared"]
    y = y + gated_ffn(u, sh["w_gate"], sh["w_up"], sh["w_down"])
    with parts.part("moe.route"):   # the load count
        got = wh > 0.0  # sigmoid scores are positive: a selected pair's weight is
        if valid is not None:
            got = got & valid[:, None]
        return y, jnp.sum(got, axis=0, dtype=jnp.int32)
