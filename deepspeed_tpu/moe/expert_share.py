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

No capacity: a held expert computes every token routed to it. The products
run over all held experts at once with the weights of the unselected pairs
zero (``[n_held, T, F]``): at the served shapes every held expert is hit by
some token of the batch anyway (64 tokens x 8 of 128: an expert is missed
with probability 0.016), so its weights are streamed either way, and a chunk
of 256 tokens pays 16 x the products a grouped form would need, which the MXU
has room for beside that stream (PERF.md, PR 32, has both forms measured).

The sum of all the shares' routed parts and the shared part once is the uncut
layer (``tests/unit/test_expert_share.py``).
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

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


def expert_share_layer(lp, u, share: ExpertShare, top_k: int, scale: float,
                       norm_topk: bool = True, valid: Optional[jnp.ndarray] = None):
    """``u [T, E]`` → (``y [T, E]``, ``counts [n_held]`` int32: the tokens
    each held expert got; with ``valid [T]`` only those rows count, e.g. the
    slots that hold a request). ``lp``: ``router [E, n_experts]``, ``bias
    [n_experts]``, ``experts`` and ``shared`` with ``w_gate, w_up, w_down``."""
    idx, w = route(u, lp["router"], lp["bias"], top_k, scale, norm_topk)
    wh = held_weights(idx, w, share)
    ex, sh = lp["experts"], lp["shared"]
    y = held_experts(u, wh, ex["w_gate"], ex["w_up"], ex["w_down"])
    y = y + gated_ffn(u, sh["w_gate"], sh["w_up"], sh["w_down"])
    got = wh > 0.0  # sigmoid scores are positive: a selected pair's weight is
    if valid is not None:
        got = got & valid[:, None]
    return y, jnp.sum(got, axis=0, dtype=jnp.int32)
