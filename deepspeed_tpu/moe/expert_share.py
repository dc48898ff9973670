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
            (with ``n_group`` > 1 over the columns of the ``topk_group`` groups a
            token KEEPS: the columns are ``n_group`` equal runs, a group's score
            the sum of its two largest ``s + b``; DeepSeek-V3's group limit)
    w_e   = scale * s_e / sum_{sel} s           for e in sel   (``norm_topk``)
          | scale * s_e                                        (not renormalised)
    y     = sum_{e in sel, e < n_experts, e held} w_e FFN_e(u)
          + FFN_shared(u)                       where ``lp`` has ``shared``
            (times sigmoid(u . w_sg)            where it has ``shared_gate``)
          + (sum_{e in sel, e >= n_experts} w_e) u             the identity columns

Two scorings, one :func:`route`: ``sigmoid`` renormalised over the picks with a
shared expert (K-EXAONE, Mistral Small 4) and ``softmax`` over 512 + 256
columns, not renormalised, no shared expert (LongCat-Flash), or over 512
columns, top-10 renormalised, beside a GATED shared expert (Qwen3-Next); and where the
router is no single matrix the family hands the scores' arguments in
(``logits``: ZAYA's three-layer MLP over a state that comes down the depth,
softmax, one pick, the probability itself the weight). The counts the
layer reports are the tokens each held expert got and, with identity columns,
the pairs that chose one of those (the last entry). Under a group limit
(``n_group`` > 1) the last entry is the ROWS that kept a group this chip holds
experts of: a chip that holds whole groups sees only those rows, and how many
they are is the deployment's load (:func:`held_groups`).

No capacity: a held expert computes every token routed to it. A row that is
no token (``valid``) picks nothing: it joins no expert's rows and no count.
Two forms of the same sum, and ONE rule between them, over what the trace can
see: :func:`kernel_runs`, a TPU and whole lane tiles in both widths. Every
call of every family takes the same form.

- GROUPED (:func:`held_experts_grouped`), where the kernel runs: the (token,
  expert) pairs whose expert is held, sorted by expert, each group padded to
  whole row tiles, multiplied tile by tile in one Pallas kernel
  (``ops/pallas/grouped_experts.py``: gate, up, silu and down over a row
  tile, the tile's expert named by a scalar-prefetched map). A hit expert's
  three matrices are streamed once over its own rows, an unhit expert's are
  not read, and the grid ends with the last live tile. The pair budget is the
  static ``T x top_k`` (every token may pick held experts only): the tile
  arrays are sized for it and no pair is dropped; the row tile comes from the
  pairs a held expert expects at the call's static shapes. What the held
  experts' matrices cost a call is therefore the experts HIT
  (:func:`experts_streamed`), at every row count the cells serve: 64 rows x 12
  of 768 hit 10-11 of the 16 held, 64 rows x 8 of 128 all of them, and the
  products of a 1 072-row call run beside the stream (PERF.md, PR 42, has the
  kernel beside ``lax.ragged_dot``, megablox ``gmm`` and the masked einsums
  at the three cells' shapes).
- MASKED (:func:`held_experts`), the plain ``jax.numpy`` form for every other
  backend and the tests' oracle: the products run over all held experts at
  once with the weights of the unselected pairs zero (``[n_held, T, F]``).

The sum of all the shares' routed parts, with the shared part and the identity
term once, is the uncut layer (``tests/unit/test_expert_share.py``).
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..ops.pallas import grouped_experts
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


def kept_groups(biased, n_group: int, topk_group: int):
    """``biased [T, columns]`` (``s + b``, float32) → ``[T, n_group]`` bool: the
    ``topk_group`` groups a token keeps. The columns are ``n_group`` equal
    runs; a group's score is the sum of its two largest entries."""
    T, N = biased.shape
    best2, _ = jax.lax.top_k(biased.reshape(T, n_group, N // n_group), 2)
    _, kept = jax.lax.top_k(jnp.sum(best2, axis=-1), topk_group)
    return jnp.any(kept[:, :, None] == jnp.arange(n_group)[None, None, :], axis=1)


def route_kept(u, router_w, bias, top_k: int, scale: float, norm_topk: bool = True,
               scoring: str = "sigmoid", logits=None, n_group: int = 1, topk_group: int = 1):
    """:func:`route`, and with it the groups each token kept (``[T, n_group]``
    bool; None where ``n_group`` is 1: no limit)."""
    if logits is None:
        logits = jnp.dot(u.astype(jnp.float32), router_w.astype(jnp.float32), precision=_HI)
    s = SCORINGS[scoring](logits.astype(jnp.float32))
    biased, kept = s + bias.astype(jnp.float32), None
    if n_group > 1:
        kept = kept_groups(biased, n_group, topk_group)
        biased = jnp.where(jnp.repeat(kept, biased.shape[-1] // n_group, axis=-1), biased, -jnp.inf)
    _, idx = jax.lax.top_k(biased, top_k)
    picked = jnp.take_along_axis(s, idx, axis=-1)
    if norm_topk:
        picked = picked / jnp.sum(picked, axis=-1, keepdims=True)
    return idx, picked * scale, kept


def route(u, router_w, bias, top_k: int, scale: float, norm_topk: bool = True,
          scoring: str = "sigmoid", logits=None, n_group: int = 1, topk_group: int = 1):
    """``u [T, E]`` → (``idx [T, k]`` int32 over all the router's columns, ``w
    [T, k]`` float32: ``scale`` times the picked scores, renormalised over the
    picks with ``norm_topk``). Scores (``scoring``: :data:`SCORINGS`) in
    float32 at full precision: the selection is discrete, and a bf16 pass
    would flip near-ties that the float32 reference keeps. ``logits [T,
    columns]``: the scores' arguments where the FAMILY computes them (a router
    that is no single matrix: ZAYA's MLP over a state carried down the depth);
    ``u`` and ``router_w`` are then not read. ``n_group``, ``topk_group``: the
    group limit (:func:`kept_groups`; 1, 1: none, and the trace is what it
    was): the picks are the ``top_k`` of the kept groups' columns, both
    selections on the same float32 scores."""
    return route_kept(u, router_w, bias, top_k, scale, norm_topk, scoring, logits, n_group, topk_group)[:2]


def held_groups(share: "ExpertShare", n_group: int):
    """The groups (of ``n_group`` equal runs of the router's columns) this
    share holds an expert of: one group's part, or some whole groups."""
    size = share.n_experts // n_group
    first, last = share.index * share.n_held, (share.index + 1) * share.n_held - 1
    return list(range(first // size, last // size + 1))


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


def block_rows(u) -> int:
    """Rows a call of the kernel takes of a program's ``u [T, E]``: all of
    them, or their largest divisor among the rows one call holds
    (``grouped_experts.max_rows``: a whole-prompt program's rows go through
    in that many at a time)."""
    T, E = u.shape
    most = max(1, grouped_experts.max_rows(E, u.dtype.itemsize))
    return T if T <= most else max(b for b in range(1, most + 1) if T % b == 0)


def held_experts_grouped(u, idx, w, share: ExpertShare, w_gate, w_up, w_down, interpret: bool = False):
    """:func:`held_experts`' sum over the pairs themselves, as ONE kernel
    (``ops/pallas/grouped_experts.py``): ``idx`` / ``w [T, k]`` as
    :func:`route` gives them. The ``T x k`` pairs are sorted by held expert,
    each group padded to whole row tiles; a tile's rows meet its expert's
    three matrices once, a pair's product is weighted in float32 before its
    one rounding, as in the masked form, and a token's pairs are summed in
    float32 in the kernel. The pairs whose expert is absent (or an identity
    column, or -1: a row that is no token) are in no tile."""
    T, k = idx.shape
    n, P = share.n_held, T * k
    tm = grouped_experts.row_tile(P, share.n_experts + share.n_zero)
    with parts.part("moe.route"):   # the sort and the tile map
        local = idx - share.index * n
        group = jnp.where((idx >= 0) & (local >= 0) & (local < n), local, n).reshape(P)
        order, tile_expert, tile_rows, tile_first, n_live = grouped_experts.tile_plan(group, n, tm)
        r = jnp.arange(tm)[None, :]
        live = r < tile_rows[:, None]                                  # [tiles, tm]: the row holds a pair
        pair = order[jnp.where(live, tile_first[:, None] + r, 0)]
        row_token = jnp.where(live, pair // k, -1)
        row_weight = jnp.where(live, w.reshape(P)[pair], 0.0)
    with parts.part("moe.experts"):
        y = grouped_experts.grouped_expert_ffn(
            u, tile_expert, tile_rows, row_token, n_live, row_weight, w_gate, w_up, w_down, tm, interpret
        )
        return jnp.where(n_live[0] > 0, y, 0.0).astype(u.dtype)   # no live tile: the kernel ran no step


def kernel_runs(lp) -> bool:
    """Whether this layer's held products take the kernel (a TPU, whole lane
    tiles): the ONE form there. Elsewhere the masked form runs."""
    return grouped_experts.grouped_experts_ok(*lp["experts"]["w_gate"].shape[1:])


def _routed(u, lp, share, top_k, scale, norm_topk, scoring, valid, logits=None, groups=(1, 1)):
    """→ (the held experts' part for ``u [T, E]`` with the identity term;
    what says which pairs are held, ``[T, n_held]``: the weights ``wh`` under
    sigmoid scores (positive: a selected pair's weight is), the selection
    itself under softmax (:func:`held_hits`); ``zero [T]`` int32: a token's
    picks among the identity columns, or under a group limit (``groups``:
    ``n_group, topk_group``) 1 where it kept a group held here, or None). A
    row that is no token (``valid``) picks nothing: no expert is read or
    counted for it."""
    with parts.part("moe.route"):
        idx, w, kept = route_kept(u, lp.get("router"), lp["bias"], top_k, scale, norm_topk, scoring, logits, *groups)
        if valid is not None:
            idx = jnp.where(valid[:, None], idx, -1)
        if kept is not None:
            here = jnp.any(kept[:, jnp.asarray(held_groups(share, groups[0]))], axis=1)
            kept = (here if valid is None else here & valid).astype(jnp.int32)
        wh = held_weights(idx, w, share)
        held = wh if scoring == "sigmoid" else held_hits(idx, share)
    ex = lp["experts"]
    if kernel_runs(lp):
        y = held_experts_grouped(u, idx, w, share, ex["w_gate"], ex["w_up"], ex["w_down"])
    else:
        with parts.part("moe.experts"):
            y = held_experts(u, wh, ex["w_gate"], ex["w_up"], ex["w_down"])
    if not share.n_zero:
        return y, held, kept
    with parts.part("moe.route"):   # the identity experts: the token itself, weighted in float32
        wz, zero = zero_weights(idx, w, share)
        y = (y.astype(jnp.float32) + wz[:, None] * u.astype(jnp.float32)).astype(u.dtype)
    return y, held, zero


def experts_streamed(counts, kernel: bool) -> int:
    """Held experts whose matrices the products of the calls behind ``counts
    [calls x layers, n_held]`` read: where the kernel runs, those some token
    picked (each has a live tile, no other has); in the masked form, all."""
    return int((counts > 0).sum()) if kernel else int(counts.size)


def expert_share_layer(lp, u, share: ExpertShare, top_k: int, scale: float,
                       norm_topk: bool = True, valid: Optional[jnp.ndarray] = None,
                       scoring: str = "sigmoid", logits: Optional[jnp.ndarray] = None,
                       n_group: int = 1, topk_group: int = 1):
    """``u [T, E]`` → (``y [T, E]``, ``counts [n_held]`` int32: the tokens
    each held expert got, and with ``share.n_zero`` one entry more, the pairs
    that chose an identity column, or with ``n_group`` > 1 (:func:`route`'s
    group limit; not both) the rows that kept a group held here; with
    ``valid [T]`` only those rows pick
    experts at all, e.g. the slots that hold a request: the others get the
    shared expert alone). ``lp``: ``router [E, n_experts + n_zero]``, ``bias``
    as wide, ``experts`` and, where the model has a shared expert, ``shared``,
    each with ``w_gate, w_up, w_down``, and where that expert has a scalar gate
    a token, ``shared_gate [E, 1]`` (``sigmoid(u . w_sg)`` times its output). ``logits [T, columns]``: the scores'
    arguments from the family, in the place of ``u @ lp["router"]``
    (:func:`route`)."""
    T, rows = u.shape[0], block_rows(u)
    groups = (n_group, topk_group)
    if n_group > 1 and share.n_zero:
        raise ValueError("a group limit over a router with identity columns is not built: the report has one last entry")
    if kernel_runs(lp) and rows < T:
        blocks = lambda a: None if a is None else a.reshape(-1, rows, *a.shape[1:])
        y, held, zero = jax.lax.map(
            lambda b: _routed(b[0], lp, share, top_k, scale, norm_topk, scoring, b[1], b[2], groups),
            (blocks(u), blocks(valid), blocks(logits)),
        )
        y, held = y.reshape(T, -1), held.reshape(T, -1)
        zero = None if zero is None else zero.reshape(T)
    else:
        y, held, zero = _routed(u, lp, share, top_k, scale, norm_topk, scoring, valid, logits, groups)
    if "shared" in lp:
        sh = lp["shared"]
        s = gated_ffn(u, sh["w_gate"], sh["w_up"], sh["w_down"])
        if "shared_gate" in lp:   # a scalar gate a token on the shared expert: sigmoid(u . w_sg), float32
            gate = jax.nn.sigmoid(jnp.matmul(u, lp["shared_gate"], preferred_element_type=jnp.float32))
            s = (gate * s.astype(jnp.float32)).astype(s.dtype)
        y = y + s
    with parts.part("moe.route"):   # the load count
        counts = jnp.sum(held > 0.0 if scoring == "sigmoid" else held, axis=0, dtype=jnp.int32)
        if zero is None:
            return y, counts
        return y, jnp.concatenate([counts, jnp.sum(zero, dtype=jnp.int32)[None]])
