"""Mixture-of-Experts with expert parallelism — einsum dispatch over the mesh.

TPU-native redesign of reference ``deepspeed/moe/sharded_moe.py`` (MOELayer:439,
TopKGate:351, top1gating:177, top2gating:278, _capacity:155, _AllToAll:89) and
``deepspeed/moe/layer.py`` (MoE:15). The reference routes tokens with an
explicit NCCL all-to-all autograd function between EP process groups; here
dispatch/combine are einsums against a capacity-slotted one-hot routing tensor
with sharding constraints — XLA lowers the expert-dim resharding to an ICI
all-to-all automatically, and the backward pass falls out of autodiff.

Gating implements the same semantics:
- top-1 (Switch) and top-2 gating with capacity factor
  (capacity = capacity_factor * tokens / experts, reference _capacity:155)
- load-balancing aux loss  l_aux = E * Σ_e  me_e · ce_e  (reference :243)
- optional probability-proportional random routing for the 2nd expert
- tokens over capacity are dropped (their combine weight is 0), like the
  reference's capacity masking.

Expert weights are stacked on a leading ``expert`` logical axis → sharded
over the ``ep`` mesh axis; expert-gradient reduction over the expert-DP
complement group (reference engine.py:2258) is subsumed by pjit.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

PyTree = Any


def _capacity(num_tokens: int, num_experts: int, capacity_factor: float, min_capacity: int = 4) -> int:
    # ceil, matching reference _capacity (sharded_moe.py:155) — truncating
    # would silently drop one extra token per expert whenever T*f/E is fractional
    import math

    cap = math.ceil(capacity_factor * num_tokens / num_experts)
    return max(cap, min_capacity)


def _one_hot(x, n):
    return jax.nn.one_hot(x, n, dtype=jnp.float32)


def top1_gating(
    logits: jnp.ndarray,  # [T, E]
    capacity_factor: float = 1.0,
    min_capacity: int = 4,
    rng=None,
    noisy_gate_policy: Optional[str] = None,
    drop_tokens: bool = True,
    use_rts: bool = True,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, Dict]:
    """Switch-style routing. Returns (l_aux, combine [T,E,C], dispatch [T,E,C]).

    Matches reference ``top1gating`` (sharded_moe.py:177):
    - ``drop_tokens=False`` → the reference lifts capacity to the allreduce-MAX
      of per-expert counts (sharded_moe.py:214 region, a dynamic shape). The
      static-shape XLA equivalent is the exact upper bound C = T: every token
      keeps its slot, nothing is dropped, and the program stays compilable.
    - ``use_rts`` (Random Token Selection, sharded_moe.py:225 region): when an
      expert is over capacity, the surviving C tokens are chosen by ranking
      ``mask1 * U(0,1)`` per expert instead of first-come-first-served, which
      de-biases the drop toward sequence position. Needs ``rng``; falls back
      to sequential priority when rng is None (deterministic eval).
    """
    T, E = logits.shape
    if noisy_gate_policy == "RSample" and rng is not None:
        rng, noise_rng = jax.random.split(rng)
        logits_for_choice = logits + jax.random.gumbel(noise_rng, logits.shape)
    else:
        logits_for_choice = logits
    gates = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)  # [T,E]
    expert_idx = jnp.argmax(logits_for_choice, axis=-1)  # [T]
    mask1 = _one_hot(expert_idx, E)  # [T,E]
    exp_counts = jnp.sum(mask1, axis=0)

    # aux loss (reference top1gating l_aux)
    me = jnp.mean(gates, axis=0)
    ce = jnp.mean(mask1, axis=0)
    l_aux = jnp.sum(me * ce) * E

    if drop_tokens:
        C = min(_capacity(T, E, capacity_factor, min_capacity), T)
        if use_rts and rng is not None:
            # Random Token Selection: priority = routed-mask * uniform noise,
            # keep the top-C priorities per expert
            priority = mask1 * jax.random.uniform(rng, mask1.shape, dtype=jnp.float32)
            _, top_idx = jax.lax.top_k(priority.T, C)  # [E,C] token ids
            sel = (
                jnp.zeros((E, T), jnp.bool_)
                .at[jnp.arange(E)[:, None], top_idx]
                .set(True)
            )
            keep = (mask1 > 0) & sel.T
        else:
            pos_in_expert = jnp.cumsum(mask1, axis=0) * mask1  # 1-based
            keep = (pos_in_expert <= C) & (mask1 > 0)
        kept = mask1 * keep
    else:
        C = T  # static no-drop bound (see docstring)
        kept = mask1

    # slot of each kept token within its expert's queue (0-based), computed
    # AFTER capacity masking like the reference (locations of new_mask1)
    locations = (jnp.cumsum(kept, axis=0) - 1.0) * kept
    loc_s = jnp.sum(locations, axis=-1).astype(jnp.int32)  # [T]
    dispatch = (kept > 0)[..., None] & (_one_hot(loc_s, C)[:, None, :] > 0)  # [T,E,C]
    gate_val = jnp.sum(gates * mask1, axis=-1, keepdims=True)  # [T,1]
    combine = gate_val[..., None] * dispatch.astype(jnp.float32)
    meta = {
        "capacity": C,
        "exp_counts": exp_counts,
        "tokens_dropped": jnp.sum(mask1) - jnp.sum(kept),
    }
    return l_aux, combine, dispatch, meta


def top2_gating(
    logits: jnp.ndarray,  # [T,E]
    capacity_factor: float = 1.0,
    min_capacity: int = 4,
    rng=None,
    second_policy: str = "random",
    drop_tokens: bool = True,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, Dict]:
    """GShard-style top-2 routing (reference top2gating:278). The 2nd expert
    is Gumbel-max sampled ∝ residual gate probability when ``second_policy ==
    "random"`` and rng is given (reference :297 gumbel_rsample), else argmax.
    ``drop_tokens=False`` lifts capacity to the static no-drop bound 2T."""
    T, E = logits.shape
    C = min(_capacity(T, E, 2 * capacity_factor, min_capacity), T)
    if not drop_tokens:
        # top-2 picks two DISTINCT experts per token, so any single expert
        # receives at most T assignments across both choices — C = T is the
        # tight static no-drop bound (not 2T). NOTE: the einsum dispatch is
        # O(T·E·C·M); at no-drop this is quadratic in T — fine for decode
        # steps and moderate prefills, long-prefill serving should chunk the
        # sequence through the MoE layer.
        C = T
    gates = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)

    idx1 = jnp.argmax(gates, axis=-1)
    mask1 = _one_hot(idx1, E)
    gates_wo_1 = gates * (1.0 - mask1)
    if second_policy == "random" and rng is not None:
        # sample 2nd expert ∝ residual gate probability (reference :305 region)
        idx2 = jax.random.categorical(rng, jnp.log(gates_wo_1 + 1e-9), axis=-1)
    else:
        idx2 = jnp.argmax(gates_wo_1, axis=-1)
    mask2 = _one_hot(idx2, E)

    me = jnp.mean(gates, axis=0)
    ce = jnp.mean(mask1, axis=0)
    l_aux = jnp.sum(me * ce) * E

    # capacity: expert-1 tokens queue first, expert-2 after (reference ordering)
    pos1 = jnp.cumsum(mask1, axis=0) * mask1
    pos2 = (jnp.cumsum(mask2, axis=0) + jnp.sum(mask1, axis=0, keepdims=True)) * mask2
    keep1 = (pos1 <= C) & (mask1 > 0)
    keep2 = (pos2 <= C) & (mask2 > 0)

    def slots(pos, keep):
        s = (pos - 1.0).clip(0) * keep
        return _one_hot(jnp.sum(s, axis=-1).astype(jnp.int32), C) * jnp.any(keep, -1, keepdims=True)

    disp1 = keep1[..., None] & (slots(pos1, keep1)[:, None, :] > 0)
    disp2 = keep2[..., None] & (slots(pos2, keep2)[:, None, :] > 0)

    g1 = jnp.sum(gates * mask1, axis=-1)
    g2 = jnp.sum(gates * mask2, axis=-1)
    denom = jnp.maximum(g1 + g2, 1e-9)
    g1, g2 = g1 / denom, g2 / denom

    combine = g1[:, None, None] * disp1.astype(jnp.float32) + g2[:, None, None] * disp2.astype(jnp.float32)
    dispatch = disp1 | disp2
    meta = {"capacity": C, "exp_counts": jnp.sum(mask1, axis=0)}
    return l_aux, combine, dispatch, meta


@dataclass
class MoEConfig:
    num_experts: int = 8
    k: int = 1  # top-k (1 or 2)
    capacity_factor: float = 1.0
    eval_capacity_factor: float = 1.0
    min_capacity: int = 4
    noisy_gate_policy: Optional[str] = None
    drop_tokens: bool = True
    use_rts: bool = True
    second_policy: str = "random"
    aux_loss_weight: float = 0.01


def init_moe_mlp_params(rng, d_model: int, d_hidden: int, num_experts: int, dtype=jnp.float32) -> PyTree:
    k1, k2, k3 = jax.random.split(rng, 3)
    std = 0.02
    return {
        "gate_w": (jax.random.normal(k1, (d_model, num_experts)) * std).astype(jnp.float32),
        "w_in": (jax.random.normal(k2, (num_experts, d_model, d_hidden)) * std).astype(dtype),
        "b_in": jnp.zeros((num_experts, d_hidden), dtype),
        "w_out": (jax.random.normal(k3, (num_experts, d_hidden, d_model)) * std).astype(dtype),
        "b_out": jnp.zeros((num_experts, d_model), dtype),
    }


def moe_mlp_logical_axes(swiglu: bool = False) -> PyTree:
    axes = {
        "gate_w": ("embed", None),
        "w_in": ("expert", "embed", "expert_mlp"),
        "b_in": ("expert", "expert_mlp"),
        "w_out": ("expert", "expert_mlp", "embed"),
        "b_out": ("expert", "embed"),
    }
    if swiglu:
        axes["w_gate"] = ("expert", "embed", "expert_mlp")
        axes.pop("b_in"), axes.pop("b_out")  # SwiGLU experts carry no biases
    return axes


def moe_mlp(
    params: PyTree,
    x: jnp.ndarray,  # [B, S, M]
    cfg: MoEConfig,
    rng=None,
    train: bool = True,
    activation: Callable = jax.nn.gelu,
    mesh=None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """MoE FFN block. Returns (output [B,S,M], aux_loss scalar).

    The reference pipeline (MOELayer.forward sharded_moe.py:491):
    gate → dispatch einsum → all-to-all → expert FFN → all-to-all → combine.
    Here the two all-to-alls are implicit in the 'tec,tm->ecm' / 'tec,ecm->tm'
    einsums once experts are sharded over ep.

    When ``mesh`` has a tp axis, tokens are scattered over tp before routing
    and gathered after combine (reference moe/mappings.py drop/gather_tokens)
    so expert work isn't duplicated tp-fold.
    """
    B, S, M = x.shape
    T = B * S
    xt = x.reshape(T, M)
    from .mappings import drop_tokens as _drop_tp, gather_tokens as _gather_tp

    xt = _drop_tp(xt, mesh)
    # routing logits always in f32 even if the engine cast params to bf16/fp16
    logits = xt.astype(jnp.float32) @ params["gate_w"].astype(jnp.float32)  # [T,E]
    capacity_factor = cfg.capacity_factor if train else cfg.eval_capacity_factor
    if cfg.k == 1:
        l_aux, combine, dispatch, _ = top1_gating(
            logits, capacity_factor, cfg.min_capacity, rng, cfg.noisy_gate_policy,
            drop_tokens=cfg.drop_tokens, use_rts=cfg.use_rts and train,
        )
    elif cfg.k == 2:
        l_aux, combine, dispatch, _ = top2_gating(
            logits, capacity_factor, cfg.min_capacity,
            rng if train else None,
            second_policy=cfg.second_policy, drop_tokens=cfg.drop_tokens,
        )
    else:
        raise ValueError(f"top-{cfg.k} gating unsupported (1 or 2)")

    dtype = x.dtype
    # dispatch: [T,E,C] x [T,M] -> [E,C,M]   (ICI all-to-all happens here)
    expert_in = jnp.einsum("tec,tm->ecm", dispatch.astype(dtype), xt)
    if "w_gate" in params:
        # SwiGLU experts (Mixtral-style): silu(x @ w_gate) * (x @ w_in)
        g = jax.nn.silu(jnp.einsum("ecm,emh->ech", expert_in, params["w_gate"]))
        u = jnp.einsum("ecm,emh->ech", expert_in, params["w_in"])
        if params.get("b_in") is not None:
            u = u + params["b_in"][:, None, :]
        h = g * u
    else:
        h = activation(jnp.einsum("ecm,emh->ech", expert_in, params["w_in"]) + params["b_in"][:, None, :])
    expert_out = jnp.einsum("ech,ehm->ecm", h, params["w_out"])
    if params.get("b_out") is not None:
        expert_out = expert_out + params["b_out"][:, None, :]
    # combine: [T,E,C] x [E,C,M] -> [T,M]    (all-to-all back)
    out = jnp.einsum("tec,ecm->tm", combine.astype(dtype), expert_out)
    out = _gather_tp(out, mesh)
    return out.reshape(B, S, M), l_aux.astype(jnp.float32)


# ---------------------------------------------------------------------------
# explicit expert parallelism (ISSUE 12 / ROADMAP item 6 seed): the two
# expert all-to-alls as EXPLICIT lax.all_to_all calls under shard_map, so
# they can ride the compressed wire.
# ---------------------------------------------------------------------------


def moe_mlp_ep(
    params: PyTree,
    x: jnp.ndarray,  # [B, S, M], B % ep == 0
    cfg: MoEConfig,
    mesh,
    rng=None,
    train: bool = True,
    activation: Callable = jax.nn.gelu,
    comm_compression=None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Expert-parallel MoE FFN with EXPLICIT all-to-alls → (out, aux_loss).

    Where :func:`moe_mlp` leaves the expert resharding to XLA (the
    dispatch/combine einsums), this variant runs the reference MOELayer
    pipeline literally (sharded_moe.py:491 / _AllToAll:89): tokens
    data-sharded over the ``ep`` axis, expert weights sharded over ``ep``,
    gate → LOCAL dispatch → **all_to_all** → expert FFN over every rank's
    contribution → **all_to_all** back → local combine. Making the
    transfer explicit is what lets it compress: with ``comm_compression``
    enabled and ``"ep"`` in its ``axes``, both exchanges move block-scaled
    int8/fp8 payloads + per-block scales
    (``comm/compressed.compressed_all_to_all``, ~3.9x fewer bytes at block
    256) and record (logical, wire) in the ``comm_wire_bytes`` ledger.
    Like the param gather — and unlike the grad reduce — the exchange is
    pure data movement, so there is no error-feedback residual to carry;
    the parity test bounds the one-shot rounding against the uncompressed
    exchange.

    Semantics note: routing/capacity are PER RANK (each dp rank routes its
    own ``T/ep`` tokens — the production EP formulation); with
    ``drop_tokens=False`` this matches :func:`moe_mlp` exactly, with drops
    the capacity boundary differs. ``aux_loss`` is the ep-mean of the
    per-rank losses. Requires ``B % ep == 0`` and
    ``num_experts % ep == 0``; top-1 gating (the Switch reference)."""
    from jax import lax as _lax
    from jax.sharding import PartitionSpec as _P

    from jax import shard_map

    world = int(mesh.shape.get("ep", 1))
    B, S, M = x.shape
    E = int(cfg.num_experts)
    if cfg.k != 1:
        raise ValueError("moe_mlp_ep implements top-1 (Switch) gating")
    if B % max(world, 1) or E % max(world, 1):
        raise ValueError(
            f"moe_mlp_ep: batch {B} and num_experts {E} must divide the ep "
            f"axis ({world})"
        )
    comp = None
    if (
        comm_compression is not None
        and bool(getattr(comm_compression, "enabled", False))
        and "ep" in tuple(getattr(comm_compression, "axes", ()) or ())
        and world > 1
    ):
        comp = (
            str(getattr(comm_compression, "method", "int8")),
            int(getattr(comm_compression, "block_size", 256)),
        )
    El = E // max(world, 1)
    cap_factor = cfg.capacity_factor if train else cfg.eval_capacity_factor
    Tl = (B // max(world, 1)) * S
    C = min(_capacity(Tl, E, cap_factor, cfg.min_capacity), Tl) \
        if cfg.drop_tokens else Tl

    def _exchange(t, dtype):
        """[world, El, C, M] → [world, El, C, M]: rank r's block j travels
        to rank j (compressed when configured)."""
        if world <= 1:
            return t
        if comp is not None:
            from ..comm import compressed as cco

            flat = t.reshape(world, -1)
            out = cco.compressed_all_to_all(flat, "ep", world, *comp)
            return out.reshape(t.shape).astype(dtype)
        return _lax.all_to_all(t, "ep", split_axis=0, concat_axis=0,
                               tiled=False)

    def local_fn(p, xb, key):
        Bl = xb.shape[0]
        xt = xb.reshape(Bl * S, M)
        logits = xt.astype(jnp.float32) @ p["gate_w"].astype(jnp.float32)
        key_l = None
        if key is not None and world > 1:
            key_l = jax.random.fold_in(key, _lax.axis_index("ep"))
        elif key is not None:
            key_l = key
        l_aux, combine, dispatch, _ = top1_gating(
            logits, cap_factor, cfg.min_capacity, key_l,
            cfg.noisy_gate_policy, drop_tokens=cfg.drop_tokens,
            use_rts=cfg.use_rts and train,
        )
        dtype = xb.dtype
        expert_in = jnp.einsum("tec,tm->ecm", dispatch.astype(dtype), xt)
        # forward exchange: group experts by owner rank, send each group home
        ein = _exchange(expert_in.reshape(world, El, C, M), dtype)
        # [world(source), El, C, M] → local experts over every rank's tokens
        ein2 = jnp.swapaxes(ein, 0, 1).reshape(El, world * C, M)
        h = activation(
            jnp.einsum("ecm,emh->ech", ein2, p["w_in"])
            + p["b_in"][:, None, :]
        )
        eout = jnp.einsum("ech,ehm->ecm", h, p["w_out"]) + p["b_out"][:, None, :]
        # return exchange: block j = rank j's tokens' results, send back
        back = jnp.swapaxes(eout.reshape(El, world, C, M), 0, 1)
        recv = _exchange(back, dtype)
        # [world(owner), El, C, M] → [E, C, M] in global expert order
        expert_out = recv.reshape(E, C, M)
        out = jnp.einsum("tec,ecm->tm", combine.astype(dtype), expert_out)
        if world > 1:
            l_aux = _lax.pmean(l_aux, "ep")
        return out.reshape(Bl, S, M), l_aux.astype(jnp.float32)

    if world <= 1:
        return local_fn(params, x, rng)

    pspec = {
        k: (_P() if k == "gate_w" else _P("ep"))
        for k in params
    }
    if rng is None:
        mapped = shard_map(
            lambda p, xb: local_fn(p, xb, None), mesh=mesh,
            in_specs=(pspec, _P("ep")),
            out_specs=(_P("ep"), _P()),
            check_vma=False,
        )
        return mapped(params, x)
    mapped = shard_map(
        local_fn, mesh=mesh,
        in_specs=(pspec, _P("ep"), _P()),
        out_specs=(_P("ep"), _P()),
        check_vma=False,
    )
    return mapped(params, x, rng)
