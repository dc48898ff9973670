"""Plain float32 reference of one chip's share of a ``longcat_flash`` model
(LongCat-Flash-Chat), written from these equations in the EXPANDED form;
nothing is imported from ``deepspeed_tpu.models``. No cache, no kernel, no
absorption; every matrix product runs at ``highest`` precision. The program
computes the absorbed form (a query as wide as the cached latent row,
multi-query attention on that row), so the comparison also checks the
absorption.

One token stream, positions i. ``x = E[ids]`` (no position table). DOUBLE layer
l (every norm an RMS norm with a gain; ``silu`` gated FFNs; no bias):

    a1 = x  + MLA_1(rms(x; g_in1))
    u1 = rms(a1; g_post1)
    m  = MoE(u1)                          the shortcut: from u1, added at the end
    b1 = a1 + FFN_1(u1)                   dense
    a2 = b1 + MLA_2(rms(b1; g_in2))
    u2 = rms(a2; g_post2)
    x  = a2 + FFN_2(u2) + m

    MLA(u):  cq = rms(u Wqa; gq);  q = s_q (cq Wqb) as H heads of [q_nope N | q_rope R],
             s_q = sqrt(hidden / q_lora_rank)
             [c|kr] = u Wkva;  c = s_kv rms(c; gkv),  s_kv = sqrt(hidden / kv_lora_rank)
             (kr: one rotary key all heads share, neither normed nor scaled)
             k_h = [c Wuk_h | rot(kr)],  v_h = c Wuv_h
             rot: interleaved pairs (2j, 2j+1), f_j = theta^(-2j/R), positions as they are
             s_ij = q_i . k_j / sqrt(N + R), causal;  out = concat_h(softmax(s) v_h) Wo
    MoE(u):  s = softmax(u Wr) over ALL n_experts + n_zero columns
             sel = top_k(s + b);  w_e = scale s_e             (not renormalised)
             m = sum_{e in sel, e < n_experts, e held} w_e FFN_e(u)
               + (sum_{e in sel, e >= n_experts} w_e) u        identity experts
    logits = rms(x; gf) W_head                                 (untied)

The share: the experts this chip holds (``held`` of them from ``first_held``
on) give their part, the identity experts' term is added once (every chip
computes it alike for its own tokens), what the absent experts would add is
left out (here as in the program), and the vocabulary is the slice held. What
the published config does not say and this reference assumes is listed in the
configuration file under ``assumed``.

The only thing taken from the system is the *layout* of its parameter tree
(``layers[l]``: ``norm_in``, ``norm_post``, ``attn``, ``ffn``, each a pair, and
``moe`` with ``router, bias, experts`` stacked on a leading axis), so the same
seeded weights feed both. Weights arrive in the type the system holds them
and are cast to float32 where they are used, a leaf at a time and the held
experts one at a time. Attention runs in blocks of query rows.

``skip`` is for the controls only (each must read as NOT correct):
``shortcut`` leaves ``m`` out, ``moe_from_u2`` takes ``m`` from ``u2``,
``identity`` leaves the identity term out, ``sigmoid`` scores with a sigmoid,
``renorm`` renormalises the weights over the picks, ``scale1`` sets
``routed_scaling_factor`` to 1, ``s_q`` / ``s_kv`` set that scale to 1,
``attn2`` skips the second attention, ``rope_score`` leaves the rotary part out
of the score, ``experts:<l>`` drops double layer l's held experts' part.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

_HI = jax.lax.Precision.HIGHEST
ROW_BLOCK = 512   # query rows attended at a time

SKIPS = ("shortcut", "moe_from_u2", "identity", "sigmoid", "renorm", "scale1", "s_q", "s_kv", "attn2", "rope_score")


class Arch(NamedTuple):
    """The numbers of the configuration the equations need (hashable: a
    static argument of the jitted functions)."""
    n_layer: int                  # double layers
    n_head: int
    hidden: int
    q_rank: int
    kv_rank: int
    nope: int
    rope: int
    v_dim: int
    scale_q: bool
    scale_kv: bool
    n_experts: int                # published: the router's real columns
    n_zero: int                   # ... and its identity columns behind them
    held: int                     # routed experts held here ...
    first_held: int               # ... from this one on
    top_k: int
    scale: float
    eps: float
    theta: float
    vocab: int

    @classmethod
    def from_config(cls, c: dict) -> "Arch":
        share = c.get("expert_share", {"chips": 1, "index": 0})
        return cls(
            n_layer=int(c["num_layers"]), n_head=int(c["num_attention_heads"]), hidden=int(c["hidden_size"]),
            q_rank=int(c["q_lora_rank"]), kv_rank=int(c["kv_lora_rank"]),
            nope=int(c["qk_nope_head_dim"]), rope=int(c["qk_rope_head_dim"]), v_dim=int(c["v_head_dim"]),
            scale_q=bool(c.get("mla_scale_q_lora", True)), scale_kv=bool(c.get("mla_scale_kv_lora", True)),
            n_experts=int(c.get("published", {}).get("n_routed_experts", c["n_routed_experts"])),
            n_zero=int(c["zero_expert_num"]),
            held=int(c["n_routed_experts"]), first_held=int(share["index"]) * int(c["n_routed_experts"]),
            top_k=int(c["moe_topk"]), scale=float(c["routed_scaling_factor"]), eps=float(c["rms_norm_eps"]),
            theta=float(c["rope_theta"]), vocab=int(c["vocab_size"]),
        )


def dot_f32(a, b):
    """a [..., M, K] @ b [..., K, N] in float32 at full precision."""
    return jnp.matmul(a, b, precision=_HI)


def _f32(x):
    return x.astype(jnp.float32)


def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * _f32(g)


def _rope(x, freq):
    """x [S, heads, R] at positions 0..S-1, interleaved pairs (2j, 2j + 1)."""
    S = x.shape[0]
    ang = jnp.arange(S, dtype=jnp.float32)[:, None, None] * freq
    xp = x.reshape(*x.shape[:-1], -1, 2)
    x1, x2 = xp[..., 0], xp[..., 1]
    return jnp.stack([x1 * jnp.cos(ang) - x2 * jnp.sin(ang), x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1).reshape(x.shape)


def _ffn(u, w, dot):
    g = dot(u, _f32(w["w_gate"]))
    return dot(g * jax.nn.sigmoid(g) * dot(u, _f32(w["w_up"])), _f32(w["w_down"]))


def _attention(lp, u, a: Arch, skip: str, dot):
    S = u.shape[0]
    H, N, R, V, C = a.n_head, a.nope, a.rope, a.v_dim, a.kv_rank
    freq = (a.theta ** (-2.0 * np.arange(R // 2, dtype=np.float64) / R)).astype(np.float32)
    s_q = math.sqrt(a.hidden / a.q_rank) if a.scale_q and skip != "s_q" else 1.0
    s_kv = math.sqrt(a.hidden / a.kv_rank) if a.scale_kv and skip != "s_kv" else 1.0
    q = s_q * dot(_rms(dot(u, _f32(lp["wq_a"])), lp["q_norm"], a.eps), _f32(lp["wq_b"])).reshape(S, H, N + R)
    kv = dot(u, _f32(lp["wkv_a"]))
    c = s_kv * _rms(kv[:, :C], lp["kv_norm"], a.eps)
    kr = _rope(kv[:, None, C:], freq)[:, 0]                                   # [S, R]
    q_nope, q_rope = q[..., :N], _rope(q[..., N:], freq)
    k_nope = dot(c, _f32(lp["w_uk"]).reshape(C, H * N)).reshape(S, H, N)
    v = dot(c, _f32(lp["w_uv"]).reshape(C, H * V)).reshape(S, H, V)
    if skip == "rope_score":
        q_rope = jnp.zeros_like(q_rope)
    qh = jnp.concatenate([q_nope, q_rope], -1).transpose(1, 0, 2)                              # [H, S, N + R]
    kh = jnp.concatenate([k_nope, jnp.broadcast_to(kr[:, None, :], (S, H, R))], -1).transpose(1, 2, 0)
    vh = v.transpose(1, 0, 2)
    blk = math.gcd(S, ROW_BLOCK)

    def rows(i):
        qi = jax.lax.dynamic_slice_in_dim(qh, i * blk, blk, 1)
        s = dot(qi, kh) / math.sqrt(N + R)                                                    # [H, blk, S]
        seen = jnp.arange(S)[None, :] <= (i * blk + jnp.arange(blk))[:, None]
        return dot(jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1), vh)            # [H, blk, V]

    o = jax.lax.map(rows, jnp.arange(S // blk))                                               # [S/blk, H, blk, V]
    o = o.transpose(0, 2, 1, 3).reshape(S, H * V)
    return dot(o, _f32(lp["wo"]))


def _experts(mp, u, a: Arch, skip: str, routed: bool, dot):
    z = dot(u, _f32(mp["router"]))                                        # [S, n_experts + n_zero]
    s = jax.nn.sigmoid(z) if skip == "sigmoid" else jax.nn.softmax(z, axis=-1)
    _, sel = jax.lax.top_k(s + _f32(mp["bias"]), a.top_k)                 # [S, k]
    picked = jnp.take_along_axis(s, sel, axis=-1)
    if skip == "renorm":
        picked = picked / jnp.sum(picked, axis=-1, keepdims=True)
    w = (1.0 if skip == "scale1" else a.scale) * picked
    out = jnp.zeros_like(u)
    if skip != "identity":
        out = jnp.sum(jnp.where(sel >= a.n_experts, w, 0.0), axis=-1)[:, None] * u
    if not routed:
        return out

    def one(acc, xs):
        e, we = xs                                                        # the expert's published index, its weights
        w_e = jnp.sum(jnp.where(sel == e, w, 0.0), axis=-1)               # [S]: 0 where not selected
        return acc + w_e[:, None] * _ffn(u, we, dot), None

    ids = a.first_held + jnp.arange(a.held)
    return jax.lax.scan(one, out, (ids, mp["experts"]))[0]


def hidden(params, ids, a: Arch, skip: str = "", dot=dot_f32):
    """ids [S] -> final hidden states [S, E] in float32, normed."""
    x = _f32(params["embed"][ids])
    for l in range(a.n_layer):
        lp = params["layers"][l]
        a1 = x + _attention(lp["attn"][0], _rms(x, lp["norm_in"][0], a.eps), a, skip, dot)
        u1 = _rms(a1, lp["norm_post"][0], a.eps)
        b1 = a1 + _ffn(u1, lp["ffn"][0], dot)
        a2 = b1 if skip == "attn2" else b1 + _attention(lp["attn"][1], _rms(b1, lp["norm_in"][1], a.eps), a, skip, dot)
        u2 = _rms(a2, lp["norm_post"][1], a.eps)
        x = a2 + _ffn(u2, lp["ffn"][1], dot)
        if skip != "shortcut":
            x = x + _experts(lp["moe"], u2 if skip == "moe_from_u2" else u1, a, skip, skip != f"experts:{l}", dot)
    return _rms(x, params["norm_f"], a.eps)


def logits(params, ids, a: Arch, skip: str = "", dot=dot_f32):
    return dot(hidden(params, ids, a, skip, dot), _f32(params["head"]))[:, : a.vocab]


@functools.partial(jax.jit, static_argnames=("arch", "skip", "first"))
def served_gaps(params, ids, n_prompt, n_valid, *, arch: Arch, skip: str = "", first: int = 0):
    """Teacher-forced check of one served request, in ``reference.py``'s
    form. ``ids`` [T] is the prompt followed by the served tokens, padded;
    position t >= n_prompt-1 predicts the served token ids[t+1]. Returns, per
    position from ``first`` on (a static row from which the head is applied),
    the largest reference logit less the reference logit of the served token,
    0 outside the served range, and the logits' std per position."""
    h = hidden(params, ids, arch, skip)[first:]
    lg = dot_f32(h, _f32(params["head"]))[:, : arch.vocab]
    nxt = jnp.roll(ids, -1)[first:]
    chosen = jnp.take_along_axis(lg, nxt[:, None], axis=-1)[:, 0]
    gap = jnp.max(lg, axis=-1) - chosen
    t = first + jnp.arange(lg.shape[0])
    served = (t >= n_prompt - 1) & (t < n_valid - 1)
    return jnp.where(served, gap, 0.0), jnp.std(lg, axis=-1)
