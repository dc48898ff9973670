"""Plain float32 reference of one chip's share of an ``exaone_moe`` model
(K-EXAONE), written from these equations; nothing is imported from
``deepspeed_tpu.models``. No cache, no kernel, no batching tricks; every
matrix product runs at ``highest`` precision.

One token stream, positions i. ``x = E[ids]`` (no position table). Layer l:

    u    = rms(x; g1)
    q    = u Wq as H heads of D;  k = u Wk, v = u Wv as KV heads of D
    q, k = rms_head(q; gq), rms_head(k; gk)            over each head's D
    on sliding_attention layers q and k take rotary positions (half-split,
    theta); on full_attention layers none
    query head h reads kv head h // (H / KV); scores q k^T / sqrt(D), causal,
    and on sliding layers only keys j > i - window
    x    = x + softmax(scores) v Wo
    u    = rms(x; g2)
    dense layers:   x = x + Wd (silu(Wg u) * Wu u)
    sparse layers:  s = sigmoid(u Wr) over ALL published experts
                    sel = top_k(s + b)            (b only selects)
                    w_e = scale * s_e / sum_{sel} s
                    x = x + sum_{e in sel, e held} w_e FFN_e(u) + FFN_shared(u)
    logits = rms(x; gf) W_head                          (untied)

The share: the experts this chip holds (``held`` of them from ``first_held``
on) give their part, the shared expert is added once, what the absent experts
would add is left out (here as in the program), and the vocabulary is the
slice held. The published config does not say three things, which this
reference assumes as the configuration file lists them under ``assumed``:
norms BEFORE attention and MLP; the selection bias ``b``; QK norm on every
layer with rotary on sliding layers only (EXAONE 4.0, arXiv:2507.11407).

The only thing taken from the system is the *layout* of its parameter tree
(``layers[l]`` with ``attn.wqkv`` = [Wq | Wk | Wv] by columns, ``experts``
stacked on a leading axis), so the same seeded weights feed both. Weights
arrive in the type the system holds them and are cast to float32 where they
are used, one layer's at a time and the held experts one at a time, so the
reference never holds a second copy of the model.

``skip`` is for the controls only (each must read as NOT correct):
``experts:<l>`` drops layer l's routed part, ``window`` the sliding layers'
window mask, ``rotary`` their rotary positions. ``dot`` is the matrix
product, for the control that computes this reference in int8
(``tools/control_exaone_moe.py``).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST


class Arch(NamedTuple):
    """The numbers of the configuration the equations need (hashable: a
    static argument of the jitted functions)."""
    n_layer: int
    n_head: int
    n_kv_head: int
    head_dim: int
    sliding: Tuple[bool, ...]     # per layer: a sliding_attention layer
    sparse: Tuple[bool, ...]      # per layer: an expert layer
    window: int
    n_experts: int                # published: the router's width
    held: int                     # routed experts held here ...
    first_held: int               # ... from this one on
    top_k: int
    scale: float
    norm_topk: bool
    eps: float
    theta: float
    vocab: int

    @classmethod
    def from_config(cls, c: dict) -> "Arch":
        L = int(c["num_hidden_layers"])
        share = c.get("expert_share", {"chips": 1, "index": 0})
        n_pub = int(c.get("num_experts_published", c.get("published", {}).get("num_experts", c["num_experts"])))
        return cls(
            n_layer=L, n_head=int(c["num_attention_heads"]), n_kv_head=int(c["num_key_value_heads"]),
            head_dim=int(c["head_dim"]),
            sliding=tuple(t == "sliding_attention" for t in c["layer_types"][:L]),
            sparse=tuple(t == "sparse" for t in c["mlp_layer_types"][:L]),
            window=int(c["sliding_window"]), n_experts=n_pub, held=int(c["num_experts"]),
            first_held=int(share["index"]) * int(c["num_experts"]),
            top_k=int(c["num_experts_per_tok"]), scale=float(c["routed_scaling_factor"]),
            norm_topk=bool(c.get("norm_topk_prob", True)), eps=float(c["rms_norm_eps"]),
            theta=float(c["rope_parameters"]["rope_theta"]), vocab=int(c["vocab_size"]),
        )


def dot_f32(a, b):
    """a [..., M, K] @ b [..., K, N] in float32 at full precision."""
    return jnp.matmul(a, b, precision=_HI)


def _f32(x):
    return x.astype(jnp.float32)


def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * _f32(g)


def _rope(x, theta):
    """x [S, heads, D] at positions 0..S-1, half-split pairs (d, d + D/2)."""
    S, _, D = x.shape
    inv = theta ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None, None] * inv
    x1, x2 = x[..., : D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang), x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], axis=-1)


def _ffn(u, w, dot):
    g = dot(u, _f32(w["w_gate"]))
    return dot(g * jax.nn.sigmoid(g) * dot(u, _f32(w["w_up"])), _f32(w["w_down"]))


def _attention(lp, u, a: Arch, sliding: bool, skip: str, dot):
    S = u.shape[0]
    H, KV, D = a.n_head, a.n_kv_head, a.head_dim
    qkv = dot(u, _f32(lp["wqkv"]))
    q = _rms(qkv[:, : H * D].reshape(S, H, D), lp["q_norm"], a.eps)
    k = _rms(qkv[:, H * D: (H + KV) * D].reshape(S, KV, D), lp["k_norm"], a.eps)
    v = qkv[:, (H + KV) * D:].reshape(S, KV, D)
    if sliding and skip != "rotary":
        q, k = _rope(q, a.theta), _rope(k, a.theta)
    k, v = jnp.repeat(k, H // KV, axis=1), jnp.repeat(v, H // KV, axis=1)   # head h reads kv head h // rep
    i, j = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    seen = j <= i
    if sliding and skip != "window":
        seen = seen & (j > i - a.window)
    s = dot(q.transpose(1, 0, 2), k.transpose(1, 2, 0)) / jnp.sqrt(jnp.float32(D))       # [H, S, S]
    p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
    o = dot(p, v.transpose(1, 0, 2)).transpose(1, 0, 2).reshape(S, H * D)
    return dot(o, _f32(lp["wo"]))


def _experts(mp, u, a: Arch, routed: bool, dot):
    s = jax.nn.sigmoid(dot(u, _f32(mp["router"])))                       # [S, n_experts]
    _, sel = jax.lax.top_k(s + _f32(mp["bias"]), a.top_k)                 # [S, k]
    picked = jnp.take_along_axis(s, sel, axis=-1)
    w = a.scale * (picked / jnp.sum(picked, axis=-1, keepdims=True) if a.norm_topk else picked)
    out = _ffn(u, mp["shared"], dot)
    if not routed:
        return out

    def one(acc, xs):
        e, we = xs                                                        # the expert's published index, its weights
        w_e = jnp.sum(jnp.where(sel == e, w, 0.0), axis=-1)               # [S]: 0 where not selected
        return acc + w_e[:, None] * _ffn(u, we, dot), None

    ids = a.first_held + jnp.arange(a.held)
    routed_part, _ = jax.lax.scan(one, jnp.zeros_like(out), (ids, mp["experts"]))
    return out + routed_part


def hidden(params, ids, a: Arch, skip: str = "", dot=dot_f32):
    """ids [S] -> final hidden states [S, E] in float32, normed."""
    x = _f32(params["embed"][ids])
    for l in range(a.n_layer):
        lp = params["layers"][l]
        x = x + _attention(lp["attn"], _rms(x, lp["norm_1"], a.eps), a, a.sliding[l], skip, dot)
        u = _rms(x, lp["norm_2"], a.eps)
        if a.sparse[l]:
            x = x + _experts(lp["moe"], u, a, skip != f"experts:{l}", dot)
        else:
            x = x + _ffn(u, lp["mlp"], dot)
    return _rms(x, params["norm_f"], a.eps)


def logits(params, ids, a: Arch, skip: str = "", dot=dot_f32):
    return dot(hidden(params, ids, a, skip, dot), _f32(params["head"]))[:, : a.vocab]


@functools.partial(jax.jit, static_argnames=("arch", "skip"))
def served_gaps(params, ids, n_prompt, n_valid, *, arch: Arch, skip: str = ""):
    """Teacher-forced check of one served request, in ``reference.py``'s
    form. ``ids`` [T] is the prompt followed by the served tokens, padded;
    position t >= n_prompt-1 predicts the served token ids[t+1]. Returns, per
    position, (largest reference logit - reference logit of the served
    token), 0 outside the served range, and the logits' std per position."""
    lg = logits(params, ids, arch, skip)
    nxt = jnp.roll(ids, -1)
    chosen = jnp.take_along_axis(lg, nxt[:, None], axis=-1)[:, 0]
    gap = jnp.max(lg, axis=-1) - chosen
    t = jnp.arange(ids.shape[0])
    served = (t >= n_prompt - 1) & (t < n_valid - 1)
    return jnp.where(served, gap, 0.0), jnp.std(lg, axis=-1)
