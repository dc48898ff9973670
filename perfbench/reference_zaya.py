"""Plain float32 reference of a ``zaya`` model (ZAYA1-8B), written from these
equations; nothing is imported from ``deepspeed_tpu`` (the blocked tied head is
``reference_phi4flash.head_gaps``, another reference's). No cache, no kernel, no
batching; every matrix product runs at ``highest`` precision. The record of
the published modelling code is ISSUE 49's (there was no network): CCA,
"Compressed Convolutional Attention", arXiv:2510.04476; the router and the
residual merge, the ZAYA1 report, arXiv:2511.17127.

One token stream, positions t, L layers; ``x = E[ids]``. E hidden, d head, Hq /
Hk query / kv heads, G = Hq / Hk, Dq = Hq d, Dk = Hk d, R the router's width,
N experts, one pick. Layer l:

    u  = RMSNorm(x; g_a)
    qp = u Wq [E,Dq],  kp = u Wk [E,Dk],  z_t = [qp | kp]
    a_t = w0[:,0] . z_{t-1} + w0[:,1] . z_t + b0           depthwise, kernel 2, causal; z_{-1} = 0
    c_t = W1[g,0] a_{t-1}^(g) + W1[g,1] a_t^(g) + b1       grouped, Hq + Hk groups of d channels; a_{-1} = b0
          (the input is padded ONCE with two zero rows in front: z_{-1} = z_{-2} = 0)
    qc = c[:Dq] as [Hq,d],  kc = c[Dq:] as [Hk,d]
    mq_h = (qp_h + kp_{h // G}) / 2,  mk_j = (mean_{h in group j} qp_h + kp_j) / 2      BEFORE the convolutions
    q = qc + mq,  k = kc + mk
    q <- sqrt(d) q / |q|_2,  k <- sqrt(d) k / |k|_2 . exp(tau_j)
    rotary on the first d/2 lanes of every head, half-split pairs, theta; the other lanes carry no position
    v_t = [u_t Wv1 | u_{t-1} Wv2] as [Hk,d], u_{-1} = 0: kv head 0 the token's own values, head 1 the token before's
    o = softmax(q k^T / sqrt(d), causal) v,  query head h reads kv head h // G;  attn = o Wo
    y  = (sx_a . x + bx_a) + (sf_a . attn + bf_a)
    w  = RMSNorm(y; g_m)
    r_l = w Wd + gamma_l . r_{l-1},  r_{-1} = 0            the SAME token's state one layer up
    s  = W3 gelu(W2 gelu(W1 RMSNorm(r_l; g_r)))            gelu exact (erf)
    p  = softmax(s),  e = argmax(p + bias_l)
    m  = p_e . Wdown_e(silu(Wgate_e w) . Wup_e w)
    x' = (sx_m . y + bx_m) + (sf_m . m + bf_m)
    logits = RMSNorm(x_L; g_f) Emb^T

What the published config does not say and this reference assumes is listed in
the configuration file under ``assumed``. The only thing taken from the system
is the *layout* of its parameter tree (``cca.w_in`` = [Wq | Wk | Wv1 | Wv2] by
columns, ``cca.w1 [groups, 2, d, d]``, ``moe.experts`` stacked on a leading
axis), so the same seeded weights feed both. Weights arrive in the type the
system holds them and are cast to float32 where they are used, a layer's
leaves and an expert at a time; attention runs in blocks of query rows and the
vocabulary is taken in blocks (:func:`head_gaps`), so that 1 280 positions at
the published widths fit beside a served model.

``skip`` is for the controls only (each must read as NOT correct):
``carry_edge`` drops what the convolutions and the value shift take from the
rows before wherever one served call hands them to another of a different
kind (every 256th position: a chunk boundary; and the first row a decode step
computes, ``handed``), ``no_shift`` takes ``u_t Wv2`` in the place of ``u_{t-1} Wv2``,
``no_mean`` leaves the q-k mean out, ``no_conv1`` the second convolution,
``no_depth`` the router's depth state (``gamma = 0``), ``no_experts`` the
experts' part, ``no_res`` ignores the residual vectors (1, 0, 1, 0). ``dot``
is the matrix product, for the control that computes this reference in int8
(``tools/control_zaya.py``).
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from perfbench.reference_phi4flash import VOCAB_BLOCKS, head_gaps  # noqa: F401  (the tied head, a block of the vocabulary at a time)

_HI = jax.lax.Precision.HIGHEST
ROW_BLOCK = 256     # query rows attended at a time
CHUNK = 256         # where ``carry_edge`` cuts

SKIPS = ("carry_edge", "no_shift", "no_mean", "no_conv1", "no_depth", "no_experts", "no_res")


class Arch(NamedTuple):
    """The numbers of the configuration the equations need (hashable: a
    static argument of the jitted functions)."""
    n_layer: int
    n_head: int
    n_kv_head: int
    head_dim: int
    rotary_dim: int
    theta: float
    n_experts: int
    eps: float
    vocab: int

    @classmethod
    def from_config(cls, c: dict) -> "Arch":
        rope = c.get("rope_parameters", {}).get("hybrid", {})
        factor = float(rope.get("partial_rotary_factor", c.get("partial_rotary_factor", 0.5)))
        return cls(
            n_layer=int(c["num_hidden_layers"]), n_head=int(c["num_attention_heads"]),
            n_kv_head=int(c["num_key_value_heads"]), head_dim=int(c["head_dim"]),
            rotary_dim=int(int(c["head_dim"]) * factor), theta=float(rope.get("rope_theta", c.get("rope_theta", 5e6))),
            n_experts=int(c["num_experts"]), eps=float(c["rms_norm_eps"]), vocab=int(c["vocab_size"]),
        )


def dot_f32(a, b):
    """a [..., M, K] @ b [..., K, N] in float32 at full precision."""
    return jnp.matmul(a, b, precision=_HI)


def _f32(x):
    return x.astype(jnp.float32)


def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * _f32(g)


def _before(x, first):
    """Row t-1 in row t's place; ``first [C]`` (or zeros) in row 0's."""
    return jnp.concatenate([jnp.broadcast_to(first, x[:1].shape), x[:-1]], axis=0)


def _rope(x, a: Arch):
    """x [S, heads, d] at positions 0..S-1: half-split pairs over the first
    ``rotary_dim`` lanes, the others as they are."""
    S, D = x.shape[0], a.rotary_dim
    inv = a.theta ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None, None] * inv
    x1, x2 = x[..., : D // 2], x[..., D // 2: D]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang), x2 * jnp.cos(ang) + x1 * jnp.sin(ang), x[..., D:]], axis=-1)


def _cca(w, u, a: Arch, skip: str, dot, handed=None):
    """One layer's attention over ``u [S, E]`` (normed) → ``[S, E]``.
    ``handed``: the first row a decode step computes (``carry_edge`` cuts
    there too), or None."""
    S = u.shape[0]
    H, KV, d = a.n_head, a.n_kv_head, a.head_dim
    G, Dq, C = H // KV, H * d, (H + KV) * d
    p = dot(u, _f32(w["w_in"]))
    z, v1, v2 = p[:, :C], p[:, C: C + d], p[:, C + d:]
    w0, b0, b1 = _f32(w["w0"]), _f32(w["b0"]), _f32(w["b1"])
    zero = jnp.zeros((C,), jnp.float32)
    # a chunk boundary that forgets: the rows there start as a sequence starts
    t = jnp.arange(S)
    edge = (((t % CHUNK == 0) | (False if handed is None else t == handed)) & (skip == "carry_edge"))[:, None]
    z_prev = jnp.where(edge, 0.0, _before(z, zero))
    act = w0[:, 0] * z_prev + w0[:, 1] * z + b0
    if skip == "no_conv1":
        c = act
    else:
        a_prev = jnp.where(edge, b0, _before(act, b0)).reshape(S, H + KV, d)
        w1 = _f32(w["w1"])                                                      # [groups, 2, d, d]
        c = (dot(a_prev.transpose(1, 0, 2), w1[:, 0]) + dot(act.reshape(S, H + KV, d).transpose(1, 0, 2), w1[:, 1]))
        c = c.transpose(1, 0, 2).reshape(S, C) + b1
    qp, kp = z[:, :Dq].reshape(S, KV, G, d), z[:, Dq:].reshape(S, KV, 1, d)
    q, k = c[:, :Dq].reshape(S, KV, G, d), c[:, Dq:].reshape(S, KV, d)
    if skip != "no_mean":
        q = q + (qp + kp) / 2
        k = k + (qp.mean(axis=2) + kp[:, :, 0]) / 2
    unit = lambda x: math.sqrt(d) * x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True))  # noqa: E731
    q = _rope(unit(q).reshape(S, H, d), a)
    k = _rope(unit(k) * jnp.exp(_f32(w["tau"]))[:, None], a)
    v2_prev = v2 if skip == "no_shift" else jnp.where(edge, 0.0, _before(v2, jnp.zeros((d,), jnp.float32)))
    v = jnp.stack([v1, v2_prev], axis=1)                                        # [S, KV = 2, d]
    k, v = jnp.repeat(k, G, axis=1), jnp.repeat(v, G, axis=1)                   # head h reads kv head h // G
    kt, vt = k.transpose(1, 2, 0), v.transpose(1, 0, 2)
    outs = []
    for r0 in range(0, S, ROW_BLOCK):
        rows = min(ROW_BLOCK, S - r0)
        s = dot(q[r0: r0 + rows].transpose(1, 0, 2), kt) / math.sqrt(d)         # [H, rows, S]
        seen = jnp.arange(S)[None, :] <= (r0 + jnp.arange(rows))[:, None]
        pr = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        outs.append(dot(pr, vt).transpose(1, 0, 2).reshape(rows, Dq))
    return dot(jnp.concatenate(outs, axis=0), _f32(w["wo"]))


def _merge(r, x, f, skip: str):
    if skip == "no_res":
        return x + f
    return (_f32(r["sx"]) * x + _f32(r["bx"])) + (_f32(r["sf"]) * f + _f32(r["bf"]))


def _gelu(x):
    return 0.5 * x * (1.0 + jax.lax.erf(x / math.sqrt(2.0)))


def _experts(m, w, r_up, a: Arch, skip: str, dot):
    """→ (the picked expert's weighted output ``[S, E]``, this layer's router
    state, and how near its pick was to falling the other way: the largest of
    ``p + bias`` less the second ``[S]``)."""
    r = dot(w, _f32(m["wd"]))
    if r_up is not None and skip != "no_depth":
        r = r + _f32(m["gamma"]) * r_up
    s = dot(_gelu(dot(_gelu(dot(_rms(r, m["norm_r"], a.eps), _f32(m["w1"]))), _f32(m["w2"]))), _f32(m["w3"]))
    p = jax.nn.softmax(s, axis=-1)
    top = jax.lax.top_k(p + _f32(m["bias"]), 2)[0]
    e, tie = jnp.argmax(p + _f32(m["bias"]), axis=-1), top[:, 0] - top[:, 1]
    p_e = jnp.take_along_axis(p, e[:, None], axis=-1)[:, 0]
    if skip == "no_experts":
        return jnp.zeros_like(w), r, tie

    def one(acc, xs):
        i, we = xs
        g = dot(w, _f32(we["w_gate"]))
        out = dot(g * jax.nn.sigmoid(g) * dot(w, _f32(we["w_up"])), _f32(we["w_down"]))
        return acc + jnp.where(e == i, p_e, 0.0)[:, None] * out, None

    out, _ = jax.lax.scan(one, jnp.zeros_like(w), (jnp.arange(a.n_experts), m["experts"]))
    return out, r, tie


def hidden(params, ids, a: Arch, skip: str = "", dot=dot_f32, handed=None):
    """ids [S] -> (final hidden states [S, E] in float32, normed; every
    layer's pick margin [L, S]: see :func:`_experts`)."""
    x = _f32(params["embed"][ids])
    r, ties = None, []
    for l in range(a.n_layer):
        lp = params["layers"][l]
        y = _merge(lp["res_a"], x, _cca(lp["cca"], _rms(x, lp["norm_a"], a.eps), a, skip, dot, handed), skip)
        m, r, tie = _experts(lp["moe"], _rms(y, lp["norm_m"], a.eps), r, a, skip, dot)
        ties.append(tie)
        x = _merge(lp["res_m"], y, m, skip)
    return _rms(x, params["norm_f"], a.eps), jnp.stack(ties)


def logits(params, ids, a: Arch, skip: str = "", dot=dot_f32):
    """Whole logits [S, vocab] (small sizes: the tests, a control's next token)."""
    return dot(hidden(params, ids, a, skip, dot)[0], _f32(params["embed"]).T)[:, : a.vocab]


@functools.partial(jax.jit, static_argnames=("arch", "skip", "rows"))
def served_gaps(params, ids, n_prompt, n_valid, *, arch: Arch, rows: int, skip: str = ""):
    """Teacher-forced check of one served request, in ``reference.py``'s
    form. ``ids`` [T] is the prompt followed by the served tokens, padded
    (``T >= n_prompt - 1 + rows``); position t >= n_prompt-1 predicts the
    served token ids[t+1]. Returns, for the ``rows`` positions from
    ``n_prompt - 1`` on (where the head is applied: ``n_prompt`` and
    ``n_valid`` are values, so one program reads every request of a length),
    the largest reference logit less the reference logit of the served token,
    0 beyond the served range, and the logits' std; and every layer's pick
    margin at every position ``[L, T]`` (:func:`_experts`)."""
    h, ties = hidden(params, ids, arch, skip, handed=n_prompt)
    at = lambda x: jax.lax.dynamic_slice_in_dim(x, n_prompt - 1, rows, 0)  # noqa: E731
    gap, std = head_gaps(params["embed"], at(h), at(jnp.roll(ids, -1)), arch.vocab)
    return jnp.where(n_prompt - 1 + jnp.arange(rows) < n_valid - 1, gap, 0.0), std, ties
