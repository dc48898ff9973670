"""Plain float32 reference of one chip's share of a ``qwen3_next`` model
(Qwen3-Next-80B-A3B), written from these equations; nothing is imported from
``deepspeed_tpu`` (the blocked head is ``reference_phi4flash.head_gaps``, another
reference's). No cache, no kernel, no batching, no chunked form: the
delta rule runs TOKEN BY TOKEN (it is what the kernels are held to); every
matrix product runs at ``highest`` precision. The record of the published
modelling code is ISSUE 52's (there was no network): ``transformers``
``models/qwen3_next/modeling_qwen3_next.py``; Gated Delta Networks,
arXiv:2412.06464.

One token stream, positions t, L layers in periods of ``interval`` (4): layer i
is a Gated DeltaNet layer unless ``(i + 1) % interval == 0``, then a gated
softmax-attention layer; ``x = E[ids]``; ``norm(x; w) = x / sqrt(mean(x^2) +
eps) * (1 + w)``. Layer l, ``u = norm(x; w_in)``:

    Gated DeltaNet (Hk key heads, Hv value heads, r = Hv / Hk, dk, dv, K taps):
      [q | k | v | z] = u Wqkvz;  [b | a] = u Wba
      c_t = silu(sum_j w_conv[:, j] . m_{t-K+1+j}),  m = [q | k | v], m_{<0} = 0, no bias
      beta = sigmoid(b);  g = -exp(A_log) . softplus(a + dt_bias)
      q <- q / sqrt(sum q^2 + 1e-6) / sqrt(dk);  k <- k / sqrt(sum k^2 + 1e-6)      per head
      value head h reads key head h // r;  S_h [dk, dv] = 0 at the start:
          S <- exp(g_t) S;  d = beta_t (v_t - S^T k_t);  S <- S + k_t d^T;  o_t = S^T q_t
      y = (o / sqrt(mean(o^2) + eps) * w_o) . silu(z)  per head, w_o a plain gain;  out = y Wout
    gated attention (H heads, Hkv kv heads, D lanes):
      q = u Wq, gate = u Wg as H heads of D;  [k | v] = u Wkv as Hkv heads of D each
      q <- norm(q; w_qn), k <- norm(k; w_kn) over D;  rotary on the first R lanes (half-split), theta
      o = softmax(q k^T / sqrt(D), causal) v, head h reads kv head h // (H / Hkv);  out = (o . sigmoid(gate)) Wo
    x <- x + out;  w = norm(x; w_post)
    p = softmax(w Wr) over ALL published experts;  idx = top-k of p;  weights = p[idx] / sum p[idx]
    x <- x + sum_{e in idx, e held} weights_e FFN_e(w) + sigmoid(w . w_sg) FFN_shared(w)
    logits = norm(x_L; w_f) Whead

The share: the experts this chip holds (``held`` of them from ``first_held``
on) give their part, the shared expert is added once, what the absent experts
would add is left out (here as in the program), and the vocabulary is the
slice held. What the published config does not say and this reference assumes
is listed in the configuration file under ``assumed``.

The only thing taken from the system is the *layout* of its parameter tree
(``lin.w_qkvz`` = [q | k | v | z] by columns, ``lin.w_ba`` = [b | a],
``attn.wkv`` = [k | v], ``moe.experts`` stacked on a leading axis), so the same
seeded weights feed both. Weights arrive in the type the system holds them and
are cast to float32 where they are used, a layer's leaves and an expert at a
time; attention runs in blocks of query rows and the vocabulary is taken in
blocks (``head_gaps``), so that 1 300 positions at the published widths
fit beside a served model.

``skip`` is for the controls only (each must read as NOT correct;
:data:`SKIPS`): ``state_bf16`` keeps the state in bfloat16 (the nearest
precision below), ``no_delta`` leaves the delta term out (``d = beta v``: gated
linear attention without the correction), ``no_decay`` ignores the decay,
``state_edge`` / ``conv_edge`` drop the state / the convolution's rows wherever
one served call hands them to another (every 256th position: a chunk boundary;
and the first row a decode step computes, ``handed``), ``no_out_gate`` /
``no_attn_gate`` leave a DeltaNet layer's / an attention layer's output gate
out, ``no_shared_gate`` the shared expert's.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from perfbench.reference_phi4flash import head_gaps  # noqa: F401  (a head over rows of the vocabulary, a block of them at a time)

_HI = jax.lax.Precision.HIGHEST
ROW_BLOCK = 256     # query rows attended at a time
CHUNK = 256         # where the ``*_edge`` controls cut

SKIPS = ("state_bf16", "no_delta", "no_decay", "state_edge", "conv_edge", "no_out_gate", "no_attn_gate", "no_shared_gate")


class Arch(NamedTuple):
    """The numbers of the configuration the equations need (hashable: a
    static argument of the jitted functions)."""
    n_layer: int
    interval: int
    n_head: int
    n_kv_head: int
    head_dim: int
    rotary_dim: int
    theta: float
    key_heads: int
    value_heads: int
    dk: int
    dv: int
    taps: int
    n_experts: int                # published: the router's width
    held: int                     # routed experts held here ...
    first_held: int               # ... from this one on
    top_k: int
    norm_topk: bool
    eps: float
    vocab: int

    @classmethod
    def from_config(cls, c: dict) -> "Arch":
        share = c.get("expert_share", {"chips": 1, "index": 0})
        return cls(
            n_layer=int(c["num_hidden_layers"]), interval=int(c["full_attention_interval"]),
            n_head=int(c["num_attention_heads"]), n_kv_head=int(c["num_key_value_heads"]), head_dim=int(c["head_dim"]),
            rotary_dim=int(int(c["head_dim"]) * float(c["partial_rotary_factor"])), theta=float(c["rope_theta"]),
            key_heads=int(c["linear_num_key_heads"]), value_heads=int(c["linear_num_value_heads"]),
            dk=int(c["linear_key_head_dim"]), dv=int(c["linear_value_head_dim"]), taps=int(c["linear_conv_kernel_dim"]),
            n_experts=int(c.get("published", {}).get("num_experts", c["num_experts"])), held=int(c["num_experts"]),
            first_held=int(share["index"]) * int(c["num_experts"]), top_k=int(c["num_experts_per_tok"]),
            norm_topk=bool(c.get("norm_topk_prob", True)), eps=float(c["rms_norm_eps"]), vocab=int(c["vocab_size"]),
        )


def dot(a, b):
    """a [..., M, K] @ b [..., K, N] in float32 at full precision."""
    return jnp.matmul(a, b, precision=_HI)


def _f32(x):
    return x.astype(jnp.float32)


def _norm(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (1.0 + _f32(w))


def _silu(x):
    return x * jax.nn.sigmoid(x)


def _edges(S: int, handed):
    """``[S]`` bool: the positions whose call took its state from another
    call's hands (a chunk's first row; the first row a decode step computes)."""
    t = jnp.arange(S)
    return (t % CHUNK == 0) | (False if handed is None else t == handed)


def _delta_net(m, u, a: Arch, skip: str, handed):
    """One Gated DeltaNet layer over ``u [S, E]`` (normed) → ``[S, E]``."""
    S = u.shape[0]
    Hk, Hv, dk, dv, K = a.key_heads, a.value_heads, a.dk, a.dv, a.taps
    kw = Hk * dk
    p = dot(u, _f32(m["w_qkvz"]))
    rows, z = p[:, :2 * kw + Hv * dv], p[:, 2 * kw + Hv * dv:].reshape(S, Hv, dv)
    ba = dot(u, _f32(m["w_ba"]))
    beta = jax.nn.sigmoid(ba[:, :Hv])
    g = -jnp.exp(_f32(m["a_log"])) * jax.nn.softplus(ba[:, Hv:] + _f32(m["dt_bias"]))
    if skip == "no_decay":
        g = jnp.zeros_like(g)
    edge = _edges(S, handed)
    t = jnp.arange(S)
    since = t - jax.lax.cummax(jnp.where(edge, t, 0))          # rows since the last hand-over
    w = _f32(m["w_conv"])
    acc = jnp.zeros_like(rows)
    for j in range(K):
        back = K - 1 - j                                        # tap j meets the row ``back`` rows before
        src = jnp.pad(rows, ((back, 0), (0, 0)))[:S]
        if skip == "conv_edge":
            src = jnp.where((back <= since)[:, None], src, 0.0)
        acc = acc + w[:, j] * src
    c = _silu(acc)
    unit = lambda x: x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)  # noqa: E731
    q = jnp.repeat(unit(c[:, :kw].reshape(S, Hk, dk)) / math.sqrt(dk), Hv // Hk, axis=1)
    k = jnp.repeat(unit(c[:, kw:2 * kw].reshape(S, Hk, dk)), Hv // Hk, axis=1)
    v = c[:, 2 * kw:].reshape(S, Hv, dv)

    def token(St, row):
        qt, kt, vt, gt, bt, et = row
        if skip == "state_edge":
            St = jnp.where(et, 0.0, St)
        St = St * jnp.exp(gt)[:, None, None]
        d = bt[:, None] * (vt if skip == "no_delta" else vt - jnp.einsum("hk,hkv->hv", kt, St, precision=_HI))
        St = St + kt[:, :, None] * d[:, None, :]
        if skip == "state_bf16":     # (a cast there and back is one the compiler may drop: excess precision is allowed)
            St = jax.lax.reduce_precision(St, exponent_bits=8, mantissa_bits=7)
        return St, jnp.einsum("hk,hkv->hv", qt, St, precision=_HI)

    _, o = jax.lax.scan(token, jnp.zeros((Hv, dk, dv), jnp.float32), (q, k, v, g, beta, edge))
    y = o / jnp.sqrt(jnp.mean(o * o, axis=-1, keepdims=True) + a.eps) * _f32(m["norm_o"])
    if skip != "no_out_gate":
        y = y * _silu(z)
    return dot(y.reshape(S, Hv * dv), _f32(m["w_out"]))


def _rope(x, a: Arch):
    """x [S, heads, D] at positions 0..S-1: half-split pairs over the first
    ``rotary_dim`` lanes, the others as they are."""
    S, R = x.shape[0], a.rotary_dim
    inv = a.theta ** (-jnp.arange(0, R, 2, dtype=jnp.float32) / R)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None, None] * inv
    x1, x2 = x[..., : R // 2], x[..., R // 2: R]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang), x2 * jnp.cos(ang) + x1 * jnp.sin(ang), x[..., R:]], axis=-1)


def _attention(m, u, a: Arch, skip: str):
    """One gated attention layer over ``u [S, E]`` (normed) → ``[S, E]``."""
    S = u.shape[0]
    H, KV, D = a.n_head, a.n_kv_head, a.head_dim
    q = _rope(_norm(dot(u, _f32(m["wq"])).reshape(S, H, D), m["q_norm"], a.eps), a)
    kv = dot(u, _f32(m["wkv"]))
    k = _rope(_norm(kv[:, :KV * D].reshape(S, KV, D), m["k_norm"], a.eps), a)
    k, v = jnp.repeat(k, H // KV, axis=1), jnp.repeat(kv[:, KV * D:].reshape(S, KV, D), H // KV, axis=1)
    kt, vt = k.transpose(1, 2, 0), v.transpose(1, 0, 2)
    outs = []
    for r0 in range(0, S, ROW_BLOCK):
        rows = min(ROW_BLOCK, S - r0)
        s = dot(q[r0: r0 + rows].transpose(1, 0, 2), kt) / math.sqrt(D)         # [H, rows, S]
        seen = jnp.arange(S)[None, :] <= (r0 + jnp.arange(rows))[:, None]
        pr = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        outs.append(dot(pr, vt).transpose(1, 0, 2).reshape(rows, H * D))
    o = jnp.concatenate(outs, axis=0)
    if skip != "no_attn_gate":
        o = o * jax.nn.sigmoid(dot(u, _f32(m["wg"])))
    return dot(o, _f32(m["wo"]))


def _ffn(w, e):
    return dot(_silu(dot(w, _f32(e["w_gate"]))) * dot(w, _f32(e["w_up"])), _f32(e["w_down"]))


def _experts(m, w, a: Arch, skip: str):
    """→ (the held experts' weighted outputs and the gated shared expert's ``[S,
    E]``; how near the router's pick was to falling the other way WHERE THAT
    MOVES THIS CHIP'S PART ``[S]``: the k-th largest argument of the softmax
    less the next, infinite where neither of the two is a held expert)."""
    s = dot(w, _f32(m["router"]))
    p = jax.nn.softmax(s, axis=-1)
    top, idx = jax.lax.top_k(s, a.top_k + 1)
    mine = lambda e: (e >= a.first_held) & (e < a.first_held + a.held)  # noqa: E731
    tie = jnp.where(mine(idx[:, -2]) | mine(idx[:, -1]), top[:, -2] - top[:, -1], jnp.inf)
    sel = idx[:, :-1]
    picked = jnp.take_along_axis(p, sel, axis=-1)
    wt = picked / jnp.sum(picked, axis=-1, keepdims=True) if a.norm_topk else picked
    shared = _ffn(w, m["shared"])
    if skip != "no_shared_gate":
        shared = jax.nn.sigmoid(dot(w, _f32(m["shared_gate"]))) * shared

    def one(acc, xs):
        e, we = xs                                                        # the expert's published index, its weights
        return acc + jnp.sum(jnp.where(sel == e, wt, 0.0), axis=-1)[:, None] * _ffn(w, we), None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(w), (a.first_held + jnp.arange(a.held), m["experts"]))
    return shared + routed, tie


def hidden(params, ids, a: Arch, skip: str = "", handed=None):
    """ids [S] -> (final hidden states [S, E] in float32, normed; every
    layer's pick margin [L, S]: see :func:`_experts`)."""
    x = _f32(params["embed"][ids])
    ties = []
    for l in range(a.n_layer):
        lp = params["layers"][l]
        u = _norm(x, lp["norm_1"], a.eps)
        x = x + (_attention(lp["attn"], u, a, skip) if (l + 1) % a.interval == 0 else _delta_net(lp["lin"], u, a, skip, handed))
        m, tie = _experts(lp["moe"], _norm(x, lp["norm_2"], a.eps), a, skip)
        ties.append(tie)
        x = x + m
    return _norm(x, params["norm_f"], a.eps), jnp.stack(ties)


def logits(params, ids, a: Arch, skip: str = ""):
    """Whole logits [S, vocab] (small sizes: the tests)."""
    return dot(hidden(params, ids, a, skip)[0], _f32(params["head"]))[:, : a.vocab]


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
    gap, std = head_gaps(params["head"].T, at(h), at(jnp.roll(ids, -1)), arch.vocab)     # the untied head as rows of the vocabulary
    return jnp.where(n_prompt - 1 + jnp.arange(rows) < n_valid - 1, gap, 0.0), std, ties
