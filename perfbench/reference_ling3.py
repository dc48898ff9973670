"""Plain float32 reference of one chip's share of a ``bailing_hybrid`` model
(Ling-3.0-flash, the language model of Ling-3.0-flash-VL), written from these
equations; nothing is imported from ``deepspeed_tpu``. No cache, no kernel, no
absorption, no batching, no chunked form: the delta rule runs TOKEN BY TOKEN
(it is what the kernels are held to) and the latent attention per head; every
matrix product runs at ``highest`` precision. What is the same equation as
another reference's is taken from it: the interleaved rotary, the gated FFN and
the plain RMS norm from ``reference_mistral4.py``, the blocked head from
``reference_phi4flash.head_gaps``.

One token stream, positions t, L layers in periods of ``group`` (6): layer i is
a KDA layer (Kimi Delta Attention, arXiv:2510.26692) unless ``(i + 1) % group
== 0``, then a latent (MLA) attention layer; ``x = E[ids]``; ``norm(x; w) = x /
sqrt(mean(x^2) + eps) * w``. Layer l, ``u = norm(x; w_1)``, H heads, ``dk = dv``,
K taps:

    KDA:
      [q | k | v | z] = u Wqkvg;  [f | b] = u Wfb           q, k, v, z, f [H, dk]; b [H]
      c_t = silu(sum_j w_conv[:, j] . m_{t-K+1+j}),  m = [q | k | v], m_{<0} = 0, no bias
      q <- q / sqrt(sum q^2 + 1e-6) / sqrt(dk);  k <- k / sqrt(sum k^2 + 1e-6)      per head
      beta = sigmoid(b);  g = lower_bound * sigmoid(exp(A_log_h) * (f + dt_bias))    [H, dk], -5 < g < 0
      S_h [dk, dv] = 0 at the start:
          S <- diag(exp(g_t)) S;  d = beta_t (v_t - S^T k_t);  S <- S + k_t d^T;  o_t = S^T q_t
      y = (o / sqrt(mean(o^2) + eps) * w_o) . sigmoid(z)     per head;  out = y Wout
    MLA (nope N, rope R, values V, latent C):
      q = u Wq [H, N + R];  [c | kr] = u Wkv_a;  c <- norm(c; w_kv)
      k_h = [c W_uk_h | rot(kr)],  v_h = c W_uv_h;  interleaved rotary on the R lanes, theta, no scaling
      o_h = softmax(q_h k_h^T / sqrt(N + R), causal) v_h
      out = concat_h(o_h * sigmoid(u w_gate)_h) Wo
    x <- x + out;  w = norm(x; w_2)
    l < first_dense:  x <- x + FFN(w)
    else:  s = sigmoid(w Wr) over ALL published experts;  s' = s + b
           group score_j = the sum of the top 2 of s' in group j's columns (n_group equal runs)
           keep the topk_group groups of largest score;  sel = top-k of s' over their columns
           w_e = scale * s_e / sum_{sel} s
           x <- x + sum_{e in sel, e held} w_e FFN_e(w) + FFN_shared(w)
    logits = norm(x_L; w_f) Whead

The share: the experts this chip holds (``held`` of them from ``first_held``
on) give their part, the shared expert is added once, what the absent experts
would add is left out (here as in the program), and the vocabulary is the
slice held. What the published config does not say and this reference assumes
is listed in the configuration file under ``assumed``.

The only thing taken from the system is the *layout* of its parameter tree
(``lin.w_qkvg`` = [q | k | v | z] by columns, ``lin.w_fb`` = [f | b],
``attn`` as ``models/mla.py`` names its leaves with ``wq`` one matrix and
``w_gate``, ``moe.experts`` stacked on a leading axis), so the same seeded
weights feed both. Weights arrive in the type the system holds them and are
cast to float32 where they are used; attention runs in blocks of query rows
and the vocabulary is taken in blocks (``head_gaps``), so that 1 300 positions
at the published widths fit beside a served model.

``skip`` is for the controls only (each must read as NOT correct, or is
written down as a reading; :data:`SKIPS`): ``state_bf16`` keeps the state in
bfloat16 (the nearest precision below), ``scalar_decay`` takes ONE decay a head
(the mean over its channels: Qwen3-Next's rule under this model's name),
``no_bound`` takes ``g = -softplus(.)`` in place of the bounded gate,
``beta_1`` sets beta to 1, ``no_conv`` leaves the convolution out (the silu
stays), ``state_edge`` / ``conv_edge`` drop the state / the convolution's rows
wherever one served call hands them to another, ``no_group_limit`` picks over
all columns, ``group_top1`` scores a group by its largest entry alone,
``bias_in_weights`` forms the weights from ``s + b``, ``scale_1`` takes
``routed_scaling_factor`` 1, ``no_head_gate`` leaves the attention's head-wise
gate out, ``rope_score`` leaves the rotary part out of the score,
``experts:<l>`` drops layer l's routed part.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from perfbench.reference_mistral4 import _f32, _ffn, _rms, _rope, dot_f32
from perfbench.reference_phi4flash import head_gaps  # noqa: F401  (a head over rows of the vocabulary, a block of them at a time)

_HI = jax.lax.Precision.HIGHEST
ROW_BLOCK = 256     # query rows attended at a time
CHUNK = 256         # where the ``*_edge`` controls cut

SKIPS = ("state_bf16", "scalar_decay", "no_bound", "beta_1", "no_conv", "state_edge", "conv_edge", "no_group_limit",
         "group_top1", "bias_in_weights", "scale_1", "no_head_gate", "rope_score")


class Arch(NamedTuple):
    """The numbers of the configuration the equations need (hashable: a
    static argument of the jitted functions)."""
    n_layer: int
    group: int                    # layer_group_size: the period
    first_dense: int
    n_head: int
    d: int                        # a KDA head's dk = dv
    taps: int
    lower_bound: float
    kv_rank: int
    nope: int
    rope: int
    v_dim: int
    theta: float
    n_experts: int                # published: the router's width
    held: int                     # routed experts held here ...
    first_held: int               # ... from this one on
    top_k: int
    n_group: int
    topk_group: int
    scale: float
    norm_topk: bool
    eps: float
    vocab: int

    @classmethod
    def from_config(cls, c: dict) -> "Arch":
        share = c.get("expert_share", {"chips": 1, "index": 0})
        return cls(
            n_layer=int(c["num_hidden_layers"]), group=int(c["layer_group_size"]), first_dense=int(c["first_k_dense_replace"]),
            n_head=int(c["num_attention_heads"]), d=int(c["head_dim"]), taps=int(c["short_conv_kernel_size"]),
            lower_bound=float(c["kda_lower_bound"]), kv_rank=int(c["kv_lora_rank"]), nope=int(c["qk_nope_head_dim"]),
            rope=int(c["qk_rope_head_dim"]), v_dim=int(c["v_head_dim"]), theta=float(c["rope_theta"]),
            n_experts=int(c.get("published", {}).get("num_experts", c["num_experts"])), held=int(c["num_experts"]),
            first_held=int(share["index"]) * int(c["num_experts"]), top_k=int(c["num_experts_per_tok"]),
            n_group=int(c["n_group"]), topk_group=int(c["topk_group"]), scale=float(c["routed_scaling_factor"]),
            norm_topk=bool(c.get("norm_topk_prob", True)), eps=float(c["rms_norm_eps"]), vocab=int(c["vocab_size"]),
        )


def _silu(x):
    return x * jax.nn.sigmoid(x)


def _edges(S: int, handed):
    """``[S]`` bool: the positions whose call took its state from another
    call's hands (a chunk's first row; the first row a decode step computes)."""
    t = jnp.arange(S)
    return (t % CHUNK == 0) | (False if handed is None else t == handed)


def _kda(m, u, a: Arch, skip: str, handed, dot):
    """One KDA layer over ``u [S, E]`` (normed) → ``[S, E]``."""
    S = u.shape[0]
    H, d, K = a.n_head, a.d, a.taps
    W = H * d
    p = dot(u, _f32(m["w_qkvg"]))
    rows, z = p[:, :3 * W], p[:, 3 * W:].reshape(S, H, d)
    fb = dot(u, _f32(m["w_fb"]))
    beta = jnp.ones((S, H)) if skip == "beta_1" else jax.nn.sigmoid(fb[:, W:])
    arg = jnp.exp(_f32(m["a_log"]))[:, None] * (fb[:, :W] + _f32(m["dt_bias"])).reshape(S, H, d)
    g = -jax.nn.softplus(arg) if skip == "no_bound" else a.lower_bound * jax.nn.sigmoid(arg)
    if skip == "scalar_decay":
        g = jnp.broadcast_to(jnp.mean(g, axis=-1, keepdims=True), g.shape)
    edge = _edges(S, handed)
    t = jnp.arange(S)
    since = t - jax.lax.cummax(jnp.where(edge, t, 0))          # rows since the last hand-over
    w = _f32(m["w_conv"])
    if skip == "no_conv":
        acc = rows
    else:
        acc = jnp.zeros_like(rows)
        for j in range(K):
            back = K - 1 - j                                    # tap j meets the row ``back`` rows before
            src = jnp.pad(rows, ((back, 0), (0, 0)))[:S]
            if skip == "conv_edge":
                src = jnp.where((back <= since)[:, None], src, 0.0)
            acc = acc + w[:, j] * src
    c = _silu(acc)
    unit = lambda x: x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)  # noqa: E731
    q = unit(c[:, :W].reshape(S, H, d)) / math.sqrt(d)
    k = unit(c[:, W:2 * W].reshape(S, H, d))
    v = c[:, 2 * W:].reshape(S, H, d)

    def token(St, row):
        qt, kt, vt, gt, bt, et = row
        if skip == "state_edge":
            St = jnp.where(et, 0.0, St)
        St = St * jnp.exp(gt)[:, :, None]                       # a decay a key channel: a row of S
        d_ = bt[:, None] * (vt - jnp.einsum("hk,hkv->hv", kt, St, precision=_HI))
        St = St + kt[:, :, None] * d_[:, None, :]
        if skip == "state_bf16":     # (a cast there and back is one the compiler may drop: excess precision is allowed)
            St = jax.lax.reduce_precision(St, exponent_bits=8, mantissa_bits=7)
        return St, jnp.einsum("hk,hkv->hv", qt, St, precision=_HI)

    _, o = jax.lax.scan(token, jnp.zeros((H, d, d), jnp.float32), (q, k, v, g, beta, edge))
    y = o / jnp.sqrt(jnp.mean(o * o, axis=-1, keepdims=True) + a.eps) * _f32(m["norm_o"]) * jax.nn.sigmoid(z)
    return dot(y.reshape(S, W), _f32(m["w_out"]))


def _attention(m, u, a: Arch, skip: str, dot):
    """One latent attention layer over ``u [S, E]`` (normed), per head → ``[S, E]``."""
    S = u.shape[0]
    H, N, R, V, C = a.n_head, a.nope, a.rope, a.v_dim, a.kv_rank
    freq = a.theta ** (-2.0 * jnp.arange(R // 2, dtype=jnp.float32) / R)
    q = dot(u, _f32(m["wq"])).reshape(S, H, N + R)
    kv = dot(u, _f32(m["wkv_a"]))
    c = _rms(kv[:, :C], m["kv_norm"], a.eps)
    kr = _rope(kv[:, None, C:], freq)[:, 0]                                   # [S, R]
    q_rope = jnp.zeros((S, H, R)) if skip == "rope_score" else _rope(q[..., N:], freq)
    k_nope = dot(c, _f32(m["w_uk"]).reshape(C, H * N)).reshape(S, H, N)
    v = dot(c, _f32(m["w_uv"]).reshape(C, H * V)).reshape(S, H, V)
    qh = jnp.concatenate([q[..., :N], q_rope], -1).transpose(1, 0, 2)         # [H, S, N + R]
    kh = jnp.concatenate([k_nope, jnp.broadcast_to(kr[:, None, :], (S, H, R))], -1).transpose(1, 2, 0)
    vh = v.transpose(1, 0, 2)
    blk = math.gcd(S, ROW_BLOCK)

    def rows(i):
        qi = jax.lax.dynamic_slice_in_dim(qh, i * blk, blk, 1)
        s = dot(qi, kh) / math.sqrt(N + R)                                    # [H, blk, S]
        seen = jnp.arange(S)[None, :] <= (i * blk + jnp.arange(blk))[:, None]
        return dot(jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1), vh)   # [H, blk, V]

    o = jax.lax.map(rows, jnp.arange(S // blk)).transpose(0, 2, 1, 3).reshape(S, H, V)
    if skip != "no_head_gate":
        o = o * jax.nn.sigmoid(dot(u, _f32(m["w_gate"])))[:, :, None]
    return dot(o.reshape(S, H * V), _f32(m["wo"]))


def _select(sb, a: Arch, skip: str):
    """``sb [S, n_experts]`` (``s + b``) → (the picks ``[S, k]``; how near the
    selection was to falling the other way WHERE THAT MOVES THIS CHIP'S PART
    ``[S]``: the smaller of the pick's margin, the k-th largest kept entry less
    the next where one of the two is a held expert, and the groups' margin,
    the last kept group's score less the next's where one of the two is a
    group held here; infinite where neither)."""
    S, N = sb.shape
    size = N // a.n_group
    mine = lambda e: (e >= a.first_held) & (e < a.first_held + a.held)  # noqa: E731
    gtie = jnp.full((S,), jnp.inf)
    if a.n_group > 1 and skip != "no_group_limit":
        per = sb.reshape(S, a.n_group, size)
        score = jnp.max(per, axis=-1) if skip == "group_top1" else jnp.sum(jax.lax.top_k(per, 2)[0], axis=-1)
        top, kept = jax.lax.top_k(score, min(a.topk_group + 1, a.n_group))
        keep = jnp.any(kept[:, :a.topk_group, None] == jnp.arange(a.n_group)[None, None, :], axis=1)    # [S, n_group]
        sb = jnp.where(jnp.repeat(keep, size, axis=1), sb, -jnp.inf)
        if a.topk_group < a.n_group:
            held_group = lambda j: (j * size < a.first_held + a.held) & ((j + 1) * size > a.first_held)  # noqa: E731
            gtie = jnp.where(held_group(kept[:, -2]) | held_group(kept[:, -1]), top[:, -2] - top[:, -1], jnp.inf)
    top, idx = jax.lax.top_k(sb, a.top_k + 1)
    tie = jnp.where(mine(idx[:, -2]) | mine(idx[:, -1]), top[:, -2] - top[:, -1], jnp.inf)
    return idx[:, :-1], jnp.minimum(tie, gtie)


def _experts(m, w, a: Arch, skip: str, routed: bool, dot):
    """→ (the held experts' weighted outputs and the shared expert's ``[S,
    E]``; the selection's margin ``[S]``: :func:`_select`)."""
    s = jax.nn.sigmoid(dot_f32(w, _f32(m["router"])))
    sb = s + _f32(m["bias"])
    sel, tie = _select(sb, a, skip)
    picked = jnp.take_along_axis(sb if skip == "bias_in_weights" else s, sel, axis=-1)
    wt = (1.0 if skip == "scale_1" else a.scale) * (picked / jnp.sum(picked, axis=-1, keepdims=True) if a.norm_topk else picked)
    out = _ffn(w, m["shared"], dot)
    if not routed:
        return out, tie

    def one(acc, xs):
        e, we = xs                                                        # the expert's published index, its weights
        return acc + jnp.sum(jnp.where(sel == e, wt, 0.0), axis=-1)[:, None] * _ffn(w, we, dot), None

    part, _ = jax.lax.scan(one, jnp.zeros_like(w), (a.first_held + jnp.arange(a.held), m["experts"]))
    return out + part, tie


def hidden(params, ids, a: Arch, skip: str = "", handed=None, dot=dot_f32):
    """ids [S] -> (final hidden states [S, E] in float32, normed; every expert
    layer's selection margin [Le, S]: see :func:`_select`)."""
    x = _f32(params["embed"][ids])
    ties = []
    for l in range(a.n_layer):
        lp = params["layers"][l]
        u = _rms(x, lp["norm_1"], a.eps)
        x = x + (_attention(lp["attn"], u, a, skip, dot) if (l + 1) % a.group == 0 else _kda(lp["lin"], u, a, skip, handed, dot))
        w = _rms(x, lp["norm_2"], a.eps)
        if l < a.first_dense:
            x = x + _ffn(w, lp["ffn"], dot)
            continue
        m, tie = _experts(lp["moe"], w, a, skip, skip != f"experts:{l}", dot)
        ties.append(tie)
        x = x + m
    return _rms(x, params["norm_f"], a.eps), jnp.stack(ties)


def logits(params, ids, a: Arch, skip: str = "", dot=dot_f32):
    """Whole logits [S, vocab] (small sizes: the tests)."""
    return dot(hidden(params, ids, a, skip, dot=dot)[0], _f32(params["head"]))[:, : a.vocab]


@functools.partial(jax.jit, static_argnames=("arch", "skip", "rows"))
def served_gaps(params, ids, n_prompt, n_valid, *, arch: Arch, rows: int, skip: str = ""):
    """Teacher-forced check of one served request, in ``reference_qwen3_next``'s
    form. ``ids`` [T] is the prompt followed by the served tokens, padded
    (``T >= n_prompt - 1 + rows``); position t >= n_prompt-1 predicts the
    served token ids[t+1]. Returns, for the ``rows`` positions from
    ``n_prompt - 1`` on (where the head is applied: ``n_prompt`` and
    ``n_valid`` are values, so one program reads every request of a length),
    the largest reference logit less the reference logit of the served token,
    0 beyond the served range, and the logits' std; and every expert layer's
    selection margin at every position ``[Le, T]`` (:func:`_select`)."""
    h, ties = hidden(params, ids, arch, skip, handed=n_prompt)
    at = lambda x: jax.lax.dynamic_slice_in_dim(x, n_prompt - 1, rows, 0)  # noqa: E731
    gap, std = head_gaps(params["head"].T, at(h), at(jnp.roll(ids, -1)), arch.vocab)     # the untied head as rows of the vocabulary
    return jnp.where(n_prompt - 1 + jnp.arange(rows) < n_valid - 1, gap, 0.0), std, ties
