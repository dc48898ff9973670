"""Plain float32 reference of a ``phi4flash`` model (Phi-4-mini-flash-reasoning),
written from these equations; nothing is imported from ``deepspeed_tpu``. No
cache, no kernel, no batching; every matrix product runs at ``highest``
precision; the recurrence is a sequential loop over the tokens; differential
attention is the published four softmax-value products a pair of heads (the
program serves zero-padded pair heads over one grouped-query kernel, so the
comparison also checks that identity).

One token stream, positions t, L layers, i from 0. ``x = E[ids]``; no
positional encoding anywhere.

    a = x + mixer_i(LN1(x));  x = a + W_down(silu(g) . v),  [g | v] = W_gate_up LN2(a)
    LN: LayerNorm with gain and bias, eps;  logits = LN_f(x) E^T (tied, no bias)

    mixer_i, half = L / 2:
      Mamba, even i <= half:   [xs | z] = W_in u
            c_t = silu(b_conv + sum_{k<K} w_conv[:, k] . xs_{t-K+1+k})     zeros before the sequence
            [delta | B_t | C_t] = W_x c_t;  dt_t = softplus(W_dt delta + b_dt);  A = -exp(A_log)
            h_t = exp(dt_t (x) A) . h_{t-1} + (dt_t . c_t) (x) B_t,  h_{-1} = 0     h [d_inner, N]
            s_t = h_t C_t + D . c_t;  out = W_out(s_t . silu(z_t))
            layer ``half`` hands s_t (BEFORE the gate) on as the memory m_t
      differential attention, odd i < half over the keys t-window+1 .. t, i = half + 1 over every key <= t:
            [q | k | v] = W_qkv u + b;  query heads pair as (2p, 2p+1) -> q1_p, q2_p; kv heads
            likewise -> k1_j, k2_j, v1_j, v2_j;  query pair p reads kv pair j = p // (H / KV)
            P1 = softmax(q1 k1^T / sqrt(D)),  P2 = softmax(q2 k2^T / sqrt(D))
            o_p = [P1 v1 | P1 v2] - lambda_i [P2 v1 | P2 v2]
            lambda_i = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda0_i,  lambda0_i = 0.8 - 0.6 exp(-0.3 i)
            o_p <- (1 - lambda0_i) RMSNorm_2D(o_p) (gain, eps);  out = W_o o + b_o
      gated memory unit, even i >= half + 2:   W_out(silu(W_in u) . m_t)
      cross-attention, odd i >= half + 3:      q = W_q u + b; K and V are layer half + 1's; differential
            as above with this layer's own lambda vectors, norm gain and lambda0_i

What the published config does not say and this reference assumes is listed in
the configuration file under ``assumed``. Departures from the published
description, each noted at its line below: none known to the builder (there
was no network to read ``modeling_phi4flash.py``; the issue's record of it is
what is written here).

The only thing taken from the system is the *layout* of its parameter tree, so
the same seeded weights feed both. Weights arrive in the type the system holds
them and are cast to float32 where they are used, a layer's leaves at a time;
the vocabulary is taken in blocks (:func:`head_gaps`). Attention runs in
blocks of query rows.

``skip`` is for the controls only (each must read as NOT correct):
``no_state`` leaves the recurrence out (``s_t = D . c_t``: what a dropped scan
state reads), ``state_bf16`` rounds the scan state to bfloat16 after every token,
``state_dirty`` starts every Mamba layer from the state and convolution rows
the sequence itself ends with (a slot that was not zeroed at admission),
``lambda`` leaves the lambda term out, ``subln`` the pair norm, ``mem_gated``
takes the memory after the gate, ``no_d`` leaves ``D . c`` out, ``window``
lets a window layer read every key, ``conv_edge`` drops the convolution's
carried rows at every 256th position (a chunk edge), ``cross_own`` lets a cross
layer attend layer half + 1's projections of ITS OWN input.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST
ROW_BLOCK = 256     # query rows attended at a time
VOCAB_BLOCKS = 12   # the head is applied to this many slices of the vocabulary in turn
CHUNK = 256         # where ``conv_edge`` cuts

SKIPS = ("no_state", "state_bf16", "state_dirty", "lambda", "subln", "mem_gated", "no_d", "window", "conv_edge", "cross_own")


class Arch(NamedTuple):
    """The numbers of the configuration the equations need (hashable: a
    static argument of the jitted functions)."""
    n_layer: int
    hidden: int
    n_head: int
    n_kv_head: int
    window: int
    d_inner: int
    d_state: int
    d_conv: int
    dt_rank: int
    eps: float
    vocab: int

    @classmethod
    def from_config(cls, c: dict) -> "Arch":
        hidden = int(c["hidden_size"])
        return cls(
            n_layer=int(c["num_hidden_layers"]), hidden=hidden, n_head=int(c["num_attention_heads"]),
            n_kv_head=int(c["num_key_value_heads"]), window=int(c["sliding_window"]),
            d_inner=int(c.get("mamba_expand", 2)) * hidden, d_state=int(c.get("mamba_d_state", 16)),
            d_conv=int(c.get("mamba_d_conv", 4)), dt_rank=-(-hidden // 16),
            eps=float(c["layer_norm_eps"]), vocab=int(c["vocab_size"]),
        )


def dot_f32(a, b):
    """a [..., M, K] @ b [..., K, N] in float32 at full precision."""
    return jnp.matmul(a, b, precision=_HI)


def _f32(x):
    return x.astype(jnp.float32)


def _ln(x, n, eps):
    c = x - jnp.mean(x, axis=-1, keepdims=True)
    return c / jnp.sqrt(jnp.mean(c * c, axis=-1, keepdims=True) + eps) * _f32(n["g"]) + _f32(n["b"])


def _silu(x):
    return x * jax.nn.sigmoid(x)


def kind(i: int, n_layer: int) -> str:
    half = n_layer // 2
    if i % 2 == 0:
        return "ssm" if i <= half else "gmu"
    return "attn" if i <= half + 1 else "cross"


def _mamba(m, u, a: Arch, skip: str, dot):
    """→ (the mixer's output [S, E], the memory [S, d_inner])."""
    S, N, K, R = u.shape[0], a.d_state, a.d_conv, a.dt_rank
    xz = dot(u, _f32(m["w_in"]))
    xs, z = xz[:, : a.d_inner], xz[:, a.d_inner:]
    w, A, D = _f32(m["w_conv"]), -jnp.exp(_f32(m["a_log"])), _f32(m["d"])

    def conv(before):
        """The causal depthwise convolution behind the K - 1 rows ``before``."""
        full = jnp.concatenate([before, xs])
        taps = [full[k: k + S] for k in range(K)]                        # taps[k][t] = xs_{t-K+1+k}
        if skip == "conv_edge":   # a chunk's first rows see zeros where the chunk before ended
            t = jnp.arange(S)[:, None]
            taps = [jnp.where((t % CHUNK) + k - (K - 1) < 0, 0.0, tap) for k, tap in enumerate(taps)]
        return _silu(_f32(m["b_conv"]) + sum(w[:, k] * taps[k] for k in range(K)))

    def scan(c, h):
        dbc = dot(c, _f32(m["w_x"]))
        dt = jax.nn.softplus(dot(dbc[:, :R], _f32(m["w_dt"])) + _f32(m["b_dt"]))
        Bm, Cm = dbc[:, R: R + N], dbc[:, R + N:]

        def token(h, row):                                                # the sequential loop over tokens
            c_t, dt_t, b_t, cc_t = row
            h = jnp.exp(dt_t[:, None] * A) * h + (dt_t * c_t)[:, None] * b_t[None, :]
            if skip == "state_bf16":   # not astype twice: the chip's compiler keeps the excess precision of such a pair
                h = jax.lax.reduce_precision(h, exponent_bits=8, mantissa_bits=7)
            y = 0.0 if skip == "no_state" else jnp.sum(h * cc_t[None, :], axis=-1)
            return h, y + (0.0 if skip == "no_d" else D * c_t)

        return jax.lax.scan(token, h, (c, dt, Bm, Cm))

    c = conv(jnp.zeros((K - 1, a.d_inner), jnp.float32))
    h, s = scan(c, jnp.zeros((a.d_inner, N), jnp.float32))
    if skip == "state_dirty":     # what a slot holds that another request just left
        c = conv(xs[S - (K - 1):])
        _, s = scan(c, h)
    gated = s * _silu(z)
    return dot(gated, _f32(m["w_out"])), (gated if skip == "mem_gated" else s)


def _diff_attention(w, q, k, v, lam0: float, window: int, a: Arch, skip: str, dot):
    """Differential attention of ``q [S, H, D]`` over ``k``, ``v [S, KV, D]``
    → ``[S, E]`` (before nothing: the output projection is in it)."""
    S, H, D = q.shape
    KV = k.shape[1]
    per = (H // 2) // (KV // 2)                                           # query pairs a kv pair
    qp = q.reshape(S, H // 2, 2, D).transpose(2, 1, 0, 3)                 # [2, pairs, S, D]
    kp = jnp.repeat(k.reshape(S, KV // 2, 2, D), per, axis=1).transpose(2, 1, 3, 0)   # [2, pairs, D, S]
    vp = jnp.repeat(v.reshape(S, KV // 2, 2, D), per, axis=1).transpose(2, 1, 0, 3)   # [2, pairs, S, D]
    lam = (jnp.exp(jnp.sum(_f32(w["lambda_q1"]) * _f32(w["lambda_k1"])))
           - jnp.exp(jnp.sum(_f32(w["lambda_q2"]) * _f32(w["lambda_k2"]))) + lam0)
    blk = math.gcd(S, ROW_BLOCK)

    def rows(i):
        t = i * blk + jnp.arange(blk)[:, None]
        j = jnp.arange(S)[None, :]
        seen = (j <= t) & ((j > t - window) if window and skip != "window" else True)

        def probs(n):
            sc = dot(jax.lax.dynamic_slice_in_dim(qp[n], i * blk, blk, 1), kp[n]) / math.sqrt(D)
            return jax.nn.softmax(jnp.where(seen[None], sc, -jnp.inf), axis=-1)   # [pairs, blk, S]

        p1, p2 = probs(0), probs(1)
        first = jnp.concatenate([dot(p1, vp[0]), dot(p1, vp[1])], axis=-1)        # P1 v1 | P1 v2
        second = jnp.concatenate([dot(p2, vp[0]), dot(p2, vp[1])], axis=-1)       # P2 v1 | P2 v2
        return first if skip == "lambda" else first - lam * second                # [pairs, blk, 2D]

    o = jax.lax.map(rows, jnp.arange(S // blk)).transpose(0, 2, 1, 3).reshape(S, H // 2, 2 * D)
    if skip != "subln":
        o = o / jnp.sqrt(jnp.mean(o * o, axis=-1, keepdims=True) + a.eps) * _f32(w["subln"])
    return dot(((1.0 - lam0) * o).reshape(S, H * D), _f32(w["wo"])) + _f32(w["bo"])


def hidden(params, ids, a: Arch, skip: str = "", dot=dot_f32):
    """ids [S] -> final hidden states [S, E] in float32, normed."""
    S, H, KV = ids.shape[0], a.n_head, a.n_kv_head
    D = a.hidden // H
    half = a.n_layer // 2
    x = _f32(params["embed"][ids])
    mem = kv = src = None
    for i in range(a.n_layer):
        lp = params["layers"][i]
        u = _ln(x, lp["norm_1"], a.eps)
        lam0 = 0.8 - 0.6 * math.exp(-0.3 * i)
        k_i = kind(i, a.n_layer)
        if k_i == "ssm":
            mix, s = _mamba(lp["ssm"], u, a, skip, dot)
            if i == half:
                mem = s
        elif k_i == "gmu":
            mix = dot(_silu(dot(u, _f32(lp["gmu"]["w_in"]))) * mem, _f32(lp["gmu"]["w_out"]))
        elif k_i == "attn":
            w = lp["attn"]
            qkv = dot(u, _f32(w["wqkv"])) + _f32(w["bqkv"])
            q = qkv[:, : H * D].reshape(S, H, D)
            k = qkv[:, H * D: (H + KV) * D].reshape(S, KV, D)
            v = qkv[:, (H + KV) * D:].reshape(S, KV, D)
            if i == half + 1:
                kv, src = (k, v), w
            mix = _diff_attention(w, q, k, v, lam0, a.window if i < half else 0, a, skip, dot)
        else:
            w = lp["cross"]
            q = (dot(u, _f32(w["wq"])) + _f32(w["bq"])).reshape(S, H, D)
            k, v = kv
            if skip == "cross_own":
                own = dot(u, _f32(src["wqkv"])) + _f32(src["bqkv"])
                k = own[:, H * D: (H + KV) * D].reshape(S, KV, D)
                v = own[:, (H + KV) * D:].reshape(S, KV, D)
            mix = _diff_attention(w, q, k, v, lam0, 0, a, skip, dot)
        x = x + mix
        gv = dot(_ln(x, lp["norm_2"], a.eps), _f32(lp["mlp"]["w_gate_up"]))
        F = gv.shape[-1] // 2
        x = x + dot(_silu(gv[:, :F]) * gv[:, F:], _f32(lp["mlp"]["w_down"]))
    return _ln(x, params["norm_f"], a.eps)


def head_gaps(embed, h, nxt, vocab: int, dot=dot_f32):
    """The tied head over ``h [T, E]``, the vocabulary a block at a time: →
    (the largest logit less the logit of ``nxt [T]``, the logits' std)."""
    n = math.gcd(vocab, VOCAB_BLOCKS)
    rows = vocab // n

    def block(carry, b):
        top, chosen, s1, s2 = carry
        lg = dot(h, _f32(jax.lax.dynamic_slice_in_dim(embed, b * rows, rows, 0)).T)     # [T, rows]
        inside = (nxt >= b * rows) & (nxt < (b + 1) * rows)
        mine = jnp.take_along_axis(lg, jnp.clip(nxt - b * rows, 0, rows - 1)[:, None], axis=-1)[:, 0]
        return (jnp.maximum(top, lg.max(-1)), jnp.where(inside, mine, chosen),
                s1 + lg.sum(-1), s2 + (lg * lg).sum(-1)), None

    T = h.shape[0]
    init = (jnp.full((T,), -jnp.inf), jnp.zeros((T,)), jnp.zeros((T,)), jnp.zeros((T,)))
    (top, chosen, s1, s2), _ = jax.lax.scan(block, init, jnp.arange(n))
    mean = s1 / vocab
    return top - chosen, jnp.sqrt(jnp.maximum(s2 / vocab - mean * mean, 0.0))


def logits(params, ids, a: Arch, skip: str = "", dot=dot_f32):
    """Whole logits [S, vocab] (small sizes: the tests, a control's next token)."""
    return dot(hidden(params, ids, a, skip, dot), _f32(params["embed"]).T)[:, : a.vocab]


@functools.partial(jax.jit, static_argnames=("arch", "skip", "first"))
def served_gaps(params, ids, n_prompt, n_valid, *, arch: Arch, skip: str = "", first: int = 0):
    """Teacher-forced check of one served request, in ``reference.py``'s
    form. ``ids`` [T] is the prompt followed by the served tokens, padded;
    position t >= n_prompt-1 predicts the served token ids[t+1]. Returns, per
    position from ``first`` on (a static row from which the head is applied),
    the largest reference logit less the reference logit of the served token,
    0 outside the served range, and the logits' std per position."""
    h = hidden(params, ids, arch, skip)[first:]
    gap, std = head_gaps(params["embed"], h, jnp.roll(ids, -1)[first:], arch.vocab)
    t = first + jnp.arange(h.shape[0])
    served = (t >= n_prompt - 1) & (t < n_valid - 1)
    return jnp.where(served, gap, 0.0), std
