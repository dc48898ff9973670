"""Plain float32 reference of one chip's share of a ``mistral4`` model
(Mistral Small 4), written from these equations in the EXPANDED form; nothing
is imported from ``deepspeed_tpu.models``. No cache, no kernel, no absorption;
every matrix product runs at ``highest`` precision. The program computes the
absorbed form (a query as wide as the cached latent row, multi-query attention
on that row), so the comparison also checks the absorption.

One token stream, positions i. ``x = E[ids]`` (no position table). Layer l:

    u      = rms(x; g1)
    cq     = rms(u Wqa; gq);  q = cq Wqb as H heads of [q_nope N | q_rope R]
    [c|kr] = u Wkva;  c = rms(c; gkv)   (kr: one rotary key all heads share,
                                         not normed)
    k_h    = [c Wuk_h | rot(kr)],  v_h = c Wuv_h       (Wkvb's columns, by head)
    rot    : interleaved pairs (2j, 2j+1), R/2 frequencies, yarn:
             f_j = theta^(-2j/R);  low = floor(R ln(orig / (beta_fast 2 pi)) / (2 ln theta)),
             high = ceil(R ln(orig / (beta_slow 2 pi)) / (2 ln theta));
             r_j = clip((j - low) / (high - low), 0, 1);  f'_j = (1 - r_j) f_j + r_j f_j / factor
    s_ij   = a_i (q_i . k_j) m^2 / sqrt(N + R), causal, with
             m = 0.1 mscale_all_dim ln(factor) + 1  and the position-scaled query
             a_i = 1 + beta ln(1 + floor(i / orig))
    x      = x + concat_h(softmax(s) v_h) Wo
    u      = rms(x; g2)
    s      = sigmoid(u Wr) over ALL published experts;  sel = top_k(s + b)
    w_e    = scale * s_e / sum_{sel} s
    x      = x + sum_{e in sel, e held} w_e FFN_e(u) + FFN_shared(u)
    logits = rms(x; gf) W_head                          (untied)

The share: the experts this chip holds (``held`` of them from ``first_held``
on) give their part, the shared expert is added once, what the absent experts
would add is left out (here as in the program), and the vocabulary is the
slice held. What the published config does not say and this reference
assumes, as the configuration file lists under ``assumed``: pre-norm blocks
and the two latent norms; sigmoid scores with a selection bias ``b`` and no
group-limited routing (the DeepSeek-V3 block the router's keys come from);
the ``m^2`` on the softmax scale (same block, ``mscale_all_dim`` non-zero);
the query scale's form (the Llama-4 / Ministral-3 one the key is named
after); initializer range 0.02, the bias included, norm gains 1; the vision
tower is not served.

The only thing taken from the system is the *layout* of its parameter tree
(``layers[l].attn`` with ``wq_a, q_norm, wq_b, wkv_a, kv_norm, w_uk [C, H, N],
w_uv [C, H, V], wo``; ``experts`` stacked on a leading axis), so the same
seeded weights feed both. Weights arrive in the type the system holds them
and are cast to float32 where they are used, a layer's at a time and the held
experts one at a time. Attention runs in blocks of query rows, so that 9k
positions fit. (The held experts each multiply every token, weight 0 where
the token did not select them: gathering a token's experts' weights instead
moves 100 MB a pair, which at 9k positions costs more than the products.)

``skip`` is for the controls only (each must read as NOT correct):
``rope_score`` leaves the rotary part out of the score, ``yarn`` takes plain
rotary frequencies, ``qscale`` sets ``a_i`` = 1, ``latent_norm`` skips the
norm of ``c``, ``experts:<l>`` drops layer l's routed part, ``fp8_rows``
rounds the cached rows ``[c | rot(kr)]`` to 8 bits as ``float8_e4m3fn`` (three
bits of mantissa), ``int8_rows`` to int8 codes with a scale a row (a reading,
not a control: it is 7 times coarser than bf16 on the rows alone and the
served tokens' gaps cannot tell it from bf16; PERF.md, PR 34).
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


class Arch(NamedTuple):
    """The numbers of the configuration the equations need (hashable: a
    static argument of the jitted functions)."""
    n_layer: int
    n_head: int
    q_rank: int
    kv_rank: int
    nope: int
    rope: int
    v_dim: int
    n_experts: int                # published: the router's width
    held: int                     # routed experts held here ...
    first_held: int               # ... from this one on
    top_k: int
    scale: float
    norm_topk: bool
    eps: float
    theta: float
    factor: float
    beta_fast: float
    beta_slow: float
    mscale_all_dim: float
    orig_max: int
    q_beta: float
    vocab: int

    @classmethod
    def from_config(cls, c: dict) -> "Arch":
        share = c.get("expert_share", {"chips": 1, "index": 0})
        rp = c["rope_parameters"]
        return cls(
            n_layer=int(c["num_hidden_layers"]), n_head=int(c["num_attention_heads"]),
            q_rank=int(c["q_lora_rank"]), kv_rank=int(c["kv_lora_rank"]),
            nope=int(c["qk_nope_head_dim"]), rope=int(c["qk_rope_head_dim"]), v_dim=int(c["v_head_dim"]),
            n_experts=int(c.get("published", {}).get("n_routed_experts", c["n_routed_experts"])),
            held=int(c["n_routed_experts"]), first_held=int(share["index"]) * int(c["n_routed_experts"]),
            top_k=int(c["num_experts_per_tok"]), scale=float(c["routed_scaling_factor"]),
            norm_topk=bool(c.get("norm_topk_prob", True)), eps=float(c["rms_norm_eps"]),
            theta=float(rp["rope_theta"]), factor=float(rp["factor"]), beta_fast=float(rp["beta_fast"]),
            beta_slow=float(rp["beta_slow"]), mscale_all_dim=float(rp["mscale_all_dim"]),
            orig_max=int(rp["original_max_position_embeddings"]), q_beta=float(rp["llama_4_scaling_beta"]),
            vocab=int(c["vocab_size"]),
        )


def dot_f32(a, b):
    """a [..., M, K] @ b [..., K, N] in float32 at full precision."""
    return jnp.matmul(a, b, precision=_HI)


def _f32(x):
    return x.astype(jnp.float32)


def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * _f32(g)


def _frequencies(a: Arch, yarn: bool) -> np.ndarray:
    j = np.arange(a.rope // 2, dtype=np.float64)
    f = a.theta ** (-2.0 * j / a.rope)
    if not yarn:
        return f.astype(np.float32)

    def dim_of(turns):
        return a.rope * math.log(a.orig_max / (turns * 2 * math.pi)) / (2 * math.log(a.theta))

    low, high = max(math.floor(dim_of(a.beta_fast)), 0), min(math.ceil(dim_of(a.beta_slow)), a.rope - 1)
    r = np.clip((j - low) / max(high - low, 1e-3), 0.0, 1.0)
    return ((1.0 - r) * f + r * f / a.factor).astype(np.float32)


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
    freq = _frequencies(a, skip != "yarn")
    q = dot(_rms(dot(u, _f32(lp["wq_a"])), lp["q_norm"], a.eps), _f32(lp["wq_b"])).reshape(S, H, N + R)
    kv = dot(u, _f32(lp["wkv_a"]))
    c = kv[:, :C] if skip == "latent_norm" else _rms(kv[:, :C], lp["kv_norm"], a.eps)
    kr = _rope(kv[:, None, C:], freq)[:, 0]                                   # [S, R]
    if skip in ("int8_rows", "fp8_rows"):   # the cached row [c | rot(kr)] in 8 bits
        row = jnp.concatenate([c, kr], axis=-1)
        if skip == "fp8_rows":
            row = row.astype(jnp.float8_e4m3fn).astype(jnp.float32)
        else:                               # int8 codes, a scale a row
            s8 = jnp.max(jnp.abs(row), axis=-1, keepdims=True) / 127.0
            row = jnp.round(row / s8) * s8
        c, kr = row[:, :C], row[:, C:]
    q_nope, q_rope = q[..., :N], _rope(q[..., N:], freq)
    k_nope = dot(c, _f32(lp["w_uk"]).reshape(C, H * N)).reshape(S, H, N)
    v = dot(c, _f32(lp["w_uv"]).reshape(C, H * V)).reshape(S, H, V)
    if skip == "rope_score":
        q_rope = jnp.zeros_like(q_rope)
    pos = jnp.arange(S, dtype=jnp.float32)
    a_i = jnp.ones((S,)) if skip == "qscale" else 1.0 + a.q_beta * jnp.log1p(jnp.floor(pos / a.orig_max))
    m = 0.1 * a.mscale_all_dim * math.log(a.factor) + 1.0 if a.mscale_all_dim and a.factor > 1 else 1.0
    qh = (jnp.concatenate([q_nope, q_rope], -1) * a_i[:, None, None]).transpose(1, 0, 2)       # [H, S, N + R]
    kh = jnp.concatenate([k_nope, jnp.broadcast_to(kr[:, None, :], (S, H, R))], -1).transpose(1, 2, 0)
    vh = v.transpose(1, 0, 2)
    blk = math.gcd(S, ROW_BLOCK)

    def rows(i):
        qi = jax.lax.dynamic_slice_in_dim(qh, i * blk, blk, 1)
        s = dot(qi, kh) * (m * m / math.sqrt(N + R))                                         # [H, blk, S]
        seen = jnp.arange(S)[None, :] <= (i * blk + jnp.arange(blk))[:, None]
        return dot(jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1), vh)            # [H, blk, V]

    o = jax.lax.map(rows, jnp.arange(S // blk))                                               # [S/blk, H, blk, V]
    o = o.transpose(0, 2, 1, 3).reshape(S, H * V)
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
        x = x + _attention(lp["attn"], _rms(x, lp["norm_1"], a.eps), a, skip, dot)
        x = x + _experts(lp["moe"], _rms(x, lp["norm_2"], a.eps), a, skip != f"experts:{l}", dot)
    return _rms(x, params["norm_f"], a.eps)


def logits(params, ids, a: Arch, skip: str = "", dot=dot_f32):
    return dot(hidden(params, ids, a, skip, dot), _f32(params["head"]))[:, : a.vocab]


@functools.partial(jax.jit, static_argnames=("arch", "skip", "first"))
def served_gaps(params, ids, n_prompt, n_valid, *, arch: Arch, skip: str = "", first: int = 0):
    """Teacher-forced check of one served request, in ``reference.py``'s
    form. ``ids`` [T] is the prompt followed by the served tokens, padded;
    position t >= n_prompt-1 predicts the served token ids[t+1]. Returns, per
    position from ``first`` on (a static row from which the head is applied:
    the served range of a 9k-token prompt is its last few hundred rows), the
    largest reference logit less the reference logit of the served token, 0
    outside the served range, and the logits' std per position."""
    h = hidden(params, ids, arch, skip)[first:]
    lg = dot_f32(h, _f32(params["head"]))[:, : arch.vocab]
    nxt = jnp.roll(ids, -1)[first:]
    chosen = jnp.take_along_axis(lg, nxt[:, None], axis=-1)[:, 0]
    gap = jnp.max(lg, axis=-1) - chosen
    t = first + jnp.arange(lg.shape[0])
    served = (t >= n_prompt - 1) & (t < n_valid - 1)
    return jnp.where(served, gap, 0.0), jnp.std(lg, axis=-1)
