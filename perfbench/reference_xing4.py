"""Plain float32 reference of one chip's share of a ``xing4_0`` model
(Xing4.0-29B-A4B), written from these equations; nothing is imported from
``deepspeed_tpu``. No cache, no kernel, no absorption, no batching; every
matrix product runs at ``highest`` precision; the Sinkhorn rounds are a Python
loop. What is the same equation as Mistral Small 4's is taken from
``reference_mistral4.py``: the yarn frequencies, the EXPANDED latent attention
(with the position-scaled query's ``beta`` 0: this model has none) and the
sigmoid router with its shared expert.

One token stream, positions i. A token's residual is ``X`` in ``R^{n x E}``
(``n = hc_mult``), ``X[k] = E[ids]`` for every k at the start. A sub-block
``F`` with its ``phi [2n + n^2, n E]`` (held transposed), ``b`` and gains
``a = (a_pre, a_post, a_res)``:

    xh   = vec(X) / sqrt(mean(vec(X)^2) + hc_eps)             over all n E values, no gain
    [p | q | r] = xh phi^T
    H_pre  = sigmoid(a_pre p + b_pre);  H_post = 2 sigmoid(a_post q + b_post)
    M      = exp(clip(a_res mat(r) + b_res, clamp_min, clamp_max))     row-major n x n
    hc_sinkhorn_iters times:  M <- M / colsum(M);  M <- M / rowsum(M);  H_res = M
    u    = sum_k H_pre[k] X[k];   y = F(rms(u; g))
    X'[k] = sum_j H_res[k, j] X[j] + H_post[k] y

Layer l: the attention sub-block (``F`` the latent attention of
``reference_mistral4._attention``: softmax scale ``m^2 / sqrt(N + R)``, ``m =
0.1 mscale_all_dim ln(factor) + 1``), then the FFN sub-block (a dense silu
gated FFN in the first ``first_k_dense_replace`` layers, after them top-k of
``sigmoid(u Wr) + bias`` over ALL published experts, weights from the sigmoid
alone renormalised over the picks times ``routed_scaling_factor``, the held
experts' part plus the shared expert). ``logits = rms(sum_k X[k]; gf) W_head``.

The only thing taken from the system is the *layout* of its parameter tree
(``layers[l]`` with ``norm_1, norm_2, attn, hc [2] {phi, a, b}`` and ``ffn`` or
``moe``), so the same seeded weights feed both.

``skip`` is for the controls only (each must read as NOT correct):
``hres_identity`` takes ``H_res = I``, ``hpost_one`` leaves the 2 out of
``H_post``, ``static_maps`` sets the gains ``a`` to 0 (the dynamic part
dropped), ``sinkhorn_1`` makes one round in place of all, ``stat_E`` takes the
statistic over each stream's ``E`` values in place of all ``n E``,
``maps_bf16`` computes the maps in bfloat16 (statistic, projection, sigmoids,
exponential and rounds), ``rope_score`` leaves the rotary part out of the
score, ``experts:<l>`` drops layer l's routed part, ``scale_1`` takes
``routed_scaling_factor`` 1.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from perfbench import reference_mistral4 as rm
from perfbench.reference_mistral4 import _f32, _ffn, _rms, dot_f32


class Arch(NamedTuple):
    """The numbers of the configuration the equations need (hashable: a
    static argument of the jitted functions)."""
    latent: rm.Arch               # the attention's and the router's numbers, as ``reference_mistral4`` reads them
    first_dense: int
    n_streams: int
    iters: int
    hc_eps: float
    clamp: tuple

    @classmethod
    def from_config(cls, c: dict) -> "Arch":
        share = c.get("expert_share", {"chips": 1, "index": 0})
        rs = c["rope_scaling"]
        latent = rm.Arch(
            n_layer=int(c["num_hidden_layers"]), n_head=int(c["num_attention_heads"]),
            q_rank=int(c["q_lora_rank"]), kv_rank=int(c["kv_lora_rank"]),
            nope=int(c["qk_nope_head_dim"]), rope=int(c["qk_rope_head_dim"]), v_dim=int(c["v_head_dim"]),
            n_experts=int(c.get("published", {}).get("n_routed_experts", c["n_routed_experts"])),
            held=int(c["n_routed_experts"]), first_held=int(share["index"]) * int(c["n_routed_experts"]),
            top_k=int(c["num_experts_per_tok"]), scale=float(c["routed_scaling_factor"]),
            norm_topk=bool(c.get("norm_topk_prob", True)), eps=float(c["rms_norm_eps"]),
            theta=float(c["rope_theta"]), factor=float(rs["factor"]), beta_fast=float(rs["beta_fast"]),
            beta_slow=float(rs["beta_slow"]), mscale_all_dim=float(rs["mscale_all_dim"]),
            orig_max=int(rs["original_max_position_embeddings"]), q_beta=0.0, vocab=int(c["vocab_size"]),
        )
        return cls(latent=latent, first_dense=int(c["first_k_dense_replace"]), n_streams=int(c["hc_mult"]),
                   iters=int(c["hc_sinkhorn_iters"]), hc_eps=float(c["hc_eps"]),
                   clamp=(float(c["mhc_h_res_clamp_min"]), float(c["mhc_h_res_clamp_max"])))


def maps(X, m, a: Arch, skip: str = "", dot=dot_f32):
    """``X [S, n, E]`` → ``H_pre [S, n]``, ``H_post [S, n]``, ``H_res [S, n,
    n]`` of one sub-block (``m``: its ``phi, a, b``)."""
    S, n, E = X.shape
    t = jnp.bfloat16 if skip == "maps_bf16" else jnp.float32
    X = X.astype(t)
    if skip == "stat_E":
        xh = (X / jnp.sqrt(jnp.mean(X * X, axis=-1, keepdims=True) + a.hc_eps)).reshape(S, n * E)
    else:
        v = X.reshape(S, n * E)
        xh = v / jnp.sqrt(jnp.mean(v * v, axis=-1, keepdims=True) + a.hc_eps)
    z = dot(xh, m["phi"].astype(t).T).astype(t)
    g = jnp.zeros((3,), t) if skip == "static_maps" else m["a"].astype(t)
    b = m["b"].astype(t)
    pre = jax.nn.sigmoid(g[0] * z[:, :n] + b[:n])
    post = (1.0 if skip == "hpost_one" else 2.0) * jax.nn.sigmoid(g[1] * z[:, n:2 * n] + b[n:2 * n])
    M = jnp.exp(jnp.clip(g[2] * z[:, 2 * n:] + b[2 * n:], a.clamp[0], a.clamp[1])).reshape(S, n, n)
    for _ in range(1 if skip == "sinkhorn_1" else a.iters):
        M = M / jnp.sum(M, axis=1, keepdims=True)       # the columns
        M = M / jnp.sum(M, axis=2, keepdims=True)       # the rows
    if skip == "hres_identity":
        M = jnp.broadcast_to(jnp.eye(n, dtype=t), (S, n, n))
    return _f32(pre), _f32(post), _f32(M)


def sub_block(X, m, norm, F, a: Arch, skip: str = "", dot=dot_f32):
    """``X' = H_res X + H_post^T F(rms(H_pre X))``."""
    pre, post, res = maps(X, m, a, skip, dot)
    u = jnp.sum(pre[:, :, None] * X, axis=1)
    y = F(_rms(u, norm, a.latent.eps))
    return jnp.einsum("sij,sje->sie", res, X, precision=jax.lax.Precision.HIGHEST) + post[:, :, None] * y[:, None, :]


def ffn_sub_block(lp, l: int, a: Arch, skip: str = "", dot=dot_f32):
    """Layer ``l``'s FFN as a function of its normed input ``[S, E]``."""
    if l < a.first_dense:
        return lambda u: _ffn(u, lp["ffn"], dot)
    la = a.latent._replace(scale=1.0) if skip == "scale_1" else a.latent
    return lambda u: rm._experts(lp["moe"], u, la, skip != f"experts:{l}", dot)


def hidden(params, ids, a: Arch, skip: str = "", dot=dot_f32):
    """ids [S] -> final hidden states [S, E] in float32, normed."""
    x = _f32(params["embed"][ids])
    X = jnp.broadcast_to(x[:, None, :], (x.shape[0], a.n_streams, x.shape[1]))
    for l in range(a.latent.n_layer):
        lp = params["layers"][l]
        attend = lambda u, lp=lp: rm._attention(lp["attn"], u, a.latent, skip, dot)  # noqa: E731
        X = sub_block(X, lp["hc"][0], lp["norm_1"], attend, a, skip, dot)
        X = sub_block(X, lp["hc"][1], lp["norm_2"], ffn_sub_block(lp, l, a, skip, dot), a, skip, dot)
    return _rms(jnp.sum(X, axis=1), params["norm_f"], a.latent.eps)


def logits(params, ids, a: Arch, skip: str = "", dot=dot_f32):
    return dot(hidden(params, ids, a, skip, dot), _f32(params["head"]))[:, : a.latent.vocab]


@functools.partial(jax.jit, static_argnames=("arch", "skip", "first"))
def served_gaps(params, ids, n_prompt, n_valid, *, arch: Arch, skip: str = "", first: int = 0):
    """Teacher-forced check of one served request, in ``reference_mistral4``'s
    form: per position from ``first`` on, the largest reference logit less the
    reference logit of the served token, 0 outside the served range, and the
    logits' std per position."""
    h = hidden(params, ids, arch, skip)[first:]
    lg = dot_f32(h, _f32(params["head"]))[:, : arch.latent.vocab]
    nxt = jnp.roll(ids, -1)[first:]
    chosen = jnp.take_along_axis(lg, nxt[:, None], axis=-1)[:, 0]
    gap = jnp.max(lg, axis=-1) - chosen
    t = first + jnp.arange(lg.shape[0])
    served = (t >= n_prompt - 1) & (t < n_valid - 1)
    return jnp.where(served, gap, 0.0), jnp.std(lg, axis=-1)
