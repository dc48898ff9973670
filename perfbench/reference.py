"""Plain float32 reference of GPT-2, written from the equations of
"Language Models are Unsupervised Multitask Learners" (Radford et al., 2019)
and the published ``gpt2`` modelling code's definitions; nothing is imported
from ``deepspeed_tpu.models``. No cache, no kernel, no batching tricks;
every matrix product runs at ``highest`` precision (on a TPU a float32
product is otherwise computed in bf16 passes).

    h_0   = wte[ids] + wpe[positions]
    a_l   = h_l + proj(softmax(causal(q k^T / sqrt(d))) v),  q,k,v = split(ln_1(h_l) W_qkv + b)
    h_l+1 = a_l + W_proj gelu_tanh(ln_2(a_l) W_fc + b_fc) + b_proj
    logits = ln_f(h_L) wte^T                                   (tied head)
    loss   = mean over positions t < S-1 of -log softmax(logits_t)[ids_{t+1}]

The only thing taken from the system is the *layout* of its parameter tree
(blocks stacked on a leading layer axis), so the same seeded weights can be
fed to both. Weights arrive in whatever type the system holds them (bf16 when
served, float32 masters when trained) and are cast to float32 layer by layer,
so the reference never holds a second full copy.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST


def _ln(x, scale, bias, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * scale + bias


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(jnp.sqrt(2.0 / jnp.pi) * (x + 0.044715 * x**3)))


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def hidden(params, ids, n_head: int, eps: float, skip_layer: int = -1):
    """ids [S] -> final hidden states [S, E] in float32. ``skip_layer`` drops
    one block: used only by the tests that show the margins catch it."""
    S = ids.shape[0]
    E = params["wte"].shape[1]
    D = E // n_head
    h = params["wte"][ids].astype(jnp.float32) + params["wpe"][:S].astype(jnp.float32)
    causal = jnp.tril(jnp.ones((S, S), bool))

    def block(h, xs):
        i, lp = xs
        lp = _f32(lp)
        x = _ln(h, lp["ln_1"]["scale"], lp["ln_1"]["bias"], eps)
        qkv = jnp.dot(x, lp["attn"]["c_attn_w"], precision=_HI) + lp["attn"]["c_attn_b"]
        q, k, v = (t.reshape(S, n_head, D) for t in jnp.split(qkv, 3, axis=-1))
        s = jnp.einsum("qhd,khd->hqk", q, k, precision=_HI) / jnp.sqrt(jnp.float32(D))
        s = jnp.where(causal[None], s, -jnp.inf)
        o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v, precision=_HI).reshape(S, E)
        a = h + jnp.dot(o, lp["attn"]["c_proj_w"], precision=_HI) + lp["attn"]["c_proj_b"]
        x = _ln(a, lp["ln_2"]["scale"], lp["ln_2"]["bias"], eps)
        m = _gelu_tanh(jnp.dot(x, lp["mlp"]["c_fc_w"], precision=_HI) + lp["mlp"]["c_fc_b"])
        out = a + jnp.dot(m, lp["mlp"]["c_proj_w"], precision=_HI) + lp["mlp"]["c_proj_b"]
        return jnp.where(i == skip_layer, h, out), None

    L = params["blocks"]["ln_1"]["scale"].shape[0]
    h, _ = jax.lax.scan(block, h, (jnp.arange(L), params["blocks"]))
    return _ln(h, params["ln_f"]["scale"].astype(jnp.float32), params["ln_f"]["bias"].astype(jnp.float32), eps)


def _logits(params, h, vocab):
    return jnp.dot(h, params["wte"].astype(jnp.float32).T, precision=_HI)[..., :vocab]


@functools.partial(jax.jit, static_argnames=("n_head", "eps", "vocab", "skip_layer"))
def served_gaps(params, ids, n_prompt, n_valid, *, n_head, eps, vocab, skip_layer=-1):
    """Teacher-forced check of one served request. ``ids`` [T] is the prompt
    followed by the served tokens, padded; ``n_prompt`` tokens are prompt and
    ``n_valid`` are real. Position t >= n_prompt-1 predicts the served token
    ids[t+1]. Returns, per position, (largest reference logit - reference logit
    of the served token): 0 where the system chose the reference's argmax, and
    small where rounding flipped a near-tie. Positions outside the served
    range give 0."""
    logits = _logits(params, hidden(params, ids, n_head, eps, skip_layer), vocab)  # [T, V]
    nxt = jnp.roll(ids, -1)
    chosen = jnp.take_along_axis(logits, nxt[:, None], axis=-1)[:, 0]
    gap = jnp.max(logits, axis=-1) - chosen
    t = jnp.arange(ids.shape[0])
    served = (t >= n_prompt - 1) & (t < n_valid - 1)
    return jnp.where(served, gap, 0.0), jnp.std(logits, axis=-1)


@functools.partial(jax.jit, static_argnames=("n_head", "eps", "vocab", "skip_layer"))
def lm_loss(params, batch_ids, *, n_head, eps, vocab, skip_layer=-1):
    """Mean next-token cross-entropy of ``batch_ids`` [B, S], one row at a
    time so that only one row's logits are alive."""

    def row(ids):
        logits = _logits(params, hidden(params, ids, n_head, eps, skip_layer), vocab)[:-1]
        lse = jax.nn.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(logits, ids[1:, None], axis=-1)[:, 0]
        return jnp.sum(lse - tgt)

    total = jnp.sum(jax.lax.map(row, batch_ids))
    return total / (batch_ids.shape[0] * (batch_ids.shape[1] - 1))
