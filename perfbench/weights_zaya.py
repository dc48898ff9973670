"""Seeded weights of a served ``zaya`` cell: the family's own draw
(``models/zaya.init_params``), then every layer's router SETTLED as a trained
router is. ``runners/serve_zaya.py`` wraps the module's initializer with
:func:`settled`; the model's module knows nothing of it.

Why a benchmark needs it: a deep stack of SEEDED attentions averages its
inputs, so the tokens' streams grow alike with depth, a router reading them has
ONE favourite expert a layer, and a top-1 pick feeds it back (the favourite's
output is the same vector for every token): with the bias merely drawn, a
decode step of 64 tokens hit 6 of 16 experts a layer and ``serve_tok_s`` moved
8.6% with the seed (PERF.md section 6, PR 49). A published checkpoint's router
holds the loads even, which is what the cell is sized by.

What is done, layer by layer on random tokens (``sequences`` of ``LENGTH``:
as many sequences as the cell has slots, since a served step's rows are one
token each of that many requests, and what the rows of ONE request share is
most of what a deep seeded stream holds), each layer's picks made under its
settled router before the next layer is looked at: the direction the tokens'
streams SHARE is taken out of ``wd``'s sight (``wd <- (I - k k^T) wd``, ``k``
the mean of the layer's normed streams), and the balancing bias is moved by
the rule it is trained with (down for an expert that got more than its share
of the picks, up for one that got less; it only selects). One program serves
every layer (layer 0 takes a router state of zeros from above, which is what
none is).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

LENGTH = 256      # tokens a sequence
ROUNDS = 400      # of the bias's rule
RATE = 0.02       # its step

_HI = jax.lax.Precision.HIGHEST


@functools.partial(jax.jit, static_argnums=0)
def _settle_layer(cfg, lp, h, r_up):
    """One layer over ``h [B, S, E]``, ``r_up [B, S, R]`` float32 → (its
    settled ``wd`` and ``bias``, the stream and the router's state below it)."""
    from deepspeed_tpu.models import zaya

    fam, n, f32 = zaya.ZayaFamily(cfg), cfg.num_experts, jnp.float32
    B, S, _ = h.shape
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    o = zaya.dense_attention(fam, lp, h, pos)
    w = fam.before_experts(lp, h, o, r_up)[1]
    moe = lp["moe"]
    k = jnp.mean(w.astype(f32), axis=0)
    k = k / jnp.linalg.norm(k)
    wd = moe["wd"].astype(f32)
    moe = {**moe, "wd": (wd - jnp.outer(k, jnp.dot(k, wd, precision=_HI))).astype(moe["wd"].dtype)}
    p = jax.nn.softmax(fam.router_logits(moe, w, r_up.reshape(B * S, -1))[0], axis=-1)

    def nudge(b, _):
        load = jnp.mean(jax.nn.one_hot(jnp.argmax(p + b, axis=-1), n, dtype=f32), axis=0)
        return b - RATE * (load * n - 1.0), None

    b, _ = jax.lax.scan(nudge, moe["bias"].astype(f32), None, length=ROUNDS)
    moe = {**moe, "bias": b.astype(moe["bias"].dtype)}
    h, r, _ = fam.after_attention({**lp, "moe": moe}, h, o, 0, carry=r_up)
    return moe["wd"], moe["bias"], h, r


def settle(cfg, params, rng, sequences: int):
    """``params`` with every layer's ``moe.wd`` and ``moe.bias`` settled on
    ``sequences x LENGTH`` random tokens drawn from ``rng``."""
    ids = jax.random.randint(rng, (sequences, LENGTH), 0, cfg.vocab_size)
    h = params["embed"][ids]
    r = jnp.zeros((sequences, LENGTH, cfg.router_hidden_size), jnp.float32)
    layers = []
    for lp in params["layers"]:
        wd, bias, h, r = _settle_layer(cfg, lp, h, r)
        layers.append({**lp, "moe": {**lp["moe"], "wd": wd, "bias": bias}})
    return {**params, "layers": layers}


def settled(module, sequences: int):
    """The family's ``ModuleSpec`` with its initializer followed by :func:`settle`."""
    cfg, draw = module.extra["config"], module.extra["init_in_dtype"]
    init = lambda rng, dtype: settle(cfg, draw(rng, dtype), jax.random.fold_in(rng, 1), sequences)  # noqa: E731
    return dataclasses.replace(module, init=lambda rng: init(rng, cfg.dtype), extra={**module.extra, "init_in_dtype": init})
