"""Multi-head LATENT attention as a serving family's pieces: what
``models/mistral4.py`` and ``models/longcat_flash.py`` have in common.

The queries come through a low-rank pair (``wq_a``, RMS norm, ``wq_b``; or,
where the config's ``q_lora_rank`` is None, through ONE matrix ``wq``), the
keys and values of ALL heads from one latent a token, ``[c | kr] = u wkv_a``
with ``c`` normed (``kv_lora_rank`` wide) and ``kr`` one rotary key every head
shares (``qk_rope_head_dim`` wide). What is cached is the row ``[c | rot(kr)]``;
a head's key is ``[c w_uk_h | rot(kr)]`` and its value ``c w_uv_h``.

:class:`LatentAttention` is a mix-in for a family class (``serving/model.py``'s
``Family`` notes): it gives ``qkv`` (ABSORBED: the query of head h is ``a [q_nope_h
w_uk_h^T | rot(q_rope_h)]``, as wide as the cached row, attention multi-query on
that row, ``w_uv`` applied after it in ``attn_out``), ``qkv_expanded`` (per-head
keys and values from the row, which the whole-prompt program and ``forward``
use), ``attn_out`` and ``attn_out_expanded``. The family says what differs:

- ``lp`` (what its ``layer`` gives) holds ``norm_1`` (the norm before the
  attention) and ``attn`` (``wq_a, q_norm, wq_b, wkv_a, kv_norm, w_uk [C, H,
  N], w_uv [C, H, V], wo``);
- ``inv_freq``: the ``qk_rope_head_dim / 2`` rotary frequencies;
- ``query_scale(positions)``: what multiplies the whole query, in float32,
  before its one rounding (a position scale ``[..., 1, 1]``, or a number);
- ``kv_lora_scale``: a number on the normed latent ``c`` (``None``: none),
  folded into the norm's gain in float32, so that ``c`` is rounded once.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..ops.layer_norm import rms_norm
from ..telemetry import parts


def rotary(x, positions, inv_freq):
    """Interleaved rotary in float32: ``x [..., S, heads, D]`` at ``positions
    [..., S]``; the pair is elements (2j, 2j + 1) → float32."""
    ang = positions.astype(jnp.float32)[..., None, None] * inv_freq      # [..., S, 1, D/2]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xp = x.astype(jnp.float32).reshape(*x.shape[:-1], -1, 2)
    x1, x2 = xp[..., 0], xp[..., 1]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).reshape(x.shape)


class LatentAttention:
    """The attention half of a latent family (the module's notes). Reads
    ``self.cfg`` (``n_head, qk_nope_head_dim, qk_head_dim, kv_lora_rank,
    rms_norm_eps``), ``self.inv_freq`` and ``self.v_width``."""

    kv_lora_scale = None

    def query_scale(self, positions):
        raise NotImplementedError

    def _projections(self, lp, h, positions):
        """→ (``q_nope [.., H, nope]``, rotated pieces in float32: ``q_rope
        [.., H, rope]``, the query's scale, and the row to cache ``[c |
        rot(kr)] [.., 1, kv_width]`` in ``h``'s type)."""
        cfg, a = self.cfg, lp["attn"]
        H, N = cfg.n_head, cfg.qk_nope_head_dim
        with parts.part("norm"):
            u = rms_norm(h, lp["norm_1"], cfg.rms_norm_eps)
        if "wq" in a:   # q_lora_rank None: the query is ONE matrix, no low-rank pair and no norm between
            q = u @ a["wq"]
        else:
            q = rms_norm(u @ a["wq_a"], a["q_norm"], cfg.rms_norm_eps) @ a["wq_b"]
        q = q.reshape(*q.shape[:-1], H, cfg.qk_head_dim)
        kv = u @ a["wkv_a"]
        gain = a["kv_norm"]
        if self.kv_lora_scale is not None:
            gain = gain.astype(jnp.float32) * self.kv_lora_scale
        c = rms_norm(kv[..., : cfg.kv_lora_rank], gain, cfg.rms_norm_eps)
        kr = rotary(kv[..., None, cfg.kv_lora_rank:], positions, self.inv_freq)
        row = jnp.concatenate([c[..., None, :], kr.astype(c.dtype)], axis=-1)
        scale = self.query_scale(positions)
        return q[..., :N], rotary(q[..., N:], positions, self.inv_freq), scale, row

    def qkv(self, lp, h, positions, l: int):
        """``h [B, S, E]`` → the ABSORBED query ``[B, S, H, kv_width]`` (``a
        [q_nope w_uk^T | rot(q_rope)]``, accumulated in float32 through
        ``w_uk`` and rounded once), the row to cache ``[B, S, 1, kv_width]``,
        and no values: they are the row's first ``v_width`` lanes."""
        q_nope, q_rope, scale, row = self._projections(lp, h, positions)
        qa = jnp.einsum("...hn,chn->...hc", q_nope, lp["attn"]["w_uk"],
                        preferred_element_type=jnp.float32)
        q = jnp.concatenate([qa, q_rope], axis=-1) * scale
        return q.astype(h.dtype), row, None

    def qkv_expanded(self, lp, h, positions, l: int):
        """The same attention per head: ``q [B, S, H, qk_head_dim]`` (scaled),
        ``k`` the same shape (``[c w_uk_h | rot(kr)]``), ``v [B, S, H,
        v_head_dim]``, and the row to cache."""
        H = self.cfg.n_head
        q_nope, q_rope, scale, row = self._projections(lp, h, positions)
        q = jnp.concatenate([q_nope.astype(jnp.float32), q_rope], axis=-1) * scale
        c, kr = row[..., 0, : self.v_width], row[..., self.v_width:]
        k_nope = jnp.einsum("...c,chn->...hn", c, lp["attn"]["w_uk"])
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(kr, (*kr.shape[:-2], H, kr.shape[-1]))], axis=-1
        )
        v = jnp.einsum("...c,chv->...hv", c, lp["attn"]["w_uv"])
        return q.astype(h.dtype), k, v, row

    def attn_out(self, lp, o, tp_axis=None):
        """``o [B, S, H * v_width]``, the absorbed attention's output (a mix
        of latents a head) → through ``w_uv`` then ``wo``."""
        H = self.cfg.n_head
        with parts.part("attn.core"):  # the values' half of the absorption belongs to the attention
            o = o.reshape(*o.shape[:-1], H, self.v_width)
            o = jnp.einsum("...hc,chv->...hv", o, lp["attn"]["w_uv"])
        return o.reshape(*o.shape[:-2], -1) @ lp["attn"]["wo"]

    def attn_out_expanded(self, lp, o, tp_axis=None):
        return o @ lp["attn"]["wo"]


def attention_leaf_shapes(cfg) -> dict:
    """``lp["attn"]``'s leaves as ``(shape, kind)`` (kind ``w``: drawn; ``one``:
    a norm's gain), from the config's published keys."""
    E, H, R, C = cfg.hidden_size, cfg.num_attention_heads, cfg.q_lora_rank, cfg.kv_lora_rank
    query = {"wq": ((E, H * cfg.qk_head_dim), "w")} if R is None else {
        "wq_a": ((E, R), "w"), "q_norm": ((R,), "one"), "wq_b": ((R, H * cfg.qk_head_dim), "w"),
    }
    return {
        **query,
        "wkv_a": ((E, cfg.kv_width), "w"), "kv_norm": ((C,), "one"),
        "w_uk": ((C, H, cfg.qk_nope_head_dim), "w"),
        "w_uv": ((C, H, cfg.v_head_dim), "w"),
        "wo": ((H * cfg.v_head_dim, E), "w"),
    }


ATTENTION_AXES = {
    "wq_a": ("embed", None), "q_norm": (None,), "wq_b": (None, "mlp"),
    "wkv_a": ("embed", None), "kv_norm": (None,),
    "w_uk": (None, "heads", None), "w_uv": (None, "heads", None),
    "wo": ("mlp", "embed"),
}


def attention_axes(cfg) -> dict:
    """:data:`ATTENTION_AXES` for ``cfg``'s leaves (:func:`attention_leaf_shapes`):
    where ``q_lora_rank`` is None the one query matrix in place of the pair."""
    if cfg.q_lora_rank is not None:
        return dict(ATTENTION_AXES)
    rest = {k: v for k, v in ATTENTION_AXES.items() if k not in ("wq_a", "q_norm", "wq_b")}
    return {"wq": ("embed", "mlp"), **rest}


def is_leaf_spec(x) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[1], str)


def draw_tree(shapes, rng, dtype, std: float, stds: dict = None):
    """A tree of ``(shape, kind)`` leaves made on the device in ``dtype``,
    every leaf by a program of its own (one a distinct shape and spread), so
    the set-up never holds more than the tree and one leaf's temporaries.
    Kind ``one`` is ones, ``w`` normal with ``std``, any other kind normal
    with ``stds[kind]``."""
    leaves, treedef = jax.tree_util.tree_flatten(shapes, is_leaf=is_leaf_spec)
    keys = jax.random.split(rng, len(leaves))

    @functools.lru_cache(maxsize=None)
    def drawn(shape, s):
        return jax.jit(lambda k: (jax.random.normal(k, shape, jnp.float32) * s).astype(dtype))

    def make(key, spec):
        shape, kind = spec
        if kind == "one":
            return jnp.ones(shape, dtype)
        return drawn(shape, std if kind == "w" else (stds or {})[kind])(key)

    return jax.tree_util.tree_unflatten(treedef, [make(k, s) for k, s in zip(keys, leaves)])


def forward(fam, params, input_ids, absorbed: bool = False) -> jnp.ndarray:
    """Whole-sequence logits ``[B, S, vocab]`` with no cache: a latent
    family's pieces under a dense masked softmax, expanded (per-head keys and
    values) or ``absorbed`` (multi-query on the cached row), for small sizes;
    the served path is ``serving/model.py``. The rest of a layer is the
    family's ``after_attention`` where it has one (a carried stream), else
    attention then ``mlp`` into the residual stream."""
    cfg = fam.cfg
    B, S = input_ids.shape
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    h = fam.embed(params, input_ids, pos)
    seen = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    own, carry = getattr(fam, "after_attention", None), None
    for l in range(fam.n_layer):
        lp = fam.layer(params, l)
        if absorbed:
            q, row, _ = fam.qkv(lp, h, pos, l)
            k = jnp.broadcast_to(row, (B, S, cfg.n_head, row.shape[-1]))
            v, out = k[..., : fam.v_width], fam.attn_out
        else:
            q, k, v, _ = fam.qkv_expanded(lp, h, pos, l)
            out = fam.attn_out_expanded
        s = jnp.einsum("bshd,bthd->bhst", q.astype(jnp.float32), k.astype(jnp.float32))
        p = jax.nn.softmax(jnp.where(seen, s * fam.sm_scale, -1e30), axis=-1)
        o = jnp.einsum("bhst,bthd->bshd", p, v.astype(jnp.float32)).astype(h.dtype).reshape(B, S, -1)
        if own is not None:
            h, carry, _ = own(lp, h, o, l, None, None, carry, out)
        else:
            h = h + out(lp, o)
            h = h + fam.mlp(lp, h, l)[0]
    return fam.logits(params, h)
