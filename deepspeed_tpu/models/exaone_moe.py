"""The ``exaone_moe`` family (K-EXAONE), as one chip's share of it serves it.

A pre-norm decoder: RMS norm, grouped-query attention with an RMS norm over
each query and key head, rotary positions (half-split) on the sliding-window
layers and none on the full-attention ones, a gated SiLU MLP on the first
``first_k_dense_replace`` layers and, on the others, ``num_experts_per_tok``
of ``num_experts_published`` sigmoid-routed experts plus a shared expert
(``moe/expert_share.py``); untied output head.

Two of the config's sizes are shares, not the model's: ``num_experts`` is the
number of routed experts HELD here (``expert_share`` says of how many chips
this is which one; the router keeps ``num_experts_published`` columns), and
``vocab_size`` the rows of the vocabulary held (ids, logits and sampling are
over the slice). Everything else is the published width.

The config reads the published keys (``from_dict``); the layer kinds come
from ``layer_types`` / ``mlp_layer_types``, of which the first
``num_hidden_layers`` entries count. What the published config does not say
and this module assumes is listed in the configuration file that runs it
(``perfbench/configs/k-exaone-236b-ep8-serve-1chip.json``, ``assumed``).

Only the served path lives here: the pieces ``serving/model.py``'s paged
programs are built from (:class:`ExaoneFamily`), and :func:`forward`, the
same pieces over a whole sequence with no cache.
"""

from __future__ import annotations

import dataclasses
import functools
from dataclasses import dataclass
from typing import Any, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..moe.expert_share import ExpertShare, expert_share_layer, gated_ffn
from ..ops.layer_norm import rms_norm
from ..runtime.module import ModuleSpec
from ..telemetry import parts

PyTree = Any

SLIDING, FULL = "sliding_attention", "full_attention"


@dataclass(frozen=True)
class ExaoneMoEConfig:
    vocab_size: int = 153600            # rows held here
    hidden_size: int = 6144
    intermediate_size: int = 18432
    moe_intermediate_size: int = 2048
    num_hidden_layers: int = 48
    num_attention_heads: int = 64
    num_key_value_heads: int = 8
    head_dim: int = 128
    layer_types: Tuple[str, ...] = (SLIDING, SLIDING, SLIDING, FULL) * 12
    mlp_layer_types: Tuple[str, ...] = ("dense",) + ("sparse",) * 47
    sliding_window: int = 128
    num_experts: int = 128              # routed experts held here
    num_experts_published: int = 128    # the router's width
    expert_chips: int = 1               # expert_share: of how many chips
    expert_index: int = 0               # ... this is which
    num_experts_per_tok: int = 8
    num_shared_experts: int = 1
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1e6
    max_position_embeddings: int = 262144
    initializer_range: float = 0.02
    attn_impl: str = "auto"             # auto | pallas (the paged kernels or their jnp fallbacks)
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        L = self.num_hidden_layers
        if len(self.layer_types) < L or len(self.mlp_layer_types) < L:
            raise ValueError(f"layer_types / mlp_layer_types shorter than num_hidden_layers={L}")
        if self.num_experts * self.expert_chips != self.num_experts_published:
            raise ValueError(
                f"num_experts={self.num_experts} held on each of {self.expert_chips} chips "
                f"is not the router's {self.num_experts_published}"
            )
        if not 0 <= self.expert_index < self.expert_chips:
            raise ValueError(f"expert_share index {self.expert_index} of {self.expert_chips} chips")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("num_attention_heads must divide by num_key_value_heads")
        if self.num_shared_experts != 1:
            raise ValueError("one shared expert is what this module builds")

    @classmethod
    def from_dict(cls, d: dict, **overrides) -> "ExaoneMoEConfig":
        """From the published keys (an HF ``config.json`` or a perfbench
        configuration file); keys this module does not know are ignored."""
        names = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in d.items() if k in names}
        for k in ("layer_types", "mlp_layer_types"):
            if k in kw:
                kw[k] = tuple(kw[k])
        if "rope_parameters" in d:
            kw["rope_theta"] = float(d["rope_parameters"]["rope_theta"])
        # a configuration file keeps the published count beside the held one
        kw.setdefault("num_experts_published", int(
            d.get("published", {}).get("num_experts", d.get("num_experts", cls.num_experts))
        ))
        share = d.get("expert_share")
        if share:
            kw["expert_chips"], kw["expert_index"] = int(share["chips"]), int(share["index"])
        kw.pop("dtype", None)  # a file says "bfloat16"; the engine's dtype decides
        kw.update(overrides)
        return cls(**kw)

    # -- the names the serving stack reads a model's geometry by -----------
    n_layer = property(lambda self: self.num_hidden_layers)
    n_head = property(lambda self: self.num_attention_heads)
    n_kv_head = property(lambda self: self.num_key_value_heads)
    n_embd = property(lambda self: self.hidden_size)
    n_positions = property(lambda self: self.max_position_embeddings)

    @property
    def share(self) -> ExpertShare:
        return ExpertShare(self.num_experts_published, self.expert_chips, self.expert_index)

    def window(self, l: int) -> int:
        """Keys a query of layer ``l`` reads, itself included; 0 = all."""
        return self.sliding_window if self.layer_types[l] == SLIDING else 0

    def is_sparse(self, l: int) -> bool:
        return self.mlp_layer_types[l] == "sparse"

    def serving_family(self):
        return ExaoneFamily(self)


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------

def _leaf_shapes(cfg: ExaoneMoEConfig) -> PyTree:
    """The tree, with (shape, kind) leaves: kind ``w`` is drawn normal with
    ``initializer_range``, ``one`` is a norm's gain."""
    E, D = cfg.hidden_size, cfg.head_dim
    H, KV = cfg.num_attention_heads, cfg.num_key_value_heads
    F, n = cfg.moe_intermediate_size, cfg.num_experts

    def ffn(lead, width):
        return {"w_gate": ((*lead, E, width), "w"), "w_up": ((*lead, E, width), "w"),
                "w_down": ((*lead, width, E), "w")}

    layers = []
    for l in range(cfg.num_hidden_layers):
        lp = {
            "norm_1": ((E,), "one"), "norm_2": ((E,), "one"),
            "attn": {
                "wqkv": ((E, (H + 2 * KV) * D), "w"), "wo": ((H * D, E), "w"),
                "q_norm": ((D,), "one"), "k_norm": ((D,), "one"),
            },
        }
        if cfg.is_sparse(l):
            lp["moe"] = {
                "router": ((E, cfg.num_experts_published), "w"),
                # drawn like a weight, not zero: s + b and s then select differently
                "bias": ((cfg.num_experts_published,), "w"),
                "experts": ffn((n,), F), "shared": ffn((), F),
            }
        else:
            lp["mlp"] = ffn((), cfg.intermediate_size)
        layers.append(lp)
    return {
        "embed": ((cfg.vocab_size, E), "w"), "head": ((E, cfg.vocab_size), "w"),
        "norm_f": ((E,), "one"), "layers": layers,
    }


def _is_leaf(x):
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[1], str)


def init_params(cfg: ExaoneMoEConfig, rng, dtype=None) -> PyTree:
    """Every leaf made on the device in ``dtype`` by a program of its own, so
    the set-up never holds more than the tree and one leaf's temporaries (a
    float32 tree beside its bf16 cast would not fit beside 7.4 GB)."""
    dtype = dtype or cfg.dtype
    shapes = _leaf_shapes(cfg)
    leaves, treedef = jax.tree_util.tree_flatten(shapes, is_leaf=_is_leaf)
    keys = jax.random.split(rng, len(leaves))
    std = cfg.initializer_range

    @functools.lru_cache(maxsize=None)
    def drawn(shape):  # one program a distinct shape, not one a leaf
        return jax.jit(lambda k: (jax.random.normal(k, shape, jnp.float32) * std).astype(dtype))

    def make(key, spec):
        shape, kind = spec
        return jnp.ones(shape, dtype) if kind == "one" else drawn(shape)(key)

    return jax.tree_util.tree_unflatten(treedef, [make(k, s) for k, s in zip(keys, leaves)])


def logical_axes(cfg: ExaoneMoEConfig) -> PyTree:
    """Logical axis names per leaf (``zero/partitioning.DEFAULT_LOGICAL_RULES``)."""
    def ax(spec):
        shape, kind = spec
        if len(shape) == 1:
            return (None,)
        if len(shape) == 3:
            return ("expert", *(("embed", "expert_mlp") if shape[1] == cfg.hidden_size else ("expert_mlp", "embed")))
        if shape[0] == cfg.vocab_size:
            return ("vocab", "embed")
        if shape[1] == cfg.vocab_size:
            return ("embed", "vocab")
        return ("embed", "mlp") if shape[0] == cfg.hidden_size else ("mlp", "embed")

    return jax.tree_util.tree_map(ax, _leaf_shapes(cfg), is_leaf=_is_leaf)


# ---------------------------------------------------------------------------
# the family's pieces
# ---------------------------------------------------------------------------

def rotary(x, positions, theta: float):
    """Half-split rotary: ``x [..., S, heads, D]`` at ``positions [..., S]``."""
    D = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = positions.astype(jnp.float32)[..., None, None] * inv      # [..., S, 1, D/2]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., : D // 2].astype(jnp.float32), x[..., D // 2:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


class ExaoneFamily:
    """What ``serving/model.py`` asks of a model (see its ``Family`` notes)."""

    prefill_block = 256   # the whole-prompt program attends in query blocks of this many
    kv_pools = 2          # a K and a V pool

    def __init__(self, cfg: ExaoneMoEConfig):
        self.cfg = cfg
        self.n_layer, self.n_head, self.n_kv_head = cfg.n_layer, cfg.n_head, cfg.n_kv_head
        self.head_dim, self.vocab_size, self.n_positions = cfg.head_dim, cfg.vocab_size, cfg.n_positions
        self.attn_impl = cfg.attn_impl
        self.v_width = cfg.head_dim
        self.windows = tuple(cfg.window(l) for l in range(cfg.n_layer))
        self.sparse_layers = tuple(l for l in range(cfg.n_layer) if cfg.is_sparse(l))
        self.experts_held = cfg.num_experts
        self.experts_per_token = cfg.num_experts_per_tok

    def embed(self, params, ids, positions):
        h = params["embed"][ids]
        return h[:, None, :] if ids.ndim == 1 else h  # the decode step: a token a slot

    def layer(self, params, l: int):
        return params["layers"][l]

    def qkv(self, lp, h, positions, l: int):
        """``h [B, S, E]`` (the residual stream) → ``q [B, S, H, D]``, ``k``,
        ``v [B, S, KV, D]``, normed per head and rotated where layer ``l``
        takes positions: what goes into the cache is what attention reads."""
        cfg = self.cfg
        H, KV, D = cfg.n_head, cfg.n_kv_head, cfg.head_dim
        with parts.part("norm"):
            u = rms_norm(h, lp["norm_1"], cfg.rms_norm_eps)
        qkv = u @ lp["attn"]["wqkv"]
        q, k, v = jnp.split(qkv, [H * D, (H + KV) * D], axis=-1)
        q = rms_norm(q.reshape(*q.shape[:-1], H, D), lp["attn"]["q_norm"], cfg.rms_norm_eps)
        k = rms_norm(k.reshape(*k.shape[:-1], KV, D), lp["attn"]["k_norm"], cfg.rms_norm_eps)
        v = v.reshape(*v.shape[:-1], KV, D)
        if self.windows[l]:
            q, k = rotary(q, positions, cfg.rope_theta), rotary(k, positions, cfg.rope_theta)
        return q, k, v

    def attn_out(self, lp, o, tp_axis=None):
        return o @ lp["attn"]["wo"]

    def mlp(self, lp, h, l: int, valid=None, tp_axis=None):
        """→ (the layer's MLP of the residual stream ``h [B, S, E]``, the
        tokens each held expert got ``[n_held]`` or ``None`` on a dense layer)."""
        cfg = self.cfg
        with parts.part("norm"):
            u = rms_norm(h, lp["norm_2"], cfg.rms_norm_eps)
        if "mlp" in lp:
            m = lp["mlp"]
            return gated_ffn(u, m["w_gate"], m["w_up"], m["w_down"]), None
        B, S, E = u.shape
        y, counts = expert_share_layer(
            lp["moe"], u.reshape(B * S, E), cfg.share, cfg.num_experts_per_tok,
            cfg.routed_scaling_factor, cfg.norm_topk_prob,
            None if valid is None else jnp.broadcast_to(valid, (B, S)).reshape(B * S),
        )
        return y.reshape(B, S, E), counts

    def logits(self, params, h):
        return rms_norm(h, params["norm_f"], self.cfg.rms_norm_eps) @ params["head"]


def forward(cfg: ExaoneMoEConfig, params: PyTree, input_ids) -> jnp.ndarray:
    """Whole-sequence logits ``[B, S, vocab]`` with no cache: the family's
    pieces under a dense masked softmax (for small sizes; the served path is
    ``serving/model.py``)."""
    fam = ExaoneFamily(cfg)
    B, S = input_ids.shape
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    h = fam.embed(params, input_ids, pos)
    i, j = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    rep = cfg.n_head // cfg.n_kv_head
    for l in range(cfg.n_layer):
        lp = fam.layer(params, l)
        q, k, v = fam.qkv(lp, h, pos, l)
        mask = (j <= i) & ((j > i - fam.windows[l]) if fam.windows[l] else True)
        qg = q.reshape(B, S, cfg.n_kv_head, rep, cfg.head_dim)
        s = jnp.einsum("bsgrd,btgd->bgrst", qg.astype(jnp.float32), k.astype(jnp.float32))
        p = jax.nn.softmax(jnp.where(mask, s / np.sqrt(cfg.head_dim), -1e30), axis=-1)
        o = jnp.einsum("bgrst,btgd->bsgrd", p, v.astype(jnp.float32)).astype(h.dtype)
        h = h + fam.attn_out(lp, o.reshape(B, S, -1))
        h = h + fam.mlp(lp, h, l)[0]
    return fam.logits(params, h)


def make_module(cfg: ExaoneMoEConfig) -> ModuleSpec:
    """For ``init_inference(model=...)``. No training path: ``loss_fn`` is
    absent on purpose (16 bytes a parameter do not fit the share one chip
    holds; ROADMAP.md)."""
    return ModuleSpec(
        init=lambda rng: init_params(cfg, rng),
        loss_fn=None,
        apply_fn=lambda params, batch: forward(cfg, params, batch["input_ids"]),
        logical_axes=logical_axes(cfg),
        num_layers=cfg.n_layer,
        extra={
            "config": cfg,
            # the inference engine makes the tree leaf by leaf in its own dtype
            "init_in_dtype": lambda rng, dtype: init_params(cfg, rng, dtype),
        },
    )
