"""The ``xing4_0`` family (Xing4.0-29B-A4B), as one chip's share of it serves it.

A pre-norm decoder with multi-head LATENT attention (``models/mla.py``: the
DeepSeek-V3 block, yarn-scaled interleaved rotary, the softmax scale with
yarn's ``m^2``, no position-scaled query), ``first_k_dense_replace`` leading
dense layers and then expert layers (``num_experts_per_tok`` of
``n_routed_experts_published`` sigmoid-routed experts beside a shared one,
``moe/expert_share.py``), whose residual is not one stream but ``n = hc_mult``
of them (mHC, arXiv 2512.24880). A token's residual is ``X`` in ``R^{n x E}``.
Every layer has two sub-blocks ``F``, the attention and the FFN, each with its
own ``phi``, ``b`` and gains ``a`` (``ops/pallas/hyper_connection.py`` has the
equations of the three maps):

    H_pre, H_post, H_res = maps(X; phi, b, a)     [n], [n], [n, n] doubly stochastic
    u     = sum_i H_pre[i] X[i]
    y     = F(rmsnorm_g(u))                       the sub-block's own pre-norm
    X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y

``X_0[i]`` is the token's embedding for every ``i``; the logits are
``head(rmsnorm_g(sum_i X_L[i]))``; untied output head.

To the serving programs (``serving/model.py``) the stream is ``[B, S, n E]``,
the streams side by side on the lanes: the programs pass it through the
family's pieces and never read its width. ``embed`` makes the ``n`` copies,
``qkv`` / ``qkv_expanded`` read the stream through the attention sub-block's
pre-map, :meth:`Xing4Family.after_attention` OWNS the rest of the layer (the
attention's write back, then the whole FFN sub-block with its own three maps),
``logits`` sums the streams. The attention sub-block's maps are computed ONCE,
in ``qkv``, and handed to ``after_attention`` in ``lp`` (``lp["handed"]``: the
``Family`` notes' hand-over; ``layer`` makes ``lp`` anew for every sub-block of
every trace).

Two of the config's sizes are shares, not the model's: ``n_routed_experts`` is
the number of routed experts HELD here (``expert_share`` says of how many
chips this is which one; the router keeps ``n_routed_experts_published``
columns), and ``vocab_size`` the rows of the vocabulary held. Everything else
is the published width. What the published config does not say and this
module assumes is listed in the configuration file that runs it
(``perfbench/configs/xing4.0-29b-a4b-ep8-l20-serve-1chip.json``, ``assumed``).
"""

from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass
from typing import Any

import jax.numpy as jnp

from ..moe.expert_share import ExpertShare, expert_share_layer, gated_ffn
from ..ops.layer_norm import rms_norm
from ..ops.pallas import hyper_connection as hc
from ..runtime.module import ModuleSpec
from ..telemetry import parts
from . import mla
from .mistral4 import yarn_inv_freq, yarn_sm_scale

PyTree = Any


@dataclass(frozen=True)
class Xing4Config:
    vocab_size: int = 131072            # rows held here
    hidden_size: int = 3584
    intermediate_size: int = 9216       # a leading dense layer's FFN
    moe_intermediate_size: int = 1024
    num_hidden_layers: int = 40
    num_attention_heads: int = 32
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    first_k_dense_replace: int = 2
    n_routed_experts: int = 64              # routed experts held here
    n_routed_experts_published: int = 64    # the router's width
    expert_chips: int = 1                   # expert_share: of how many chips
    expert_index: int = 0                   # ... this is which
    num_experts_per_tok: int = 4
    n_shared_experts: int = 1
    n_group: int = 1
    topk_group: int = 1
    scoring_func: str = "sigmoid"
    routed_scaling_factor: float = 2.0
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    # the residual: hc_mult streams, the Sinkhorn rounds, the statistic's eps, the clamp under the exponential
    hc_mult: int = 4
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    mhc_h_res_clamp_min: float = -30.0
    mhc_h_res_clamp_max: float = 30.0
    # rope_scaling (yarn)
    rope_theta: float = 10000.0
    rope_factor: float = 64.0
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 1.0
    original_max_position_embeddings: int = 4096
    max_position_embeddings: int = 262144
    initializer_range: float = 0.02
    attn_impl: str = "auto"             # auto | pallas | jnp: the latent kernels AND the mixing's pair, or their jnp forms
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if self.n_routed_experts * self.expert_chips != self.n_routed_experts_published:
            raise ValueError(
                f"n_routed_experts={self.n_routed_experts} held on each of {self.expert_chips} "
                f"chips is not the router's {self.n_routed_experts_published}"
            )
        if not 0 <= self.expert_index < self.expert_chips:
            raise ValueError(f"expert_share index {self.expert_index} of {self.expert_chips} chips")
        if self.hc_mult < 2:
            raise ValueError(f"hc_mult={self.hc_mult}: a residual of two or more streams is what this module builds")
        if self.n_group != 1 or self.topk_group != 1:
            raise ValueError(f"n_group={self.n_group}, topk_group={self.topk_group}: one group of experts is what this module builds")
        if self.scoring_func != "sigmoid":
            raise ValueError(f"scoring_func={self.scoring_func!r}: sigmoid scores are what this module builds")
        if self.n_shared_experts != 1:
            raise ValueError("one shared expert is what this module builds")
        if not 0 <= self.first_k_dense_replace <= self.num_hidden_layers:
            raise ValueError(f"first_k_dense_replace={self.first_k_dense_replace} of {self.num_hidden_layers} layers")
        if self.qk_rope_head_dim % 2:
            raise ValueError("interleaved rotary pairs over an even qk_rope_head_dim is what this module builds")

    @classmethod
    def from_dict(cls, d: dict, **overrides) -> "Xing4Config":
        """From the published keys (an HF ``config.json`` or a perfbench
        configuration file); keys this module does not know are ignored."""
        names = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in d.items() if k in names}
        rs = d.get("rope_scaling", {})
        for key, name in (("factor", "rope_factor"), ("beta_fast", "beta_fast"), ("beta_slow", "beta_slow"),
                          ("mscale", "mscale"), ("mscale_all_dim", "mscale_all_dim")):
            if key in rs:
                kw[name] = float(rs[key])
        if "original_max_position_embeddings" in rs:
            kw["original_max_position_embeddings"] = int(rs["original_max_position_embeddings"])
        # a configuration file keeps the published count beside the held one
        kw.setdefault("n_routed_experts_published", int(
            d.get("published", {}).get("n_routed_experts", d.get("n_routed_experts", cls.n_routed_experts))
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
    n_embd = property(lambda self: self.hidden_size)
    n_positions = property(lambda self: self.max_position_embeddings)
    # the cached row and the values inside it
    kv_width = property(lambda self: self.kv_lora_rank + self.qk_rope_head_dim)
    qk_head_dim = property(lambda self: self.qk_nope_head_dim + self.qk_rope_head_dim)
    # a sub-block's maps: H_pre and H_post, n each, and the n x n of H_res
    hc_maps = property(lambda self: 2 * self.hc_mult + self.hc_mult ** 2)

    @property
    def share(self) -> ExpertShare:
        return ExpertShare(self.n_routed_experts_published, self.expert_chips, self.expert_index)

    def serving_family(self):
        return Xing4Family(self)


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------

def _leaf_shapes(cfg: Xing4Config) -> PyTree:
    """The tree, with (shape, kind) leaves (``mla.draw_tree``): ``w`` drawn
    with ``initializer_range``, ``one`` a norm's gain or a map's gain ``a``
    (``a_pre, a_post, a_res``), ``phi`` a sub-block's projection, drawn with
    std ``1 / sqrt(n E)`` so that ``a p``, ``a q``, ``a r`` have a standard
    deviation near 1 over tokens (``xh`` has ``n E`` values of unit mean
    square): the maps then MOVE with the token, and a program that dropped
    their dynamic part would differ; ``hcb`` the maps' biases, std 1."""
    E, n = cfg.hidden_size, cfg.n_routed_experts
    n_pub, K, nE = cfg.n_routed_experts_published, cfg.hc_maps, cfg.hc_mult * cfg.hidden_size

    def ffn(lead, width):
        return {"w_gate": ((*lead, E, width), "w"), "w_up": ((*lead, E, width), "w"),
                "w_down": ((*lead, width, E), "w")}

    def layer(l):
        lay = {
            "norm_1": ((E,), "one"), "norm_2": ((E,), "one"),
            "attn": mla.attention_leaf_shapes(cfg),
            # the attention sub-block's maps, then the FFN sub-block's; phi is held [2n + n^2, n E]
            "hc": [{"phi": ((K, nE), "phi"), "a": ((3,), "one"), "b": ((K,), "hcb")}] * 2,
        }
        if l < cfg.first_k_dense_replace:
            lay["ffn"] = ffn((), cfg.intermediate_size)
        else:
            F = cfg.moe_intermediate_size
            lay["moe"] = {
                "router": ((E, n_pub), "w"),
                # drawn like a weight, not zero: s + b and s then select differently
                "bias": ((n_pub,), "w"),
                "experts": ffn((n,), F), "shared": ffn((), F),
            }
        return lay

    return {
        "embed": ((cfg.vocab_size, E), "w"), "head": ((E, cfg.vocab_size), "w"),
        "norm_f": ((E,), "one"), "layers": [layer(l) for l in range(cfg.num_hidden_layers)],
    }


def init_params(cfg: Xing4Config, rng, dtype=None) -> PyTree:
    """Every leaf made on the device in ``dtype`` by a program of its own
    (``mla.draw_tree``)."""
    return mla.draw_tree(_leaf_shapes(cfg), rng, dtype or cfg.dtype, cfg.initializer_range,
                         {"phi": 1.0 / math.sqrt(cfg.hc_mult * cfg.hidden_size), "hcb": 1.0})


def logical_axes(cfg: Xing4Config) -> PyTree:
    """Logical axis names per leaf (``zero/partitioning.DEFAULT_LOGICAL_RULES``)."""
    def ffn(lead, mlp):
        return {"w_gate": (*lead, "embed", mlp), "w_up": (*lead, "embed", mlp), "w_down": (*lead, mlp, "embed")}

    def layer(l):
        lay = {
            "norm_1": (None,), "norm_2": (None,), "attn": dict(mla.ATTENTION_AXES),
            "hc": [{"phi": (None, None), "a": (None,), "b": (None,)}] * 2,
        }
        if l < cfg.first_k_dense_replace:
            lay["ffn"] = ffn((), "mlp")
        else:
            lay["moe"] = {"router": ("embed", None), "bias": (None,),
                          "experts": ffn(("expert",), "expert_mlp"), "shared": ffn((), "expert_mlp")}
        return lay

    return {"embed": ("vocab", "embed"), "head": ("embed", "vocab"), "norm_f": (None,),
            "layers": [layer(l) for l in range(cfg.num_hidden_layers)]}


# ---------------------------------------------------------------------------
# the family's pieces
# ---------------------------------------------------------------------------

class Xing4Family(mla.LatentAttention):
    """What ``serving/model.py`` asks of a model (see its ``Family`` notes): a
    LATENT family (the attention half is ``models/mla.LatentAttention``) whose
    stream is ``n`` streams a token, ``[B, S, n E]``, and which OWNS THE
    COMBINATION (:meth:`after_attention`)."""

    prefill_block = 128   # the whole-prompt program attends (expanded) in query blocks of this many
    kv_pools = 1

    def __init__(self, cfg: Xing4Config):
        self.cfg = cfg
        self.n_layer, self.n_head, self.n_kv_head = cfg.n_layer, cfg.n_head, 1
        self.head_dim, self.v_width = cfg.kv_width, cfg.kv_lora_rank
        self.vocab_size, self.n_positions = cfg.vocab_size, cfg.n_positions
        self.attn_impl = cfg.attn_impl
        self.sm_scale = yarn_sm_scale(cfg)
        self.windows = (0,) * cfg.n_layer
        self.sparse_layers = tuple(range(cfg.first_k_dense_replace, cfg.n_layer))
        self.experts_held = cfg.n_routed_experts
        self.experts_per_token = cfg.num_experts_per_tok
        self.inv_freq = yarn_inv_freq(cfg)
        self.streams = cfg.hc_mult
        # what one row of the stream is on the device: the mixing's unit of traffic
        self.stream_row_width = cfg.hc_mult * cfg.hidden_size

    def query_scale(self, positions):
        return 1.0

    def embed(self, params, ids, positions):
        """The token's embedding in every stream: ``[B, S, n E]``."""
        h = jnp.tile(params["embed"][ids], self.streams)
        return h[:, None, :] if ids.ndim == 1 else h  # the decode step: a token a slot

    def layer(self, params, l: int):
        """Layer ``l``'s weights in a dict of this call's own: what ``qkv``
        hands to ``after_attention`` travels in it."""
        return dict(params["layers"][l])

    # -- the mixing ---------------------------------------------------------
    def pre(self, m, h):
        """The stream through one sub-block's pre-map → (``u [B, S, E]``, the
        sub-block's maps, float32)."""
        cfg = self.cfg
        return hc.hc_pre(
            h, m["phi"], m["a"], m["b"], n=cfg.hc_mult, eps=cfg.hc_eps, iters=cfg.hc_sinkhorn_iters,
            clamp=(cfg.mhc_h_res_clamp_min, cfg.mhc_h_res_clamp_max), impl=self.attn_impl,
        )

    def post(self, h, y, maps):
        return hc.hc_post(h, y, maps, n=self.cfg.hc_mult, impl=self.attn_impl)

    def qkv(self, lp, h, positions, l: int):
        u, lp["handed"] = self.pre(lp["hc"][0], h)
        return super().qkv(lp, u, positions, l)

    def qkv_expanded(self, lp, h, positions, l: int):
        u, lp["handed"] = self.pre(lp["hc"][0], h)
        return super().qkv_expanded(lp, u, positions, l)

    def after_attention(self, lp, h, o, l: int, valid=None, tp_axis=None, carry=None, attn_out=None):
        """The rest of layer ``l`` → (the stream, nothing carried, the expert
        layer's report or None): the attention's output written back through
        the maps ``qkv`` handed over, then the FFN sub-block (a dense FFN in
        the leading layers, else the expert layer) between its own."""
        cfg = self.cfg
        with parts.part("attn.out"):
            y = (attn_out or self.attn_out)(lp, o, tp_axis)
        h = self.post(h, y, lp.pop("handed"))
        u, maps = self.pre(lp["hc"][1], h)
        with parts.part("norm"):
            u = rms_norm(u, lp["norm_2"], cfg.rms_norm_eps)
        counts = None
        if "moe" in lp:
            B, S, E = u.shape
            with parts.part("mlp"):   # the shared expert's; the routed parts name their own
                y, counts = expert_share_layer(
                    lp["moe"], u.reshape(B * S, E), cfg.share, cfg.num_experts_per_tok,
                    cfg.routed_scaling_factor, cfg.norm_topk_prob,
                    None if valid is None else jnp.broadcast_to(valid, (B, S)).reshape(B * S),
                )
            y = y.reshape(B, S, E)
        else:
            f = lp["ffn"]
            with parts.part("mlp"):
                y = gated_ffn(u, f["w_gate"], f["w_up"], f["w_down"])
        return self.post(h, y, maps), None, counts

    def logits(self, params, h):
        """``h [..., n E]``: the streams summed (float32, one rounding), the
        final norm and the head."""
        with parts.part("hc.mix"):
            x = jnp.sum(h.reshape(*h.shape[:-1], self.streams, -1).astype(jnp.float32), axis=-2).astype(h.dtype)
        return rms_norm(x, params["norm_f"], self.cfg.rms_norm_eps) @ params["head"]


def forward(cfg: Xing4Config, params: PyTree, input_ids, absorbed: bool = False) -> jnp.ndarray:
    """Whole-sequence logits ``[B, S, vocab]`` with no cache (``mla.forward``:
    expanded, or ``absorbed``), for small sizes."""
    return mla.forward(Xing4Family(cfg), params, input_ids, absorbed)


def make_module(cfg: Xing4Config) -> ModuleSpec:
    """For ``init_inference(model=...)``. No training path: ``loss_fn`` is
    absent on purpose (16 bytes a parameter do not fit the share one chip
    holds beside a four-stream model's activations; ROADMAP.md)."""
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
