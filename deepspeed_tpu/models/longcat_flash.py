"""The ``longcat_flash`` family (LongCat-Flash-Chat), as one chip's share of it
serves it.

A decoder of shortcut-connected DOUBLE layers. One layer holds two latent
attentions (``models/mla.py``), two dense FFNs and ONE expert layer, which
reads the first attention's post-norm and is added after the second's FFN
(``x`` the residual stream; every norm an RMS norm; ``silu`` gated FFNs; no
bias):

    a1 = x  + MLA_1(norm_in_1(x))
    u1 = norm_post_1(a1)
    m  = MoE(u1)                         the shortcut: computed from u1, added at the end
    b1 = a1 + FFN_1(u1)
    a2 = b1 + MLA_2(norm_in_2(b1))
    u2 = norm_post_2(a2)
    y  = a2 + FFN_2(u2) + m

    MLA(u):  c_q = rms(u Wq_a);  q = s_q (c_q Wq_b),  s_q = sqrt(hidden / q_lora_rank)
             [c | kr] = u Wkv_a;  c = s_kv rms(c),  s_kv = sqrt(hidden / kv_lora_rank)
             scores q_h k_h / sqrt(nope + rope), plain interleaved rotary (``rope_theta``)
    MoE(u):  s = softmax(u W_r) over n_routed_experts + zero_expert_num columns
             sel = top_k(s + b);  w_e = routed_scaling_factor s_e  (not renormalised)
             m = sum_{e in sel, real, held} w_e FFN_e(u) + (sum_{e in sel, identity} w_e) u

(``moe/expert_share.py``: softmax scoring, identity columns, no shared expert.)
Untied output head.

To the serving programs (``serving/model.py``) a double layer is TWO cached
sub-blocks: ``n_layer`` is ``2 x num_layers``, the one latent pool has that
many layers, ``layer(params, l)`` gives sub-block l's weights, and
:meth:`LongcatFlashFamily.after_attention` owns the combination: sub-block 0
computes ``m`` and carries it, sub-block 1 adds it.

Two of the config's sizes are shares, not the model's: ``n_routed_experts`` is
the number of routed experts HELD here (``expert_share`` says of how many chips
this is which one; the router keeps ``n_routed_experts_published +
zero_expert_num`` columns), and ``vocab_size`` the rows of the vocabulary held.
Everything else is the published width. What the published config does not say
and this module assumes is listed in the configuration file that runs it
(``perfbench/configs/longcat-flash-560b-ep32-serve-1chip.json``, ``assumed``).
"""

from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass
from typing import Any

import jax.numpy as jnp
import numpy as np

from ..moe.expert_share import ExpertShare, expert_share_layer, gated_ffn
from ..ops.layer_norm import rms_norm
from ..runtime.module import ModuleSpec
from ..telemetry import parts
from . import mla

PyTree = Any


@dataclass(frozen=True)
class LongcatFlashConfig:
    vocab_size: int = 131072            # rows held here
    hidden_size: int = 6144
    ffn_hidden_size: int = 12288
    expert_ffn_hidden_size: int = 2048
    num_layers: int = 28                # double layers
    num_attention_heads: int = 64
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    mla_scale_q_lora: bool = True
    mla_scale_kv_lora: bool = True
    n_routed_experts: int = 512             # routed experts held here
    n_routed_experts_published: int = 512   # the router's real columns
    expert_chips: int = 1                   # expert_share: of how many chips
    expert_index: int = 0                   # ... this is which
    zero_expert_num: int = 256              # the router's identity columns, behind the real ones
    zero_expert_type: str = "identity"
    moe_topk: int = 12
    routed_scaling_factor: float = 6.0
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000000.0
    max_position_embeddings: int = 131072
    initializer_range: float = 0.02
    attn_impl: str = "auto"             # auto | pallas | jnp (the latent kernels or their jnp fallback)
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if self.n_routed_experts * self.expert_chips != self.n_routed_experts_published:
            raise ValueError(
                f"n_routed_experts={self.n_routed_experts} held on each of {self.expert_chips} "
                f"chips is not the router's {self.n_routed_experts_published}"
            )
        if not 0 <= self.expert_index < self.expert_chips:
            raise ValueError(f"expert_share index {self.expert_index} of {self.expert_chips} chips")
        if self.zero_expert_type != "identity":
            raise ValueError(
                f"zero_expert_type={self.zero_expert_type!r}: identity is the zero-compute expert this module builds"
            )
        if self.qk_rope_head_dim % 2:
            raise ValueError("interleaved rotary pairs over an even qk_rope_head_dim is what this module builds")

    @classmethod
    def from_dict(cls, d: dict, **overrides) -> "LongcatFlashConfig":
        """From the published keys (an HF ``config.json`` or a perfbench
        configuration file); keys this module does not know are ignored."""
        names = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in d.items() if k in names}
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
    n_layer = property(lambda self: 2 * self.num_layers)     # cached sub-blocks
    n_head = property(lambda self: self.num_attention_heads)
    n_embd = property(lambda self: self.hidden_size)
    n_positions = property(lambda self: self.max_position_embeddings)
    # the cached row and the values inside it
    kv_width = property(lambda self: self.kv_lora_rank + self.qk_rope_head_dim)
    qk_head_dim = property(lambda self: self.qk_nope_head_dim + self.qk_rope_head_dim)
    router_width = property(lambda self: self.n_routed_experts_published + self.zero_expert_num)

    @property
    def share(self) -> ExpertShare:
        return ExpertShare(self.n_routed_experts_published, self.expert_chips, self.expert_index,
                           self.zero_expert_num)

    def serving_family(self):
        return LongcatFlashFamily(self)


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------

def _leaf_shapes(cfg: LongcatFlashConfig) -> PyTree:
    """The tree, with (shape, kind) leaves (``mla.draw_tree``): ``w`` drawn
    with ``initializer_range``, ``one`` a norm's gain, ``bias`` the router's
    selection bias, drawn at the scale of a softmax score (``1 /
    router_width``): at a weight's 0.02 it would be 15 times the mean score
    and the selection the same for every token."""
    E, F, X, n = cfg.hidden_size, cfg.ffn_hidden_size, cfg.expert_ffn_hidden_size, cfg.n_routed_experts

    def ffn(lead, width):
        return {"w_gate": ((*lead, E, width), "w"), "w_up": ((*lead, E, width), "w"),
                "w_down": ((*lead, width, E), "w")}

    layer = {
        "norm_in": [((E,), "one")] * 2, "norm_post": [((E,), "one")] * 2,
        "attn": [mla.attention_leaf_shapes(cfg)] * 2,
        "ffn": [ffn((), F)] * 2,
        "moe": {"router": ((E, cfg.router_width), "w"), "bias": ((cfg.router_width,), "bias"),
                "experts": ffn((n,), X)},
    }
    return {
        "embed": ((cfg.vocab_size, E), "w"), "head": ((E, cfg.vocab_size), "w"),
        "norm_f": ((E,), "one"), "layers": [layer] * cfg.num_layers,
    }


def init_params(cfg: LongcatFlashConfig, rng, dtype=None) -> PyTree:
    """Every leaf made on the device in ``dtype`` by a program of its own."""
    return mla.draw_tree(_leaf_shapes(cfg), rng, dtype or cfg.dtype, cfg.initializer_range,
                         {"bias": 1.0 / cfg.router_width})


def logical_axes(cfg: LongcatFlashConfig) -> PyTree:
    """Logical axis names per leaf (``zero/partitioning.DEFAULT_LOGICAL_RULES``)."""
    def ffn(lead, mlp):
        return {"w_gate": (*lead, "embed", mlp), "w_up": (*lead, "embed", mlp), "w_down": (*lead, mlp, "embed")}

    layer = {
        "norm_in": [(None,)] * 2, "norm_post": [(None,)] * 2,
        "attn": [dict(mla.ATTENTION_AXES)] * 2,
        "ffn": [ffn((), "mlp")] * 2,
        "moe": {"router": ("embed", None), "bias": (None,), "experts": ffn(("expert",), "expert_mlp")},
    }
    return {"embed": ("vocab", "embed"), "head": ("embed", "vocab"), "norm_f": (None,),
            "layers": [layer] * cfg.num_layers}


# ---------------------------------------------------------------------------
# the family's pieces
# ---------------------------------------------------------------------------

class LongcatFlashFamily(mla.LatentAttention):
    """What ``serving/model.py`` asks of a model (see its ``Family`` notes): a
    LATENT family (the attention half is ``models/mla.LatentAttention``) that
    OWNS THE COMBINATION (:meth:`after_attention`). ``n_layer`` counts cached
    sub-blocks, two a double layer; ``sparse_layers`` are the first of each
    pair, where the expert layer is computed."""

    prefill_block = 128   # the whole-prompt program attends (expanded) in query blocks of this many
    kv_pools = 1

    def __init__(self, cfg: LongcatFlashConfig):
        self.cfg = cfg
        self.n_layer, self.n_head, self.n_kv_head = cfg.n_layer, cfg.n_head, 1
        self.head_dim, self.v_width = cfg.kv_width, cfg.kv_lora_rank
        self.vocab_size, self.n_positions = cfg.vocab_size, cfg.n_positions
        self.attn_impl = cfg.attn_impl
        self.sm_scale = 1.0 / math.sqrt(cfg.qk_head_dim)
        self.windows = (0,) * cfg.n_layer
        self.sparse_layers = tuple(range(0, cfg.n_layer, 2))
        self.experts_held = cfg.n_routed_experts
        self.experts_per_token = cfg.moe_topk
        self.zero_experts = cfg.zero_expert_num
        D = cfg.qk_rope_head_dim
        self.inv_freq = (cfg.rope_theta ** (-np.arange(0, D, 2, dtype=np.float64) / D)).astype(np.float32)
        self.q_lora_scale = math.sqrt(cfg.hidden_size / cfg.q_lora_rank) if cfg.mla_scale_q_lora else 1.0
        self.kv_lora_scale = math.sqrt(cfg.hidden_size / cfg.kv_lora_rank) if cfg.mla_scale_kv_lora else None

    def query_scale(self, positions):
        return self.q_lora_scale

    def embed(self, params, ids, positions):
        h = params["embed"][ids]
        return h[:, None, :] if ids.ndim == 1 else h  # the decode step: a token a slot

    def layer(self, params, l: int):
        """Sub-block ``l``'s weights under the names the shared attention
        reads (``norm_1``, ``attn``), its post-attention norm and dense FFN,
        and, on the first of a pair, the double layer's expert layer."""
        lay, j = params["layers"][l // 2], l % 2
        lp = {"norm_1": lay["norm_in"][j], "norm_2": lay["norm_post"][j], "attn": lay["attn"][j],
              "ffn": lay["ffn"][j]}
        if j == 0:
            lp["moe"] = lay["moe"]
        return lp

    def after_attention(self, lp, h, o, l: int, valid=None, tp_axis=None, carry=None, attn_out=None):
        """The rest of sub-block ``l`` → (the stream, what the next sub-block
        is handed, the expert layer's report or None). The first of a pair
        computes the expert layer ``m`` from its post-attention norm and
        carries it; the second adds it after its own dense FFN."""
        cfg = self.cfg
        with parts.part("attn.out"):
            a = h + (attn_out or self.attn_out)(lp, o, tp_axis)
        with parts.part("norm"):
            u = rms_norm(a, lp["norm_2"], cfg.rms_norm_eps)
        counts = None
        if "moe" in lp:
            B, S, E = u.shape
            m, counts = expert_share_layer(
                lp["moe"], u.reshape(B * S, E), cfg.share, cfg.moe_topk, cfg.routed_scaling_factor,
                False, None if valid is None else jnp.broadcast_to(valid, (B, S)).reshape(B * S),
                scoring="softmax",
            )
            carry = m.reshape(B, S, E)
        f = lp["ffn"]
        with parts.part("mlp.dense"):
            h = a + gated_ffn(u, f["w_gate"], f["w_up"], f["w_down"])
        if "moe" in lp:
            return h, carry, counts
        with parts.part("moe.route"):   # the shortcut's add
            return h + carry, None, None

    def logits(self, params, h):
        return rms_norm(h, params["norm_f"], self.cfg.rms_norm_eps) @ params["head"]


def forward(cfg: LongcatFlashConfig, params: PyTree, input_ids, absorbed: bool = False) -> jnp.ndarray:
    """Whole-sequence logits ``[B, S, vocab]`` with no cache (``mla.forward``:
    expanded, or ``absorbed``), for small sizes."""
    return mla.forward(LongcatFlashFamily(cfg), params, input_ids, absorbed)


def make_module(cfg: LongcatFlashConfig) -> ModuleSpec:
    """For ``init_inference(model=...)``. No training path: ``loss_fn`` is
    absent on purpose (16 bytes a parameter do not fit ONE double layer of the
    share one chip holds; ROADMAP.md)."""
    return ModuleSpec(
        init=lambda rng: init_params(cfg, rng),
        loss_fn=None,
        apply_fn=lambda params, batch: forward(cfg, params, batch["input_ids"]),
        logical_axes=logical_axes(cfg),
        num_layers=cfg.num_layers,
        extra={
            "config": cfg,
            # the inference engine makes the tree leaf by leaf in its own dtype
            "init_in_dtype": lambda rng, dtype: init_params(cfg, rng, dtype),
        },
    )
