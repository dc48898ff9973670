"""The ``mistral4`` family (Mistral Small 4), as one chip's share of it serves it.

A pre-norm decoder with multi-head LATENT attention: the queries come through
a low-rank pair (``wq_a``, RMS norm, ``wq_b``), the keys and values of ALL
heads from one latent a token, ``[c | kr] = u wkv_a`` with ``c`` normed
(``kv_lora_rank`` wide) and ``kr`` one rotary key every head shares
(``qk_rope_head_dim`` wide). What is cached is the row ``[c | rot(kr)]``; a
head's key is ``[c w_uk_h | rot(kr)]`` and its value ``c w_uv_h`` (``w_uk`` /
``w_uv``: the published ``kv_b_proj``'s columns, by head, split into the key
part and the value part). Rotary positions are yarn-scaled with interleaved
pairs, the softmax scale carries yarn's ``m^2`` and the query the position
scale ``a_i`` (``llama_4_scaling_beta``). Every layer's MLP is
``num_experts_per_tok`` of ``n_routed_experts_published`` sigmoid-routed
experts plus a shared expert (``moe/expert_share.py``); untied output head.

Served ABSORBED (:meth:`Mistral4Family.qkv`): the query of head h is ``a_i
[q_nope_h w_uk_h^T | rot(q_rope_h)]``, as wide as the cached row, attention is
multi-query on that one row, whose first ``kv_lora_rank`` lanes are the
values, and ``w_uv`` is applied to the attention's output before ``wo``
(:meth:`attn_out`). Equal, in exact arithmetic, to the EXPANDED form
(:meth:`qkv_expanded`: per-head keys and values from the row through
``w_uk`` / ``w_uv``), which the whole-prompt program and :func:`forward` use.

Two of the config's sizes are shares, not the model's: ``n_routed_experts`` is
the number of routed experts HELD here (``expert_share`` says of how many
chips this is which one; the router keeps ``n_routed_experts_published``
columns), and ``vocab_size`` the rows of the vocabulary held. Everything else
is the published width. What the published config does not say and this
module assumes is listed in the configuration file that runs it
(``perfbench/configs/mistral-small-4-119b-ep8-serve-1chip.json``, ``assumed``).
"""

from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..moe.expert_share import ExpertShare, expert_share_layer
from ..ops.layer_norm import rms_norm
from ..runtime.module import ModuleSpec
from ..telemetry import parts
from . import mla
from .mla import rotary  # noqa: F401  (the family's rotary: shared with models/longcat_flash.py)

PyTree = Any


@dataclass(frozen=True)
class Mistral4Config:
    vocab_size: int = 131072            # rows held here
    hidden_size: int = 4096
    moe_intermediate_size: int = 2048
    num_hidden_layers: int = 36
    num_attention_heads: int = 32
    q_lora_rank: int = 1024
    kv_lora_rank: int = 256
    qk_nope_head_dim: int = 64
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    first_k_dense_replace: int = 0
    n_routed_experts: int = 128             # routed experts held here
    n_routed_experts_published: int = 128   # the router's width
    expert_chips: int = 1                   # expert_share: of how many chips
    expert_index: int = 0                   # ... this is which
    num_experts_per_tok: int = 4
    n_shared_experts: int = 1
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    # rope_parameters (yarn)
    rope_theta: float = 10000.0
    rope_factor: float = 128.0
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 1.0
    original_max_position_embeddings: int = 8192
    llama_4_scaling_beta: float = 0.1
    rope_interleave: bool = True
    max_position_embeddings: int = 1048576
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
        if self.n_shared_experts != 1 or self.first_k_dense_replace != 0:
            raise ValueError("one shared expert and no leading dense layer is what this module builds")
        if not self.rope_interleave or self.qk_rope_head_dim % 2:
            raise ValueError("interleaved rotary pairs over an even qk_rope_head_dim is what this module builds")

    @classmethod
    def from_dict(cls, d: dict, **overrides) -> "Mistral4Config":
        """From the published keys (an HF ``config.json`` or a perfbench
        configuration file); keys this module does not know are ignored."""
        names = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in d.items() if k in names}
        rp = d.get("rope_parameters", {})
        for key, name in (("rope_theta", "rope_theta"), ("factor", "rope_factor"),
                          ("beta_fast", "beta_fast"), ("beta_slow", "beta_slow"),
                          ("mscale", "mscale"), ("mscale_all_dim", "mscale_all_dim"),
                          ("llama_4_scaling_beta", "llama_4_scaling_beta")):
            if key in rp:
                kw[name] = float(rp[key])
        if "original_max_position_embeddings" in rp:
            kw["original_max_position_embeddings"] = int(rp["original_max_position_embeddings"])
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

    @property
    def share(self) -> ExpertShare:
        return ExpertShare(self.n_routed_experts_published, self.expert_chips, self.expert_index)

    @property
    def sm_scale(self) -> float:
        return yarn_sm_scale(self)

    def serving_family(self):
        return Mistral4Family(self)


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------

def _leaf_shapes(cfg: Mistral4Config) -> PyTree:
    """The tree, with (shape, kind) leaves: kind ``w`` is drawn normal with
    ``initializer_range``, ``one`` is a norm's gain."""
    E, F, n = cfg.hidden_size, cfg.moe_intermediate_size, cfg.n_routed_experts
    n_pub = cfg.n_routed_experts_published

    def ffn(lead):
        return {"w_gate": ((*lead, E, F), "w"), "w_up": ((*lead, E, F), "w"),
                "w_down": ((*lead, F, E), "w")}

    layer = {
        "norm_1": ((E,), "one"), "norm_2": ((E,), "one"),
        "attn": mla.attention_leaf_shapes(cfg),
        "moe": {
            "router": ((E, n_pub), "w"),
            # drawn like a weight, not zero: s + b and s then select differently
            "bias": ((n_pub,), "w"),
            "experts": ffn((n,)), "shared": ffn(()),
        },
    }
    return {
        "embed": ((cfg.vocab_size, E), "w"), "head": ((E, cfg.vocab_size), "w"),
        "norm_f": ((E,), "one"), "layers": [layer] * cfg.num_hidden_layers,
    }


def init_params(cfg: Mistral4Config, rng, dtype=None) -> PyTree:
    """Every leaf made on the device in ``dtype`` by a program of its own
    (``mla.draw_tree``)."""
    return mla.draw_tree(_leaf_shapes(cfg), rng, dtype or cfg.dtype, cfg.initializer_range)


def logical_axes(cfg: Mistral4Config) -> PyTree:
    """Logical axis names per leaf (``zero/partitioning.DEFAULT_LOGICAL_RULES``)."""
    def ffn(lead):
        return {"w_gate": (*lead, "embed", "expert_mlp"), "w_up": (*lead, "embed", "expert_mlp"),
                "w_down": (*lead, "expert_mlp", "embed")}

    layer = {
        "norm_1": (None,), "norm_2": (None,),
        "attn": dict(mla.ATTENTION_AXES),
        "moe": {"router": ("embed", None), "bias": (None,),
                "experts": ffn(("expert",)), "shared": ffn(())},
    }
    return {"embed": ("vocab", "embed"), "head": ("embed", "vocab"), "norm_f": (None,),
            "layers": [layer] * cfg.num_hidden_layers}


# ---------------------------------------------------------------------------
# the family's pieces
# ---------------------------------------------------------------------------

def yarn_sm_scale(cfg) -> float:
    """``m^2 / sqrt(qk_head_dim)``: yarn's attention factor, squared because
    it scales queries and keys alike, on the usual scale."""
    m = 1.0
    if cfg.mscale_all_dim and cfg.rope_factor > 1.0:
        m = 0.1 * cfg.mscale_all_dim * math.log(cfg.rope_factor) + 1.0
    return m * m / math.sqrt(cfg.qk_head_dim)


def yarn_inv_freq(cfg) -> np.ndarray:
    """The ``qk_rope_head_dim / 2`` rotary frequencies, yarn-scaled: below
    ``low`` a pair keeps its frequency, above ``high`` it is divided by
    ``rope_factor``, between them a linear ramp of the two."""
    D, base, orig = cfg.qk_rope_head_dim, cfg.rope_theta, cfg.original_max_position_embeddings
    f = base ** (-np.arange(0, D, 2, dtype=np.float64) / D)

    def dim_of(turns):  # the pair that makes ``turns`` rotations over the original context
        return D * math.log(orig / (turns * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(dim_of(cfg.beta_fast)), 0)
    high = min(math.ceil(dim_of(cfg.beta_slow)), D - 1)
    r = np.clip((np.arange(D // 2) - low) / max(high - low, 1e-3), 0.0, 1.0)
    return ((1.0 - r) * f + r * f / cfg.rope_factor).astype(np.float32)


class Mistral4Family(mla.LatentAttention):
    """What ``serving/model.py`` asks of a model (see its ``Family`` notes): a
    LATENT family (the attention half is ``models/mla.LatentAttention``). One pool, whose one "kv head" is the cached row: ``head_dim``
    is the row's width and ``v_width`` the leading lanes that are the values."""

    prefill_block = 128   # the whole-prompt program attends (expanded) in query blocks of this many
    kv_pools = 1

    def __init__(self, cfg: Mistral4Config):
        self.cfg = cfg
        self.n_layer, self.n_head, self.n_kv_head = cfg.n_layer, cfg.n_head, 1
        self.head_dim, self.v_width = cfg.kv_width, cfg.kv_lora_rank
        self.vocab_size, self.n_positions = cfg.vocab_size, cfg.n_positions
        self.attn_impl = cfg.attn_impl
        self.sm_scale = cfg.sm_scale
        self.windows = (0,) * cfg.n_layer
        self.sparse_layers = tuple(range(cfg.n_layer))
        self.experts_held = cfg.n_routed_experts
        self.experts_per_token = cfg.num_experts_per_tok
        self.inv_freq = yarn_inv_freq(cfg)

    def embed(self, params, ids, positions):
        h = params["embed"][ids]
        return h[:, None, :] if ids.ndim == 1 else h  # the decode step: a token a slot

    def layer(self, params, l: int):
        return params["layers"][l]

    def query_scale(self, positions):
        """The position-scaled query ``a_i [..., 1, 1]``: 1 inside the original
        context, then steps."""
        cfg = self.cfg
        scale = 1.0 + cfg.llama_4_scaling_beta * jnp.log1p(jnp.floor(
            positions.astype(jnp.float32) / cfg.original_max_position_embeddings
        ))
        return scale[..., None, None]

    def mlp(self, lp, h, l: int, valid=None, tp_axis=None):
        """→ (the layer's expert MLP of the residual stream ``h [B, S, E]``,
        the tokens each held expert got ``[n_held]``)."""
        cfg = self.cfg
        with parts.part("norm"):
            u = rms_norm(h, lp["norm_2"], cfg.rms_norm_eps)
        B, S, E = u.shape
        y, counts = expert_share_layer(
            lp["moe"], u.reshape(B * S, E), cfg.share, cfg.num_experts_per_tok,
            cfg.routed_scaling_factor, cfg.norm_topk_prob,
            None if valid is None else jnp.broadcast_to(valid, (B, S)).reshape(B * S),
        )
        return y.reshape(B, S, E), counts

    def logits(self, params, h):
        return rms_norm(h, params["norm_f"], self.cfg.rms_norm_eps) @ params["head"]


def forward(cfg: Mistral4Config, params: PyTree, input_ids, absorbed: bool = False) -> jnp.ndarray:
    """Whole-sequence logits ``[B, S, vocab]`` with no cache (``mla.forward``:
    expanded, or ``absorbed``), for small sizes."""
    return mla.forward(Mistral4Family(cfg), params, input_ids, absorbed)


def make_module(cfg: Mistral4Config) -> ModuleSpec:
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
