"""The ``bailing_hybrid`` family (Ling-3.0-flash, the language model of
Ling-3.0-flash-VL), as one chip of several that share each layer serves a cut
of its depth.

Layers come in periods of ``layer_group_size`` (6): layer ``i`` is a KDA layer
(Kimi Delta Attention, arXiv:2510.26692: a delta rule over a matrix state a
head whose decay is a VECTOR over the key channels) unless ``(i + 1) % 6 ==
0``, then a latent (MLA) attention layer (``models/mla.py``, the query ONE
matrix: ``q_lora_rank`` null) with a head-wise output gate. The first
``first_k_dense_replace`` layers end in a dense FFN, the others in an expert
layer (sigmoid scores, a selection bias, the picks limited to ``topk_group`` of
``n_group`` groups, ``moe/expert_share.py``). ``E`` hidden, ``H`` heads, ``dk =
dv = head_dim``, ``K`` taps; no projection has a bias.

    norm(x; w) = x / sqrt(mean(x^2) + eps) * w
    u = norm(x; w_1)

    KDA:
      [q | k | v] = u [Wq | Wk | Wv]                       each [H, dk]
      c_t = silu(sum_j w_conv[:, j] . m_{t-K+1+j}),  m = [q | k | v]   depthwise, causal, no bias
      q <- q / sqrt(sum q^2 + 1e-6) / sqrt(dk);  k <- k / sqrt(sum k^2 + 1e-6)
      beta = sigmoid(u Wb)                                  [H]
      g    = kda_lower_bound * sigmoid(exp(A_log_h) * (u Wf + dt_bias))    [H, dk], float32
      S_h [dk, dv] float32, 0 at a request's start:
          S <- diag(exp(g)) S;  d = beta (v - S^T k);  S <- S + k d^T;  o = S^T q
      y = (o / sqrt(mean(o^2) + eps) * w_o) . sigmoid(u Wg)     per head
      out = y Wout

    MLA:  q = u Wq [H, nope + rope];  [c | kr] = u Wkv_a;  c <- norm(c; w_kv)
      k_h = [c W_uk_h | rot(kr)],  v_h = c W_uv_h;  interleaved rotary, theta, no scaling
      o_h = softmax(q_h k_h^T / sqrt(nope + rope), causal) v_h
      out = concat_h(o_h * sigmoid(u w_gate)_h) Wo          w_gate [E, H]: head-wise

    x <- x + out;  w = norm(x; w_2)
    x <- x + FFN(w)                                          the leading dense layers
       | x + sum_{e in sel, e held} w_e FFN_e(w) + FFN_shared(w)
    logits = norm(x_L; w_f) Whead

To the serving programs (``serving/model.py``) a KDA layer is a ``"lin"``
sub-block whose :meth:`Ling3Family.lin_gates` gives ``g [..., H, dk]``: the
programs and ``ops/pallas/gated_delta.py`` branch on that RANK (the kernels
``kda_step`` / ``kda_chunk``); the family states the decays' lower bound
(``lin_g_min``), which the chunk kernel's form rests on. An MLA layer is an
``"attn"`` sub-block over the ONE latent pool (``kv_pools`` 1): this is the
family that holds both. The output gate of either mixer is a projection of the
same normed stream: KDA's travels in ``lin_in``'s ``rest``, MLA's is made in
:meth:`after_attention`, which the protocol hands the stream.

The chip's share: ``num_experts`` held of ``num_experts_published`` (whole
routing groups, or a divisor of one), the router full width, the rows of the
vocabulary held. What the published config does not say and this module
assumes is listed in the configuration file that runs it
(``perfbench/configs/ling-3.0-flash-ep8-l12-serve-1chip.json``, ``assumed``).
The vision tower of the VL model is not built: its config has no key for it.
Only the served path lives here, and :func:`forward`, the same pieces over a
whole sequence with no cache.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from dataclasses import dataclass
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..moe.expert_share import ExpertShare, expert_share_layer, gated_ffn
from ..ops.layer_norm import rms_norm
from ..ops.pallas import gated_delta
from ..ops.pallas.selective_scan import conv_rows
from ..runtime.module import ModuleSpec
from ..telemetry import parts
from . import mla

PyTree = Any
LIN, ATTN = "lin", "attn"

# the published switches whose other setting this module does not build: key -> the value it builds
_BUILT = {
    "use_kda_lora": False, "no_kda_lora": True, "kda_safe_gate": True, "use_nGPT": False, "value_norm": False,
    "up_proj_norm": False, "scale_router_input": False, "linear_silu": True, "use_qk_norm": True,
    "use_mla_nope": False, "group_norm_size": 1, "num_shared_experts": 1, "tie_word_embeddings": False,
    "score_function": "sigmoid", "gated_attention_proj_granularity_type": "head_wise", "q_lora_rank": None,
    "moe_router_enable_expert_bias": True,
}


@dataclass(frozen=True)
class Ling3Config:
    vocab_size: int = 157184            # rows held here
    hidden_size: int = 2560
    intermediate_size: int = 6144       # a leading dense layer's FFN
    moe_intermediate_size: int = 768
    moe_shared_expert_intermediate_size: int = 768
    num_hidden_layers: int = 42
    layer_group_size: int = 6
    num_attention_heads: int = 32
    head_dim: int = 128                 # a KDA head's dk = dv
    num_kv_heads_for_linear_attn: int = 0   # 0: as many as heads
    short_conv_kernel_size: int = 4
    kda_lower_bound: float = -5.0
    kda_safe_gate: bool = True
    use_kda_lora: bool = False
    no_kda_lora: bool = True
    q_lora_rank: Optional[int] = None
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 6e6
    gated_attention_proj_granularity_type: str = "head_wise"
    first_k_dense_replace: int = 2
    num_experts: int = 512              # routed experts held here
    num_experts_published: int = 512    # the router's width
    expert_chips: int = 1               # expert_share: of how many chips
    expert_index: int = 0               # ... this is which
    num_experts_per_tok: int = 8
    num_shared_experts: int = 1
    n_group: int = 8
    topk_group: int = 4
    score_function: str = "sigmoid"
    moe_router_enable_expert_bias: bool = True
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    expert_swiglu_limit_list: tuple = ()
    share_expert_swiglu_limit_list: tuple = ()
    use_nGPT: bool = False
    value_norm: bool = False
    up_proj_norm: bool = False
    scale_router_input: bool = False
    linear_silu: bool = True
    use_qk_norm: bool = True
    use_mla_nope: bool = False
    group_norm_size: int = 1
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 131072
    tie_word_embeddings: bool = False
    initializer_range: float = 0.02
    attn_impl: str = "auto"             # auto | pallas | jnp (the latent kernels or their jnp fallbacks)
    lin_impl: str = "auto"              # auto | pallas | interpret | jnp (ops/pallas/gated_delta.kernel_runs)
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        for key, built in _BUILT.items():
            if getattr(self, key) != built:
                raise ValueError(f"{key}={getattr(self, key)!r}: {key}={built!r} is what this module builds")
        L = self.num_hidden_layers
        for key in ("expert_swiglu_limit_list", "share_expert_swiglu_limit_list"):
            if any(getattr(self, key)[:L]):
                raise ValueError(f"{key} is nonzero in one of the {L} layers kept: the clamp's form is not published and none is built")
        if self.num_kv_heads_for_linear_attn not in (0, self.num_attention_heads):
            raise ValueError(
                f"num_kv_heads_for_linear_attn={self.num_kv_heads_for_linear_attn}: a key head a value head "
                f"(0 or {self.num_attention_heads}) is what this module builds")
        if self.num_experts * self.expert_chips != self.num_experts_published:
            raise ValueError(
                f"num_experts={self.num_experts} held on each of {self.expert_chips} chips "
                f"is not the router's {self.num_experts_published}"
            )
        if not 0 <= self.expert_index < self.expert_chips:
            raise ValueError(f"expert_share index {self.expert_index} of {self.expert_chips} chips")
        if self.num_experts_published % self.n_group or not 1 <= self.topk_group <= self.n_group:
            raise ValueError(f"n_group={self.n_group}, topk_group={self.topk_group} over {self.num_experts_published} experts")
        group = self.num_experts_published // self.n_group
        if self.num_experts % group and group % self.num_experts:
            raise ValueError(
                f"num_experts={self.num_experts} held is neither whole routing groups of {group} nor a divisor of one")
        if not 0 <= self.first_k_dense_replace <= L:
            raise ValueError(f"first_k_dense_replace={self.first_k_dense_replace} of {L} layers")
        if self.qk_rope_head_dim % 2:
            raise ValueError("interleaved rotary pairs over an even qk_rope_head_dim is what this module builds")
        gated_delta.holds_decay(self.kda_lower_bound)

    @classmethod
    def from_dict(cls, d: dict, **overrides) -> "Ling3Config":
        """From the published keys (an HF ``config.json`` or a perfbench
        configuration file); keys this module does not know are ignored."""
        names = {f.name for f in dataclasses.fields(cls)}
        kw = {k: (tuple(v) if isinstance(v, list) else v) for k, v in d.items() if k in names}
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
    n_embd = property(lambda self: self.hidden_size)
    n_positions = property(lambda self: self.max_position_embeddings)
    # the cached row and the values inside it
    kv_width = property(lambda self: self.kv_lora_rank + self.qk_rope_head_dim)
    qk_head_dim = property(lambda self: self.qk_nope_head_dim + self.qk_rope_head_dim)
    # KDA: every head's keys (or values) side by side, and the convolved channels [q | k | v]
    lin_width = property(lambda self: self.num_attention_heads * self.head_dim)
    conv_width = property(lambda self: 3 * self.lin_width)

    def kind(self, i: int) -> str:
        return ATTN if (i + 1) % self.layer_group_size == 0 else LIN

    @property
    def share(self) -> ExpertShare:
        return ExpertShare(self.num_experts_published, self.expert_chips, self.expert_index)

    def serving_family(self):
        return Ling3Family(self)


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------

def _leaf_shapes(cfg: Ling3Config) -> PyTree:
    """The tree, with (shape, kind) leaves. ``w``: normal at
    ``initializer_range``; ``one`` / ``zero``: a gain, ``A_log``. Drawn so that
    a check against the reference SEES the recurrence (``models/qwen3_next.
    _leaf_shapes`` argues it): ``decay``, ``dt_bias`` a CHANNEL, the logit of
    ``ln 2 / (half-life x |kda_lower_bound|)`` with the half-lives log-uniform
    in 4 to 4 096 tokens (``A_log`` 0; ``u Wf`` has a std near 1, so a token
    moves its own decays by a factor of e either way, inside the bound);
    ``conv`` uniform in ``+-1 / sqrt(K)``; ``wide`` normal at 0.05 (``Wb``:
    beta spans (0.1, 0.9)). The selection bias is drawn like a weight, not
    zero: ``s + b`` and ``s`` then select differently."""
    E, H, d = cfg.hidden_size, cfg.num_attention_heads, cfg.head_dim
    n, N, W = cfg.num_experts, cfg.num_experts_published, cfg.lin_width

    def ffn(lead, width):
        return {"w_gate": ((*lead, E, width), "w"), "w_up": ((*lead, E, width), "w"),
                "w_down": ((*lead, width, E), "w")}

    layers = []
    for i in range(cfg.num_hidden_layers):
        lp = {"norm_1": ((E,), "one"), "norm_2": ((E,), "one")}
        if cfg.kind(i) == LIN:
            lp[LIN] = {
                "w_qkvg": ((E, cfg.conv_width + W), "w"),       # [q | k | v | the output gate]
                "w_fb": ((E, W + H), "wfb"),                      # [the decays' projection Wf | beta's Wb]
                "w_conv": ((cfg.conv_width, cfg.short_conv_kernel_size), "conv"),
                "a_log": ((H,), "zero"), "dt_bias": ((W,), "decay"),
                "norm_o": ((d,), "one"), "w_out": ((W, E), "w"),
            }
        else:
            lp[ATTN] = {**mla.attention_leaf_shapes(cfg), "w_gate": ((E, H), "w")}
        if i < cfg.first_k_dense_replace:
            lp["ffn"] = ffn((), cfg.intermediate_size)
        else:
            lp["moe"] = {"router": ((E, N), "w"), "bias": ((N,), "w"), "experts": ffn((n,), cfg.moe_intermediate_size),
                         "shared": ffn((), cfg.moe_shared_expert_intermediate_size)}
        layers.append(lp)
    return {"embed": ((cfg.vocab_size, E), "w"), "head": ((E, cfg.vocab_size), "w"),
            "norm_f": ((E,), "one"), "layers": layers}


def init_params(cfg: Ling3Config, rng, dtype=None) -> PyTree:
    """Every leaf made on the device in ``dtype`` by a program of its own, so
    the set-up never holds more than the tree and one leaf's temporaries."""
    dtype = dtype or cfg.dtype
    leaves, treedef = jax.tree_util.tree_flatten(_leaf_shapes(cfg), is_leaf=mla.is_leaf_spec)
    keys = jax.random.split(rng, len(leaves))
    W, bound = cfg.lin_width, abs(cfg.kda_lower_bound)

    @functools.lru_cache(maxsize=None)
    def drawn(shape, kind):  # one program a distinct shape and kind, not one a leaf
        def make(k):
            if kind == "decay":
                life = jnp.exp(jax.random.uniform(k, shape, jnp.float32, math.log(4.0), math.log(4096.0)))
                p = math.log(2.0) / (life * bound)
                return (jnp.log(p) - jnp.log1p(-p)).astype(dtype)            # sigmoid^-1
            if kind == "conv":
                b = 1.0 / math.sqrt(shape[-1])
                return jax.random.uniform(k, shape, jnp.float32, -b, b).astype(dtype)
            x = jax.random.normal(k, shape, jnp.float32)
            if kind == "wfb":   # Wf at the range of a weight, Wb wide
                return (x * jnp.where(jnp.arange(shape[-1]) < W, cfg.initializer_range, 0.05)).astype(dtype)
            return (x * cfg.initializer_range).astype(dtype)
        return jax.jit(make)

    def make(key, spec):
        shape, kind = spec
        if kind in ("one", "zero"):
            return jnp.full(shape, float(kind == "one"), dtype)
        return drawn(shape, kind)(key)

    return jax.tree_util.tree_unflatten(treedef, [make(k, s) for k, s in zip(keys, leaves)])


def logical_axes(cfg: Ling3Config) -> PyTree:
    """Logical axis names per leaf (``zero/partitioning.DEFAULT_LOGICAL_RULES``)."""
    def ffn(lead, mlp):
        return {"w_gate": (*lead, "embed", mlp), "w_up": (*lead, "embed", mlp), "w_down": (*lead, mlp, "embed")}

    def layer(i):
        lay = {"norm_1": (None,), "norm_2": (None,)}
        if cfg.kind(i) == LIN:
            lay[LIN] = {"w_qkvg": ("embed", "mlp"), "w_fb": ("embed", None), "w_conv": (None, None), "a_log": (None,),
                        "dt_bias": (None,), "norm_o": (None,), "w_out": ("mlp", "embed")}
        else:
            lay[ATTN] = {**mla.attention_axes(cfg), "w_gate": ("embed", None)}
        if i < cfg.first_k_dense_replace:
            lay["ffn"] = ffn((), "mlp")
        else:
            lay["moe"] = {"router": ("embed", None), "bias": (None,),
                          "experts": ffn(("expert",), "expert_mlp"), "shared": ffn((), "expert_mlp")}
        return lay

    return {"embed": ("vocab", "embed"), "head": ("embed", "vocab"), "norm_f": (None,),
            "layers": [layer(i) for i in range(cfg.num_hidden_layers)]}


# ---------------------------------------------------------------------------
# the family's pieces
# ---------------------------------------------------------------------------

def _norm(x, w, eps):
    with parts.part("norm"):
        return rms_norm(x, w, eps)


class Ling3Family(mla.LatentAttention):
    """What ``serving/model.py`` asks of a model (see its ``Family`` notes):
    ``"lin"`` sub-blocks (the pieces around the delta rule, whose decay is a
    vector: ``lin_gates`` gives ``g [..., H, dk]``) beside ``"attn"`` ones over
    ONE latent pool (the attention half is ``models/mla.LatentAttention``),
    and the combination its own (:meth:`after_attention`: the attention's
    head-wise gate, the dense FFN or the expert layer)."""

    prefill_block = 128   # the whole-prompt program attends (expanded) in query blocks of this many
    kv_pools = 1

    def __init__(self, cfg: Ling3Config):
        self.cfg = cfg
        L, H, d = cfg.n_layer, cfg.n_head, cfg.head_dim
        self.n_layer, self.n_head, self.n_kv_head = L, H, 1
        self.head_dim, self.v_width = cfg.kv_width, cfg.kv_lora_rank
        self.vocab_size, self.n_positions, self.attn_impl = cfg.vocab_size, cfg.n_positions, cfg.attn_impl
        self.sm_scale = 1.0 / math.sqrt(cfg.qk_head_dim)
        self.inv_freq = 1.0 / cfg.rope_theta ** (np.arange(0, cfg.qk_rope_head_dim, 2, dtype=np.float32) / cfg.qk_rope_head_dim)
        self.kinds = tuple(cfg.kind(i) for i in range(L))
        self.windows = (0,) * L
        self.sparse_layers = tuple(range(cfg.first_k_dense_replace, L))
        self.experts_held = cfg.num_experts
        self.experts_per_token = cfg.num_experts_per_tok
        self.expert_groups = cfg.n_group     # > 1: a report's last entry is the rows that kept a held group
        # a slot's state a "lin" sub-block: [H, dk, dv] float32, and the convolution's last K - 1 inputs
        self.lin_state = (H, d, d)
        self.lin_conv = (cfg.short_conv_kernel_size, cfg.conv_width)
        self.lin_impl = cfg.lin_impl
        self.lin_g_min = cfg.kda_lower_bound  # every decay a channel lies in (lin_g_min, 0)

    def query_scale(self, positions):
        return 1.0

    def embed(self, params, ids, positions):
        h = params["embed"][ids]
        return h[:, None, :] if ids.ndim == 1 else h  # the decode step: a token a slot

    def layer(self, params, l: int):
        return params["layers"][l]

    # -- a KDA sub-block, in the pieces the programs put the state between:
    # -- in, (convolution,) gates, (the delta rule,) out
    def lin_in(self, lp, h):
        """``h [..., E]`` → (``m [..., conv_width]``: what the convolution
        takes, ``[q | k | v]``; the rest of the row: the output gate's
        arguments ``[..., H dv]`` and ``[f | b] [..., H dk + H]`` in float32)."""
        m = lp[LIN]
        u = _norm(h, lp["norm_1"], self.cfg.rms_norm_eps)
        with parts.part("lin.proj"):
            p = u @ m["w_qkvg"]
            fb = jnp.matmul(u, m["w_fb"], preferred_element_type=jnp.float32)
            return p[..., :self.cfg.conv_width], (p[..., self.cfg.conv_width:], fb)

    def lin_taps(self, lp):
        """The convolution's taps ``[conv_width, K]`` (no bias)."""
        return lp[LIN]["w_conv"]

    @parts.scoped("lin.proj")
    def lin_gates(self, lp, c, rest):
        """The convolved rows ``c [..., conv_width]`` and :meth:`lin_in`'s rest
        → ``q``, ``k [..., H, dk]`` (unit length, ``q`` scaled), ``v [..., H,
        dv]``, ``g [..., H, dk]`` (a decay a key channel, in ``(kda_lower_bound,
        0)``), ``beta [..., H]``, float32."""
        cfg, m, f32 = self.cfg, lp[LIN], jnp.float32
        H, d, W = cfg.n_head, cfg.head_dim, cfg.lin_width
        heads = lambda x: x.reshape(*x.shape[:-1], H, d)  # noqa: E731
        c = c.astype(f32)
        unit = lambda x: x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)  # noqa: E731
        a = heads(rest[1][..., :W] + m["dt_bias"].astype(f32))
        g = cfg.kda_lower_bound * jax.nn.sigmoid(jnp.exp(m["a_log"].astype(f32))[:, None] * a)
        return (unit(heads(c[..., :W])) / math.sqrt(d), unit(heads(c[..., W:2 * W])), heads(c[..., 2 * W:]),
                g, jax.nn.sigmoid(rest[1][..., W:]))

    @parts.scoped("lin.proj")
    def lin_out(self, lp, o, rest, tp_axis=None):
        """The rule's ``o [..., H, dv]`` (float32) normed per head under a plain
        gain, gated by the sigmoid of its projection and projected."""
        m, f32 = lp[LIN], jnp.float32
        z = rest[0].astype(f32).reshape(o.shape)
        y = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + self.cfg.rms_norm_eps) * m["norm_o"].astype(f32)
        return (y * jax.nn.sigmoid(z)).reshape(*o.shape[:-2], -1).astype(m["w_out"].dtype) @ m["w_out"]

    # -- a latent attention sub-block: ``mla.LatentAttention``'s qkv and
    # -- qkv_expanded; the output meets a gate a head before ``wo``
    def _gated(self, lp, o, h):
        """``o [..., H, v_head_dim]`` times ``sigmoid(u w_gate) [..., H]``
        (float32), then ``wo``; ``u`` the normed stream ``h`` the attention read."""
        a = lp[ATTN]
        gate = jnp.matmul(_norm(h, lp["norm_1"], self.cfg.rms_norm_eps), a["w_gate"], preferred_element_type=jnp.float32)
        y = (o.astype(jnp.float32) * jax.nn.sigmoid(gate)[..., None]).astype(o.dtype)
        return y.reshape(*y.shape[:-2], -1) @ a["wo"]

    def attn_out(self, lp, o, tp_axis=None, h=None):
        """``o [B, S, H * v_width]``, the absorbed attention's output → through
        ``w_uv``, the head-wise gate, then ``wo``."""
        with parts.part("attn.core"):  # the values' half of the absorption belongs to the attention
            o = o.reshape(*o.shape[:-1], self.cfg.n_head, self.v_width)
            o = jnp.einsum("...hc,chv->...hv", o, lp[ATTN]["w_uv"])
        return self._gated(lp, o, h)

    def attn_out_expanded(self, lp, o, tp_axis=None, h=None):
        return self._gated(lp, o.reshape(*o.shape[:-1], self.cfg.n_head, self.cfg.v_head_dim), h)

    # -- the rest of a layer --------------------------------------------------
    def after_attention(self, lp, h, o, l: int, valid=None, tp_axis=None, carry=None, attn_out=None):
        """The rest of sub-block ``l`` → (the stream, ``carry`` as it came, the
        expert layer's report or None): the mixer's output in (a KDA's as the
        program hands it, an attention's through its gate: ``attn_out`` is
        then this family's own, absorbed or expanded), then the dense FFN or
        the expert layer."""
        cfg = self.cfg
        B, S, E = h.shape
        if LIN in lp:
            with parts.part("lin.proj"):
                h = h + attn_out(lp, o, tp_axis)
        else:
            with parts.part("attn.out"):
                h = h + (attn_out or self.attn_out)(lp, o, tp_axis, h)
        with parts.part("mlp"):
            w = _norm(h, lp["norm_2"], cfg.rms_norm_eps)
            if "ffn" in lp:
                f = lp["ffn"]
                return h + gated_ffn(w, f["w_gate"], f["w_up"], f["w_down"]), carry, None
            m, counts = expert_share_layer(
                lp["moe"], w.reshape(B * S, E), cfg.share, cfg.num_experts_per_tok, cfg.routed_scaling_factor,
                cfg.norm_topk_prob, None if valid is None else jnp.broadcast_to(valid, (B, S)).reshape(B * S),
                n_group=cfg.n_group, topk_group=cfg.topk_group,
            )
            return h + m.reshape(B, S, E), carry, counts

    def logits(self, params, h):
        return _norm(h, params["norm_f"], self.cfg.rms_norm_eps) @ params["head"]


def forward(cfg: Ling3Config, params: PyTree, input_ids, absorbed: bool = False) -> jnp.ndarray:
    """Whole-sequence logits ``[B, S, vocab]`` with no cache: the family's
    pieces, the delta rule token by token from a zero state
    (``gated_delta.recurrence``) and a dense masked softmax, per head or
    ``absorbed`` (multi-query on the cached row); for small sizes, the served
    path is ``serving/model.py``."""
    fam = Ling3Family(cfg)
    B, S = input_ids.shape
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    h = fam.embed(params, input_ids, pos)
    seen = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    K = cfg.short_conv_kernel_size
    for l, kind in enumerate(fam.kinds):
        lp = fam.layer(params, l)
        if kind == LIN:
            m, rest = fam.lin_in(lp, h)
            c, _ = conv_rows(fam.lin_taps(lp), jnp.zeros((), jnp.float32), m, jnp.zeros((B, K - 1, m.shape[-1]), m.dtype))
            q, k, v, g, beta = fam.lin_gates(lp, c, rest)
            o, _ = jax.vmap(lambda *a: gated_delta.recurrence(*a, jnp.zeros(fam.lin_state, jnp.float32)))(q, k, v, g, beta)
            h, _, _ = fam.after_attention(lp, h, fam.lin_out(lp, o, rest), l, attn_out=lambda lp, a, tp: a)
            continue
        if absorbed:
            q, row, _ = fam.qkv(lp, h, pos, l)
            k_ = jnp.broadcast_to(row, (B, S, cfg.n_head, row.shape[-1]))
            v, out = k_[..., : fam.v_width], fam.attn_out
        else:
            q, k_, v, _ = fam.qkv_expanded(lp, h, pos, l)
            out = fam.attn_out_expanded
        s = jnp.einsum("bshd,bthd->bhst", q.astype(jnp.float32), k_.astype(jnp.float32))
        p = jax.nn.softmax(jnp.where(seen, s * fam.sm_scale, -1e30), axis=-1)
        o = jnp.einsum("bhst,bthd->bshd", p, v.astype(jnp.float32)).astype(h.dtype).reshape(B, S, -1)
        h, _, _ = fam.after_attention(lp, h, o, l, attn_out=out)
    return fam.logits(params, h)


def make_module(cfg: Ling3Config) -> ModuleSpec:
    """For ``init_inference(model=...)``. No training path: ``loss_fn`` is
    absent on purpose (the chunked delta rule has no backward here, and 16
    bytes a parameter do not fit the share one chip holds; ROADMAP.md R7)."""
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
