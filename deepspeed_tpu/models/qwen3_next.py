"""The ``qwen3_next`` family (Qwen3-Next-80B-A3B), as one chip of several that
share each layer serves a cut of its depth.

Layers come in periods of ``full_attention_interval`` (4): layer ``i`` is a
Gated DeltaNet layer (linear attention: a matrix-valued recurrent state a head;
Gated Delta Networks, arXiv:2412.06464) unless ``(i + 1) % 4 == 0``, then a
gated softmax-attention layer; every layer ends in the same expert block. ``E``
hidden; no projection has a bias.

    norm(x; w) = x / sqrt(mean(x^2) + eps) * (1 + w)       "zero-centred" gain, float32
    u = norm(x; w_in)

    Gated DeltaNet: Hk key heads, Hv value heads (r = Hv / Hk), dk, dv, K taps
      [q | k | v | z] = u Wqkvz          q, k [Hk, dk]; v, z [Hv, dv]
      [b | a]         = u Wba            [Hv] each
      c_t = silu(sum_j w_conv[:, j] . m_{t-K+1+j}),  m = [q | k | v]   depthwise, causal, no bias
      beta = sigmoid(b);  g = -exp(A_log) . softplus(a + dt_bias)      float32, g <= 0
      q <- q / sqrt(sum q^2 + 1e-6) / sqrt(dk);  k <- k / sqrt(sum k^2 + 1e-6)
      value head h reads key head h // r;  S_h [dk, dv] float32, 0 at a request's start:
          S <- exp(g) S;  d = beta (v - S^T k);  S <- S + k d^T;  o = S^T q
      y = (o / sqrt(mean(o^2) + eps) * w_o) . silu(z)      per head; w_o a PLAIN gain
      out = y Wout

    gated attention: H heads, Hkv kv heads, D lanes
      q = u Wq, gate = u Wg [H, D];  [k | v] = u Wkv [Hkv, D] each
      q <- norm(q; w_qn), k <- norm(k; w_kn) over D; rotary on the first
      partial_rotary_factor . D lanes (half-split pairs), theta
      o = softmax(q k^T / sqrt(D), causal) v;  out = (o . sigmoid(gate)) Wo

    x <- x + out;  w = norm(x; w_post)
    p = softmax(w Wr) over ALL published experts, float32; top-k, renormalised
    x <- x + sum_{e picked, held} p_e FFN_e(w) + sigmoid(w . w_sg) FFN_shared(w)
    logits = norm(x_L; w_f) Whead

To the serving programs (``serving/model.py``) a DeltaNet layer is a ``"lin"``
sub-block: the family states ``lin_state`` (``(Hv, dk, dv)``: a slot's state a
sub-block, float32) and ``lin_conv`` (taps, convolved channels) and gives the
pieces the programs put the state between, :meth:`Qwen3NextFamily.lin_in`,
:meth:`lin_taps`, :meth:`lin_gates`, :meth:`lin_out`; the recurrence itself is
``ops/pallas/gated_delta.py``'s. An attention layer is an ``"attn"`` sub-block
over the paged K/V pools; its output gate is a projection of the SAME normed
stream, so :meth:`after_attention`, which the protocol hands the stream, makes
it there (nothing travels beside ``q``, ``k``, ``v``).

The chip's share (``moe/expert_share.py``): ``num_experts`` held of
``num_experts_published``, the router full width, the rows of the vocabulary
held. What the published config does not say and this module assumes is
listed in the configuration file that runs it
(``perfbench/configs/qwen3-next-80b-ep8-l12-serve-1chip.json``, ``assumed``).
Only the served path lives here, and :func:`forward`, the same pieces over a
whole sequence with no cache.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..moe.expert_share import ExpertShare, expert_share_layer
from ..ops.layer_norm import rms_norm
from ..ops.pallas import gated_delta
from ..ops.pallas.selective_scan import conv_rows
from ..runtime.module import ModuleSpec
from ..telemetry import parts
from .exaone_moe import rotary

PyTree = Any
LIN, ATTN = "lin", "attn"


@dataclass(frozen=True)
class Qwen3NextConfig:
    vocab_size: int = 151936            # rows held here
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    full_attention_interval: int = 4
    num_attention_heads: int = 16
    num_key_value_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 1e7
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_conv_kernel_dim: int = 4
    num_experts: int = 512              # routed experts held here
    num_experts_published: int = 512    # the router's width
    expert_chips: int = 1               # expert_share: of how many chips
    expert_index: int = 0               # ... this is which
    num_experts_per_tok: int = 10
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 262144
    tie_word_embeddings: bool = False
    initializer_range: float = 0.02
    attn_impl: str = "auto"             # auto | pallas (the paged kernels or their jnp fallbacks)
    lin_impl: str = "auto"              # auto | pallas | interpret | jnp (ops/pallas/gated_delta.kernel_runs)
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if self.num_experts * self.expert_chips != self.num_experts_published:
            raise ValueError(
                f"num_experts={self.num_experts} held on each of {self.expert_chips} chips "
                f"is not the router's {self.num_experts_published}"
            )
        if not 0 <= self.expert_index < self.expert_chips:
            raise ValueError(f"expert_share index {self.expert_index} of {self.expert_chips} chips")
        if self.num_attention_heads % self.num_key_value_heads or self.linear_num_value_heads % self.linear_num_key_heads:
            raise ValueError("query heads must divide by kv heads, value heads by key heads")
        if self.tie_word_embeddings:
            raise ValueError("an untied head is what this module builds")

    @classmethod
    def from_dict(cls, d: dict, **overrides) -> "Qwen3NextConfig":
        """From the published keys (an HF ``config.json`` or a perfbench
        configuration file); keys this module does not know are ignored."""
        names = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in d.items() if k in names}
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
    rotary_dim = property(lambda self: int(self.head_dim * self.partial_rotary_factor))
    key_width = property(lambda self: self.linear_num_key_heads * self.linear_key_head_dim)
    value_width = property(lambda self: self.linear_num_value_heads * self.linear_value_head_dim)
    conv_width = property(lambda self: 2 * self.key_width + self.value_width)       # the convolved channels [q | k | v]

    def kind(self, i: int) -> str:
        return ATTN if (i + 1) % self.full_attention_interval == 0 else LIN

    @property
    def share(self) -> ExpertShare:
        return ExpertShare(self.num_experts_published, self.expert_chips, self.expert_index)

    def serving_family(self):
        return Qwen3NextFamily(self)


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------

def _leaf_shapes(cfg: Qwen3NextConfig) -> PyTree:
    """The tree, with (shape, kind) leaves. ``w``: normal at
    ``initializer_range`` (a zero-centred gain too: ``1 + w`` is then near 1
    and a plain ``w`` would be seen); ``one``: the DeltaNet output norm's plain
    gain. Drawn so that a check against the reference SEES the recurrence (the
    published initialisation, ``A`` uniform in (0, 16) and ``dt_bias`` 1, gives
    ``exp(g)`` near e^-20 a token: a state that forgets at once): ``decay``
    (``A_log`` 0 beside ``dt_bias`` whose softplus is ``ln 2 / half-life``, the
    half-lives log-uniform in 4 to 4 096 tokens over the heads), ``conv``
    uniform in ``+-1 / sqrt(K)`` (a depthwise convolution's fan-in), ``wide``
    normal at 0.05 (``Wba``: ``b`` and ``a`` of a std near 2, so that beta
    spans (0.1, 0.9) and a token moves its decay)."""
    E, D, F = cfg.hidden_size, cfg.head_dim, cfg.moe_intermediate_size
    H, KV, Hv, dv = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.linear_num_value_heads, cfg.linear_value_head_dim
    n, N, Fs = cfg.num_experts, cfg.num_experts_published, cfg.shared_expert_intermediate_size

    def ffn(lead, width):
        return {"w_gate": ((*lead, E, width), "w"), "w_up": ((*lead, E, width), "w"),
                "w_down": ((*lead, width, E), "w")}

    layers = []
    for i in range(cfg.num_hidden_layers):
        lp = {
            "norm_1": ((E,), "w"), "norm_2": ((E,), "w"),
            "moe": {
                "router": ((E, N), "w"), "bias": ((N,), "zero"),      # the published router has no selection bias
                "experts": ffn((n,), F), "shared": ffn((), Fs), "shared_gate": ((E, 1), "w"),
            },
        }
        if cfg.kind(i) == LIN:
            lp[LIN] = {
                "w_qkvz": ((E, cfg.conv_width + cfg.value_width), "w"), "w_ba": ((E, 2 * Hv), "wide"),
                "w_conv": ((cfg.conv_width, cfg.linear_conv_kernel_dim), "conv"),
                "a_log": ((Hv,), "zero"), "dt_bias": ((Hv,), "decay"),
                "norm_o": ((dv,), "one"), "w_out": ((cfg.value_width, E), "w"),
            }
        else:
            lp[ATTN] = {
                "wq": ((E, H * D), "w"), "wg": ((E, H * D), "w"), "wkv": ((E, 2 * KV * D), "w"), "wo": ((H * D, E), "w"),
                "q_norm": ((D,), "w"), "k_norm": ((D,), "w"),
            }
        layers.append(lp)
    return {"embed": ((cfg.vocab_size, E), "w"), "head": ((E, cfg.vocab_size), "w"),
            "norm_f": ((E,), "w"), "layers": layers}


def _is_leaf(x):
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[1], str)


def init_params(cfg: Qwen3NextConfig, rng, dtype=None) -> PyTree:
    """Every leaf made on the device in ``dtype`` by a program of its own, so
    the set-up never holds more than the tree and one leaf's temporaries."""
    dtype = dtype or cfg.dtype
    leaves, treedef = jax.tree_util.tree_flatten(_leaf_shapes(cfg), is_leaf=_is_leaf)
    keys = jax.random.split(rng, len(leaves))

    @functools.lru_cache(maxsize=None)
    def drawn(shape, kind):  # one program a distinct shape and kind, not one a leaf
        def make(k):
            if kind == "decay":
                rate = math.log(2.0) / jnp.exp(jax.random.uniform(k, shape, jnp.float32, math.log(4.0), math.log(4096.0)))
                return (rate + jnp.log(-jnp.expm1(-rate))).astype(dtype)   # softplus^-1
            if kind == "conv":
                bound = 1.0 / math.sqrt(shape[-1])
                return jax.random.uniform(k, shape, jnp.float32, -bound, bound).astype(dtype)
            std = 0.05 if kind == "wide" else cfg.initializer_range
            return (jax.random.normal(k, shape, jnp.float32) * std).astype(dtype)
        return jax.jit(make)

    def make(key, spec):
        shape, kind = spec
        if kind in ("one", "zero"):
            return jnp.full(shape, float(kind == "one"), dtype)
        return drawn(shape, kind)(key)

    return jax.tree_util.tree_unflatten(treedef, [make(k, s) for k, s in zip(keys, leaves)])


def logical_axes(cfg: Qwen3NextConfig) -> PyTree:
    """Logical axis names per leaf (``zero/partitioning.DEFAULT_LOGICAL_RULES``)."""
    def ax(spec):
        shape, _ = spec
        if len(shape) == 1:
            return (None,)
        if len(shape) == 3:
            return ("expert", *(("embed", "expert_mlp") if shape[1] == cfg.hidden_size else ("expert_mlp", "embed")))
        if shape[0] == cfg.vocab_size:
            return ("vocab", "embed")
        if shape[1] == cfg.vocab_size:
            return ("embed", "vocab")
        if shape[0] == cfg.hidden_size:
            return ("embed", "mlp")
        return ("mlp", "embed") if shape[1] == cfg.hidden_size else (None, None)

    return jax.tree_util.tree_map(ax, _leaf_shapes(cfg), is_leaf=_is_leaf)


# ---------------------------------------------------------------------------
# the family's pieces
# ---------------------------------------------------------------------------

def _norm(x, w, eps):
    """The zero-centred norm: the gain is ``1 + w``."""
    with parts.part("norm"):
        return rms_norm(x, 1.0 + w.astype(jnp.float32), eps)


class Qwen3NextFamily:
    """What ``serving/model.py`` asks of a model (see its ``Family`` notes):
    ``"lin"`` sub-blocks (the pieces around the gated delta rule) and ``"attn"``
    ones over the paged pools, and the combination its own
    (:meth:`after_attention`: the attention's output gate, the expert block)."""

    prefill_block = 256   # the whole-prompt program attends in query blocks of this many
    kv_pools = 2          # a K and a V pool

    def __init__(self, cfg: Qwen3NextConfig):
        self.cfg = cfg
        L = cfg.n_layer
        self.n_layer, self.n_head, self.n_kv_head = L, cfg.n_head, cfg.n_kv_head
        self.head_dim = self.v_width = cfg.head_dim
        self.vocab_size, self.n_positions, self.attn_impl = cfg.vocab_size, cfg.n_positions, cfg.attn_impl
        self.kinds = tuple(cfg.kind(i) for i in range(L))
        self.windows = (0,) * L
        self.sparse_layers = tuple(range(L))
        self.experts_held = cfg.num_experts
        self.experts_per_token = cfg.num_experts_per_tok
        # a slot's state a "lin" sub-block: [Hv, dk, dv] float32, and the convolution's last K - 1 inputs
        self.lin_state = (cfg.linear_num_value_heads, cfg.linear_key_head_dim, cfg.linear_value_head_dim)
        self.lin_conv = (cfg.linear_conv_kernel_dim, cfg.conv_width)
        self.lin_impl = cfg.lin_impl

    def embed(self, params, ids, positions):
        h = params["embed"][ids]
        return h[:, None, :] if ids.ndim == 1 else h  # the decode step: a token a slot

    def layer(self, params, l: int):
        return params["layers"][l]

    # -- a DeltaNet sub-block, in the pieces the programs put the state
    # -- between: in, (convolution,) gates, (the delta rule,) out
    def lin_in(self, lp, h):
        """``h [..., E]`` → (``m [..., conv_width]``: what the convolution
        takes, ``[q | k | v]``; the rest of the row: ``z [..., Hv dv]`` and
        ``[b | a] [..., 2 Hv]`` in float32)."""
        m = lp[LIN]
        u = _norm(h, lp["norm_1"], self.cfg.rms_norm_eps)
        with parts.part("lin.proj"):
            p = u @ m["w_qkvz"]
            ba = jnp.matmul(u, m["w_ba"], preferred_element_type=jnp.float32)
            return p[..., :self.cfg.conv_width], (p[..., self.cfg.conv_width:], ba)

    def lin_taps(self, lp):
        """The convolution's taps ``[conv_width, K]`` (no bias)."""
        return lp[LIN]["w_conv"]

    @parts.scoped("lin.proj")
    def lin_gates(self, lp, c, rest):
        """The convolved rows ``c [..., conv_width]`` and :meth:`lin_in`'s rest
        → ``q``, ``k [..., Hk, dk]`` (unit length, ``q`` scaled), ``v [..., Hv,
        dv]``, ``g``, ``beta [..., Hv]``, float32."""
        cfg, m, f32 = self.cfg, lp[LIN], jnp.float32
        Hk, dk, Hv, dv = cfg.linear_num_key_heads, cfg.linear_key_head_dim, cfg.linear_num_value_heads, cfg.linear_value_head_dim
        c = c.astype(f32)
        heads = lambda x, H, d: x.reshape(*x.shape[:-1], H, d)  # noqa: E731
        q, k = heads(c[..., :cfg.key_width], Hk, dk), heads(c[..., cfg.key_width:2 * cfg.key_width], Hk, dk)
        unit = lambda x: x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)  # noqa: E731
        b, a = rest[1][..., :Hv], rest[1][..., Hv:]
        g = -jnp.exp(m["a_log"].astype(f32)) * jax.nn.softplus(a + m["dt_bias"].astype(f32))
        return unit(q) / math.sqrt(dk), unit(k), heads(c[..., 2 * cfg.key_width:], Hv, dv), g, jax.nn.sigmoid(b)

    @parts.scoped("lin.proj")
    def lin_out(self, lp, o, rest, tp_axis=None):
        """The rule's ``o [..., Hv, dv]`` (float32) normed per head, gated by
        ``silu(z)`` and projected."""
        m, f32 = lp[LIN], jnp.float32
        z = rest[0].astype(f32).reshape(o.shape)
        y = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + self.cfg.rms_norm_eps) * m["norm_o"].astype(f32)
        return (y * jax.nn.silu(z)).reshape(*o.shape[:-2], -1).astype(m["w_out"].dtype) @ m["w_out"]

    # -- a gated attention sub-block ------------------------------------------
    def qkv(self, lp, h, positions, l: int):
        """``h [B, S, E]`` → ``q [B, S, H, D]``, ``k``, ``v [B, S, KV, D]``,
        normed per head (zero-centred) and rotated on the first ``rotary_dim``
        lanes: what goes into the cache is what attention reads."""
        cfg, a = self.cfg, lp[ATTN]
        H, KV, D, R = cfg.n_head, cfg.n_kv_head, cfg.head_dim, cfg.rotary_dim
        u = _norm(h, lp["norm_1"], cfg.rms_norm_eps)
        q, kv = u @ a["wq"], u @ a["wkv"]
        q = _norm(q.reshape(*q.shape[:-1], H, D), a["q_norm"], cfg.rms_norm_eps)
        k = _norm(kv[..., :KV * D].reshape(*kv.shape[:-1], KV, D), a["k_norm"], cfg.rms_norm_eps)
        turn = lambda x: jnp.concatenate([rotary(x[..., :R], positions, cfg.rope_theta), x[..., R:]], axis=-1)  # noqa: E731
        return turn(q), turn(k), kv[..., KV * D:].reshape(*kv.shape[:-1], KV, D)

    def attn_out(self, lp, o, tp_axis=None, h=None):
        """``(o . sigmoid(gate)) Wo``; the gate is a projection of the normed
        stream ``h`` the attention read."""
        a = lp[ATTN]
        gate = _norm(h, lp["norm_1"], self.cfg.rms_norm_eps) @ a["wg"]
        return (o.astype(jnp.float32) * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(o.dtype) @ a["wo"]

    # -- the rest of a layer --------------------------------------------------
    def after_attention(self, lp, h, o, l: int, valid=None, tp_axis=None, carry=None, attn_out=None):
        """The rest of sub-block ``l`` → (the stream, ``carry`` as it came, the
        experts' token counts): the mixer's output in (an attention's through
        its gate, a DeltaNet's as the program hands it), then the expert block."""
        cfg = self.cfg
        B, S, E = h.shape
        with parts.part("lin.proj" if attn_out is not None else "attn.out"):
            h = h + (attn_out(lp, o, tp_axis) if attn_out is not None else self.attn_out(lp, o, tp_axis, h))
        with parts.part("mlp"):
            w = _norm(h, lp["norm_2"], cfg.rms_norm_eps).reshape(B * S, E)
            m, counts = expert_share_layer(
                lp["moe"], w, cfg.share, cfg.num_experts_per_tok, 1.0, cfg.norm_topk_prob,
                None if valid is None else jnp.broadcast_to(valid, (B, S)).reshape(B * S),
                scoring="softmax",
            )
            return h + m.reshape(B, S, E), carry, counts

    def logits(self, params, h):
        return _norm(h, params["norm_f"], self.cfg.rms_norm_eps) @ params["head"]


def forward(cfg: Qwen3NextConfig, params: PyTree, input_ids) -> jnp.ndarray:
    """Whole-sequence logits ``[B, S, vocab]`` with no cache: the family's
    pieces, the delta rule token by token from a zero state
    (``gated_delta.recurrence``) and a dense masked softmax (for small sizes;
    the served path is ``serving/model.py``)."""
    fam = Qwen3NextFamily(cfg)
    B, S = input_ids.shape
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    h = fam.embed(params, input_ids, pos)
    mask = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    K, Hv = cfg.linear_conv_kernel_dim, cfg.linear_num_value_heads
    for l, kind in enumerate(fam.kinds):
        lp = fam.layer(params, l)
        if kind == LIN:
            m, rest = fam.lin_in(lp, h)
            c, _ = conv_rows(fam.lin_taps(lp), jnp.zeros((), jnp.float32), m, jnp.zeros((B, K - 1, m.shape[-1]), m.dtype))
            q, k, v, g, beta = fam.lin_gates(lp, c, rest)
            o, _ = jax.vmap(lambda q, k, *a: gated_delta.recurrence(
                gated_delta._repeat(q, Hv), gated_delta._repeat(k, Hv), *a, jnp.zeros(fam.lin_state, jnp.float32)
            ))(q, k, v, g, beta)
            h, _, _ = fam.after_attention(lp, h, fam.lin_out(lp, o, rest), l, attn_out=lambda lp, a, tp: a)
            continue
        q, k, v = fam.qkv(lp, h, pos, l)
        qg = q.reshape(B, S, cfg.n_kv_head, cfg.n_head // cfg.n_kv_head, cfg.head_dim)
        s = jnp.einsum("bsgrd,btgd->bgrst", qg.astype(jnp.float32), k.astype(jnp.float32))
        p = jax.nn.softmax(jnp.where(mask, s / np.sqrt(cfg.head_dim), -1e30), axis=-1)
        o = jnp.einsum("bgrst,btgd->bsgrd", p, v.astype(jnp.float32)).astype(h.dtype).reshape(B, S, -1)
        h, _, _ = fam.after_attention(lp, h, o, l)
    return fam.logits(params, h)


def make_module(cfg: Qwen3NextConfig) -> ModuleSpec:
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
