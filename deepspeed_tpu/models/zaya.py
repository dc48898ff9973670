"""The ``zaya`` family (ZAYA1-8B), as one chip serves a cut of its depth.

Every layer is one CCA attention sublayer ("Compressed Convolutional
Attention", arXiv:2510.04476) and one expert sublayer behind an MLP router
(the ZAYA1 report, arXiv:2511.17127). ``E`` hidden, ``d`` head, ``Hq`` / ``Hk``
query / kv heads, ``G = Hq / Hk``, ``Dq = Hq d``, ``Dk = Hk d``, ``R`` the
router's width, ``N`` experts, one pick:

    u  = RMSNorm(x; g_a)
    z_t = [u Wq | u Wk]                                   Dq + Dk channels, the LATENT
    a_t = w0[:,0] . z_{t-1} + w0[:,1] . z_t + b0          depthwise, kernel 2, causal
    c_t = W1[g,0] a_{t-1}^(g) + W1[g,1] a_t^(g) + b1      grouped: Hq + Hk groups of d channels
          z_{-1} = z_{-2} = 0, padded ONCE in front: a_{-1} = b0
    q = c[:Dq] + mq,  k = c[Dq:] + mk                     the q-k mean of the latents BEFORE the convolutions:
          mq_h = (qp_h + kp_{h // G}) / 2,  mk_j = (mean_{h in j} qp_h + kp_j) / 2
    q <- sqrt(d) q / |q|,  k <- sqrt(d) k / |k| . exp(tau_j);  rotary on the first d/2 lanes of a head
    v_t = [u_t Wv1 | u_{t-1} Wv2] as [Hk, d]              kv head 0: the token's own values, head 1: the token before
    attn = softmax(q k^T / sqrt(d), causal) v Wo
    y  = (sx_a . x + bx_a) + (sf_a . attn + bf_a)         the residual merge: four learned vectors a sublayer
    w  = RMSNorm(y; g_m)
    r_l = w Wd + gamma_l . r_{l-1}                        the router's state of the SAME token one layer up
    s  = W3 gelu(W2 gelu(W1 RMSNorm(r_l; g_r)))           float32
    p  = softmax(s),  e = argmax(p + bias_l)              the bias only selects
    m  = p_e FFN_e(w),  x' = (sx_m . y + bx_m) + (sf_m . m + bf_m)
    logits = RMSNorm(x_L; g_f) Emb^T                      tied

To the serving programs (``serving/model.py``) a layer is ONE ``"attn"``
sub-block whose K and V are paged as any grouped-query cache (the finished
``k`` and ``v`` are what is written) and whose q, k and v of a call's first
rows need rows of the call before: the family states ``carry_width`` (``2 (Dq +
Dk) + d``: ``z_{t-1}``, ``z_{t-2}`` and ``u_{t-1} Wv2``, a slot and layer) and
gives ``qkv`` as two pieces, :meth:`ZayaFamily.attn_in` (what is a row's own)
and :meth:`ZayaFamily.attn_mix` (what needs the rows before), so that a mixed
call's chunk and decode rows go through the projections once. The router's
state ``r_l`` travels from sub-block to sub-block in ``after_attention``'s
``carry``; the scores' arguments go to ``moe/expert_share.py`` as ``logits``.

What the published config does not say and this module assumes is listed in
the configuration file that runs it
(``perfbench/configs/zaya1-8b-l14-serve-1chip.json``, ``assumed``). Only the
served path lives here, and :func:`forward`, the same pieces over a whole
sequence with no cache.
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
from ..runtime.module import ModuleSpec
from ..telemetry import parts
from .exaone_moe import rotary

PyTree = Any
_HI = jax.lax.Precision.HIGHEST


@dataclass(frozen=True)
class ZayaConfig:
    vocab_size: int = 262272
    hidden_size: int = 2048
    num_hidden_layers: int = 40
    num_attention_heads: int = 8
    num_key_value_heads: int = 2
    head_dim: int = 128
    cca_time0: int = 2
    cca_time1: int = 2
    partial_rotary_factor: float = 0.5
    rope_theta: float = 5e6
    num_experts: int = 16
    num_experts_per_tok: int = 1
    moe_intermediate_size: int = 2048
    router_hidden_size: int = 256
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 131072
    initializer_range: float = 0.02
    attn_impl: str = "auto"             # auto | pallas (the paged kernels or their jnp fallbacks)
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if self.cca_time0 != 2 or self.cca_time1 != 2:
            raise ValueError("two kernel-2 convolutions are what this module builds (cca_time0 = cca_time1 = 2)")
        if self.num_attention_heads % self.num_key_value_heads or self.num_key_value_heads != 2:
            raise ValueError("the value shift fills two kv heads: num_key_value_heads must be 2 and divide the query heads")
        if self.num_experts_per_tok != 1:
            raise ValueError("one pick a token is what this module builds")

    @classmethod
    def from_dict(cls, d: dict, **overrides) -> "ZayaConfig":
        """From the published keys (an HF ``config.json`` or a perfbench
        configuration file); keys this module does not know are ignored."""
        names = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in d.items() if k in names}
        rope = d.get("rope_parameters", {}).get("hybrid")
        if rope:
            kw["rope_theta"] = float(rope["rope_theta"])
            kw["partial_rotary_factor"] = float(rope.get("partial_rotary_factor", kw.get("partial_rotary_factor", 0.5)))
        kw.pop("dtype", None)  # a file says "bfloat16"; the engine's dtype decides
        kw.update(overrides)
        return cls(**kw)

    # -- the names the serving stack reads a model's geometry by -----------
    n_layer = property(lambda self: self.num_hidden_layers)
    n_head = property(lambda self: self.num_attention_heads)
    n_kv_head = property(lambda self: self.num_key_value_heads)
    n_embd = property(lambda self: self.hidden_size)
    n_positions = property(lambda self: self.max_position_embeddings)
    q_width = property(lambda self: self.num_attention_heads * self.head_dim)
    kv_width = property(lambda self: self.num_key_value_heads * self.head_dim)
    latent = property(lambda self: self.q_width + self.kv_width)       # the convolved channels
    rotary_dim = property(lambda self: int(self.head_dim * self.partial_rotary_factor))

    @property
    def share(self) -> ExpertShare:
        return ExpertShare(self.num_experts)

    def serving_family(self):
        return ZayaFamily(self)


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------

def _leaf_shapes(cfg: ZayaConfig) -> PyTree:
    """The tree, with (shape, kind) leaves. ``w``: normal at
    ``initializer_range``; ``one``: a norm's gain. The others are drawn so
    that a check against the reference SEES the mechanism they belong to (at
    ``initializer_range`` a convolution's output is a thousandth of what
    stands beside it, a temperature of 0 and residual vectors of (1, 0, 1, 0)
    are the identity, and a router at 0.02 gives every expert 1/16):
    ``conv0`` / ``conv1`` uniform in ``+-1 / sqrt(fan_in)`` (a convolution's
    own default: fan-in 2 depthwise, ``2 d`` grouped), ``shift`` (their
    biases) normal at 0.1, ``gamma`` uniform in [0.5, 1), ``tau`` normal at
    0.3, ``scale`` (the residual merge's) uniform in [0.8, 1.2) with its
    biases at ``initializer_range`` (wider, and 28 sublayers compound into a
    stream a few lanes own), ``fan`` normal at ``1 / sqrt(fan_in)`` (the
    router's two inner matrices) and ``fan4`` at four times that (its last:
    softmax arguments of a spread near 2). The balancing bias is drawn like a
    weight."""
    E, d, F = cfg.hidden_size, cfg.head_dim, cfg.moe_intermediate_size
    C, Dq, Dk = cfg.latent, cfg.q_width, cfg.kv_width
    R, N = cfg.router_hidden_size, cfg.num_experts
    merge = lambda: {"sx": ((E,), "scale"), "bx": ((E,), "w"), "sf": ((E,), "scale"), "bf": ((E,), "w")}  # noqa: E731
    layer = lambda: {  # noqa: E731
        "norm_a": ((E,), "one"), "norm_m": ((E,), "one"), "res_a": merge(), "res_m": merge(),
        "cca": {
            "w_in": ((E, C + Dk), "w"),          # [Wq | Wk | Wv1 | Wv2]
            "w0": ((C, 2), "conv0"), "b0": ((C,), "shift"),
            "w1": ((C // d, 2, d, d), "conv1"), "b1": ((C,), "shift"),
            "tau": ((cfg.num_key_value_heads,), "tau"), "wo": ((Dq, E), "w"),
        },
        "moe": {
            "wd": ((E, R), "w"), "gamma": ((R,), "gamma"), "norm_r": ((R,), "one"),
            "w1": ((R, R), "fan"), "w2": ((R, R), "fan"), "w3": ((R, N), "fan4"),
            "bias": ((N,), "w"),    # drawn like a weight, not zero: p + b and p then select differently
            "experts": {"w_gate": ((N, E, F), "w"), "w_up": ((N, E, F), "w"), "w_down": ((N, F, E), "w")},
        },
    }
    return {"embed": ((cfg.vocab_size, E), "w"), "norm_f": ((E,), "one"),
            "layers": [layer() for _ in range(cfg.num_hidden_layers)]}


def _is_leaf(x):
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[1], str)


def init_params(cfg: ZayaConfig, rng, dtype=None) -> PyTree:
    """Every leaf made on the device in ``dtype`` by a program of its own, so
    the set-up never holds more than the tree and one leaf's temporaries."""
    dtype = dtype or cfg.dtype
    leaves, treedef = jax.tree_util.tree_flatten(_leaf_shapes(cfg), is_leaf=_is_leaf)
    keys = jax.random.split(rng, len(leaves))

    @functools.lru_cache(maxsize=None)
    def drawn(shape, kind):  # one program a distinct shape and kind, not one a leaf
        def make(k):
            if kind in ("conv0", "conv1"):
                bound = 1.0 / math.sqrt(2 * (shape[-2] if kind == "conv1" else 1))
                return jax.random.uniform(k, shape, jnp.float32, -bound, bound).astype(dtype)
            if kind in ("gamma", "scale"):
                lo, hi = (0.5, 1.0) if kind == "gamma" else (0.8, 1.2)
                return jax.random.uniform(k, shape, jnp.float32, lo, hi).astype(dtype)
            std = {"w": cfg.initializer_range, "tau": 0.3, "shift": 0.1,
                   "fan": 1.0 / math.sqrt(shape[0]), "fan4": 4.0 / math.sqrt(shape[0])}[kind]
            return (jax.random.normal(k, shape, jnp.float32) * std).astype(dtype)
        return jax.jit(make)

    def make(key, spec):
        shape, kind = spec
        return jnp.ones(shape, dtype) if kind == "one" else drawn(shape, kind)(key)

    return jax.tree_util.tree_unflatten(treedef, [make(k, s) for k, s in zip(keys, leaves)])


def logical_axes(cfg: ZayaConfig) -> PyTree:
    """Logical axis names per leaf (``zero/partitioning.DEFAULT_LOGICAL_RULES``)."""
    def ax(spec):
        shape, _ = spec
        if len(shape) == 1:
            return (None,)
        if len(shape) == 4:
            return (None,) * 4
        if len(shape) == 3:
            return ("expert", *(("embed", "expert_mlp") if shape[1] == cfg.hidden_size else ("expert_mlp", "embed")))
        if shape[0] == cfg.vocab_size:
            return ("vocab", "embed")
        if shape[0] == cfg.hidden_size:
            return ("embed", "mlp")
        return ("mlp", "embed") if shape[1] == cfg.hidden_size else (None, None)

    return jax.tree_util.tree_map(ax, _leaf_shapes(cfg), is_leaf=_is_leaf)


# ---------------------------------------------------------------------------
# the family's pieces
# ---------------------------------------------------------------------------

def _merge(r, x, f):
    """The residual merge ``(sx . x + bx) + (sf . f + bf)`` in float32."""
    x32, f32 = x.astype(jnp.float32), f.astype(jnp.float32)
    g = lambda n: r[n].astype(jnp.float32)  # noqa: E731
    return ((g("sx") * x32 + g("bx")) + (g("sf") * f32 + g("bf"))).astype(x.dtype)


class ZayaFamily:
    """What ``serving/model.py`` asks of a model (see its ``Family`` notes):
    every sub-block an ``"attn"`` one that CARRIES ROWS (``carry_width``,
    :meth:`attn_in`, :meth:`attn_mix`) and owns its combination
    (:meth:`after_attention`)."""

    prefill_block = 256   # the whole-prompt program attends in query blocks of this many
    kv_pools = 2          # a K and a V pool

    def __init__(self, cfg: ZayaConfig):
        self.cfg = cfg
        self.n_layer, self.n_head, self.n_kv_head = cfg.n_layer, cfg.n_head, cfg.n_kv_head
        self.head_dim = self.v_width = cfg.head_dim
        self.vocab_size, self.n_positions, self.attn_impl = cfg.vocab_size, cfg.n_positions, cfg.attn_impl
        self.windows = (0,) * cfg.n_layer
        self.sparse_layers = tuple(range(cfg.n_layer))
        self.experts_held = cfg.num_experts
        self.experts_per_token = cfg.num_experts_per_tok
        # what a slot and sub-block carries from call to call: [z_{t-1} | z_{t-2} | u_{t-1} Wv2]
        self.carry_width = 2 * cfg.latent + cfg.head_dim

    def embed(self, params, ids, positions):
        h = params["embed"][ids]
        return h[:, None, :] if ids.ndim == 1 else h  # the decode step: a token a slot

    def layer(self, params, l: int):
        return params["layers"][l]

    # -- attention: a row's own part, then what needs the rows before -------
    def attn_in(self, lp, h):
        """``h [B, S, E]`` → ``p [B, S, Dq + 2 Dk]``: the norm and the ONE
        product a row needs of the weights, ``[qp | kp | u Wv1 | u Wv2]``."""
        with parts.part("norm"):
            u = rms_norm(h, lp["norm_a"], self.cfg.rms_norm_eps)
        return u @ lp["cca"]["w_in"]

    @parts.scoped("attn.cca")
    def attn_mix(self, lp, p, prev, positions, l: int):
        """What CCA adds between the projections and the kernel. ``p [B, S,
        Dq + 2 Dk]`` (:meth:`attn_in`), ``prev [B, carry_width]``: what the
        sequence of each batch row carried in (zeros at its start),
        ``positions [B, S]`` or ``[S]`` → ``q [B, S, Hq, d]``, ``k``, ``v [B,
        S, Hk, d]`` as they are cached and read, and ``nxt [B, S,
        carry_width]``: what a sequence that ends with row ``t`` carries out."""
        cfg, w = self.cfg, lp["cca"]
        B, S, _ = p.shape
        C, Dq, d, Hq, Hk = cfg.latent, cfg.q_width, cfg.head_dim, cfg.n_head, cfg.n_kv_head
        f32 = jnp.float32
        z, v1, v2 = p[..., :C], p[..., C:C + d], p[..., C + d:]
        prev = prev.astype(p.dtype)
        zz = jnp.concatenate([prev[:, None, C:2 * C], prev[:, None, :C], z], axis=1)         # z_{-2}, z_{-1}, z_0 ..
        nxt = jnp.concatenate([z, zz[:, 1:-1], v2], axis=-1)
        v = jnp.concatenate([v1, jnp.concatenate([prev[:, None, 2 * C:], v2[:, :-1]], axis=1)], axis=-1)
        w0, zf = w["w0"].astype(f32), zz.astype(f32)
        a = w0[:, 0] * zf[:, :-1] + w0[:, 1] * zf[:, 1:] + w["b0"].astype(f32)               # a_{-1} .. a_{S-1}
        ag = a.astype(p.dtype).reshape(B, S + 1, C // d, d)
        c = (jnp.einsum("bsgi,gio->bsgo", ag[:, :-1], w["w1"][:, 0], preferred_element_type=f32)
             + jnp.einsum("bsgi,gio->bsgo", ag[:, 1:], w["w1"][:, 1], preferred_element_type=f32)
             + w["b1"].astype(f32).reshape(C // d, d))
        qp = zf[:, 2:, :Dq].reshape(B, S, Hk, Hq // Hk, d)
        kp = zf[:, 2:, Dq:].reshape(B, S, Hk, 1, d)
        q = c[:, :, :Hq].reshape(B, S, Hk, Hq // Hk, d) + (qp + kp) / 2
        k = c[:, :, Hq:] + (jnp.mean(qp, axis=3) + kp[:, :, :, 0]) / 2
        unit = lambda x: x * (math.sqrt(d) * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-12))  # noqa: E731
        q = unit(q).reshape(B, S, Hq, d)
        k = unit(k) * jnp.exp(w["tau"].astype(f32))[:, None]
        D = cfg.rotary_dim   # the lanes of a head that take positions (half-split pairs); the others carry none
        turn = lambda x: jnp.concatenate(  # noqa: E731
            [rotary(x[..., :D], positions, cfg.rope_theta), x[..., D:]], axis=-1
        ).astype(p.dtype)
        return turn(q), turn(k), v.reshape(B, S, Hk, d), nxt

    def qkv(self, lp, h, positions, l: int, prev=None):
        """Both pieces over whole sequences: ``prev`` None is a start."""
        with parts.part("attn.qkv"):
            p = self.attn_in(lp, h)
        if prev is None:
            prev = jnp.zeros((h.shape[0], self.carry_width), p.dtype)
        return self.attn_mix(lp, p, prev, positions, l)

    def attn_out(self, lp, o, tp_axis=None):
        return o @ lp["cca"]["wo"]

    # -- the rest of a layer --------------------------------------------------
    def router_logits(self, m, w, r_up):
        """``w [T, E]`` (the normed stream), ``r_up [T, R]`` float32 (the
        router's state of the same tokens one layer up, or None at layer 0) →
        (the softmax's arguments ``[T, N]``, this layer's state), float32 at
        full precision: the pick is discrete."""
        f32 = jnp.float32
        dot = lambda a, b: jnp.dot(a, b.astype(f32), precision=_HI)  # noqa: E731
        r = dot(w.astype(f32), m["wd"])
        if r_up is not None:
            r = r + m["gamma"].astype(f32) * r_up
        x = rms_norm(r, m["norm_r"].astype(f32), self.cfg.rms_norm_eps)
        gelu = functools.partial(jax.nn.gelu, approximate=False)
        return dot(gelu(dot(gelu(dot(x, m["w1"])), m["w2"])), m["w3"]), r

    def after_attention(self, lp, h, o, l: int, valid=None, tp_axis=None, carry=None, attn_out=None):
        """The rest of sub-block ``l`` → (the stream, the router's state
        ``[B, S, R]`` for the next sub-block, the experts' token counts)."""
        B, S, E = h.shape
        y, w, logits, r = self.before_experts(lp, h, o, carry, tp_axis, attn_out)
        m, counts = expert_share_layer(
            lp["moe"], w, self.cfg.share, 1, 1.0, False,
            None if valid is None else jnp.broadcast_to(valid, (B, S)).reshape(B * S),
            scoring="softmax", logits=logits,
        )
        with parts.part("moe.experts"):
            return _merge(lp["res_m"], y, m.reshape(B, S, E)), r.reshape(B, S, -1), counts

    def before_experts(self, lp, h, o, carry=None, tp_axis=None, attn_out=None):
        """→ (the stream behind the attention's merge ``y [B, S, E]``, its norm
        ``w [B S, E]``, the router's softmax arguments ``[B S, N]`` and state
        ``[B S, R]``)."""
        with parts.part("attn.out"):   # the residual vectors go with the part whose output they scale
            y = _merge(lp["res_a"], h, (attn_out or self.attn_out)(lp, o, tp_axis))
        with parts.part("norm"):
            w = rms_norm(y, lp["norm_m"], self.cfg.rms_norm_eps).reshape(-1, y.shape[-1])
        with parts.part("moe.route"):
            logits, r = self.router_logits(lp["moe"], w, None if carry is None else carry.reshape(w.shape[0], -1))
        return y, w, logits, r

    def logits(self, params, h):
        return rms_norm(h, params["norm_f"], self.cfg.rms_norm_eps) @ params["embed"].T


def dense_attention(fam: ZayaFamily, lp, h, pos):
    """One layer's attention over whole sequences ``h [B, S, E]`` with no
    cache: the family's ``qkv`` from a start, under a dense masked softmax →
    ``o [B, S, Dq]`` (what ``after_attention`` takes)."""
    cfg = fam.cfg
    B, S, _ = h.shape
    q, k, v, _ = fam.qkv(lp, h, pos, 0)
    qg = q.reshape(B, S, cfg.n_kv_head, cfg.n_head // cfg.n_kv_head, cfg.head_dim)
    s = jnp.einsum("bsgrd,btgd->bgrst", qg.astype(jnp.float32), k.astype(jnp.float32))
    mask = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    p = jax.nn.softmax(jnp.where(mask, s / np.sqrt(cfg.head_dim), -1e30), axis=-1)
    return jnp.einsum("bgrst,btgd->bsgrd", p, v.astype(jnp.float32)).astype(h.dtype).reshape(B, S, -1)


def forward(cfg: ZayaConfig, params: PyTree, input_ids) -> jnp.ndarray:
    """Whole-sequence logits ``[B, S, vocab]`` with no cache: the family's
    pieces under a dense masked softmax (for small sizes; the served path is
    ``serving/model.py``)."""
    fam = ZayaFamily(cfg)
    B, S = input_ids.shape
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    h, carry = fam.embed(params, input_ids, pos), None
    for l in range(cfg.n_layer):
        lp = fam.layer(params, l)
        h, carry, _ = fam.after_attention(lp, h, dense_attention(fam, lp, h, pos), l, carry=carry)
    return fam.logits(params, h)


def make_module(cfg: ZayaConfig) -> ModuleSpec:
    """For ``init_inference(model=...)``. No training path: ``loss_fn`` is
    absent on purpose (top-1 experts without capacity drops have no sharded
    training layer here; ROADMAP.md)."""
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
