"""The ``phi4flash`` family (Phi-4-mini-flash-reasoning), served whole.

A decoder-decoder hybrid (SambaY): a SELF-decoder of state-space (Mamba)
layers alternating with differential attention, and a CROSS-decoder of gated
memory units alternating with cross-attentions, which keep no state of their
own and read two layers of the self-decoder. ``L`` layers, ``i`` from 0:

    a = x + mixer_i(LN1(x));  y = a + MLP(LN2(a))        every layer
    LN: LayerNorm with gain and bias;  MLP(u) = W_down(silu(g) . v), [g | v] = W_gate_up u
    logits = LN_f(y) E^T (tied);  no positional encoding anywhere

    mixer_i:  Mamba                      even i <= L/2          kind "ssm"
              differential attention     odd  i <  L/2          "attn", a window of ``sliding_window`` keys
              differential attention     i = L/2 + 1            "attn", every key: the one paged cache
              gated memory unit          even i >= L/2 + 2      "gmu",   reads layer L/2's scan output
              cross-attention            odd  i >= L/2 + 3      "cross", reads layer L/2 + 1's K and V

    Mamba(u): [xs | z] = W_in u;  c = silu(conv(xs));  [delta | B | C] = W_x c
              dt = softplus(W_dt delta + b_dt);  A = -exp(A_log)
              h_t = exp(dt_t (x) A) h_{t-1} + (dt_t . c_t) (x) B_t;  s_t = h_t C_t + D . c_t
              out = W_out(s . silu(z));  layer L/2 hands s (BEFORE the gate) on as the memory m
    GMU(u):   W_out(silu(W_in u) . m), m the memory of the same token
    diff attention: query heads pair as (2p, 2p+1), kv heads likewise; pair p reads kv pair p // 2
              o_p = softmax(q1 k1^T / sqrt(D)) [v1|v2] - lambda softmax(q2 k2^T / sqrt(D)) [v1|v2]
              lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda0_i,  lambda0_i = 0.8 - 0.6 exp(-0.3 i)
              o_p <- (1 - lambda0_i) RMSNorm_2D(o_p);  out = W_o o + b_o

To the serving programs (``serving/model.py``) the PAIR is the cached head:
``softmax([q1|0] . [k1|k2]^T / sqrt(D)) [v1|v2]`` is the first product above and
``[0|q2]`` gives the second, so the family states ``n_head`` zero-padded query
heads ``2D`` wide over ``n_kv_head / 2`` kv heads ``2D`` wide with ``sm_scale``
``1 / sqrt(D)``: the grouped-query shape the paged and ring kernels serve,
every cached lane real, one copy of V. :meth:`Phi4FlashFamily.attn_out`
combines the pairs. The sub-blocks' ``kinds``, the ``sources`` a cross layer or
a gated memory unit reads and ``stop_after`` (prompt rows leave the stream
after layer L/2 + 1: the cross-decoder produces nothing but logits) are the
family's to state; the recurrent state (``[N, d_inner]`` float32 and the
convolution's last ``d_conv - 1`` inputs a slot and Mamba layer) is the
programs' to keep.

What the published config does not say and this module assumes is listed in
the configuration file that runs it
(``perfbench/configs/phi-4-mini-flash-serve-1chip.json``, ``assumed``).
Only the served path lives here, and :func:`forward`, the same pieces over
whole sequences with no cache.
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

from ..ops.layer_norm import layer_norm_inference, rms_norm
from ..ops.pallas import selective_scan
from ..runtime.module import ModuleSpec
from ..telemetry import parts

PyTree = Any

SSM, ATTN, GMU, CROSS = "ssm", "attn", "gmu", "cross"


@dataclass(frozen=True)
class Phi4FlashConfig:
    vocab_size: int = 200064
    hidden_size: int = 2560
    intermediate_size: int = 10240
    num_hidden_layers: int = 32
    num_attention_heads: int = 40
    num_key_value_heads: int = 20
    mb_per_layer: int = 2
    sliding_window: int = 512
    layer_norm_eps: float = 1e-5
    max_position_embeddings: int = 262144
    tie_word_embeddings: bool = True
    # not keys of the published config (the configuration file's ``assumed``)
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    initializer_range: float = 0.02
    prefill_stops: bool = True          # prompt rows leave after the self-decoder (tests turn it off)
    attn_impl: str = "auto"             # auto | pallas (the paged kernels or their jnp fallbacks)
    ssm_impl: str = "auto"              # auto | pallas | jnp (ops/pallas/selective_scan.kernel_runs)
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        L = self.num_hidden_layers
        if L % 2 or L < 4 or self.mb_per_layer != 2:
            raise ValueError("phi4flash: an even depth of at least 4 and mb_per_layer 2 are what this module builds")
        if self.num_attention_heads % 2 or self.num_key_value_heads % 2:
            raise ValueError("differential attention pairs heads: even counts of query and kv heads")
        if self.num_attention_heads % self.num_key_value_heads or self.hidden_size % self.num_attention_heads:
            raise ValueError("num_attention_heads must divide hidden_size and by num_key_value_heads")
        if not self.tie_word_embeddings:
            raise ValueError("the output head is the embedding's transpose")

    @classmethod
    def from_dict(cls, d: dict, **overrides) -> "Phi4FlashConfig":
        """From the published keys (an HF ``config.json`` or a perfbench
        configuration file); keys this module does not know are ignored."""
        names = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in d.items() if k in names}
        kw.pop("dtype", None)  # a file says "bfloat16"; the engine's dtype decides
        kw.update(overrides)
        return cls(**kw)

    # -- the names the serving stack reads a model's geometry by -----------
    n_layer = property(lambda self: self.num_hidden_layers)
    n_head = property(lambda self: self.num_attention_heads)     # the padded pair-heads, as many as the heads
    n_kv_head = property(lambda self: self.num_key_value_heads // 2)
    n_embd = property(lambda self: self.hidden_size)
    n_positions = property(lambda self: self.max_position_embeddings)
    head_size = property(lambda self: self.hidden_size // self.num_attention_heads)   # D: a published head
    head_dim = property(lambda self: 2 * self.head_size)                              # the cached pair
    d_inner = property(lambda self: self.mamba_expand * self.hidden_size)
    dt_rank = property(lambda self: -(-self.hidden_size // 16))

    def kind(self, i: int) -> str:
        half = self.num_hidden_layers // 2
        if i % self.mb_per_layer == 0:
            return SSM if i <= half else GMU
        return ATTN if i <= half + 1 else CROSS

    def window(self, i: int) -> int:
        """Keys a query of attention layer ``i`` reads, itself included; 0: all."""
        return self.sliding_window if self.kind(i) == ATTN and i < self.num_hidden_layers // 2 else 0

    def lambda_init(self, i: int) -> float:
        return 0.8 - 0.6 * math.exp(-0.3 * i)

    def serving_family(self):
        return Phi4FlashFamily(self)


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------

def _leaf_shapes(cfg: Phi4FlashConfig) -> PyTree:
    """The tree, with (shape, kind) leaves. Kinds: ``w`` normal at
    ``initializer_range``, ``lam`` normal at 0.1, ``one`` / ``zero``, and the
    Mamba initialisation: ``a_log`` (log(1..N) a row), ``dt_bias`` (softplus of
    it log-uniform in [1e-3, 1e-1]: at a normal draw every channel would
    forget in a few tokens, and a state that is dropped would not show) and
    ``conv`` (uniform in ``+-1 / sqrt(d_conv)``, a depthwise convolution's own
    default: at ``initializer_range`` the scan's input is 0.02, the state's
    term a thousandth of ``D . c`` and a state-space mixer's output a
    fiftieth of an MLP's, so that a recurrence left out moved the served
    logits by 0.008 of their 1.0; PERF.md section 6, PR 43)."""
    E, F, D = cfg.hidden_size, cfg.intermediate_size, cfg.head_size
    H, KV = cfg.num_attention_heads, cfg.num_key_value_heads
    di, N, K, R = cfg.d_inner, cfg.mamba_d_state, cfg.mamba_d_conv, cfg.dt_rank
    norm = lambda: {"g": ((E,), "one"), "b": ((E,), "zero")}  # noqa: E731
    diff = {f"lambda_{n}": ((D,), "lam") for n in ("q1", "k1", "q2", "k2")}
    diff["subln"] = ((2 * D,), "one")
    out = {"wo": ((H * D, E), "w"), "bo": ((E,), "zero")}
    layers = []
    for i in range(cfg.num_hidden_layers):
        lp = {"norm_1": norm(), "norm_2": norm(),
              "mlp": {"w_gate_up": ((E, 2 * F), "w"), "w_down": ((F, E), "w")}}
        kind = cfg.kind(i)
        if kind == SSM:
            lp[SSM] = {
                "w_in": ((E, 2 * di), "w"), "w_conv": ((di, K), "conv"), "b_conv": ((di,), "zero"),
                "w_x": ((di, R + 2 * N), "w"), "w_dt": ((R, di), "w"), "b_dt": ((di,), "dt_bias"),
                "a_log": ((di, N), "a_log"), "d": ((di,), "one"), "w_out": ((di, E), "w"),
            }
        elif kind == ATTN:
            lp[ATTN] = {"wqkv": ((E, (H + 2 * KV) * D), "w"), "bqkv": (((H + 2 * KV) * D,), "zero"), **out, **diff}
        elif kind == CROSS:
            lp[CROSS] = {"wq": ((E, H * D), "w"), "bq": ((H * D,), "zero"), **out, **diff}
        else:
            lp[GMU] = {"w_in": ((E, di), "w"), "w_out": ((di, E), "w")}
        layers.append(lp)
    return {"embed": ((cfg.vocab_size, E), "w"), "norm_f": norm(), "layers": layers}


def _is_leaf(x):
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[1], str)


def init_params(cfg: Phi4FlashConfig, rng, dtype=None) -> PyTree:
    """Every leaf made on the device in ``dtype`` by a program of its own, so
    the set-up never holds more than the tree and one leaf's temporaries."""
    dtype = dtype or cfg.dtype
    leaves, treedef = jax.tree_util.tree_flatten(_leaf_shapes(cfg), is_leaf=_is_leaf)
    keys = jax.random.split(rng, len(leaves))

    @functools.lru_cache(maxsize=None)
    def drawn(shape, kind):  # one program a distinct shape and kind, not one a leaf
        def make(k):
            if kind == "dt_bias":
                dt = jnp.exp(jax.random.uniform(k, shape, jnp.float32, math.log(1e-3), math.log(1e-1)))
                return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)      # softplus^-1
            if kind == "conv":
                bound = 1.0 / math.sqrt(shape[-1])
                return jax.random.uniform(k, shape, jnp.float32, -bound, bound).astype(dtype)
            std = 0.1 if kind == "lam" else cfg.initializer_range
            return (jax.random.normal(k, shape, jnp.float32) * std).astype(dtype)
        return jax.jit(make)

    def make(key, spec):
        shape, kind = spec
        if kind in ("one", "zero"):
            return jnp.full(shape, float(kind == "one"), dtype)
        if kind == "a_log":
            return jnp.broadcast_to(jnp.log(jnp.arange(1, shape[1] + 1, dtype=jnp.float32)), shape).astype(dtype)
        return drawn(shape, kind)(key)

    return jax.tree_util.tree_unflatten(treedef, [make(k, s) for k, s in zip(keys, leaves)])


def logical_axes(cfg: Phi4FlashConfig) -> PyTree:
    """Logical axis names per leaf (``zero/partitioning.DEFAULT_LOGICAL_RULES``)."""
    def ax(spec):
        shape, _ = spec
        if len(shape) == 1:
            return (None,)
        if shape[0] == cfg.vocab_size:
            return ("vocab", "embed")
        return ("embed", "mlp") if shape[0] == cfg.hidden_size else ("mlp", "embed")

    return jax.tree_util.tree_map(ax, _leaf_shapes(cfg), is_leaf=_is_leaf)


# ---------------------------------------------------------------------------
# the family's pieces
# ---------------------------------------------------------------------------

class Phi4FlashFamily:
    """What ``serving/model.py`` asks of a model (see its ``Family`` notes)."""

    prefill_block = 256   # the whole-prompt program attends in query blocks of this many
    kv_pools = 2          # a K and a V pool, of head PAIRS
    sparse_layers = ()
    experts_held = 0

    def __init__(self, cfg: Phi4FlashConfig):
        self.cfg = cfg
        L, half = cfg.n_layer, cfg.n_layer // 2
        self.n_layer, self.n_head, self.n_kv_head = L, cfg.n_head, cfg.n_kv_head
        self.head_dim = self.v_width = cfg.head_dim
        self.sm_scale = 1.0 / math.sqrt(cfg.head_size)
        self.vocab_size, self.n_positions, self.attn_impl = cfg.vocab_size, cfg.n_positions, cfg.attn_impl
        self.kinds = tuple(cfg.kind(i) for i in range(L))
        self.windows = tuple(cfg.window(i) for i in range(L))
        # whom a sub-block that keeps nothing reads: the memory, the K and V
        self.sources = {i: half if self.kinds[i] == GMU else half + 1
                        for i in range(L) if self.kinds[i] in (GMU, CROSS)}
        self.stop_after = half + 1 if cfg.prefill_stops else None
        # the recurrent state a slot and "ssm" sub-block: [N, d_inner] float32, d_conv - 1 rows of d_inner
        self.ssm_state = (cfg.mamba_d_state, cfg.d_inner)
        self.ssm_conv = cfg.mamba_d_conv
        self.ssm_impl = cfg.ssm_impl

    def embed(self, params, ids, positions):
        h = params["embed"][ids]
        return h[:, None, :] if ids.ndim == 1 else h  # the decode step: a token a slot

    def layer(self, params, l: int):
        # lambda0 depends on the layer's depth: a Python number beside the weights
        return {**params["layers"][l], "lambda_init": self.cfg.lambda_init(l)}

    def _norm(self, h, n):
        with parts.part("norm"):
            return layer_norm_inference(h, n["g"], n["b"], self.cfg.layer_norm_eps)

    def _pad_pairs(self, q):
        """``q [..., H, D]`` → ``[..., H, 2D]``: head 2p is ``[q1 | 0]``, head
        2p + 1 ``[0 | q2]``, against the cached pair ``[k1 | k2]``."""
        H, D = q.shape[-2:]
        q = q.reshape(*q.shape[:-2], H // 2, 2, 1, D) * jnp.eye(2, dtype=q.dtype)[:, :, None]
        return q.reshape(*q.shape[:-4], H, 2 * D)

    def qkv(self, lp, h, positions, l: int):
        """``h [B, S, E]`` → the padded pair queries ``[B, S, H, 2D]`` and the
        kv PAIRS ``k``, ``v [B, S, KV/2, 2D]`` (two neighbouring heads side by
        side: a reshape)."""
        cfg = self.cfg
        H, KV, D = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_size
        a = lp[ATTN]
        qkv = self._norm(h, lp["norm_1"]) @ a["wqkv"] + a["bqkv"]
        q, k, v = jnp.split(qkv, [H * D, (H + KV) * D], axis=-1)
        pairs = lambda x: x.reshape(*x.shape[:-1], KV // 2, 2 * D)  # noqa: E731
        return self._pad_pairs(q.reshape(*q.shape[:-1], H, D)), pairs(k), pairs(v)

    def q_cross(self, lp, h, positions, l: int):
        """A cross layer's padded pair queries ``[B, S, H, 2D]``; its K and V
        are its source's."""
        a = lp[CROSS]
        q = self._norm(h, lp["norm_1"]) @ a["wq"] + a["bq"]
        return self._pad_pairs(q.reshape(*q.shape[:-1], self.cfg.num_attention_heads, self.cfg.head_size))

    def attn_out(self, lp, o, tp_axis=None):
        """``o [B, S, H * 2D]`` (head 2p the first softmax's product, 2p + 1
        the second's) → the pairs combined, normed, back to heads, projected."""
        a = lp[ATTN] if ATTN in lp else lp[CROSS]
        f32 = lambda n: a[n].astype(jnp.float32)  # noqa: E731
        lam0 = lp["lambda_init"]
        lam = jnp.exp(jnp.sum(f32("lambda_q1") * f32("lambda_k1"))) - jnp.exp(jnp.sum(f32("lambda_q2") * f32("lambda_k2"))) + lam0
        o = o.astype(jnp.float32).reshape(*o.shape[:-1], self.n_head // 2, 2, self.head_dim)
        op = rms_norm(o[..., 0, :] - lam * o[..., 1, :], a["subln"], self.cfg.layer_norm_eps) * (1.0 - lam0)
        return op.reshape(*op.shape[:-2], -1).astype(a["wo"].dtype) @ a["wo"] + a["bo"]

    # -- a state-space sub-block, in the four pieces the programs put the
    # -- state between: in, (convolution,) dt / B / C, (scan,) out
    def ssm_in(self, lp, h):
        """→ ``xs``, ``z [B, S, d_inner]``."""
        with parts.part("ssm.proj"):
            return jnp.split(self._norm(h, lp["norm_1"]) @ lp[SSM]["w_in"], 2, axis=-1)

    def ssm_consts(self, lp):
        """→ ``A [N, d_inner]`` (negative), ``D [d_inner]`` in float32, the
        convolution's taps ``[d_inner, K]`` and bias."""
        m = lp[SSM]
        return -jnp.exp(m["a_log"].astype(jnp.float32)).T, m["d"].astype(jnp.float32), m["w_conv"], m["b_conv"]

    def ssm_dt(self, lp, c):
        """The convolved rows ``c [..., d_inner]`` → ``dt [..., d_inner]``,
        ``B``, ``C [..., N]`` in float32."""
        m, N, R = lp[SSM], self.cfg.mamba_d_state, self.cfg.dt_rank
        with parts.part("ssm.proj"):
            dbc = jnp.matmul(c, m["w_x"], preferred_element_type=jnp.float32)
            delta, Bm, Cm = jnp.split(dbc, [R, R + N], axis=-1)
            dt = jnp.matmul(delta.astype(c.dtype), m["w_dt"], preferred_element_type=jnp.float32)
            return jax.nn.softplus(dt + m["b_dt"].astype(jnp.float32)), Bm, Cm

    def ssm_out(self, lp, s, z, tp_axis=None):
        """The scan's ``s [..., d_inner]`` (float32) gated and projected."""
        with parts.part("ssm.proj"):
            return (s * jax.nn.silu(z.astype(jnp.float32))).astype(z.dtype) @ lp[SSM]["w_out"]

    def gmu(self, lp, h, m, tp_axis=None):
        """A gated memory unit over the memory ``m [B, S, d_inner]`` (float32)
        of the same tokens."""
        with parts.part("ssm.proj"):
            g = self._norm(h, lp["norm_1"]) @ lp[GMU]["w_in"]
            return (jax.nn.silu(g.astype(jnp.float32)) * m).astype(g.dtype) @ lp[GMU]["w_out"]

    def mlp(self, lp, h, l: int, valid=None, tp_axis=None):
        gv = self._norm(h, lp["norm_2"]) @ lp["mlp"]["w_gate_up"]
        g, v = jnp.split(gv, 2, axis=-1)
        return (jax.nn.silu(g) * v) @ lp["mlp"]["w_down"], None

    def logits(self, params, h):
        n = params["norm_f"]
        return layer_norm_inference(h, n["g"], n["b"], self.cfg.layer_norm_eps) @ params["embed"].T


def forward(cfg: Phi4FlashConfig, params: PyTree, input_ids, cache=None):
    """Logits ``[B, S, vocab]`` of ``input_ids [B, S]`` behind what ``cache``
    holds, and the cache after them: the family's pieces under dense masked
    softmaxes, the plain cached forward (small sizes; the served path is
    ``serving/model.py``). ``cache``: ``None`` (a sequence's start) or what a
    call returned: per layer the Mamba state and convolution rows, or the kv
    pairs so far."""
    fam = Phi4FlashFamily(cfg)
    B, S = input_ids.shape
    N, di = fam.ssm_state
    past = 0 if cache is None else cache["len"]
    h = fam.embed(params, input_ids, None)
    new, mem = {"len": past + S}, {}
    i, j = past + jnp.arange(S)[:, None], jnp.arange(past + S)[None, :]
    rep = cfg.n_head // cfg.n_kv_head
    for l, kind in enumerate(fam.kinds):
        lp = fam.layer(params, l)
        if kind == SSM:
            xs, z = fam.ssm_in(lp, h)
            A, D, wc, bc = fam.ssm_consts(lp)
            h0, prev = (jnp.zeros((B, N, di), jnp.float32), jnp.zeros((B, fam.ssm_conv - 1, di), xs.dtype)) \
                if cache is None else cache[l]
            c, full = selective_scan.conv_rows(wc, bc, xs, prev)
            dt, Bm, Cm = fam.ssm_dt(lp, c)
            s, h1 = jax.vmap(lambda *a: selective_scan.scan_rows(*a[:4], A, D, a[4], impl="jnp"))(
                c.astype(jnp.float32), dt, Bm, Cm, h0)
            new[l], mem[l] = (h1, full[:, S:]), s
            a = fam.ssm_out(lp, s, z)
        elif kind == GMU:
            a = fam.gmu(lp, h, mem[fam.sources[l]])
        else:
            if kind == ATTN:
                q, k, v = fam.qkv(lp, h, None, l)
                if cache is not None:
                    k, v = (jnp.concatenate([o, n], axis=1) for o, n in zip(cache[l], (k, v)))
                new[l] = (k, v)
            else:
                q, (k, v) = fam.q_cross(lp, h, None, l), new[fam.sources[l]]
            mask = (j <= i) & ((j > i - fam.windows[l]) if fam.windows[l] else True)
            qg = q.reshape(B, S, cfg.n_kv_head, rep, cfg.head_dim)
            sc = jnp.einsum("bsgrd,btgd->bgrst", qg.astype(jnp.float32), k.astype(jnp.float32)) * fam.sm_scale
            p = jax.nn.softmax(jnp.where(mask, sc, -1e30), axis=-1)
            o = jnp.einsum("bgrst,btgd->bsgrd", p, v.astype(jnp.float32)).astype(h.dtype)
            a = fam.attn_out(lp, o.reshape(B, S, -1))
        h = h + a
        h = h + fam.mlp(lp, h, l)[0]
    return fam.logits(params, h), new


def make_module(cfg: Phi4FlashConfig) -> ModuleSpec:
    """For ``init_inference(model=...)``. No training path: ``loss_fn`` is
    absent on purpose (the scan has no backward here; ROADMAP.md R7)."""
    return ModuleSpec(
        init=lambda rng: init_params(cfg, rng),
        loss_fn=None,
        apply_fn=lambda params, batch: forward(cfg, params, batch["input_ids"])[0],
        logical_axes=logical_axes(cfg),
        num_layers=cfg.n_layer,
        extra={
            "config": cfg,
            # the inference engine makes the tree leaf by leaf in its own dtype
            "init_in_dtype": lambda rng, dtype: init_params(cfg, rng, dtype),
        },
    )
