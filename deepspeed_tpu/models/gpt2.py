"""GPT-2 model family, TPU-first.

This is the flagship training workload (BASELINE.md configs: GPT-2 125M ZeRO-1,
GPT-2-XL 1.5B ZeRO-3). It is NOT a port of any torch modeling code — it is
written for XLA:

- **scan-over-layers**: all transformer blocks are stacked into one pytree
  with a leading ``layers`` dim and executed with ``lax.scan`` → O(1) HLO
  size regardless of depth, fast compiles, and a natural unit for pipeline
  stage partitioning later.
- **logical axis annotations** on every param (consumed by
  ``ZeroShardingPolicy``): Megatron-style column-parallel QKV/FC1 (out-dim on
  ``tp``) and row-parallel proj/FC2 (in-dim on ``tp``); ``vocab`` on ``tp``;
  ZeRO then shards the biggest free dim over ``dp``. XLA inserts the TP
  allreduces the reference does by hand inside fused kernels
  (ops/transformer/inference/transformer_inference.py TP allreduce).
- **remat** per block via ``jax.checkpoint`` (the activation-checkpointing
  analog of runtime/activation_checkpointing/checkpointing.py).
- attention runs through ``deepspeed_tpu.ops.attention`` which picks a Pallas
  flash kernel on TPU or a reference jnp path elsewhere.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax import lax
from jax.sharding import PartitionSpec

from ..ops.quantizer import maybe_dequantize as _deq
from ..ops.layer_norm import layer_norm
from ..runtime.module import ModuleSpec
from ..runtime.zero.partitioning import on_batch_axis
from ..telemetry import parts

PyTree = Any


@dataclass(frozen=True)
class GPT2Config:
    vocab_size: int = 50257
    n_positions: int = 1024
    n_embd: int = 768
    n_layer: int = 12
    n_head: int = 12
    dropout: float = 0.0
    layer_norm_epsilon: float = 1e-5
    use_bias: bool = True
    remat: bool = False
    # activation-checkpointing extensions (reference checkpointing.py:367/:480):
    # shard the saved per-layer boundary activation over tp (needs cfg.mesh),
    # and/or offload it to pinned host RAM between forward and backward
    partition_activations: bool = False
    cpu_checkpointing: bool = False
    # remat granularity: "full" recomputes the whole block in backward
    # (cheapest memory, +~1/3 executed flops); "dots" saves every matmul
    # output PLUS the attention-kernel output and recomputes only the cheap
    # elementwise ops (memory between no-remat and full remat,
    # near-no-remat flops); "attn" saves ONLY the attention output — one
    # extra [B,S,E] per layer beyond full remat, but the backward never
    # re-runs the (flash-kernel) attention forward, the most expensive
    # recompute in the block
    remat_policy: str = "full"
    attn_impl: str = "auto"  # auto | pallas | jnp | ring | ring_flash | ulysses | sparse
    # >0: compute the LM cross-entropy in sequence chunks of this many
    # positions, never materializing the full [B,S,V] logits (at GPT-2
    # vocab 50257 and seq 1024 those are ~100 MB/sample in f32 — the
    # dominant activation). Backward recomputes each chunk's logits
    # (jax.checkpoint). 0 = classic full-logits path.
    ce_chunk: int = 0
    # for attn_impl="sparse": a SparsityConfig instance (or None → Fixed
    # defaults). Built from the engine config's ``sparse_attention`` section
    # via ops.sparse_attention.from_ds_config (reference
    # get_sparse_attention_config, deepspeed/__init__.py)
    sparsity: Any = None
    # mesh is required for the sequence-parallel attention impls ("ring",
    # "ulysses") — they shard_map over its sp axis (parallel/sequence.py)
    mesh: Any = None
    dtype: Any = jnp.float32  # param init dtype (master)
    # MoE (DeepSpeed-MoE capability, Switch-style: every MLP is an expert
    # layer so scan-over-layers stays homogeneous). 0 = dense.
    moe_experts: int = 0
    moe_top_k: int = 1
    moe_capacity_factor: float = 1.25
    moe_eval_capacity_factor: Optional[float] = None  # None → moe_capacity_factor
    moe_aux_loss_weight: float = 0.01
    moe_drop_tokens: bool = True  # False → static no-drop capacity (C = T)
    moe_use_rts: bool = True  # Random Token Selection on capacity overflow
    moe_second_policy: str = "random"  # top-2 second expert: random | argmax

    # Megatron-style vocab padding (make-vocab-size-divisible-by): pad the
    # embedding table to a multiple of this so every head matmul runs on an
    # MXU-lane-aligned vocab dim (GPT-2's 50257 is not 128-divisible).
    # vocab_size stays the LOGICAL vocab everywhere — ids, labels, analytic
    # FLOPs; only the wte array and logits carry padded_vocab_size columns,
    # which the loss and sampling paths mask to -inf (pad rows are
    # zero-initialized and receive exactly zero gradient). 1 = off.
    pad_vocab_multiple: int = 1

    @property
    def padded_vocab_size(self) -> int:
        m = max(1, int(self.pad_vocab_multiple))
        return -(-self.vocab_size // m) * m

    @property
    def head_dim(self) -> int:
        return self.n_embd // self.n_head

    @property
    def is_moe(self) -> bool:
        return self.moe_experts > 0

    def serving_family(self):
        """The pieces ``serving/model.py``'s paged programs are built from."""
        return GPT2Family(self)

    def per_head_cache(self) -> "GPT2Config":
        """This config with a serving family that caches a head a PUBLISHED
        head whatever its width: what an int8 cache is served with, whose
        pages carry one scale a cached head (a pair under one scale would
        quantise the quieter head of the two coarser)."""
        return _PerHeadCacheConfig(**{f.name: getattr(self, f.name) for f in dataclasses.fields(self)})


@dataclass(frozen=True)
class _PerHeadCacheConfig(GPT2Config):
    """:meth:`GPT2Config.per_head_cache`'s: the same model, no head pairs."""

    def serving_family(self):
        return GPT2Family(self, pairs=False)


# name → config, sizes per the GPT-2 paper / HF checkpoints
PRESETS: Dict[str, Dict] = {
    "gpt2-tiny": dict(n_embd=64, n_layer=2, n_head=4, vocab_size=512, n_positions=128),
    "gpt2": dict(n_embd=768, n_layer=12, n_head=12),
    "gpt2-125m": dict(n_embd=768, n_layer=12, n_head=12),
    "gpt2-medium": dict(n_embd=1024, n_layer=24, n_head=16),
    "gpt2-large": dict(n_embd=1280, n_layer=36, n_head=20),
    "gpt2-xl": dict(n_embd=1600, n_layer=48, n_head=25),
}


def get_config(name: str, **overrides) -> GPT2Config:
    base = dict(PRESETS[name])
    base.update(overrides)
    # the engine config's ``sparse_attention`` section (dict or typed) turns
    # on the block-sparse kernel with the requested pattern (reference
    # get_sparse_attention_config consumption in client models)
    section = base.pop("sparse_attention", None)
    if section is not None:
        from ..ops.sparse_attention import from_ds_config

        # an explicit attn_impl override wins (e.g. attn_impl="jnp" to A/B
        # the dense path with the section still present)
        base.setdefault("attn_impl", "sparse")
        base["sparsity"] = from_ds_config(section, base.get("n_head", 12))
    return GPT2Config(**base)


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------

def init_params(cfg: GPT2Config, rng) -> PyTree:
    """Initializer; runs under jit with sharded out_shardings (zero.Init analog)."""
    E, L, V, P = cfg.n_embd, cfg.n_layer, cfg.vocab_size, cfg.n_positions
    k = iter(jax.random.split(rng, 16))
    std = 0.02
    # residual-projection init scaled by 1/sqrt(2L) (GPT-2 scheme)
    pstd = std / jnp.sqrt(2.0 * L)
    dt = cfg.dtype

    def normal(key, shape, s):
        return (jax.random.normal(key, shape) * s).astype(dt)

    Vp = cfg.padded_vocab_size
    wte = normal(next(k), (Vp, E), std)
    if Vp > V:  # pad rows exactly zero: masked out of loss/sampling, zero grad
        wte = wte.at[V:].set(0)
    params = {
        "wte": wte,
        "wpe": normal(next(k), (P, E), std),
        "ln_f": {"scale": jnp.ones((E,), dt), "bias": jnp.zeros((E,), dt)},
        "blocks": {
            "ln_1": {"scale": jnp.ones((L, E), dt), "bias": jnp.zeros((L, E), dt)},
            "ln_2": {"scale": jnp.ones((L, E), dt), "bias": jnp.zeros((L, E), dt)},
            "attn": {
                "c_attn_w": normal(next(k), (L, E, 3 * E), std),
                "c_attn_b": jnp.zeros((L, 3 * E), dt),
                "c_proj_w": normal(next(k), (L, E, E), pstd),
                "c_proj_b": jnp.zeros((L, E), dt),
            },
            "mlp": _init_mlp(cfg, [next(k), next(k), next(k)], std, pstd, dt),
        },
    }
    return params


def _init_mlp(cfg: GPT2Config, keys, std, pstd, dt):
    E, L = cfg.n_embd, cfg.n_layer

    def normal(key, shape, s):
        return (jax.random.normal(key, shape) * s).astype(dt)

    if not cfg.is_moe:
        return {
            "c_fc_w": normal(keys[0], (L, E, 4 * E), std),
            "c_fc_b": jnp.zeros((L, 4 * E), dt),
            "c_proj_w": normal(keys[1], (L, 4 * E, E), pstd),
            "c_proj_b": jnp.zeros((L, E), dt),
        }
    X = cfg.moe_experts
    return {
        "gate_w": normal(keys[2], (L, E, X), std).astype(jnp.float32),
        "w_in": normal(keys[0], (L, X, E, 4 * E), std),
        "b_in": jnp.zeros((L, X, 4 * E), dt),
        "w_out": normal(keys[1], (L, X, 4 * E, E), pstd),
        "b_out": jnp.zeros((L, X, E), dt),
    }


def logical_axes(cfg: Optional[GPT2Config] = None) -> PyTree:
    """Logical-axis names per param (see zero/partitioning.DEFAULT_LOGICAL_RULES)."""
    moe = cfg is not None and cfg.is_moe
    if moe:
        mlp = {
            "gate_w": ("layers", "embed", None),
            "w_in": ("layers", "expert", "embed", "expert_mlp"),
            "b_in": ("layers", "expert", "expert_mlp"),
            "w_out": ("layers", "expert", "expert_mlp", "embed"),
            "b_out": ("layers", "expert", "embed"),
        }
    else:
        mlp = {
            "c_fc_w": ("layers", "embed", "mlp"),
            "c_fc_b": ("layers", "mlp"),
            "c_proj_w": ("layers", "mlp", "embed"),
            "c_proj_b": ("layers", "embed"),
        }
    return {
        "wte": ("vocab", "embed"),
        "wpe": (None, "embed"),
        "ln_f": {"scale": ("embed",), "bias": ("embed",)},
        "blocks": {
            "ln_1": {"scale": ("layers", "embed"), "bias": ("layers", "embed")},
            "ln_2": {"scale": ("layers", "embed"), "bias": ("layers", "embed")},
            "attn": {
                "c_attn_w": ("layers", "embed", "qkv"),
                "c_attn_b": ("layers", "qkv"),
                "c_proj_w": ("layers", "heads", "embed"),
                "c_proj_b": ("layers", "embed"),
            },
            "mlp": mlp,
        },
    }


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _layer_norm(x, scale, bias, eps):
    return layer_norm(x, scale, bias, eps)


def _dropout(x, rate: float, rng, train: bool):
    if not train or rate <= 0.0 or rng is None:
        return x
    keep = jax.random.bernoulli(rng, 1.0 - rate, x.shape)
    return jnp.where(keep, x / (1.0 - rate), jnp.zeros((), x.dtype))


def _attention(cfg: GPT2Config, lp, h, train: bool, rng=None):
    B, S, E = h.shape
    H, D = cfg.n_head, cfg.head_dim
    with parts.part("attn.qkv"):
        qkv = h @ _deq(lp["c_attn_w"], h.dtype) + lp["c_attn_b"]  # [B,S,3E]
        q, k_, v = jnp.split(qkv, 3, axis=-1)

    def heads(x):
        return x.reshape(B, S, H, D)

    with parts.part("attn.core"):
        q, k_, v = heads(q), heads(k_), heads(v)

        if cfg.attn_impl in ("ring", "ring_flash", "ulysses"):
            from ..parallel.sequence import sequence_parallel_attention

            assert cfg.mesh is not None, f"attn_impl={cfg.attn_impl} requires cfg.mesh"
            o = sequence_parallel_attention(q, k_, v, cfg.mesh, impl=cfg.attn_impl)
        elif cfg.attn_impl == "sparse":
            from ..ops.sparse_attention import FixedSparsityConfig, sparse_attention

            sp = cfg.sparsity or FixedSparsityConfig(num_heads=H)
            o = sparse_attention(q, k_, v, sp, causal=True)
        else:
            from ..ops.attention import causal_attention

            o = causal_attention(q, k_, v, impl=cfg.attn_impl)  # [B,S,H,D]
        # name the kernel output so remat policies can save it: a Pallas
        # custom_vjp output is not a dot_general, so even dots_saveable would
        # otherwise re-run the whole flash forward to rebuild c_proj's input
        o = checkpoint_name(o.reshape(B, S, E), "attn_out")
    with parts.part("attn.out"):
        out = o @ _deq(lp["c_proj_w"], o.dtype) + lp["c_proj_b"]
    return out


def _mlp(cfg: GPT2Config, lp, h, train: bool, rng=None, tp_axis=None):
    """Dense or MoE FFN; returns (out, aux_loss).

    ``tp_axis`` (ISSUE 14): under the TP-sharded serving ``shard_map``, the
    dense branch's weights arrive column-parallel (``c_fc``) / row-parallel
    (``c_proj``) slices — the projection's partial product is psum-reduced
    over the named axis BEFORE the replicated bias is added once. None (the
    default, and every training caller) is the exact historical graph."""
    if cfg.is_moe:
        from ..moe.sharded_moe import MoEConfig, moe_mlp

        mcfg = MoEConfig(
            num_experts=cfg.moe_experts,
            k=cfg.moe_top_k,
            capacity_factor=cfg.moe_capacity_factor,
            eval_capacity_factor=(
                cfg.moe_eval_capacity_factor
                if cfg.moe_eval_capacity_factor is not None
                else cfg.moe_capacity_factor
            ),
            drop_tokens=cfg.moe_drop_tokens,
            use_rts=cfg.moe_use_rts,
            second_policy=cfg.moe_second_policy,
        )
        return moe_mlp(lp, h, mcfg, rng=rng, train=train, mesh=cfg.mesh)
    x = h @ _deq(lp["c_fc_w"], h.dtype) + lp["c_fc_b"]
    x = jax.nn.gelu(x, approximate=True)
    out = x @ _deq(lp["c_proj_w"], x.dtype)
    if tp_axis is not None:
        out = jax.lax.psum(out, tp_axis)
    return out + lp["c_proj_b"], jnp.float32(0.0)


class GPT2Family:
    """What ``serving/model.py`` asks of a model (see its ``Family`` notes):
    learned positions, LayerNorm, one K and V head per query head, every
    layer's cache paged under the block table, a tied head. Under the TP
    ``shard_map`` the config is the per-rank one and ``tp_axis`` names the
    mesh axis the row-parallel partial products are summed over.

    Where two heads fill the 128 lanes (``2 * head_dim == 128``: every
    published GPT-2) and the attention goes through the paged dispatcher, the
    cache holds head PAIRS, as the ``Family`` notes describe for differential
    attention: ``n_kv_head = ceil(n_head / 2)`` heads of 128 lanes, ``[k_2p |
    k_2p+1]``, under ``n_head = 2 * n_kv_head`` zero-padded queries ``[q_2p |
    0]``, ``[0 | q_2p+1]``, scaled by the PUBLISHED head's width. The zeros add
    exactly 0 to every product, so the scores, the probabilities and the lanes
    ``attn_out`` keeps (head 2p's first half, head 2p + 1's second) are the
    published model's; every lane of a page is a value, where a 64-wide head
    pads its tile to twice its bytes, and the paged kernels batch half the
    heads. An ODD head count (XL's 25) gets one zero head behind the last, in
    the activations only: the weights stay as published. Under TP the pairs
    are made inside a rank's heads. Not paired: ``attn_impl="jnp"`` (the
    programs' own dense branches, which mirror ``generate()``'s bits a head a
    published head) and a config from :meth:`GPT2Config.per_head_cache` (an
    int8 cache, whose pages carry one scale a cached head)."""

    prefill_block = 0      # the whole-prompt program attends as one dense product
    kv_pools = 2           # a K and a V pool
    sparse_layers = ()     # no layer reports expert loads
    experts_held = 0
    experts_per_token = 0
    sm_scale = None        # 1 / sqrt(head_dim), but for pairs (below)
    # the leaves ``embed`` takes ROWS of: a placement lays them row-major, once (a v5e's own
    # order for [50257, 1600] is vocabulary-minor, which every program re-laid to gather 8
    # rows); the tied head contracts against the same bytes
    row_gathered = ("wte", "wpe")

    def __init__(self, cfg: GPT2Config, pairs: bool = True):
        self.cfg = cfg
        self.n_layer, self.n_head, self.n_kv_head = cfg.n_layer, cfg.n_head, cfg.n_head
        self.head_dim, self.vocab_size, self.n_positions = cfg.head_dim, cfg.vocab_size, cfg.n_positions
        self.attn_impl = cfg.attn_impl
        self.windows = (0,) * cfg.n_layer
        self.pairs = pairs and 2 * cfg.head_dim == 128 and cfg.attn_impl in ("auto", "pallas")
        if self.pairs:
            self.n_kv_head = -(-cfg.n_head // 2)
            self.n_head, self.head_dim = 2 * self.n_kv_head, 2 * cfg.head_dim
            self.sm_scale = 1.0 / np.sqrt(cfg.head_dim)
            # [pairs, 2, 128]: the lanes of pair p that are published head 2p + r's own
            # (none for the padding head behind an odd count's last)
            head = 2 * np.arange(self.n_kv_head)[:, None, None] + np.arange(2)[:, None]
            self._own = (head < cfg.n_head) & ((np.arange(self.head_dim) >= cfg.head_dim) == (head % 2 == 1))
        self.v_width = self.head_dim

    def embed(self, params, ids, positions):
        te, pe = params["wte"][ids], params["wpe"][positions]
        if ids.ndim == 1:  # the decode step: a token a slot
            return te[:, None, :] + pe[:, None, :]
        return te + (pe if pe.ndim == te.ndim else pe[None])  # one row's [S] positions

    def layer(self, params, l: int):
        # a static index: XLA folds the slices into their consumers
        return jax.tree_util.tree_map(lambda x: x[l], params["blocks"])

    def qkv(self, lp, h, positions, l: int):
        from ..ops.layer_norm import layer_norm_inference

        cfg = self.cfg
        with parts.part("norm"):
            hn = layer_norm_inference(h, lp["ln_1"]["scale"], lp["ln_1"]["bias"], cfg.layer_norm_epsilon)
        qkv = hn @ _deq(lp["attn"]["c_attn_w"], hn.dtype) + lp["attn"]["c_attn_b"]
        if not self.pairs:
            return tuple(
                t.reshape(*t.shape[:-1], cfg.n_head, cfg.head_dim) for t in jnp.split(qkv, 3, axis=-1)
            )
        # in LANES, before any reshape: a pair is one lane tile of a window of
        # the row. An odd count's last pair ends in a zero head: V's window runs
        # into the row's padding, K's (which runs into V) is cut to its own
        # lanes, Q's (which runs into K) by the select below
        E, KV, W = cfg.n_embd, self.n_kv_head, self.head_dim
        lanes, zero = qkv.ndim - 1, jnp.zeros((), qkv.dtype)
        row = lax.pad(qkv, zero, [(0, 0, 0)] * lanes + [(0, KV * W - E, 0)])
        q, k, v = (
            lax.slice_in_dim(row, i * E, i * E + KV * W, axis=lanes).reshape(*row.shape[:-1], KV, W)
            for i in range(3)
        )
        if KV * W > E:
            k = jnp.where(self._own.any(1), k, zero)
        # head 2p is [q | 0], head 2p + 1 [0 | q]: a select over a constant of lanes
        q = jnp.where(self._own, q[..., None, :], zero)
        return q.reshape(*q.shape[:-3], 2 * KV, W), k, v

    def attn_out(self, lp, o, tp_axis=None):
        if self.pairs:
            # head 2p's product is its first 64 lanes, head 2p + 1's its last;
            # the padding head goes
            o = o.reshape(*o.shape[:-1], *self._own.shape)
            o = jnp.where(self._own, o, jnp.zeros((), o.dtype)).sum(-2)
            o = o.reshape(*o.shape[:-2], -1)[..., : self.cfg.n_embd]
        # row-parallel under TP: the partial product is summed over the axis
        # BEFORE the replicated bias is added once
        out = o @ _deq(lp["attn"]["c_proj_w"], o.dtype)
        if tp_axis is not None:
            out = lax.psum(out, tp_axis)
        return out + lp["attn"]["c_proj_b"]

    def mlp(self, lp, h, l: int, valid=None, tp_axis=None):
        from ..ops.layer_norm import layer_norm_inference

        with parts.part("norm"):
            hn = layer_norm_inference(h, lp["ln_2"]["scale"], lp["ln_2"]["bias"], self.cfg.layer_norm_epsilon)
        return _mlp(self.cfg, lp["mlp"], hn, False, None, tp_axis=tp_axis)[0], None

    def logits(self, params, h):
        from ..ops.layer_norm import layer_norm_inference

        h = layer_norm_inference(h, params["ln_f"]["scale"], params["ln_f"]["bias"], self.cfg.layer_norm_epsilon)
        return (h @ params["wte"].T)[..., : self.cfg.vocab_size]


def _block(cfg: GPT2Config, layer_params, h, train: bool, rng=None):
    eps = cfg.layer_norm_epsilon
    r1 = r2 = r3 = None
    if rng is not None:
        # distinct keys per stochastic op: attn dropout, MoE routing, mlp dropout
        r1, r2, r3 = jax.random.split(rng, 3)
    # the residual adds go with the part whose output they take in
    with parts.part("norm"):
        hn = _layer_norm(h, layer_params["ln_1"]["scale"], layer_params["ln_1"]["bias"], eps)
    a = _attention(cfg, layer_params["attn"], hn, train, r1)
    with parts.part("attn.out"):
        h = h + _dropout(a, cfg.dropout, r1, train)
    with parts.part("norm"):
        hn = _layer_norm(h, layer_params["ln_2"]["scale"], layer_params["ln_2"]["bias"], eps)
    with parts.part("mlp"):
        m, aux = _mlp(cfg, layer_params["mlp"], hn, train, r2)
        # the residual stream a block hands on is STATED to be sharded over the batch:
        # under ZeRO-3 the layer's collectives are then its weights' (a gather at each
        # use, a reduce-scatter of each gradient), where the partitioner otherwise takes
        # the feature-sharded weights' placement for the activations' and runs the layer
        # tensor-parallel over dp. This one pin is enough: the layer's other activations
        # follow it, forward and (a cotangent takes its primal's constraint) backward
        return on_batch_axis(h + _dropout(m, cfg.dropout, r3, train)), aux


def _tag_boundary(cfg: GPT2Config, h):
    """Mark the block-input boundary activation for host offload under
    ``cpu_checkpointing`` (reference checkpointing.py:480). With the
    save-and-offload remat policy the saved residual — the checkpointed
    body's input — lives in pinned host RAM between forward and backward."""
    if cfg.remat and cfg.cpu_checkpointing:
        from ..runtime.activation_checkpointing.checkpointing import offload_name

        return offload_name(h)
    return h


def _partition_boundary(cfg: GPT2Config, h):
    """Shard the block-output boundary activation over tp (reference
    partition_activations, checkpointing.py:367): the scan saves each carry
    as a residual, so constraining the produced carry makes every saved
    checkpoint live as 1/tp slices; XLA all-gathers in backward exactly where
    the reference calls gather_partitioned_activations:259."""
    if (
        cfg.partition_activations
        and cfg.mesh is not None
        and "tp" in cfg.mesh.axis_names
        and cfg.mesh.shape["tp"] > 1
        and h.shape[-1] % cfg.mesh.shape["tp"] == 0
    ):
        from jax.sharding import NamedSharding

        return lax.with_sharding_constraint(
            h, NamedSharding(cfg.mesh, PartitionSpec(None, None, "tp"))
        )
    return h


def _remat_policy(cfg: GPT2Config):
    """jax.checkpoint policy for the block body: offload-capable when
    cpu_checkpointing; "dots" saves matmul + attention-kernel outputs
    (recompute only the cheap elementwise tail); "attn" saves only the
    attention output (backward never re-runs the flash forward); default
    full remat (save nothing, recompute)."""
    if cfg.cpu_checkpointing:
        from ..runtime.activation_checkpointing.checkpointing import _offload_policy

        return _offload_policy()
    if cfg.remat_policy == "dots":
        return jax.checkpoint_policies.save_from_both_policies(
            jax.checkpoint_policies.dots_saveable,
            jax.checkpoint_policies.save_only_these_names("attn_out"),
        )
    if cfg.remat_policy == "attn":
        return jax.checkpoint_policies.save_only_these_names("attn_out")
    if cfg.remat_policy != "full":
        raise ValueError(
            f"unknown remat_policy {cfg.remat_policy!r} (full|dots|attn)"
        )
    return None


def _pld_block(cfg: GPT2Config, layer_params, h, train: bool, key, theta, layer_id, pld_key):
    """Stochastic-depth block for Progressive Layer Drop (reference
    progressive_layer_drop.py:5). Layer i of L keeps with probability
    ``1 - (i/L)*(1-theta)``; ``lax.cond`` actually skips the dropped block's
    FLOPs (the training-speedup point of PLD), and the kept output's residual
    delta is scaled by 1/keep_prob so the eval forward (all layers, no
    scaling) matches in expectation."""
    kp = 1.0 - (layer_id / cfg.n_layer) * (1.0 - theta)
    keep = jax.random.bernoulli(pld_key, kp)
    hb, aux = lax.cond(
        keep,
        lambda hh: _block(cfg, layer_params, hh, train, key),
        lambda hh: (hh, jnp.float32(0.0)),
        h,
    )
    # both the residual delta and the MoE aux loss are inverse-scaled so their
    # expectations match the all-layers forward (aux fires only when kept)
    return h + (hb - h) / kp.astype(h.dtype), aux / kp


def hidden_with_aux(
    cfg: GPT2Config,
    params: PyTree,
    input_ids: jnp.ndarray,
    train: bool = False,
    rng=None,
    pld_theta=None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """input_ids [B,S] → (final-LN hidden states [B,S,E], moe_aux_loss
    scalar) — the pre-head trunk, so losses can choose whether to
    materialize full logits. ``pld_theta`` (traced scalar) engages
    progressive layer drop during training."""
    B, S = input_ids.shape
    with parts.part("embed"):
        h = params["wte"][input_ids] + params["wpe"][:S][None, :, :]
    # rng per layer when dropout or MoE stochastic routing needs it
    need_rng = rng is not None and (
        (train and cfg.dropout > 0.0)
        or (cfg.is_moe and train and (cfg.moe_top_k == 2 or cfg.moe_use_rts))
    )
    use_pld = pld_theta is not None and train and rng is not None
    if need_rng or use_pld:
        if train and cfg.dropout > 0.0:
            with parts.part("embed"):
                h = _dropout(h, cfg.dropout, jax.random.fold_in(rng, cfg.n_layer), train)
        xs = {
            "lp": params["blocks"],
            "key": jax.random.split(jax.random.fold_in(rng, 0), cfg.n_layer),
        }
        if use_pld:
            theta = jnp.asarray(pld_theta, jnp.float32)
            xs["pld_key"] = jax.random.split(jax.random.fold_in(rng, 1), cfg.n_layer)
            xs["layer_id"] = jnp.arange(cfg.n_layer, dtype=jnp.float32)

        def body(carry, x):
            h, aux_sum = carry
            h = _tag_boundary(cfg, h)
            key = x["key"] if need_rng else None
            if use_pld:
                h, aux = _pld_block(
                    cfg, x["lp"], h, train, key, theta, x["layer_id"], x["pld_key"]
                )
            else:
                h, aux = _block(cfg, x["lp"], h, train, key)
            return (_partition_boundary(cfg, h), aux_sum + aux), None

    else:

        def body(carry, layer_params):
            h, aux_sum = carry
            h = _tag_boundary(cfg, h)
            h, aux = _block(cfg, layer_params, h, train, None)
            return (_partition_boundary(cfg, h), aux_sum + aux), None

        xs = params["blocks"]

    if cfg.remat:
        body = jax.checkpoint(body, policy=_remat_policy(cfg), prevent_cse=False)
    (h, aux_total), _ = lax.scan(body, (h, jnp.float32(0.0)), xs)
    with parts.part("head"):
        h = _layer_norm(h, params["ln_f"]["scale"], params["ln_f"]["bias"], cfg.layer_norm_epsilon)
    return h, aux_total


def forward_with_aux(
    cfg: GPT2Config,
    params: PyTree,
    input_ids: jnp.ndarray,
    train: bool = False,
    rng=None,
    pld_theta=None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """input_ids [B,S] → (logits [B,S,V], moe_aux_loss scalar)."""
    h, aux_total = hidden_with_aux(
        cfg, params, input_ids, train=train, rng=rng, pld_theta=pld_theta
    )
    # tied embeddings; the public contract is [B,S,V] LOGICAL vocab — slice
    # off padded head columns (pad_vocab_multiple) rather than masking, so
    # shape-checking consumers (one_hot sizing, tokenizer tables) stay right
    logits = (h @ params["wte"].T)[..., : cfg.vocab_size]
    return logits, aux_total


def forward(cfg: GPT2Config, params: PyTree, input_ids: jnp.ndarray, train: bool = False, rng=None) -> jnp.ndarray:
    """input_ids [B,S] → logits [B,S,V]. ``rng`` enables dropout when train."""
    return forward_with_aux(cfg, params, input_ids, train=train, rng=rng)[0]


def lm_loss(
    cfg: GPT2Config,
    params: PyTree,
    batch: Dict[str, jnp.ndarray],
    rng,
    train: bool,
    pld_theta=None,
) -> Tuple[jnp.ndarray, Dict]:
    """Next-token cross-entropy. batch: {"input_ids": [B,S]} and optional
    {"labels": [B,S]} (-100 = ignore, HF convention) / {"attention_mask"}."""
    ids = batch["input_ids"]
    h, moe_aux = hidden_with_aux(
        cfg, params, ids, train=train, rng=rng, pld_theta=pld_theta
    )
    loss, ntokens = _head_token_loss(cfg, params["wte"], h, batch)
    # aux load-balancing penalty only shapes the training objective; eval loss
    # stays pure LM cross-entropy (comparable to dense baselines)
    if cfg.is_moe and train:
        loss = loss + cfg.moe_aux_loss_weight * moe_aux
    return loss, {"ntokens": ntokens, "moe_aux": moe_aux}


def _head_token_loss(cfg: GPT2Config, wte, h, batch):
    """Head projection + shifted CE from final hidden states; chunked when
    cfg.ce_chunk > 0 (shared by the plain, pipeline, and offload paths so
    the knob works everywhere). Math lives in models/lm_loss.py."""
    from .lm_loss import head_token_loss

    with parts.part("head"):
        return head_token_loss(
            lambda x: x @ wte.T, h, batch, cfg.ce_chunk, logical_vocab=cfg.vocab_size
        )


def pipeline_lm_loss(cfg: GPT2Config, params: PyTree, batch_micro, rng, train: bool, mesh):
    """All-microbatch LM loss through the pp pipeline.

    batch_micro leaves are [M, mb, ...]; blocks run as pipeline stages
    (parallel/pipeline.py), embedding/head replicated (tied-grad psum is
    automatic — the _exec_reduce_tied_grads analog).
    """
    from ..parallel.pipeline import pipeline_apply

    ids = batch_micro["input_ids"]  # [M, mb, S]
    M, mb, S = ids.shape
    h0 = params["wte"][ids] + params["wpe"][:S][None, None, :, :]  # [M, mb, S, E]
    use_rng = rng is not None and train and cfg.dropout > 0.0
    if use_rng:
        h0 = _dropout(h0, cfg.dropout, jax.random.fold_in(rng, 2), train)

        def stage_fn(local_layers, h, key):
            def body(carry, lp):
                hh, j = carry
                out, _aux = _block(cfg, lp, hh, train, jax.random.fold_in(key, j))
                return (out, j + 1), None

            (h, _), _ = lax.scan(body, (h, jnp.int32(0)), local_layers)
            return h

    else:

        def stage_fn(local_layers, h):
            def body(carry, lp):
                out, _aux = _block(cfg, lp, carry, train, None)
                return out, None

            h, _ = lax.scan(body, h, local_layers)
            return h

    h_out = pipeline_apply(
        stage_fn,
        params["blocks"],
        h0,
        mesh,
        remat_stage=cfg.remat,
        rng=jax.random.fold_in(rng, 1) if use_rng else None,
    )
    h_out = _layer_norm(h_out, params["ln_f"]["scale"], params["ln_f"]["bias"], cfg.layer_norm_epsilon)

    # head matmul + loss per microbatch: materializing [M, mb, S, V] logits at
    # once would cost M× the activation memory the pipeline exists to save
    def per_micro(i, acc):
        micro_batch = jax.tree.map(lambda x: x[i], batch_micro)
        return acc + _head_token_loss(cfg, params["wte"], h_out[i], micro_batch)[0]

    total = lax.fori_loop(0, M, per_micro, jnp.float32(0.0))
    return total / M, {}


# ---------------------------------------------------------------------------
# incremental decode with KV cache (reference transformer_inference
# softmax_context path: ops/transformer/inference/transformer_inference.py:231,
# csrc/transformer/inference attention kernels with layer_past)
# ---------------------------------------------------------------------------

class KVCache(NamedTuple):
    """Per-layer stacked KV cache. ``pos`` is the filled length (i32)."""

    k: jnp.ndarray  # [L, B, Smax, H, D]
    v: jnp.ndarray  # [L, B, Smax, H, D]
    pos: jnp.ndarray  # i32


def init_cache(cfg: GPT2Config, batch_size: int, max_len: int, dtype=jnp.bfloat16) -> KVCache:
    shape = (cfg.n_layer, batch_size, max_len, cfg.n_head, cfg.head_dim)
    return KVCache(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype), pos=jnp.int32(0))


def cache_logical_axes() -> KVCache:
    """Shard the cache over heads (tp) like attention activations."""
    return KVCache(k=(None, None, None, "heads", None), v=(None, None, None, "heads", None), pos=None)


def _attention_cached(cfg: GPT2Config, lp, h, k_cache, v_cache, pos):
    """Attention for h [B,S,E] against a KV cache.

    Writes this chunk's K/V at [pos, pos+S), attends causally to everything
    ≤ its absolute position. S=prompt length at prefill, 1 at decode."""
    B, S, E = h.shape
    H, D = cfg.n_head, cfg.head_dim
    qkv = h @ _deq(lp["c_attn_w"], h.dtype) + lp["c_attn_b"]
    q, k_, v = jnp.split(qkv, 3, axis=-1)
    q = q.reshape(B, S, H, D)
    k_ = k_.reshape(B, S, H, D).astype(k_cache.dtype)
    v = v.reshape(B, S, H, D).astype(v_cache.dtype)

    k_cache = lax.dynamic_update_slice(k_cache, k_, (0, pos, 0, 0))
    v_cache = lax.dynamic_update_slice(v_cache, v, (0, pos, 0, 0))

    Smax = k_cache.shape[1]
    scale = 1.0 / np.sqrt(D)

    if S == 1 and cfg.attn_impl in ("auto", "pallas"):
        # single-token decode: ops.cached_attention dispatches to the Pallas
        # online-softmax kernel on TPU (streams the cache through VMEM
        # instead of materializing [B,H,1,Smax] scores — the reference
        # softmax_context fused kernel) with a jnp fallback built in
        from ..ops.attention import cached_attention

        o1 = cached_attention(q[:, 0], k_cache, v_cache, pos, impl=cfg.attn_impl, sm_scale=scale)
        o = o1.reshape(B, 1, E).astype(h.dtype)  # [B,H,D] -> [B,1,E]
        return o @ _deq(lp["c_proj_w"], h.dtype) + lp["c_proj_b"], k_cache, v_cache

    scores = jnp.einsum("bshd,bthd->bhst", q.astype(jnp.float32), k_cache.astype(jnp.float32)) * scale
    # query i sits at absolute position pos+i; may see keys j <= pos+i
    j_idx = jnp.arange(Smax)
    i_idx = pos + jnp.arange(S)
    mask = j_idx[None, :] <= i_idx[:, None]  # [S, Smax]
    scores = jnp.where(mask[None, None, :, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(v_cache.dtype)
    o = jnp.einsum("bhst,bthd->bshd", probs, v_cache)
    o = o.reshape(B, S, E).astype(h.dtype)
    return o @ _deq(lp["c_proj_w"], h.dtype) + lp["c_proj_b"], k_cache, v_cache


def forward_cached(
    cfg: GPT2Config, params: PyTree, input_ids: jnp.ndarray, cache: KVCache,
    logits_at=None,
) -> Tuple[jnp.ndarray, KVCache]:
    """input_ids [B,S] (S tokens starting at cache.pos) → (last-token logits
    [B,V], updated cache). One function serves prefill (S=prompt) and decode
    (S=1) — the reference splits these across qkv_gemm/softmax_context kernels.

    ``logits_at`` (optional traced i32): read the head at this in-chunk
    position instead of the last one — the bucket-padded prefill
    (serving/model.generate_padded) feeds a right-padded chunk and needs the
    logits of the true last prompt token.
    """
    B, S = input_ids.shape
    pos = cache.pos
    eps = cfg.layer_norm_epsilon
    positions = pos + jnp.arange(S)
    h = params["wte"][input_ids] + params["wpe"][positions][None, :, :]

    def body(carry, xs):
        h = carry
        lp, k_c, v_c = xs
        a, k_c, v_c = _attention_cached(
            cfg, lp["attn"], _layer_norm(h, lp["ln_1"]["scale"], lp["ln_1"]["bias"], eps), k_c, v_c, pos
        )
        h = h + a
        m, _aux = _mlp(cfg, lp["mlp"], _layer_norm(h, lp["ln_2"]["scale"], lp["ln_2"]["bias"], eps), False, None)
        return h + m, (k_c, v_c)

    h, (new_k, new_v) = lax.scan(body, h, (params["blocks"], cache.k, cache.v))
    h = h[:, -1] if logits_at is None else jnp.take(h, logits_at, axis=1)
    h = _layer_norm(h, params["ln_f"]["scale"], params["ln_f"]["bias"], eps)
    # [B, V] logical vocab: padded head columns sliced off (see forward_with_aux)
    logits = (h @ params["wte"].T)[..., : cfg.vocab_size]
    return logits, KVCache(k=new_k, v=new_v, pos=pos + S)


def generate(
    cfg: GPT2Config,
    params: PyTree,
    input_ids: jnp.ndarray,
    max_new_tokens: int,
    temperature: float = 0.0,
    rng=None,
    max_len: Optional[int] = None,
    cache_dtype=jnp.bfloat16,
    top_k: int = 0,
    top_p: float = 1.0,
) -> jnp.ndarray:
    """Fully jitted autoregressive generation: prefill once, then a
    ``lax.scan`` of single-token decode steps over the KV cache (the
    compiled-executable analog of the reference's CUDA-graph decode replay,
    inference/engine.py:486). Returns [B, max_new_tokens]."""
    B, S = input_ids.shape
    if max_len is None:
        max_len = S + max_new_tokens
    if max_len > cfg.n_positions or max_len < S + max_new_tokens:
        raise ValueError(
            f"prompt ({S}) + max_new_tokens ({max_new_tokens}) needs a cache of "
            f"{S + max_new_tokens} but max_len={max_len} (n_positions={cfg.n_positions}); "
            "a shorter cache would silently overwrite KV entries"
        )
    if rng is None:
        rng = jax.random.PRNGKey(0)

    cache = init_cache(cfg, B, max_len, dtype=cache_dtype)
    logits, cache = forward_cached(cfg, params, input_ids, cache)

    from ..ops.sampling import sample_logits

    def sample(logits, key):
        return sample_logits(logits, key, temperature, top_k, top_p)

    first = sample(logits, rng)

    def step(carry, key):
        token, cache = carry
        logits, cache = forward_cached(cfg, params, token[:, None].astype(input_ids.dtype), cache)
        nxt = sample(logits, key)
        return (nxt, cache), token

    if max_new_tokens == 1:
        return first[:, None]
    # each step consumes token t_i, emits it, and produces t_{i+1};
    # N-1 steps yield [t_1..t_{N-1}] with t_N left in the carry
    keys = jax.random.split(jax.random.fold_in(rng, 1), max_new_tokens - 1)
    (last, _), tokens = lax.scan(step, (first, cache), keys)
    return jnp.concatenate([jnp.moveaxis(tokens, 0, 1), last[:, None]], axis=1)


def make_block_api(cfg: GPT2Config):
    """Block-structured view for ZeRO-Infinity parameter streaming
    (runtime/zero/infinity.py) — the analog of the reference's per-submodule
    fetch/release cycle (partitioned_param_coordinator.py:237,356) expressed
    as explicit embed/block/head programs. Persistent part = wte/wpe/ln_f
    (tied head), matching stage3_param_persistence_threshold semantics."""
    from ..runtime.zero.infinity import BlockAPI

    assert not cfg.is_moe, "block streaming: dense blocks only (v1)"
    E, V, P, L = cfg.n_embd, cfg.vocab_size, cfg.n_positions, cfg.n_layer
    std = 0.02
    pstd = std / float(np.sqrt(2.0 * L))
    dt = cfg.dtype
    eps = cfg.layer_norm_epsilon

    def init_persistent(rng):
        k1, k2 = jax.random.split(rng)
        wte = (jax.random.normal(k1, (cfg.padded_vocab_size, E)) * std).astype(dt)
        if cfg.padded_vocab_size > V:
            wte = wte.at[V:].set(0)
        return {
            "wte": wte,
            "wpe": (jax.random.normal(k2, (P, E)) * std).astype(dt),
            "ln_f": {"scale": jnp.ones((E,), dt), "bias": jnp.zeros((E,), dt)},
        }

    def init_block(rng, i):
        k = iter(jax.random.split(jax.random.fold_in(rng, i), 8))

        def normal(key, shape, s):
            return (jax.random.normal(key, shape) * s).astype(dt)

        return {
            "ln_1": {"scale": jnp.ones((E,), dt), "bias": jnp.zeros((E,), dt)},
            "ln_2": {"scale": jnp.ones((E,), dt), "bias": jnp.zeros((E,), dt)},
            "attn": {
                "c_attn_w": normal(next(k), (E, 3 * E), std),
                "c_attn_b": jnp.zeros((3 * E,), dt),
                "c_proj_w": normal(next(k), (E, E), pstd),
                "c_proj_b": jnp.zeros((E,), dt),
            },
            "mlp": {
                "c_fc_w": normal(next(k), (E, 4 * E), std),
                "c_fc_b": jnp.zeros((4 * E,), dt),
                "c_proj_w": normal(next(k), (4 * E, E), pstd),
                "c_proj_b": jnp.zeros((E,), dt),
            },
        }

    def embed_fwd(pers, batch, rng, train):
        ids = batch["input_ids"]
        S = ids.shape[1]
        h = pers["wte"][ids] + pers["wpe"][:S][None, :, :]
        if train and cfg.dropout > 0.0:
            h = _dropout(h, cfg.dropout, rng, train)
        return h

    def block_fwd(blk, h, rng, train):
        key = rng if (train and cfg.dropout > 0.0) else None
        h, _aux = _block(cfg, blk, h, train, key)
        return h

    def head_loss(pers, h, batch):
        h = _layer_norm(h, pers["ln_f"]["scale"], pers["ln_f"]["bias"], eps)
        loss, _ntok = _head_token_loss(cfg, pers["wte"], h, batch)
        return loss

    def split_params(params):
        pers = {"wte": params["wte"], "wpe": params["wpe"], "ln_f": params["ln_f"]}
        blocks = [
            jax.tree.map(lambda x: x[i], params["blocks"]) for i in range(L)
        ]
        return pers, blocks

    # numpy-native init (InfinityEngine host_init): same structure and
    # distribution as the device init, built straight into DRAM — at 13B the
    # device path would stream ~50 GB of initial masters D2H before step 0
    def host_init_persistent(gen):
        wte = gen.standard_normal((cfg.padded_vocab_size, E), dtype=np.float32) * std
        if cfg.padded_vocab_size > V:
            wte[V:] = 0
        return {
            "wte": wte,
            "wpe": gen.standard_normal((P, E), dtype=np.float32) * std,
            "ln_f": {"scale": np.ones((E,), np.float32), "bias": np.zeros((E,), np.float32)},
        }

    def host_init_block(gen, i):
        def normal(shape, s):
            return gen.standard_normal(shape, dtype=np.float32) * s

        return {
            "ln_1": {"scale": np.ones((E,), np.float32), "bias": np.zeros((E,), np.float32)},
            "ln_2": {"scale": np.ones((E,), np.float32), "bias": np.zeros((E,), np.float32)},
            "attn": {
                "c_attn_w": normal((E, 3 * E), std),
                "c_attn_b": np.zeros((3 * E,), np.float32),
                "c_proj_w": normal((E, E), pstd),
                "c_proj_b": np.zeros((E,), np.float32),
            },
            "mlp": {
                "c_fc_w": normal((E, 4 * E), std),
                "c_fc_b": np.zeros((4 * E,), np.float32),
                "c_proj_w": normal((4 * E, E), pstd),
                "c_proj_b": np.zeros((E,), np.float32),
            },
        }

    return BlockAPI(
        num_blocks=L,
        init_persistent=init_persistent,
        init_block=init_block,
        embed_fwd=embed_fwd,
        block_fwd=block_fwd,
        head_loss=head_loss,
        split_params=split_params,
        host_init_persistent=host_init_persistent,
        host_init_block=host_init_block,
    )


def make_module(cfg: GPT2Config) -> ModuleSpec:
    return ModuleSpec(
        init=lambda rng: init_params(cfg, rng),
        loss_fn=lambda params, batch, rng, train: lm_loss(cfg, params, batch, rng, train),
        pld_loss_fn=lambda params, batch, rng, train, theta: lm_loss(
            cfg, params, batch, rng, train, pld_theta=theta
        ),
        apply_fn=lambda params, batch: forward(cfg, params, batch["input_ids"], train=False),
        logical_axes=logical_axes(cfg),
        num_layers=cfg.n_layer,
        pipeline_loss_fn=None if cfg.is_moe else (
            lambda params, batch, rng, train, mesh: pipeline_lm_loss(cfg, params, batch, rng, train, mesh)
        ),
        extra={
            "config": cfg,
            # lazy: built only when the engine engages the param-offload tier
            "block_api": (None if cfg.is_moe else (lambda: make_block_api(cfg))),
        },
    )
