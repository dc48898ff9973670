"""Placement + ProgramSet: where each serving program runs, and on what.

ISSUE 14's tentpole abstraction. A :class:`Placement` is a named mesh slice
(``tp`` consecutive devices under a one-axis ``Mesh(("tp",))``, or a single
device) plus the sharding-spec table that maps the injected gpt2 tree onto
it. A :class:`ProgramSet` is everything that must live *together* on one
placement: the placed parameter tree, the paged K/V pools (+ int8 scales)
sharded ``1/tp`` over the KV-head axis, the page allocator that hands out
page ids in that pool, and the AOT-compiled executables that consume them.

The scheduler composes these two ways:

- **shared** (default): one placement, one ProgramSet — prefill, decode /
  verify, and chunked prefill all target the same pools. ``tp = 1``
  reproduces the pre-ISSUE-14 engine byte-for-byte (no mesh, no
  ``shard_map`` wrapper, identical HLO).
- **disaggregated** (``serving.placement.disaggregate``): prefill +
  chunked prefill compile for a *prefill* placement with its own (smaller)
  pool and allocator; decode/verify for a *decode* placement that owns the
  slot table. Finished prompt KV rides a gather → ``jax.device_put`` →
  scatter handoff from the prefill pool into the decode pool's pages
  (scheduler ``_complete_handoff``); block tables, refcounts, COW and the
  prefix index stay host-side and placement-local.

The spec table (:data:`GPT2_SERVING_RULES`) is simultaneously operational
(it builds the ``NamedSharding``s and ``shard_map`` in_specs) and verified
(``ServingEngine.verify()`` feeds the same table through Engine F
*pre-compile* — ``analysis.sharding.rules`` overrides it for both uses, so
the verifier can never drift from the placement it describes).

Head-parallel TP (see /opt/skills/guides: shard heads, psum once after the
output projection): ``c_attn`` is column-parallel with rank-major QKV
columns (``module_inject.tp_shard``), attention runs over the local
``H/tp`` heads against the locally-resident ``KV/tp`` pool slice, and
``attn/c_proj`` + ``mlp/c_proj`` are row-parallel — two ``psum``s per
layer, identical in every program, so Engine D's cross-program
collective-order check passes by construction.
"""

from __future__ import annotations

import dataclasses
from typing import Any, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ..analysis.sharding_rules import (
    ShardingRuleContext,
    _compile_table,
    _first_match,
    verify_spec_table,
)
from ..module_inject.tp_shard import tp_shard_serving_params
from jax import shard_map
from .kv_cache import PageAllocator, init_pools

PyTree = Any

TP_AXIS = "tp"

# The committed ``match_partition_rules`` table for the injected gpt2
# serving tree (satellite 1). First match wins (``re.search``); ``None``
# entries are replicated dims. Kept as plain JSON-compatible lists so the
# same value round-trips through ``analysis.sharding.rules``.
#
#   c_attn:   column-parallel (rank-major QKV columns, tp_shard permute)
#   attn/c_proj, mlp/c_proj: row-parallel (input dim is heads-major /
#             role-free — no permute), bias replicated, added post-psum
#   mlp/c_fc: column-parallel, bias sharded with its columns
#   ln_* / wte / wpe: replicated (gpt2-tiny's wte is ~131 KB — far under
#             Engine F's 1 MB replicated-large-leaf threshold)
GPT2_SERVING_RULES: List[Tuple[str, list]] = [
    ("attn/c_attn_w$", [None, None, TP_AXIS]),
    ("attn/c_attn_b$", [None, TP_AXIS]),
    ("attn/c_proj_w$", [None, TP_AXIS, None]),
    ("attn/c_proj_b$", []),
    ("mlp/c_fc_w$", [None, None, TP_AXIS]),
    ("mlp/c_fc_b$", [None, TP_AXIS]),
    ("mlp/c_proj_w$", [None, TP_AXIS, None]),
    ("mlp/c_proj_b$", []),
    ("ln_[12f]/(scale|bias)$", []),
    ("^w[tp]e$", []),
]


def _path_of(keypath) -> str:
    parts = []
    for k in keypath:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
        elif hasattr(k, "name"):
            parts.append(str(k.name))
        else:
            parts.append(str(k))
    return "/".join(parts)


class Placement:
    """A named core-set: ``tp`` devices under a one-axis mesh + the spec
    table that places the serving tree on it. ``tp == 1`` means no mesh and
    no ``shard_map`` — programs compile exactly as before ISSUE 14, pinned
    to ``devices[0]`` by their committed operands."""

    def __init__(self, name: str, devices: Sequence, tp: int = 1,
                 rules: Optional[Sequence[Tuple[str, list]]] = None):
        self.name = str(name)
        self.devices = list(devices)
        self.tp = int(tp)
        if self.tp < 1:
            raise ValueError(f"placement {name!r}: tp must be >= 1, got {tp}")
        if len(self.devices) != self.tp:
            raise ValueError(
                f"placement {name!r}: got {len(self.devices)} devices for "
                f"tp={self.tp}"
            )
        self.rules = list(rules) if rules is not None else list(GPT2_SERVING_RULES)
        self.device = self.devices[0]
        if self.tp > 1:
            self.mesh: Optional[Mesh] = Mesh(
                np.asarray(self.devices), (TP_AXIS,)
            )
            self.tp_axis: Optional[str] = TP_AXIS
        else:
            self.mesh = None
            self.tp_axis = None

    def __repr__(self):
        devs = ",".join(str(getattr(d, "id", d)) for d in self.devices)
        return f"Placement({self.name!r}, tp={self.tp}, devices=[{devs}])"

    @property
    def mesh_axes(self):
        return {TP_AXIS: self.tp}

    def suffix(self) -> str:
        """Program-name suffix: distinct placements compile distinct HLO
        with distinct per-device footprints, so Engine E budgets and the
        ``.dsmem-budgets.json`` ledger key on it."""
        return f"_tp{self.tp}" if self.tp > 1 else ""

    # -- model / pool geometry ------------------------------------------

    def local_model_config(self, cfg):
        """The per-shard model config the programs trace with: ``n_embd``
        and ``n_head`` divided by tp (``head_dim`` — a derived property —
        is preserved). Identity at tp=1."""
        if self.tp == 1:
            return cfg
        E, H = int(cfg.n_embd), int(cfg.n_head)
        if E % self.tp or H % self.tp:
            raise ValueError(
                f"placement {self.name!r}: n_embd={E}/n_head={H} not "
                f"divisible by tp={self.tp}"
            )
        return dataclasses.replace(cfg, n_embd=E // self.tp, n_head=H // self.tp)

    def pool_spec(self, ndim: int) -> PartitionSpec:
        """KV pools / scales / packed handoff buffers all carry the KV-head
        axis at dim 2 (``[L, P, KV, ...]``) — shard it, replicate the rest."""
        entries = [None] * ndim
        if self.tp > 1:
            entries[2] = TP_AXIS
        return PartitionSpec(*entries)

    def rep_spec(self) -> PartitionSpec:
        return PartitionSpec()

    def put(self, x, spec: Optional[PartitionSpec] = None):
        """Place one array on this placement (``NamedSharding`` at tp>1,
        plain device at tp=1). The default single-device placement is a
        no-op so the legacy path keeps uncommitted arrays untouched."""
        if self.mesh is not None:
            return jax.device_put(
                x, NamedSharding(self.mesh, spec if spec is not None else PartitionSpec())
            )
        if self.device is jax.devices()[0]:
            return x
        return jax.device_put(x, self.device)

    def put_pool(self, x):
        return self.put(x, self.pool_spec(getattr(x, "ndim", len(x.shape))))

    def pull_pool(self, x):
        """Cross-placement transfer of a packed handoff buffer: ALWAYS
        ``device_put`` (unlike :meth:`put`, which leaves default-device
        arrays untouched) — the source lives on ANOTHER placement's
        devices, and the compiled scatter requires its operands here."""
        if self.mesh is not None:
            return jax.device_put(
                x, NamedSharding(self.mesh, self.pool_spec(x.ndim))
            )
        return jax.device_put(x, self.device)

    # -- params ----------------------------------------------------------

    def spec_for(self, path: str) -> PartitionSpec:
        spec, _ = _first_match(_compile_table(self.rules), path)
        return PartitionSpec(*spec)

    def param_spec_tree(self, params: PyTree) -> PyTree:
        """Pytree of ``PartitionSpec``s matching ``params``, resolved
        through the table first-match-wins — the ``shard_map`` in_spec and
        the ``NamedSharding`` source, from ONE resolution path (Engine F's
        ``_first_match``) so verifier and placement cannot disagree."""
        compiled = _compile_table(self.rules)
        return jax.tree_util.tree_map_with_path(
            lambda kp, _leaf: PartitionSpec(
                *_first_match(compiled, _path_of(kp))[0]
            ),
            params,
        )

    def shard_params(self, params: PyTree) -> PyTree:
        """QKV-permute (rank-major columns) + device_put the tree onto this
        placement. tp=1: placement pin only (no permute, no resharding on
        the default device)."""
        if self.tp == 1:
            if self.device is jax.devices()[0]:
                return params
            return jax.tree.map(lambda x: jax.device_put(x, self.device), params)
        permuted = tp_shard_serving_params(params, self.tp)
        specs = self.param_spec_tree(permuted)
        return jax.tree.map(
            lambda x, s: jax.device_put(x, NamedSharding(self.mesh, s)),
            permuted, specs,
        )

    def verify_rules(self, params: PyTree, program: str = "serving_params",
                     replicated_min_bytes: int = 1 << 20):
        """Engine F pre-compile check of this placement's table against the
        (unpermuted) serving tree."""
        ctx = ShardingRuleContext(
            program=program, mesh_axes=self.mesh_axes,
            replicated_min_bytes=int(replicated_min_bytes),
        )
        return verify_spec_table(self.rules, params, ctx)

    # -- compilation -----------------------------------------------------

    def aot(self, fn, example_args: Sequence, in_specs: Sequence,
            out_specs: Sequence, donate: Sequence[int] = ()):
        """AOT-compile ``fn`` for this placement.

        tp=1: plain ``jax.jit(...).lower(...).compile()`` — byte-identical
        to the pre-ISSUE-14 path (placement pinning comes from the
        committed example operands). tp>1: ``shard_map`` over the mesh with
        the given specs, donation threaded through the outer jit (XLA
        aliases the sharded pool buffers per-device)."""
        donate = tuple(donate)
        if self.mesh is None:
            jitted = jax.jit(fn, donate_argnums=donate) if donate else jax.jit(fn)
            return jitted.lower(*example_args).compile()
        mapped = shard_map(
            fn, mesh=self.mesh, in_specs=tuple(in_specs),
            out_specs=tuple(out_specs), check_vma=False,
        )
        jitted = (
            jax.jit(mapped, donate_argnums=donate) if donate else jax.jit(mapped)
        )
        return jitted.lower(*example_args).compile()


class ProgramSet:
    """One placement's working set: placed params, paged K/V pools (+ int8
    scales) sharded over the placement, the page allocator for that pool,
    and the compiled programs that consume them. Donated-pool rehoming
    (``take_pools``) lives here because the donated buffers belong to THIS
    pool, whichever placement ran the program."""

    def __init__(self, placement: Placement, mcfg, num_pages: int,
                 page_size: int, cache_dtype, params: PyTree):
        self.placement = placement
        self.num_pages = int(num_pages)
        self.page_size = int(page_size)
        self.n_layer = int(mcfg.n_layer)
        self.n_kv_head = int(mcfg.n_head)
        self.head_dim = int(mcfg.head_dim)
        k, v, scales = init_pools(
            self.n_layer, self.num_pages, self.n_kv_head, self.page_size,
            self.head_dim, dtype=cache_dtype,
        )
        self.k_pool = placement.put_pool(k)
        self.v_pool = placement.put_pool(v)
        self.kv_scales = placement.put_pool(scales) if scales is not None else None
        self.allocator = PageAllocator(self.num_pages)
        self.params = placement.shard_params(params)
        self.param_specs = (
            placement.param_spec_tree(self.params)
            if placement.mesh is not None else None
        )

    @property
    def quantized(self) -> bool:
        return self.kv_scales is not None

    def pool_args(self) -> tuple:
        """The donated pool operands, in program order."""
        if self.kv_scales is not None:
            return (self.k_pool, self.v_pool, self.kv_scales)
        return (self.k_pool, self.v_pool)

    def take_pools(self, out: tuple):
        """Rehome the donated pools from a program's output tuple and
        return the rest (single element unwrapped, like the scheduler's
        original helper)."""
        self.k_pool, self.v_pool = out[0], out[1]
        rest = out[2:]
        if self.kv_scales is not None:
            self.kv_scales = rest[0]
            rest = rest[1:]
        return rest[0] if len(rest) == 1 else rest

    def set_pools(self, pools: tuple) -> None:
        """Install a full replacement pool tuple (scatter-handoff output)."""
        self.k_pool, self.v_pool = pools[0], pools[1]
        if self.kv_scales is not None:
            self.kv_scales = pools[2]

    # -- geometry for Engines A/E (per-DEVICE shapes at tp>1) ------------

    def local_kv_heads(self) -> int:
        return self.n_kv_head // self.placement.tp

    def local_pool_dims(self) -> str:
        return (
            f"{self.n_layer},{self.num_pages},{self.local_kv_heads()},"
            f"{self.page_size},{self.head_dim}"
        )

    def local_scales_dims(self) -> str:
        return f"{self.n_layer},{self.num_pages},{self.local_kv_heads()},2"

    def packed_dims(self, n_pages: int) -> str:
        """Per-device shape of the gather/scatter handoff payload over
        ``n_pages`` pages."""
        return (
            f"{self.n_layer},{int(n_pages)},{self.local_kv_heads()},"
            f"{self.page_size},{self.head_dim}"
        )

    def packed_scales_dims(self, n_pages: int) -> str:
        return f"{self.n_layer},{int(n_pages)},{self.local_kv_heads()},2"

    def local_pool_bytes(self) -> int:
        """Per-device K+V pool bytes (the quantity the resident-session
        bench and env_report report per placement)."""
        itemsize = jnp.dtype(self.k_pool.dtype).itemsize
        return (
            2 * self.n_layer * self.num_pages * self.local_kv_heads()
            * self.page_size * self.head_dim * itemsize
        )

    def local_scales_bytes(self) -> int:
        if self.kv_scales is None:
            return 0
        return self.n_layer * self.num_pages * self.local_kv_heads() * 2 * 4
