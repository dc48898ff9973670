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

import contextlib
import dataclasses
import functools
import re
from typing import Any, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.layout import Format, Layout
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ..analysis.sharding_rules import (
    ShardingRuleContext,
    _compile_table,
    _first_match,
    verify_spec_table,
)
from ..module_inject.tp_shard import tp_shard_serving_params
from jax import shard_map
from .kv_cache import PAGED_FIELDS, Cache, PageAllocator, PoolLayoutError, init_pools, stored_as, viewed

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


# one instruction of an optimised HLO module: "= <dtype>[<dims>]{<layout>} <opcode>("
_HLO_RESULT = re.compile(r"=\s+(\w+)\[([\d,]*)\](?:\{[^}]*\})?\s+([\w-]+)\(")
_RELAYOUT_OPCODES = frozenset(("copy", "slice", "transpose", "dynamic-slice"))
WEIGHT_LEAF_MIN_BYTES = 1 << 20  # a leaf the weights' census looks for: 1 MB or more a device


def _relayout_results(hlo_text: str):
    """``(opcode, dtype, dims)`` of every instruction of an optimised HLO module
    (fused computations included) that copies, slices or transposes."""
    for dtype, dims, opcode in _HLO_RESULT.findall(hlo_text):
        if opcode in _RELAYOUT_OPCODES:
            yield opcode, dtype, tuple(int(d) for d in dims.split(",") if d)


def pool_relayout_ops(hlo_text: str, layer_elems: int) -> int:
    """How many instructions of an optimised HLO module (fused computations
    included) copy, slice or transpose a whole number of pool layers:
    results of ``layer_elems`` (= P * KV * page * D, per device) elements or
    a multiple. 0 is what a program reads whose pool stays in one layout
    from its entry to its kernels and back; each one is a layer or a pool of
    HBM traffic per call that no kernel asked for."""
    n = 0
    for _, _, dims in _relayout_results(hlo_text):
        elems = int(np.prod(dims or (1,)))
        n += elems >= layer_elems and elems % layer_elems == 0
    return n


def _hlo_dtype(dtype) -> str:
    """A numpy dtype as HLO text names it: ``bf16``, ``f32``, ``s8``, ``u32``."""
    dtype = np.dtype(dtype)
    kind = {"f": "f", "i": "s", "u": "u"}.get(dtype.kind, "f")
    return ("b" if dtype.name == "bfloat16" else "") + f"{kind}{8 * dtype.itemsize}"


def weight_relayout(hlo_text: str, leaves) -> Tuple[int, int]:
    """``(instructions, bytes)`` of an optimised HLO module that COPY or
    TRANSPOSE a whole parameter leaf: a result in the type and with the dims,
    in any order, of one of ``leaves`` (``(dtype, per-device dims)``). Each is
    that leaf read and written again in every call, for an order its consumer
    wants and the placed leaf has not (``Placement.shard_params`` lays a
    row-gathered table out once, at load); 0 is a program that reads every
    such leaf where it lies. Slices are not counted: one that gives a whole
    leaf is no instruction, and what is left are pieces of a LARGER leaf that
    happen to have another leaf's dims (a fused ``wqkv``'s thirds, one expert
    of a stack beside a shared expert: found on the chip, PR 61)."""
    whole = {(_hlo_dtype(dtype), tuple(sorted(dims))) for dtype, dims in leaves}
    n = nbytes = 0
    for opcode, dtype, dims in _relayout_results(hlo_text):
        if opcode in ("copy", "transpose") and (dtype, tuple(sorted(dims))) in whole:
            n += 1
            nbytes += int(np.prod(dims)) * int(re.search(r"\d+", dtype).group()) // 8
    return n, nbytes


class WeightLayoutError(RuntimeError):
    """A compiled program would take a weight in another order than the placed
    leaf lies in: raised at set-up, never re-laid inside a run."""


def row_major_format(x) -> Optional[Format]:
    """The format that holds ``x`` on the devices it is on with its dimensions
    in their own order from major to minor, or ``None`` where it lies so
    already (every array of a CPU; a TPU's, where its rows are whole lane
    tiles). What decides is the order the device gave the array, ``x.format``."""
    fmt = getattr(x, "format", None)
    order = tuple(range(x.ndim))
    if fmt is None or fmt.layout is None or tuple(fmt.layout.major_to_minor) == order:
        return None
    return Format(Layout(major_to_minor=order), fmt.sharding)


@contextlib.contextmanager
def _compiled_in_this_process():
    """No program compiled inside is read from, or written to, jax's
    persistent compilation cache."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


def lay_row_major(x):
    """``x`` row-major on its devices: itself, or one copy, made once.

    The copy's program (``jax.device_put`` to a format is a jitted identity)
    is compiled here, never taken from the persistent cache: under libtpu
    0.0.34 the results of a DESERIALIZED executable report the device's
    default order in ``Array.format`` whatever order the program wrote them
    in, every program lowered over such an array then asks for the default
    order, and its first call fails on the buffer's size (found on the chip,
    PR 61). The programs themselves, which TAKE the leaf in its order, come
    from the cache as ever."""
    fmt = row_major_format(x)
    if fmt is None:
        return x
    with _compiled_in_this_process():
        laid = jax.device_put(x, fmt)
    if row_major_format(laid) is not None:
        raise WeightLayoutError(
            f"{x.dtype.name}{list(x.shape)} was put as {fmt.layout} and "
            f"reports {laid.format.layout}"
        )
    return laid


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

    def pool_spec(self, ndim: int, kv_axis: int = 2) -> PartitionSpec:
        """KV pools / scales / packed handoff buffers all carry the KV-head
        axis at dim 2 (``[L, P, KV, ...]``) — shard it, replicate the rest.
        (``kv_axis`` is for :class:`ProgramSet`, which alone knows the shape
        its pools are stored in.)"""
        entries = [None] * ndim
        if self.tp > 1:
            entries[kv_axis] = TP_AXIS
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

    def shard_params(self, params: PyTree, row_gathered: Sequence[str] = ()) -> PyTree:
        """QKV-permute (rank-major columns) + device_put the tree onto this
        placement. tp=1: placement pin only (no permute, no resharding on
        the default device). The leaves at the paths ``row_gathered`` (a
        family's ``row_gathered``: the tables its ``embed`` takes rows of) lie
        row-major from here on (:func:`lay_row_major`), so that no program
        re-lays a table out to gather a few rows of it; a leaf whose own order
        on the device is row-major already is the one that came in."""
        if self.tp == 1:
            if self.device is jax.devices()[0]:
                placed = params
            else:
                placed = jax.tree.map(lambda x: jax.device_put(x, self.device), params)
        else:
            permuted = tp_shard_serving_params(params, self.tp)
            specs = self.param_spec_tree(permuted)
            placed = jax.tree.map(
                lambda x, s: jax.device_put(x, NamedSharding(self.mesh, s)),
                permuted, specs,
            )
        if not row_gathered:
            return placed
        return jax.tree_util.tree_map_with_path(
            lambda kp, x: lay_row_major(x) if _path_of(kp) in row_gathered else x, placed
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
    """One placement's working set: placed params, the served cache
    (``kv_cache.Cache``, ONE value: the paged K/V pools, an int8 cache's
    scales, a window family's rings, a recurrent family's state, the rows an
    attention carries; docs/SERVING.md, "The cache", says what each field is)
    sharded over the placement, the page allocator for the paged pools, and
    the compiled programs that consume them. The cache a program gives back
    is rehomed here (:meth:`call`) because the donated buffers belong to THIS
    set, whichever placement ran the program. No allocator for the per-slot
    fields: a slot's ring or row is the slot's, and the program that takes a
    request's first rows starts it from zeros.

    The pools of pages may be STORED with their page axis split
    (``kv_cache.pool_stored_shape``), and :meth:`aot` alone knows: a program
    compiled through it works on the ``[L, P, KV, page, D]`` views, the host
    reads a page through :meth:`page_column`, and the verifiers get the
    stored per-device dims from :meth:`local_pool_dims`. Payloads of page
    columns (:meth:`packed_sds`) are ``[L, n, KV, page, D]`` always."""

    def __init__(self, placement: Placement, mcfg, num_pages: int,
                 page_size: int, cache_dtype, params: PyTree,
                 ring_slots: int = 0, ring_pages: int = 0):
        self.placement = placement
        self.num_pages = int(num_pages)
        self.page_size = int(page_size)
        from .model import pool_layers

        # the family of ONE rank (a family that caches pairs of heads makes
        # them inside a rank's heads): the pools' kv-heads are tp x its own
        fam = placement.local_model_config(mcfg).serving_family()
        # the paged pools hold the layers that read their whole context; a
        # window layer's K/V live in the rings, a recurrent sub-block's state
        # in ``rec`` and ``conv``
        self.n_layer, self.n_window_layer, n_state = pool_layers(fam)
        self.n_kv_head = int(fam.n_kv_head) * placement.tp
        self.kv_pools = int(fam.kv_pools)   # 2: a K and a V pool; 1: a latent family's one pool
        k, v, scales = init_pools(
            self.n_layer, self.num_pages, self.n_kv_head, self.page_size,
            int(fam.head_dim), dtype=cache_dtype, pools=self.kv_pools,
        )
        self.head_dim = int(k.shape[-1])  # a latent row as it is stored
        self._kv_axis = k.ndim - 3  # [..., KV, page, D], however P is stored
        # window layers: ``ring_pages`` pages statically owned by each of
        # ``ring_slots`` slots, after a scratch page; the bytes do not grow
        # with context
        slots = int(ring_slots)
        self.ring_pages = int(ring_pages) if self.n_window_layer else 0
        win_k = win_v = rec = conv = carry = None
        if self.n_window_layer:
            win_k, win_v, _ = init_pools(
                self.n_window_layer, 1 + slots * self.ring_pages,
                self.n_kv_head, self.page_size, self.head_dim, dtype=cache_dtype,
            )
        # the recurrent state a slot and sub-block, and the channels its convolution carries
        # rows of: a state-space mixer's [N, d_inner], or a linear attention's [Hv, dk, dv]
        self.lin_state = bool(n_state and getattr(fam, "lin_state", None))
        if n_state:
            shape, (K, channels) = (
                (fam.lin_state, fam.lin_conv) if self.lin_state
                else (fam.ssm_state, (fam.ssm_conv, fam.ssm_state[1]))
            )
            rec = jnp.zeros((n_state, slots, *shape), jnp.float32)
            conv = jnp.zeros((n_state, slots, K - 1, channels), k.dtype)
        width = int(getattr(fam, "carry_width", 0) or 0)
        if width:   # a row a slot and "attn" sub-block (``serving/model._qkv_carried``)
            carry = jnp.zeros((self.n_layer + self.n_window_layer, slots, width), k.dtype)
        cache = Cache(k, v, scales, win_k, win_v, rec, conv, carry)
        self.cache = Cache(*(
            x if x is None else placement.put(x, spec)
            for x, spec in zip(cache, self._cache_specs(cache))
        ))
        self._check_pool_layout()
        self.allocator = PageAllocator(self.num_pages)
        self.params = placement.shard_params(params, getattr(fam, "row_gathered", ()))
        self.param_specs = (
            placement.param_spec_tree(self.params)
            if placement.mesh is not None else None
        )

    @property
    def quantized(self) -> bool:
        return self.cache.scales is not None

    def _cache_specs(self, cache: Cache) -> Cache:
        """One ``PartitionSpec`` a field that is there: the pools of pages
        and the scales shard their kv-head axis, a slot's own state is
        replicated."""
        plc = self.placement
        return Cache(*(
            None if x is None
            else plc.pool_spec(x.ndim, x.ndim - 3) if f in PAGED_FIELDS
            else plc.pool_spec(x.ndim) if f == "scales" else plc.rep_spec()
            for f, x in zip(Cache._fields, cache)
        ))

    def _check_pool_layout(self) -> None:
        """Where the paged kernels run, the pools must have come out
        row-major on the device (``kv_cache.pool_stored_shape`` arranges it
        by shape alone; the compiler has the last word)."""
        from ..ops.pallas.decode_attention import paged_page_ok
        from ..ops.pallas.latent_attention import latent_attention_ok

        pool = self.cache.k
        ok = latent_attention_ok if self.cache.latent else paged_page_ok
        if not ok(self.page_size, self.head_dim, pool.dtype.itemsize):
            return
        got = tuple(pool.format.layout.major_to_minor)
        if got != tuple(range(pool.ndim)):
            raise PoolLayoutError(
                f"KV pool {pool.dtype.name}{list(pool.shape)} "
                f"is laid out major-to-minor {got} on this device, not "
                "row-major: every paged program would re-lay it out around "
                "its kernels. An axis longer than the head moves: choose "
                f"num_pages ({self.num_pages}) as a product of factors of "
                "at most 64, and no more than 64 layers or kv-heads a "
                "device at this head width"
            )

    def aot(self, fn, operands: Sequence, operand_specs: Sequence = (),
            result_specs: Sequence = (), *, with_params: bool = False,
            returns_cache: bool = True, donate: bool = True):
        """AOT-compile ``fn([params,] cache, *operands)`` for this set's
        placement, over this set's cache.

        ``fn`` is written for ``[L, P, KV, page, D]`` pools and, with
        ``returns_cache``, gives the cache back first (``cache, *rest``).
        The compiled program takes and returns the pools in the shape they
        are stored in: the views both ways are bitcasts (the identity for a
        pool stored 5-D). ``donate`` donates the cache, every field of it.
        ``operand_specs`` and ``result_specs`` are the specs of what follows
        the cache, used at tp > 1 only."""
        plc = self.placement
        at = int(with_params)   # the cache's place among the arguments

        @functools.wraps(fn)  # jit(decode_fn): the name traces are read by
        def program(*args):
            stored = args[at]  # per device under shard_map
            out = fn(*args[:at], viewed(stored), *args[at + 1:])
            if not returns_cache:
                return out
            return (stored_as(out[0], stored), *out[1:])

        args = ((self.params,) if with_params else ()) + (self.cache,) + tuple(operands)
        dn = (at,) if donate else ()
        if plc.mesh is None:
            exe = plc.aot(program, args, (), (), dn)
        else:
            specs = self._cache_specs(self.cache)
            exe = plc.aot(
                program, args,
                ((self.param_specs,) if with_params else ())
                + (specs,) + tuple(operand_specs),
                ((specs,) if returns_cache else ()) + tuple(result_specs),
                dn,
            )
        # the program must take the cache as it lies and give it back so:
        # a layout that differs would be refused at the first call (or, for
        # a result, at the one after), inside a run
        took = exe.input_formats[0][at]
        gave = exe.output_formats[0] if returns_cache else ()
        for what, fmts in (("takes", took), ("returns", gave)):
            for pool, fmt in zip(jax.tree.leaves(self.cache), jax.tree.leaves(fmts)):
                if fmt.layout != pool.format.layout:
                    raise PoolLayoutError(
                        f"{getattr(fn, '__name__', fn)} {what} a "
                        f"{pool.dtype.name}{list(pool.shape)} pool as "
                        f"{fmt.layout}, and the live pool is "
                        f"{pool.format.layout}"
                    )
        if with_params:
            # and every weight as the placed leaf lies (a row-gathered table
            # row-major: ``Placement.shard_params``)
            for (kp, x), fmt in zip(
                jax.tree_util.tree_leaves_with_path(self.params),
                jax.tree.leaves(exe.input_formats[0][0]),
            ):
                lies = getattr(getattr(x, "format", None), "layout", None)
                # (a leaf the program does not read has no layout there)
                if lies is not None and fmt.layout is not None and fmt.layout != lies:
                    raise WeightLayoutError(
                        f"{getattr(fn, '__name__', fn)} takes {_path_of(kp)} "
                        f"{x.dtype.name}{list(x.shape)} as {fmt.layout}, and "
                        f"the placed leaf is {lies}"
                    )
        return exe

    def call(self, exe, *host):
        """One call of a program compiled ``with_params`` over this set's
        weights and cache: the cache it gives back is the set's from here on
        (the one it took was donated). → the rest of its results, a single
        one unwrapped."""
        self.cache, *rest = exe(self.params, self.cache, *host)
        return rest[0] if len(rest) == 1 else tuple(rest)

    def page_column(self, pid: int) -> tuple:
        """Page ``pid`` of every layer, as device arrays ``(k, v, scales)``:
        ``[L, KV, page, D]`` twice and ``[L, KV, 2]`` or ``None`` (the host
        tier's demotion read; dispatched now, fetched by whoever waits)."""
        k, v, scales = self.cache[:3]
        at = (slice(None),) + tuple(int(i) for i in np.unravel_index(
            int(pid), k.shape[1:self._kv_axis]
        ))
        return (
            k[at], v[at] if v is not None else None,
            scales[:, pid] if scales is not None else None,
        )

    def program_census(self, name: str, exe) -> Tuple[int, int, int, int]:
        """(``pool_relayout_ops``, HLO temp bytes, then :func:`weight_relayout`'s
        instructions and bytes) of a compiled program over this set's pools
        and weights; per device at tp>1. A program that hands the pools
        to a Pallas kernel has to read 0: there the kernels take the pool
        where it lies, and a copy or a slice of a layer means the pool was
        re-laid out on the way (:class:`PoolLayoutError`). The ``jnp``
        fallbacks slice their layer out and are only counted, and so are the
        weights: a whole leaf of ``WEIGHT_LEAF_MIN_BYTES`` or more that a
        program copies for another order is traffic of every call, and no
        error."""
        layer = (
            self.num_pages * self.local_kv_heads() * self.page_size
            * self.head_dim
        )
        text = exe.as_text()
        relayout = pool_relayout_ops(text, layer)
        if relayout and "tpu_custom_call" in text:
            raise PoolLayoutError(
                f"{name}: {relayout} instruction(s) copy, slice or transpose "
                f"a whole layer of the {list(self.cache.k.shape)} KV pool or "
                "more around its kernels (placement.pool_relayout_ops)"
            )
        mem = exe.memory_analysis()
        w_ops, w_bytes = weight_relayout(text, self._weight_leaves())
        return relayout, int(getattr(mem, "temp_size_in_bytes", 0) or 0), w_ops, w_bytes

    def _weight_leaves(self) -> set:
        """``(dtype, per-device dims)`` of every leaf of the placed weights
        that holds ``WEIGHT_LEAF_MIN_BYTES`` or more a device."""
        leaves = set()
        for x in jax.tree.leaves(self.params):
            shard_shape = getattr(getattr(x, "sharding", None), "shard_shape", None)
            shape = tuple(shard_shape(x.shape) if shard_shape else x.shape)
            if int(np.prod(shape)) * x.dtype.itemsize >= WEIGHT_LEAF_MIN_BYTES:
                leaves.add((x.dtype, shape))
        return leaves

    # -- geometry for Engines A/E (per-DEVICE shapes at tp>1) ------------

    def local_kv_heads(self) -> int:
        return self.n_kv_head // self.placement.tp

    def _local_dims(self, shape, kv_axis: int = 2) -> str:
        shape = list(shape)
        shape[kv_axis] //= self.placement.tp
        return ",".join(str(int(d)) for d in shape)

    def local_pool_dims(self) -> str:
        """Per-device dims of a pool as the compiled programs take it: the
        STORED shape (their entry parameters, the donation aliases)."""
        return self._local_dims(self.cache.k.shape, self._kv_axis)

    def kv_pool_dims(self) -> tuple:
        """Every per-device dims string a pool-sized buffer of a compiled
        program may carry: the stored shape and, where it differs, the
        ``[L, P, KV, page, D]`` view the program body works on."""
        view = self._local_dims(
            (self.n_layer, self.num_pages, self.n_kv_head, self.page_size,
             self.head_dim)
        )
        stored = self.local_pool_dims()
        return (stored,) if stored == view else (stored, view)

    def local_scales_dims(self) -> str:
        return f"{self.n_layer},{self.num_pages},{self.local_kv_heads()},2"

    def packed_sds(self, n_pages: int) -> tuple:
        """Global shapes of a page-column payload over ``n_pages`` pages:
        ``[L, n, KV, page, D]`` for K and V (whatever shape the pools are
        stored in), then ``[L, n, KV, 2]`` for an int8 cache's scales."""
        kv = jax.ShapeDtypeStruct(
            (self.n_layer, int(n_pages), self.n_kv_head, self.page_size,
             self.head_dim), self.cache.k.dtype,
        )
        if not self.quantized:
            return (kv, kv)
        return (kv, kv, jax.ShapeDtypeStruct(
            (self.n_layer, int(n_pages), self.n_kv_head, 2), jnp.float32
        ))

    def packed_specs(self) -> tuple:
        """One ``PartitionSpec`` per :meth:`packed_sds` payload."""
        return tuple(
            self.placement.pool_spec(x.ndim) for x in self.packed_sds(1)
        )

    def packed_dims(self, n_pages: int) -> str:
        """Per-device shape of the gather/scatter handoff payload over
        ``n_pages`` pages."""
        return self._local_dims(self.packed_sds(n_pages)[0].shape)

    def packed_scales_dims(self, n_pages: int) -> str:
        return f"{self.n_layer},{int(n_pages)},{self.local_kv_heads()},2"

    def cache_bytes(self) -> dict:
        """The cache's bytes by class, over all devices (a device holds ``1 /
        tp`` of ``pages`` and ``scales``): ``pages`` (K and V, or a latent
        family's one pool, rows as they are stored), ``scales``, ``window``
        (the rings), ``state`` (the recurrent state and its convolution's
        rows, whatever the contexts' lengths), ``lin_state`` (of those, a
        linear attention's matrix a value head; 0 for a state-space scan's)
        and ``carry``."""
        c = self.cache

        def of(*xs):
            return sum(int(x.nbytes) for x in xs if x is not None)

        return {
            "pages": of(c.k, c.v), "scales": of(c.scales), "window": of(c.win_k, c.win_v),
            "state": of(c.rec, c.conv), "lin_state": of(c.rec) if self.lin_state else 0,
            "carry": of(c.carry),
        }
