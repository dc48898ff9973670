"""ZeRO stages 0-3 as sharding policy over the ``dp`` mesh axis.

TPU-native redesign of the reference ZeRO implementations:

- ``runtime/zero/stage_1_and_2.py`` (DeepSpeedZeroOptimizer, 2388 LoC) and
  ``runtime/zero/stage3.py`` (DeepSpeedZeroOptimizer_Stage3, 2557 LoC) manage
  flattening, round-robin partitioning, grad-hook bucketing, and hand-rolled
  allgather/reduce-scatter overlap on CUDA streams.
- ``runtime/zero/partition_parameters.py`` (zero.Init, 1643 LoC) monkey-patches
  module construction to shard params at birth.

On TPU none of that machinery is needed: ZeRO is a choice of
``PartitionSpec`` per tensor AND of where the activations live, and XLA
inserts and schedules the collectives.

    stage 0: params, grads, optimizer state replicated over dp
    stage 1: optimizer state sharded over dp
    stage 2: + gradient (accumulation buffer) sharded over dp  (reduce-scatter)
    stage 3: + parameters sharded over dp                      (allgather per use)

What is STATED: the specs above, and that the block's activations are sharded
over the batch (:func:`on_batch_axis`, which the model places on the residual
stream a block hands on). The second is not implied by the first: stage 3
shards a weight's largest free dimension, a feature dimension for every
projection, and a partitioner told nothing else runs the layer column- and
row-parallel over ``dp`` as a Megatron layer runs over ``tp`` (the global
batch's activations all-gathered, all-reduced and re-laid with all-to-alls,
27% of the four-chip step exposed before ISSUE 40). What is LEFT to XLA: the
schedule (which weight gathers run async under the previous product, which
gradients' reductions are combined), and the reduction's precision follows the
gradients' dtype: four chips' partial products are summed in the compute dtype
(bf16), then cast to the accumulation dtype.

Tensor parallelism composes first: a param's logical axes map to ``tp`` (and
friends) via axis rules; ZeRO then shards the largest still-free dimension
over ``dp``. This is the `FSDP + TP` layout used by production JAX LLM stacks.

``zero.Init`` (params born sharded, never materialized densely) is
``init_partitioned``: jit the initializer with sharded out_shardings.
``GatheredParameters`` is ``gather_full``: constraint back to replicated.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ...utils.logging import logger

PyTree = Any

# Default logical-axis → mesh-axis rules (t5x-style). Models annotate params
# with logical names; these rules decide which mesh axis implements each.
DEFAULT_LOGICAL_RULES: Tuple[Tuple[str, Optional[str]], ...] = (
    ("batch", "dp"),
    ("vocab", "tp"),
    ("embed", None),
    ("mlp", "tp"),
    ("heads", "tp"),
    ("kv", None),
    ("qkv", "tp"),
    ("expert", "ep"),
    ("expert_mlp", "tp"),
    ("seq", "sp"),
    # stacked layer dim shards over pp = pipeline stage partition
    # (PipelineModule._partition_layers analog); degrades to replicated
    # when the mesh has no pp axis
    ("layers", "pp"),
    ("stack", None),
)


def logical_to_spec(
    logical_axes: Sequence[Optional[str]],
    rules: Sequence[Tuple[str, Optional[str]]] = DEFAULT_LOGICAL_RULES,
    mesh: Optional[Mesh] = None,
) -> PartitionSpec:
    """Map a tuple of logical axis names to a PartitionSpec via rules.

    Mesh axes not present in ``mesh`` (or of size 1) degrade to replicated,
    so the same annotated model runs on any mesh shape.
    """
    rule_map = dict(rules)
    out = []
    used = set()
    for name in logical_axes:
        mesh_axis = rule_map.get(name) if name is not None else None
        if mesh_axis is not None and mesh is not None:
            if mesh.shape.get(mesh_axis, 1) <= 1:
                mesh_axis = None
        if mesh_axis in used:  # a mesh axis may shard only one dim
            mesh_axis = None
        if mesh_axis is not None:
            used.add(mesh_axis)
        out.append(mesh_axis)
    while out and out[-1] is None:
        out.pop()
    return PartitionSpec(*out)


def on_batch_axis(x, axis: str = "dp"):
    """State that activation ``x`` lives sharded over its batch: dimension 0
    over ``axis``, every other dimension left to the partitioner
    (``PartitionSpec.UNCONSTRAINED``: a ``tp`` or ``sp`` placement stays its
    own choice).

    ZeRO's parameter specs alone do not make a ZeRO program. Stage 3 shards a
    weight's largest free dimension over ``dp``, which for a projection is a
    FEATURE dimension, and with nothing said of the activations the
    partitioner takes the weights' sharding for theirs: it gathers the whole
    batch onto every chip and runs the layer column- and row-parallel over
    ``dp`` (activation all-gathers and all-reduces, an all-to-all back to the
    batch axis), every collective behind a data dependence. With the block's
    activations pinned to the batch axis the collectives are the WEIGHTS': an
    all-gather at each use, a reduce-scatter of each gradient (a cotangent
    takes its primal's constraint), and none carries an activation.

    The mesh is the ambient one (``jax.set_mesh``: ``DeepSpeedEngine`` sets it
    around its steps). With no ambient mesh, no ``axis`` of more than one
    device in it, ``axis`` manual (inside a ``shard_map`` over it) or a batch
    it does not divide, ``x`` is returned as it came and NOTHING is traced:
    one chip's programs and the serving programs are unchanged."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or axis in mesh.manual_axes:
        return x
    n = mesh.shape.get(axis, 1)
    if n <= 1 or x.shape[0] % n:
        return x
    free = (PartitionSpec.UNCONSTRAINED,) * (x.ndim - 1)
    return jax.lax.with_sharding_constraint(x, PartitionSpec(axis, *free))


def add_zero_axis(
    spec: PartitionSpec,
    shape: Tuple[int, ...],
    mesh: Mesh,
    zero_axis: str = "dp",
    min_size_to_shard: int = 2**14,
) -> PartitionSpec:
    """Shard the largest still-free dim over ``zero_axis`` (ZeRO-3/FSDP layout).

    Dims already sharded keep their assignment; the chosen dim must be
    divisible by the axis size *after* existing sharding. Small tensors
    (< min_size_to_shard elements) stay replicated — the analog of the
    reference's ``stage3_param_persistence_threshold`` (small params are kept
    gathered because allgather latency would dominate).
    """
    n = mesh.shape.get(zero_axis, 1)
    if n <= 1:
        return spec
    if int(np.prod(shape)) < min_size_to_shard:
        return spec
    entries = list(spec) + [None] * (len(shape) - len(spec))
    flat_used = {a for e in entries if e is not None for a in (e if isinstance(e, tuple) else (e,))}
    if zero_axis in flat_used:
        return spec
    # candidate dims, largest effective size first
    best_dim, best_size = -1, 0
    for d, dim_size in enumerate(shape):
        existing = entries[d]
        existing_axes = existing if isinstance(existing, tuple) else ((existing,) if existing else ())
        denom = int(np.prod([mesh.shape[a] for a in existing_axes])) if existing_axes else 1
        eff = dim_size // denom
        if dim_size % denom == 0 and eff % n == 0 and eff > best_size:
            best_dim, best_size = d, eff
    if best_dim < 0:
        return spec  # nothing divisible — stays replicated (correct, just unsharded)
    existing = entries[best_dim]
    if existing is None:
        entries[best_dim] = zero_axis
    elif isinstance(existing, tuple):
        entries[best_dim] = existing + (zero_axis,)
    else:
        entries[best_dim] = (existing, zero_axis)
    while entries and entries[-1] is None:
        entries.pop()
    return PartitionSpec(*entries)


class ZeroShardingPolicy:
    """Produces param/grad/opt-state shardings for a given ZeRO stage."""

    def __init__(
        self,
        mesh: Mesh,
        stage: int = 0,
        rules: Sequence[Tuple[str, Optional[str]]] = DEFAULT_LOGICAL_RULES,
        min_size_to_shard: int = 2**14,
        grad_min_size_to_shard: int = 2**7,
        zero_axis: str = "dp",
    ):
        assert 0 <= stage <= 3
        self.mesh = mesh
        self.stage = stage
        self.rules = tuple(rules)
        # params honor the persistence threshold (small params stay gathered —
        # stage3_param_persistence_threshold); grads/opt state shard at any
        # meaningful size, like the reference partitions ALL optimizer state
        self.min_size_to_shard = min_size_to_shard
        self.grad_min_size_to_shard = grad_min_size_to_shard
        self.zero_axis = zero_axis

    # -- spec builders ------------------------------------------------------
    def tp_spec(self, logical_axes: Sequence[Optional[str]]) -> PartitionSpec:
        return logical_to_spec(logical_axes, self.rules, self.mesh)

    def param_spec(self, logical_axes, shape) -> PartitionSpec:
        spec = self.tp_spec(logical_axes)
        if self.stage >= 3:
            spec = add_zero_axis(spec, shape, self.mesh, self.zero_axis, self.min_size_to_shard)
        return spec

    def grad_spec(self, logical_axes, shape) -> PartitionSpec:
        spec = self.tp_spec(logical_axes)
        if self.stage >= 2:
            spec = add_zero_axis(spec, shape, self.mesh, self.zero_axis, self.grad_min_size_to_shard)
        return spec

    def opt_spec(self, logical_axes, shape) -> PartitionSpec:
        spec = self.tp_spec(logical_axes)
        if self.stage >= 1:
            spec = add_zero_axis(spec, shape, self.mesh, self.zero_axis, self.grad_min_size_to_shard)
        return spec

    # -- compressed / bucketed grad-reduce wiring ---------------------------
    # (comm_compression section → comm/compressed.py; the engine consumes
    # bucket_spec / residual_shardings / supports_compressed_grads, so the
    # ZeRO stage stays the single source of truth for HOW the gradient
    # dp-reduction is implemented)
    def grad_reduce_op(self) -> str:
        """The collective implementing the grad reduction at this stage:
        stage >= 2 shards the accumulation buffer over ``zero_axis`` so XLA
        emits reduce-scatter (stage3.py:1145 analog); below that the grads
        stay replicated and the reduction is an all-reduce. ``bucket_spec``
        derives the bucketed path's sharding from this decision."""
        return "reduce_scatter" if self.stage >= 2 else "all_reduce"

    def bucket_spec(self) -> PartitionSpec:
        """Sharding of a flat gradient bucket on the bucketed reduce path:
        dp-sharded (flat reduce-scatter) when :meth:`grad_reduce_op` says
        this stage reduce-scatters, replicated (all-reduce per bucket)
        otherwise."""
        if (
            self.grad_reduce_op() == "reduce_scatter"
            and self.mesh.shape.get(self.zero_axis, 1) > 1
        ):
            return PartitionSpec(self.zero_axis)
        return PartitionSpec()

    def supports_compressed_grads(self) -> bool:
        """Compressed grad collectives run under ``shard_map`` with params
        replicated over ``zero_axis`` — stage 3's dp-sharded params would
        need an (uncompressed) allgather inside the mapped region, defeating
        the wire savings. Stage <= 2 with a nontrivial axis qualifies."""
        return self.stage <= 2 and self.mesh.shape.get(self.zero_axis, 1) > 1

    def gathers_params_in_step(self) -> bool:
        """Whether the train step all-gathers its parameters at each use:
        they are sharded over ``zero_axis`` (stage 3) and the axis has more
        than one device. What :func:`on_batch_axis` asks of the ambient mesh,
        asked of the policy's own; the engine's step then asks the compiler
        for a layer's weights one layer ahead
        (``DeepSpeedEngine._step_compiler_options``)."""
        return self.stage >= 3 and self.mesh.shape.get(self.zero_axis, 1) > 1

    def supports_compressed_param_gather(self) -> bool:
        """The OTHER side of the compression story (ISSUE 12): at stage 3
        the dominant wire transfer is the param all-gather, and an explicit
        materialization (:func:`gather_full`) can run it block-quantized.
        Wherever the step gathers its parameters."""
        return self.gathers_params_in_step()

    def param_gather_fn(self, comp_cfg=None) -> Callable[[PyTree], PyTree]:
        """→ callable(tree) materializing fully-replicated params: the
        compressed all-gather (``comm/compressed.compressed_all_gather``)
        when ``comm_compression`` covers this policy — enabled, stage 3,
        ``zero_axis`` listed in ``axes`` — else plain :func:`gather_full`.
        The gate lives HERE so the ZeRO stage stays the single source of
        truth for how params move, exactly like ``grad_reduce_op``."""
        if (
            comp_cfg is not None
            and bool(getattr(comp_cfg, "enabled", False))
            and self.zero_axis in tuple(getattr(comp_cfg, "axes", ()) or ())
            and self.supports_compressed_param_gather()
        ):
            method = str(getattr(comp_cfg, "method", "int8"))
            block = int(getattr(comp_cfg, "block_size", 256))
            return lambda tree: gather_full_compressed(
                tree, self.mesh, zero_axis=self.zero_axis,
                method=method, block=block,
            )
        return lambda tree: gather_full(tree, self.mesh)

    def residual_shardings(self, abstract_params: PyTree) -> PyTree:
        """Shardings for the error-feedback residuals
        (``TrainState.comm_error``): one ``[world, ...]``-leading buffer per
        param leaf, sharded over ``zero_axis`` so each rank's shard IS its
        rank-local residual (claiming divergent buffers replicated is
        undefined behaviour under reshard/donation)."""
        sh = NamedSharding(self.mesh, PartitionSpec(self.zero_axis))
        return jax.tree.map(lambda _: sh, abstract_params)

    # -- pytree-level -------------------------------------------------------
    def param_shardings(self, abstract_params: PyTree, logical_axes: Optional[PyTree] = None) -> PyTree:
        return self._tree_shardings(abstract_params, logical_axes, self.param_spec)

    def grad_shardings(self, abstract_params: PyTree, logical_axes: Optional[PyTree] = None) -> PyTree:
        return self._tree_shardings(abstract_params, logical_axes, self.grad_spec)

    def opt_shardings_for_params(self, abstract_params: PyTree, logical_axes: Optional[PyTree] = None) -> PyTree:
        return self._tree_shardings(abstract_params, logical_axes, self.opt_spec)

    def opt_state_shardings(self, abstract_opt_state: PyTree, abstract_params: PyTree, logical_axes: Optional[PyTree] = None) -> PyTree:
        """Shard optimizer state: leaves shaped like a param follow that
        param's opt_spec; scalars (loss-scale counters, step) replicate.

        The shape-match heuristic covers optax's mu/nu/trust-ratio trees
        (which mirror the param tree structure exactly).
        """
        param_spec_tree = self.opt_shardings_for_params(abstract_params, logical_axes)
        flat_params, _ = jax.tree.flatten(abstract_params)
        flat_specs, _ = jax.tree.flatten(param_spec_tree, is_leaf=_is_sharding)
        shape_to_spec: Dict[Tuple[Tuple[int, ...], str], Any] = {}
        for p, s in zip(flat_params, flat_specs):
            shape_to_spec.setdefault(tuple(p.shape), s)

        def assign(leaf):
            spec = shape_to_spec.get(tuple(getattr(leaf, "shape", ())))
            if spec is not None and len(getattr(leaf, "shape", ())) > 0:
                return spec
            return NamedSharding(self.mesh, PartitionSpec())

        return jax.tree.map(assign, abstract_opt_state)

    def _tree_shardings(self, abstract_params, logical_axes, spec_fn) -> PyTree:
        if logical_axes is None:
            logical_axes = jax.tree.map(lambda p: tuple([None] * len(p.shape)), abstract_params)
        else:
            logical_axes = _align_axes(abstract_params, logical_axes)

        def make(p, axes):
            return NamedSharding(self.mesh, spec_fn(axes, tuple(p.shape)))

        return jax.tree.map(make, abstract_params, logical_axes, is_leaf=lambda x: hasattr(x, "shape"))


def _is_axes_leaf(x):
    """An axes annotation: a tuple/list of axis names (str) / None."""
    return isinstance(x, (tuple, list)) and all(
        e is None or isinstance(e, str) for e in x
    )


def _align_axes(abstract_params, logical_axes):
    """Project a logical-axes tree onto the params structure by path.

    Model families declare axes for their FULL surface (e.g. the decoder
    zoo's optional biases / wpe); a converted checkpoint may carry only a
    subset, and bias-less archs must not fail the pytree zip. Missing paths
    default to unsharded (all-None axes)."""
    by_path = {}
    for path, axes in jax.tree_util.tree_flatten_with_path(
        logical_axes, is_leaf=_is_axes_leaf
    )[0]:
        by_path[jax.tree_util.keystr(path)] = tuple(axes)

    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract_params)
    aligned = []
    matched = 0
    for path, leaf in flat:
        axes = by_path.get(jax.tree_util.keystr(path))
        if axes is not None:
            matched += 1
        aligned.append(axes if axes is not None else tuple([None] * len(leaf.shape)))
    if flat and by_path and matched == 0:
        # a whole-tree miss is a structure bug (e.g. an extra nesting level),
        # not a legitimate subset — silently replicating everything would
        # drop every TP/ZeRO annotation
        raise ValueError(
            "logical_axes shares no paths with the param tree — the two "
            f"structures are misaligned (params e.g. {jax.tree_util.keystr(flat[0][0])!r}, "
            f"axes e.g. {next(iter(by_path))!r})"
        )
    if flat and matched < len(flat) / 2:
        from ...utils.logging import warning_once

        warning_once(
            f"logical_axes covers only {matched}/{len(flat)} param leaves; "
            "unmatched leaves are left unsharded (replicated)"
        )
    return jax.tree_util.tree_unflatten(treedef, aligned)


def _is_sharding(x):
    return isinstance(x, (NamedSharding, PartitionSpec))


# ---------------------------------------------------------------------------
# zero.Init / GatheredParameters analogs
# ---------------------------------------------------------------------------

def init_partitioned(init_fn: Callable[..., PyTree], shardings: PyTree, *args) -> PyTree:
    """Initialize params *born sharded* — the ``zero.Init`` analog
    (reference partition_parameters.py:537). The initializer is jit-compiled
    with sharded out_shardings, so each device only ever materializes its own
    shard; no device ever holds the full model.
    """
    return jax.jit(init_fn, out_shardings=shardings)(*args)


def gather_full(tree: PyTree, mesh: Mesh) -> PyTree:
    """Materialize fully-replicated copies — the ``GatheredParameters`` analog
    (reference partition_parameters.py:1512). Use sparingly (it defeats ZeRO-3
    memory savings, exactly like the reference warns)."""
    replicated = NamedSharding(mesh, PartitionSpec())
    return jax.tree.map(lambda x: jax.device_put(x, replicated), tree)


def _leaf_zero_dim(leaf, zero_axis: str) -> Optional[int]:
    """The dim a leaf is sharded over ``zero_axis`` on — only when the dim's
    spec entry is EXACTLY the zero axis (a composite ``(tp, dp)`` entry
    would interleave shards from two axes in the flat gather order; those
    leaves take the plain device_put path instead). None = not dp-sharded."""
    sharding = getattr(leaf, "sharding", None)
    spec = getattr(sharding, "spec", None)
    if spec is None:
        return None
    for d, entry in enumerate(spec):
        if entry == zero_axis:
            return d
    return None


def gather_full_compressed(
    tree: PyTree,
    mesh: Mesh,
    zero_axis: str = "dp",
    method: str = "int8",
    block: int = 256,
) -> PyTree:
    """ZeRO-3 param all-gather on the compressed wire (ISSUE 12): the
    low-precision :func:`gather_full`. Each leaf sharded over ``zero_axis``
    all-gathers as block-scaled int8/fp8 + per-block fp32 scales
    (``comm/compressed.compressed_all_gather``) — ~3.9x less ICI/DCN bytes
    at block 256 than the fp32 gather — and lands bit-identical on every
    rank (all ranks dequantize the same codes). Leaves not sharded over the
    axis (persistence-threshold params, scalars, composite-sharded dims)
    replicate as-is.

    LOSSY, bounded by the block quantizer's round-trip error: this is the
    export / eval-time materialization path (checkpoint conversion, serving
    weight hand-off), not the train step — XLA's implicit per-use stage-3
    gathers are untouched. Every gather records (logical, wire) bytes in
    the ``comm_wire_bytes`` trace ledger under ``all_gather``/``dp``
    (logical is fp32-normalized per the module convention — see
    :func:`~deepspeed_tpu.comm.compressed.compressed_all_gather`)."""
    world = int(mesh.shape.get(zero_axis, 1))
    replicated = NamedSharding(mesh, PartitionSpec())

    def gather_leaf(leaf):
        d = _leaf_zero_dim(leaf, zero_axis)
        if world <= 1 or d is None:
            return jax.device_put(leaf, replicated)
        spec = leaf.sharding.spec
        mapped = _compressed_gather_program(
            mesh, zero_axis, world, method, block,
            tuple(spec), d, tuple(leaf.shape), str(leaf.dtype),
        )
        return mapped(leaf)

    return jax.tree.map(gather_leaf, tree)


@functools.lru_cache(maxsize=256)
def _compressed_gather_program(mesh, zero_axis, world, method, block,
                               spec, d, shape, dtype):
    """One compiled shard_map program per (mesh, spec, shape, dtype) leaf
    signature — cached so a param tree with hundreds of leaves compiles
    only its distinct shapes, once, instead of re-tracing every leaf on
    every :func:`gather_full_compressed` call (jit caches key on function
    identity, and a per-leaf closure defeats them)."""
    import jax.numpy as jnp

    from ...comm import compressed as cco
    from jax import shard_map

    in_spec = PartitionSpec(*spec)
    out_entries = list(spec) + [None] * (len(shape) - len(spec))
    out_entries[d] = None
    out_spec = PartitionSpec(*out_entries)

    def f(local):
        flat = local.reshape(-1)
        full = cco.compressed_all_gather(flat, zero_axis, world, method, block)
        parts = full.reshape((world,) + local.shape)
        return jnp.concatenate(
            [parts[i] for i in range(world)], axis=d
        ).astype(dtype)

    return jax.jit(shard_map(
        f, mesh=mesh, in_specs=(in_spec,), out_specs=out_spec,
        check_vma=False,
    ))
