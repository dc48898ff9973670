"""ZeRO-Infinity parameter tier: block-streamed training with params on host/NVMe.

Analog of the reference's NVMe parameter path — ``AsyncPartitionedParameterSwapper``
engaged from stage 3 (``/root/reference/deepspeed/runtime/zero/stage3.py:465``,
``swap_tensor/partitioned_param_swapper.py:35``) plus the param-coordinator
fetch/release cycle (``partitioned_param_coordinator.py:237,356``). The torch
design hooks every submodule to allgather params just-in-time and re-partition
after use. The TPU-native formulation exploits the model's block structure
directly:

- **persistent part** (embeddings, final norm, tied head — the analog of
  ``stage3_param_persistence_threshold`` keeping small params resident):
  bf16 copy stays in HBM for the whole step.
- **streamed blocks**: each transformer block's bf16 params live on host DRAM
  (``offload_param.device="cpu"``) or NVMe files via the aio engine
  (``"nvme"``). The forward sweep runs block-at-a-time with a two-deep
  prefetch window (``device_put`` of block i+1 is dispatched before block i's
  compute, so the H2D copy overlaps the matmuls); the backward sweep re-fetches
  blocks in reverse and streams each block's grads back to host as soon as the
  next block's VJP is dispatched.
- **optimizer tier**: fp32 master + Adam moments per block live in DRAM or in
  NVMe ``[master|m|v]`` records through ``PipelinedOptimizerSwapper`` (step(i)
  overlaps prefetch(i+1)/writeback(i-1) — reference
  ``pipelined_optimizer_swapper.py``); the update runs on host cores through
  the SIMD C++ Adam (``csrc/adam``).

HBM high-water = persistent part + ~2 blocks (current + prefetch) + one
block's grads + the L boundary activations — the property that lets a 13-20B
model train on one 16 GB chip (see ``memory_math`` and
tests/unit/test_infinity.py).
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ...ops.cpu_adam import DeepSpeedCPUAdam
from ...utils.logging import log_dist

PyTree = Any

try:  # numpy has no native bfloat16; jax ships ml_dtypes
    import ml_dtypes

    _BF16 = np.dtype(ml_dtypes.bfloat16)
except Exception:  # pragma: no cover
    _BF16 = np.dtype(np.float16)


@dataclass
class BlockAPI:
    """Block-structured view of a model for parameter streaming.

    All block params must have identical pytree structure/shapes so one
    compiled ``block_fwd``/VJP serves every layer (scan-over-layers unrolled
    into a host loop).
    """

    num_blocks: int
    init_persistent: Callable[[Any], PyTree]  # rng -> persistent params
    init_block: Callable[[Any, int], PyTree]  # (rng, layer_idx) -> block params
    embed_fwd: Callable  # (persistent, batch, rng, train) -> h
    block_fwd: Callable  # (block_params, h, rng, train) -> h
    head_loss: Callable  # (persistent, h, batch) -> scalar mean loss
    # full-params pytree -> (persistent, [block_0 .. block_{L-1}]); lets the
    # engine adopt externally initialized weights (and the parity tests start
    # both engines from identical values)
    split_params: Optional[Callable[[PyTree], Tuple[PyTree, List[PyTree]]]] = None
    # numpy-native init (np.random.Generator -> np pytrees): at 13B scale the
    # device-init path would materialize every block on chip and pull ~50 GB
    # device-to-host before training starts; host init builds the
    # fp32 masters directly in DRAM (reference analog: offload_config
    # ``fast_init`` intent). Structure must match init_persistent/init_block.
    host_init_persistent: Optional[Callable[[Any], PyTree]] = None
    host_init_block: Optional[Callable[[Any, int], PyTree]] = None


def memory_math(
    n_layer: int,
    n_embd: int,
    vocab_size: int,
    seq: int,
    micro_batch: int,
    n_positions: Optional[int] = None,
    mlp_ratio: int = 4,
    param_from_master: bool = False,
) -> Dict[str, float]:
    """HBM footprint estimate (bytes) for the streamed step; the demo that a
    13-20B model fits one 16 GB chip (BASELINE.md ZeRO-Infinity row)."""
    P = n_positions or seq
    block_params = 12 * n_embd * n_embd  # attn 4E^2 + mlp 2*ratio*E^2 (=8E^2 at 4x)
    persistent_params = vocab_size * n_embd + P * n_embd + 2 * n_embd
    total_params = n_layer * block_params + persistent_params
    bf16 = 2
    act = micro_batch * seq * n_embd * bf16
    hbm = {
        "persistent_bf16": persistent_params * bf16,
        "blocks_resident_bf16": 2 * block_params * bf16,  # current + prefetch
        "block_grads_fp32": 2 * block_params * 4,  # vjp out for 2 in-flight blocks
        "boundary_acts_bf16": (n_layer + 1) * act,
        # vjp workspace: recomputed internals of ONE block (qkv, attn probs
        # tiled by flash, mlp hidden) ~ 8 activations deep
        "vjp_workspace": 8 * act + micro_batch * seq * mlp_ratio * n_embd * bf16,
        "logits_fp32": micro_batch * seq * vocab_size * 4,
    }
    hbm["total_hbm"] = float(sum(hbm.values()))
    hbm["total_params"] = float(total_params)
    # bf16 copy + fp32 master/m/v; with param_from_master the bf16 compute
    # copy is cast from the master at load time and never stored
    hbm["dram_or_nvme_bytes"] = float(
        total_params * ((0 if param_from_master else 2) + 12)
    )
    return hbm


class InfinityEngine:
    """Block-streaming train step over any device mesh.

    Single chip: blocks upload whole. Multi-device mesh (dp>1): each block
    streams as ONE contiguous flat buffer *sharded over every mesh axis* —
    each chip uploads only its 1/N slice of the block (H2D bandwidth divides
    by N, the analog of the reference's per-rank NVMe partitions,
    ``swap_tensor/partitioned_param_swapper.py:35``), XLA allgathers the
    flat buffer in-graph where the block math needs it, and the block's
    grads are reduce-scattered back to the same layout so each chip D2H
    streams only its slice. The batch rides the ``dp`` axis (sharded by
    ``engine.shard_batch``), making the grads global means; the host tier
    (one controller process) then steps masters exactly as at dp=1 — the
    single-controller formulation of the reference's per-rank swapper +
    grad-reduce design (``stage3.py:465``).
    """

    def __init__(
        self,
        api: BlockAPI,
        lr_schedule,
        betas=(0.9, 0.999),
        eps: float = 1e-8,
        weight_decay: float = 0.0,
        device: str = "cpu",  # offload_param.device: cpu | nvme
        opt_device: str = "cpu",  # offload_optimizer.device: cpu | nvme | hybrid
        nvme_path: str = "/tmp/ds_tpu_nvme",
        gradient_clipping: float = 0.0,
        compute_dtype=jnp.bfloat16,
        seed: int = 0,
        initial_params: Optional[PyTree] = None,
        trace_validator=None,
        aio_config=None,
        mesh=None,
        # bf16 compute copies are cast from the fp32 masters at load time
        # instead of being stored (saves 2 B/param of host/NVMe capacity —
        # the knob that lets OPT-13B fit a 125 GB-DRAM + 80 GB-disk host)
        param_from_master: bool = False,
        # numpy-native init in DRAM (BlockAPI.host_init_*); avoids the
        # ~4 B/param device-init D2H at multi-B scale
        host_init: bool = False,
        # "hybrid" opt tier: first K block records stay in DRAM, the rest
        # swap via the pipelined NVMe swapper. K from this DRAM budget
        # (bytes; 0 = auto from /proc/meminfo minus a working-set reserve).
        opt_dram_budget: float = 0.0,
        # eager=None auto-engages the per-block optimizer step inside the
        # backward sweep (bounds DRAM grad high-water to ~2 blocks) whenever
        # it is exact: gas==1, no loss scale, no global clipping
        eager: Optional[bool] = None,
    ):
        assert device in ("cpu", "nvme"), device
        assert opt_device in ("cpu", "nvme", "hybrid"), opt_device
        self.api = api
        self.mesh = mesh
        # debug mode: block fetch order must replay the recorded trace
        # (runtime/debug.BlockTraceValidator; reference coordinator.py:300-307);
        # only train-step fetches are traced (eval's fwd-only order differs)
        self._trace_validator = trace_validator
        self._tracing = False
        self.device = device
        self.opt_device = opt_device
        self.lr_schedule = lr_schedule
        self.clip = float(gradient_clipping)
        self._param_from_master = bool(param_from_master)
        self._eager_requested = eager
        self._eager = False
        self._eager_sq = 0.0
        self._eager_lr = 0.0
        self.compute_dtype = compute_dtype
        # host compute-copy dtype follows the engine's compute dtype: fp16
        # configs store fp16 block copies (loss-scaled math end to end)
        self._cdt = (
            np.dtype(np.float16)
            if jnp.dtype(compute_dtype) == jnp.float16
            else _BF16
        )
        self.opt = DeepSpeedCPUAdam(
            lr=1e-3, betas=betas, eps=eps, weight_decay=weight_decay, adamw_mode=True
        )
        L = api.num_blocks

        # ---- host-side parameter storage --------------------------------
        rng = jax.random.PRNGKey(seed)
        pers_rng, *block_rngs = jax.random.split(rng, L + 1)
        init_blocks = None
        host_gen = None
        if initial_params is not None:
            assert api.split_params is not None, "block API lacks split_params"
            pers, init_blocks = api.split_params(jax.device_get(initial_params))
            pers = jax.device_get(pers)
        elif host_init and api.host_init_block is not None and api.host_init_persistent is not None:
            # numpy init straight into DRAM: no device materialization, no
            # multi-GB D2H through the (possibly remote) device transport
            host_gen = np.random.default_rng(seed)
            pers = api.host_init_persistent(host_gen)
        else:
            # persistent part: fp32 master pytree in DRAM (small)
            pers = jax.device_get(jax.jit(api.init_persistent)(pers_rng))
        self._pers_leaves, self._pers_tree = jax.tree.flatten(pers)
        # np.array forces a writable copy (zero-copy views of jax buffers are
        # read-only and the SIMD Adam updates masters in place)
        self._pers_master = [np.array(l, dtype=np.float32) for l in self._pers_leaves]
        self._pers_shapes = [l.shape for l in self._pers_leaves]

        # block template: flatten/unflatten spec shared by every block
        if init_blocks is not None:
            b0 = jax.device_get(init_blocks[0])
        elif host_gen is not None:
            b0 = api.host_init_block(host_gen, 0)
        else:
            b0 = jax.device_get(jax.jit(lambda k: api.init_block(k, 0))(block_rngs[0]))
        b0_leaves, self._blk_tree = jax.tree.flatten(b0)
        self._blk_shapes = [l.shape for l in b0_leaves]
        self._blk_sizes = [int(np.prod(s)) if s else 1 for s in self._blk_shapes]
        self._blk_offsets = np.cumsum([0] + self._blk_sizes)
        self.block_numel = int(self._blk_offsets[-1])

        # multi-device layout: flat block buffers shard over every mesh axis
        # (padded to divide); persistent params replicate. None => 1-device.
        from jax.sharding import NamedSharding, PartitionSpec

        n_mesh = int(np.prod(list(mesh.shape.values()))) if mesh is not None else 1
        if mesh is not None and n_mesh > 1:
            self._flat_sharding = NamedSharding(mesh, PartitionSpec(tuple(mesh.axis_names)))
            self._repl_sharding = NamedSharding(mesh, PartitionSpec())
            self._blk_pad = (-self.block_numel) % n_mesh
        else:
            self._flat_sharding = None
            self._repl_sharding = None
            self._blk_pad = 0

        # ---- optimizer-tier placement: which blocks' [master|m|v] records
        # live in DRAM vs swap through NVMe. "hybrid" packs as many records
        # as the DRAM budget holds and spills the rest — the split that lets
        # a 13B model train on a host where neither tier alone fits.
        rec_bytes = 3.0 * self.block_numel * 4.0
        if opt_device == "hybrid":
            budget = float(opt_dram_budget)
            if budget <= 0:
                budget = self._auto_dram_budget(L)
            k = int(max(0, min(L, budget // rec_bytes)))
            self._opt_nvme = frozenset(range(k, L))
            log_dist(
                f"ZeRO-Infinity hybrid optimizer tier: {k}/{L} block records in "
                f"DRAM ({k * rec_bytes / 1e9:.1f} GB), {L - k} on NVMe "
                f"({(L - k) * rec_bytes / 1e9:.1f} GB)"
            )
        elif opt_device == "nvme":
            self._opt_nvme = frozenset(range(L))
        else:
            self._opt_nvme = frozenset()

        # bf16 compute copies per block (DRAM or NVMe; none in from_master
        # mode — loads cast from the fp32 master record instead)
        self._param_swapper = None
        self._blk_bf16: List[Optional[np.ndarray]] = [None] * L
        # fp32 master + moments per block (DRAM or NVMe [master|m|v] records)
        self._opt_swapper = None
        self._blk_master: List[Optional[np.ndarray]] = [None] * L
        if device == "nvme" or self._opt_nvme:
            os.makedirs(nvme_path, exist_ok=True)
        if device == "nvme" and not self._param_from_master:
            from ...ops.aio import AsyncIOHandle
            from ..swap_tensor.partitioned_param_swapper import (
                AsyncPartitionedParameterSwapper,
            )

            # each swapper/stream gets its own C++ thread pool sized by the
            # ``aio`` config section (reference aio_config.py knobs)
            self._param_swapper = AsyncPartitionedParameterSwapper(
                os.path.join(nvme_path, "infinity"), dtype=self._cdt,
                aio_handle=AsyncIOHandle.from_config(aio_config),
            )
        if self._opt_nvme:
            from ...ops.aio import AsyncIOHandle
            from ..swap_tensor.partitioned_optimizer_swapper import (
                PipelinedOptimizerSwapper,
            )

            self._opt_swapper = PipelinedOptimizerSwapper(
                os.path.join(nvme_path, "infinity_opt"), n_tensors=3,
                read_handle=AsyncIOHandle.from_config(aio_config),
                write_handle=AsyncIOHandle.from_config(aio_config),
            )

        for i in range(L):
            if init_blocks is not None:
                blk = jax.device_get(init_blocks[i]) if i else b0
            elif host_gen is not None:
                blk = b0 if i == 0 else api.host_init_block(host_gen, i)
            else:
                blk = b0 if i == 0 else jax.device_get(
                    jax.jit(lambda k, i=i: api.init_block(k, i))(block_rngs[i])
                )
            flat = np.concatenate(
                [np.asarray(l, np.float32).reshape(-1) for l in jax.tree.leaves(blk)]
            )
            self._store_block_master(i, flat, init=True)
            if not self._param_from_master:
                self._store_block_bf16(i, flat.astype(self._cdt))
        del b0

        self._g_pers_acc: Optional[List[np.ndarray]] = None
        self._g_blk_acc: Dict[int, np.ndarray] = {}
        # device-resident persistent bf16 copy, refreshed after each step
        self._pers_dev = None
        # instrumentation: how many block-param device buffers are live at
        # once (the "window"); the memory-bound test asserts <= 2
        self._resident_blocks = 0
        self.max_resident_blocks = 0
        self._build_jits()
        total = L * self.block_numel + sum(int(np.prod(s)) for s in self._pers_shapes)
        log_dist(
            f"ZeRO-Infinity param tier: {total} params, {L} streamed blocks "
            f"({self.block_numel} params each) on {device}; optimizer tier on "
            f"{opt_device}; HBM window = persistent + 2 blocks"
        )

    # ---- block storage ----------------------------------------------------
    def _auto_dram_budget(self, L: int) -> float:
        """DRAM bytes available for resident optimizer records: MemAvailable
        minus a working-set reserve (in-flight grads + upload staging +
        persistent masters + runtime) and, when bf16 copies are stored in
        DRAM, the copies themselves."""
        avail = 64e9
        try:
            with open("/proc/meminfo") as f:
                for line in f:
                    if line.startswith("MemAvailable"):
                        avail = float(line.split()[1]) * 1024
                        break
        except OSError:
            pass
        reserve = 18e9
        if self.device == "cpu" and not self._param_from_master:
            reserve += L * self.block_numel * self._cdt.itemsize
        return max(0.0, avail - reserve)

    def _cast_master(self, master: np.ndarray) -> np.ndarray:
        """fp32 master -> compute-dtype copy for upload (SIMD cast when bf16)."""
        if self._cdt == _BF16:
            try:
                from ...ops.cpu_adam import f32_to_bf16

                return f32_to_bf16(master).view(_BF16)
            except Exception:
                pass
        return master.astype(self._cdt)

    def _pad_flat(self, flat: np.ndarray) -> np.ndarray:
        """Host flat buffers carry the shard padding so every load is
        upload-ready with no per-step concatenate."""
        if self._blk_pad:
            return np.concatenate([flat, np.zeros(self._blk_pad, flat.dtype)])
        return flat

    def _store_block_bf16(self, i: int, flat_bf16: np.ndarray) -> None:
        if self._param_from_master:
            return  # compute copies are cast from the master at load time
        if flat_bf16.size == self.block_numel:
            flat_bf16 = self._pad_flat(flat_bf16)
        if self._param_swapper is not None:
            # register adopts the array into an aligned buffer; swap_out
            # persists + frees the DRAM copy
            self._param_swapper.register(i, flat_bf16)
            self._param_swapper.swap_out([i], release=True)
        else:
            self._blk_bf16[i] = flat_bf16

    def _load_block_bf16(self, i: int) -> np.ndarray:
        if self._param_from_master:
            if i in self._opt_nvme and self._blk_master[i] is None:
                # partial record read: only the master slot comes off disk
                master = self._opt_swapper.read_tensor_slot(i, 0)
            else:
                master = self._blk_master[i]
            return self._pad_flat(self._cast_master(master))
        if self._param_swapper is not None:
            self._param_swapper.swap_in([i])
            return self._param_swapper.get(i)
        return self._blk_bf16[i]

    def _release_block_bf16(self, i: int) -> None:
        if self._param_from_master:
            return  # nothing cached: the cast copy dies with the caller ref
        if self._param_swapper is not None and self._param_swapper.available(i):
            # drop the DRAM copy without rewriting (params unchanged since load)
            self._param_swapper._buffers.pop(i, None)
            self._param_swapper._available.discard(i)

    def _store_block_master(self, i: int, master: np.ndarray, init: bool = False) -> None:
        if i in self._opt_nvme:
            if init:
                z = np.zeros_like(master)
                # initialize_subgroup persists the record itself; just drop
                # the DRAM staging buffer (no second write)
                self._opt_swapper.initialize_subgroup(i, [master, z, z])
                self._opt_swapper.release(i)
            # non-init: run_pipeline writes back via its own swap_out
        else:
            self._blk_master[i] = master
            if init:
                pass  # moments lazy-init inside DeepSpeedCPUAdam

    # ---- compiled per-block programs --------------------------------------
    def _build_jits(self) -> None:
        api = self.api

        self._j_embed = jax.jit(api.embed_fwd, static_argnums=3)

        # blocks enter compute as ONE flat (possibly mesh-sharded) buffer and
        # unflatten in-graph: XLA sees the slice/reshape and inserts the
        # allgather exactly where a shard is consumed — the just-in-time
        # param fetch of the reference coordinator, as a compiler decision
        offs, shapes = self._blk_offsets, self._blk_shapes
        blk_tree = self._blk_tree
        flat_sharding = self._flat_sharding

        def unflat(flat):
            leaves = [
                flat[int(offs[j]) : int(offs[j + 1])].reshape(shapes[j])
                for j in range(len(shapes))
            ]
            return jax.tree.unflatten(blk_tree, leaves)

        def block_fwd_flat(flat, h, rng, train):
            return api.block_fwd(unflat(flat), h, rng, train)

        self._j_block = jax.jit(block_fwd_flat, static_argnums=3)

        def blk_bwd(flat, h, rng, dh):
            _, vjp = jax.vjp(lambda f, x: block_fwd_flat(f, x, rng, True), flat, h)
            gf, dx = vjp(dh)
            if flat_sharding is not None:
                # reduce-scatter: each chip keeps only its slice of the
                # block's grads; the D2H fetch then streams 1/N per chip
                gf = jax.lax.with_sharding_constraint(gf, flat_sharding)
            return gf, dx

        self._j_block_bwd = jax.jit(blk_bwd)

        def head_scaled(pers, h, batch, scale):
            # fp16: the dynamic loss scale multiplies the head loss so the
            # whole backward sweep (dh through every block VJP) runs scaled
            return api.head_loss(pers, h, batch) * scale

        self._j_head = jax.jit(jax.value_and_grad(head_scaled, argnums=(0, 1)))
        self._j_head_loss = jax.jit(api.head_loss)

        def embed_bwd(pers, batch, rng, dh):
            _, vjp = jax.vjp(lambda p: api.embed_fwd(p, batch, rng, True), pers)
            (gp,) = vjp(dh)
            return gp

        self._j_embed_bwd = jax.jit(embed_bwd)

    # ---- device staging ----------------------------------------------------
    def _put_block(self, i: int):
        """Upload block i as one flat buffer; sharded over the mesh when
        dp>1 (each chip receives only its slice), whole otherwise."""
        if self._trace_validator is not None and self._tracing:
            self._trace_validator.record_fetch(i)
        flat = self._load_block_bf16(i)
        if self._flat_sharding is not None:
            dev = jax.device_put(flat, self._flat_sharding)
        else:
            dev = jnp.asarray(flat)
        self._release_block_bf16(i)
        self._resident_blocks += 1
        self.max_resident_blocks = max(self.max_resident_blocks, self._resident_blocks)
        return dev

    def _mark_block_released(self) -> None:
        """Caller drops its reference; XLA frees the buffers once the last
        dispatched computation using them retires."""
        self._resident_blocks -= 1

    def _persistent_device(self):
        if self._pers_dev is None:
            # device_put the HOST arrays (one H2D per leaf, replicated in
            # the same transfer on a mesh) — not jnp.asarray-then-replicate
            leaves = [
                jax.device_put(
                    m.astype(self._cdt).reshape(s),
                    *( (self._repl_sharding,) if self._repl_sharding is not None else () ),
                )
                for m, s in zip(self._pers_master, self._pers_shapes)
            ]
            self._pers_dev = jax.tree.unflatten(self._pers_tree, leaves)
        return self._pers_dev

    # ---- the streamed step -------------------------------------------------
    def _micro_sweep(self, batch_dev: PyTree, rng, scale: float = 1.0) -> jnp.ndarray:
        """One microbatch fwd+bwd; accumulates host grads (loss-scaled when
        ``scale`` != 1). Returns the UNscaled loss."""
        L = self.api.num_blocks
        pers = self._persistent_device()
        rngs = jax.random.split(rng, L + 1)

        h = self._j_embed(pers, batch_dev, rngs[L], True)
        acts = [h]
        nxt = self._put_block(0)
        for i in range(L):
            cur, nxt = nxt, None
            if i + 1 < L:
                nxt = self._put_block(i + 1)  # async H2D overlaps compute
            h = self._j_block(cur, h, rngs[i], True)
            acts.append(h)
            cur = None
            self._mark_block_released()

        (loss_scaled, (g_pers, dh)) = self._j_head(
            pers, acts[L], batch_dev, jnp.float32(scale)
        )
        loss = loss_scaled / scale
        self._acc_pers(g_pers)

        nxt = self._put_block(L - 1)
        pending: Optional[Tuple[int, Any]] = None
        for i in range(L - 1, -1, -1):
            cur, nxt = nxt, None
            if i - 1 >= 0:
                nxt = self._put_block(i - 1)
            g_blk, dh = self._j_block_bwd(cur, acts[i], rngs[i], dh)
            acts[i + 1] = None  # boundary act consumed
            if pending is not None:
                # D2H of block i+1's grads overlaps block i's VJP on device
                self._sink_block(*pending)
            pending = (i, g_blk)
            cur = None
            self._mark_block_released()
        if pending is not None:
            self._sink_block(*pending)

        g_pers_embed = self._j_embed_bwd(pers, batch_dev, rngs[L], dh)
        self._acc_pers(g_pers_embed)
        return loss

    def _acc_pers(self, g_pers_dev: PyTree) -> None:
        leaves = [np.asarray(l, np.float32).reshape(-1) for l in jax.tree.leaves(
            jax.device_get(g_pers_dev)
        )]
        if self._g_pers_acc is None:
            self._g_pers_acc = leaves
        else:
            for a, g in zip(self._g_pers_acc, leaves):
                a += g

    def _acc_block(self, i: int, g_flat_dev) -> None:
        flat = np.asarray(jax.device_get(g_flat_dev), np.float32).reshape(-1)
        flat = flat[: self.block_numel]  # strip shard padding
        if i in self._g_blk_acc:
            self._g_blk_acc[i] += flat
        else:
            self._g_blk_acc[i] = flat

    def _sink_block(self, i: int, g_flat_dev) -> None:
        if self._eager:
            self._eager_block_step(i, g_flat_dev)
        else:
            self._acc_block(i, g_flat_dev)

    def _eager_block_step(self, i: int, g_flat_dev) -> None:
        """Apply block i's optimizer update inside the backward sweep.

        Exact only under the conditions train_step checks (gas==1, no loss
        scale, no global clipping): then the accumulate-everything path would
        apply the identical per-block update later, while holding every
        block's fp32 grad in DRAM at once (~4 B/param — at 13B that alone is
        ~50 GB). Eager bounds the grad high-water to the ~2 in-flight blocks.
        """
        g = np.asarray(jax.device_get(g_flat_dev), np.float32).reshape(-1)
        g = g[: self.block_numel]
        self._eager_sq += float(np.dot(g, g))
        lr = self._eager_lr
        if i in self._opt_nvme:
            # previous record's async writeback must land (and its staging
            # buffer free) before this one stages — bounds DRAM to one
            # in-flight record while the write overlaps the next blocks'
            # device VJPs (the reference's writeback(i-1) pipeline stage)
            self._opt_swapper.drain_writes()
            self._opt_swapper.swap_in(i)
            master, m, v = self._opt_swapper.tensors(i)
            self.opt.set_state(i, [m, v])
            self.opt._step.setdefault(i, 0)
            self.opt.step(master, g, key=i, lr=lr)
            if not self._param_from_master:
                self._store_block_bf16(i, master.astype(self._cdt))
            del self.opt._m[i], self.opt._v[i]  # views into the record
            self._opt_swapper.swap_out(i, release=True, async_op=True)
        else:
            self.opt.step(self._blk_master[i], g, key=i, lr=lr)
            if not self._param_from_master:
                self._store_block_bf16(i, self._blk_master[i].astype(self._cdt))

    def train_step(
        self, batch_gas: PyTree, global_step: int, rng, scale: Optional[float] = None
    ) -> Dict[str, Any]:
        """batch_gas leaves are [gas, micro, ...] device (or host) arrays.

        ``scale`` engages fp16 dynamic-loss-scale semantics: grads accumulate
        scaled, an overflow (any non-finite accumulator) skips the host
        optimizer step entirely (params/moments untouched) and returns
        ``overflow=True`` for the engine to back the scale off."""
        gas = int(jax.tree.leaves(batch_gas)[0].shape[0])
        scale_f = 1.0 if scale is None else float(scale)
        lr_now = (
            float(self.lr_schedule(global_step))
            if callable(self.lr_schedule)
            else float(self.lr_schedule)
        )
        # eager per-block updates are exact only when nothing global gates
        # the step: single micro-batch, no loss-scale overflow check, no
        # global-norm clipping
        eager_ok = gas == 1 and scale is None and self.clip == 0.0
        self._eager = eager_ok if self._eager_requested is None else (
            bool(self._eager_requested) and eager_ok
        )
        self._eager_sq = 0.0
        self._eager_lr = lr_now
        self._g_pers_acc = None
        self._g_blk_acc = {}
        losses = []
        if self._trace_validator is not None:
            self._trace_validator.begin_step()
        self._tracing = True
        try:
            for g in range(gas):
                micro = jax.tree.map(lambda x: x[g], batch_gas)
                losses.append(
                    self._micro_sweep(micro, jax.random.fold_in(rng, g), scale_f)
                )
        finally:
            # an aborted sweep must not leave a partial trace that makes the
            # next (healthy) step look divergent
            self._tracing = False
        loss = float(np.mean([float(jax.device_get(l)) for l in losses]))

        if scale is not None:
            overflow = not (
                all(np.isfinite(a).all() for a in self._g_blk_acc.values())
                and all(np.isfinite(a).all() for a in self._g_pers_acc)
            )
            if overflow:
                # drop grads, keep masters/moments/compute copies untouched
                self._g_blk_acc = {}
                self._g_pers_acc = None
                if self._trace_validator is not None:
                    self._trace_validator.end_step()
                return {
                    "loss": loss,
                    "grad_norm": float("nan"),
                    "lr": lr_now,
                    "overflow": True,
                }

        # mean over gas, unscale + global grad norm (host side, all staged).
        # Eager mode already applied every block's update inside the backward
        # sweep (conditions guarantee inv == 1 and coef == 1); its per-block
        # squared norms fold into the reported global norm here.
        inv = 1.0 / (gas * scale_f)
        sq = self._eager_sq if self._eager else 0.0
        for gacc in self._g_blk_acc.values():
            gacc *= inv
            sq += float(np.dot(gacc, gacc))
        for gacc in self._g_pers_acc:
            gacc *= inv
            sq += float(np.dot(gacc, gacc))
        gnorm = float(np.sqrt(sq))
        coef = 1.0
        if self.clip > 0.0 and gnorm > self.clip:
            coef = self.clip / (gnorm + 1e-6)

        lr = lr_now

        # ---- per-block optimizer tier (pipelined when NVMe) -------------
        L = self.api.num_blocks

        if not self._eager:
            nvme_ids = sorted(self._opt_nvme)
            if nvme_ids:

                def step_fn(i, tensors):
                    master, m, v = tensors
                    self.opt.set_state(i, [m, v])
                    self.opt._step.setdefault(i, 0)
                    g = self._g_blk_acc[i]
                    if coef != 1.0:
                        g = g * coef
                    self.opt.step(master, g, key=i, lr=lr)
                    if not self._param_from_master:
                        self._store_block_bf16(i, master.astype(self._cdt))
                    del self.opt._m[i], self.opt._v[i]  # views into the record
                    del self._g_blk_acc[i]

                self._opt_swapper.run_pipeline(nvme_ids, step_fn)
            for i in range(L):
                if i in self._opt_nvme:
                    continue
                g = self._g_blk_acc.pop(i)
                if coef != 1.0:
                    g = g * coef
                self.opt.step(self._blk_master[i], g, key=i, lr=lr)
                if not self._param_from_master:
                    self._store_block_bf16(i, self._blk_master[i].astype(self._cdt))

        # ---- persistent part (always DRAM; key space above the blocks) --
        for j, (m, g) in enumerate(zip(self._pers_master, self._g_pers_acc)):
            if coef != 1.0:
                g = g * coef
            self.opt.step(m.reshape(-1), g, key=L + j, lr=lr)
        if self._eager and self._opt_swapper is not None:
            # flush the last async record writeback: no pending write (or
            # its staging buffer) survives the step
            self._opt_swapper.drain_writes()
        self._pers_dev = None  # refresh device copy next step
        self._g_pers_acc = None
        if self._trace_validator is not None:
            self._trace_validator.end_step()
        return {"loss": loss, "grad_norm": gnorm * coef, "lr": lr, "overflow": False}

    def eval_loss(self, batch_gas: PyTree, rng) -> float:
        """Forward-only streamed sweep (train=False), mean loss over gas."""
        L = self.api.num_blocks
        pers = self._persistent_device()
        gas = int(jax.tree.leaves(batch_gas)[0].shape[0])
        losses = []
        for g in range(gas):
            micro = jax.tree.map(lambda x: x[g], batch_gas)
            h = self._j_embed(pers, micro, rng, False)
            nxt = self._put_block(0)
            for i in range(L):
                cur, nxt = nxt, None
                if i + 1 < L:
                    nxt = self._put_block(i + 1)
                h = self._j_block(cur, h, rng, False)
                cur = None
                self._mark_block_released()
            losses.append(float(jax.device_get(self._j_head_loss(pers, h, micro))))
        return float(np.mean(losses))

    # ---- checkpoint surface ------------------------------------------------
    def state_dict(self) -> Dict[str, Any]:
        L = self.api.num_blocks
        blocks = np.empty((L, self.block_numel), np.float32)
        ms = np.empty((L, self.block_numel), np.float32)
        vs = np.empty((L, self.block_numel), np.float32)
        for i in range(L):
            if i in self._opt_nvme:
                self._opt_swapper.swap_in(i)
                master, m, v = self._opt_swapper.tensors(i)
                blocks[i], ms[i], vs[i] = master, m, v
                self._opt_swapper.release(i)  # read-only: no writeback
            else:
                blocks[i] = self._blk_master[i]
                m, v = self.opt.state_tensors(i, self.block_numel)
                ms[i], vs[i] = m, v
        pers_state = [
            self.opt.state_tensors(L + j, m.size) for j, m in enumerate(self._pers_master)
        ]
        return {
            "blocks": blocks,
            "block_m": ms,
            "block_v": vs,
            "persistent": [m.copy() for m in self._pers_master],
            "persistent_m": [m.copy() for m, _ in pers_state],
            "persistent_v": [v.copy() for _, v in pers_state],
            "steps": {k: int(s) for k, s in self.opt._step.items()},
        }

    def load_state_dict(self, sd: Dict[str, Any]) -> None:
        L = self.api.num_blocks
        for i in range(L):
            master = np.asarray(sd["blocks"][i], np.float32)
            if i in self._opt_nvme:
                self._opt_swapper.swap_in(i)
                t_master, t_m, t_v = self._opt_swapper.tensors(i)
                t_master[:] = master
                t_m[:] = sd["block_m"][i]
                t_v[:] = sd["block_v"][i]
                self._opt_swapper.swap_out(i, release=True)
            else:
                self._blk_master[i] = master.copy()
                self.opt.set_state(i, [np.array(sd["block_m"][i]), np.array(sd["block_v"][i])])
            if not self._param_from_master:
                self._store_block_bf16(i, master.astype(self._cdt))
        for j, (m, saved) in enumerate(zip(self._pers_master, sd["persistent"])):
            m[:] = saved
            if "persistent_m" in sd:
                self.opt.set_state(
                    L + j,
                    [np.array(sd["persistent_m"][j]), np.array(sd["persistent_v"][j])],
                )
        for k, s in sd.get("steps", {}).items():
            self.opt._step[int(k)] = int(s)
        self._pers_dev = None

    def adopt_params(self, params: PyTree) -> None:
        """Adopt an externally built full param tree into the host tiers —
        params only, Adam moments reset (the reference ``load_module_only``
        semantics). Used by ``engine.load_megatron_checkpoint`` so Megatron
        ingestion works on engines whose params never materialize on device.
        Persistent leaves whose leading dim differs (vocab padding) are
        padded/sliced to the engine's shapes."""
        assert self.api.split_params is not None, "block API lacks split_params"
        L = self.api.num_blocks
        pers, blocks = self.api.split_params(jax.device_get(params))
        new_leaves, tree2 = jax.tree.flatten(pers)
        assert tree2 == self._pers_tree, "persistent structure mismatch"
        for j, leaf in enumerate(new_leaves):
            a = np.asarray(leaf, np.float32)
            tgt = self._pers_master[j]
            if a.shape != tgt.shape:
                assert a.shape[1:] == tgt.shape[1:], (a.shape, tgt.shape)
                if a.shape[0] >= tgt.shape[0]:
                    a = a[: tgt.shape[0]]
                else:
                    a = np.concatenate(
                        [a, np.zeros((tgt.shape[0] - a.shape[0],) + a.shape[1:], np.float32)]
                    )
            tgt[...] = a
            self.opt._m.pop(L + j, None)
            self.opt._v.pop(L + j, None)
            self.opt._step.pop(L + j, None)
        for i, blk in enumerate(blocks):
            flat = np.concatenate(
                [np.asarray(l, np.float32).reshape(-1) for l in jax.tree.leaves(blk)]
            )
            assert flat.size == self.block_numel, (flat.size, self.block_numel)
            if i in self._opt_nvme:
                self._opt_swapper.swap_in(i)
                master, m, v = self._opt_swapper.tensors(i)
                master[:] = flat
                m[:] = 0.0
                v[:] = 0.0
                self._opt_swapper.swap_out(i, release=True)
            else:
                self._blk_master[i] = flat
                self.opt._m.pop(i, None)
                self.opt._v.pop(i, None)
            self.opt._step.pop(i, None)
            if not self._param_from_master:
                self._store_block_bf16(i, flat.astype(self._cdt))
        self._pers_dev = None
