"""Compressed gradient collectives + overlap-aware bucketed reduce (ISSUE 2).

Covers the comm/compressed.py layer (quantize/dequant round-trip bounds,
two-stage compressed allreduce, bucket plans), the engine wiring (bucketed
grad path equivalence vs the fused path, compressed training convergence,
error-feedback residuals in TrainState), and the accounting surfaces
(wire-vs-logical bytes >= 3x, CommsLogger ratio columns, telemetry gauges).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

import deepspeed_tpu.comm.comm as dscomm
from deepspeed_tpu.comm import compressed as cco
from deepspeed_tpu.runtime.config import DeepSpeedConfig, DeepSpeedConfigError
from deepspeed_tpu.runtime.engine import DeepSpeedEngine
from jax import shard_map

from .simple_model import base_config, make_simple_model, random_batches

WORLD = 8


def setup_function(_):
    cco.reset_records()


def _make_engine(mesh, stage=0, bucket_bytes=None, compression=None, **extra):
    model = make_simple_model()
    zo = {"stage": stage}
    if bucket_bytes is not None:
        zo["reduce_bucket_size"] = bucket_bytes
    cfg_dict = base_config(stage=stage, dp=WORLD, **extra)
    cfg_dict["zero_optimization"] = zo
    if compression is not None:
        cfg_dict["comm_compression"] = compression
    cfg = DeepSpeedConfig.load(cfg_dict, dp_world_size=WORLD)
    return DeepSpeedEngine(model, cfg, mesh=mesh, seed=1)


# ---------------------------------------------------------------------------
# quantizer round-trip error bounds
# ---------------------------------------------------------------------------

class TestQuantizers:
    def test_int8_roundtrip_bound(self):
        x = np.random.RandomState(0).randn(4096).astype(np.float32) * 3.0
        q, s = cco.quantize_blocks(jnp.asarray(x), "int8", 256)
        assert q.dtype == jnp.int8 and s.shape == (16,)
        deq = np.asarray(cco.dequantize_blocks(q, s, 256))
        # round-to-nearest: |err| <= scale/2 = amax/(2*127) per block
        amax = np.abs(x.reshape(-1, 256)).max(axis=1, keepdims=True)
        bound = amax / 127.0 * 0.5 + 1e-7
        assert np.all(np.abs(deq - x).reshape(-1, 256) <= bound)

    def test_fp8_roundtrip_bound(self):
        x = np.random.RandomState(1).randn(4096).astype(np.float32)
        q, s = cco.quantize_blocks(jnp.asarray(x), "fp8", 256)
        assert q.dtype == jnp.float8_e4m3fn
        deq = np.asarray(cco.dequantize_blocks(q, s, 256))
        # e4m3: 3 mantissa bits -> relative rounding error <= 2^-4 of the
        # element, plus a subnormal floor from the block's amax scaling
        amax = np.repeat(np.abs(x.reshape(-1, 256)).max(axis=1), 256)
        assert np.all(np.abs(deq - x) <= np.abs(x) * 2.0**-4 + amax * 2.0**-9 + 1e-7)

    def test_zero_block_exact(self):
        x = jnp.zeros((512,), jnp.float32)
        for method in cco.METHODS:
            q, s = cco.quantize_blocks(x, method, 256)
            assert np.all(np.asarray(cco.dequantize_blocks(q, s, 256)) == 0)

    def test_wire_bytes_formula(self):
        # 1 byte/elem + 4 bytes per block scale, ~3.94x under fp32 at 256
        assert cco.wire_bytes(1024, "int8", 256) == 1024 + 16
        assert 4 * 1024 / cco.wire_bytes(1024, "int8", 256) > 3.9


# ---------------------------------------------------------------------------
# compressed collectives under shard_map
# ---------------------------------------------------------------------------

class TestCompressedCollectives:
    def _run(self, fn, mesh, xs, n_out=2):
        mapped = jax.jit(
            shard_map(
                fn, mesh=mesh, in_specs=(P("dp"),),
                out_specs=tuple([P("dp")] * n_out), check_vma=False,
            )
        )
        return mapped(jnp.asarray(xs))

    def test_allreduce_approximates_pmean(self, mesh_dp8):
        n = WORLD * 512
        xs = np.random.RandomState(0).randn(WORLD, n).astype(np.float32)

        def f(xb):
            m, r = cco.compressed_all_reduce(xb[0], "dp", WORLD, "int8", 64)
            return m[None], r[None]

        m, r = self._run(f, mesh_dp8, xs)
        m = np.asarray(m)
        true = xs.mean(axis=0)
        # int8 block-scaled: ~1% relative error on the reduced value
        assert np.abs(m[0] - true).max() <= 0.02 * np.abs(true).max()
        # the all_gather broadcast makes every rank's copy identical
        assert all(np.array_equal(m[0], m[i]) for i in range(WORLD))
        # residual == input - what the wire carried (per-rank local error)
        assert np.asarray(r).shape == (WORLD, n)

    def test_reduce_scatter_chunks(self, mesh_dp8):
        n = WORLD * 256
        xs = np.random.RandomState(1).randn(WORLD, n).astype(np.float32)

        def f(xb):
            c, r = cco.compressed_reduce_scatter(xb[0], "dp", WORLD, "int8", 64)
            return c[None], r[None]

        c, _ = self._run(f, mesh_dp8, xs)
        chunks = np.asarray(c).reshape(-1)  # [world * n/world] == full vector
        true = xs.mean(axis=0)
        assert np.abs(chunks - true).max() <= 0.02 * np.abs(true).max()

    def test_trace_time_records_ratio(self, mesh_dp8):
        n = WORLD * 64 * 8

        def f(xb):
            m, _ = cco.compressed_all_reduce(xb[0], "dp", WORLD, "int8", 64)
            return (m[None],)

        self._run(f, mesh_dp8, np.zeros((WORLD, n), np.float32), n_out=1)
        by_axis = cco.records_by_axis()
        assert "dp" in by_axis
        rec = by_axis["dp"]
        assert rec["logical_bytes"] > rec["wire_bytes"] > 0
        assert rec["ratio"] >= 3.0  # acceptance: >= 3x under fp32


class TestCompressedGatherAndAllToAll:
    """ISSUE 12: the two remaining big transfers on the compressed wire —
    the ZeRO-3 param all-gather and the (MoE) all-to-all. Pure data
    movement: no error feedback, parity bounded by the block codec's
    one-shot rounding, wire >= 3x under fp32."""

    def _map(self, fn, mesh, n_in=1, n_out=1):
        return jax.jit(shard_map(
            fn, mesh=mesh, in_specs=tuple([P("dp")] * n_in),
            out_specs=(P("dp") if n_out == 1 else tuple([P("dp")] * n_out)),
            check_vma=False,
        ))

    def test_all_gather_rank_identical_and_bounded(self, mesh_dp8):
        n = 192  # NOT a block multiple: exercises the remainder path
        xs = np.random.RandomState(5).randn(WORLD, n).astype(np.float32)

        def f(xb):
            full = cco.compressed_all_gather(xb[0], "dp", WORLD, "int8", 64)
            return full[None]

        out = np.asarray(self._map(f, mesh_dp8)(jnp.asarray(xs)))
        # out[r] is rank r's gathered copy: all ranks bit-identical
        assert all(np.array_equal(out[0], out[r]) for r in range(WORLD))
        flat = xs.reshape(-1)
        amax = np.abs(flat).max()
        assert np.abs(out[0] - flat).max() <= amax / 127.0 * 0.5 + 1e-7

    def test_all_to_all_parity_and_wire_ratio(self, mesh_dp8):
        cco.reset_records()
        n = WORLD * 96
        xs = np.random.RandomState(6).randn(WORLD, n).astype(np.float32)

        def f_plain(xb):
            from jax import lax

            return lax.all_to_all(
                xb[0].reshape(WORLD, n // WORLD), "dp",
                split_axis=0, concat_axis=0, tiled=False,
            ).reshape(1, n)

        def f_comp(xb):
            return cco.compressed_all_to_all(
                xb[0].reshape(WORLD, n // WORLD), "dp", WORLD, "int8", 64
            ).reshape(1, n)

        ref = np.asarray(self._map(f_plain, mesh_dp8)(jnp.asarray(xs)))
        got = np.asarray(self._map(f_comp, mesh_dp8)(jnp.asarray(xs)))
        amax = np.abs(xs).max()
        assert np.abs(got - ref).max() <= amax / 127.0 * 0.5 + 1e-7
        rec = cco.records()[("all_to_all", "dp")]
        assert rec["logical_bytes"] / rec["wire_bytes"] >= 3.0

    def test_gather_full_compressed_tree(self, mesh_dp8):
        """partitioning.gather_full_compressed: dp-sharded leaves gather on
        the compressed wire, unsharded leaves replicate untouched (exact),
        dtypes preserved."""
        from jax.sharding import NamedSharding
        from deepspeed_tpu.runtime.zero.partitioning import (
            gather_full_compressed,
        )

        rs = np.random.RandomState(7)
        sharded = jax.device_put(
            jnp.asarray(rs.randn(WORLD * 16, 8), jnp.float32),
            NamedSharding(mesh_dp8, P("dp")),
        )
        small = jax.device_put(
            jnp.asarray(rs.randn(4), jnp.float32),
            NamedSharding(mesh_dp8, P()),
        )
        tree = {"big": sharded, "small": small}
        out = gather_full_compressed(tree, mesh_dp8, "dp", "int8", 64)
        assert out["big"].sharding.is_fully_replicated
        assert out["big"].dtype == jnp.float32
        amax = float(jnp.max(jnp.abs(sharded)))
        assert float(jnp.max(jnp.abs(out["big"] - sharded))) <= amax / 127.0 * 0.5 + 1e-6
        np.testing.assert_array_equal(np.asarray(out["small"]), np.asarray(small))

    def test_policy_gate_requires_stage3_and_axis(self, mesh_dp8):
        from deepspeed_tpu.runtime.config import CommCompressionConfig
        from deepspeed_tpu.runtime.zero.partitioning import (
            ZeroShardingPolicy,
            gather_full,
        )

        cc = CommCompressionConfig(enabled=True)
        p3 = ZeroShardingPolicy(mesh_dp8, stage=3)
        p2 = ZeroShardingPolicy(mesh_dp8, stage=2)
        assert p3.supports_compressed_param_gather()
        assert not p2.supports_compressed_param_gather()
        # the ledger is the non-vacuous witness of which path ran: a
        # compressed gather records ("all_gather", "dp"); the plain
        # device_put path records nothing — and irrational values can't
        # round-trip the int8 codec by luck, so bit-equality with
        # gather_full proves the plain path bit-wise too
        x = jnp.asarray(np.random.RandomState(0).randn(8), jnp.float32)
        for policy, cfg in ((p2, cc), (p3, CommCompressionConfig(enabled=False))):
            cco.reset_records()
            out = policy.param_gather_fn(cfg)({"x": x})["x"]
            np.testing.assert_array_equal(
                np.asarray(out), np.asarray(gather_full({"x": x}, mesh_dp8)["x"])
            )
            assert ("all_gather", "dp") not in cco.records()


# ---------------------------------------------------------------------------
# error feedback on a toy quadratic
# ---------------------------------------------------------------------------

class TestErrorFeedback:
    def _gd(self, mesh, targets, steps, lr, compressed, error_feedback=True):
        world, n = targets.shape

        def f(w, res, t):
            g = w - t[0]
            if compressed:
                comp = g + res[0] if error_feedback else g
                m, e = cco.compressed_all_reduce(comp, "dp", world, "int8", 64)
                if not error_feedback:
                    e = jnp.zeros_like(e)
            else:
                m, e = jax.lax.pmean(g, "dp"), res[0]
            return m, e[None]

        step = jax.jit(
            shard_map(
                f, mesh=mesh, in_specs=(P(), P("dp"), P("dp")),
                out_specs=(P(), P("dp")), check_vma=False,
            )
        )
        w = jnp.zeros((n,), jnp.float32)
        res = jnp.zeros((world, n), jnp.float32)
        t = jnp.asarray(targets)
        for _ in range(steps):
            m, res = step(w, res, t)
            w = w - lr * m
        return np.asarray(w)

    def test_quadratic_convergence_matches_uncompressed(self, mesh_dp8):
        """min_w mean_r 0.5||w - t_r||^2 by GD: with error feedback the
        compressed run lands on the optimum like the exact run; without it
        the bias from repeated rounding is measurably larger."""
        rs = np.random.RandomState(7)
        targets = rs.randn(WORLD, 512).astype(np.float32)
        opt = targets.mean(axis=0)
        steps, lr = 40, 0.5
        w_ref = self._gd(mesh_dp8, targets, steps, lr, compressed=False)
        w_ef = self._gd(mesh_dp8, targets, steps, lr, compressed=True)
        w_noef = self._gd(
            mesh_dp8, targets, steps, lr, compressed=True, error_feedback=False
        )
        scale = np.abs(opt).max()
        assert np.abs(w_ref - opt).max() <= 1e-5 * scale  # exact GD converged
        ef_err = np.abs(w_ef - opt).max()
        noef_err = np.abs(w_noef - opt).max()
        assert ef_err <= 5e-3 * scale, ef_err
        assert ef_err <= noef_err + 1e-6, (ef_err, noef_err)


# ---------------------------------------------------------------------------
# bucket plans
# ---------------------------------------------------------------------------

class TestBucketPlan:
    def test_cap_and_coverage(self):
        sizes = [100, 200, 50, 1000, 30]
        plan = cco.build_bucket_plan(sizes, bucket_bytes=300 * 4, itemsize=4)
        covered = sorted(i for rows in plan.entries for i, _, _ in rows)
        assert covered == list(range(len(sizes)))
        for rows in plan.entries:
            total = sum(s for _, _, s in rows)
            # a bucket may exceed the cap only when a single oversized leaf
            # owns it (leaves are never split)
            assert total <= plan.cap_elems or len(rows) == 1

    def test_padding_multiple_and_roundtrip(self):
        sizes = (100, 200, 50, 1000)
        plan = cco.build_bucket_plan(sizes, 1200 * 4, 4, multiple=16)
        assert all(p % 16 == 0 for p in plan.padded)
        leaves = [jnp.arange(s, dtype=jnp.float32) + i for i, s in enumerate(sizes)]
        buckets = cco.flatten_to_buckets(leaves, plan)
        back = cco.unflatten_from_buckets(buckets, plan, [(s,) for s in sizes])
        for a, b in zip(leaves, back):
            assert np.array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# engine: bucketed grad path vs fused path (compression OFF)
# ---------------------------------------------------------------------------

class TestBucketedGradPath:
    @pytest.mark.parametrize("stage,gas", [(0, 2), (0, 1)])
    def test_bit_identical_when_same_collective(self, mesh_dp8, stage, gas):
        """With fully replicated state both paths reduce by the same
        all-reduce, so bucketing (concat/pad/split are exact) must be
        bit-identical. (With dp-sharded opt/grad state — stages 1/2 — XLA's
        partitioner may legally re-associate all-reduce+slice into
        reduce-scatter in one program and not the other; see the
        reduction-order test below.)"""
        b = random_batches(1, WORLD * 4 * gas)[0]
        e_ref = _make_engine(mesh_dp8, stage=stage, micro=4, gas=gas)
        e_bkt = _make_engine(
            mesh_dp8, stage=stage, micro=4, gas=gas,
            bucket_bytes=4096, compression={"bucketing": True},
        )
        for _ in range(3):
            l1 = e_ref.train_batch(b)["loss"]
            l2 = e_bkt.train_batch(b)["loss"]
        assert float(l1) == float(l2)
        p1 = jax.tree.leaves(jax.device_get(e_ref.state.params))
        p2 = jax.tree.leaves(jax.device_get(e_bkt.state.params))
        for a, c in zip(p1, p2):
            np.testing.assert_array_equal(a, c)

    @pytest.mark.parametrize("stage", [1, 2])
    def test_sharded_stages_match_to_reduction_order(self, mesh_dp8, stage):
        """Stage 2 buckets reduce-scatter over the flat concat while the
        fused path all-reduces small leaves / reduce-scatters large ones —
        a different (but mathematically identical) collective, so agreement
        is to summation-order precision (1-2 ulp), not bitwise; same for
        stage 1, where the dp-sharded opt state lets the partitioner
        re-associate the reduction."""
        b = random_batches(1, WORLD * 8)[0]
        e_ref = _make_engine(mesh_dp8, stage=stage)
        e_bkt = _make_engine(
            mesh_dp8, stage=stage, bucket_bytes=4096, compression={"bucketing": True}
        )
        for _ in range(3):
            e_ref.train_batch(b)
            e_bkt.train_batch(b)
        p1 = jax.tree.leaves(jax.device_get(e_ref.state.params))
        p2 = jax.tree.leaves(jax.device_get(e_bkt.state.params))
        for a, c in zip(p1, p2):
            np.testing.assert_allclose(a, c, rtol=0, atol=1e-7)

    def test_multiple_buckets_emitted(self, mesh_dp8):
        """A small cap must actually split the leaves into several buckets."""
        e = _make_engine(
            mesh_dp8, stage=0, bucket_bytes=4096, compression={"bucketing": True}
        )
        sizes = cco.leaf_sizes(e.state.params)
        plan = cco.build_bucket_plan(sizes, 4096, itemsize=4)
        assert plan.num_buckets >= 2


# ---------------------------------------------------------------------------
# engine: compressed grad collectives
# ---------------------------------------------------------------------------

class TestCompressedEngine:
    def test_training_converges_close_to_uncompressed(self, mesh_dp8):
        b = random_batches(1, WORLD * 8)[0]
        e_ref = _make_engine(mesh_dp8, stage=2)
        e_cmp = _make_engine(
            mesh_dp8, stage=2, bucket_bytes=8192,
            compression={"enabled": True, "method": "int8", "block_size": 64},
        )
        for _ in range(12):
            l_ref = float(e_ref.train_batch(b)["loss"])
            l_cmp = float(e_cmp.train_batch(b)["loss"])
        # toy-convergence acceptance: compressed loss within tolerance of the
        # uncompressed baseline after the same number of steps
        assert l_cmp <= l_ref * 1.15 + 0.05, (l_ref, l_cmp)

    def test_fp8_training_step_runs(self, mesh_dp8):
        b = random_batches(1, WORLD * 8)[0]
        e = _make_engine(
            mesh_dp8, stage=0,
            compression={"enabled": True, "method": "fp8", "block_size": 64},
        )
        first = float(e.train_batch(b)["loss"])
        for _ in range(5):
            last = float(e.train_batch(b)["loss"])
        assert np.isfinite(last) and last < first

    def test_no_error_feedback_skips_residual_buffers(self, mesh_dp8):
        """error_feedback=false must not allocate or carry the grad-sized
        [dp, ...] residual buffers (code-review finding)."""
        b = random_batches(1, WORLD * 8)[0]
        e = _make_engine(
            mesh_dp8, stage=0,
            compression={"enabled": True, "method": "int8", "block_size": 64,
                         "error_feedback": False},
        )
        assert e.state.comm_error == ()
        first = float(e.train_batch(b)["loss"])
        for _ in range(5):
            last = float(e.train_batch(b)["loss"])
        assert np.isfinite(last) and last < first
        assert e.state.comm_error == ()

    def test_stats_stable_across_relower(self, mesh_dp8):
        """_compression_stats is analytic (bucket plan), so re-tracing the
        same program (bench's device-only loop, comms accounting .lower())
        must not inflate the reported per-step bytes."""
        b = random_batches(1, WORLD * 8)[0]
        e = _make_engine(
            mesh_dp8, stage=0,
            compression={"enabled": True, "method": "int8", "block_size": 64},
        )
        e.train_batch(b)
        before = e._compression_stats()
        jax.jit(e._step_builder()).lower(
            e.state, e.shard_batch(b), jax.random.PRNGKey(0)
        )  # deliberate extra trace
        e.train_batch(b)
        assert e._compression_stats() == before

    def test_residuals_carried_in_state(self, mesh_dp8):
        b = random_batches(1, WORLD * 8)[0]
        e = _make_engine(
            mesh_dp8, stage=0,
            compression={"enabled": True, "method": "int8", "block_size": 64},
        )
        res0 = jax.tree.leaves(e.state.comm_error)
        assert res0 and all(r.shape[0] == WORLD for r in res0)
        e.train_batch(b)
        res1 = jax.tree.leaves(jax.device_get(e.state.comm_error))
        # after one step the quantization error is nonzero and fed back
        assert any(np.abs(r).max() > 0 for r in res1)

    def test_wire_bytes_drop_3x(self, mesh_dp8):
        """Acceptance: telemetry-reported wire bytes for the grad reduce axis
        drop >= 3x vs logical bytes with int8 on."""
        b = random_batches(1, WORLD * 8)[0]
        e = _make_engine(
            mesh_dp8, stage=2, bucket_bytes=8192,
            compression={"enabled": True, "method": "int8", "block_size": 64},
        )
        e.train_batch(b)
        stats = e._compression_stats()
        assert "dp" in stats, stats
        assert stats["dp"]["logical_bytes"] >= 3 * stats["dp"]["wire_bytes"]
        assert stats["dp"]["ratio"] >= 3.0

    def test_telemetry_surfaces_wire_and_ratio(self, mesh_dp8, tmp_path):
        import json

        b = random_batches(1, WORLD * 8)[0]
        e = _make_engine(
            mesh_dp8, stage=0,
            compression={"enabled": True, "method": "int8", "block_size": 64},
            telemetry={"enabled": True, "trace_path": str(tmp_path), "flush_interval": 1},
        )
        e.train_batch(b)
        e.telemetry.flush()
        recs = []
        for f in tmp_path.glob("*.jsonl"):
            recs += [json.loads(l) for l in f.read_text().splitlines() if l.strip()]
        step_recs = [r for r in recs if r.get("kind") == "train_step"]
        assert step_recs and "comm_wire_bytes" in step_recs[-1]
        assert step_recs[-1]["comm_compression"]["dp"]["ratio"] >= 3.0
        ratio = e.telemetry.registry.get("comm_compression_ratio")
        assert ratio is not None and ratio.value(axis="dp") >= 3.0

    def test_comms_logger_wire_columns(self, mesh_dp8):
        dscomm.comms_logger.reset()
        dscomm.comms_logger.configure(enabled=True)
        try:
            b = random_batches(1, WORLD * 8)[0]
            e = _make_engine(
                mesh_dp8, stage=0,
                compression={"enabled": True, "method": "int8", "block_size": 64},
            )
            e.train_batch(b)
            text = dscomm.log_summary()
            assert "wire size" in text and "ratio" in text
            a2a = dscomm.comms_logger.comms_dict[("all_to_all", "dp")]
            assert a2a["bytes"] >= 3 * a2a["wire_bytes"]
            # the comms-accounting path re-lowers (re-traces) the step; the
            # compressed rows must not double (suspend_records guard)
            count_before = a2a["count"]
            e.comms_summary()
            assert (
                dscomm.comms_logger.comms_dict[("all_to_all", "dp")]["count"]
                == count_before
            )
        finally:
            dscomm.comms_logger.reset()
            dscomm.comms_logger.configure(enabled=False)

    def test_checkpoint_roundtrip_restores_residuals(self, mesh_dp8, tmp_path):
        b = random_batches(1, WORLD * 8)[0]
        e = _make_engine(
            mesh_dp8, stage=0,
            compression={"enabled": True, "method": "int8", "block_size": 64},
        )
        e.train_batch(b)
        want = jax.device_get(e.state.comm_error)
        e.save_checkpoint(str(tmp_path), tag="t0")
        e2 = _make_engine(
            mesh_dp8, stage=0,
            compression={"enabled": True, "method": "int8", "block_size": 64},
        )
        e2.load_checkpoint(str(tmp_path), tag="t0")
        got = jax.device_get(e2.state.comm_error)
        for a, c in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
            np.testing.assert_array_equal(a, c)


    def test_checkpoint_cross_config_resume(self, mesh_dp8, tmp_path):
        """Toggling comm_compression between save and resume must not brick
        the run (residuals are a best-effort accelerant): saved-with →
        resume-without drops them; saved-without → resume-with restarts
        error feedback from zero."""
        comp = {"enabled": True, "method": "int8", "block_size": 64}
        b = random_batches(1, WORLD * 8)[0]

        e_on = _make_engine(mesh_dp8, stage=0, compression=comp)
        e_on.train_batch(b)
        want_params = jax.device_get(e_on.state.params)
        e_on.save_checkpoint(str(tmp_path / "on"), tag="t")
        e_off = _make_engine(mesh_dp8, stage=0)
        e_off.load_checkpoint(str(tmp_path / "on"), tag="t")
        assert e_off.state.comm_error == ()
        for a, c in zip(
            jax.tree.leaves(want_params),
            jax.tree.leaves(jax.device_get(e_off.state.params)),
        ):
            np.testing.assert_array_equal(a, c)

        e_plain = _make_engine(mesh_dp8, stage=0)
        e_plain.train_batch(b)
        e_plain.save_checkpoint(str(tmp_path / "off"), tag="t")
        e_on2 = _make_engine(mesh_dp8, stage=0, compression=comp)
        e_on2.load_checkpoint(str(tmp_path / "off"), tag="t")
        res = jax.tree.leaves(jax.device_get(e_on2.state.comm_error))
        assert res and all(np.all(r == 0) for r in res)
        e_on2.train_batch(b)  # resumed engine still steps


# ---------------------------------------------------------------------------
# config surface
# ---------------------------------------------------------------------------

class TestConfig:
    def test_section_parses(self):
        cfg = DeepSpeedConfig.load(
            {
                "train_micro_batch_size_per_gpu": 1,
                "comm_compression": {"enabled": True, "method": "fp8", "block_size": 128},
            }
        )
        assert cfg.comm_compression.enabled and cfg.comm_compression.method == "fp8"
        assert cfg.comm_compression.axes == ["dp"]

    def test_bad_method_rejected(self):
        with pytest.raises(DeepSpeedConfigError):
            DeepSpeedConfig.load(
                {
                    "train_micro_batch_size_per_gpu": 1,
                    "comm_compression": {"method": "int4"},
                }
            )

    def test_fp16_combination_rejected(self, mesh_dp8):
        with pytest.raises(ValueError, match="fp16"):
            _make_engine(
                mesh_dp8, stage=0,
                compression={"enabled": True}, fp16={"enabled": True},
            )

    def test_stage3_compresses_gather_not_grads(self, mesh_dp8):
        """ISSUE 12: stage 3 + comm_compression no longer rejects — the grad
        reduce stays uncompressed (params are dp-sharded inside the grad
        region) and compression covers the explicit param all-gather."""
        model = make_simple_model()
        cfg_dict = base_config(stage=3, dp=WORLD)
        # drop the persistence threshold so the tiny test params actually
        # shard over dp (the production default keeps small params gathered)
        cfg_dict["zero_optimization"] = {
            "stage": 3, "stage3_param_persistence_threshold": 2,
        }
        cfg_dict["comm_compression"] = {"enabled": True}
        cfg = DeepSpeedConfig.load(cfg_dict, dp_world_size=WORLD)
        eng = DeepSpeedEngine(model, cfg, mesh=mesh_dp8, seed=1)
        assert not eng._compress_grads
        assert any(
            not p.sharding.is_fully_replicated
            for p in jax.tree.leaves(eng.state.params)
        )
        cco.reset_records()
        gathered = eng.gather_params()
        # every gathered leaf replicated and ≈ the sharded original
        for g, p in zip(jax.tree.leaves(gathered), jax.tree.leaves(eng.state.params)):
            assert g.sharding.is_fully_replicated
            gn = np.asarray(g, np.float32)
            pn = np.asarray(p, np.float32)
            amax = np.abs(pn).max()
            assert np.abs(gn - pn).max() <= amax / 127.0 * 0.5 + 1e-6
        # the dp-sharded leaves went over the compressed wire
        recs = cco.records_by_axis()
        assert "dp" in recs and recs["dp"]["ratio"] >= 3.0
