"""Tests for 1-bit optimizers + compressed allreduce.

Reference analog: tests/onebit/ (NCCL/MPI compressed-allreduce correctness)
and tests/unit tests of OnebitAdam/OnebitLamb/ZeroOneAdam configs.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.runtime.comm.compressed import (
    compressed_allreduce,
    pack_signs,
    padded_length,
    unpack_signs,
)
from deepspeed_tpu.runtime.config import DeepSpeedConfig
from deepspeed_tpu.runtime.engine import DeepSpeedEngine
from deepspeed_tpu.runtime.fp16.onebit import ZeroOneAdam

from .simple_model import make_simple_model, random_batches


class TestPackedSigns:
    def test_roundtrip(self):
        rs = np.random.RandomState(0)
        signs = rs.rand(4, 64) > 0.5
        packed = pack_signs(jnp.asarray(signs))
        assert packed.dtype == jnp.uint8
        assert packed.shape == (4, 8)  # 8x volume reduction
        back = unpack_signs(packed, 64)
        assert np.array_equal(np.asarray(back), signs)

    def test_padded_length(self):
        assert padded_length(1000, 8) % 8 == 0
        assert padded_length(1000, 8) >= 1000
        assert padded_length(64, 8) == 64


class TestCompressedAllreduce:
    def test_error_feedback_convergence(self, mesh_dp8):
        """Cumulative compressed averages converge to the true mean — the
        compensated-compression guarantee (reference nccl.py error feedback)."""
        world = 8
        n = padded_length(512, world)
        rs = np.random.RandomState(1)
        xs = rs.randn(world, n).astype(np.float32)
        true_mean = xs.mean(0)

        f = shard_map(
            lambda x, we, se: compressed_allreduce(x[0], we[0], se[0], "dp", world),
            mesh=mesh_dp8,
            in_specs=(P("dp"), P("dp"), P("dp")),
            out_specs=(P(), P("dp"), P("dp")),
            check_vma=False,
        )
        we = np.zeros((world, n), np.float32)
        se = np.zeros((world, n // world), np.float32)
        acc = np.zeros(n, np.float32)
        errs = []
        for it in range(20):
            avg, we_n, se_n = f(xs, we, se)
            we = np.asarray(we_n).reshape(world, n)
            se = np.asarray(se_n).reshape(world, n // world)
            acc += np.asarray(avg)
            errs.append(
                np.linalg.norm(acc / (it + 1) - true_mean) / np.linalg.norm(true_mean)
            )
        assert errs[-1] < 0.5 * errs[0]  # error decays ~1/T
        assert errs[-1] < 0.3


def onebit_config(opt_type: str, opt_params=None, micro=2, gas=1):
    return {
        "train_micro_batch_size_per_gpu": micro,
        "gradient_accumulation_steps": gas,
        "optimizer": {
            "type": opt_type,
            "params": {"lr": 1e-2, "freeze_step": 4, **(opt_params or {})},
        },
        "zero_optimization": {"stage": 0},
        "steps_per_print": 10**9,
    }


class TestOnebitTraining:
    @pytest.mark.parametrize("opt_type", ["OneBitAdam", "OneBitLamb"])
    def test_trains_through_stage_switch(self, mesh_dp8, opt_type):
        model = make_simple_model()
        ds = DeepSpeedConfig.load(onebit_config(opt_type), dp_world_size=8)
        engine = DeepSpeedEngine(model, ds, mesh=mesh_dp8, seed=0)
        assert engine.onebit
        batch = random_batches(1, 16)[0]
        losses = []
        for _ in range(10):  # crosses freeze_step=4 → compressed stage
            m = engine.train_batch(batch)
            losses.append(float(jax.device_get(m["loss"])))
        assert all(np.isfinite(l) for l in losses)
        assert losses[-1] < losses[0], f"no learning: {losses}"
        # both stages compiled
        assert len(engine._onebit_step_cache) == 2

    def test_error_buffers_stored_per_rank(self, mesh_dp8):
        """Error-feedback buffers legitimately diverge across dp ranks; they
        must be stored with a leading dp-sharded axis (not falsely claimed
        replicated) so reshard/donate/checkpoint preserves every rank's
        values (ADVICE r1: compensated compression corruption on resume)."""
        model = make_simple_model()
        ds = DeepSpeedConfig.load(onebit_config("OneBitAdam"), dp_world_size=8)
        engine = DeepSpeedEngine(model, ds, mesh=mesh_dp8, seed=0)
        st = engine.state.opt_state
        assert st.worker_error.shape[0] == 8
        assert st.server_error.shape[0] == 8
        assert st.worker_error.sharding.spec[0] == "dp"
        batch = random_batches(1, 16)[0]
        for _ in range(6):  # past freeze_step=4 → compressed stage ran
            engine.train_batch(batch)
        we = np.asarray(jax.device_get(engine.state.opt_state.worker_error))
        assert np.abs(we).sum() > 0, "compressed stage should populate error feedback"
        # ranks genuinely differ -> storing them per-rank is load-bearing
        assert any(
            not np.array_equal(we[0], we[r]) for r in range(1, 8)
        ), "worker_error identical across ranks (suspicious)"
        # resharding the divergent per-rank array to replicated must gather
        # every rank's values (under the old falsely-replicated claim this
        # information did not survive: each device held a different "copy")
        from jax.sharding import NamedSharding

        replicated = NamedSharding(mesh_dp8, P())
        gathered = jax.device_put(engine.state.opt_state.worker_error, replicated)
        assert np.array_equal(np.asarray(jax.device_get(gathered)), we)

    def test_zero_one_adam(self, mesh_dp8):
        model = make_simple_model()
        ds = DeepSpeedConfig.load(
            onebit_config(
                "ZeroOneAdam",
                {"var_freeze_step": 4, "local_step_scaler": 2, "local_step_clipper": 2},
            ),
            dp_world_size=8,
        )
        engine = DeepSpeedEngine(model, ds, mesh=mesh_dp8, seed=0)
        batch = random_batches(1, 16)[0]
        losses = [float(jax.device_get(engine.train_batch(batch)["loss"])) for _ in range(10)]
        assert all(np.isfinite(l) for l in losses)
        assert losses[-1] < losses[0]

    def test_zero_one_policies(self):
        opt = ZeroOneAdam(
            var_freeze_step=8, var_update_scaler=2, local_step_scaler=4, local_step_clipper=2
        )
        # variance updates at exponentially spaced boundaries before freeze
        updates = [s for s in range(20) if opt.variance_update_step(s)]
        assert updates[0] == 0
        assert all(u < 8 for u in updates)
        # intervals double: gaps between consecutive updates grow
        gaps = np.diff(updates)
        assert all(g2 >= g1 for g1, g2 in zip(gaps, gaps[1:]))
        # before freeze every step syncs; after, interval-gated
        assert all(opt.sync_step(s) for s in range(8))
        post = [opt.sync_step(s) for s in range(8, 30)]
        assert not all(post)
        assert any(post)

    def test_onebit_rejects_zero_and_fp16(self, mesh_dp8):
        model = make_simple_model()
        with pytest.raises(ValueError, match="ZeRO"):
            cfg = onebit_config("OneBitAdam")
            cfg["zero_optimization"]["stage"] = 2
            DeepSpeedEngine(model, DeepSpeedConfig.load(cfg, dp_world_size=8), mesh=mesh_dp8)
        with pytest.raises(ValueError, match="fp16"):
            cfg = onebit_config("OneBitAdam")
            cfg["fp16"] = {"enabled": True}
            DeepSpeedEngine(model, DeepSpeedConfig.load(cfg, dp_world_size=8), mesh=mesh_dp8)

    def test_matches_uncompressed_adam_warmup(self, mesh_dp8):
        """During warmup (uncompressed stage) OneBitAdam must track plain Adam."""
        model = make_simple_model()
        batch = random_batches(1, 16)[0]

        ds1 = DeepSpeedConfig.load(onebit_config("OneBitAdam"), dp_world_size=8)
        e1 = DeepSpeedEngine(model, ds1, mesh=mesh_dp8, seed=0)
        cfg2 = onebit_config("Adam")
        cfg2["optimizer"]["params"].pop("freeze_step")
        ds2 = DeepSpeedConfig.load(cfg2, dp_world_size=8)
        e2 = DeepSpeedEngine(model, ds2, mesh=mesh_dp8, seed=0)

        for _ in range(3):  # all inside warmup (freeze_step=4)
            l1 = float(jax.device_get(e1.train_batch(batch)["loss"]))
            l2 = float(jax.device_get(e2.train_batch(batch)["loss"]))
        assert l1 == pytest.approx(l2, rel=2e-2)
