"""Drives a trained configuration: ``deepspeed_tpu.initialize`` and
``engine.train_batch``, steps back to back, a fresh seeded batch every step,
the loss read (``block_until_ready``) after every step as a training loop that
logs it does."""

from __future__ import annotations

import math
import time

from perfbench import reference, traffic as tg
from perfbench.context import Step
from perfbench.runners.serve import model_config  # same keys build the same GPT2Config

clock = time.perf_counter


class Runner:
    def __init__(self, ctx, seed: int, devices, span, log):
        self.ctx, self.seed, self.devices, self.span, self.log = ctx, int(seed), devices, span, log
        self.cfg = ctx.config
        self.losses = []

    def setup(self):
        import deepspeed_tpu
        from deepspeed_tpu.models import gpt2
        from deepspeed_tpu.parallel.topology import MeshSpec

        if self.ctx.traffic["loop"] != "train_steps":
            raise ValueError(f"the train runner drives train_steps, not {self.ctx.traffic['loop']!r}")
        self.mcfg = model_config(self.cfg)
        n = len(self.devices)
        mesh = MeshSpec(dp=n, devices=self.devices).build_mesh()
        t0 = clock()
        self.engine, _, _, _ = deepspeed_tpu.initialize(
            model=gpt2.make_module(self.mcfg), config=dict(self.cfg["engine"]), mesh=mesh,
            seed=self.seed % (2**31 - 1),
        )
        if self.engine.dp_world_size != n:
            raise RuntimeError(f"dp {self.engine.dp_world_size} != chips {n}")
        self.batch_size = int(self.engine.train_batch_size)
        self.seq = int(self.cfg["seq"])
        self.ctx.tokens_per_step = self.batch_size * self.seq
        self.log(f"engine {clock() - t0:.1f}s")
        t0 = clock()
        for i in range(int(self.cfg.get("warmup_steps", 2))):
            self._one(-1 - i, record=False)
        self.log(f"warm-up steps {clock() - t0:.1f}s")

    def _batch(self, step: int):
        return {"input_ids": tg.train_batch(self.seed, step, self.batch_size, self.seq, self.mcfg.vocab_size)}

    def _one(self, step: int, record=True):
        import jax

        batch = self._batch(step)
        t0 = clock()
        with self.span("perfbench.train_batch"):
            m = self.engine.train_batch(batch)
            loss = float(jax.block_until_ready(m["loss"]))
        t1 = clock()
        if record:
            self.ctx.steps.append(Step("train_batch", t0, t1, {}))
            self.ctx.step_ends.append(t1)
            self.losses.append(loss)
        return loss

    def measure(self, seconds: float, tracer):
        t_open = clock()               # the last warm-up step has just ended: a step boundary
        self.ctx.window = (t_open, t_open + seconds)
        self.ctx.step_ends.append(t_open)
        i = 0
        while True:
            rel = clock() - t_open
            tracer.tick(rel, seconds)
            if rel >= seconds:
                break
            self._one(i)
            i += 1
        tracer.stop()

    def finish(self):
        """The step after the window's last is the one the float32 reference
        checks: same parameters (the float32 masters as they stand), same
        batch, loss against loss."""
        t0, t1 = self.ctx.window
        inside = [t for t in self.ctx.step_ends if t0 <= t <= t1]
        attempted = max(0, len(inside) - 1)
        failed = sum(1 for x in self.losses[:attempted] if not math.isfinite(x))
        ok, notes = self.reference_check()
        return ok and failed == 0 and attempted > 0, attempted, failed, {"reference": notes}

    def reference_check(self, skip_layer: int = -1):
        import jax.numpy as jnp

        batch = self._batch(10**6)
        ref = float(reference.lm_loss(
            self.engine.state.params, jnp.asarray(batch["input_ids"]),
            n_head=self.mcfg.n_head, eps=float(self.mcfg.layer_norm_epsilon),
            vocab=self.mcfg.vocab_size, skip_layer=skip_layer,
        ))
        got = self._one(10**6, record=False)
        tol = float(self.cfg["reference"]["loss_tol"])
        return abs(got - ref) <= tol, {"loss": got, "reference_loss": ref, "tol": tol, "abs_diff": abs(got - ref)}
