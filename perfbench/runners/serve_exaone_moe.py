"""Drives a served ``exaone_moe`` configuration (K-EXAONE, one chip's share):
``runners/serve.py``'s loops, stamps and counts as they are, with this
family's model, set-up and reference check. The configuration file holds the
published keys (``models/exaone_moe.ExaoneMoEConfig.from_dict`` reads them)
and the share: ``num_experts`` held of ``published.num_experts``,
``expert_share``, the ``vocab_size`` rows held.
"""

from __future__ import annotations

import numpy as np

from perfbench import reference_exaone_moe as reference
from perfbench.manifest import ManifestError
from perfbench.runners import serve
from perfbench.runners.serve import clock


def model_config(cfg: dict):
    try:
        from deepspeed_tpu.models import exaone_moe
    except ImportError as e:   # a checkout from before the family was added
        raise ManifestError(f"this checkout's program cannot run model_type {cfg['model_type']!r}: {e}") from e
    return exaone_moe.ExaoneMoEConfig.from_dict(cfg, **cfg.get("model_overrides", {}))


class Runner(serve.Runner):
    def setup(self):
        import jax.numpy as jnp

        self.mcfg = model_config(self.cfg)
        import deepspeed_tpu
        from deepspeed_tpu.models import exaone_moe

        dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[self.cfg["dtype"]]
        t0 = clock()
        self.engine = deepspeed_tpu.init_inference(
            model=exaone_moe.make_module(self.mcfg), dtype=dtype, seed=self.seed % (2**31 - 1)
        )
        self.srv = self.engine.serve(dict(self.sv), clock=clock)
        self.srv.executable_names()   # compiles (or loads from the cache) the program set
        self.log(f"engine+programs {clock() - t0:.1f}s")
        # warm-up: the short prompt through the whole-prompt program, the long
        # one through the chunk program and past a wrap of the window rings;
        # they are also the two requests the float32 reference checks
        lens = sorted({min(self.cfg["warmup_short_prompt"], self.sv["max_prompt_len"]),
                       min(self.cfg["warmup_long_prompt"], self.sv["max_prompt_len"])})
        rng = np.random.default_rng([self.seed % 2**63, 9])
        t0 = clock()
        self.warm = [
            self.srv.submit(rng.integers(0, self.mcfg.vocab_size, n).astype(np.int32),
                            max_new_tokens=int(self.cfg["warmup_new_tokens"]), seed=i)
            for i, n in enumerate(lens)
        ]
        self.srv.run()
        self.log(f"warm-up requests {clock() - t0:.1f}s (prompts {lens})")

    def reference_check(self, skip: str = ""):
        """Teacher-forced float32 reference on the two warm-up requests. Per
        served position, the gap: the reference's largest logit less its logit
        of the served token (0 where the program chose the reference's
        argmax). Two limits, and a run is correct inside both: the LARGEST gap
        within ``logit_margin`` (a wrong mask or wrong positions move single
        logits by several std), and the MEAN gap over all served positions
        within ``mean_gap_limit`` (a fault as small as one layer's routed
        experts left out, or a lower precision, flips more near-ties than bf16
        does, each by little: the largest gap cannot tell them from bf16 at any
        margin, the mean over some hundreds of positions can; PERF.md, PR 32).
        ``skip`` is for the controls."""
        import jax.numpy as jnp

        ref = self.cfg["reference"]
        margin, mean_limit = float(ref["logit_margin"]), float(ref.get("mean_gap_limit", "inf"))
        arch = reference.Arch.from_config(self.cfg)
        gaps, stds = [], []
        for r in self.warm:
            ids = np.concatenate([np.asarray(r.prompt, np.int32), np.asarray(r.tokens, np.int32)])
            n_valid, n_prompt = len(ids), len(r.prompt)
            padded = np.zeros((-(-n_valid // 128) * 128,), np.int32)
            padded[:n_valid] = ids
            gap, std = reference.served_gaps(
                self.engine.params, jnp.asarray(padded), n_prompt, n_valid, arch=arch, skip=skip
            )
            gaps.append(np.asarray(gap)[n_prompt - 1: n_valid - 1])
            stds.append(float(np.asarray(std)[n_prompt - 1: n_valid - 1].mean()))
        gaps = np.concatenate(gaps) if gaps else np.zeros((0,))
        worst, mean = (float(gaps.max()), float(gaps.mean())) if len(gaps) else (0.0, 0.0)
        ok = len(self.warm) > 0 and all(len(r.tokens) > 0 for r in self.warm) and worst <= margin and mean <= mean_limit
        return ok, {"max_logit_gap": worst, "margin": margin, "mean_logit_gap": mean, "mean_gap_limit": mean_limit,
                    "off_argmax": int((gaps > 0).sum()), "positions": int(len(gaps)), "logit_std": stds}
