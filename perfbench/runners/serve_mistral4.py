"""Drives a served ``mistral4`` configuration (Mistral Small 4, one chip's
share): ``runners/serve.py``'s loops, stamps and counts as they are, with this
family's model, set-up and reference check. The configuration file holds the
published keys (``models/mistral4.Mistral4Config.from_dict`` reads them) and
the share: ``n_routed_experts`` held of ``published.n_routed_experts``,
``expert_share``, the ``vocab_size`` rows held.
"""

from __future__ import annotations

import numpy as np

from perfbench import reference_mistral4 as reference
from perfbench.manifest import ManifestError
from perfbench.runners import serve
from perfbench.runners.serve import clock


def model_config(cfg: dict):
    try:
        from deepspeed_tpu.models import mistral4
    except ImportError as e:   # a checkout from before the family was added
        raise ManifestError(f"this checkout's program cannot run model_type {cfg['model_type']!r}: {e}") from e
    return mistral4.Mistral4Config.from_dict(cfg, **cfg.get("model_overrides", {}))


class Runner(serve.Runner):
    def setup(self):
        import jax.numpy as jnp

        self.mcfg = model_config(self.cfg)
        import deepspeed_tpu
        from deepspeed_tpu.models import mistral4

        dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[self.cfg["dtype"]]
        t0 = clock()
        self.engine = deepspeed_tpu.init_inference(
            model=mistral4.make_module(self.mcfg), dtype=dtype, seed=self.seed % (2**31 - 1)
        )
        self.srv = self.engine.serve(dict(self.sv), clock=clock)
        self.srv.executable_names()   # compiles (or loads from the cache) the program set
        self.log(f"engine+programs {clock() - t0:.1f}s")
        # warm-up: the short prompt through the whole-prompt program, the long
        # one through the chunk program (nine chunks, and past the original
        # context, so that the query's position scale is not 1 on compared
        # positions); they are also the two requests the float32 reference checks
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
        """Teacher-forced float32 reference (the EXPANDED equations; the
        program computes absorbed) on the two warm-up requests. Per served
        position, the gap: the reference's largest logit less its logit of the
        served token (0 where the program chose the reference's argmax). Two
        limits, and a run is correct inside both: the LARGEST gap within
        ``logit_margin`` (a wrong rotary moves whole logits), and the MEAN over
        all served positions of the gap CAPPED at ``gap_cap`` within
        ``mean_gap_limit``. Why capped: with every layer an expert layer and no
        dense layer before them, a sound bf16 run differs from the float32
        reference in a routed expert at a few positions in some hundreds (a
        near-tie of the top-k falls the other way), and there the logits move
        by whole units; those few sizes make the plain mean swing 2.5 times
        between seeds, while what a fault as small as one layer's routed
        experts, a query scale of 1 or a lower precision raises is the NUMBER
        of positions with a gap of 0.05 to 0.25, which the capped mean counts
        (the configuration's ``reference.why`` has the readings; PERF.md, PR
        34). ``skip`` is for the controls."""
        ref = self.cfg["reference"]
        margin, mean_limit = float(ref["logit_margin"]), float(ref.get("mean_gap_limit", "inf"))
        cap = float(ref.get("gap_cap", "inf"))
        gaps, stds = self.served_gaps(skip)
        worst, mean, raw = (float(gaps.max()), float(np.minimum(gaps, cap).mean()), float(gaps.mean())) if len(gaps) else (0.0, 0.0, 0.0)
        ok = len(self.warm) > 0 and all(len(r.tokens) > 0 for r in self.warm) and worst <= margin and mean <= mean_limit
        return ok, {"max_logit_gap": worst, "margin": margin, "mean_logit_gap": mean, "mean_gap_limit": mean_limit,
                    "gap_cap": cap, "uncapped_mean_logit_gap": raw,
                    "off_argmax": int((gaps > 0).sum()), "positions": int(len(gaps)), "logit_std": stds}

    def served_gaps(self, skip: str = ""):
        """→ (the gaps of all served positions of the warm-up requests, in
        order; the logits' mean std a request)."""
        import jax.numpy as jnp

        arch = reference.Arch.from_config(self.cfg)
        gaps, stds = [], []
        for r in self.warm:
            ids = np.concatenate([np.asarray(r.prompt, np.int32), np.asarray(r.tokens, np.int32)])
            n_valid, n_prompt = len(ids), len(r.prompt)
            padded = np.zeros((-(-n_valid // 128) * 128,), np.int32)
            padded[:n_valid] = ids
            first = (n_prompt - 1) // 128 * 128   # the head from the served rows' block on
            gap, std = reference.served_gaps(
                self.engine.params, jnp.asarray(padded), n_prompt, n_valid, arch=arch, skip=skip, first=first
            )
            gaps.append(np.asarray(gap)[n_prompt - 1 - first: n_valid - 1 - first])
            stds.append(float(np.asarray(std)[n_prompt - 1 - first: n_valid - 1 - first].mean()))
        return (np.concatenate(gaps) if gaps else np.zeros((0,))), stds
