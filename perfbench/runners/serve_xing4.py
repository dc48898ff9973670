"""Drives a served ``xing4_0`` configuration (Xing4.0-29B-A4B, one chip's
share): ``runners/serve.py``'s loops, stamps and counts and
``runners/serve_mistral4.py``'s two-limit reference check as they are, with
this family's model, set-up and reference. The configuration file holds the
published keys (``models/xing4.Xing4Config.from_dict`` reads them) and the
share: ``n_routed_experts`` held of ``published.n_routed_experts``,
``expert_share``, the ``vocab_size`` rows held.
"""

from __future__ import annotations

import numpy as np

from perfbench import reference_xing4 as reference
from perfbench.manifest import ManifestError
from perfbench.runners import serve_mistral4
from perfbench.runners.serve import clock


def model_config(cfg: dict):
    try:
        from deepspeed_tpu.models import xing4
    except ImportError as e:   # a checkout from before the family was added
        raise ManifestError(f"this checkout's program cannot run model_type {cfg['model_type']!r}: {e}") from e
    return xing4.Xing4Config.from_dict(cfg, **cfg.get("model_overrides", {}))


class Runner(serve_mistral4.Runner):
    """``serve_mistral4.Runner``'s ``reference_check`` as it is (the largest gap
    within ``logit_margin``, the mean gap, capped where the configuration gives
    a ``gap_cap``, within ``mean_gap_limit``; the configuration's
    ``reference.why`` has the readings the limits lie between), over this
    family's model, warm-up and reference."""

    def setup(self):
        import jax.numpy as jnp

        self.mcfg = model_config(self.cfg)
        import deepspeed_tpu
        from deepspeed_tpu.models import xing4

        dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[self.cfg["dtype"]]
        t0 = clock()
        self.engine = deepspeed_tpu.init_inference(
            model=xing4.make_module(self.mcfg), dtype=dtype, seed=self.seed % (2**31 - 1)
        )
        self.srv = self.engine.serve(dict(self.sv), clock=clock)
        self.srv.executable_names()   # compiles (or loads from the cache) the program set
        self.log(f"engine+programs {clock() - t0:.1f}s")
        # warm-up: the short prompt through the whole-prompt program, the long
        # one through the chunk program across four chunks; they are also the
        # two requests the float32 reference checks
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
