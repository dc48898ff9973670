"""Drives a served ``bailing_hybrid`` configuration (Ling-3.0-flash, the language
model of Ling-3.0-flash-VL; one chip's share of a cut of its depth):
``runners/serve.py``'s loops, stamps and counts as they are, and the
``qwen3_next`` runner's warm-up and three-limit reference check
(``runners/serve_qwen3_next.Runner``: the same state pools and hand-overs, so
the same requests and the same rule), with this family's model and reference.
The configuration file holds the published keys
(``models/ling3.Ling3Config.from_dict`` reads them) and the share:
``num_experts`` held of ``published.num_experts`` (one whole routing group),
``expert_share``, the ``vocab_size`` rows held, the depth held.
"""

from __future__ import annotations

import numpy as np

from perfbench import reference_ling3 as reference
from perfbench.manifest import ManifestError
from perfbench.runners import serve_qwen3_next
from perfbench.runners.serve import clock
from perfbench.runners.serve_qwen3_next import clear, handed  # noqa: F401  (the limits' two rules, as they are)


def model_config(cfg: dict):
    try:
        from deepspeed_tpu.models import ling3
    except ImportError as e:   # a checkout from before the family was added
        raise ManifestError(f"this checkout's program cannot run model_type {cfg['model_type']!r}: {e}") from e
    return ling3.Ling3Config.from_dict(cfg, **cfg.get("model_overrides", {}))


class Runner(serve_qwen3_next.Runner):
    def setup(self):
        import jax.numpy as jnp

        self.mcfg = model_config(self.cfg)
        import deepspeed_tpu
        from deepspeed_tpu.models import ling3

        dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[self.cfg["dtype"]]
        t0 = clock()
        self.engine = deepspeed_tpu.init_inference(
            model=ling3.make_module(self.mcfg), dtype=dtype, seed=self.seed % (2**31 - 1)
        )
        self.srv = self.engine.serve(dict(self.sv), clock=clock)
        self.srv.executable_names()   # compiles (or loads from the cache) the program set
        self.log(f"engine+programs {clock() - t0:.1f}s")
        # the qwen3_next runner's warm-up (its notes): a short prompt through the
        # whole-prompt program, the others through chunks whose LAST has one or
        # two rows, then decode steps on the state those left; they are also the
        # requests the float32 reference checks
        lens = sorted({min(n, self.sv["max_prompt_len"]) for n in
                       (self.cfg["warmup_short_prompt"], self.cfg["warmup_long_prompt"], *self.cfg.get("warmup_edge_prompts", ()))})
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
        """→ a request: ``gap [n_new]`` of its served positions in order,
        ``tie [n_valid]`` (the smallest selection margin over the expert
        layers, every position from 0: ``reference_ling3._select``),
        ``n_prompt``, the logits' mean ``std``."""
        import jax.numpy as jnp

        arch = reference.Arch.from_config(self.cfg)
        rows = max((len(r.tokens) for r in self.warm), default=0)
        T = -(-max((len(r.prompt) + rows for r in self.warm), default=0) // 256) * 256   # one length: one program
        out = []
        for r in self.warm:
            ids = np.concatenate([np.asarray(r.prompt, np.int32), np.asarray(r.tokens, np.int32)])
            n_valid, n_prompt = len(ids), len(r.prompt)
            padded = np.zeros((T,), np.int32)
            padded[:n_valid] = ids
            gap, std, ties = reference.served_gaps(self.engine.params, jnp.asarray(padded), jnp.int32(n_prompt),
                                                   jnp.int32(n_valid), arch=arch, rows=rows, skip=skip)
            n_new = n_valid - n_prompt
            out.append({"gap": np.asarray(gap)[:n_new], "tie": np.asarray(ties).min(axis=0)[:n_valid],
                        "n_prompt": n_prompt, "std": float(np.asarray(std)[:n_new].mean())})
        return out
