"""Drives a served ``qwen3_next`` configuration (Qwen3-Next-80B-A3B, one chip's
share of a cut of its depth): ``runners/serve.py``'s loops, stamps and counts
as they are, with this family's model, set-up and reference check. The
configuration file holds the published keys
(``models/qwen3_next.Qwen3NextConfig.from_dict`` reads them) and the share:
``num_experts`` held of ``published.num_experts``, ``expert_share``, the
``vocab_size`` rows held, the depth held.
"""

from __future__ import annotations

import numpy as np

from perfbench import reference_qwen3_next as reference
from perfbench.manifest import ManifestError
from perfbench.runners import serve
from perfbench.runners.serve import clock


def model_config(cfg: dict):
    try:
        from deepspeed_tpu.models import qwen3_next
    except ImportError as e:   # a checkout from before the family was added
        raise ManifestError(f"this checkout's program cannot run model_type {cfg['model_type']!r}: {e}") from e
    return qwen3_next.Qwen3NextConfig.from_dict(cfg, **cfg.get("model_overrides", {}))


class Runner(serve.Runner):
    def setup(self):
        import jax.numpy as jnp

        self.mcfg = model_config(self.cfg)
        import deepspeed_tpu
        from deepspeed_tpu.models import qwen3_next

        dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[self.cfg["dtype"]]
        t0 = clock()
        self.engine = deepspeed_tpu.init_inference(
            model=qwen3_next.make_module(self.mcfg), dtype=dtype, seed=self.seed % (2**31 - 1)
        )
        self.srv = self.engine.serve(dict(self.sv), clock=clock)
        self.srv.executable_names()   # compiles (or loads from the cache) the program set
        self.log(f"engine+programs {clock() - t0:.1f}s")
        # warm-up: the short prompt through the whole-prompt program (shorter
        # than its bucket: the state and the convolution's rows are taken at
        # the true length), the others through the chunk program with a LAST
        # chunk of one or two rows and sub-chunks that end inside a chunk
        # (state handed from chunk to chunk, and the first served logits read
        # off a state another call left), then decode steps on that state;
        # they are also the requests the float32 reference checks
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

    def reference_check(self, skip: str = ""):
        """Teacher-forced float32 reference (the delta rule token by token) on
        the warm-up requests. Per served position, the gap: the reference's
        largest logit less its logit of the served token (0 where the program
        chose the reference's argmax). Three numbers, and a run is correct
        inside all three limits.

        The LARGEST gap within ``logit_margin`` and the MEAN of the gaps capped
        at ``gap_cap`` within ``mean_gap_limit``, both over the positions the
        tie rule keeps (:func:`clear`, a rule on the reference alone: the K-EXAONE
        and ZAYA cells' rule): top-10 of 512 has a near-tie at many positions,
        a sound bf16 run falls the other way there, and a held expert in or
        out moves the logits by more than any rounding, which says nothing of
        the program.

        The FOURTH largest gap over the positions that immediately follow a
        HAND-OVER of the state between calls of different kinds (:func:`handed`;
        every one of them, the tie rule does not apply) within
        ``handover_margin``: a program that loses or misplaces the state or the
        convolution's rows at a hand-over moves every request that crosses it.
        ``skip`` is for the controls."""
        ref = self.cfg["reference"]
        margin, mean_limit, hand_limit = float(ref["logit_margin"]), float(ref["mean_gap_limit"]), float(ref["handover_margin"])
        cap, tie = float(ref["gap_cap"]), float(ref["tie_margin"])
        reads = self.served_gaps(skip)
        gaps = np.concatenate([r["gap"] for r in reads] or [np.zeros((0,))])
        kept = np.concatenate([clear(r, tie) for r in reads] or [np.zeros((0,), bool)])
        edges = np.sort(gaps[np.concatenate([handed(r, self.sv["prefill_chunk_tokens"]) for r in reads] or [kept])])
        worst, mean = (float(gaps[kept].max()), float(np.minimum(gaps[kept], cap).mean())) if kept.any() else (0.0, 0.0)
        edge = float(edges[-4]) if len(edges) > 3 else 0.0
        ok = bool(len(self.warm) > 0 and all(len(r.tokens) > 0 for r in self.warm) and kept.any()
                  and worst <= margin and mean <= mean_limit and edge <= hand_limit)
        return ok, {"max_logit_gap": worst, "margin": margin, "mean_logit_gap": mean, "mean_gap_limit": mean_limit, "gap_cap": cap,
                    "handover_gap": edge, "handover_margin": hand_limit, "handover_largest": float(edges[-1]) if len(edges) else 0.0,
                    "handover_positions": int(len(edges)), "tie_margin": tie, "left_out": int((~kept).sum()),
                    "positions": int(len(gaps)), "off_argmax": int((gaps[kept] > 0).sum()), "logit_std": [r["std"] for r in reads]}

    def served_gaps(self, skip: str = ""):
        """→ a request: ``gap [n_new]`` of its served positions in order,
        ``tie [n_valid]`` (the smallest pick margin over the layers, every
        position from 0), ``n_prompt``, the logits' mean ``std``."""
        import jax.numpy as jnp

        arch = reference.Arch.from_config(self.cfg)
        rows = max((len(r.tokens) for r in self.warm), default=0)
        T = -(-max((len(r.prompt) + rows for r in self.warm), default=0) // 128) * 128   # one length: one program
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


def clear(read: dict, tie_margin: float):
    """Of a request's served positions, those the tie rule keeps ``[n_new]``:
    in no layer was the reference's own pick a near-tie that moves this chip's
    part (the tenth largest argument of the router's softmax less the
    eleventh under ``tie_margin``, one of the two a held expert), at the
    position or at one of the three rows before it (the rows its
    convolutions read)."""
    near = read["tie"] < tie_margin
    near = near | np.roll(near, 1) | np.roll(near, 2) | np.roll(near, 3)     # a prompt is longer than three rows: nothing wraps into a served position
    return ~near[read["n_prompt"] - 1: read["n_prompt"] - 1 + len(read["gap"])]


def handed(read: dict, chunk: int):
    """Of a request's served positions ``[n_new]``, those that immediately
    follow a hand-over of the state between calls of different kinds."""
    n, out = read["n_prompt"], np.zeros((len(read["gap"]),), bool)
    out[1:4] = True                                       # the first three rows the decode step computes
    out[0] = n > chunk and (n - 1) % chunk < 3            # the last row of a prompt, if among its last chunk's first three
    return out
