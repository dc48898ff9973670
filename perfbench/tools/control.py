"""The control of the served configurations' ``correct`` limit: a copy of the
plain reference computed in int8, put where the program's tokens would stand.

    python3 perfbench/tools/control.py --config gpt2-xl-serve-1chip --seeds 1 2 3

For every seed: the weights the served program would hold (``init_inference``
from the seed, no server), the two warm-up prompts of ``runners/serve.py``, and
``warmup_new_tokens`` tokens chosen greedily by :func:`hidden_int8`, a copy of
``reference.hidden`` in which every matrix product (the four weight products of
a block, scores, weighted sum, the head) takes both operands rounded to int8,
per row of the left operand and per column of the right one, and is summed in
int32; norms, softmax, GELU and the residual stay float32. Then the float32
reference reads those tokens as it reads the program's
(``reference.served_gaps``): the largest gap beside the configuration's
``logit_margin``. The control has to come out as NOT correct; the benchmark's
own runs never run it. ``tests/perfbench/test_control.py`` keeps it at
``gpt2-tiny``. No timed window: one process reads every seed.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from perfbench import reference  # noqa: E402
from perfbench.reference import _f32, _gelu_tanh, _ln  # noqa: E402


def _q(x, axis):
    """Symmetric int8 along ``axis``: (codes, scale) with x ~ codes * scale."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8), scale


def dot8(a, b):
    """a [..., M, K] @ b [..., K, N] with both operands in int8 (a by row, b by
    column), summed in int32."""
    qa, sa = _q(a, -1)
    qb, sb = _q(b, -2)
    return jnp.matmul(qa, qb, preferred_element_type=jnp.int32).astype(jnp.float32) * sa * sb


def hidden_int8(params, ids, n_head: int, eps: float):
    """``reference.hidden`` with every matrix product through :func:`dot8`."""
    S = ids.shape[0]
    E = params["wte"].shape[1]
    D = E // n_head
    h = params["wte"][ids].astype(jnp.float32) + params["wpe"][:S].astype(jnp.float32)
    causal = jnp.tril(jnp.ones((S, S), bool))

    def block(h, lp):
        lp = _f32(lp)
        x = _ln(h, lp["ln_1"]["scale"], lp["ln_1"]["bias"], eps)
        qkv = dot8(x, lp["attn"]["c_attn_w"]) + lp["attn"]["c_attn_b"]
        q, k, v = (t.reshape(S, n_head, D).transpose(1, 0, 2) for t in jnp.split(qkv, 3, axis=-1))  # [H, S, D]
        s = dot8(q, k.transpose(0, 2, 1)) / jnp.sqrt(jnp.float32(D))
        s = jnp.where(causal[None], s, -jnp.inf)
        o = dot8(jax.nn.softmax(s, axis=-1), v).transpose(1, 0, 2).reshape(S, E)
        a = h + dot8(o, lp["attn"]["c_proj_w"]) + lp["attn"]["c_proj_b"]
        x = _ln(a, lp["ln_2"]["scale"], lp["ln_2"]["bias"], eps)
        m = _gelu_tanh(dot8(x, lp["mlp"]["c_fc_w"]) + lp["mlp"]["c_fc_b"])
        return a + dot8(m, lp["mlp"]["c_proj_w"]) + lp["mlp"]["c_proj_b"], None

    h, _ = jax.lax.scan(block, h, params["blocks"])
    return _ln(h, params["ln_f"]["scale"].astype(jnp.float32), params["ln_f"]["bias"].astype(jnp.float32), eps)


def logits_int8(params, ids, n_head, eps, vocab):
    """[T, vocab]: the tied head through :func:`dot8` as well."""
    return dot8(hidden_int8(params, ids, n_head, eps), params["wte"].astype(jnp.float32).T)[:, :vocab]


@functools.partial(jax.jit, static_argnames=("n_head", "eps", "vocab"))
def next_token_int8(params, ids, n, *, n_head, eps, vocab):
    """Greedy next token after the first ``n`` of the padded ``ids``."""
    return jnp.argmax(logits_int8(params, ids, n_head, eps, vocab)[n - 1])


def control_gap(params, prompt, new_tokens: int, *, n_head, eps, vocab, n_positions):
    """The control's tokens after ``prompt`` and the float32 reference's
    verdict on them: largest gap, positions where it is above 0, logit std,
    and the largest error of an int8 logit at those positions."""
    n_prompt = len(prompt)
    T = min(-(-(n_prompt + new_tokens) // 128) * 128, n_positions)
    ids = np.zeros((T,), np.int32)
    ids[:n_prompt] = prompt
    for n in range(n_prompt, n_prompt + new_tokens):
        ids[n] = int(next_token_int8(params, jnp.asarray(ids), n, n_head=n_head, eps=eps, vocab=vocab))
    gap, std = reference.served_gaps(params, jnp.asarray(ids), n_prompt, n_prompt + new_tokens,
                                     n_head=n_head, eps=eps, vocab=vocab)
    gap = np.asarray(gap)
    served = slice(n_prompt - 1, n_prompt + new_tokens - 1)
    return {"gap": float(gap.max()), "off_argmax": int((gap > 0).sum()), "logit_std": float(np.asarray(std)[served].mean()),
            "logit_err": float(_logit_err(params, jnp.asarray(ids), n_head=n_head, eps=eps, vocab=vocab)[served].max())}


@functools.partial(jax.jit, static_argnames=("n_head", "eps", "vocab"))
def _logit_err(params, ids, *, n_head, eps, vocab):
    """Per position, the largest |int8 logit - float32 logit|: what the
    rounding did, whether or not it changed a token."""
    lo = logits_int8(params, ids, n_head, eps, vocab)
    return jnp.max(jnp.abs(lo - reference._logits(params, reference.hidden(params, ids, n_head, eps), vocab)), axis=-1)


def main(argv=None) -> int:
    import deepspeed_tpu
    from deepspeed_tpu.models import gpt2
    from perfbench import run
    from perfbench.manifest import Manifest
    from perfbench.runners.serve import model_config

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cfg = Manifest(_ROOT).config(args.config)
    run.setup_jax_cache()
    run.check_device(1, require_tpu=True)
    mcfg = model_config(cfg)
    kw = dict(n_head=mcfg.n_head, eps=float(mcfg.layer_norm_epsilon), vocab=mcfg.vocab_size)
    margin = float(cfg["reference"]["logit_margin"])
    lens = sorted({min(cfg[k], cfg["serving"]["max_prompt_len"]) for k in ("warmup_short_prompt", "warmup_long_prompt")})
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[cfg["dtype"]]
    for seed in args.seeds:
        engine = deepspeed_tpu.init_inference(model=gpt2.make_module(mcfg), dtype=dtype, seed=seed % (2**31 - 1))
        rng = np.random.default_rng([seed % 2**63, 9])   # the warm-up prompts of runners/serve.py
        rows = [control_gap(engine.params, rng.integers(0, mcfg.vocab_size, n).astype(np.int32),
                            int(cfg["warmup_new_tokens"]), n_positions=mcfg.n_positions, **kw) for n in lens]
        worst = max(r["gap"] for r in rows)
        print(json.dumps({"seed": seed, "control_max_logit_gap": worst, "margin": margin, "correct": worst <= margin,
                          "positions_off_the_argmax": sum(r["off_argmax"] for r in rows),
                          "positions": len(lens) * int(cfg["warmup_new_tokens"]),
                          "largest_logit_error": max(r["logit_err"] for r in rows), "per_prompt": rows}), flush=True)
        del engine
    return 0


if __name__ == "__main__":
    sys.exit(main())
