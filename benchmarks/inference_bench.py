"""Inference benchmark: GPT-2 prefill+decode and BERT-large encoder on TPU.

BASELINE.md's inference row ("BERT-large inference, kernel injection →
Pallas: parity outputs, fused decode path") has the parity tests in
tests/unit/test_inference.py / test_model_zoo.py; this script adds the
measured numbers. One JSON line per mode:

    python benchmarks/inference_bench.py decode   # gpt2-medium KV-cache decode
    python benchmarks/inference_bench.py bert     # bert-large encoder fwd

- "decode": batch 8, prompt 128, 128 greedy tokens through the compiled
  prefill + lax.scan single-token decode path (Pallas decode-attention
  kernel on TPU). Reports prefill ms and sustained decode tokens/sec.
- "bert": batch 8, seq 384 (S % 128 == 0 so the unmasked encoder rides the
  Pallas bidirectional flash dispatcher), forward() sequences/sec and
  ms/sequence.

Weights are random-init (throughput does not depend on values); shapes are
the published model shapes.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def _decode_bench():
    import jax

    import deepspeed_tpu
    from deepspeed_tpu.models import gpt2

    on_tpu = jax.default_backend() not in ("cpu",)
    name = os.environ.get("BENCH_INF_MODEL", "gpt2-medium" if on_tpu else "gpt2-tiny")
    B = int(os.environ.get("BENCH_INF_BATCH", "8"))
    prompt = int(os.environ.get("BENCH_INF_PROMPT", "128"))
    new = int(os.environ.get("BENCH_INF_NEW", "128" if on_tpu else "8"))

    cfg = gpt2.get_config(name, n_positions=max(1024, prompt + new))
    eng = deepspeed_tpu.init_inference(model=gpt2.make_module(cfg))
    rs = np.random.RandomState(0)
    ids = rs.randint(0, cfg.vocab_size, size=(B, prompt)).astype(np.int32)

    out = eng.generate(ids, max_new_tokens=new)  # compile + warm
    assert out.shape == (B, prompt + new)
    t0 = time.perf_counter()
    iters = 3
    for _ in range(iters):
        out = eng.generate(ids, max_new_tokens=new)
    dt = (time.perf_counter() - t0) / iters

    # prefill-only timing: 1 new token isolates prompt processing
    eng.generate(ids, max_new_tokens=1)
    t0 = time.perf_counter()
    for _ in range(iters):
        eng.generate(ids, max_new_tokens=1)
    dt_prefill = (time.perf_counter() - t0) / iters

    # BENCH_PROFILE=<dir>: xplane trace of one generate call for ms/token
    # attribution (weights stream vs cache reads vs dispatch overhead —
    # the r4 capture's 5.46 ms/token is ~16% of pure weight-streaming
    # bandwidth, so something besides HBM is the limit)
    prof_dir = os.environ.get("BENCH_PROFILE")
    if prof_dir:
        with jax.profiler.trace(prof_dir):
            out = eng.generate(ids, max_new_tokens=new)
            # block INSIDE the trace: async-dispatched device work outside
            # the context would truncate the captured xplane (ADVICE r4)
            jax.block_until_ready(out)

    decode_tok_s = B * new / max(dt - dt_prefill, 1e-9)
    # decode is weight-streaming-bound: the floor per token is model bytes /
    # HBM bandwidth. v5e ≈ 819 GB/s vs A100-80G ≈ 2039 GB/s, so per-chip
    # bandwidth parity vs an A100 decode number means ≥ 0.40× of it.
    n_params = 12 * cfg.n_layer * cfg.n_embd**2 + cfg.vocab_size * cfg.n_embd
    from deepspeed_tpu.telemetry.introspect import chip_peak

    hbm_gbs = chip_peak().hbm_bytes_per_s / 1e9
    bw_floor_ms = n_params * 2 / (hbm_gbs * 1e9) * 1e3  # bf16 weights
    ms_tok = (dt - dt_prefill) * 1e3 / new
    print(json.dumps({
        "metric": f"kv-decode tokens/sec {name} b{B} prompt{prompt} new{new}",
        "value": round(decode_tok_s, 1),
        "unit": "tokens/sec",
        "prefill_ms": round(dt_prefill * 1e3, 2),
        "e2e_ms": round(dt * 1e3, 2),
        "ms_per_token": round(ms_tok, 3),
        "weight_stream_floor_ms": round(bw_floor_ms, 3),
        "pct_of_bw_bound": round(100 * bw_floor_ms / max(ms_tok, 1e-9), 1),
        "hbm_gbs_assumed": hbm_gbs,
        "a100_bw_ratio": round(hbm_gbs / 2039.0, 3),
        "batch": B,
    }))


def _bert_bench():
    import jax

    import deepspeed_tpu
    from deepspeed_tpu.models import bert

    on_tpu = jax.default_backend() not in ("cpu",)
    name = os.environ.get("BENCH_INF_MODEL", "bert-large" if on_tpu else "bert-tiny")
    B = int(os.environ.get("BENCH_INF_BATCH", "8"))
    S = int(os.environ.get("BENCH_INF_SEQ", "384" if on_tpu else "128"))

    cfg = bert.get_config(name, n_positions=max(512, S))
    eng = deepspeed_tpu.init_inference(model=bert.make_module(cfg))
    rs = np.random.RandomState(0)
    batch = {"input_ids": rs.randint(0, cfg.vocab_size, size=(B, S)).astype(np.int32)}

    import jax.numpy as jnp

    from benchmarks.device_timing import chained_ms

    out = eng.forward(batch)  # compile + warm
    jax.block_until_ready(out)
    iters = 20 if on_tpu else 3

    # chained-scan timing (see device_timing.py): the ids ride the carry
    # through a runtime-dependent no-op roll so the forward is neither
    # loop-invariant (hoistable) nor dead — every iteration must execute.
    def step(c):
        ids, acc = c
        s = sum(
            jnp.sum(l).astype(jnp.float32)
            for l in jax.tree.leaves(eng.forward({"input_ids": ids}))
        )
        shift = (s > jnp.float32(3e38)).astype(jnp.int32)  # always 0 at runtime
        return jnp.roll(ids, shift, axis=0), acc + s

    ids0 = jnp.asarray(batch["input_ids"])
    dt = chained_ms(step, (ids0, jnp.float32(0.0)), iters) / 1e3

    # encoder forward is compute-bound: report achieved model TFLOP/s and
    # the utilization of the chip's bf16 peak. v5e peak 197 vs A100 fp16
    # dense 312 TFLOP/s: per-chip compute parity means ≥ 0.63× an A100
    # sequences/sec number at equal utilization.
    E, Lz = cfg.n_embd, cfg.n_layer
    flops_per_seq = 2.0 * 12 * Lz * E * E * S + 4.0 * Lz * S * S * E
    from deepspeed_tpu.telemetry.introspect import chip_peak

    peak = chip_peak().peak_flops
    achieved = flops_per_seq * B / dt
    print(json.dumps({
        "metric": f"encoder seq/sec {name} b{B} seq{S}",
        "value": round(B / dt, 1),
        "unit": "sequences/sec",
        "ms_per_batch": round(dt * 1e3, 2),
        "ms_per_seq": round(dt * 1e3 / B, 3),
        "model_tflops": round(achieved / 1e12, 2),
        "util_of_peak": round(achieved / peak, 4),
        "a100_compute_ratio": round(peak / 1e12 / 312.0, 3),
        "batch": B,
        "seq": S,
    }))


if __name__ == "__main__":
    from deepspeed_tpu.utils.jax_env import setup_compile_cache

    setup_compile_cache()
    mode = sys.argv[1] if len(sys.argv) > 1 else "decode"
    {"decode": _decode_bench, "bert": _bert_bench}[mode]()
