"""ZeRO-Offload + ZeRO-Infinity benchmark (BASELINE rows: ">30 TFLOPS
sustained on one device with CPU offload" and "max params/chip under
ZeRO-Infinity", docs/_pages/training.md:293).

Two configs, one JSON line each (run on the TPU chip):

  python benchmarks/offload_bench.py offload    # gpt2-xl, host Adam tier
  python benchmarks/offload_bench.py infinity   # largest streamed decoder

- "offload": the full 1.5B GPT-2-XL trains on ONE chip (fp32 master + Adam
  moments in host DRAM; bf16 compute on device). Sustained model-TFLOPS =
  analytic train flops / wall time; gradient accumulation amortizes the
  host optimizer pass the same way the reference's optimal-offload schedule
  does. This host has ONE CPU core (the reference's 30 TFLOPS point assumed
  a many-core AVX512 host), so gas is the honest lever, reported in the line.
- "infinity": the largest GPT-class model whose fp32 master + moments fit
  host DRAM (~125 GB here) trains with block streaming on one 16 GB chip.
  Primary metric: params/chip (the DDP OOM bound is ~1.4B params — BASELINE).
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def train_flops_per_token(L, h, vocab, S):
    return 3.0 * (2.0 * (12.0 * L * h * h + vocab * h) + 4.0 * L * S * h)


def bench_offload():
    import jax

    from deepspeed_tpu.models import gpt2
    from deepspeed_tpu.parallel.topology import MeshSpec
    from deepspeed_tpu.runtime.config import DeepSpeedConfig
    from deepspeed_tpu.runtime.engine import DeepSpeedEngine

    model = os.environ.get("BENCH_MODEL", "gpt2-xl")
    seq = int(os.environ.get("BENCH_SEQ", "1024"))
    micro = int(os.environ.get("BENCH_MICRO", "4"))
    gas = int(os.environ.get("BENCH_GAS", "8"))
    steps = int(os.environ.get("BENCH_STEPS", "3"))

    cfg = gpt2.get_config(model, n_positions=seq, remat=True)
    ds = DeepSpeedConfig.load(
        {
            "train_micro_batch_size_per_gpu": micro,
            "gradient_accumulation_steps": gas,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
            "zero_optimization": {
                "stage": 2,
                "offload_optimizer": {"device": "cpu"},
            },
            "gradient_clipping": 1.0,
            "bf16": {"enabled": True},
            "steps_per_print": 10**9,
        },
        dp_world_size=1,
    )
    mesh = MeshSpec(dp=1, devices=jax.devices()[:1]).build_mesh()
    engine = DeepSpeedEngine(gpt2.make_module(cfg), ds, mesh=mesh, seed=0)
    rs = np.random.RandomState(0)
    batch = {
        "input_ids": rs.randint(0, cfg.vocab_size, (engine.train_batch_size, seq)).astype(np.int32)
    }
    m = engine.train_batch(batch)  # compile + warm (device grads + host Adam)
    float(m["loss"])
    t0 = time.perf_counter()
    for _ in range(steps):
        m = engine.train_batch(batch)
        float(m["loss"])
    dt = (time.perf_counter() - t0) / steps

    tokens_per_step = engine.train_batch_size * seq
    fpt = train_flops_per_token(cfg.n_layer, cfg.n_embd, cfg.vocab_size, seq)
    tflops = fpt * tokens_per_step / dt / 1e12
    n_params = 12 * cfg.n_layer * cfg.n_embd**2 + cfg.vocab_size * cfg.n_embd
    from deepspeed_tpu.telemetry.introspect import chip_peak

    peak_tflops = chip_peak().peak_flops / 1e12
    print(json.dumps({
        "metric": f"ZeRO-Offload sustained model TFLOPS {model} seq{seq} micro{micro} gas{gas} (1 chip, host Adam)",
        "value": round(tflops, 2),
        "unit": "model TFLOPS/chip",
        "vs_baseline": round(tflops / 30.0, 3),  # reference >30 TFLOPS claim
        "params": n_params,
        "step_ms": round(dt * 1e3, 1),
        "tokens_per_sec_chip": round(tokens_per_step / dt, 1),
        "mfu": round(tflops / peak_tflops, 4),
        "host_cores": os.cpu_count(),
        "loss": round(float(m["loss"]), 4),
    }))


def bench_infinity():
    """The BASELINE "OPT-13B on one chip" run (docs/_pages/training.md:293
    analog at Infinity scale): BENCH_EMBD=5120 BENCH_LAYERS=40 is the
    OPT-13B shape (~12.9 B params). The hybrid optimizer tier packs as many
    [master|m|v] records as DRAM holds and spills the rest to NVMe; compute
    copies cast from the masters at load (from_master), init is numpy-native
    in DRAM (host_init), and the per-block optimizer step runs eagerly
    inside the backward sweep so grads never pile up host-side."""
    import jax

    from deepspeed_tpu.models import gpt2
    from deepspeed_tpu.parallel.topology import MeshSpec
    from deepspeed_tpu.runtime.config import DeepSpeedConfig
    from deepspeed_tpu.runtime.engine import DeepSpeedEngine

    # default sizing: largest decoder whose fp32 master+moments fit the
    # DRAM+disk budget at 12 B/param (from_master stores no bf16 copies)
    avail = float(os.environ.get("BENCH_HOST_BYTES", 0)) or _free_ram()
    E = int(os.environ.get("BENCH_EMBD", "4096"))
    L = int(os.environ.get("BENCH_LAYERS", "0"))
    if not L:
        budget = avail * 0.80
        per_layer = 12 * E * E * 12.0
        fixed = 50257 * E * 12.0
        L = max(2, int((budget - fixed) // per_layer))
    seq = int(os.environ.get("BENCH_SEQ", "1024"))
    micro = int(os.environ.get("BENCH_MICRO", "1"))
    steps = int(os.environ.get("BENCH_STEPS", "1"))
    nvme_path = os.environ.get("BENCH_NVME_PATH", "/tmp/ds_tpu_nvme")
    opt_device = os.environ.get("BENCH_OPT_DEVICE", "hybrid")

    cfg = gpt2.get_config("gpt2", n_positions=seq, n_embd=E, n_layer=L,
                          n_head=E // 128, remat=True)
    ds = DeepSpeedConfig.load(
        {
            "train_micro_batch_size_per_gpu": micro,
            "gradient_accumulation_steps": 1,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
            "zero_optimization": {
                "stage": 3,
                "offload_param": {
                    "device": "cpu",
                    "nvme_path": nvme_path,
                    "from_master": bool(int(os.environ.get("BENCH_FROM_MASTER", "1"))),
                    "host_init": bool(int(os.environ.get("BENCH_HOST_INIT", "1"))),
                },
                "offload_optimizer": {
                    "device": opt_device,
                    "dram_budget_gb": float(os.environ.get("BENCH_OPT_DRAM_GB", "0")),
                },
            },
            "bf16": {"enabled": True},
            "steps_per_print": 10**9,
        },
        dp_world_size=1,
    )
    mesh = MeshSpec(dp=1, devices=jax.devices()[:1]).build_mesh()
    t_init = time.perf_counter()
    engine = DeepSpeedEngine(gpt2.make_module(cfg), ds, mesh=mesh, seed=0)
    init_s = time.perf_counter() - t_init
    inf = engine._infinity
    n_params = 12 * L * E * E + 50257 * E + seq * E
    rs = np.random.RandomState(0)
    batch = {"input_ids": rs.randint(0, cfg.vocab_size, (micro, seq)).astype(np.int32)}
    t_first = time.perf_counter()
    m = engine.train_batch(batch)
    warm = time.perf_counter() - t_first
    t0 = time.perf_counter()
    for _ in range(steps):
        m = engine.train_batch(batch)
    dt = (time.perf_counter() - t0) / steps

    fpt = train_flops_per_token(L, E, cfg.vocab_size, seq)
    tflops = fpt * micro * seq / dt / 1e12
    try:
        hbm_peak = jax.devices()[0].memory_stats().get("peak_bytes_in_use")
    except Exception:
        hbm_peak = None
    rss = None
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmHWM"):
                    rss = int(line.split()[1]) * 1024
                    break
    except OSError:
        pass
    print(json.dumps({
        "metric": f"ZeRO-Infinity params/chip (L={L} E={E} streamed, 1 chip)",
        "value": n_params,
        "unit": "params/chip",
        "vs_baseline": round(n_params / 1.4e9, 2),  # DDP OOM bound (BASELINE.md)
        "model_tflops": round(tflops, 2),
        "step_s": round(dt, 1),
        "first_step_s": round(warm, 1),
        "init_s": round(init_s, 1),
        "hbm_peak_bytes": hbm_peak,
        "host_dram_bytes": int(avail),
        "host_peak_rss_bytes": rss,
        "opt_device": opt_device,
        "opt_nvme_blocks": len(inf._opt_nvme),
        "opt_dram_blocks": L - len(inf._opt_nvme),
        "eager_step": bool(inf._eager),
        "from_master": bool(inf._param_from_master),
        "max_resident_blocks": inf.max_resident_blocks,
        "loss": round(float(m["loss"]), 4),
        "grad_norm": round(float(m.get("grad_norm", float("nan"))), 4),
    }))


def _free_ram() -> float:
    with open("/proc/meminfo") as f:
        for line in f:
            if line.startswith("MemAvailable"):
                return float(line.split()[1]) * 1024
    return 64e9


if __name__ == "__main__":
    from deepspeed_tpu.utils.jax_env import setup_compile_cache

    setup_compile_cache()
    mode = sys.argv[1] if len(sys.argv) > 1 else "offload"
    if mode == "offload":
        bench_offload()
    else:
        bench_infinity()
