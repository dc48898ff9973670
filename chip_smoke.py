"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the two main paths once, through the entry points a user calls, at the
full width of GPT-2-XL (1600 wide, 25 heads of 64, vocab 50257):

1. **kernels** — ``ops.attention`` dispatchers with ``impl="pallas"`` at the
   exact shapes the next two phases use, compiled by Mosaic and matched
   against ``impl="jnp"``;
2. **train** — ``deepspeed_tpu.initialize`` + ``engine.train_batch``: ZeRO-3,
   bf16, AdamW, clipping, dp over every local device. Depth is cut so weights,
   gradients and Adam state fit one 16 GB chip; nothing else is;
3. **serve** — ``deepspeed_tpu.init_inference(...).serve(...)``: full-depth
   XL, paged KV pool, more requests than slots, run to completion.

``python chip_smoke.py`` takes no flags, always runs full width and requires a
TPU: it exits non-zero with a traceback (and prints no result line) when
``jax.default_backend() != "tpu"``, when the chip is not in the peak table, or
when any assertion fails. On success stdout ends with two JSON lines: the
report (device, versions, per-phase sizes, compile seconds, losses, request
times, cache hits; timings are set-up information, not a benchmark) and, last,
the verdict ``{"ok": true, "device": {"platform", "kind", "count"}}`` with
exactly those keys, the device as JAX reports it. The only environment
variable read is ``JAX_COMPILATION_CACHE_DIR``.

:func:`run` is the same body with the sizes as an argument, so the tier-1
suite drives all three phases at ``gpt2-tiny`` on the CPU mesh
(``tests/unit/test_chip_smoke.py``).
"""

from __future__ import annotations

import contextlib
import gc
import importlib.metadata
import json
import math
import time

# Full width. Depth of the TRAINED model is the only cut: 16 of XL's 48
# layers is 574M parameters, 10.3 GB of weights + gradients + Adam state at
# 18 B/param, which leaves activations (micro batch 4, remat) room in 16 GB
# at dp=1. The SERVED model is all 48 layers (3.1 GB of bf16 weights) beside a
# 512-page pool (4.9 MB per 16-token page, 2.5 GB of values; 5.0 GB on the
# device, which keeps it row-major and so pads a 64-wide head to its 128
# lanes: serving/kv_cache.py::pool_stored_shape). The benchmark's served
# cells hold the same 512 pages.
FULL_SIZES = {
    "model": "gpt2-xl",
    "train_layers": 16,
    "seq": 1024,
    "micro_batch": 4,
    "train_steps": 4,
    "serving": {
        "max_slots": 8,
        "page_size": 16,
        "num_pages": 512,
        "max_prompt_len": 512,
        "max_new_tokens": 64,
        # every prompt prefills through the chunk program, whose attention
        # is the multitoken paged kernel: one no longer than this in ONE
        # call, a longer one in several (a server that chunks builds no
        # whole-prompt program)
        "prefill_chunk_tokens": 128,
    },
    "requests": 12,
    "min_prompt": 32,
}


def _compile_counters(reg):
    return {
        "compiles": reg.counter("jit_compiles_total").value(),
        "compile_s": reg.counter("jit_compile_seconds_total").value(),
        "cache_hits": reg.counter("jit_cache_hits_total").value(),
        "cache_misses": reg.counter("jit_cache_misses_total").value(),
    }


def _memory(devices):
    """``memory_stats()`` of every device (None where the backend has none,
    i.e. the CPU mesh). ``peak_bytes_in_use`` is cumulative over the process,
    so a later phase reports at least the earlier phases' peak."""
    stats = [d.memory_stats() for d in devices]
    if any(s is None for s in stats):
        return None
    return {
        "bytes_in_use": [int(s["bytes_in_use"]) for s in stats],
        "peak_bytes_in_use": [int(s["peak_bytes_in_use"]) for s in stats],
    }


def _max_err(a, b):
    import numpy as np

    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    assert a.shape == b.shape, (a.shape, b.shape)
    assert np.isfinite(a).all(), "non-finite kernel output"
    return float(np.abs(a - b).max())


def kernels_phase(sizes, cfg, on_tpu):
    """Mosaic-compile the three attention kernels the train and serve phases
    dispatch to, at those phases' shapes, and match each against the jnp
    path (bf16 tolerances of tests/unit/ops/test_tpu_hardware.py). Off the
    chip (the tier-1 body) the same kernels run under Pallas's TPU
    interpreter; the train and serve phases there take the jnp path."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental.pallas import tpu as pltpu

    from deepspeed_tpu.ops.attention import (
        causal_attention,
        paged_cached_attention,
        paged_multitoken_cached_attention,
    )

    sv = sizes["serving"]
    H, D = cfg.n_head, cfg.head_dim
    S, B = sizes["seq"], sizes["micro_batch"]
    page, slots = sv["page_size"], sv["max_slots"]
    chunk = sv["prefill_chunk_tokens"]
    n_pg = -(-(sv["max_prompt_len"] + sv["max_new_tokens"]) // page)
    rs = np.random.RandomState(0)
    bf16 = jnp.bfloat16
    mode = contextlib.nullcontext() if on_tpu else pltpu.force_tpu_interpret_mode()
    out = {}

    with mode:
        # flash forward + backward at the train step's [micro, seq, H, D]
        q, k, v = (jnp.asarray(rs.randn(B, S, H, D), bf16) for _ in range(3))

        def fwd_and_grads(impl, q, k, v):
            def loss(q, k, v):
                o = causal_attention(q, k, v, impl=impl)
                return jnp.sum(o.astype(jnp.float32) ** 2), o

            (_, o), g = jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)
            return o, g

        o_pal, g_pal = jax.jit(fwd_and_grads, static_argnums=0)("pallas", q, k, v)
        o_ref, g_ref = jax.jit(fwd_and_grads, static_argnums=0)("jnp", q, k, v)
        # f32 + highest precision is the truth both bf16 paths are measured
        # against: on TPU even the jnp path carries bf16 MXU noise
        with jax.default_matmul_precision("highest"):
            _, g_true = jax.jit(fwd_and_grads, static_argnums=0)(
                "jnp", *(x.astype(jnp.float32) for x in (q, k, v))
            )
        err = _max_err(o_pal, o_ref)
        assert err <= 2e-2 * max(1.0, float(jnp.abs(o_ref).max())), f"flash fwd err {err}"
        out["flash_fwd_max_err"] = err
        for name, a, b, t in zip(("dq", "dk", "dv"), g_pal, g_ref, g_true):
            scale = float(np.abs(np.asarray(t, np.float32)).max()) + 1e-6
            e_pal, e_ref = _max_err(a, t) / scale, _max_err(b, t) / scale
            assert e_pal <= max(2.0 * e_ref, 2e-2), (
                f"flash {name}: pallas err {e_pal:.4f} vs jnp err {e_ref:.4f}"
            )
            out[f"flash_{name}_rel_err"] = e_pal

        # paged decode at the decode program's [slots, H, D] x [P, H, page, D]
        P = slots * n_pg + 1  # page 0 is scratch; tables never name it
        kp = jnp.asarray(rs.randn(P, H, page, D), bf16)
        vp = jnp.asarray(rs.randn(P, H, page, D), bf16)
        bt = jnp.asarray(rs.permutation(np.arange(1, P)).reshape(slots, n_pg), jnp.int32)
        q1 = jnp.asarray(rs.randn(slots, H, D), bf16)
        pos = jnp.asarray(rs.randint(0, n_pg * page, (slots,)), jnp.int32)
        pos = pos.at[0].set(0).at[-1].set(n_pg * page - 1)
        if slots > 2:  # the last row of a page, the first of the next
            pos = pos.at[1].set(page - 1).at[2].set(page)
        # ragged lengths over padded tables, as the scheduler leaves them:
        # entries past a slot's last page name scratch page 0, which the
        # kernel must never read (NaN there; the jnp path gathers it, masked)
        owned = jnp.arange(n_pg)[None, :] <= (pos // page)[:, None]
        bt_pad = jnp.where(owned, bt, 0)
        paged = jax.jit(paged_cached_attention, static_argnames=("impl",))
        err = _max_err(
            paged(q1, kp.at[0].set(jnp.nan), vp.at[0].set(jnp.nan), bt_pad,
                  pos, impl="pallas"),
            paged(q1, kp, vp, bt_pad, pos, impl="jnp"),
        )
        assert err <= 2e-2, f"paged decode err {err}"
        out["paged_decode_max_err"] = err

        # multitoken at the chunk-prefill program's [1, chunk, H, D]
        qm = jnp.asarray(rs.randn(1, chunk, H, D), bf16)
        base = jnp.asarray([min(chunk, n_pg * page - chunk)], jnp.int32)
        multi = jax.jit(paged_multitoken_cached_attention, static_argnames=("impl",))
        err = _max_err(
            multi(qm, kp, vp, bt[:1], base, impl="pallas"),
            multi(qm, kp, vp, bt[:1], base, impl="jnp"),
        )
        assert err <= 2e-2, f"paged multitoken err {err}"
        out["paged_multitoken_max_err"] = err
    out["shapes"] = {
        "flash": [B, S, H, D], "paged_decode": [slots, H, D],
        "paged_multitoken": [1, chunk, H, D], "pool": [P, H, page, D],
    }
    return out


def train_phase(sizes, on_tpu, counters):
    import jax
    import numpy as np

    import deepspeed_tpu
    from deepspeed_tpu.models import gpt2

    devices = jax.devices()
    n = len(devices)
    cfg = gpt2.get_config(
        sizes["model"], n_layer=sizes["train_layers"],
        n_positions=sizes["seq"], remat=True,
    )
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=gpt2.make_module(cfg),
        config={
            "train_micro_batch_size_per_gpu": sizes["micro_batch"],
            "gradient_accumulation_steps": 1,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-4, "weight_decay": 0.01}},
            "zero_optimization": {"stage": 3},
            "gradient_clipping": 1.0,
            "bf16": {"enabled": True},
            "steps_per_print": 10**9,
        },
    )
    assert engine.dp_world_size == n, (engine.dp_world_size, n)
    assert engine.zero_stage == 3
    rs = np.random.RandomState(0)
    batch = {
        "input_ids": rs.randint(
            0, cfg.vocab_size, (engine.train_batch_size, sizes["seq"])
        ).astype(np.int32)
    }

    losses, step_s = [], []
    c0 = counters()
    for i in range(sizes["train_steps"]):
        t0 = time.perf_counter()
        m = engine.train_batch(batch)
        losses.append(float(jax.block_until_ready(m["loss"])))
        step_s.append(round(time.perf_counter() - t0, 3))
        if i == 0:
            # the compiled step must hold the flash kernel, not the jnp path
            # (this analysis copy compiles once more, before the window below)
            if on_tpu:
                assert "tpu_custom_call" in engine._compiled_step().as_text(), (
                    "train step compiled without the Mosaic flash kernel"
                )
            c_warm = counters()
    c1 = counters()
    assert c1["compiles"] == c_warm["compiles"], (
        f"{c1['compiles'] - c_warm['compiles']} compilation(s) after the first train step"
    )
    assert all(math.isfinite(x) for x in losses), losses
    # random init: the first loss is the uniform-prediction entropy ln(vocab)
    assert abs(losses[0] - math.log(cfg.vocab_size)) < 1.0, (losses[0], cfg.vocab_size)
    assert losses[-1] < losses[0], f"loss did not fall: {losses}"

    # ZeRO-3: master weights and Adam moments live 1/n per device, except
    # leaves under the persistence threshold, which stay replicated by design
    state_leaves = jax.tree.leaves((engine.state.params, engine.state.opt_state))
    large = [x for x in state_leaves if x.size >= engine.policy.min_size_to_shard]

    def device0_bytes(leaves):
        return sum(
            s.data.nbytes for x in leaves
            for s in x.addressable_shards if s.device == devices[0]
        )

    total = sum(x.nbytes for x in state_leaves)
    large_total = sum(x.nbytes for x in large)
    assert device0_bytes(large) <= 1.05 * large_total / n, (
        device0_bytes(large), large_total, n
    )
    mem = _memory(devices)
    if n > 1 and mem is not None:
        use = mem["bytes_in_use"]
        assert max(use) <= 2 * min(use), f"uneven device memory: {use}"
    n_params = sum(x.size for x in jax.tree.leaves(engine.state.params))
    return {
        "model": sizes["model"], "n_layer": cfg.n_layer, "n_embd": cfg.n_embd,
        "n_head": cfg.n_head, "vocab": cfg.vocab_size, "seq": sizes["seq"],
        "params": int(n_params), "dp": n, "zero_stage": 3,
        "micro_batch": sizes["micro_batch"], "train_batch": engine.train_batch_size,
        "losses": [round(x, 4) for x in losses],
        "step_seconds": step_s,
        "compile_seconds": round(c1["compile_s"] - c0["compile_s"], 2),
        "compiles": int(c1["compiles"] - c0["compiles"]),
        "state_bytes_total": int(total),
        "state_bytes_sharded_leaves": int(large_total),
        "state_bytes_device0": int(device0_bytes(state_leaves)),
        "memory": mem,
    }


def serve_phase(sizes, on_tpu, counters):
    import jax
    import jax.numpy as jnp
    import numpy as np

    import deepspeed_tpu
    from deepspeed_tpu.models import gpt2
    from deepspeed_tpu.serving.request import RequestStatus

    cfg = gpt2.get_config(sizes["model"])
    sv = sizes["serving"]
    c0 = counters()
    t0 = time.perf_counter()
    eng = deepspeed_tpu.init_inference(model=gpt2.make_module(cfg), dtype=jnp.bfloat16)
    srv = eng.serve(dict(sv))
    programs = dict(srv.executable_names())  # AOT-compiles the program set
    setup_s = time.perf_counter() - t0
    assert len(srv.executables) == srv.expected_executables, (
        len(srv.executables), srv.expected_executables
    )
    if on_tpu:
        # the whole program set of a server that chunks: decode and the
        # chunk program that prefills every prompt must hold the paged kernels
        for name in ("serving_decode", "serving_chunk_prefill"):
            assert "tpu_custom_call" in programs[name].as_text(), (
                f"{name} compiled without the Mosaic paged kernel"
            )

    rs = np.random.RandomState(1)
    chunk, n_new = sv["prefill_chunk_tokens"], sv["max_new_tokens"]

    def prompt(n):
        return rs.randint(0, cfg.vocab_size, (n,)).astype(np.int32)

    def check(reqs):
        for r in reqs:
            assert r.status == RequestStatus.FINISHED, (r.status, r.detail)
            assert len(r.tokens) == n_new, (len(r.tokens), n_new)
            assert all(0 <= t < cfg.vocab_size for t in r.tokens)

    # warm-up: a prompt of one chunk and one of several
    warm = [
        srv.submit(prompt(sizes["min_prompt"]), max_new_tokens=n_new, seed=1),
        srv.submit(prompt(sv["max_prompt_len"]), max_new_tokens=n_new, seed=2),
    ]
    srv.run()
    check(warm)
    c_warm = counters()

    lens = [sizes["min_prompt"], sv["max_prompt_len"]] + [
        int(x) for x in rs.randint(
            sizes["min_prompt"], sv["max_prompt_len"] + 1, (sizes["requests"] - 2,)
        )
    ]
    assert len(lens) > sv["max_slots"], "the smoke must queue behind full slots"
    t0 = time.perf_counter()
    reqs = [
        srv.submit(prompt(n), max_new_tokens=n_new, seed=10 + i)
        for i, n in enumerate(lens)
    ]
    srv.run()
    run_s = time.perf_counter() - t0
    check(reqs)
    c1 = counters()
    assert c1["compiles"] == c_warm["compiles"], (
        f"{c1['compiles'] - c_warm['compiles']} compilation(s) after the warm-up requests"
    )
    srv.check_no_leaks()
    assert len(srv.executables) == srv.expected_executables
    return {
        "model": sizes["model"], "n_layer": cfg.n_layer, "n_embd": cfg.n_embd,
        "n_head": cfg.n_head, "vocab": cfg.vocab_size, "serving": dict(sv),
        "programs": sorted(programs),
        "requests": len(reqs), "prompt_lens": lens,
        "chunked_prompts": sum(1 for n in lens if n > chunk),
        "new_tokens_each": n_new,
        "setup_seconds": round(setup_s, 2),
        "compile_seconds": round(c1["compile_s"] - c0["compile_s"], 2),
        "compiles": int(c1["compiles"] - c0["compiles"]),
        "run_seconds": round(run_s, 2),
        "request_seconds": [round(r.t_finish - r.t_submit, 3) for r in reqs],
        "memory": _memory(jax.devices()[:1]),
    }


def run(sizes, require_tpu=True):
    """All three phases at ``sizes`` → the result dict. With ``require_tpu``
    anything but a TPU in the peak table raises before any phase runs."""
    import jax
    import jaxlib

    from deepspeed_tpu.models import gpt2
    from deepspeed_tpu.telemetry import compile_stats
    from deepspeed_tpu.telemetry.introspect import chip_peak
    from deepspeed_tpu.telemetry.registry import MetricsRegistry

    platform = jax.default_backend()
    on_tpu = platform == "tpu"
    if require_tpu and not on_tpu:
        raise RuntimeError(
            f"chip_smoke requires a TPU; jax.default_backend() is {platform!r} "
            f"(devices: {jax.devices()})"
        )
    devices = jax.devices()
    peak = chip_peak(devices[0].device_kind)  # raises for an unknown accelerator
    assert on_tpu == (peak.source == "table"), peak

    reg = MetricsRegistry()
    compile_stats.install(reg)

    def counters():
        return _compile_counters(reg)

    result = {
        "ok": False,
        "device": {
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
        },
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "n_devices": len(devices),
        "versions": {
            "jax": jax.__version__,
            "jaxlib": jaxlib.__version__,
            "libtpu": importlib.metadata.version("libtpu") if on_tpu else None,
        },
        "note": "seconds are set-up information, not a benchmark",
    }
    t_all = time.perf_counter()
    c0 = counters()
    result["kernels"] = kernels_phase(sizes, gpt2.get_config(sizes["model"]), on_tpu)
    c1 = counters()
    result["kernels"]["compile_seconds"] = round(c1["compile_s"] - c0["compile_s"], 2)
    result["kernels"]["memory"] = _memory(devices[:1])
    gc.collect()
    result["train"] = train_phase(sizes, on_tpu, counters)
    gc.collect()  # the train state must be gone before the served model loads
    result["serve"] = serve_phase(sizes, on_tpu, counters)
    c = counters()
    result["compile_cache"] = {
        "dir": jax.config.jax_compilation_cache_dir,
        "hits": int(c["cache_hits"]),
        "misses": int(c["cache_misses"]),
        "compile_seconds_total": round(c["compile_s"], 2),
    }
    result["total_seconds"] = round(time.perf_counter() - t_all, 1)
    result["ok"] = True
    return result


def main():
    """Full width, TPU required. Prints the report, then the verdict as the
    last line of stdout; both only after every phase passed."""
    from deepspeed_tpu.utils.jax_env import setup_compile_cache

    setup_compile_cache()
    report = run(FULL_SIZES, require_tpu=True)
    print(json.dumps(report))
    print(json.dumps({"ok": report["ok"], "device": report["device"]}), flush=True)


if __name__ == "__main__":
    main()
