"""chip_smoke.py's body on the CPU mesh: the same three phases the chip run
takes (kernels under Pallas's TPU interpreter, ZeRO-3 train steps over the 8
virtual devices, a paged serving run with more requests than slots), at
gpt2-tiny sizes. The chip-only assertions (Mosaic custom call in the compiled
text, device memory balance) are the ones this cannot reach."""

import os

import jax
import pytest

import chip_smoke

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

TINY_SIZES = {
    "model": "gpt2-tiny",
    "train_layers": 2,
    "seq": 128,
    "micro_batch": 1,
    "train_steps": 3,
    "serving": {
        "max_slots": 2,
        "page_size": 4,
        "num_pages": 64,
        "max_prompt_len": 24,
        "max_new_tokens": 6,
        "prefill_chunk_tokens": 8,
    },
    "requests": 3,
    "min_prompt": 4,
}


def test_body_runs_all_phases_on_cpu_mesh(devices):
    res = chip_smoke.run(TINY_SIZES, require_tpu=False)
    assert res["ok"] and res["device"] == {
        "platform": "cpu", "kind": "cpu", "count": len(devices),
    }
    assert res["train"]["dp"] == len(devices)
    assert res["train"]["losses"][-1] < res["train"]["losses"][0]
    assert res["serve"]["requests"] == 3 and res["serve"]["chunked_prompts"] >= 1
    # a server that chunks: the step program and the chunk program, no whole-prompt program (ISSUE 63)
    assert res["serve"]["programs"] == ["serving_chunk_prefill", "serving_decode"]
    assert res["kernels"]["paged_decode_max_err"] <= 2e-2


def test_main_refuses_anything_but_a_tpu(monkeypatch, tmp_path, capsys):
    # with the variable set the cache helper leaves this process's jax alone
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    with pytest.raises(RuntimeError, match="requires a TPU.*'cpu'"):
        chip_smoke.main()
    assert capsys.readouterr().out == ""  # no result line


def test_last_stdout_line_is_the_verdict(monkeypatch, tmp_path, capsys):
    import json

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    report = {"ok": True, "device": device, "train": {"losses": [10.8, 9.1]}}
    monkeypatch.setattr(chip_smoke, "run", lambda sizes, require_tpu: report)
    chip_smoke.main()
    lines = capsys.readouterr().out.splitlines()
    assert json.loads(lines[-2]) == report
    # exactly these keys: the driver's parser refuses anything more
    assert json.loads(lines[-1]) == {"ok": True, "device": device}


def test_full_width_constants_are_gpt2_xl():
    from deepspeed_tpu.models import gpt2

    cfg = gpt2.get_config(chip_smoke.FULL_SIZES["model"])
    assert (cfg.n_embd, cfg.n_head, cfg.head_dim, cfg.vocab_size, cfg.n_layer) == (
        1600, 25, 64, 50257, 48,
    )
    assert chip_smoke.FULL_SIZES["seq"] == 1024
    sv = chip_smoke.FULL_SIZES["serving"]
    assert chip_smoke.FULL_SIZES["requests"] > sv["max_slots"]


def test_compile_cache_helper(monkeypatch, tmp_path):
    from deepspeed_tpu.utils.jax_env import setup_compile_cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert setup_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before  # nothing else set

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        want = os.path.join(REPO_ROOT, ".jax_cache")
        assert setup_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


class TestKernelUnderMultiDeviceMesh:
    """What the four-chip run caught: GSPMD cannot partition a Mosaic custom
    call, so inside a jit over more than one device the flash kernel has to
    sit in a shard_map (batch over dp, heads over tp). The CPU mesh never
    takes the kernel path by itself; here it is asked for by name and the
    kernel body runs in Pallas's interpreter."""

    def _spy(self, monkeypatch):
        import functools

        from deepspeed_tpu.ops.pallas import flash_attention as fa

        monkeypatch.setattr(
            fa, "flash_attention", functools.partial(fa.flash_attention, interpret=True)
        )
        calls = []
        real = jax.shard_map

        def spy(f, **kw):
            calls.append(kw["in_specs"][0])
            return real(f, **kw)

        monkeypatch.setattr(jax, "shard_map", spy)
        return calls

    def test_dispatcher_shards_batch_and_heads(self, mesh_dp4_tp2, monkeypatch):
        import jax.numpy as jnp
        import numpy as np
        from jax.sharding import PartitionSpec as P

        from deepspeed_tpu.ops.attention import causal_attention

        calls = self._spy(monkeypatch)
        rs = np.random.RandomState(0)
        q, k, v = (jnp.asarray(rs.randn(4, 128, 4, 16), jnp.float32) for _ in range(3))
        ref = causal_attention(q, k, v, impl="jnp")
        with jax.set_mesh(mesh_dp4_tp2):
            out = jax.jit(lambda q, k, v: causal_attention(q, k, v, impl="pallas"))(q, k, v)
        assert calls == [P("dp", None, "tp", None)]
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)
        # no ambient mesh (one device, or a caller already inside a
        # shard_map): the kernel is called bare
        causal_attention(q[:1], k[:1], v[:1], impl="pallas")
        assert len(calls) == 1

    def test_engine_step_traces_under_its_mesh(self, monkeypatch):
        import numpy as np
        from jax.sharding import PartitionSpec as P

        import deepspeed_tpu
        from deepspeed_tpu.models import gpt2

        calls = self._spy(monkeypatch)
        cfg = gpt2.get_config("gpt2-tiny", attn_impl="pallas")
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=gpt2.make_module(cfg),
            config={
                "train_micro_batch_size_per_gpu": 1,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                "zero_optimization": {"stage": 3},
                "steps_per_print": 10**9,
            },
        )
        ids = np.random.RandomState(0).randint(0, cfg.vocab_size, (engine.train_batch_size, 128))
        loss = float(engine.train_batch({"input_ids": ids.astype(np.int32)})["loss"])
        assert abs(loss - np.log(cfg.vocab_size)) < 0.5
        assert calls and all(c == P("dp", None, None, None) for c in calls)
