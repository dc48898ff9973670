"""Public-API parity surfaces: OnDevice, DeepSpeedTransformerLayer,
add_tuning_arguments, revert_transformer_layer (reference __init__.py:16-33
export list)."""

import argparse

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu import (
    DeepSpeedTransformerConfig,
    DeepSpeedTransformerLayer,
    OnDevice,
)


class TestOnDevice:
    def test_meta_init_is_abstract_and_free(self):
        """device='meta' == jax.eval_shape: shapes/dtypes, no storage
        (reference OnDevice meta-tensor semantics, utils/init_on_device.py:81)."""
        def init(rng):
            return {"w": jax.random.normal(rng, (512, 512)), "b": jnp.zeros(512)}

        with OnDevice(dtype=jnp.bfloat16, device="meta") as ctx:
            abstract = ctx.init(init, jax.random.PRNGKey(0))
        assert isinstance(abstract["w"], jax.ShapeDtypeStruct)
        assert abstract["w"].shape == (512, 512)
        assert abstract["w"].dtype == jnp.bfloat16  # dtype override applied

    def test_device_init_materializes(self):
        def init(rng):
            return {"w": jax.random.normal(rng, (8, 8))}

        with OnDevice(device=jax.devices()[0]) as ctx:
            params = ctx.init(init, jax.random.PRNGKey(0))
        assert isinstance(params["w"], jax.Array)
        assert params["w"].devices() == {jax.devices()[0]}

    def test_disabled_passthrough(self):
        ctx = OnDevice(enabled=False)
        out = ctx.init(lambda: {"x": np.ones(3)})
        assert isinstance(out["x"], np.ndarray)


class TestTransformerLayerOp:
    def _layer(self, **kw):
        cfg = DeepSpeedTransformerConfig(
            hidden_size=64, heads=4, attn_dropout_ratio=0.0,
            hidden_dropout_ratio=0.0, **kw,
        )
        layer = DeepSpeedTransformerLayer(cfg)
        params = layer.init(jax.random.PRNGKey(0))
        return cfg, layer, params

    def test_forward_shape_and_grads(self):
        cfg, layer, params = self._layer()
        x = jnp.asarray(np.random.RandomState(0).randn(2, 16, 64), jnp.float32)
        y = jax.jit(lambda p, x: layer(p, x))(params, x)
        assert y.shape == x.shape and np.isfinite(np.asarray(y)).all()
        # full fwd+bwd through one jitted program (the reference kernel's
        # contract: training layer, not inference-only)
        g = jax.grad(lambda p: jnp.sum(layer(p, x) ** 2))(params)
        flat = jax.tree.leaves(g)
        assert all(np.isfinite(np.asarray(l)).all() for l in flat)
        assert any(float(jnp.abs(l).max()) > 0 for l in flat)

    def test_padding_mask_isolates_padded_positions(self):
        cfg, layer, params = self._layer()
        rs = np.random.RandomState(1)
        x = jnp.asarray(rs.randn(1, 8, 64), jnp.float32)
        mask = jnp.asarray([[1, 1, 1, 1, 0, 0, 0, 0]], jnp.int32)
        y1 = layer(params, x, attention_mask=mask)
        # changing PADDED content must not change kept positions' outputs
        x2 = x.at[:, 4:].set(jnp.asarray(rs.randn(1, 4, 64), jnp.float32))
        y2 = layer(params, x2, attention_mask=mask)
        np.testing.assert_allclose(
            np.asarray(y1[:, :4]), np.asarray(y2[:, :4]), atol=1e-5
        )

    def test_pre_vs_post_layer_norm_differ(self):
        _, pre, p1 = self._layer(pre_layer_norm=True)
        _, post, p2 = self._layer(pre_layer_norm=False)
        x = jnp.asarray(np.random.RandomState(2).randn(2, 8, 64), jnp.float32)
        assert not np.allclose(np.asarray(pre(p1, x)), np.asarray(post(p1, x)))

    def test_dropout_train_vs_eval(self):
        cfg = DeepSpeedTransformerConfig(hidden_size=64, heads=4,
                                         hidden_dropout_ratio=0.5)
        layer = DeepSpeedTransformerLayer(cfg)
        params = layer.init(jax.random.PRNGKey(0))
        x = jnp.asarray(np.random.RandomState(3).randn(2, 8, 64), jnp.float32)
        rng = jax.random.PRNGKey(7)
        y_eval = layer(params, x, train=False, rng=rng)
        y_train = layer(params, x, train=True, rng=rng)
        assert not np.allclose(np.asarray(y_eval), np.asarray(y_train))


class TestTuningArguments:
    def test_reference_arg_names_parse(self):
        p = deepspeed_tpu.add_tuning_arguments(argparse.ArgumentParser())
        a = p.parse_args(
            ["--lr_schedule", "OneCycle", "--cycle_min_lr", "0.02",
             "--warmup_num_steps", "500", "--lr_range_test_step_size", "200"]
        )
        assert a.lr_schedule == "OneCycle" and a.cycle_min_lr == 0.02
        assert a.warmup_num_steps == 500 and a.lr_range_test_step_size == 200


class TestRevertTransformerLayer:
    def test_gpt2_round_trip(self):
        """convert -> perturb -> revert: the HF model's torch forward must
        reflect the perturbed weights (reference revert_transformer_layer,
        replace_module.py:1001)."""
        torch = pytest.importorskip("torch")
        transformers = pytest.importorskip("transformers")

        hf_cfg = transformers.GPT2Config(
            vocab_size=128, n_positions=32, n_embd=32, n_layer=2, n_head=2
        )
        hf = transformers.GPT2LMHeadModel(hf_cfg).eval()
        kind, cfg, params = deepspeed_tpu.replace_transformer_layer(hf)
        assert kind == "gpt2"
        # perturb one attention weight and an embedding row
        params["blocks"]["attn"]["c_attn_w"] = (
            np.asarray(params["blocks"]["attn"]["c_attn_w"]) * 0.5
        )
        params["wte"] = np.asarray(params["wte"]) + 0.25
        deepspeed_tpu.revert_transformer_layer(hf, params)
        got_w = hf.transformer.h[0].attn.c_attn.weight.detach().numpy()
        np.testing.assert_allclose(
            got_w, params["blocks"]["attn"]["c_attn_w"][0], atol=1e-6
        )
        got_e = hf.transformer.wte.weight.detach().numpy()
        np.testing.assert_allclose(got_e, params["wte"], atol=1e-6)

    def test_no_revert_policy_raises(self):
        class Fake:
            pass

        with pytest.raises((ValueError, NotImplementedError)):
            deepspeed_tpu.revert_transformer_layer(Fake(), {})


def test_package_reads_nothing_at_the_repository_root():
    """The package measures nothing through files a script above it wrote:
    no module under ``deepspeed_tpu/`` imports the benchmark (or the
    pre-chip ``bench`` it replaced), and none names a ``BENCH_*.json``
    record, in code, help text, docstring or any other string."""
    import ast
    import os
    import re

    pkg = os.path.dirname(os.path.abspath(deepspeed_tpu.__file__))
    record = re.compile(r"BENCH_\w+\.json")
    forbidden = {"bench", "perfbench"}
    bad = []
    for dirpath, _, names in os.walk(pkg):
        for name in names:
            if not name.endswith(".py"):
                continue
            path = os.path.join(dirpath, name)
            with open(path, encoding="utf-8") as fh:
                tree = ast.parse(fh.read(), filename=path)
            for node in ast.walk(tree):
                mods = []
                if isinstance(node, ast.Import):
                    mods = [a.name for a in node.names]
                elif isinstance(node, ast.ImportFrom) and node.level == 0:
                    mods = [node.module or ""]
                elif isinstance(node, ast.Constant) and isinstance(node.value, str):
                    named = record.search(node.value)
                    if named:
                        bad.append(f"{path}:{node.lineno}: names {named.group()}")
                bad.extend(
                    f"{path}:{node.lineno}: imports {mod}"
                    for mod in mods if mod.split(".")[0] in forbidden
                )
    assert not bad, "\n".join(bad)
