"""Public-API parity surfaces: OnDevice, DeepSpeedTransformerLayer,
add_tuning_arguments, revert_transformer_layer (reference __init__.py:16-33
export list)."""

import argparse
import ast
import functools
import os
import re

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


@pytest.mark.parametrize("opt_type", ["OneBitAdam", "OneBitLamb", "ZeroOneAdam"])
def test_a_removed_optimizer_is_refused_by_name(opt_type):
    """The 1-bit optimizers left in PR 46. Their names must not fall through
    to plain Adam or LAMB (a config that asks for compression would train
    without it): ``initialize`` refuses them before it builds any state."""
    from deepspeed_tpu.runtime.module import ModuleSpec

    built = []

    def init(rng):
        built.append(rng)
        return {"w": jnp.zeros((4, 4))}

    model = ModuleSpec(init=init, loss_fn=lambda params, batch, rng, train: (0.0, {}))
    config = {
        "train_micro_batch_size_per_gpu": 1,
        "optimizer": {"type": opt_type, "params": {"lr": 1e-3}},
        "mesh": {"dp": 8},
    }
    with pytest.raises(ValueError) as refused:
        deepspeed_tpu.initialize(model=model, config=config)
    said = str(refused.value)
    assert opt_type in said and "removed in PR 46" in said
    assert "comm_compression" in said and "docs/COMM_COMPRESSION.md" in said
    assert not built


_REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


@functools.lru_cache(maxsize=None)
def _trees():
    """``{dotted name: (path, ast)}`` of every module of the package, of the
    benchmark and of ``chip_smoke.py``."""
    paths = [os.path.join(_REPO, "chip_smoke.py")]
    for top in ("deepspeed_tpu", "perfbench"):
        for dirpath, _, names in os.walk(os.path.join(_REPO, top)):
            paths.extend(os.path.join(dirpath, n) for n in names if n.endswith(".py"))
    trees = {}
    for path in paths:
        parts = os.path.relpath(path, _REPO)[:-3].split(os.sep)
        if parts[-1] == "__init__":
            parts.pop()
        with open(path, encoding="utf-8") as fh:
            trees[".".join(parts)] = (path, ast.parse(fh.read(), filename=path))
    return trees


@functools.lru_cache(maxsize=None)
def _imports():
    """``{module: the modules of ``_trees`` it imports}``, at module level or
    inside a function. Importing ``a.b.c`` runs ``a`` and ``a.b`` too."""
    trees = _trees()

    def known(dotted):
        parts = dotted.split(".")
        return {".".join(parts[:i]) for i in range(1, len(parts) + 1)} & trees.keys()

    graph = {}
    for name, (path, tree) in trees.items():
        package = name if path.endswith("__init__.py") else name.rpartition(".")[0]
        found = set()
        for node in ast.walk(tree):
            if isinstance(node, ast.Import):
                for alias in node.names:
                    found |= known(alias.name)
            elif isinstance(node, ast.ImportFrom):
                base = node.module or ""
                if node.level:
                    above = package.split(".")
                    above = above[: len(above) - (node.level - 1)]
                    base = ".".join(above + ([node.module] if node.module else []))
                for alias in node.names:
                    found |= known(base + "." + alias.name)
        graph[name] = found | known(name) - {name}
    return graph


def _subpackage(module):
    """``runtime`` of ``deepspeed_tpu.runtime.engine``; None of the root."""
    parts = module.split(".")
    return parts[1] if parts[0] == "deepspeed_tpu" and len(parts) > 1 else None


@functools.lru_cache(maxsize=None)
def _reached():
    """Every module an entry point runs: the package's root, ``setup.py``'s
    console scripts, the tools with a ``main``, ``chip_smoke.py``, and the
    benchmark (``perfbench/run.py`` and the runners and readers that
    ``perfbench/manifest.py`` loads by path)."""
    trees, graph = _trees(), _imports()
    with open(os.path.join(_REPO, "setup.py"), encoding="utf-8") as fh:
        scripts = re.findall(r"=\s*(deepspeed_tpu[\w.]*):", fh.read())
    assert len(scripts) == 5, scripts
    todo = ["deepspeed_tpu", "chip_smoke", "perfbench.run", *scripts]
    for name, (_, tree) in trees.items():
        if name.startswith(("perfbench.runners.", "perfbench.metrics.readers.")):
            todo.append(name)
        elif name.startswith("deepspeed_tpu.tools.") and any(
            isinstance(n, ast.FunctionDef) and n.name == "main" for n in tree.body
        ):
            todo.append(name)
    seen = set()
    while todo:
        name = todo.pop()
        if name not in seen:
            seen.add(name)
            todo.extend(graph[name])
    return seen


# modules no entry point reaches, each with the ROADMAP debt that names it:
# tests import them, nothing that runs does
UNREACHED = {
    "deepspeed_tpu.compression": "D7",
    "deepspeed_tpu.compression.basic_layer": "D7",
    "deepspeed_tpu.compression.compress": "D7",
    "deepspeed_tpu.runtime.quantize": "D7",
    "deepspeed_tpu.runtime.zero.tiling": "D7",
    "deepspeed_tpu.ops.fused_adam": "D7",
}

# bottom to top: a subpackage imports from those before it. ``env_report`` is
# the one module at the package's root.
LAYERS = (
    "utils", "telemetry", "comm", "ops", "parallel", "moe", "compression",
    "monitor", "checkpoint", "resilience", "elasticity", "models",
    "module_inject", "runtime", "serving", "inference", "analysis",
    "launcher", "tools", "env_report",
)

# today's imports against that order (ROADMAP D20): importer -> imported
UPWARD = {
    ("utils", "checkpoint"): "D20",
    ("telemetry", "analysis"): "D20",
    ("ops", "models"): "D20",
    ("checkpoint", "resilience"): "D20",
    ("resilience", "analysis"): "D20",
    ("models", "runtime"): "D20",
    ("runtime", "analysis"): "D20",
    ("serving", "analysis"): "D20",
}


def _subpackages():
    pkg = os.path.join(_REPO, "deepspeed_tpu")
    return sorted(
        d for d in os.listdir(pkg)
        if os.path.isfile(os.path.join(pkg, d, "__init__.py"))
    )


@pytest.mark.parametrize("subpackage", _subpackages())
def test_every_module_is_reached_from_an_entry_point_or_is_a_named_debt(subpackage):
    """No port sits in the package unseen: a module that nothing an entry
    point runs imports stands in ``UNREACHED`` under its ROADMAP label, and
    leaves the table when something reaches it or when it goes."""
    reached = _reached()
    mine = {m for m in _trees() if _subpackage(m) == subpackage}
    unreached = mine - reached
    named = {m for m in UNREACHED if _subpackage(m) == subpackage}
    assert unreached - named == set(), "reached by no entry point and not in UNREACHED"
    assert named - unreached == set(), "in UNREACHED, but reached or gone"


def test_no_new_upward_import():
    """The subpackages have one declared order (``LAYERS``); the imports that
    point up it are the debt ``UPWARD`` lists, no more and no fewer."""
    assert sorted(LAYERS[:-1]) == _subpackages()
    rank = {name: i for i, name in enumerate(LAYERS)}
    upward = {}
    for module, imported in _imports().items():
        for other in imported:
            a, b = _subpackage(module), _subpackage(other)
            if a and b and rank[a] < rank[b]:
                upward.setdefault((a, b), set()).add(f"{module} -> {other}")
    new = {edge: sorted(upward[edge]) for edge in upward.keys() - UPWARD.keys()}
    assert not new, f"new upward imports: {new}"
    assert not UPWARD.keys() - upward.keys(), "repaired: take it out of UPWARD"


def test_package_reads_nothing_at_the_repository_root():
    """The package measures nothing through files a script above it wrote:
    no module under ``deepspeed_tpu/`` imports the benchmark (or the
    pre-chip ``bench`` it replaced), and none names a ``BENCH_*.json``
    record, in code, help text, docstring or any other string."""
    record = re.compile(r"BENCH_\w+\.json")
    forbidden = {"bench", "perfbench"}
    bad = []
    for name, (path, tree) in _trees().items():
        if not name.startswith("deepspeed_tpu"):
            continue
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
