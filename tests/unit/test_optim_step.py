"""ISSUE 45: the optimizer step reads and writes each state leaf once. The
gradient stays in the type the backward wrote it in up to the update, a leaf
the device holds in another order gets its gradient in that order, and the
compute-dtype copy of the masters comes out of the update's own fusion
(``TrainState.compute_params``). On the CPU's forced devices: the restructured
step against the parent's formulation, kept here as the oracle, bit for bit;
what the compiled step holds; the gauges that say so; and that whoever hands
the engine a state gets a compute copy made from it."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.experimental.layout import Layout

import deepspeed_tpu
from deepspeed_tpu.models import gpt2
from deepspeed_tpu.parallel.topology import MeshSpec
from deepspeed_tpu.runtime import engine as eng_mod
from deepspeed_tpu.runtime.config import DeepSpeedConfig
from deepspeed_tpu.runtime.engine import DeepSpeedEngine, TrainState
from deepspeed_tpu.runtime.fp16 import loss_scaler as ls
from deepspeed_tpu.telemetry import introspect, parts, spans

from .simple_model import base_config, make_simple_model, random_batches

DP = 4
OPTIMIZERS = {
    "adamw_masked": {"type": "AdamW", "params": {"lr": 1e-2, "weight_decay": 0.01}},   # decay on ndim >= 2 alone
    "adam_plain": {"type": "Adam", "params": {"lr": 1e-2}},                            # no decay, no mask
    "adam_l2": {"type": "Adam", "params": {"lr": 1e-2, "weight_decay": 0.01, "adam_w_mode": False}},
}


def _engine(stage=0, gas=1, dtype="bf16", clip=1.0, opt="adamw_masked", dp=DP, **extra):
    config = base_config(stage=stage, micro=4, gas=gas, dp=dp, optimizer=OPTIMIZERS[opt],
                         gradient_clipping=clip, **extra)
    if dtype in ("bf16", "fp16"):
        config[dtype] = {"enabled": True, **({"initial_scale_power": 4, "hysteresis": 1} if dtype == "fp16" else {})}
    mesh = MeshSpec(dp=dp, devices=jax.devices()[:dp]).build_mesh()
    return DeepSpeedEngine(make_simple_model(), DeepSpeedConfig.load(config, dp_world_size=dp), mesh=mesh, seed=1)


def _parent_step(engine):
    """The step of the parent (commit 24139fb), its optimizer block word for
    word: the masters cast at the step's start, the gradient cast to the
    accumulation type, unscale, zero-on-overflow and clip as passes over the
    tree, the selects whatever ``fp16`` says."""
    model, tx, cfg = engine.module, engine.optimizer, engine.config
    compute_dtype, acc_dtype = engine.compute_dtype, engine.grad_accum_dtype
    grad_shardings, fp16 = engine.grad_shardings, engine.fp16_enabled
    clip, gas = cfg.gradient_clipping, engine.gradient_accumulation_steps_value

    def scaled_loss_fn(cparams, micro, rng, scale):
        loss, metrics = model.loss_fn(cparams, micro, rng, True)
        return loss.astype(jnp.float32) * scale, (loss, metrics)

    grad_fn = jax.value_and_grad(scaled_loss_fn, has_aux=True)

    def step(state, batch, rng):
        rng = jax.random.fold_in(rng, state.global_step + state.skipped_steps)
        scale = state.loss_scale.cur_scale if fp16 else jnp.float32(1.0)
        cparams = eng_mod._cast_params(state.params, compute_dtype)
        if gas == 1:
            micro = jax.tree.map(lambda x: x[0], batch)
            (_, (loss, _)), grads = grad_fn(cparams, micro, jax.random.fold_in(rng, 0), scale)
            grads = jax.lax.with_sharding_constraint(
                jax.tree.map(lambda g: g.astype(acc_dtype), grads), grad_shardings)
            loss_sum = loss.astype(jnp.float32)
        else:
            def micro_step(carry, xs):
                grads_acc, loss_acc, i = carry
                micro = jax.tree.map(lambda x: x[i], batch)
                (_, (loss, _)), grads = grad_fn(cparams, micro, jax.random.fold_in(rng, i), scale)
                grads_acc = jax.tree.map(lambda a, g: a + g.astype(acc_dtype), grads_acc, grads)
                grads_acc = jax.lax.with_sharding_constraint(grads_acc, grad_shardings)
                return (grads_acc, loss_acc + loss.astype(jnp.float32), i + 1), None

            zero = jax.tree.map(lambda p: jnp.zeros(p.shape, acc_dtype), state.params)
            zero = jax.lax.with_sharding_constraint(zero, grad_shardings)
            (grads, loss_sum, _), _ = jax.lax.scan(micro_step, (zero, jnp.float32(0.0), 0), None, length=gas)
        inv = 1.0 / (scale * gas) if fp16 else 1.0 / gas
        grads = jax.tree.map(lambda g: (g.astype(jnp.float32) * inv), grads)
        overflow = ls.has_inf_or_nan(grads) if fp16 else jnp.bool_(False)
        grads = jax.tree.map(lambda g: jnp.where(overflow, jnp.zeros_like(g), g), grads)
        gnorm = eng_mod.global_norm(grads)
        if clip > 0.0:
            coef = jnp.minimum(1.0, clip / (gnorm + 1e-6))
            grads = jax.tree.map(lambda g: g * coef, grads)
        updates, new_opt_state = tx.update(grads, state.opt_state, state.params)
        new_params = optax.apply_updates(state.params, updates)
        new_params = eng_mod._tree_select(~overflow, new_params, state.params)
        new_opt_state = eng_mod._tree_select(~overflow, new_opt_state, state.opt_state)
        new_scale = ls.update(state.loss_scale, overflow, dynamic=engine.dynamic_loss_scale,
                              scale_window=cfg.fp16.loss_scale_window, min_scale=cfg.fp16.min_loss_scale)
        new_state = TrainState(
            params=new_params, opt_state=new_opt_state, loss_scale=new_scale,
            global_step=state.global_step + jnp.where(overflow, 0, 1),
            skipped_steps=state.skipped_steps + jnp.where(overflow, 1, 0),
        )
        return new_state, {"loss": loss_sum / gas, "grad_norm": gnorm, "overflow": overflow}

    return jax.jit(step, out_shardings=(eng_mod._persistent(engine.state_shardings), None))


def _bits(tree):
    return [np.asarray(x) for x in jax.tree.leaves(jax.device_get(tree))]


def _assert_same_bits(got, want, what):
    for i, (a, b) in enumerate(zip(_bits(got), _bits(want), strict=True)):
        assert a.dtype == b.dtype and a.shape == b.shape, (what, i)
        assert a.tobytes() == b.tobytes(), f"{what}: leaf {i} differs by up to {np.max(np.abs(a - b))}"


# each value of each axis beside each value of every other at least once, not the whole product
CASES = [
    # dtype, clip, optimizer, stage, gas
    ("bf16", 1.0, "adamw_masked", 3, 1), ("bf16", 0.0, "adamw_masked", 0, 1), ("bf16", 1.0, "adam_plain", 1, 1),
    ("bf16", 0.0, "adam_plain", 2, 1), ("bf16", 1.0, "adam_l2", 2, 2), ("bf16", 0.0, "adamw_masked", 3, 2),
    ("bf16", 1.0, "adam_plain", 0, 2), ("bf16", 0.0, "adam_l2", 1, 2), ("fp32", 1.0, "adamw_masked", 0, 1),
    ("fp32", 0.0, "adam_plain", 3, 1), ("fp32", 1.0, "adam_l2", 1, 1), ("fp32", 0.0, "adamw_masked", 2, 1),
    ("fp32", 1.0, "adam_plain", 3, 2), ("fp32", 0.0, "adam_l2", 0, 2), ("fp32", 1.0, "adamw_masked", 1, 2),
    ("fp32", 0.0, "adam_plain", 2, 2), ("fp16", 1.0, "adamw_masked", 3, 1), ("fp16", 0.0, "adam_plain", 0, 2),
]


@pytest.mark.parametrize("dtype, clip, opt, stage, gas", CASES)
def test_three_steps_are_the_parents_bit_for_bit(dtype, clip, opt, stage, gas):
    engine = _engine(stage=stage, gas=gas, dtype=dtype, clip=clip, opt=opt)
    oracle = _parent_step(engine)
    state = jax.device_put(jax.device_get(eng_mod._persistent(engine.state)),
                           eng_mod._persistent(engine.state_shardings))
    # carried: the copy of each leaf whose moments are laid as the leaf is (every leaf at stage 0; at the
    # other stages those too small for their moments to be sharded, and at stage 3 those large enough to
    # be sharded themselves)
    masters, placed = jax.tree.leaves(engine.state.params), jax.tree.leaves(engine.param_shardings)
    moments = {}
    for m in jax.tree.leaves(engine.state.opt_state):
        moments.setdefault(m.shape, []).append(m.sharding)
    want_carried = tuple(i for i, (x, sh) in enumerate(zip(masters, placed)) if dtype != "fp32"
                         and all(sh.is_equivalent_to(m, x.ndim) for m in moments.get(x.shape, ())))
    assert engine._carried == want_carried and len(engine.state.compute_params) == len(want_carried)
    assert len(want_carried) == (0 if dtype == "fp32" else len(masters) if stage == 0 else 3)
    carries = bool(want_carried)
    for batch in random_batches(3, engine.train_batch_size, seed=5):
        device_batch = engine.shard_batch(batch)
        with engine._mesh_scope():
            state, want = oracle(state, device_batch, engine._rng)
        got = engine.train_batch(batch)
        for k in ("loss", "grad_norm"):
            _assert_same_bits(got[k], want[k], k)
        _assert_same_bits(engine.state.params, state.params, "masters")
        _assert_same_bits(engine.state.opt_state, state.opt_state, "moments")
        assert not bool(want["overflow"]) and int(engine.state.global_step) == int(state.global_step)
    if carries:   # what the update wrote is what a cast of the masters gives
        _assert_same_bits(engine.state.compute_params,
                          eng_mod._cast_leaves(engine.state.params, engine.compute_dtype, engine._carried), "copy")


@pytest.mark.parametrize("stage", [0, 3])
def test_fp16_overflow_still_skips_the_step_and_halves_the_scale(stage):
    engine = _engine(stage=stage, dtype="fp16")
    oracle = _parent_step(engine)
    good = random_batches(1, engine.train_batch_size, seed=5)[0]
    bad = {k: v.copy() for k, v in good.items()}
    bad["x"][:] = np.inf
    state = jax.device_put(jax.device_get(eng_mod._persistent(engine.state)),
                           eng_mod._persistent(engine.state_shardings))
    before = jax.device_get(eng_mod._persistent(engine.state))
    copy_before = jax.device_get(engine.state.compute_params)
    scale = engine.loss_scale
    for batch, overflows in ((bad, True), (good, False)):
        with engine._mesh_scope():
            state, want = oracle(state, engine.shard_batch(batch), engine._rng)
        got = engine.train_batch(batch)
        assert bool(got["overflow"]) == bool(want["overflow"]) == overflows
        _assert_same_bits(engine.state.params, state.params, "masters")
        _assert_same_bits(engine.state.opt_state, state.opt_state, "moments")
        _assert_same_bits(engine.state.loss_scale, state.loss_scale, "loss scale")
        if overflows:
            _assert_same_bits(engine.state.params, before.params, "skipped masters")
            _assert_same_bits(engine.state.compute_params, copy_before, "skipped copy")
            assert engine.loss_scale == scale / 2 and engine.get_global_step() == 0
    assert engine.get_global_step() == 1


# -- what the compiled step holds ---------------------------------------------------------

B, S, V = 8, 64, 512


def _gpt2_engine(dp=1, stage=3, telemetry=None, monkeypatch=None, column_major=()):
    cfg = gpt2.GPT2Config(n_embd=128, n_head=4, n_layer=2, n_positions=S, vocab_size=V, remat=True, attn_impl="jnp")
    config = {"train_micro_batch_size_per_gpu": B // dp, "gradient_accumulation_steps": 1,
              "optimizer": OPTIMIZERS["adamw_masked"], "zero_optimization": {"stage": stage},
              "gradient_clipping": 1.0, "bf16": {"enabled": True}, "steps_per_print": 10**9}
    if telemetry:
        config["telemetry"] = {"enabled": True, "trace_path": telemetry}
    if column_major:   # as a TPU holds a leaf whose last dimension does not fill its lanes
        def layouts(self):
            return {k: (Layout(major_to_minor=(1, 0)) if k in column_major else
                        jax.tree.map(lambda x: None, v)) for k, v in self.state.params.items()}
        monkeypatch.setattr(DeepSpeedEngine, "_state_layouts", layouts)
    mesh = MeshSpec(dp=dp, devices=jax.devices()[:dp]).build_mesh()
    engine, _, _, _ = deepspeed_tpu.initialize(model=gpt2.make_module(cfg), config=config, mesh=mesh, seed=7)
    engine.train_batch({"input_ids": np.random.default_rng(3).integers(0, V, (B, S), dtype=np.int32)})
    return engine


def _leaf_shapes(engine):
    return [tuple(x.sharding.shard_shape(x.shape)) for x in jax.tree.leaves(engine.state.params)]


def test_no_float32_array_shaped_like_a_gradient_but_the_updates_own(tmp_path):
    t_start = spans._clock()
    engine = _gpt2_engine(telemetry=str(tmp_path / "traces"))
    text = engine._compiled_step().as_text()
    shapes = _leaf_shapes(engine)
    table = parts.table_of(text)
    comps = introspect.instructions_by_computation(text)
    fused = {c for parsed in comps.values() for ni in parsed if ni.op == "fusion"
             for c in parts._CALLS.findall(ni.attrs)}
    # counted by hand: the instructions of part optim that run as operations, and their leaf-shaped results
    writers, f32_made_by = set(), {}
    for comp, parsed in comps.items():
        if comp in fused:
            continue
        for ni in parsed:
            if table[ni.name].part != "optim" or ni.op in parts._NO_TRAFFIC or ni.op.endswith(("-done", "-start")):
                continue
            for dt, dd in ni.result_shapes:
                dims = tuple(int(d) for d in dd.split(",") if d)
                if dims in shapes:
                    writers.add(ni.name)
                    if dt == "f32":
                        what = table[ni.name].op_name.rsplit("/", 1)[-1]
                        f32_made_by[what] = f32_made_by.get(what, 0) + 1
    # the update's own outputs, the new master and two moments of each leaf (optax's `add`s), and the square
    # under the norm's sum, which a CPU writes out and a TPU keeps inside the sum's fusion: no gradient made
    # float32 (`convert_element_type`), no unscale or clip of its own (`mul`), no select (`select_n`)
    assert f32_made_by == {"add": 3 * len(shapes), "square": len(shapes)}
    traffic = parts.optim_traffic(text, shapes)
    assert traffic.passes == len(writers) > 0
    assert sum(1 for _, dt, _ in traffic.leaf_results if dt == "f32") == 4 * len(shapes)
    # every carried leaf's copy comes out of the optimizer: a bf16 result of its shape, and no cast of it at the start
    assert len(engine.state.compute_params) == len(shapes)
    n = sum(np.prod(s) for s in shapes)
    assert traffic.written >= n * (4 * 3 + 2) and traffic.read >= n * (4 * 3 + 2)
    reg = engine.telemetry.registry
    nbytes = reg.gauge("train_step_optim_bytes", "", labelnames=("direction",))
    assert nbytes.value(direction="read") == traffic.read and nbytes.value(direction="written") == traffic.written
    assert reg.gauge("train_step_optim_passes", "").value() == traffic.passes
    (attrs,) = [p[3] for p in spans.phases(since=t_start) if p[0] == "ds.init.programs"]
    assert attrs["optim"] == f"{traffic.read / 1e9:.2f}r+{traffic.written / 1e9:.2f}w/{traffic.passes}p"


def test_a_leaf_held_in_another_order_gets_its_gradient_in_that_order(monkeypatch):
    plain = _gpt2_engine()
    assert jax.tree.leaves(plain._state_layouts(), is_leaf=lambda x: x is None) == [None] * len(_leaf_shapes(plain))
    assert "LayoutConstraint" not in plain._train_step.lower(*plain._step_arg_structs).as_text()
    turned = _gpt2_engine(monkeypatch=monkeypatch, column_major=("wte", "wpe"))
    lowered = turned._train_step.lower(*turned._step_arg_structs).as_text()
    assert lowered.count("LayoutConstraint") == 2   # the two leaves' gradients, and nothing else
    # the same numbers: an order is no value
    _assert_same_bits(turned.state.params, plain.state.params, "masters")


# the loop collectives of the parent's compiled step on this mesh (kind, element type), read from commit
# 24139fb by the same reader: the CPU backend widens a bf16 wire to float32, a TPU keeps it
PARENT_LOOP_COLLECTIVES = {
    3: [("all_gather", "f32")] * 8 + [("all_reduce", "f32")],
    2: [("all_reduce", "f32")], 1: [("all_reduce", "f32")], 0: [("all_reduce", "f32")],
}
PARENT_COLLECTIVES = {   # every collective of the module, by opcode
    3: {"all-gather": 21, "all-reduce": 7}, 2: {"all-gather": 16, "all-reduce": 7},
    1: {"all-gather": 16, "all-reduce": 7}, 0: {"all-reduce": 3},
}


@pytest.mark.parametrize("stage", [3, 2, 1, 0])
def test_on_a_dp_mesh_every_collective_is_the_parents(stage):
    cfg = gpt2.GPT2Config(n_embd=256, n_head=4, n_layer=2, n_positions=128, vocab_size=V, remat=True, attn_impl="jnp")
    config = {"train_micro_batch_size_per_gpu": 4, "gradient_accumulation_steps": 1,
              "optimizer": {"type": "AdamW", "params": {"lr": 1e-3, "weight_decay": 0.01}},
              "zero_optimization": {"stage": stage}, "gradient_clipping": 1.0, "bf16": {"enabled": True},
              "steps_per_print": 10**9}
    mesh = MeshSpec(dp=DP, devices=jax.devices()[:DP]).build_mesh()
    engine, _, _, _ = deepspeed_tpu.initialize(model=gpt2.make_module(cfg), config=config, mesh=mesh, seed=7)
    engine.train_batch({"input_ids": np.random.default_rng(3).integers(0, V, (16, 128), dtype=np.int32)})
    text = engine._compiled_step().as_text()
    found = sorted((c.kind, c.shapes[0][0]) for c in introspect.loop_collectives(text))
    assert found == sorted(PARENT_LOOP_COLLECTIVES[stage])
    counts = {}
    for parsed in introspect.instructions_by_computation(text).values():
        for ni in parsed:
            op = ni.op.removesuffix("-start")
            if op in ("all-gather", "all-reduce", "reduce-scatter", "all-to-all") and not ni.op.endswith("-done"):
                counts[op] = counts.get(op, 0) + 1
                assert {dt for dt, _ in ni.result_shapes} == {"f32"}, ni.name
    assert counts == PARENT_COLLECTIVES[stage]
    # stage 3 carries the leaves that are sharded as their moments are; stages 1 and 2 none (the masters are
    # gathered after the update: a carried copy would be gathered beside them); stage 0 all
    n = len(jax.tree.leaves(engine.state.params))
    assert len(engine._carried) == {3: 5, 2: 0, 1: 0, 0: n}[stage]


# -- whoever hands the engine a state -----------------------------------------------------

def test_a_state_handed_to_the_engine_gets_its_copy_made_again(tmp_path):
    engine = _engine(stage=0, dtype="bf16")
    batches = random_batches(3, engine.train_batch_size, seed=5)
    engine.train_batch(batches[0])
    poisoned = jax.tree.map(lambda p: p * 0 + 1, engine.state.params)
    engine.state = engine.state._replace(params=poisoned)   # a stale copy beside new masters
    _assert_same_bits(engine.state.compute_params,
                      eng_mod._cast_leaves(poisoned, jnp.bfloat16, engine._carried), "copy")
    # a checkpoint holds no copy, and a load makes one from the masters it read
    engine.save_checkpoint(str(tmp_path), tag="t")
    want = jax.device_get(eng_mod._persistent(engine.state))
    engine.train_batch(batches[1])
    engine.load_checkpoint(str(tmp_path), tag="t")
    _assert_same_bits(eng_mod._persistent(engine.state), want, "loaded state")
    _assert_same_bits(engine.state.compute_params,
                      eng_mod._cast_leaves(engine.state.params, jnp.bfloat16, engine._carried), "copy")
    assert np.isfinite(float(engine.train_batch(batches[2])["loss"]))
    # float32 compute, and a path that runs several programs a step, carry nothing
    assert _engine(stage=0, dtype="fp32").state.compute_params == ()
