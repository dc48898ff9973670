"""ISSUE 40: under a ``dp`` axis the block's residual stream is pinned to the
batch axis (``partitioning.on_batch_axis``), so that ZeRO-3's collectives are
the weights' and none carries an activation. On the CPU's forced devices: what
the compiled step holds, that the numbers are data parallelism's, that one chip
and the serving programs trace nothing of it, and the gauges that say so."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec

import deepspeed_tpu
from deepspeed_tpu.models import gpt2
from deepspeed_tpu.parallel.topology import MeshSpec
from deepspeed_tpu.runtime.zero import partitioning
from deepspeed_tpu.telemetry import introspect, spans

B, S, V = 16, 128, 512
KINDS = introspect.COLLECTIVE_KINDS


def _engine(dp=4, tp=1, stage=3, remat=True, layers=2, dtype="bf16", telemetry=None, optimizer=None):
    cfg = gpt2.GPT2Config(n_embd=256, n_head=4, n_layer=layers, n_positions=S, vocab_size=V, remat=remat,
                          attn_impl="jnp")   # float32 masters; the engine casts for compute
    config = {"train_micro_batch_size_per_gpu": B // dp, "gradient_accumulation_steps": 1,
              "optimizer": optimizer or {"type": "AdamW", "params": {"lr": 1e-3, "weight_decay": 0.01}},
              "zero_optimization": {"stage": stage}, "gradient_clipping": 1.0, "steps_per_print": 10**9}
    if dtype == "bf16":
        config["bf16"] = {"enabled": True}
    if telemetry:   # a directory for its trace files
        config["telemetry"] = {"enabled": True, "trace_path": telemetry}
    mesh = MeshSpec(dp=dp, tp=tp, devices=jax.devices()[: dp * tp]).build_mesh()
    engine, _, _, _ = deepspeed_tpu.initialize(model=gpt2.make_module(cfg), config=config, mesh=mesh, seed=7)
    return engine


def _batches(n, seed=3):
    rng = np.random.default_rng(seed)
    return [{"input_ids": rng.integers(0, V, (B, S), dtype=np.int32)} for _ in range(n)]


def _losses(engine, n):
    return np.array([float(engine.train_batch(b)["loss"]) for b in _batches(n)])


def _unpinned(monkeypatch):
    """The parent build: the helper gives its operand back."""
    monkeypatch.setattr(gpt2, "on_batch_axis", lambda x, axis="dp": x)


def _census(engine):
    found = introspect.loop_collectives(engine._compiled_step().as_text())
    return found, [c for c in found if c.carries(B * S)]


def _strip(text):
    text = re.sub(r",? ?metadata=\{[^}]*\}", "", text)
    return text[text.index("\n\n", text.index("StackFrames")):] if "StackFrames" in text else text


# -- what the compiled step holds --------------------------------------------------------

@pytest.mark.parametrize("stage, remat", [(3, True), (3, False), (2, True), (1, True)])
def test_the_loop_bodies_hold_no_all_to_all_and_no_activation_shaped_collective(stage, remat):
    engine = _engine(stage=stage, remat=remat)
    engine.train_batch(_batches(1)[0])
    found, activations = _census(engine)
    assert [c for c in found if c.kind == "all_to_all"] == []
    assert activations == []
    gathers = [c for c in found if c.kind == "all_gather"]
    if stage == 3:   # the weights', at each use: forward, (recompute,) backward
        assert len(gathers) >= 6 and all(c.nbytes >= 256 * 256 * 2 for c in gathers)
        assert {c.shapes[0][1][-2:] for c in gathers} >= {(256, 768), (256, 1024), (1024, 256)}
    else:            # parameters replicated: nothing to gather
        assert gathers == []


def test_the_unpinned_stage_3_step_runs_the_layer_tensor_parallel_over_dp(monkeypatch):
    """What the pin cures, so that the tests above cannot pass by the census
    seeing nothing: the parent's step gathers the global batch's activations
    and re-lays them with all-to-alls."""
    _unpinned(monkeypatch)
    engine = _engine(stage=3)
    engine.train_batch(_batches(1)[0])
    found, activations = _census(engine)
    assert len([c for c in found if c.kind == "all_to_all"]) >= 4
    assert {c.kind for c in activations} >= {"all_gather"}
    assert any(dims[:2] == (B, S) for c in activations for _, dims in c.shapes)


# -- the numbers are data parallelism's ----------------------------------------------------

def test_three_steps_equal_the_unpinned_builds(monkeypatch):
    """Losses within 2e-4, and the masters where the parent's are within bf16
    rounding: under SGD a master moves by the sum of its (bf16) gradients, so
    the two builds' masters may differ by a few bf16 roundings of the leaf's
    largest movement (4 for a matrix, 16 for a bias or a norm; AdamW's first steps move every element by the learning
    rate whatever its gradient's size: a rounding flips a sign there)."""
    sgd = {"type": "SGD", "params": {"lr": 0.1}}
    pinned = _engine(optimizer=sgd)
    init = jax.tree.map(lambda x: np.asarray(x, np.float32), pinned.state.params)
    got = _losses(pinned, 3)
    _unpinned(monkeypatch)
    parent = _engine(optimizer=sgd)
    want = _losses(parent, 3)
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=0)
    moved = 0.0
    for a, b, w0 in zip(*(jax.tree.leaves(t) for t in (pinned.state.params, parent.state.params, init))):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        ulps = 4 if a.ndim >= 3 or a.shape[0] > 2 else 16   # (a matrix; a bias or a norm: see the gradients' test)
        assert np.abs(a - b).max() <= ulps * 2.0 ** -8 * np.abs(b - w0).max(), (a.shape, np.abs(a - b).max())
        moved = max(moved, np.abs(b - w0).max())
    assert moved > 1e-4   # (the steps did move the masters)


def test_per_leaf_gradients_at_dp4_are_dp1s_within_bf16_rounding():
    """ISSUE 40 item 3: the layer's weight gradients are four chips' products
    summed where they were one product over the global batch; both round to
    bf16. Held per leaf against the leaf's largest value."""
    batch = jnp.asarray(_batches(1)[0]["input_ids"])
    grads = {}
    for dp in (1, 4):
        engine = _engine(dp=dp)
        cparams = jax.tree.map(lambda x: x.astype(jnp.bfloat16), engine.state.params)

        def grad(p, ids, loss_fn=engine.module.loss_fn):
            return jax.grad(lambda p: loss_fn(p, {"input_ids": ids}, None, True)[0])(p)

        with engine._mesh_scope():
            g = jax.jit(grad, out_shardings=engine.grad_shardings)(cparams, batch)
        grads[dp] = jax.tree.map(lambda x: np.asarray(x, np.float32), g)
    flat1, flat4 = jax.tree.leaves(grads[1]), jax.tree.leaves(grads[4])
    assert len(flat1) == len(flat4) >= 12
    for a, b in zip(flat1, flat4):
        # bf16 keeps 8 bits. A matrix's gradient is four rounded products summed against one
        # rounded product (read: 0.9-2.0 roundings, the unpinned build's 1.7-2.0); a bias's or a
        # norm's is a sum over the tokens whose four partials cancel (7-12, the unpinned 4-5)
        ulps = 4 if a.ndim >= 3 or a.shape[0] > 2 else 16
        assert np.abs(a - b).max() <= ulps * 2.0 ** -8 * np.abs(a).max(), (a.shape, np.abs(a - b).max(), np.abs(a).max())


def test_twenty_steps_stay_as_close_to_dp1_as_the_unpinned_build_does(monkeypatch):
    one = _losses(_engine(dp=1), 20)
    pinned = _losses(_engine(dp=4), 20)
    _unpinned(monkeypatch)
    parent = _losses(_engine(dp=4), 20)
    assert one[-1] < one[0]   # it trains
    # the same bf16 noise, by another order of the same sums: no further off than the parent's own
    assert np.abs(pinned - one).max() <= max(np.abs(parent - one).max(), 2e-4)


# -- who is left alone ------------------------------------------------------------------------

def test_on_one_dp_rank_the_compiled_text_is_the_unpinned_builds(monkeypatch):
    def text():
        engine = _engine(dp=1)
        engine.train_batch(_batches(1)[0])
        return engine._compiled_step().as_text()

    pinned = text()
    _unpinned(monkeypatch)
    assert _strip(pinned) == _strip(text())
    assert "sharding_constraint" not in pinned


def test_dp2_tp2_keeps_its_parity_and_the_pin_leaves_tp_to_the_partitioner():
    one = _losses(_engine(dp=1, dtype="f32"), 3)
    both = _engine(dp=2, tp=2, dtype="f32")
    np.testing.assert_allclose(_losses(both, 3), one, rtol=2e-5, atol=2e-5)
    # the constraint names dp on the batch and nothing else
    with jax.set_mesh(both.mesh):
        jaxpr = jax.make_jaxpr(partitioning.on_batch_axis)(jnp.zeros((B, S, 256)))
    (eqn,) = [e for e in jaxpr.eqns if e.primitive.name == "sharding_constraint"]
    spec = eqn.params["sharding"].spec
    assert spec[0] == "dp" and all(s is PartitionSpec.UNCONSTRAINED for s in spec[1:])
    # and tp still splits the layer: its collectives are in the loop bodies
    found, activations = _census(both)
    assert found and activations == [] and [c for c in found if c.kind == "all_gather"]


@pytest.mark.parametrize("case", ["no-mesh", "dp1", "manual", "indivisible"])
def test_the_helper_returns_its_operand_where_there_is_no_dp_axis_to_state(case):
    from jax import shard_map

    x = jnp.zeros((6 if case == "indivisible" else 8, 4))
    if case == "no-mesh":
        assert partitioning.on_batch_axis(x) is x
        return
    n = 1 if case == "dp1" else 4
    mesh = MeshSpec(dp=n, devices=jax.devices()[:n]).build_mesh()
    with jax.set_mesh(mesh):
        if case == "manual":   # inside a shard_map over dp the batch is this rank's own
            f = shard_map(partitioning.on_batch_axis, in_specs=PartitionSpec("dp"), out_specs=PartitionSpec("dp"))
        else:
            f = partitioning.on_batch_axis
        assert "sharding_constraint" not in str(jax.make_jaxpr(f)(x))


def test_a_serving_decode_program_is_the_unpinned_builds(monkeypatch):
    from deepspeed_tpu.inference.engine import InferenceEngine
    from deepspeed_tpu.telemetry import parts

    serving = dict(max_slots=3, page_size=4, num_pages=96, max_prompt_len=40, max_new_tokens=4,
                   prefill_chunk_tokens=8, temperature=0.0, kv_cache_dtype="float32")

    def texts():
        parts.clear()
        cfg = gpt2.get_config("gpt2-tiny", attn_impl="jnp")
        srv = InferenceEngine(gpt2.make_module(cfg), params=gpt2.init_params(cfg, jax.random.PRNGKey(0)),
                              dtype=jnp.float32).serve(dict(serving))
        srv._ensure_compiled()
        out = {name: parts._programs[name]() for name in parts.registered()}
        parts.clear()
        return out

    pinned = texts()
    _unpinned(monkeypatch)
    null = texts()
    assert set(pinned) == {"jit_prefill_fn", "jit_decode_fn", "jit_chunk_decode_fn"}
    for name, text in pinned.items():
        assert _strip(text) == _strip(null[name]), name
        assert "sharding_constraint" not in text


# -- the gauges ----------------------------------------------------------------------------------

@pytest.mark.parametrize("dp", [4, 1])
def test_the_gauges_say_whether_the_mechanism_engaged(dp, tmp_path):
    t_start = spans._clock()
    engine = _engine(dp=dp, telemetry=str(tmp_path / "traces"))
    engine.train_batch(_batches(1)[0])
    reg = engine.telemetry.registry
    n = reg.gauge("train_step_collectives", "", labelnames=("kind", "operand"))
    nbytes = reg.gauge("train_step_collective_bytes", "", labelnames=("kind",))
    got = {(k, o): n.value(kind=k, operand=o) for k in KINDS for o in ("weight", "activation")}
    (attrs,) = [p[3] for p in spans.phases(since=t_start) if p[0] == "ds.init.programs"]
    if dp == 1:
        assert set(got.values()) == {0.0} and {nbytes.value(kind=k) for k in KINDS} == {0.0}
        assert attrs["collectives"] == "all_gather=0w+0a reduce_scatter=0w+0a all_reduce=0w+0a all_to_all=0w+0a"
        return
    assert got[("all_to_all", "weight")] == got[("all_to_all", "activation")] == 0
    assert all(got[(k, "activation")] == 0 for k in KINDS)
    assert got[("all_gather", "weight")] >= 6 and nbytes.value(kind="all_gather") >= 6 * 256 * 768 * 2
    assert got[("reduce_scatter", "weight")] + got[("all_reduce", "weight")] >= 1   # the gradients' reduction
    assert re.fullmatch(r"all_gather=\d+w\+0a reduce_scatter=\d+w\+0a all_reduce=\d+w\+0a all_to_all=0w\+0a",
                        attrs["collectives"])


# -- the reader of the text, on the forms a backend writes --------------------------------------

HAND = """HloModule jit_step, entry_computation_layout={()->f32[]}

%sum (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %add = f32[] add(%a, %b)
}

%fused_computation.1 (p: bf16[1,64,32]) -> (bf16[1,64,32], bf16[1,64,128]) {
  %p = bf16[1,64,32]{2,1,0} parameter(0)
  %all-gather.7 = bf16[1,64,128]{2,1,0} all-gather(%p), dimensions={2}, metadata={op_name="jit(step)/while/body/dspart.mlp/dot_general"}
  ROOT %t = (bf16[1,64,32]{2,1,0}, bf16[1,64,128]{2,1,0}) tuple(%p, %all-gather.7)
}

%async_collective_fusion.2 (p: bf16[1,64,32]) -> bf16[1,64,128] {
  %p.1 = bf16[1,64,32]{2,1,0} parameter(0)
  ROOT %all-gather.8 = bf16[1,64,128]{2,1,0} all-gather(%p.1), dimensions={2}
}

%fused_computation.3 (p: bf16[1,64,32]) -> bf16[1,64,128] {
  %p.2 = bf16[1,64,32]{2,1,0} parameter(0)
  ROOT %all-gather.9 = bf16[1,64,128]{2,1,0} all-gather(%p.2), dimensions={2}
}

%all-reduce-scatter.4 (p: bf16[64,128]) -> bf16[16,128] {
  %p.3 = bf16[64,128]{1,0} parameter(0)
  %all-reduce.5 = bf16[64,128]{1,0} all-reduce(%p.3), to_apply=%sum
  ROOT %dynamic-slice.6 = bf16[16,128]{1,0} dynamic-slice(%all-reduce.5), dynamic_slice_sizes={16,128}
}

%body (carry: (s32[], bf16[8,16,32])) -> (s32[], bf16[8,16,32]) {
  %carry = (s32[], bf16[8,16,32]{2,1,0}) parameter(0)
  %w = bf16[1,64,32]{2,1,0} constant(0)
  %async-collective-start = (bf16[1,64,32]{2,1,0}, bf16[1,64,128]{2,1,0}) fusion(%w), kind=kCustom, calls=%fused_computation.1
  %fusion.10 = bf16[1,64,128]{2,1,0} fusion(%w), kind=kOutput, calls=%async_collective_fusion.2
  %async-collective-done = bf16[1,64,128]{2,1,0} fusion(%w), kind=kCustom, calls=%fused_computation.3
  %g = bf16[64,128]{1,0} constant(0)
  %fusion.11 = bf16[16,128]{1,0} fusion(%g), kind=kCustom, calls=%all-reduce-scatter.4, metadata={op_name="jit(step)/transpose(jvp())/while/body/dspart.mlp/dot_general"}
  %x = bf16[8,16,32]{2,1,0} get-tuple-element(%carry), index=1
  %all-gather.12 = bf16[32,16,32]{2,1,0} all-gather(%x), dimensions={0}, metadata={op_name="jit(step)/while/body/dspart.attn.qkv/dot_general"}
  %all-to-all.13 = bf16[4,8,16,32]{3,2,1,0} all-to-all(%all-gather.12), dimensions={0}
  %all-reduce-start.14 = (f32[32]{0}, f32[32]{0}) all-reduce-start(%w), to_apply=%sum
  %all-reduce-done.15 = f32[32]{0} all-reduce-done(%all-reduce-start.14)
  %i = s32[] get-tuple-element(%carry), index=0
  ROOT %out = (s32[], bf16[8,16,32]{2,1,0}) tuple(%i, %x)
}

%cond (carry.1: (s32[], bf16[8,16,32])) -> pred[] {
  %carry.1 = (s32[], bf16[8,16,32]{2,1,0}) parameter(0)
  ROOT %lt = pred[] constant(true)
}

ENTRY %main () -> f32[] {
  %init = (s32[], bf16[8,16,32]{2,1,0}) constant(0)
  %while.1 = (s32[], bf16[8,16,32]{2,1,0}) while(%init), condition=%cond, body=%body
  %outside = f32[4096]{0} all-reduce(%init), to_apply=%sum
  ROOT %r = f32[] constant(0)
}
"""


def test_loop_collectives_counts_each_collective_once_in_every_spelling():
    found = {c.name: c for c in introspect.loop_collectives(HAND)}
    # the async pair once, by its start; the fusion that runs beside it and the one that awaits it: passed over
    assert set(found) == {"async-collective-start", "fusion.11", "all-gather.12", "all-to-all.13", "all-reduce-start.14"}
    start = found["async-collective-start"]
    assert (start.kind, start.shapes, start.nbytes, start.overlapped) == (
        "all_gather", (("bf16", (1, 64, 128)),), 64 * 128 * 2, True)
    assert start.op_name.endswith("dspart.mlp/dot_general")
    scatter = found["fusion.11"]     # the shard, not the all-reduce inside
    assert (scatter.kind, scatter.shapes, scatter.nbytes, scatter.overlapped) == (
        "reduce_scatter", (("bf16", (16, 128)),), 16 * 128 * 2, False)
    assert (found["all-reduce-start.14"].kind, found["all-reduce-start.14"].nbytes,
            found["all-reduce-start.14"].overlapped) == ("all_reduce", 128, True)
    assert not found["all-gather.12"].overlapped
    # 32 x 16 tokens of 32 features: the global batch, and the all-to-all that re-lays it
    tokens = 32 * 16
    assert [n for n, c in found.items() if c.carries(tokens)] == ["all-gather.12", "all-to-all.13"]
    assert not start.carries(tokens) and not scatter.carries(tokens)
