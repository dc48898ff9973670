"""``telemetry.parts``: the closed vocabulary of scopes, the part table read
from a compiled program's text, and the registry that holds callables. The
hand-written HLO pins the table's rules; the compiled programs (``gpt2-tiny``
training, the three served families' decode and mixed programs, on the CPU)
pin that the scopes sit where the matmuls are and change no instruction.
Nothing here is a device number."""

import contextlib
import gc
import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.inference.engine import InferenceEngine
from deepspeed_tpu.models import exaone_moe, gpt2, mistral4
from deepspeed_tpu.telemetry import parts

from .test_serving_exaone import CFG as KX_CFG
from .test_serving_mistral4 import CFG as MS4_CFG


@pytest.fixture
def registry(monkeypatch):
    """An empty registry for one test, the process's own put back after it."""
    monkeypatch.setattr(parts, "_programs", {})
    monkeypatch.setattr(parts, "_built", {})


# -- the vocabulary -----------------------------------------------------------

def test_a_scope_outside_the_vocabulary_is_refused():
    with pytest.raises(ValueError, match="attention"):
        parts.part("attention")
    with parts.part("attn.core"):
        pass
    assert len(set(parts.PARTS)) == len(parts.PARTS) == 19      # 13, since PR 43 ssm.proj and ssm.scan, since PR 49 attn.cca, since PR 52 lin.proj and lin.scan, since PR 57 hc.mix
    with parts.part("ssm.scan"), parts.part("ssm.proj"):
        pass


@pytest.mark.parametrize("op_name, part, phase", [
    ("jit(train_step)/jvp()/while/body/closed_call/dspart.mlp/dot_general", "mlp", "fwd"),
    ("jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/dspart.attn.qkv/dot_general", "attn.qkv", "bwd"),
    ("jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/rematted_computation/dspart.norm/div",
     "norm", "recompute"),
    ("jit(train_step)/dspart.optim/sub", "optim", "none"),
    ("jit(train_step)/transpose(jvp(dspart.head))/convert_element_type", "head", "bwd"),
    ("jit(decode_fn)/dspart.mlp/dspart.moe.route/top_k", "moe.route", "none"),   # the innermost scope wins
    ("jit(decode_fn)/dspart.attn.core/decode_fn/pallas_call", "attn.core", "none"),
    ("jit(train_step)/jvp()/while/body/dynamic_slice", None, "fwd"),
    ("jit(f)/dspart.nothing/add", None, "none"),                                 # not of the vocabulary
])
def test_part_and_phase_of_an_op_name(op_name, part, phase):
    assert parts.part_of(op_name) == part
    assert parts.phase_of(op_name) == phase


# -- the table's rules, on a hand-written module ---------------------------------

HLO = """HloModule jit_step, is_scheduled=true

FileNames
1 "/repo/deepspeed_tpu/models/gpt2.py"
2 "/repo/deepspeed_tpu/ops/pallas/decode_attention.py"

FunctionNames
1 "_mlp"

FileLocations
1 {file_name_id=1 function_name_id=1 line=320 end_line=320 column=8 end_column=40}
2 {file_name_id=2 function_name_id=1 line=443 end_line=443 column=8 end_column=40}

StackFrames
1 {file_location_id=1 parent_frame_id=1}
2 {file_location_id=2 parent_frame_id=2}

%fused_dot (p0: bf16[8,64], p1: bf16[64,64], p2: bf16[8,64]) -> bf16[8,64] {
  %p0 = bf16[8,64]{1,0} parameter(0)
  %p1 = bf16[64,64]{1,0} parameter(1)
  %p2 = bf16[8,64]{1,0} parameter(2)
  %dot.1 = bf16[8,64]{1,0} dot(%p0, %p1), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(step)/jvp()/dspart.mlp/dot_general" stack_frame_id=1}
  ROOT %add.1 = bf16[8,64]{1,0} add(%dot.1, %p2), metadata={op_name="jit(step)/jvp()/dspart.attn.out/add" stack_frame_id=1}
}

%fused_norm (q0: bf16[8,64]) -> bf16[8,64] {
  %q0 = bf16[8,64]{1,0} parameter(0)
  ROOT %mul.1 = bf16[8,64]{1,0} multiply(%q0, %q0), metadata={op_name="jit(step)/transpose(jvp())/while/body/checkpoint/rematted_computation/dspart.norm/mul"}
}

%body (arg: (s32[], bf16[8,64])) -> (s32[], bf16[8,64]) {
  %arg = (s32[], bf16[8,64]{1,0}) parameter(0)
  %i = s32[] get-tuple-element(%arg), index=0
  %h = bf16[8,64]{1,0} get-tuple-element(%arg), index=1
  %fusion.7 = bf16[8,64]{1,0} fusion(%h), kind=kLoop, calls=%fused_norm, backend_config={"x":[1,2]}
  ROOT %tuple.1 = (s32[], bf16[8,64]{1,0}) tuple(%i, %fusion.7)
}

%cond (arg.1: (s32[], bf16[8,64])) -> pred[] {
  %arg.1 = (s32[], bf16[8,64]{1,0}) parameter(0)
  %i.1 = s32[] get-tuple-element(%arg.1), index=0
  %c = s32[] constant(4)
  ROOT %lt.1 = pred[] compare(%i.1, %c), direction=LT, metadata={op_name="jit(step)/transpose(jvp())/while/cond/lt"}
}

ENTRY %main (x: bf16[8,64], w: bf16[64,64]) -> bf16[8,64] {
  %x = bf16[8,64]{1,0} parameter(0), metadata={op_name="x"}
  %w = bf16[64,64]{1,0} parameter(1), metadata={op_name="w"}
  %copy.1 = bf16[64,64]{0,1} copy(%w), metadata={op_name="w"}
  %fusion.3 = bf16[8,64]{1,0} fusion(%x, %copy.1, %x), kind=kOutput, calls=%fused_dot, metadata={op_name="jit(step)/jvp()/dspart.attn.out/add" stack_frame_id=1}
  %decode_fn.4 = bf16[8,64]{1,0} custom-call(%fusion.3), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/pallas_call" stack_frame_id=2}
  %copy.2 = bf16[8,64]{1,0} copy(%decode_fn.4)
  %zero = s32[] constant(0)
  %tuple.2 = (s32[], bf16[8,64]{1,0}) tuple(%zero, %copy.2)
  %while.1 = (s32[], bf16[8,64]{1,0}) while(%tuple.2), condition=%cond, body=%body, metadata={op_name="jit(step)/transpose(jvp())/while"}
  %gte.1 = bf16[8,64]{1,0} get-tuple-element(%while.1), index=1
  %copy-start.1 = (bf16[8,64]{1,0}, bf16[8,64]{1,0}, u32[]) copy-start(%gte.1)
  %copy-done.1 = bf16[8,64]{1,0} copy-done(%copy-start.1)
  ROOT %sub.1 = bf16[8,64]{1,0} subtract(%copy-done.1, %x), metadata={op_name="jit(step)/dspart.optim/sub"}
}
"""


@pytest.fixture(scope="module")
def hand_table():
    return parts.table_of(HLO)


def test_module_name_is_what_the_trace_shows():
    assert parts.module_name(HLO) == "jit_step"
    assert parts.module_name("no module here") is None


def test_a_fusion_with_a_dot_inside_whose_root_is_an_add(hand_table):
    e = hand_table["fusion.3"]
    assert (e.part, e.phase, e.has_dot) == ("attn.out", "fwd", True)    # its root's part: the add's
    assert e.parts_inside == ("attn.out", "mlp")                        # drawn across a boundary: mixed
    assert e.source == "/repo/deepspeed_tpu/models/gpt2.py:320"
    assert hand_table["dot.1"].part == "mlp" and hand_table["dot.1"].has_dot
    assert not hand_table["add.1"].has_dot


def test_a_loop_body_is_part_of_the_table(hand_table):
    e = hand_table["fusion.7"]       # no metadata of its own: its root's, inside the while's body
    assert (e.part, e.phase, e.has_dot, e.parts_inside) == ("norm", "recompute", False, ("norm",))
    assert hand_table["while.1"].part is None and hand_table["while.1"].phase == "bwd"
    assert hand_table["lt.1"].part is None


def test_an_instruction_without_metadata_takes_its_one_neighbours_part(hand_table):
    assert hand_table["copy.1"].part == "attn.out"            # one consumer: the fusion
    assert hand_table["copy.1"].op_name == "w"               # an argument's name names no operation
    assert hand_table["copy-start.1"].part == "optim"         # through copy-done to the subtract
    assert hand_table["copy-done.1"][:2] == ("optim", "none")
    # its consumer (a tuple) has no part: its one operand's producer is the kernel
    assert hand_table["copy.2"].part == "attn.core"
    assert hand_table["zero"].part is None and hand_table["x"].part is None


def test_an_unnamed_kernel_takes_the_part_of_its_file(hand_table):
    e = hand_table["decode_fn.4"]
    assert e.op_name == "jit(step)/pallas_call" and parts.part_of(e.op_name) is None
    assert (e.part, e.has_dot) == ("attn.core", True)
    assert e.source.endswith("ops/pallas/decode_attention.py:443")


def test_a_kernel_whose_source_a_transform_rewrote_takes_what_surrounds_it():
    hlo = HLO.replace("stack_frame_id=2}", "stack_frame_id=1}")   # as under checkpoint: the caller's line
    assert parts.table_of(hlo)["decode_fn.4"].part == "attn.out"  # its producer's and nothing else's
    alone = re.sub(r"custom-call\(%fusion.3\)", "custom-call(%x)", hlo)
    assert parts.table_of(alone)["decode_fn.4"].part is None


# -- the registry -------------------------------------------------------------

def test_register_stores_the_callable_uncalled_and_tables_builds_once(registry, tmp_path):
    calls = []

    def text():
        calls.append(1)
        return HLO

    parts.register("jit_step", text)
    parts.register("jit_gone", lambda: None)            # a program that is no more
    assert calls == [] and parts.registered() == ("jit_step", "jit_gone")
    got = parts.tables()
    assert calls == [1] and set(got) == {"jit_step"}
    assert got["jit_step"]["fusion.3"].part == "attn.out"
    assert parts.tables()["jit_step"] is got["jit_step"] and calls == [1]     # cached
    cost = parts.costs()["jit_step"]
    assert cost["bytes"] == len(HLO) and cost["instructions"] == len(got["jit_step"]) and cost["seconds"] >= 0
    parts.dump(str(tmp_path / "parts.json"))
    dumped = json.loads((tmp_path / "parts.json").read_text())
    assert dumped["jit_step"]["fusion.3"] == [
        "attn.out", "fwd", True, ["attn.out", "mlp"], "jit(step)/jvp()/dspart.attn.out/add",
        "/repo/deepspeed_tpu/models/gpt2.py:320"]
    parts.register("jit_step", text)                    # compiled again: the table is forgotten
    assert parts.tables() and calls == [1, 1]


def test_the_table_is_keyed_by_the_modules_own_name_and_a_failing_program_is_skipped(registry):
    def broken():
        raise RuntimeError("no executable")

    parts.register("some_label", lambda: HLO)
    parts.register("jit_broken", broken)
    assert set(parts.tables()) == {"jit_step"}


# -- the programs the package compiles ---------------------------------------------

def _strip(text):
    """An optimised module without what a scope may change: the metadata and
    the tables of files and frames it points into."""
    text = re.sub(r",? ?metadata=\{[^}]*\}", "", text)
    return text[text.index("\n\n", text.index("StackFrames")):] if "StackFrames" in text else text


def _train_engine(**model):
    from deepspeed_tpu.parallel.topology import MeshSpec

    cfg = gpt2.get_config("gpt2-tiny", attn_impl="jnp", **model)
    config = {"train_micro_batch_size_per_gpu": 2, "gradient_accumulation_steps": 1,
              "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}}, "gradient_clipping": 1.0,
              "zero_optimization": {"stage": 3}, "bf16": {"enabled": True}, "steps_per_print": 10**9}
    mesh = MeshSpec(dp=1, devices=jax.devices()[:1]).build_mesh()
    engine, _, _, _ = deepspeed_tpu.initialize(model=gpt2.make_module(cfg), config=config, mesh=mesh, seed=0)
    batch = {"input_ids": np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 32)).astype(np.int32)}
    return engine, batch


def test_the_training_step_every_dot_has_a_part_and_all_four_phases_occur(registry):
    engine, batch = _train_engine(remat=True)
    assert parts.registered() == ()
    engine.train_batch(batch)
    assert parts.registered() == ("jit_train_step",) and not parts._built    # registered, nothing rendered
    table = parts.tables()["jit_train_step"]
    dots = {n: e for n, e in table.items() if e.has_dot}
    assert len(dots) >= 12 and all(e.part for e in dots.values()), {n: e for n, e in dots.items() if not e.part}
    assert {e.part for e in dots.values()} == {"attn.qkv", "attn.core", "attn.out", "mlp", "head"}
    assert {e.phase for e in table.values() if e.part} == set(parts.PHASES)
    assert {e.phase for e in table.values() if e.part == "optim"} == {"none"}
    assert {e.part for e in table.values()} >= {"embed", "norm", "optim", "head"}
    # the registry holds the engine weakly: its state does not outlive it there
    del engine, table, dots
    gc.collect()
    parts.register("jit_other", lambda: None)     # (any call: the dead engine's text is asked for again below)
    parts._built.clear()
    assert parts.tables() == {}


def test_a_scope_is_metadata_the_stripped_program_is_the_null_scope_builds(monkeypatch):
    def text():
        engine, batch = _train_engine(remat=True)
        engine.train_batch(batch)
        return engine._compiled_step().as_text()

    scoped = text()
    monkeypatch.setattr(parts, "part", lambda name: contextlib.nullcontext())
    null = text()
    assert "dspart." in scoped and "dspart." not in null
    assert _strip(scoped) == _strip(null)


SERVING = dict(max_slots=3, page_size=4, num_pages=96, max_prompt_len=40, max_new_tokens=4,
               prefill_chunk_tokens=8, temperature=0.0, kv_cache_dtype="float32")


def _served(family):
    if family == "gpt2":
        cfg = gpt2.get_config("gpt2-tiny", attn_impl="jnp")
        return InferenceEngine(gpt2.make_module(cfg), params=gpt2.init_params(cfg, jax.random.PRNGKey(0)),
                               dtype=jnp.float32)
    mod, cfg = (exaone_moe, exaone_moe.ExaoneMoEConfig.from_dict(KX_CFG)) if family == "exaone_moe" \
        else (mistral4, mistral4.Mistral4Config.from_dict(MS4_CFG))
    return deepspeed_tpu.init_inference(model=mod.make_module(cfg), dtype=jnp.float32, seed=3)


@pytest.mark.parametrize("family, expected", [
    ("gpt2", {"embed", "norm", "attn.qkv", "attn.core", "attn.out", "kv.write", "mlp", "head", "sample"}),
    ("exaone_moe", {"embed", "norm", "attn.qkv", "attn.core", "attn.out", "kv.write", "mlp", "moe.route",
                    "moe.experts", "head", "sample"}),
    ("mistral4", {"embed", "norm", "attn.qkv", "attn.core", "attn.out", "kv.write", "mlp", "moe.route",
                  "moe.experts", "head", "sample"}),
])
def test_a_served_familys_programs_every_dot_has_a_part(registry, family, expected):
    engine = _served(family)
    srv = engine.serve(dict(SERVING))
    srv._ensure_compiled()
    assert set(parts.registered()) == {"jit_decode_fn", "jit_chunk_decode_fn"}    # a server that chunks: no whole-prompt program
    whole = engine.serve(dict(SERVING, prefill_chunk_tokens=0))                  # (the registry holds a server weakly)
    whole._ensure_compiled()
    assert set(parts.registered()) == {"jit_prefill_fn", "jit_decode_fn", "jit_chunk_decode_fn"}
    assert not parts._built
    tables = parts.tables()
    for module in ("jit_decode_fn", "jit_chunk_decode_fn"):
        table = tables[module]
        dots = {n: e for n, e in table.items() if e.has_dot}
        assert dots and all(e.part for e in dots.values()), (module, {n: e for n, e in dots.items() if not e.part})
        assert {e.part for e in table.values() if e.part} == expected, module
        assert {e.phase for e in table.values()} == {"none"}
