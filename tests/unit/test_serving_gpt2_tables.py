"""ISSUE 61: the tables a family's ``embed`` gathers rows of lie row-major on the
device, once, from load (``Placement.shard_params`` over ``GPT2Family.
row_gathered``), and no program re-lays them.

A CPU lays every array row-major, so there the rule has nothing to do and the
served tree is the engine's own. To drive the mechanism here the tests hand the
engine a ``wte`` and ``wpe`` laid COLUMN-major, as a v5e lays a table whose
rows are no whole lane tiles (``tests/unit/ops/test_mosaic_compile.py`` holds
that fact, and the programs, for a described chip)."""

import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.layout import Format, Layout

from deepspeed_tpu.models import gpt2
from deepspeed_tpu.serving import placement as plc

warnings.filterwarnings("ignore")

pytestmark = pytest.mark.serving

SERVING = {
    "max_slots": 4,
    "page_size": 4,
    "num_pages": 64,
    "max_prompt_len": 12,
    "max_new_tokens": 8,
    "kv_cache_dtype": "float32",
}
CONFIGS = {
    "gpt2-tiny": lambda: gpt2.get_config("gpt2-tiny", attn_impl="jnp"),
    # no multiple of 128 lanes wide, and a ``wte`` of 1.5 MB: a leaf the weights' census looks for
    "w192": lambda: gpt2.GPT2Config(
        n_embd=192, n_head=6, n_layer=2, vocab_size=2048, n_positions=128, attn_impl="jnp"
    ),
}
MODES = {"whole_prompt": {}, "chunked": {"prefill_chunk_tokens": 4}}


def _column_major(x):
    return jax.device_put(x, Format(Layout(major_to_minor=(1, 0)), x.sharding))


def _order(x):
    return tuple(x.format.layout.major_to_minor)


def _engine(cfg, moved: bool):
    from deepspeed_tpu.inference.engine import InferenceEngine

    params = gpt2.init_params(cfg, jax.random.PRNGKey(0))
    engine = InferenceEngine(gpt2.make_module(cfg), params=params, dtype=jnp.float32)
    if moved:
        engine.params = dict(
            engine.params, **{k: _column_major(engine.params[k]) for k in ("wte", "wpe")}
        )
        assert _order(engine.params["wte"]) == (1, 0)
    return engine


def _requests(vocab, seed=7):
    rs = np.random.RandomState(seed)
    return [
        (rs.randint(0, vocab, (n,)).astype(np.int32), new)
        for n, new in ((2, 6), (12, 3), (7, 8), (5, 1), (11, 6), (3, 6))
    ]


def _streams(srv, reqs):
    subs = [srv.submit(p, max_new_tokens=n, seed=i) for i, (p, n) in enumerate(reqs)]
    srv.run()
    srv.check_no_leaks()
    return [list(r.tokens) for r in subs]


@pytest.fixture(scope="module", params=list(CONFIGS))
def engines(request):
    cfg = CONFIGS[request.param]()
    return cfg, _engine(cfg, moved=False), _engine(cfg, moved=True)


class TestRowMajorRule:
    def test_a_leaf_that_lies_row_major_is_left_where_it_is(self):
        x = jnp.arange(12.0).reshape(3, 4)
        assert plc.row_major_format(x) is None and plc.lay_row_major(x) is x
        host = np.zeros((3, 4))
        assert plc.lay_row_major(host) is host  # no format to read: left alone

    def test_a_leaf_in_another_order_is_copied_once_into_its_own_order(self):
        x = _column_major(jnp.arange(12.0).reshape(3, 4))
        fmt = plc.row_major_format(x)
        assert tuple(fmt.layout.major_to_minor) == (0, 1) and fmt.sharding == x.sharding
        y = plc.lay_row_major(x)
        assert _order(y) == (0, 1) and y.shape == x.shape and y.dtype == x.dtype
        np.testing.assert_array_equal(np.asarray(y), np.asarray(x))
        assert plc.lay_row_major(y) is y

    def test_the_copys_program_is_never_read_from_the_persistent_cache(self, monkeypatch):
        """Found on the chip (PR 61): the results of a DESERIALIZED copy
        program report the device's default order, so the copy is compiled
        with the persistent cache off, the setting is restored behind it, and
        a result that reports another order than was asked is refused here,
        not at a program's first call."""
        seen = []
        real = jax.device_put

        def put(x, fmt):
            seen.append(jax.config.jax_enable_compilation_cache)
            return real(x, fmt)

        monkeypatch.setattr(plc.jax, "device_put", put)
        was = jax.config.jax_enable_compilation_cache
        x = _column_major(jnp.arange(12.0).reshape(3, 4))
        assert _order(plc.lay_row_major(x)) == (0, 1)
        assert seen[-1] is False and jax.config.jax_enable_compilation_cache == was
        monkeypatch.setattr(plc.jax, "device_put", lambda x, fmt: x)   # a result that lies as it lay
        with pytest.raises(plc.WeightLayoutError, match="was put as .* and reports"):
            plc.lay_row_major(x)
        assert jax.config.jax_enable_compilation_cache == was

    def test_only_the_leaves_the_family_names_are_laid(self):
        cfg = CONFIGS["gpt2-tiny"]()
        assert cfg.serving_family().row_gathered == ("wte", "wpe")
        params = gpt2.init_params(cfg, jax.random.PRNGKey(0))
        params["wte"] = _column_major(params["wte"])
        params["blocks"]["mlp"]["c_fc_b"] = _column_major(params["blocks"]["mlp"]["c_fc_b"])
        place = plc.Placement("shared", jax.devices()[:1], 1)
        assert place.shard_params(params)["wte"] is params["wte"]   # no family's word: the parent's tree
        placed = place.shard_params(params, ("wte", "wpe"))
        assert _order(placed["wte"]) == (0, 1) and placed["wpe"] is params["wpe"]
        assert placed["blocks"]["mlp"]["c_fc_b"] is params["blocks"]["mlp"]["c_fc_b"]


@pytest.mark.parametrize("mode", list(MODES))
@pytest.mark.parametrize("tp", [1, 2])
class TestServedOverATableTheDeviceLaidAnotherWay:
    def test_tokens_are_the_parents_and_the_table_is_one_array(self, engines, tp, mode):
        """Through the whole-prompt (or chunk) and decode programs at tp 1 and
        2: the streams of an engine whose tables came column-major are those of
        the engine whose tables lie as the CPU lays them (the parent's tree and
        programs), which are sequential ``generate``'s (``wte[ids]``, ``h @
        wte.T``); the served tables are row-major, bitwise the engine's, and at
        tp 1 the engine holds the SAME arrays: one table on the device."""
        if tp > jax.device_count():
            pytest.skip("needs the forced multi-device CPU mesh")
        cfg, plain, moved = engines
        serving = dict(SERVING, **MODES[mode], **({"placement": {"tp": tp}} if tp > 1 else {}))
        reqs = _requests(cfg.vocab_size)
        srv = plain.serve(serving)
        want = _streams(srv, reqs)
        if tp == 1:   # on a CPU nothing is laid anew: the served leaves ARE the engine's
            assert srv.decode_set.params["wte"] is plain.params["wte"]
            for (prompt, n), got in zip(reqs, want):
                ref = np.asarray(plain.generate(prompt[None, :], max_new_tokens=n))[0, len(prompt):]
                assert got == ref.tolist()
        table = np.asarray(moved.params["wte"])
        srv = moved.serve(serving)
        served = srv.decode_set.params
        for name in ("wte", "wpe"):
            assert _order(served[name]) == (0, 1)
            assert served[name].shape == plain.params[name].shape
        np.testing.assert_array_equal(np.asarray(served["wte"]), table)
        assert _streams(srv, reqs) == want
        if tp == 1:
            assert moved.params["wte"] is served["wte"] and moved.params["wpe"] is served["wpe"]
        # every program took the tables as they lie (``ProgramSet.aot`` checked it at build time)
        for rec in srv._program_info.values():
            if rec["kind"] in ("decode", "chunk", "prefill"):
                took = rec["exe"].input_formats[0][0]
                assert took["wte"].layout == served["wte"].format.layout


class TestLogitsAndSampling:
    def test_embedding_and_logits_are_bitwise_the_parents_formulas(self, engines):
        from deepspeed_tpu.ops.layer_norm import layer_norm_inference

        cfg, plain, moved = engines
        srv = moved.serve(SERVING)
        served, fam = srv.decode_set.params, cfg.serving_family()
        rs = np.random.RandomState(0)
        ids = jnp.asarray(rs.randint(0, cfg.vocab_size, (3, 5)), jnp.int32)
        pos = jnp.asarray(rs.randint(0, cfg.n_positions, (3, 5)), jnp.int32)
        p = plain.params
        np.testing.assert_array_equal(
            np.asarray(jax.jit(fam.embed)(served, ids, pos)),
            np.asarray(jax.jit(lambda p: p["wte"][ids] + p["wpe"][pos])(p)),
        )
        np.testing.assert_array_equal(   # the decode step's: a token a slot
            np.asarray(jax.jit(fam.embed)(served, ids[:, 0], pos[:, 0])),
            np.asarray(jax.jit(lambda p: (p["wte"][ids[:, 0]] + p["wpe"][pos[:, 0]])[:, None])(p)),
        )
        h = jnp.asarray(rs.standard_normal((3, 5, cfg.n_embd)), jnp.float32)

        def head(p, h):
            h = layer_norm_inference(h, p["ln_f"]["scale"], p["ln_f"]["bias"], cfg.layer_norm_epsilon)
            return (h @ p["wte"].T)[..., : cfg.vocab_size]

        got = np.asarray(jax.jit(fam.logits)(served, h))
        assert got.shape == (3, 5, cfg.vocab_size)
        np.testing.assert_array_equal(got, np.asarray(jax.jit(head)(p, h)))

    def test_sampled_streams_are_seeded_generates(self, engines):
        cfg, plain, moved = engines
        srv = moved.serve(dict(SERVING, temperature=0.8, top_k=5))
        reqs = _requests(cfg.vocab_size, seed=3)[:3]
        subs = [srv.submit(p, max_new_tokens=5, seed=100 + i) for i, (p, _) in enumerate(reqs)]
        srv.run()
        for (prompt, _), req in zip(reqs, subs):
            ref = np.asarray(plain.generate(
                prompt[None, :], max_new_tokens=5, temperature=0.8, top_k=5, seed=req.seed
            ))[0]
            np.testing.assert_array_equal(req.output, ref)


class TestTheServedTreeKeepsItsPublishedShapes:
    def test_a_checkpoint_round_trips_into_the_served_tree_and_back(self, engines):
        """Only the ORDER on the device changes: the served tree's ``wte`` has
        the published shape, so what reads the tree by shape (the loaders, the
        spec table's verifier) reads what it read before."""
        from deepspeed_tpu.checkpoint.megatron_loader import (
            gpt2_tree_to_megatron,
            megatron_to_gpt2_tree,
        )

        cfg, plain, moved = engines
        state = gpt2_tree_to_megatron(plain.params)
        assert state["embedding.word_embeddings.weight"].shape == (cfg.padded_vocab_size, cfg.n_embd)
        loaded = jax.tree.map(jnp.asarray, megatron_to_gpt2_tree(state))
        loaded.update({k: _column_major(loaded[k]) for k in ("wte", "wpe")})
        engine = _engine(cfg, moved=False)
        engine.params = loaded
        srv = engine.serve(SERVING)
        served = srv.decode_set.params
        assert served["wte"].shape == (cfg.padded_vocab_size, cfg.n_embd) and _order(served["wte"]) == (0, 1)
        assert srv.decode_placement.verify_rules(served, replicated_min_bytes=1 << 30) == []
        back = gpt2_tree_to_megatron(served)
        assert sorted(back) == sorted(state)
        for key, want in state.items():
            np.testing.assert_array_equal(back[key], want)


class TestWeightCensus:
    def test_every_program_is_counted_and_gpt2_tiny_reads_zero(self, engines):
        """``serving_weight_relayout_bytes``, one value a program, is what
        ``program_census`` counts and what the ``ds.init.programs`` phase
        logs. gpt2-tiny has no leaf of a megabyte and reads 0. The 192-wide
        stand-in's 1.5 MB ``wte`` is a leaf the census looks for, and on a CPU
        it is only COUNTED: this backend's matmul transposes the tied head's
        table in every program, the parent's too (what a v5e's compiler does
        with XL's is ``tests/unit/ops/test_mosaic_compile.py``'s: 0)."""
        from deepspeed_tpu.telemetry import spans

        cfg, _, moved = engines
        # the program set of an engine that chunks its cold prompts, and of one that does not (ISSUE 63)
        for chunk, programs in ((4, ["serving_decode", "serving_chunk_prefill"]), (0, ["serving_prefill", "serving_decode"])):
            srv = moved.serve(dict(SERVING, prefill_chunk_tokens=chunk))
            t0 = spans._clock()
            names = [name for name, _ in srv.executable_names()]
            assert names == programs
            big = srv.decode_set._weight_leaves()
            assert ((jnp.dtype("float32"), (cfg.vocab_size, cfg.n_embd)) in big) == (cfg.n_embd == 192)
            attrs = [r for r in spans.phases(since=t0) if r[0] == "ds.init.programs"][0][3]
            logged = dict(kv.split("=") for kv in attrs["weight_relayout"].split())
            gauge = srv.metrics.get("serving_weight_relayout_bytes")
            for name in names:
                rec = srv._program_info[name]
                ops, nbytes = rec["pset"].program_census(name, rec["exe"])[2:]
                assert gauge.value(program=name) == nbytes and logged[name] == f"{ops}/{nbytes}"
                if not big:
                    assert (ops, nbytes) == (0, 0)
                else:   # whole tables or nothing: a multiple of the one leaf's bytes
                    assert nbytes == ops * 4 * cfg.vocab_size * cfg.n_embd

    def test_the_count_on_hlo_as_the_tpu_compiler_prints_it(self):
        """Whole leaves copied or transposed, in the leaf's own type. Not a
        layer of a stacked leaf, and not the slices that read 0.4 to 1.2 GB a
        call in five families on the chip (PR 61) and were pieces of a LARGER
        leaf with another leaf's dims: a third of ``wqkv`` beside ``wo``, one
        expert of a stack beside the shared expert; nor an index array that
        happens to have a router's dims."""
        hlo = """
  %copy.78 = bf16[50257,1600]{1,0:T(8,128)(2,1)} copy(%p__wte__.1), sharding={replicated}
  %copy.79 = bf16[1024,1600]{1,0:T(8,128)(2,1)S(1)} copy(%p__wpe__.1)
  %slice.3 = bf16[1,1600,6400]{2,1,0:T(8,128)(2,1)} slice(%p__blocks__mlp__c_fc_w), slice={[3:4], [0:1600], [0:6400]}
  %transpose.1 = f32[6144,768]{1,0} transpose(%wq_b), dimensions={1,0}
  %slice.9 = bf16[2560,2560]{1,0:T(8,128)(2,1)} slice(%wqkv), slice={[0:2560], [2560:5120]}
  %slice.11 = bf16[1024,3584]{1,0:T(8,128)(2,1)} dynamic-slice(%w_down_stack, %i, %z, %z)
  %copy.12 = s32[2048,512]{1,0:T(8,128)} copy(%order)
  %convolution.33 = bf16[8,50257]{1,0:T(8,128)(2,1)} convolution(%fusion.192, %fusion.347), dim_labels=bf_oi->bf
  %copy.2 = bf16[8,1600]{1,0} copy(%rows)
"""
        bf16, f32 = jnp.dtype("bfloat16"), jnp.dtype("float32")
        leaves = {(bf16, (50257, 1600)), (bf16, (1024, 1600)), (bf16, (48, 1600, 6400)), (f32, (768, 6144)),
                  (bf16, (2560, 2560)), (bf16, (1024, 3584)), (bf16, (2048, 512)), (bf16, (1600,))}
        assert plc.weight_relayout(hlo, leaves) == (
            3, 2 * 50257 * 1600 + 2 * 1024 * 1600 + 4 * 6144 * 768
        )
        assert plc.weight_relayout(hlo, {(bf16, (48, 1600, 6400))}) == (0, 0)   # a layer of a stacked leaf is no whole leaf
        assert plc.weight_relayout(hlo, {(bf16, (768, 6144))}) == (0, 0)        # the leaf's own type, or it is no weight
        assert [plc._hlo_dtype(t) for t in ("bfloat16", "float32", "float16", "int8", "uint32", "int32")] == [
            "bf16", "f32", "f16", "s8", "u32", "s32"]

    def test_a_program_that_would_take_a_leaf_in_another_order_is_refused_at_build_time(
        self, engines, monkeypatch
    ):
        from types import SimpleNamespace as NS

        _, plain, _ = engines
        pset = plain.serve(SERVING).decode_set
        real = pset.placement.aot

        def aot(*a):
            exe = real(*a)
            took, kw = exe.input_formats
            other = dict(took[0], wte=NS(layout="vocabulary-minor"))
            return NS(input_formats=((other,) + took[1:], kw), output_formats=exe.output_formats)

        monkeypatch.setattr(pset.placement, "aot", aot)
        with pytest.raises(plc.WeightLayoutError, match="takes wte float32.* as vocabulary-minor"):
            pset.aot(lambda p, cache, i: (cache, i + p["wte"][0, 0].astype(jnp.int32)),
                     (jnp.zeros((), jnp.int32),), with_params=True)
        monkeypatch.setattr(pset.placement, "aot", real)
        # a leaf the program does not read has no layout in it, and is not asked for one
        pset.aot(lambda p, cache, i: (cache, i), (jnp.zeros((), jnp.int32),), with_params=True)
