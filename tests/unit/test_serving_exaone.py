"""The ``exaone_moe`` family through the paged programs at a small size on the
CPU (2 sparse layers after a dense one, 16 experts top-4 of which a chip holds
4, window 8, page 4, chunk 8), in float32: the served streams against the
float32 reference's full forward (``perfbench/reference_exaone_moe.py``, which
imports nothing from the model's module), two kinds of KV state side by side,
the spans and counters, and the refusals."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models import exaone_moe as m
from deepspeed_tpu.telemetry import spans
from perfbench import reference_exaone_moe as reference

CFG = dict(
    vocab_size=96, hidden_size=32, intermediate_size=64, moe_intermediate_size=16, num_hidden_layers=3,
    num_attention_heads=4, num_key_value_heads=2, head_dim=8, sliding_window=8,
    layer_types=["sliding_attention", "full_attention", "sliding_attention"],
    mlp_layer_types=["dense", "sparse", "sparse"], num_experts=4, num_experts_published=16,
    expert_share={"chips": 4, "index": 1}, num_experts_per_tok=4, routed_scaling_factor=2.5, rms_norm_eps=1e-5,
    rope_parameters={"rope_theta": 1e6}, max_position_embeddings=512, initializer_range=0.25,
)
SERVING = dict(max_slots=3, page_size=4, num_pages=64, max_prompt_len=40, max_new_tokens=12,
               prefill_chunk_tokens=8, temperature=0.0)
PROMPTS = (5, 8, 19, 33, 40, 27, 9)     # ONE chunk (<= a chunk: first and last in one call) and 2-5 chunks; 33 and 40 wrap the ring
# The reference sums in another order than the programs (one product a layer
# against paged blocks and an online softmax), both in float32: the served
# token is the reference's argmax but for a tie closer than this.
GAP_TOL = 1e-4


@pytest.fixture(scope="module")
def mcfg():
    return m.ExaoneMoEConfig.from_dict(CFG)


@pytest.fixture(scope="module")
def engine(mcfg):
    return deepspeed_tpu.init_inference(model=m.make_module(mcfg), dtype=jnp.float32, seed=3)


def _serve(engine, prompts, **over):
    srv = engine.serve(dict(SERVING, **over))
    reqs = [srv.submit(p, max_new_tokens=12, seed=i) for i, p in enumerate(prompts)]
    srv.run()
    return srv, reqs


@pytest.fixture(scope="module")
def prompts():
    rng = np.random.default_rng(0)
    return [rng.integers(0, 96, n).astype(np.int32) for n in PROMPTS]


@pytest.fixture(scope="module")
def served(engine, prompts):
    return _serve(engine, prompts)


def test_weights_are_made_in_the_engines_dtype_leaf_by_leaf(engine):
    assert {x.dtype for x in jax.tree.leaves(engine.params)} == {jnp.dtype(jnp.float32)}
    bf = deepspeed_tpu.init_inference(model=m.make_module(m.ExaoneMoEConfig.from_dict(CFG)), dtype=jnp.bfloat16, seed=3)
    assert {x.dtype for x in jax.tree.leaves(bf.params)} == {jnp.dtype(jnp.bfloat16)}
    bias = np.asarray(engine.params["layers"][1]["moe"]["bias"])
    assert bias.shape == (16,) and np.abs(bias).min() > 0        # drawn, not zero
    assert engine.params["layers"][1]["moe"]["router"].shape == (32, 16)     # the router keeps its published width
    assert engine.params["layers"][1]["moe"]["experts"]["w_gate"].shape == (4, 32, 16)


def test_served_streams_are_the_references_past_a_ring_wrap_and_across_chunk_boundaries(engine, served, prompts):
    srv, reqs = served
    arch = reference.Arch.from_config(CFG)
    assert srv.ring_pages == 5 and 5 * 4 < max(PROMPTS)          # the ring wraps on the long prompts
    for r, p in zip(reqs, prompts):
        assert r.status == "finished" and len(r.tokens) == 12
        ids = np.concatenate([p, np.asarray(r.tokens, np.int32)])
        padded = np.zeros((64,), np.int32)
        padded[: len(ids)] = ids
        gap, _ = reference.served_gaps(engine.params, jnp.asarray(padded), len(p), len(ids), arch=arch)
        assert float(np.asarray(gap).max()) <= GAP_TOL, (len(p), np.asarray(gap).max())
    srv.drain(0.0)
    srv.check_no_leaks()


def test_served_logits_match_the_references_full_forward(engine, mcfg, prompts):
    """Prefill then decode through the paged programs, the logits themselves:
    the model's own whole-sequence forward is the programs' pieces under a
    dense mask, and the reference agrees with it to float32 rounding."""
    arch = reference.Arch.from_config(CFG)
    ids = jnp.asarray(prompts[4])
    want = np.asarray(reference.logits(engine.params, ids, arch))
    got = np.asarray(m.forward(mcfg, engine.params, ids[None])[0])
    np.testing.assert_allclose(got, want, atol=5e-5, rtol=1e-4)


def test_window_state_does_not_grow_with_context_and_the_paged_pool_holds_full_layers_only(engine, served):
    srv, _ = served
    ds = srv.decode_set
    page_bytes = 2 * 2 * 4 * 8 * 4          # K and V x kv heads x page x head_dim x float32
    assert ds.cache.k.shape == (1, 64, 2, 4, 8) and ds.n_layer == 1            # the one full layer
    assert ds.cache.win_k.shape == (2, 1 + 3 * 5, 2, 4, 8)                # two sliding layers, 3 slots x 5 pages + scratch
    g = srv.metrics.gauge("serving_kv_bytes", "", labelnames=("class",))
    assert g.value(**{"class": "window"}) == (3 * 5 + 1) * page_bytes * 2 == srv.stats()["kv_window_bytes"]
    assert g.value(**{"class": "paged"}) == 64 * page_bytes * 1
    assert srv.metrics.gauge("serving_window_pages_per_slot", "").value() == 5
    assert srv.metrics.gauge("serving_moe_experts_held", "").value() == 4
    longer = engine.serve(dict(SERVING, max_prompt_len=80, num_pages=128))
    assert longer.decode_set.cache_bytes()["window"] == ds.cache_bytes()["window"]
    assert longer.pages_per_slot > srv.pages_per_slot


def test_spans_and_counters_report_the_expert_loads(engine, prompts):
    t0 = spans._clock()      # not the last record's end: `since` is inclusive, and that record may be another server's emit
    srv, reqs = _serve(engine, prompts[:4])
    recs = [r for r in spans.snapshot(since=t0)]
    emits = [r[3] for r in recs if r[0] == "ds.serve.emit"]
    assert emits and all({"moe_pairs_held", "moe_pairs_routed", "moe_load_max", "moe_experts_hit"} <= set(a) for a in emits)
    disp = [r[3] for r in recs if r[0] == "ds.serve.decode.dispatch"]
    for a, d in zip(emits, disp):
        # tokens x top-4 x 2 sparse layers; a step that carried a chunk counts the chunk's tokens too
        rode = a["moe_pairs_routed"] // (4 * 2) - d["active"]
        assert a["moe_pairs_routed"] % (4 * 2) == 0 and 0 <= rode <= 8
        assert 0 <= a["moe_pairs_held"] <= a["moe_pairs_routed"] and a["moe_experts_hit"] <= 4 * 2
        assert a["moe_load_max"] <= d["active"] + rode
    every = [r[3] for r in recs if r[0] == "ds.serve.chunk"]
    chunks = [c for c in every if "moe_calls" in c]
    long = [len(p) for p in prompts[:4]]     # every prompt goes in chunks: one of 5 or 8 tokens in ONE (ISSUE 63)
    # a prompt reports the calls that rode no decode step; one that rode is in its step's emit
    assert sum(c["chunks"] + c["rode"] for c in every) == sum(-(-n // 8) for n in long)
    assert sum(c["moe_calls"] for c in chunks) == sum(c["chunks"] for c in every) > 0
    assert sum(c["rode"] for c in every) == srv.metrics.counter("serving_chunks_rode_total", "").value() > 0
    assert sum(a["moe_pairs_routed"] for a in emits + chunks) == (sum(d["active"] for d in disp) + sum(long)) * 4 * 2
    held = srv.metrics.counter("serving_moe_pairs_held_total", "").value()
    routed = srv.metrics.counter("serving_moe_pairs_routed_total", "").value()
    assert held == sum(a["moe_pairs_held"] for a in emits + chunks)
    assert routed == sum(a["moe_pairs_routed"] for a in emits + chunks) and 0 < held < routed
    # a window layer reads at most its window of a context: the mean over the layers is below the context
    d = [r[3] for r in recs if r[0] == "ds.serve.decode.dispatch"][-1]
    assert d["active"] * 8 * 2 // 3 <= d["attended"] < d["active"] * 52     # contexts of up to 52 tokens, window 8


def test_the_verify_step_emits_the_decode_steps_stream(engine, served, prompts):
    """Speculation on: T tokens a slot into the rings, each query bounded
    below; rejected drafts land where nothing reads. Same streams."""
    _, plain = served
    srv, spec = _serve(engine, prompts, speculative={"enabled": True, "k": 3, "ngram": 2})
    assert srv.ring_pages == 5
    for a, b in zip(plain, spec):
        assert list(a.tokens) == list(b.tokens)
    srv.drain(0.0)
    srv.check_no_leaks()


@pytest.mark.parametrize("section,what", [
    ({"prefix_cache": {"enabled": True}}, "serving.prefix_cache"),
    ({"prefix_cache": {"enabled": True}, "tiering": {"enabled": True}}, "serving.prefix_cache"),
    ({"kv_cache_dtype": "int8"}, "serving.kv_cache_dtype=int8"),
    ({"placement": {"tp": 2}}, "serving.placement.tp > 1"),
    ({"placement": {"disaggregate": True}}, "serving.placement.disaggregate"),
])
def test_mechanisms_that_know_one_kind_of_state_are_refused_by_name(engine, section, what):
    with pytest.raises(ValueError, match="sliding-window layers") as e:
        engine.serve(dict(SERVING, **section))
    assert what in str(e.value)


def test_tiering_alone_is_refused_by_name(engine):
    from deepspeed_tpu.runtime.config import ServingConfig

    cfg = ServingConfig.from_dict(dict(SERVING))
    cfg.tiering.enabled = True
    with pytest.raises(ValueError, match="serving.tiering"):
        engine.serve(cfg)


def test_a_model_without_the_pieces_is_refused():
    from deepspeed_tpu.serving import ServingEngine

    class NoFamily:
        model_config = object()

    with pytest.raises(ValueError, match="serving_family"):
        ServingEngine(NoFamily(), dict(SERVING))
