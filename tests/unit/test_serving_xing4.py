"""The ``xing4_0`` family (a four-stream residual, mHC, around latent attention)
through the paged programs at a small size on the CPU (3 layers of which the
first is dense, hidden 64 in 4 streams, 4 heads with nope/rope/v 8/8/16,
``kv_lora_rank`` 16, 16 experts top-2 of which a chip holds 4, page 4, chunk
8), in float32: the served streams and logits against the float32 reference's
full forward (``perfbench/reference_xing4.py``, which imports nothing from the
model's module), absorbed against expanded, the whole-prompt program against
the chunked one, the share, the hand-over between ``qkv`` and
``after_attention``, and the refusals."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models import xing4 as m
from deepspeed_tpu.serving import model as smodel
from deepspeed_tpu.serving.kv_cache import Cache
from deepspeed_tpu.telemetry import parts, spans
from perfbench import reference_xing4 as reference

CFG = dict(
    vocab_size=96, hidden_size=64, intermediate_size=96, moe_intermediate_size=48, num_hidden_layers=3,
    num_attention_heads=4, q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=16,
    first_k_dense_replace=1, n_routed_experts=4, published={"n_routed_experts": 16}, expert_share={"chips": 4, "index": 1},
    num_experts_per_tok=2, n_shared_experts=1, n_group=1, topk_group=1, scoring_func="sigmoid", routed_scaling_factor=2.0,
    norm_topk_prob=True, rms_norm_eps=1e-6, hc_mult=4, hc_sinkhorn_iters=20, hc_eps=1e-6, mhc_h_res_clamp_min=-30,
    mhc_h_res_clamp_max=30, rope_theta=10000,
    rope_scaling={"factor": 8, "beta_fast": 32, "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1,
                  "original_max_position_embeddings": 16, "type": "yarn"},
    max_position_embeddings=4096, initializer_range=0.25,
)
SERVING = dict(max_slots=3, page_size=4, num_pages=64, max_prompt_len=40, max_new_tokens=12,
               prefill_chunk_tokens=8, temperature=0.0)
PROMPTS = (5, 8, 19, 33, 40, 27, 9)     # ONE chunk (<= a chunk: first and last in one call) and 2-5 chunks
# The reference sums in another order than the programs (expanded against
# absorbed, one product a layer against paged blocks and an online softmax),
# both in float32: the served token is the reference's argmax but for a tie
# closer than this.
GAP_TOL = 1e-4
K = 2 * 4 + 4 * 4


@pytest.fixture(scope="module")
def mcfg():
    return m.Xing4Config.from_dict(CFG)


@pytest.fixture(scope="module")
def engine(mcfg):
    return deepspeed_tpu.init_inference(model=m.make_module(mcfg), dtype=jnp.float32, seed=3)


@pytest.fixture(scope="module")
def prompts():
    rng = np.random.default_rng(0)
    return [rng.integers(0, 96, n).astype(np.int32) for n in PROMPTS]


@pytest.fixture(scope="module")
def served(engine, prompts):
    srv = engine.serve(dict(SERVING))
    reqs = [srv.submit(p, max_new_tokens=12, seed=i) for i, p in enumerate(prompts)]
    srv.run()
    return srv, reqs


def test_config_reads_the_published_keys_the_share_and_the_residuals_keys(mcfg):
    assert (mcfg.n_routed_experts, mcfg.n_routed_experts_published, mcfg.expert_chips, mcfg.expert_index) == (4, 16, 4, 1)
    assert mcfg.kv_width == 24 and mcfg.qk_head_dim == 16 and mcfg.original_max_position_embeddings == 16
    assert (mcfg.hc_mult, mcfg.hc_sinkhorn_iters, mcfg.hc_maps, mcfg.rope_factor) == (4, 20, K, 8.0)
    fam = mcfg.serving_family()
    m_ = 0.1 * np.log(8.0) + 1.0
    assert fam.sm_scale == pytest.approx(m_ * m_ / 4.0) and fam.query_scale(None) == 1.0
    assert fam.sparse_layers == (1, 2) and fam.experts_held == 4 and fam.n_layer == 3 and fam.stream_row_width == 256
    assert (fam.kv_pools, fam.head_dim, fam.v_width, fam.n_kv_head) == (1, 24, 16, 1)
    # the published configuration: the softmax scale carries yarn's m = 0.1 ln 64 + 1 squared
    big = m.Xing4Config()
    assert big.hc_maps == 24 and m.Xing4Family(big).sm_scale == pytest.approx(1.4159 ** 2 / np.sqrt(192), rel=1e-4)


@pytest.mark.parametrize("change,match", [
    ({"hc_mult": 1}, "hc_mult=1"), ({"n_group": 2}, "n_group=2"), ({"scoring_func": "softmax"}, "scoring_func='softmax'"),
    ({"n_routed_experts": 5}, "is not the router's"), ({"n_shared_experts": 2}, "one shared expert"),
    ({"first_k_dense_replace": 4}, "first_k_dense_replace=4"),
])
def test_a_config_the_module_does_not_build_is_refused_by_name(change, match):
    with pytest.raises(ValueError, match=match):
        m.Xing4Config.from_dict(dict(CFG, **change))


def test_weights_are_made_in_the_engines_dtype_leaf_by_leaf_and_the_maps_move_with_the_token(engine, mcfg, prompts):
    assert {x.dtype for x in jax.tree.leaves(engine.params)} == {jnp.dtype(jnp.float32)}
    dense, sparse = engine.params["layers"][0], engine.params["layers"][1]
    assert "ffn" in dense and "moe" not in dense and dense["ffn"]["w_gate"].shape == (64, 96)
    assert "moe" in sparse and "ffn" not in sparse and sparse["moe"]["experts"]["w_gate"].shape == (4, 64, 48)
    assert sparse["moe"]["router"].shape == (64, 16) and np.abs(np.asarray(sparse["moe"]["bias"])).min() > 0
    hcs = [h for lay in engine.params["layers"] for h in lay["hc"]]
    assert len(hcs) == 6 and all(h["phi"].shape == (K, 256) and h["b"].shape == (K,) for h in hcs)
    assert all(np.array_equal(np.asarray(h["a"]), np.ones(3)) for h in hcs)
    assert float(np.std(np.asarray(hcs[0]["phi"]))) == pytest.approx(1 / 16, rel=0.1)     # 1 / sqrt(n E)
    # a p, a q, a r over tokens: a standard deviation near 1, so a dropped dynamic part would show
    fam = mcfg.serving_family()
    h = fam.embed(engine.params, jnp.asarray(prompts[4])[None], None)
    x = np.asarray(h[0], np.float32)
    z = (x / np.sqrt((x * x).mean(-1, keepdims=True) + 1e-6)) @ np.asarray(hcs[0]["phi"]).T
    assert 0.6 < z.std() < 1.5


def test_absorbed_equals_expanded(engine, mcfg, prompts):
    ids = jnp.asarray(prompts[4])[None]
    a = np.asarray(m.forward(mcfg, engine.params, ids, absorbed=True))
    b = np.asarray(m.forward(mcfg, engine.params, ids))
    np.testing.assert_allclose(a, b, atol=5e-5, rtol=1e-4)      # float32, another order of the same sums


def test_the_module_forward_is_the_references(engine, mcfg, prompts):
    arch = reference.Arch.from_config(CFG)
    ids = prompts[3]
    got = np.asarray(m.forward(mcfg, engine.params, jnp.asarray(ids)[None]))[0]
    padded = np.zeros((64,), np.int32)
    padded[: len(ids)] = ids
    want = np.asarray(reference.logits(engine.params, jnp.asarray(padded), arch))[: len(ids)]
    np.testing.assert_allclose(got, want, atol=5e-5, rtol=1e-4)


def test_served_streams_are_the_references_across_chunk_boundaries(engine, served, prompts):
    srv, reqs = served
    arch = reference.Arch.from_config(CFG)
    for r, p in zip(reqs, prompts):
        assert r.status == "finished" and len(r.tokens) == 12
        ids = np.concatenate([p, np.asarray(r.tokens, np.int32)])
        padded = np.zeros((64,), np.int32)
        padded[: len(ids)] = ids
        gap, _ = reference.served_gaps(engine.params, jnp.asarray(padded), len(p), len(ids), arch=arch)
        assert float(np.asarray(gap).max()) <= GAP_TOL, (len(p), np.asarray(gap).max())
    srv.drain(0.0)
    srv.check_no_leaks()


def test_the_whole_prompt_program_and_the_chunked_one_give_the_same_tokens(engine, prompts):
    """A 33-token prompt through the whole-prompt program (``max_prompt_len``
    rows, expanded attention) and through five 8-token chunks (absorbed, paged):
    the same twelve tokens."""
    whole = engine.serve(dict(SERVING, prefill_chunk_tokens=40))
    chunked = engine.serve(dict(SERVING))
    a = whole.submit(prompts[3], max_new_tokens=12, seed=0)
    b = chunked.submit(prompts[3], max_new_tokens=12, seed=0)
    whole.run()
    chunked.run()
    assert list(a.tokens) == list(b.tokens) and len(a.tokens) == 12


@pytest.mark.parametrize("chunked", [False, True], ids=["prefill-then-decode", "chunks-then-decode"])
def test_paged_programs_logits_match_the_references_full_forward(engine, mcfg, prompts, chunked):
    """The programs themselves, logits and not tokens: a 19-token prompt
    through the whole-prompt program (expanded, blocked) or three chunks
    (absorbed, the latent kernel's fallback), then four decode steps through
    the cache, each step's next-token logits against the float32 reference's
    full forward. Float32 both sides; the tolerance is the sums' other order."""
    fam = mcfg.serving_family()
    arch = reference.Arch.from_config(CFG)
    page, n_pg = 4, 8
    ids = np.asarray(prompts[2][:19])
    pool = jnp.zeros((3, 16, 1, page, 24), jnp.float32)
    table = jnp.arange(1, 1 + n_pg, dtype=jnp.int32)
    key = jnp.zeros((2,), jnp.uint32)
    seq = list(ids)
    chunk = jax.jit(functools.partial(smodel.paged_chunk_prefill, mcfg))

    def last_logits(n):      # the reference's logits at position n - 1 of the stream so far
        padded = np.zeros((32,), np.int32)
        padded[:n] = seq[:n]
        return np.asarray(reference.logits(engine.params, jnp.asarray(padded), arch))[n - 1]

    if chunked:
        for start in range(0, 19, 8):
            buf = np.zeros((1, 8), np.int32)
            seg = ids[start:start + 8]
            buf[0, : len(seg)] = seg
            (pool, *_), tok, _ = chunk(
                engine.params, jnp.asarray(buf), jnp.int32(start), jnp.int32(19), Cache(pool),
                table[start // page: start // page + 2], table[None], key)
    else:
        buf = np.zeros((1, 24), np.int32)
        buf[0, :19] = ids
        (pool, *_), tok, _ = jax.jit(functools.partial(smodel.paged_prefill, mcfg))(
            engine.params, jnp.asarray(buf), jnp.int32(19), Cache(pool), table[:6], key)
    assert int(tok[0]) == int(np.argmax(last_logits(19)))
    seq.append(int(tok[0]))

    @jax.jit
    def step(params, pool, token, n):     # the token at position n - 1 through the family's own pieces
        h = fam.embed(params, token[None], n[None] - 1)
        assert h.shape == (1, 1, 4 * 64)
        pos = n[None, None] - 1
        for l in range(3):
            lp = fam.layer(params, l)
            q, row, _ = fam.qkv(lp, h, pos, l)
            pool = pool.at[l, table[(n - 1) // page], 0, (n - 1) % page].set(row[0, 0, 0])
            o = smodel._attend_latent(fam, q, pool, l, table[None], n[None] - 1, None)
            h, carry, _ = fam.after_attention(lp, h, o, l)
            assert carry is None and "handed" not in lp      # the hand-over was taken
        return pool, fam.logits(params, h[:, -1])

    for _ in range(4):
        n = len(seq)
        pool, got = step(engine.params, pool, jnp.int32(seq[-1]), jnp.int32(n))
        got = np.asarray(got)[0]
        np.testing.assert_allclose(got, last_logits(n), atol=5e-5, rtol=1e-4)
        seq.append(int(np.argmax(got)))


def test_the_kernel_pair_serves_inside_the_programs_interpreted(prompts):
    """At streams of 128 lanes the family's switch takes the kernels (here
    interpreted): the decode program's logits are the ``jnp`` form's."""
    big = dict(CFG, hidden_size=128, num_hidden_layers=2)
    outs = {}
    for impl in ("jnp", "interpret"):
        cfg = m.Xing4Config.from_dict(big, attn_impl=impl)
        fam = cfg.serving_family()
        params = m.init_params(cfg, jax.random.PRNGKey(1), jnp.float32)

        def run(params, ids, fam=fam):
            h = fam.embed(params, ids, None)
            u, maps = fam.pre(params["layers"][1]["hc"][1], h)
            return u, maps, fam.post(h, u, maps)

        outs[impl] = jax.jit(run)(params, jnp.asarray(prompts[2])[None])
    for a, b in zip(outs["jnp"], outs["interpret"]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-6, rtol=1e-5)


def test_the_cache_is_one_pool_and_the_gauges_say_what_a_row_of_the_stream_is(engine, served):
    srv, _ = served
    ds = srv.decode_set
    assert ds.cache.k.shape == (3, 64, 1, 4, 16 + 8) and ds.cache.latent and ds.kv_pools == 1
    assert srv.metrics.gauge("serving_hc_row_bytes", "").value() == 4 * 64 * 4
    assert srv.metrics.gauge("serving_moe_experts_held", "").value() == 4
    prog = [r[3] for r in spans.phases() if r[0] == "ds.init.programs" and "hc_row_bytes" in r[3]][-1]
    assert prog["hc_row_bytes"] == 1024 and "latent=" in prog["kv_bytes"] and prog["moe_experts_held"] == 4


def test_spans_count_the_expert_layers_alone_and_the_mixing_has_a_part(engine, mcfg, prompts):
    t0 = spans._clock()      # not the last record's end: `since` is inclusive, and that record may be another server's emit
    srv = engine.serve(dict(SERVING))
    reqs = [srv.submit(p, max_new_tokens=12, seed=i) for i, p in enumerate(prompts[:4])]
    srv.run()
    recs = list(spans.snapshot(since=t0))
    emits = [r[3] for r in recs if r[0] == "ds.serve.emit"]
    assert emits and all({"moe_pairs_held", "moe_pairs_routed", "moe_load_max", "moe_experts_hit"} <= set(a) for a in emits)
    # tokens x top-2 x the TWO expert layers (layer 0 is dense and reports nothing)
    assert all(a["moe_pairs_routed"] % (2 * 2) == 0 for a in emits)
    assert all(a["moe_experts_streamed"] == 4 * 2 >= a["moe_experts_hit"] for a in emits)
    # the decode program's lowered text: the mixing's operations are under the part hc.mix
    text = jax.jit(functools.partial(smodel.paged_decode_step, mcfg)).lower(
        engine.params, jnp.zeros((3,), jnp.int32), jnp.zeros((3,), jnp.int32), Cache(jnp.zeros((3, 64, 1, 4, 24), jnp.float32)),
        jnp.zeros((3, 13), jnp.int32), jnp.zeros((3, 2), jnp.uint32)).as_text(debug_info=True)
    assert parts.PREFIX + "hc.mix" in text
    # ONE traced and lowered function each for the program's six sub-blocks' two calls
    assert text.count("func.func private @_pre(") == 1 and text.count("func.func private @_post(") == 1
    assert text.count("call @_pre(") == 6 and text.count("call @_post(") == 6


def test_another_latent_familys_decode_program_lowers_as_it_did_before_this_family():
    """The programs pass the stream through and gained no branch: the lowered
    text of the ``mistral4`` decode program has nothing of the mixing in it
    (PERF.md, PR 57, has the byte-for-byte comparison with the parent commit)."""
    from deepspeed_tpu.models import mistral4
    from tests.unit.test_serving_mistral4 import CFG as MS4

    cfg = mistral4.Mistral4Config.from_dict(MS4)
    params = jax.eval_shape(lambda: mistral4.init_params(cfg, jax.random.PRNGKey(0), jnp.float32))
    text = jax.jit(functools.partial(smodel.paged_decode_step, cfg)).lower(
        params, jnp.zeros((3,), jnp.int32), jnp.zeros((3,), jnp.int32), Cache(jnp.zeros((2, 64, 1, 4, 24), jnp.float32)),
        jnp.zeros((3, 13), jnp.int32), jnp.zeros((3, 2), jnp.uint32)).as_text(debug_info=True)
    assert parts.PREFIX + "hc.mix" not in text and "@_pre(" not in text and "@_post(" not in text


def test_the_shares_routed_parts_and_the_shared_expert_once_add_up_to_the_uncut_layer_and_through_h_post_to_the_uncut_stream():
    """Top-2 of 16 sigmoid-routed experts cut four ways (the small size's
    eight-way cut): what every share gives of the routed part, summed, plus
    the shared expert counted once, is the uncut reference's expert layer, and
    written back through the sub-block's maps it is the uncut ``X'``."""
    from deepspeed_tpu.moe import expert_share as es
    from deepspeed_tpu.ops.pallas import hyper_connection as hc

    rng = np.random.default_rng(1)
    E, F, n_all, k, n, T = 64, 48, 16, 2, 4, 40
    w = lambda *s: jnp.asarray(rng.normal(size=s) * 0.2, jnp.float32)  # noqa: E731
    ex = {"w_gate": w(n_all, E, F), "w_up": w(n_all, E, F), "w_down": w(n_all, F, E)}
    lp = {"router": w(E, n_all), "bias": w(n_all), "shared": {"w_gate": w(E, F), "w_up": w(E, F), "w_down": w(F, E)}}
    sub = {"phi": w(K, n * E) / 3.2, "a": jnp.ones((3,)), "b": w(K) * 5}
    x = w(T, n * E) * 5
    u, maps = hc.hc_pre(x, sub["phi"], sub["a"], sub["b"], n=n, eps=1e-6, iters=20, impl="jnp")
    # the uncut layer, by the reference's own lines (all 16 experts held)
    arch = reference.Arch.from_config(dict(CFG, n_routed_experts=16, published={"n_routed_experts": 16},
                                           expert_share={"chips": 1, "index": 0}))
    whole = reference.rm._experts(dict(lp, experts=ex), u, arch.latent, True, reference.dot_f32)
    X = reference.sub_block(x.reshape(T, n, E), sub, jnp.ones((E,)), lambda v: reference.rm._experts(
        dict(lp, experts=ex), v, arch.latent, True, reference.dot_f32), arch)
    shared = es.gated_ffn(u, **lp["shared"])
    routed = []
    for i in range(4):
        held = jax.tree.map(lambda a: a[i * 4:(i + 1) * 4], ex)
        y, c = es.expert_share_layer(dict(lp, experts=held), u, es.ExpertShare(n_all, 4, i), k, 2.0)
        routed.append(y - shared)
    total = sum(routed) + shared
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole), atol=5e-5, rtol=1e-4)
    # ... the reference's sub-block norms u before F; so does this, with a gain of ones
    from deepspeed_tpu.ops.layer_norm import rms_norm
    un = rms_norm(u, jnp.ones((E,)), 1e-6)
    routed = [es.expert_share_layer(dict(lp, experts=jax.tree.map(lambda a: a[i * 4:(i + 1) * 4], ex)), un,
                                    es.ExpertShare(n_all, 4, i), k, 2.0)[0] - es.gated_ffn(un, **lp["shared"]) for i in range(4)]
    y = sum(routed) + es.gated_ffn(un, **lp["shared"])
    out = hc.hc_post(x, y, maps, n=n, impl="jnp")
    np.testing.assert_allclose(np.asarray(out).reshape(T, n, E), np.asarray(X), atol=2e-4, rtol=1e-4)


@pytest.mark.parametrize("section,what", [
    ({"prefix_cache": {"enabled": True}}, "serving.prefix_cache"),
    ({"kv_cache_dtype": "int8"}, "serving.kv_cache_dtype=int8"),
    ({"placement": {"tp": 2}}, "serving.placement.tp > 1"),
])
def test_mechanisms_that_know_k_and_v_pools_are_refused_by_name(engine, section, what):
    with pytest.raises(ValueError, match="a latent KV pool") as e:
        engine.serve(dict(SERVING, **section))
    assert what in str(e.value)
