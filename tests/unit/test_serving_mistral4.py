"""The ``mistral4`` family (latent attention) through the paged programs at a
small size on the CPU (2 layers, hidden 64, 4 heads with nope/rope/v 8/8/16,
``kv_lora_rank`` 16, ``q_lora_rank`` 32, 16 experts top-2 of which a chip
holds 4, page 4, chunk 8, ``original_max_position_embeddings`` 16 so that the
query's position scale steps inside the test), in float32: the served streams
and logits against the float32 reference's full forward in the EXPANDED form
(``perfbench/reference_mistral4.py``, which imports nothing from the model's
module) while the programs compute ABSORBED, the one latent pool, the spans
and counters, the share, and the refusals."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models import mistral4 as m
from deepspeed_tpu.serving import model as smodel
from deepspeed_tpu.serving.kv_cache import Cache
from deepspeed_tpu.telemetry import spans
from perfbench import reference_mistral4 as reference

CFG = dict(
    vocab_size=96, hidden_size=64, moe_intermediate_size=48, num_hidden_layers=2, num_attention_heads=4,
    q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=16,
    n_routed_experts=4, published={"n_routed_experts": 16}, expert_share={"chips": 4, "index": 1},
    num_experts_per_tok=2, routed_scaling_factor=1.0, rms_norm_eps=1e-6, norm_topk_prob=True,
    rope_parameters={"rope_theta": 10000, "factor": 8, "beta_fast": 32, "beta_slow": 1, "mscale": 1,
                     "mscale_all_dim": 1, "original_max_position_embeddings": 16, "llama_4_scaling_beta": 0.1},
    max_position_embeddings=4096, initializer_range=0.25,
)
SERVING = dict(max_slots=3, page_size=4, num_pages=64, max_prompt_len=40, max_new_tokens=12,
               prefill_chunk_tokens=8, temperature=0.0)
PROMPTS = (5, 8, 19, 33, 40, 27, 9)     # ONE chunk (<= a chunk: first and last in one call) and 2-5 chunks; all but two pass position 16
# The reference sums in another order than the programs (expanded against
# absorbed, one product a layer against paged blocks and an online softmax),
# both in float32: the served token is the reference's argmax but for a tie
# closer than this.
GAP_TOL = 1e-4


@pytest.fixture(scope="module")
def mcfg():
    return m.Mistral4Config.from_dict(CFG)


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


def test_config_reads_the_published_keys_and_the_share(mcfg):
    assert (mcfg.n_routed_experts, mcfg.n_routed_experts_published, mcfg.expert_chips, mcfg.expert_index) == (4, 16, 4, 1)
    assert mcfg.kv_width == 24 and mcfg.qk_head_dim == 16 and mcfg.original_max_position_embeddings == 16
    m_ = 0.1 * np.log(8.0) + 1.0
    assert mcfg.sm_scale == pytest.approx(m_ * m_ / 4.0)
    with pytest.raises(ValueError, match="is not the router's"):
        m.Mistral4Config.from_dict(dict(CFG, n_routed_experts=5))
    # the published configuration: yarn's ramp runs from pair 12 to pair 25 of 32
    f = m.yarn_inv_freq(m.Mistral4Config())
    plain = 10000.0 ** (-np.arange(32) / 32.0)
    np.testing.assert_allclose(f[:13], plain[:13], rtol=1e-6)
    np.testing.assert_allclose(f[25:], plain[25:] / 128.0, rtol=1e-6)
    assert m.Mistral4Config().sm_scale == pytest.approx(0.19497, rel=1e-4)


def test_weights_are_made_in_the_engines_dtype_leaf_by_leaf(engine):
    assert {x.dtype for x in jax.tree.leaves(engine.params)} == {jnp.dtype(jnp.float32)}
    moe = engine.params["layers"][1]["moe"]
    assert np.abs(np.asarray(moe["bias"])).min() > 0 and moe["bias"].shape == (16,)     # drawn, not zero
    assert moe["router"].shape == (64, 16) and moe["experts"]["w_gate"].shape == (4, 64, 48)
    attn = engine.params["layers"][0]["attn"]
    assert attn["wkv_a"].shape == (64, 24) and attn["w_uk"].shape == (16, 4, 8) and attn["w_uv"].shape == (16, 4, 16)


def test_absorbed_equals_expanded(engine, mcfg, prompts):
    ids = jnp.asarray(prompts[4])[None]
    a = np.asarray(m.forward(mcfg, engine.params, ids, absorbed=True))
    b = np.asarray(m.forward(mcfg, engine.params, ids))
    np.testing.assert_allclose(a, b, atol=2e-5, rtol=1e-4)      # float32, another order of the same sums


def test_served_streams_are_the_references_across_chunk_boundaries_and_past_the_scale_step(engine, served, prompts):
    srv, reqs = served
    arch = reference.Arch.from_config(CFG)
    assert max(PROMPTS) + 12 > 3 * 16        # the query scale takes three values on compared positions
    for r, p in zip(reqs, prompts):
        assert r.status == "finished" and len(r.tokens) == 12
        ids = np.concatenate([p, np.asarray(r.tokens, np.int32)])
        padded = np.zeros((64,), np.int32)
        padded[: len(ids)] = ids
        gap, _ = reference.served_gaps(engine.params, jnp.asarray(padded), len(p), len(ids), arch=arch)
        assert float(np.asarray(gap).max()) <= GAP_TOL, (len(p), np.asarray(gap).max())
    srv.drain(0.0)
    srv.check_no_leaks()


@pytest.mark.parametrize("chunked", [False, True], ids=["prefill-then-decode", "chunks-then-decode"])
def test_paged_programs_logits_match_the_references_full_forward(engine, mcfg, prompts, chunked):
    """The programs themselves, logits and not tokens: a 19-token prompt
    through the whole-prompt program (expanded, blocked) or three chunks
    (absorbed, the latent kernel's fallback), then four decode steps, each
    step's next-token logits against the expanded float32 reference. Float32
    both sides; the tolerance is the sums' other order."""
    fam = mcfg.serving_family()
    arch = reference.Arch.from_config(CFG)
    page, n_pg = 4, 8
    ids = np.asarray(prompts[2][:19])
    pool = jnp.zeros((2, 16, 1, page, 24), jnp.float32)
    table = jnp.arange(1, 1 + n_pg, dtype=jnp.int32)
    key = jnp.zeros((2,), jnp.uint32)
    seq = list(ids)
    # (each program compiled once, as the engine calls it: eagerly its operations dispatch one at a time)
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
        pos = n[None, None] - 1
        for l in range(2):
            lp = fam.layer(params, l)
            q, row, _ = fam.qkv(lp, h, pos, l)
            pool = pool.at[l, table[(n - 1) // page], 0, (n - 1) % page].set(row[0, 0, 0])
            o = smodel._attend_latent(fam, q, pool, l, table[None], n[None] - 1, None)
            h = h + fam.attn_out(lp, o)
            h = h + fam.mlp(lp, h, l)[0]
        return pool, fam.logits(params, h[:, -1])

    for _ in range(4):       # positions 19..22: past the scale's first step at 16
        n = len(seq)
        pool, got = step(engine.params, pool, jnp.int32(seq[-1]), jnp.int32(n))
        got = np.asarray(got)[0]
        np.testing.assert_allclose(got, last_logits(n), atol=2e-5, rtol=1e-4)
        seq.append(int(np.argmax(got)))


def test_the_cache_is_one_pool_of_one_latent_row_a_token(engine, served):
    srv, _ = served
    ds = srv.decode_set
    assert ds.cache.k.shape == (2, 64, 1, 4, 16 + 8) and ds.cache.latent and ds.kv_pools == 1
    assert len(jax.tree.leaves(ds.cache)) == 1
    g = srv.metrics.gauge("serving_kv_bytes", "", labelnames=("class",))
    row_bytes = (16 + 8) * 4
    assert g.value(**{"class": "latent"}) == 64 * 4 * 2 * row_bytes == srv.stats()["kv_pool_bytes"]
    assert srv.metrics.gauge("serving_kv_row_bytes", "").value() == row_bytes
    assert srv.metrics.gauge("serving_moe_experts_held", "").value() == 4
    assert srv.stats()["kv_window_bytes"] == 0


def test_the_weights_census_counts_this_familys_programs_too(engine, served, monkeypatch):
    """ISSUE 61: ``serving_weight_relayout_bytes`` has a value for each program
    of a family that names no ``row_gathered`` leaves (the census only counts
    there: no leaf is laid anew, nothing is refused); with the threshold at
    this size's leaves it is what ``program_census`` reads of each program."""
    from deepspeed_tpu.serving import placement

    srv, _ = served
    assert not hasattr(srv.family, "row_gathered")
    assert all(a is b for a, b in zip(jax.tree.leaves(srv.decode_set.params), jax.tree.leaves(engine.params)))
    gauge = srv.metrics.get("serving_weight_relayout_bytes")
    names = [name for name, _ in srv.executable_names()]
    assert names and all(gauge.value(program=name) == 0 for name in names)   # no leaf of a megabyte here
    monkeypatch.setattr(placement, "WEIGHT_LEAF_MIN_BYTES", 1 << 10)
    for name in names:
        rec = srv._program_info[name]
        ops, nbytes = rec["pset"].program_census(name, rec["exe"])[2:]
        assert ops >= 0 and (nbytes > 0) == (ops > 0)


def test_spans_and_counters_count_latent_rows_and_expert_loads(engine, prompts):
    t0 = spans._clock()      # not the last record's end: `since` is inclusive, and that record may be another server's emit
    srv, reqs = _serve(engine, prompts[:4])
    recs = [r for r in spans.snapshot(since=t0)]
    emits = [r[3] for r in recs if r[0] == "ds.serve.emit"]
    assert emits and all({"moe_pairs_held", "moe_pairs_routed", "moe_load_max", "moe_experts_hit"} <= set(a) for a in emits)
    disp = [r[3] for r in recs if r[0] == "ds.serve.decode.dispatch"]
    for a, d in zip(emits, disp):
        # tokens x top-2 x 2 layers; a step that carried a chunk counts the chunk's tokens too
        assert a["moe_pairs_routed"] % (2 * 2) == 0 and 0 <= a["moe_pairs_routed"] // (2 * 2) - d["active"] <= 8
        assert d["active"] <= d["attended"] and d["pages"] >= d["active"]
    chunks = [r[3] for r in recs if r[0] == "ds.serve.chunk"]
    long = [len(p) for p in prompts[:4]]     # every prompt goes in chunks: one of 5 or 8 tokens in ONE (ISSUE 63)
    assert sum(c["tokens"] for c in chunks) == sum(long)
    # a prompt of n tokens in chunks: every query reads the rows before it and itself, n (n + 1) / 2 in all
    assert sum(c["attended"] for c in chunks) == sum(n * (n + 1) // 2 for n in long)
    # a prompt reports the calls that rode no decode step; one that rode is in its step's emit
    assert sum(c["chunks"] + c["rode"] for c in chunks) == sum(-(-n // 8) for n in long)
    assert sum(c["moe_calls"] for c in chunks if "moe_calls" in c) == sum(c["chunks"] for c in chunks)
    reports = [c for c in chunks if "moe_calls" in c]
    assert sum(a["moe_pairs_routed"] for a in emits + reports) == (sum(d["active"] for d in disp) + sum(long)) * 2 * 2
    # off the TPU every call is masked: each expert layer of a step streams all 4 held experts, hit or not
    assert all(a["moe_experts_streamed"] == 4 * 2 >= a["moe_experts_hit"] for a in emits)
    assert all(c["moe_experts_streamed"] == 4 * 2 * c["moe_calls"] for c in reports)
    assert srv.metrics.counter("serving_moe_experts_streamed_total", "").value() == sum(
        a["moe_experts_streamed"] for a in emits + reports)
    prog = [r[3] for r in spans.phases(since=t0) if r[0] == "ds.init.programs"][-1]
    assert "latent=" in prog["kv_bytes"] and prog["kv_row_bytes"] == 24 * 4 and prog["moe_experts_held"] == 4


def test_the_walk_counters_count_the_pairs_the_calls_own_beside_their_rectangles(engine, prompts, monkeypatch):
    """``serving_attn_walk_steps_total`` against ``serving_attn_rect_steps_total``,
    by the kernel's name: host arithmetic on the calls' lengths by the kernel's
    own block rule. (Off the TPU the fallback attends; the test tells the
    gauge's rule that the kernel is taken, which is all the counters ask.)"""
    from deepspeed_tpu.ops import attention

    real = attention.latent_attention_grid_steps
    monkeypatch.setattr(attention, "latent_attention_grid_steps", lambda impl, *a, **k: real("pallas", *a, **k))
    srv, reqs = _serve(engine, prompts[:4])
    walk = lambda k: srv.metrics.counter("serving_attn_walk_steps_total", "", ("program",)).value(program=k)
    rect = lambda k: srv.metrics.counter("serving_attn_rect_steps_total", "", ("program",)).value(program=k)
    page, H, n = SERVING["page_size"], CFG["num_attention_heads"], srv.pages_per_slot
    assert n == 13
    # 4 heads on pages of 4 in a table of 13: both shapes hold 8 pages, 32 keys, a step; 2 blocks a table
    one = lambda B, T: real("pallas", B, H, page, 128, 4, n, T)
    assert (one(1, 8), one(3, 1)) == (2, 6)
    # the chunk shape: a call of 8 queries from `start` owns blocks 0 .. (start + 7) // 32, at most both
    long = [len(p) for p in prompts[:4]]     # every prompt goes in chunks: one of 5 or 8 tokens in ONE (ISSUE 63)
    starts = [s for m_ in long for s in range(0, m_, 8)]
    assert walk("mla_paged_chunk") == sum(min((s + 7) // 32, 1) + 1 for s in starts) == 11
    assert rect("mla_paged_chunk") == len(starts) * one(1, 8) == 20
    # the decode shape: a request of n prompt tokens decodes at lengths n .. n + 10 (its first token
    # is its prefill's), each owning blocks 0 .. length // 32; an idle row owns its one masked step
    steps = srv.metrics.counter("serving_decode_steps_total", "").value()
    slot_steps = srv.metrics.counter("serving_decode_slot_steps_total", "").value()
    own = sum(min(m_ // 32, 1) + 1 for p in prompts[:4] for m_ in range(len(p), len(p) + 11))
    assert slot_steps == 4 * 11 and own == 55
    assert walk("mla_paged_decode") == own + (3 * steps - slot_steps)
    assert rect("mla_paged_decode") == steps * one(3, 1)
    # ... and nothing is counted where the programs hold no latent kernel
    monkeypatch.undo()
    srv, _ = _serve(engine, prompts[:2])
    assert srv.metrics.counter("serving_attn_rect_steps_total", "", ("program",)).value(program="mla_paged_decode") == 0


def test_the_verify_step_emits_the_decode_steps_stream(engine, served, prompts):
    _, plain = served
    srv, spec = _serve(engine, prompts, speculative={"enabled": True, "k": 3, "ngram": 2})
    for a, b in zip(plain, spec):
        assert list(a.tokens) == list(b.tokens)
    srv.drain(0.0)
    srv.check_no_leaks()


def test_the_eight_shares_routed_parts_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    """Top-4 of 16 experts, cut four ways (the small size's eight-way cut):
    what every share gives of the routed part, summed, plus the shared expert
    counted once, is the layer with all the experts on one chip."""
    from deepspeed_tpu.moe import expert_share as es

    rng = np.random.default_rng(1)
    E, F, n_all, k = 64, 48, 16, 4
    w = lambda *s: jnp.asarray(rng.normal(size=s) * 0.2, jnp.float32)
    ex = {"w_gate": w(n_all, E, F), "w_up": w(n_all, E, F), "w_down": w(n_all, F, E)}
    lp = {"router": w(E, n_all), "bias": w(n_all), "shared": {"w_gate": w(E, F), "w_up": w(E, F), "w_down": w(F, E)}}
    u = w(40, E)
    whole, counts = es.expert_share_layer(dict(lp, experts=ex), u, es.ExpertShare(n_all), k, 1.0)
    assert int(counts.sum()) == 40 * k
    shared = es.gated_ffn(u, **lp["shared"])
    parts = []
    for i in range(4):
        held = jax.tree.map(lambda x: x[i * 4:(i + 1) * 4], ex)
        y, c = es.expert_share_layer(dict(lp, experts=held), u, es.ExpertShare(n_all, 4, i), k, 1.0)
        parts.append(y - shared)
        np.testing.assert_array_equal(np.asarray(c), np.asarray(counts[i * 4:(i + 1) * 4]))
    np.testing.assert_allclose(np.asarray(sum(parts) + shared), np.asarray(whole), atol=2e-5, rtol=1e-5)


@pytest.mark.parametrize("section,what", [
    ({"prefix_cache": {"enabled": True}}, "serving.prefix_cache"),
    ({"prefix_cache": {"enabled": True}, "tiering": {"enabled": True}}, "serving.prefix_cache"),
    ({"kv_cache_dtype": "int8"}, "serving.kv_cache_dtype=int8"),
    ({"placement": {"tp": 2}}, "serving.placement.tp > 1"),
    ({"placement": {"disaggregate": True}}, "serving.placement.disaggregate"),
])
def test_mechanisms_that_know_k_and_v_pools_are_refused_by_name(engine, section, what):
    with pytest.raises(ValueError, match="a latent KV pool") as e:
        engine.serve(dict(SERVING, **section))
    assert what in str(e.value)


def test_tiering_alone_and_migration_are_refused_by_name(engine, served):
    from deepspeed_tpu.runtime.config import ServingConfig

    cfg = ServingConfig.from_dict(dict(SERVING))
    cfg.tiering.enabled = True
    with pytest.raises(ValueError, match="serving.tiering"):
        engine.serve(cfg)
    with pytest.raises(ValueError, match="session migration .* latent"):
        served[0]._ensure_migration_programs()
