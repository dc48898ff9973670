"""The ``longcat_flash`` family (shortcut-connected double layers, latent
attention, softmax routing with identity experts) through the paged programs
at a small size on the CPU (2 double layers = 4 cached sub-blocks, hidden 64,
4 heads with nope/rope/v 8/8/16, ``kv_lora_rank`` 16, ``q_lora_rank`` 32, dense
FFN 96, 16 experts of 48 + 8 identity columns, top-3, of which a chip holds
4, page 4, chunk 8), in float32: the served streams and logits against the
float32 reference's full forward in the EXPANDED form
(``perfbench/reference_longcat_flash.py``, which imports nothing from the
model's module) while the programs compute ABSORBED, the one latent pool of
``2 x num_layers`` layers, the spans and counters (held, routed and ZERO
pairs), the share, and the refusals."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models import longcat_flash as m
from deepspeed_tpu.serving import model as smodel
from deepspeed_tpu.serving.kv_cache import Cache
from deepspeed_tpu.telemetry import spans
from perfbench import reference_longcat_flash as reference

CFG = dict(
    vocab_size=96, hidden_size=64, ffn_hidden_size=96, expert_ffn_hidden_size=48, num_layers=2,
    num_attention_heads=4, q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=16,
    mla_scale_q_lora=True, mla_scale_kv_lora=True,
    n_routed_experts=4, published={"n_routed_experts": 16}, expert_share={"chips": 4, "index": 1},
    zero_expert_num=8, zero_expert_type="identity", moe_topk=3, routed_scaling_factor=6.0, rms_norm_eps=1e-5,
    rope_theta=10000000.0, max_position_embeddings=4096, initializer_range=0.25,
)
SERVING = dict(max_slots=3, page_size=4, num_pages=64, max_prompt_len=40, max_new_tokens=12,
               prefill_chunk_tokens=8, temperature=0.0)
PROMPTS = (5, 8, 19, 33, 40, 27, 9)     # ONE chunk (<= a chunk: first and last in one call) and 2-5 chunks
K, L = 3, 2                              # picks a token, expert layers
# The reference sums in another order than the programs (expanded against
# absorbed, one product a layer against paged blocks and an online softmax),
# both in float32: the served token is the reference's argmax but for a tie
# closer than this.
GAP_TOL = 2e-4


@pytest.fixture(scope="module")
def mcfg():
    return m.LongcatFlashConfig.from_dict(CFG)


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
    assert (mcfg.num_layers, mcfg.n_layer, mcfg.moe_topk, mcfg.zero_expert_num, mcfg.router_width) == (2, 4, 3, 8, 24)
    assert mcfg.kv_width == 24 and mcfg.qk_head_dim == 16
    assert mcfg.share == (16, 4, 1, 8) and mcfg.share.n_held == 4
    fam = mcfg.serving_family()
    assert fam.sm_scale == pytest.approx(0.25) and fam.sparse_layers == (0, 2) and fam.zero_experts == 8
    assert fam.q_lora_scale == pytest.approx(np.sqrt(2.0)) and fam.kv_lora_scale == pytest.approx(2.0)
    with pytest.raises(ValueError, match="is not the router's"):
        m.LongcatFlashConfig.from_dict(dict(CFG, n_routed_experts=5))
    with pytest.raises(ValueError, match="identity is the zero-compute expert"):
        m.LongcatFlashConfig.from_dict(dict(CFG, zero_expert_type="copy"))
    # the published configuration: 64 heads on a 576-wide row whose first 512 lanes are the values
    pub = m.LongcatFlashConfig().serving_family()
    assert (pub.head_dim, pub.v_width, pub.n_head, pub.n_layer) == (576, 512, 64, 56)
    assert pub.q_lora_scale == 2.0 and pub.kv_lora_scale == pytest.approx(3.4641, rel=1e-4)
    assert pub.sm_scale == pytest.approx(1 / np.sqrt(192)) and pub.inv_freq[1] == pytest.approx(1e7 ** (-2 / 64))


def test_weights_are_made_in_the_engines_dtype_leaf_by_leaf(engine):
    assert {x.dtype for x in jax.tree.leaves(engine.params)} == {jnp.dtype(jnp.float32)}
    lay = engine.params["layers"][1]
    moe = lay["moe"]
    assert "shared" not in moe and moe["router"].shape == (64, 24) and moe["experts"]["w_gate"].shape == (4, 64, 48)
    b = np.asarray(moe["bias"])
    assert b.shape == (24,) and np.abs(b).min() > 0 and np.abs(b).max() < 4.0 / 24   # drawn at the scores' scale
    assert len(lay["attn"]) == len(lay["ffn"]) == len(lay["norm_in"]) == len(lay["norm_post"]) == 2
    assert lay["attn"][1]["wkv_a"].shape == (64, 24) and lay["ffn"][0]["w_gate"].shape == (64, 96)
    assert not np.array_equal(np.asarray(lay["attn"][0]["wo"]), np.asarray(lay["attn"][1]["wo"]))


def test_absorbed_equals_expanded_and_both_are_the_reference(engine, mcfg, prompts):
    ids = jnp.asarray(prompts[4])[None]
    a = np.asarray(m.forward(mcfg, engine.params, ids, absorbed=True))
    b = np.asarray(m.forward(mcfg, engine.params, ids))
    np.testing.assert_allclose(a, b, atol=5e-5, rtol=1e-4)      # float32, another order of the same sums
    ref = np.asarray(reference.logits(engine.params, ids[0], reference.Arch.from_config(CFG)))
    np.testing.assert_allclose(b[0], ref, atol=5e-5, rtol=1e-4)


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


def _reference_last_logits(engine, seq, n):
    """The reference's logits at position n - 1 of the stream so far."""
    padded = np.zeros((32,), np.int32)
    padded[:n] = seq[:n]
    return np.asarray(reference.logits(engine.params, jnp.asarray(padded), reference.Arch.from_config(CFG)))[n - 1]


@pytest.mark.parametrize("chunked", [False, True], ids=["prefill-then-decode", "chunks-then-decode"])
def test_paged_programs_logits_match_the_references_full_forward(engine, mcfg, prompts, chunked):
    """The programs themselves, logits and not tokens: a 19-token prompt
    through the whole-prompt program (expanded, blocked) or three chunks
    (absorbed, the latent kernel's fallback), then four decode steps through
    the family's own pieces (the carried ``m`` across the two sub-blocks),
    each step's next-token logits against the expanded float32 reference."""
    fam = mcfg.serving_family()
    page, n_pg = 4, 8
    ids = np.asarray(prompts[2][:19])
    pool = jnp.zeros((4, 16, 1, page, 24), jnp.float32)
    table = jnp.arange(1, 1 + n_pg, dtype=jnp.int32)
    key = jnp.zeros((2,), jnp.uint32)
    seq = list(ids)
    # (each program compiled once, as the engine calls it: eagerly its operations dispatch one at a time)
    chunk = jax.jit(functools.partial(smodel.paged_chunk_prefill, mcfg))
    if chunked:
        for start in range(0, 19, 8):
            buf = np.zeros((1, 8), np.int32)
            seg = ids[start:start + 8]
            buf[0, : len(seg)] = seg
            (pool, *_), tok, counts = chunk(
                engine.params, jnp.asarray(buf), jnp.int32(start), jnp.int32(19), Cache(pool),
                table[start // page: start // page + 2], table[None], key)
            n_real = len(seg)
            assert counts.shape == (L, 4 + 1) and int(counts.sum()) <= n_real * K * L
    else:
        buf = np.zeros((1, 24), np.int32)
        buf[0, :19] = ids
        (pool, *_), tok, counts = jax.jit(functools.partial(smodel.paged_prefill, mcfg))(
            engine.params, jnp.asarray(buf), jnp.int32(19), Cache(pool), table[:6], key)
        assert counts.shape == (L, 4 + 1)
    assert int(tok[0]) == int(np.argmax(_reference_last_logits(engine, seq, 19)))
    seq.append(int(tok[0]))

    @jax.jit
    def step(params, pool, token, n):     # the token at position n - 1 through the family's own pieces
        h = fam.embed(params, token[None], n[None] - 1)
        pos = n[None, None] - 1
        carry = None
        for l in range(fam.n_layer):
            lp = fam.layer(params, l)
            q, row, _ = fam.qkv(lp, h, pos, l)
            pool = pool.at[l, table[(n - 1) // page], 0, (n - 1) % page].set(row[0, 0, 0])
            o = smodel._attend_latent(fam, q, pool, l, table[None], n[None] - 1, None)
            h, carry, _ = fam.after_attention(lp, h, o, l, None, None, carry)
            assert (carry is None) == (l % 2 == 1)
        return pool, fam.logits(params, h[:, -1])

    for _ in range(4):
        n = len(seq)
        pool, got = step(engine.params, pool, jnp.int32(seq[-1]), jnp.int32(n))
        got = np.asarray(got)[0]
        np.testing.assert_allclose(got, _reference_last_logits(engine, seq, n), atol=5e-5, rtol=1e-4)
        seq.append(int(np.argmax(got)))


@pytest.mark.parametrize("program", ["decode", "mixed", "verify"])
def test_step_programs_logits_are_the_references(engine, mcfg, prompts, program, monkeypatch):
    """The decode, mixed and verify PROGRAMS with a 13-token context in slot
    0 (and an idle slot 1): the logits each hands its sampler, caught at
    ``fam.logits``, against the reference's full forward."""
    page = 4
    ids = np.asarray(prompts[2][:19])
    pool = jnp.zeros((4, 16, 1, page, 24), jnp.float32)
    table = jnp.arange(1, 9, dtype=jnp.int32)
    key = jnp.zeros((2,), jnp.uint32)
    buf = np.zeros((1, 16), np.int32)
    buf[0, :13] = ids[:13]
    cache, _, _ = jax.jit(functools.partial(smodel.paged_prefill, mcfg))(
        engine.params, jnp.asarray(buf), jnp.int32(13), Cache(pool), table[:4], key)
    caught = []
    fam_cls = type(mcfg.serving_family())
    plain = fam_cls.logits
    monkeypatch.setattr(fam_cls, "logits", lambda self, params, h: caught.append(plain(self, params, h)) or caught[-1])
    tables = jnp.stack([table, jnp.zeros_like(table)])
    seq_lens = jnp.asarray([13, 0], jnp.int32)
    keys = jnp.zeros((2, 2), jnp.uint32)
    want = lambda n: _reference_last_logits(engine, list(ids), n)
    if program == "decode":
        out = smodel.paged_decode_step(mcfg, engine.params, jnp.asarray([ids[13], 0]), seq_lens, cache, tables, keys)
        np.testing.assert_allclose(np.asarray(caught[0])[0], want(14), atol=5e-5, rtol=1e-4)
    elif program == "verify":
        toks = jnp.asarray([ids[13:16], [0, 0, 0]], jnp.int32)
        out = smodel.paged_verify_step(mcfg, engine.params, toks, seq_lens, cache, tables)
        for t in range(3):
            np.testing.assert_allclose(np.asarray(caught[0])[0, t], want(14 + t), atol=5e-5, rtol=1e-4)
    else:   # one call: slot 0 decodes token 13, a second request's first chunk of 8 rides
        other = np.asarray(prompts[3][:8])
        row2 = jnp.arange(9, 17, dtype=jnp.int32)
        out = smodel.paged_mixed_step(
            mcfg, engine.params, jnp.asarray([ids[13], 0]), seq_lens, jnp.asarray(other)[None], jnp.int32(0),
            jnp.int32(8), cache, tables, row2[:2], row2[None, :8], keys, key)
        lg = np.asarray(caught[0])      # [1 + B, V]: the chunk's last prompt position, then the slots
        np.testing.assert_allclose(lg[1], want(14), atol=5e-5, rtol=1e-4)
        np.testing.assert_allclose(lg[0], _reference_last_logits(engine, list(other), 8), atol=5e-5, rtol=1e-4)
    counts = np.asarray(out[-1])
    assert counts.shape == (L, 4 + 1)
    real = {"decode": 1, "verify": 3, "mixed": 9}[program]       # the idle slot's rows count nowhere
    assert counts[:, -1].sum() <= real * K * L and counts[:, :-1].sum() <= real * K * L


def test_the_cache_is_one_latent_pool_of_two_layers_a_double_layer(engine, served):
    srv, _ = served
    ds = srv.decode_set
    assert ds.cache.k.shape == (2 * 2, 64, 1, 4, 16 + 8) and ds.cache.latent and ds.kv_pools == 1
    assert len(jax.tree.leaves(ds.cache)) == 1
    g = srv.metrics.gauge("serving_kv_bytes", "", labelnames=("class",))
    row_bytes = (16 + 8) * 4
    assert g.value(**{"class": "latent"}) == 64 * 4 * 4 * row_bytes == srv.stats()["kv_pool_bytes"]
    assert srv.metrics.gauge("serving_kv_row_bytes", "").value() == row_bytes
    assert srv.metrics.gauge("serving_moe_experts_held", "").value() == 4
    assert srv.stats()["kv_window_bytes"] == 0


def test_spans_and_counters_count_zero_held_and_routed_pairs(engine, prompts):
    t0 = spans._clock()      # not the last record's end: `since` is inclusive, and that record may be another server's emit
    srv, reqs = _serve(engine, prompts[:4])
    recs = [r for r in spans.snapshot(since=t0)]
    emits = [r[3] for r in recs if r[0] == "ds.serve.emit"]
    keys = {"moe_pairs_held", "moe_pairs_routed", "moe_pairs_zero", "moe_load_max", "moe_experts_hit"}
    assert emits and all(keys <= set(a) for a in emits)
    disp = [r[3] for r in recs if r[0] == "ds.serve.decode.dispatch"]
    for a, d in zip(emits, disp):
        # tokens x top-3 x 2 expert layers; a step that carried a chunk counts the chunk's tokens too
        assert a["moe_pairs_routed"] % (K * L) == 0 and 0 <= a["moe_pairs_routed"] // (K * L) - d["active"] <= 8
        assert a["moe_pairs_zero"] + a["moe_pairs_held"] <= a["moe_pairs_routed"]
        assert a["moe_experts_hit"] <= 4 * L and a["moe_load_max"] * a["moe_experts_hit"] >= a["moe_pairs_held"]
    chunks = [r[3] for r in recs if r[0] == "ds.serve.chunk"]
    long = [len(p) for p in prompts[:4]]     # every prompt goes in chunks: one of 5 or 8 tokens in ONE (ISSUE 63)
    assert sum(c["tokens"] for c in chunks) == sum(long)
    assert sum(c["attended"] for c in chunks) == sum(n * (n + 1) // 2 for n in long)
    reports = [c for c in chunks if "moe_calls" in c]
    assert all("moe_pairs_zero" in c for c in reports)
    both = emits + reports
    routed = sum(a["moe_pairs_routed"] for a in both)
    assert routed == (sum(d["active"] for d in disp) + sum(long)) * K * L
    zero, held = sum(a["moe_pairs_zero"] for a in both), sum(a["moe_pairs_held"] for a in both)
    # 8 of 24 columns are identity and 4 of 24 held: near a third and a sixth of the pairs at seeded weights
    assert 0.15 < zero / routed < 0.55 and 0.05 < held / routed < 0.35
    c = srv.metrics.counter
    assert c("serving_moe_pairs_zero_total", "").value() == zero
    assert c("serving_moe_pairs_held_total", "").value() == held and c("serving_moe_pairs_routed_total", "").value() == routed
    # off the TPU every call is masked: each expert layer of a step streams all 4 held experts, hit or not
    assert all(a["moe_experts_streamed"] == 4 * L >= a["moe_experts_hit"] for a in emits)
    assert all(a["moe_experts_streamed"] == 4 * L * a["moe_calls"] for a in reports)
    assert c("serving_moe_experts_streamed_total", "").value() == sum(a["moe_experts_streamed"] for a in both)
    prog = [r[3] for r in spans.phases(since=t0) if r[0] == "ds.init.programs"][-1]
    assert "latent=" in prog["kv_bytes"] and prog["kv_row_bytes"] == 24 * 4 and prog["moe_experts_held"] == 4


def test_the_verify_step_emits_the_decode_steps_stream(engine, served, prompts):
    _, plain = served
    srv, spec = _serve(engine, prompts, speculative={"enabled": True, "k": 3, "ngram": 2})
    for a, b in zip(plain, spec):
        assert list(a.tokens) == list(b.tokens)
    srv.drain(0.0)
    srv.check_no_leaks()


def test_the_part_table_splits_a_double_layer_into_attention_dense_ffn_and_expert_layer(engine, monkeypatch):
    from deepspeed_tpu.telemetry import parts

    monkeypatch.setattr(parts, "_programs", {})
    monkeypatch.setattr(parts, "_built", {})
    # an engine that chunks builds the step and the chunk program, one that does not the whole-prompt program
    held = [engine.serve(dict(SERVING, **over)) for over in ({}, {"prefill_chunk_tokens": 0})]   # the table holds them weakly
    for srv in held:
        srv._ensure_compiled()
    tables = parts.tables()
    for module in ("jit_prefill_fn", "jit_decode_fn", "jit_chunk_decode_fn"):
        dots = {e.part for e in tables[module].values() if e.has_dot}
        assert None not in dots, module
        assert {"mlp.dense", "moe.experts", "moe.route", "attn.qkv", "attn.out", "head"} <= dots, (module, dots)
        assert "mlp" not in dots        # no shared expert, and the dense FFNs have a part of their own


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
    assert what in str(e.value) and "LongcatFlashConfig" in str(e.value)


def test_tiering_alone_and_migration_are_refused_by_name(engine, served):
    from deepspeed_tpu.runtime.config import ServingConfig

    cfg = ServingConfig.from_dict(dict(SERVING))
    cfg.tiering.enabled = True
    with pytest.raises(ValueError, match="serving.tiering"):
        engine.serve(cfg)
    with pytest.raises(ValueError, match="session migration .* latent"):
        served[0]._ensure_migration_programs()
