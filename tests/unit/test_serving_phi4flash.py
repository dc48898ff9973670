"""The ``phi4flash`` family through the paged programs at a small size on the
CPU (8 layers that keep the pattern: Mamba, window, Mamba, window,
Mamba-memory, full, GMU, cross; window 8, page 4, chunk 8), in float32: the
served streams against the float32 reference's full forward
(``perfbench/reference_phi4flash.py``), the three kinds of per-slot state side
by side (pages, rings, the recurrent state), what must leave the recurrent
state alone, the stop behind the self-decoder, the spans and counters, and the
refusals."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models import phi4flash as m
from deepspeed_tpu.serving import model as smodel
from deepspeed_tpu.serving.kv_cache import Cache
from deepspeed_tpu.telemetry import spans
from perfbench import reference_phi4flash as reference

CFG = dict(
    vocab_size=96, hidden_size=32, intermediate_size=64, num_hidden_layers=8, num_attention_heads=4,
    num_key_value_heads=2, mb_per_layer=2, sliding_window=8, layer_norm_eps=1e-5, max_position_embeddings=512,
    tie_word_embeddings=True, initializer_range=0.25,
)
SERVING = dict(max_slots=3, page_size=4, num_pages=64, max_prompt_len=40, max_new_tokens=12,
               prefill_chunk_tokens=8, temperature=0.0)
PROMPTS = (5, 8, 19, 33, 40, 27, 9)     # ONE chunk (<= a chunk: first and last in one call) and 2-5 chunks; 33 and 40 wrap the ring
GAP_TOL = 1e-4                          # float32 both ways, summed in another order
PAGE, W = 4, 13                         # the hand-driven programs: page size, pages a slot


@pytest.fixture(scope="module")
def mcfg():
    return m.Phi4FlashConfig.from_dict(CFG)


@pytest.fixture(scope="module")
def engine(mcfg):
    return deepspeed_tpu.init_inference(model=m.make_module(mcfg), dtype=jnp.float32, seed=3)


def _serve(engine, prompts, new=12, **over):
    srv = engine.serve(dict(SERVING, **over))
    reqs = [srv.submit(p, max_new_tokens=new, seed=i) for i, p in enumerate(prompts)]
    srv.run()
    return srv, reqs


@pytest.fixture(scope="module")
def prompts():
    rng = np.random.default_rng(0)
    return [rng.integers(0, 96, n).astype(np.int32) for n in PROMPTS]


@pytest.fixture(scope="module")
def served(engine, prompts):
    return _serve(engine, prompts)


def _gaps(params, prompt, tokens):
    ids = np.concatenate([prompt, np.asarray(tokens, np.int32)])
    padded = np.zeros((64,), np.int32)
    padded[: len(ids)] = ids
    gap, _ = reference.served_gaps(params, jnp.asarray(padded), len(prompt), len(ids), arch=reference.Arch.from_config(CFG))
    return np.asarray(gap)


# -- (a) the served streams are the reference's --------------------------------

def test_served_streams_are_the_references_across_chunks_ring_wraps_and_slot_reuse(engine, served, prompts):
    srv, reqs = served
    assert srv.ring_pages == 5 and 5 * 4 < max(PROMPTS) and srv.decode_set.cache.rec is not None and srv.decode_set.cache.win_k is not None and not srv.decode_set.cache.latent
    for r, p in zip(reqs, prompts):      # 7 requests through 3 slots: every slot is used again
        assert r.status == "finished" and len(r.tokens) == 12
        assert float(_gaps(engine.params, p, r.tokens).max()) <= GAP_TOL, len(p)
    srv.drain(0.0)
    srv.check_no_leaks()


@pytest.mark.parametrize("seed", [4])
def test_other_seeds_weights_serve_the_references_streams(mcfg, prompts, seed):
    eng = deepspeed_tpu.init_inference(model=m.make_module(mcfg), dtype=jnp.float32, seed=seed)
    _, reqs = _serve(eng, prompts[2:5])
    for r, p in zip(reqs, prompts[2:5]):
        assert float(_gaps(eng.params, p, r.tokens).max()) <= GAP_TOL


def test_the_scan_kernels_interpreted_inside_the_served_programs_give_the_same_streams(prompts):
    """128 channels, so that the kernels take them: the chunk and step entries
    of ``ops/pallas/selective_scan.py`` under the served programs."""
    wide = dict(CFG, hidden_size=64, num_attention_heads=4)
    streams = []
    for impl in ("jnp", "interpret"):
        cfg = m.Phi4FlashConfig.from_dict(wide, ssm_impl=impl)
        eng = deepspeed_tpu.init_inference(model=m.make_module(cfg), dtype=jnp.float32, seed=3)
        _, reqs = _serve(eng, prompts[:4], new=6)
        streams.append([list(r.tokens) for r in reqs])
    assert streams[0] == streams[1]


# -- the three kinds of state --------------------------------------------------

def test_three_kinds_of_state_are_sized_by_what_each_holds(engine, served):
    srv, _ = served
    ds = srv.decode_set
    assert (ds.n_layer, ds.n_window_layer) == (1, 2) and smodel.pool_layers(srv.family) == (1, 2, 3)
    assert ds.cache.k.shape == (1, 64, 1, 4, 16)                        # ONE paged layer of head PAIRS, every lane real
    assert ds.cache.win_k.shape == (2, 1 + 3 * 5, 1, 4, 16)
    ssm, conv, by = ds.cache.rec, ds.cache.conv, ds.cache_bytes()
    assert ssm.shape == (3, 3, 16, 64) and ssm.dtype == jnp.float32 and conv.shape == (3, 3, 3, 64)
    assert len(jax.tree.leaves(ds.cache)) == 6 and by["state"] == 3 * 3 * (16 * 64 * 4 + 3 * 64 * 4) and by["lin_state"] == 0
    g = srv.metrics.gauge("serving_kv_bytes", "", labelnames=("class",))
    assert g.value(**{"class": "state"}) == by["state"]
    assert g.value(**{"class": "paged"}) == 2 * 64 * 4 * 16 * 4 and g.value(**{"class": "window"}) == by["window"]
    phase = [p for p in spans.phases() if p[0] == "ds.init.programs"][-1]
    assert "state=" in phase[3]["kv_bytes"] and "paged=" in phase[3]["kv_bytes"] and "window=" in phase[3]["kv_bytes"]
    assert smodel._kv_homes(srv.family) == [(False, 0), (True, 0), (False, 1), (True, 1), (False, 2), (False, 0),
                                            (False, -1), (False, 0)]      # the cross layer has the full layer's home


def _pools(fam, slots, ring, dirty=None):
    """The hand-driven programs' cache; ``dirty``: the recurrent state filled
    with another request's leavings."""
    n_paged, n_win, n_ssm = smodel.pool_layers(fam)
    KV, D = fam.n_kv_head, fam.head_dim
    kv = jnp.zeros((n_paged, 64, KV, PAGE, D), jnp.float32)
    win = jnp.zeros((n_win, 1 + slots * ring, KV, PAGE, D), jnp.float32)
    N, d = fam.ssm_state
    shapes = ((n_ssm, slots, N, d), (n_ssm, slots, fam.ssm_conv - 1, d))
    if dirty is None:
        state = tuple(jnp.zeros(s, jnp.float32) for s in shapes)
    else:
        state = tuple(jnp.asarray(np.random.default_rng(dirty).normal(size=s), jnp.float32) for s in shapes)
    return Cache(kv, kv, None, win, win, *state)


@functools.lru_cache(maxsize=None)
def _jitted(name, cfg, ring, sampler):
    return jax.jit(functools.partial(getattr(smodel, name), cfg, ring=ring))


def _program(name, cfg, ring):
    """A served program jitted as the engine has it (``cfg`` and ``ring``
    static): the hand-driven calls compile once a shape, not once an op.
    Keyed by the sampler too, which ``logits_out`` replaces."""
    return _jitted(name, cfg, ring, smodel.sample_logits)


def _prefill(cfg, params, prompt, slot, chunk, pages, dirty=None, slots=3):
    """A prompt into ``slot``'s state in chunks of ``chunk`` tokens (0: the
    whole-prompt program) → (logits of the sampled row, the cache, the ring)."""
    fam = cfg.serving_family()
    n = len(prompt)
    ring = -(-(8 + (chunk or 1)) // PAGE) + 1
    cache = _pools(fam, slots, ring, dirty)
    row = np.zeros((1, W), np.int32)
    row[0, : len(pages)] = pages
    key = jnp.zeros((2,), jnp.uint32)
    if not chunk:
        Sp = -(-n // PAGE) * PAGE
        ids = np.zeros((1, Sp), np.int32)
        ids[0, :n] = prompt
        cache, lg = _program("paged_prefill", cfg, ring)(
            params, jnp.asarray(ids), jnp.int32(n), cache, jnp.asarray(row[0, : Sp // PAGE]), key,
            slot=jnp.int32(slot))
        return lg, cache, ring
    for start in range(0, n, chunk):
        ids = np.zeros((1, chunk), np.int32)
        seg = prompt[start: start + chunk]
        ids[0, : len(seg)] = seg
        p0 = start // PAGE
        page_ids = np.zeros((chunk // PAGE,), np.int32)
        avail = row[0, p0: p0 + chunk // PAGE]
        page_ids[: len(avail)] = avail
        cache, lg = _program("paged_chunk_prefill", cfg, ring)(
            params, jnp.asarray(ids), jnp.int32(start), jnp.int32(n), cache, jnp.asarray(page_ids),
            jnp.asarray(row), key, slot=jnp.int32(slot))
    return lg, cache, ring


def _logits(lg, *a, **kw):
    return lg


@pytest.fixture
def logits_out(monkeypatch):
    """The programs hand the sampled rows' LOGITS back in the token's place."""
    monkeypatch.setattr(smodel, "sample_logits", _logits)
    monkeypatch.setattr(smodel, "_sample_slots", _logits)


# -- (b) chunking ----------------------------------------------------------------

@pytest.mark.parametrize("chunk,calls", [(0, 1), (12, 2), (4, 5)])
def test_a_prompt_in_1_2_and_5_chunks_leaves_the_same_state_pages_and_first_logits(mcfg, engine, logits_out, chunk, calls):
    """... whatever the slot held before (``dirty``): a request's first rows
    start from zeros."""
    prompt = np.random.default_rng(1).integers(0, 96, 19).astype(np.int32)
    pages = [7, 3, 9, 12, 5]
    assert calls == (1 if not chunk else -(-19 // chunk))
    want_lg, w, _ = _prefill(mcfg, engine.params, prompt, 1, 0, pages)
    want = np.asarray(reference.logits(engine.params, jnp.asarray(np.pad(prompt, (0, 13))), reference.Arch.from_config(CFG)))[18]
    flat = lambda pool: np.asarray(pool[0, np.asarray(pages)]).transpose(0, 2, 1, 3).reshape(-1, 16)[:19]  # noqa: E731
    for dirty in (None, 7):
        lg, c, _ = _prefill(mcfg, engine.params, prompt, 1, chunk, pages, dirty)
        ssm, conv = c.rec, c.conv
        np.testing.assert_allclose(lg, want_lg, atol=2e-5)
        np.testing.assert_allclose(ssm[:, 1], w.rec[:, 1], rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(conv[:, 1], w.conv[:, 1], rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(flat(c.k), flat(w.k), atol=1e-5)
        np.testing.assert_allclose(flat(c.v), flat(w.v), atol=1e-5)
        np.testing.assert_allclose(np.asarray(lg)[0], want, atol=2e-4)
    # and the other slots' leavings are where they were
    d_ssm, d_conv = _pools(mcfg.serving_family(), 3, 5, 7)[5:7]
    for other in (0, 2):
        assert np.array_equal(ssm[:, other], d_ssm[:, other]) and np.array_equal(conv[:, other], d_conv[:, other])


# -- (c), (e) what must not move -----------------------------------------------------

def _decode_operands(slot_pages, lens, tokens):
    bt = np.zeros((3, W), np.int32)
    for b, pages in slot_pages.items():
        bt[b, : len(pages)] = pages
    return jnp.asarray(tokens, jnp.int32), jnp.asarray(lens, jnp.int32), jnp.asarray(bt), jnp.zeros((3, 2), jnp.uint32)


def test_idle_slots_and_padding_rows_leave_the_recurrent_state_bitwise_unchanged(mcfg, engine, logits_out):
    prompt = np.random.default_rng(2).integers(0, 96, 11).astype(np.int32)
    pages = [7, 3, 9, 12]
    _, cache, ring = _prefill(mcfg, engine.params, prompt, 1, 8, pages, dirty=5)
    before = tuple(np.asarray(s) for s in (cache.rec, cache.conv))
    tok, lens, bt, keys = _decode_operands({1: pages}, [0, 11, 0], [0, 17, 0])      # slots 0 and 2 idle
    out = _program("paged_decode_step", mcfg, ring)(engine.params, tok, lens, cache, bt, keys)
    ssm, conv = out[0].rec, out[0].conv
    for idle in (0, 2):
        assert np.array_equal(ssm[:, idle], before[0][:, idle]) and np.array_equal(conv[:, idle], before[1][:, idle])
    assert not np.array_equal(ssm[:, 1], before[0][:, 1]) and np.array_equal(np.asarray(conv[:, 1, :2]), before[1][:, 1, 1:])
    # a chunk of 8 with 3 real rows: the 5 rows of padding behind them move nothing
    s11, c11 = _prefill(mcfg, engine.params, prompt, 1, 8, pages)[1][5:7]
    s_whole, c_whole = _prefill(mcfg, engine.params, prompt, 1, 0, pages)[1][5:7]
    np.testing.assert_allclose(s11[:, 1], s_whole[:, 1], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(c11[:, 1], c_whole[:, 1], rtol=1e-5, atol=1e-5)


def test_a_chunk_in_the_mixed_step_leaves_the_other_slots_decode_rows_as_the_decode_step_has_them(mcfg, engine, logits_out):
    prompt = np.random.default_rng(3).integers(0, 96, 14).astype(np.int32)
    pages = [7, 3, 9, 12, 5]
    _, cache, ring = _prefill(mcfg, engine.params, prompt, 1, 8, pages, dirty=6)
    state = (cache.rec, cache.conv)
    tok, lens, bt, keys = _decode_operands({1: pages}, [0, 14, 0], [0, 23, 0])
    want = _program("paged_decode_step", mcfg, ring)(engine.params, tok, lens, cache, bt, keys)
    # the same step carrying the first chunk of another prompt into slot 2
    other = np.random.default_rng(4).integers(0, 96, 8).astype(np.int32)
    row = np.zeros((1, W), np.int32)
    row[0, :3] = [20, 21, 22]
    got = _program("paged_mixed_step", mcfg, ring)(
        engine.params, tok, lens, jnp.asarray(other[None]), jnp.int32(0), jnp.int32(13), cache, bt,
        jnp.asarray(row[0, :2]), jnp.asarray(row), keys, jnp.zeros((2,), jnp.uint32),
        slot=jnp.int32(2))
    np.testing.assert_allclose(got[1][1], want[1][1], atol=2e-5)                 # slot 1's logits
    got, want = got[0], want[0]          # the caches
    np.testing.assert_allclose(got.rec[:, 1], want.rec[:, 1], rtol=1e-5, atol=1e-5)            # its scan state
    np.testing.assert_allclose(got.conv[:, 1], want.conv[:, 1], rtol=1e-5, atol=1e-5)
    assert np.array_equal(got.rec[:, 0], state[0][:, 0]) and np.array_equal(got.conv[:, 0], state[1][:, 0])   # idle slot 0
    assert not np.array_equal(got.rec[:, 2], state[0][:, 2])                       # the chunk's slot took its rows
    s2, c2 = _prefill(mcfg, engine.params, other, 2, 8, [20, 21, 22])[1][5:7]
    np.testing.assert_allclose(got.rec[:, 2], s2[:, 2], rtol=1e-5, atol=1e-5)                 # ... from zeros, not from its leavings
    np.testing.assert_allclose(got.conv[:, 2], c2[:, 2], rtol=1e-5, atol=1e-5)


# -- (d) a slot another request just left ----------------------------------------------

def test_a_request_in_a_slot_another_just_left_is_the_request_in_a_fresh_engine(engine, prompts):
    """One slot, so every request is served where the one before it ended."""
    long, mid, short = prompts[4], prompts[3], prompts[0]
    # fresh, then behind a 40-token prompt (five chunks), then behind a prompt of ONE chunk
    _, (fresh, _, after_long, _, after_short) = _serve(engine, [mid, long, mid, short, mid], max_slots=1)
    assert list(fresh.tokens) == list(after_long.tokens) == list(after_short.tokens)
    _, (short_fresh, _, short_after) = _serve(engine, [short, long, short], max_slots=1)   # a prompt's one chunk zeroes too
    assert list(short_fresh.tokens) == list(short_after.tokens)


# -- (h) the stop behind the self-decoder ----------------------------------------------------

@pytest.mark.parametrize("chunk", [0, 8])
def test_with_and_without_the_stop_the_sampled_rows_logits_agree(engine, logits_out, chunk):
    prompt = np.random.default_rng(5).integers(0, 96, 21).astype(np.int32)
    pages = [7, 3, 9, 12, 5, 6]
    stops, runs_all = (m.Phi4FlashConfig.from_dict(CFG, prefill_stops=s) for s in (True, False))
    assert stops.serving_family().stop_after == 5 and runs_all.serving_family().stop_after is None
    a, ca, _ = _prefill(stops, engine.params, prompt, 0, chunk, pages)
    b, cb, _ = _prefill(runs_all, engine.params, prompt, 0, chunk, pages)
    np.testing.assert_allclose(a, b, atol=2e-5)
    np.testing.assert_allclose(ca.rec, cb.rec, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(ca.k, cb.k, rtol=1e-5, atol=1e-5)


def test_the_stop_takes_the_matrix_work_of_the_cross_decoder_off_the_prompt_rows(engine):
    """Counted on the lowered chunk program: with the stop the 3 sub-blocks'
    products behind layer 5 run on 1 + B rows, without it on C + B."""
    def flops(stops):
        cfg = m.Phi4FlashConfig.from_dict(CFG, prefill_stops=stops)
        cache = _pools(cfg.serving_family(), 3, 5)
        tok, lens, bt, keys = _decode_operands({}, [0, 0, 0], [0, 0, 0])
        fn = lambda p: smodel.paged_mixed_step(  # noqa: E731
            cfg, p, tok, lens, jnp.zeros((1, 8), jnp.int32), jnp.int32(0), jnp.int32(8), cache, bt,
            jnp.zeros((2,), jnp.int32), jnp.zeros((1, W), jnp.int32), keys, jnp.zeros((2,), jnp.uint32),
            slot=jnp.int32(0), ring=5)
        return jax.jit(fn).lower(engine.params).compile().cost_analysis()["flops"]

    with_stop, without = flops(True), flops(False)
    mlp = 2 * 3 * 32 * 64                       # a row's MLP products: the largest part of a sub-block
    assert without - with_stop > 7 * 2 * mlp    # 7 chunk rows skip 2 sub-blocks' MLPs and more
    assert with_stop < without


def test_the_chunk_span_counts_rows_through_each_half_and_the_skipped_ones(engine, prompts):
    t0 = spans._clock()      # not the last record's end: `since` is inclusive, and that record may be another server's emit
    srv, reqs = _serve(engine, prompts)
    chunks = [r[3] for r in spans.snapshot(since=t0) if r[0] == "ds.serve.chunk"]
    long = [len(p) for p in prompts]         # every prompt goes in chunks: one of 5 or 8 tokens in ONE (ISSUE 63)
    assert all("rows_self" in c and "rows_cross" in c for c in chunks)
    assert sum(c["rows_self"] for c in chunks) == sum(long) == sum(c["tokens"] for c in chunks)
    assert sum(c["rows_cross"] for c in chunks) == len(long)                   # 1 a prompt: its final chunk's sampled row
    for c in chunks:
        assert 0 <= c["rows_cross"] <= c["chunks"] + c["rode"]
    skipped = srv.metrics.counter("serve_prefill_rows_skipped_total", "").value()
    assert skipped == sum(long) - len(long)
    # the decode step's count of keys: 2 rings of at most 8, the full layer and its cross reader, over 8 sub-blocks
    d = [r[3] for r in spans.snapshot(since=t0) if r[0] == "ds.serve.decode.dispatch"][-1]
    assert d["attended"] <= d["active"] * (2 * 8 + 2 * 52) // 8
    off = m.Phi4FlashConfig.from_dict(CFG, prefill_stops=False)
    eng = deepspeed_tpu.init_inference(model=m.make_module(off), dtype=jnp.float32, seed=3)
    t1 = spans.snapshot()[-1][2]
    srv2, reqs2 = _serve(eng, prompts)
    assert [list(r.tokens) for r in reqs2] == [list(r.tokens) for r in reqs]    # same weights, same streams
    chunks2 = [r[3] for r in spans.snapshot(since=t1) if r[0] == "ds.serve.chunk"]
    assert sum(c["rows_cross"] for c in chunks2) == sum(c["rows_self"] for c in chunks2) == sum(long)
    assert srv2.metrics.counter("serve_prefill_rows_skipped_total", "").value() == 0           # nothing leaves early there


# -- (i) the refusals --------------------------------------------------------------------

@pytest.mark.parametrize("section,what", [
    ({"prefix_cache": {"enabled": True}}, "serving.prefix_cache"),
    ({"kv_cache_dtype": "int8"}, "serving.kv_cache_dtype=int8"),
    ({"placement": {"tp": 2}}, "serving.placement.tp > 1"),
    ({"placement": {"disaggregate": True}}, "serving.placement.disaggregate"),
    ({"speculative": {"enabled": True, "k": 3, "ngram": 2}}, "serving.speculative"),
])
def test_mechanisms_that_know_pages_only_are_refused_by_name(engine, section, what):
    with pytest.raises(ValueError, match="recurrent state") as e:
        engine.serve(dict(SERVING, **section))
    assert what in str(e.value) and "Phi4FlashConfig" in str(e.value)


def test_tiering_alone_is_refused_by_name(engine):
    from deepspeed_tpu.runtime.config import ServingConfig

    cfg = ServingConfig.from_dict(dict(SERVING))
    cfg.tiering.enabled = True
    with pytest.raises(ValueError, match="serving.tiering"):
        engine.serve(cfg)


def test_migration_is_refused_by_name(engine):
    srv = engine.serve(dict(SERVING))
    with pytest.raises(ValueError, match="session migration is not available for a model with recurrent state"):
        srv._ensure_migration_programs()


def test_the_family_is_named_where_a_model_without_the_pieces_is_refused():
    from deepspeed_tpu.serving import ServingEngine

    class NoFamily:
        model_config = object()

    with pytest.raises(ValueError, match="phi4flash"):
        ServingEngine(NoFamily(), dict(SERVING))


def test_no_new_knob_in_the_serving_config():
    import dataclasses

    from deepspeed_tpu.runtime.config import ServingConfig

    assert not any("ssm" in f.name or "recurrent" in f.name or "state" in f.name for f in dataclasses.fields(ServingConfig))
