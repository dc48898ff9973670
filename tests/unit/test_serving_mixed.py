"""The mixed step (ISSUE 35): a prefilling slot's chunk and the decode rows of
a step through the weights once, in the chunk program's place.

Three families at their tiny sizes on the CPU, float32: ``gpt2`` (two pools,
also int8), ``exaone_moe`` (a paged layer beside window rings, expert layers)
and ``mistral4`` (one latent pool, expert layers). The program itself against
the chunk call followed by the decode step on the same pools; the engine with
chunks riding against every request served alone (where nothing decodes, so
no chunk can ride); when a rider starts decoding and what stamps its first
token; two slots prefilling in one step; the counts the benchmark's readers
live on; and the paths that must not ride (speculation, disaggregation)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.inference.engine import InferenceEngine
from deepspeed_tpu.models import exaone_moe, gpt2, mistral4
from deepspeed_tpu.serving import model as smodel
from deepspeed_tpu.serving.kv_cache import Cache
from deepspeed_tpu.telemetry import spans

from .test_serving_exaone import CFG as KX_CFG
from .test_serving_mistral4 import CFG as MS4_CFG

SERVING = dict(max_slots=3, page_size=4, num_pages=96, max_prompt_len=40, max_new_tokens=12,
               prefill_chunk_tokens=8, temperature=0.0, kv_cache_dtype="float32")
PROMPTS = (5, 19, 33, 40, 27, 9, 22)    # ONE chunk (<= a chunk: its first and last) and 2-5 chunks
FAMILIES = ("gpt2", "exaone_moe", "mistral4")


@pytest.fixture(scope="module")
def engines():
    """family -> (engine, vocabulary), each built once."""
    made = {}

    def get(family):
        if family not in made:
            if family == "gpt2":
                cfg = gpt2.get_config("gpt2-tiny", attn_impl="jnp")
                eng = InferenceEngine(gpt2.make_module(cfg), params=gpt2.init_params(cfg, jax.random.PRNGKey(0)),
                                      dtype=jnp.float32)
                made[family] = (eng, cfg.vocab_size)
            else:
                mod, cfg = (exaone_moe, exaone_moe.ExaoneMoEConfig.from_dict(KX_CFG)) if family == "exaone_moe" \
                    else (mistral4, mistral4.Mistral4Config.from_dict(MS4_CFG))
                made[family] = (deepspeed_tpu.init_inference(model=mod.make_module(cfg), dtype=jnp.float32, seed=3),
                                cfg.vocab_size)
        return made[family]

    return get


def _prompts(vocab, lens=PROMPTS, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, n).astype(np.int32) for n in lens]


def _together(engine, prompts, **over):
    srv = engine.serve(dict(SERVING, **over))
    reqs = [srv.submit(p, max_new_tokens=12, seed=i) for i, p in enumerate(prompts)]
    srv.run()
    return srv, reqs


def _alone(engine, prompts, **over):
    """Each request through a server that holds nothing else: its chunks find
    no decode step to ride, its decode steps carry no chunk."""
    srv = engine.serve(dict(SERVING, **over))
    reqs = []
    for i, p in enumerate(prompts):
        reqs.append(srv.submit(p, max_new_tokens=12, seed=i))
        srv.run()
    return srv, reqs


def _count(srv, name):
    return srv.metrics.counter(name, "").value()


def _steps(recs):
    """The ring's records, a list of {leaf name: attrs} a ``ds.serve.step``
    (a step's leaves are recorded before the step itself)."""
    out, cur = [], {}
    for name, _, _, attrs in recs:
        if name == "ds.serve.step":
            out.append(cur)
            cur = {}
        else:
            cur[name] = attrs
    return out


# -- the program ----------------------------------------------------------------

def _pools(fam, rng, P, page, B, ring, int8):
    """A cache whose every page holds something (a cached context is any
    values at all): K, V | None, scales | None, the rings | None."""
    n_paged = sum(1 for w in fam.windows if not w)

    def pool(layers, pages):
        shape = (layers, pages, fam.n_kv_head, page, fam.head_dim)
        if int8:
            return jnp.asarray(rng.integers(-127, 128, shape), jnp.int8)
        return jnp.asarray(rng.normal(size=shape) * 0.3, jnp.float32)

    k = pool(n_paged, P)
    v = pool(n_paged, P) if fam.kv_pools == 2 else None
    scales = jnp.asarray(rng.uniform(0.002, 0.004, (n_paged, P, fam.n_kv_head, 2)), jnp.float32) if int8 else None
    n_win = len(fam.windows) - n_paged
    win = (pool(n_win, 1 + B * ring), pool(n_win, 1 + B * ring)) if n_win else (None, None)
    return Cache(k, v, scales, *win)


def _program(fn, cfg):
    """``fn(cfg, ...)`` as the engine calls it, one compiled program (called
    eagerly its operations dispatch one at a time). A new program every time:
    what the trace reads of ``smodel`` is read again."""
    return jax.jit(functools.partial(fn, cfg), static_argnames=("ring",))


def _one_call(engines, family, int8):
    """A step's operands: slot 2 prefills (two pages cached, the chunk at 8
    of a 13-token prompt: 5 real rows), 0 and 3 decode, 1 is empty."""
    engine, vocab = engines(family)
    cfg, params = engine.model_config, engine.params
    fam = cfg.serving_family()
    rng = np.random.default_rng(5)
    B, page, P, W, C = 4, 4, 40, 13, 8
    ring = -(-(max(fam.windows) + C) // page) + 1 if any(fam.windows) else 0
    cache = _pools(fam, rng, P, page, B, ring, int8)
    row = np.zeros((1, W), np.int32)
    row[0, :5] = [21, 22, 23, 24, 25]
    bt = np.zeros((B, W), np.int32)
    bt[0, :4], bt[3, :6] = [1, 2, 3, 4], [5, 6, 7, 8, 9, 10]
    seq_lens = np.asarray([9, 0, 0, 20], np.int32)        # slot 3 writes at offset 0 of a page: an int8 scale is set
    tokens = np.asarray([7, 0, 0, 11], np.int32)
    keys = np.zeros((B, 2), np.uint32)
    ids = rng.integers(0, vocab, (1, C)).astype(np.int32)
    start, plen, slot = np.int32(8), np.int32(13), np.int32(2)
    page_ids, key0 = np.asarray([23, 24], np.int32), np.asarray([0, 3], np.uint32)
    kw = dict(ring=ring, slot=slot)
    chunk = (ids, start, plen, cache)
    return cfg, params, fam, (tokens, seq_lens), chunk, bt, (page_ids, row), (keys, key0), kw


@pytest.mark.parametrize("family,int8", [("gpt2", False), ("gpt2", True), ("exaone_moe", False), ("mistral4", False)])
def test_one_mixed_call_is_the_chunk_call_then_the_decode_step(engines, family, int8):
    """The same pools, rows and keys: tokens equal, every real page equal
    (the scratch page takes idle rows' and padding's writes in any order)."""
    cfg, params, fam, (tokens, seq_lens), (ids, start, plen, cache), bt, (page_ids, row), (keys, key0), kw = \
        _one_call(engines, family, int8)
    B, ring, slot = len(tokens), kw["ring"], kw.pop("slot")
    after_chunk = _program(smodel.paged_chunk_prefill, cfg)(params, ids, start, plen, cache, page_ids, row, key0, slot=slot, **kw)
    c1, tok_c = after_chunk[:2]
    after_step = _program(smodel.paged_decode_step, cfg)(params, tokens, seq_lens, c1, bt, keys, ring=ring)
    mixed = _program(smodel.paged_mixed_step, cfg)(params, tokens, seq_lens, ids, start, plen, cache, bt, page_ids, row, keys,
                                                   key0, slot=slot, **kw)

    toks = np.asarray(mixed[1])
    assert toks.shape == (B + 1,)
    np.testing.assert_array_equal(toks[[0, 3]], np.asarray(after_step[1])[[0, 3]])
    np.testing.assert_array_equal(toks[-1:], np.asarray(tok_c))
    assert jax.tree.structure(mixed[0]) == jax.tree.structure(after_step[0]) == jax.tree.structure(cache)   # a latent family has no V pool
    for f, got, want in zip(Cache._fields, mixed[0], after_step[0]):
        if got is not None:
            got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
            np.testing.assert_allclose(got[:, 1:], want[:, 1:], atol=1 if int8 and f in "kv" else 1e-5)   # a code may round apart
    if cache.win_k is not None:
        # the prefilling slot's own decode row is idle: its ring holds the chunk's pages and nothing of that row
        mine = 1 + 2 * ring + np.arange(ring)
        for got, chunk_only in zip(mixed[0][3:5], c1[3:5]):
            np.testing.assert_allclose(np.asarray(got)[:, mine], np.asarray(chunk_only)[:, mine], atol=1e-5)
    if fam.sparse_layers:
        counts, c_chunk, c_step = (np.asarray(x[2]) for x in (mixed, after_chunk, after_step))
        assert counts.shape == (len(fam.sparse_layers), fam.experts_held)
        np.testing.assert_array_equal(counts, c_chunk + c_step)    # ONE count of the call's real tokens
        assert counts.sum() <= (5 + 2) * fam.experts_per_token * len(fam.sparse_layers)


@pytest.mark.parametrize("family", FAMILIES)
def test_a_call_with_no_real_decode_row_is_the_chunk_call_and_may_skip_the_rows_reads(engines, family, monkeypatch):
    """What a chunk takes that rides nothing: the chunk's token and every real
    page are the call's with no decode row at all, whether the idle rows'
    attention is skipped (as it is where the slots are many) or read; and
    with a real row the skip's conditional changes nothing."""
    cfg, params, fam, rows, (ids, start, plen, cache), bt, (page_ids, row), (keys, key0), kw = \
        _one_call(engines, family, False)

    def call(rows, bt, skip):
        monkeypatch.setattr(smodel, "SKIP_IDLE_READS_FROM_SLOTS", 1 if skip else 1 << 30)
        return _program(smodel.paged_mixed_step, cfg)(params, *rows, ids, start, plen, cache, bt, page_ids, row, keys, key0, **kw)

    def same(a, b, toks):
        np.testing.assert_array_equal(np.asarray(a[1])[toks], np.asarray(b[1])[toks])
        for x, y in zip(jax.tree.leaves(a[0]), jax.tree.leaves(b[0])):
            np.testing.assert_allclose(np.asarray(x)[:, 1:], np.asarray(y)[:, 1:], atol=1e-5)

    alone = _program(smodel.paged_chunk_prefill, cfg)(params, ids, start, plen, cache, page_ids, row, key0, **kw)
    idle = (np.zeros_like(rows[0]), np.zeros_like(rows[1]))
    for skip in (True, False):
        got = call(idle, np.zeros_like(bt), skip)
        same((got[0], got[1][-1:]), alone, slice(None))       # tokens [slots + 1]: the chunk's is last
        if fam.sparse_layers:                                   # idle rows are no tokens of the call
            np.testing.assert_array_equal(np.asarray(got[2]), np.asarray(alone[2]))
    same(call(rows, bt, True), call(rows, bt, False), [0, 3, 4])


# -- the engine -------------------------------------------------------------------

@pytest.mark.parametrize("family", FAMILIES)
def test_requests_served_with_chunks_riding_get_the_tokens_they_get_alone(engines, family):
    engine, vocab = engines(family)
    prompts = _prompts(vocab)
    srv, mixed = _together(engine, prompts)
    ref, alone = _alone(engine, prompts)
    for a, b in zip(mixed, alone):
        assert a.status == b.status == "finished" and list(a.tokens) == list(b.tokens)
    n_chunks = sum(-(-len(p) // 8) for p in prompts)                      # a prompt of 5 is one chunk, as one of 8 is
    assert _count(srv, "serving_chunk_prefills_total") == _count(ref, "serving_chunk_prefills_total") == n_chunks
    assert _count(ref, "serving_chunks_rode_total") == 0                 # alone: nothing decodes beside a prefill
    assert 0 < _count(srv, "serving_chunks_rode_total") <= n_chunks
    # the mixed program is the chunk program, and an engine that chunks has no whole-prompt program (ISSUE 63)
    assert len(srv.executables) == srv.expected_executables == 2
    for s in (srv, ref):
        s.drain(0.0)
        s.check_no_leaks()


@pytest.mark.parametrize("over", [{"kv_cache_dtype": "int8"}, {"prefix_cache": {"enabled": True}},
                                  {"kv_cache_dtype": "int8", "prefix_cache": {"enabled": True}}],
                         ids=["int8", "prefix", "int8-prefix"])
def test_int8_pools_and_prefix_tails_ride_too(engines, over):
    """The gpt2 family's other pools: int8 codes and scales, and a prefix
    cache, whose hits' tails always take the chunk program."""
    engine, vocab = engines("gpt2")
    rng = np.random.default_rng(2)
    shared = rng.integers(0, vocab, 16).astype(np.int32)
    prompts = [np.concatenate([shared, rng.integers(0, vocab, n).astype(np.int32)]) for n in (3, 9, 14, 6, 20)]
    srv, mixed = _together(engine, prompts, **over)
    ref, alone = _alone(engine, prompts, **over)
    for a, b in zip(mixed, alone):
        assert a.status == b.status == "finished" and list(a.tokens) == list(b.tokens)
    assert _count(srv, "serving_chunks_rode_total") > 0 == _count(ref, "serving_chunks_rode_total")
    if "prefix_cache" in over:
        assert srv.stats()["prefix_hits_partial"] + srv.stats()["prefix_hits_full"] > 0
    for s in (srv, ref):
        s.drain(0.0)
        s.release_prefix_cache()
        s.check_no_leaks()


@pytest.mark.parametrize("family", FAMILIES)
def test_a_rider_starts_decoding_the_step_after_its_last_chunk_and_that_step_stamps_its_first_token(engines, family):
    engine, vocab = engines(family)
    short, long = _prompts(vocab, (5, 20), seed=1)
    ticks = iter(range(10_000))
    from deepspeed_tpu.serving import ServingEngine
    srv = ServingEngine(engine, dict(SERVING), clock=lambda: float(next(ticks)))
    a = srv.submit(short, max_new_tokens=12, seed=0)
    srv.step()                                     # a's one chunk, alone and waited for, and the first decode step
    assert len(a.tokens) == 2 and _count(srv, "serving_chunk_prefills_total") == 1
    b = srv.submit(long, max_new_tokens=12, seed=1)
    for n_chunk in (1, 2, 3):                      # 20 tokens: three chunks, each beside a's decode step
        srv.step()
        assert _count(srv, "serving_chunks_rode_total") == n_chunk == _count(srv, "serving_chunk_prefills_total") - 1
        assert len(a.tokens) == 2 + n_chunk         # no decode step was held back
    # the last chunk rode the step that call LAUNCHED, which is in flight: b's first token is its last place, on
    # the device, and b's rows are launched from the next step on all the same
    assert len(b.tokens) == 0 and b.t_first_token is None
    assert not any(s.prefilling for s in srv.slots if s.request is not None)
    t_before = float(next(ticks))
    srv.step()                                     # launches b's first decode row, then reads the step the chunk rode
    assert len(b.tokens) == 1 and b.t_first_token is not None and b.t_first_token > t_before
    assert b.t_emissions == [b.t_first_token] and a.t_emissions[-1] <= b.t_first_token
    assert len(a.tokens) == 6
    srv.step()                                     # no step was lost: the row launched ahead is read here
    assert len(b.tokens) == 2 and len(a.tokens) == 7
    srv.run()
    _, alone = _alone(engine, [short, long])
    assert [list(r.tokens) for r in (a, b)] == [list(r.tokens) for r in alone]


@pytest.mark.parametrize("family", FAMILIES)
def test_of_two_slots_prefilling_in_a_step_one_rides_and_one_is_a_call_with_no_decode_row(engines, family):
    engine, vocab = engines(family)
    short, l1, l2 = _prompts(vocab, (5, 30, 26), seed=3)
    t0 = spans._clock()
    srv = engine.serve(dict(SERVING))
    reqs = [srv.submit(short, max_new_tokens=12, seed=0)]
    srv.step()
    reqs += [srv.submit(p, max_new_tokens=12, seed=i + 1) for i, p in enumerate((l1, l2))]
    launched, launch = [], srv._launch_chunk
    srv._launch_chunk = lambda i, rows, carried=0: (launched.append((spans._clock(), rows[2].any(), carried))
                                                    or launch(i, rows, carried))
    srv.step()
    srv._launch_chunk = launch
    assert sum(1 for s in srv.slots if s.request is not None and s.prefilling) == 2
    assert _count(srv, "serving_chunk_prefills_total") == 1 + 2 and _count(srv, "serving_chunks_rode_total") == 1
    # both calls under the dispatch leaf, the one with no decode row first (nothing waits for it), each under a
    # ds.serve.launch leaf of its own that says what it carried: a trace's reader knows a call by its number
    (_, d0, d1, _), = [r for r in spans.snapshot(since=t0) if r[0] == "ds.serve.decode.dispatch"][-1:]
    assert [(rows, carried) for _, rows, carried in launched] == [(False, 0), (True, 1)]
    assert all(d0 <= t <= d1 for t, _, _ in launched)
    leaves = [r for r in spans.snapshot(since=t0) if r[0] == "ds.serve.launch" and d0 <= r[1] and r[2] <= d1]
    assert [(r[3]["kind"], r[3]["rows"], r[3]["tokens"]) for r in leaves] == [("chunk", 0, 8), ("mixed", 1, 8)]
    assert leaves[1][3]["launch"] == leaves[0][3]["launch"] + 1 == srv._flight.launch
    step = _steps(spans.snapshot(since=t0))[-1]
    assert (step["ds.serve.chunk"]["chunks"], step["ds.serve.chunk"]["rode"]) == (1, 1)
    assert step["ds.serve.chunk"]["tokens"] == 16 and step["ds.serve.decode.dispatch"]["active"] == 1
    srv.run()
    _, alone = _alone(engine, [short, l1, l2])
    assert [list(r.tokens) for r in reqs] == [list(r.tokens) for r in alone]
    assert _count(srv, "serving_chunk_prefills_total") == 1 + 4 + 4
    srv.drain(0.0)
    srv.check_no_leaks()


@pytest.mark.parametrize("family", FAMILIES)
def test_the_spans_carry_the_counts_the_readers_live_on(engines, family):
    engine, vocab = engines(family)
    fam = engine.model_config.serving_family()
    prompts = _prompts(vocab)
    t0 = spans._clock()
    srv, reqs = _together(engine, prompts)
    steps = _steps(spans.snapshot(since=t0))
    chunks = [s["ds.serve.chunk"] for s in steps if "ds.serve.chunk" in s]
    long = [len(p) for p in prompts]           # every prompt goes in chunks, one of 5 tokens in one
    # every chunk advanced is in tokens / attended, ridden or not; chunks counts the calls with no decode row
    assert all(c["rode"] in (0, 1) for c in chunks)
    assert sum(c["chunks"] + c["rode"] for c in chunks) == sum(-(-n // 8) for n in long)
    assert sum(c["tokens"] for c in chunks) == sum(long)
    assert sum(c["attended"] for c in chunks) == sum(n * (n + 1) // 2 for n in long)
    assert sum(c["rode"] for c in chunks) == _count(srv, "serving_chunks_rode_total") > 0
    assert sum(c["chunks"] + c["rode"] for c in chunks) == _count(srv, "serving_chunk_prefills_total")
    assert sum(c["whole"] for c in chunks) == sum(n <= 8 for n in long) == 1     # the prompts that took ONE call
    n_sparse = len(fam.sparse_layers)
    # a call reads the step the call before launched (an empty server's first call launches two and reads the
    # first): the n-th emit leaf is the n-th dispatch leaf's, and a chunk rides its own call's first dispatch
    launches, emits, cur = [], [], None
    for name, _, _, attrs in spans.snapshot(since=t0):
        if name == "ds.serve.chunk":
            cur = attrs
        elif name == "ds.serve.decode.dispatch":
            launches.append((attrs, cur))
            cur = None
        elif name == "ds.serve.emit":
            emits.append(attrs)
        elif name == "ds.serve.step":
            assert cur is None or not cur["rode"]                  # a chunk rides a decode dispatch only
            cur = None
    assert len(launches) == len(emits) and sum(d["ahead"] for d, _ in launches) >= len(launches) - 2
    for (d, c), e in zip(launches, emits):
        assert d["active"] >= 1
        # the dispatch leaf counts the decode rows alone, whatever rode
        assert e["tokens"] == d["active"] <= d["attended"] and d["pages"] >= d["active"]
        if n_sparse:
            rode = c["tokens"] if c is not None and c["rode"] and not c["chunks"] else None
            routed = e["moe_pairs_routed"] // (fam.experts_per_token * n_sparse)
            assert routed == d["active"] + rode if rode is not None else routed >= d["active"]
            assert e["moe_experts_hit"] <= fam.experts_held * n_sparse      # the union over the call's rows
            assert e["moe_load_max"] <= routed
    assert sum(d["active"] for d, _ in launches) == sum(len(r.tokens) - 1 for r in reqs)
    if n_sparse:
        reports = [c for c in chunks if "moe_calls" in c]
        # no ridden call is reported under moe_calls: a prompt reports the calls that rode nothing
        assert sum(c["moe_calls"] for c in reports) == sum(c["chunks"] for c in chunks)
        emits = [s["ds.serve.emit"] for s in steps if "ds.serve.emit" in s]
        per_token = fam.experts_per_token * n_sparse
        assert sum(a["moe_pairs_routed"] for a in emits + reports) \
            == (sum(len(r.tokens) - 1 for r in reqs) + sum(long)) * per_token
        assert _count(srv, "serving_moe_pairs_held_total") == sum(a["moe_pairs_held"] for a in emits + reports)
    else:
        assert not any("moe_calls" in c for c in chunks)


@pytest.mark.parametrize("family,kernels", [("gpt2", ("decode_fn", "chunk_fn")), ("exaone_moe", ("decode_fn", "chunk_fn")),
                                            ("mistral4", ("mla_paged_decode", "mla_paged_chunk"))])
def test_the_dispatch_leaf_and_the_counters_carry_the_attention_kernels_walk(engines, family, kernels, monkeypatch):
    """ISSUE 58: the items a step's one-token kernel call owns beside its rectangle, on the
    ``ds.serve.decode.dispatch`` leaf whichever implementation attends, and in the registry by the kernel's
    name in a trace where the programs call the kernel (off the TPU the test tells the census so). Host
    arithmetic by the wrapper's rule: a live slot owns the page blocks its length reaches, an idle one ONE item."""
    from deepspeed_tpu.ops import attention
    from deepspeed_tpu.ops.pallas.decode_attention import paged_decode_blocks

    engine, vocab = engines(family)
    fam = engine.model_config.serving_family()
    prompts = _prompts(vocab)[:4]
    steps = "latent_attention_grid_steps" if fam.kv_pools == 1 else "paged_attention_grid_steps"
    real = getattr(attention, steps)
    monkeypatch.setattr(attention, steps, lambda impl, *a, **k: real("pallas", *a, **k))
    t0 = spans._clock()
    srv, reqs = _together(engine, prompts)
    walk = lambda k: srv.metrics.counter("serving_attn_walk_steps_total", "", ("program",)).value(program=k)  # noqa: E731
    rect = lambda k: srv.metrics.counter("serving_attn_rect_steps_total", "", ("program",)).value(program=k)  # noqa: E731
    disp = [r[3] for r in spans.snapshot(since=t0) if r[0] == "ds.serve.decode.dispatch"]
    # 13 pages of 4 a slot, 8 of them (32 keys) a grid step in every family here: 2 blocks a slot, 3 slots
    assert srv.pages_per_slot == 13
    if fam.kv_pools == 2:
        assert paged_decode_blocks(fam.n_kv_head, 4, fam.head_dim, 4, 13) == (fam.n_kv_head, 8)
    assert all(d["rect_steps"] == 3 * 2 for d in disp)
    # a step of `active` rows at lengths that sum to `attended - active`: each owns 1 + length // 32, the rest one item
    assert all(3 <= d["walk_steps"] <= 3 + d["active"] for d in disp)
    lens = [range(len(p), len(p) + 11) for p in prompts]      # a request's first token is its prefill's
    assert sum(d["walk_steps"] for d in disp) == sum(3 - d["active"] for d in disp) + sum(1 + n // 32 for r in lens for n in r)
    assert walk(kernels[0]) == sum(d["walk_steps"] for d in disp) and rect(kernels[0]) == 6 * len(disp)
    starts = [s for p in prompts for s in range(0, len(p), 8)]
    assert walk(kernels[1]) == sum(min((s + 7) // 32, 1) + 1 for s in starts) and rect(kernels[1]) == 2 * len(starts)
    # nothing is counted where the programs hold no kernel; the leaf still says what the call owns
    monkeypatch.undo()
    t0 = spans._clock()
    srv, _ = _together(engine, prompts[:2])
    assert srv.metrics.counter("serving_attn_rect_steps_total", "", ("program",)).value(program=kernels[0]) == 0
    assert all(d["rect_steps"] == 6 for d in (r[3] for r in spans.snapshot(since=t0) if r[0] == "ds.serve.decode.dispatch"))


# -- what must not ride -------------------------------------------------------------

@pytest.mark.parametrize("over,n_exe", [
    ({"speculative": {"enabled": True, "k": 3, "ngram": 2}}, 2),
    ({"placement": {"disaggregate": True}}, 4),
    ({"placement": {"tp": 2}}, 2),
], ids=["speculation", "disaggregated", "tp2"])
def test_speculation_and_disaggregation_keep_the_chunk_alone_and_tp_rides(engines, over, n_exe):
    """The verify step and a prefill placement of its own take the chunk
    program with no decode row, as they took the chunk program; a
    tensor-parallel placement is one placement and rides. Same tokens, and
    no whole-prompt program in any of them: the verify (or decode) step, the
    chunk program and, disaggregated, the handoff's pair."""
    engine, vocab = engines("gpt2")
    prompts = _prompts(vocab)
    srv, got = _together(engine, prompts, **over)
    _, alone = _alone(engine, prompts)
    for a, b in zip(got, alone):
        assert a.status == "finished" and list(a.tokens) == list(b.tokens)
    assert len(srv.executables) == srv.expected_executables == n_exe
    rode = _count(srv, "serving_chunks_rode_total")
    assert rode > 0 if "tp" in over.get("placement", {}) else rode == 0
    assert _count(srv, "serving_chunk_prefills_total") == sum(-(-len(p) // 8) for p in prompts)
    assert not any("prefill" in name for name, _ in srv.executable_names() if "chunk" not in name)
    srv.drain(0.0)
    srv.check_no_leaks()
