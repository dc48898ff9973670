"""GPT-2's 64-wide heads cached as PAIRS (ISSUE 56): where two heads fill the
128 lanes, ``GPT2Family`` gives the serving programs ``ceil(H / 2)`` cached
heads of 128 lanes under ``2 * ceil(H / 2)`` zero-padded queries, and the
zeros add exactly 0 to every product.

At a tiny depth on the CPU, float32: the family's pieces around a pair
attention against ``models/gpt2._attention`` on the same weights (an even and
an odd head count); a served engine against ``generate()``'s greedy tokens
down every program (whole-prompt prefill, chunks, mixed steps, decode, the
verify step, at tp 2); what stays a head a published head (``attn_impl="jnp"``,
an int8 cache); and the shapes the pools and the kernels' plans come out with."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.engine import InferenceEngine
from deepspeed_tpu.models import gpt2
from deepspeed_tpu.ops.attention import paged_multitoken_cached_attention
from deepspeed_tpu.ops.pallas.decode_attention import (
    paged_decode_blocks,
    paged_multitoken_blocks,
)
from deepspeed_tpu.serving import model as smodel
from deepspeed_tpu.serving.kv_cache import Cache, pool_stored_shape

pytestmark = pytest.mark.serving

SERVING = dict(max_slots=3, page_size=4, num_pages=96, max_prompt_len=40, max_new_tokens=10,
               temperature=0.0, kv_cache_dtype="float32")
PROMPTS = (5, 19, 33, 40, 27, 9, 22)


def _cfg(n_head, **over):
    return gpt2.GPT2Config(n_embd=64 * n_head, n_head=n_head, n_layer=2, vocab_size=512,
                           n_positions=128, **over)


# -- the family ------------------------------------------------------------------

@pytest.mark.parametrize("n_head", [4, 5])
def test_family_geometry_is_pairs_of_128_lanes(n_head):
    fam = _cfg(n_head).serving_family()
    assert fam.pairs
    assert (fam.n_kv_head, fam.n_head, fam.head_dim, fam.v_width) == (3 if n_head == 5 else 2, 2 * fam.n_kv_head, 128, 128)
    assert fam.sm_scale == pytest.approx(1 / 8)            # the published head's width


@pytest.mark.parametrize("how", ["head_dim_16", "head_dim_128", "attn_impl_jnp", "per_head_cache"])
def test_what_stays_a_head_a_published_head(how):
    cfg = {
        "head_dim_16": gpt2.get_config("gpt2-tiny"),
        "head_dim_128": gpt2.GPT2Config(n_embd=256, n_head=2, n_layer=1, vocab_size=64, n_positions=32),
        "attn_impl_jnp": _cfg(5, attn_impl="jnp"),
        "per_head_cache": _cfg(5).per_head_cache(),
    }[how]
    fam = cfg.serving_family()
    assert not fam.pairs and fam.sm_scale is None
    assert (fam.n_head, fam.n_kv_head, fam.head_dim) == (cfg.n_head, cfg.n_head, cfg.head_dim)
    # the rule survives the per-rank replace a TP placement makes
    assert type(dataclasses.replace(cfg, n_layer=1)) is type(cfg)


@pytest.mark.parametrize("path", ["whole_prompt", "paged"])
@pytest.mark.parametrize("n_head", [4, 5])
def test_pair_attention_is_the_published_attention(n_head, path):
    """``qkv`` -> attention over the pair heads -> ``attn_out`` against
    ``_attention`` on the same weights: the whole-prompt program's dense branch
    (which also writes the pages) and the paged dispatcher's jnp path over
    those pages."""
    from deepspeed_tpu.ops.layer_norm import layer_norm_inference

    cfg = _cfg(n_head)
    fam = cfg.serving_family()
    lp = fam.layer(gpt2.init_params(cfg, jax.random.PRNGKey(0)), 1)
    S, page = 24, 4
    h = jax.random.normal(jax.random.PRNGKey(1), (1, S, cfg.n_embd))
    hn = layer_norm_inference(h, lp["ln_1"]["scale"], lp["ln_1"]["bias"], cfg.layer_norm_epsilon)
    want = gpt2._attention(cfg, lp["attn"], hn, False, None)

    q, k, v = fam.qkv(lp, h, None, 1)
    assert q.shape == (1, S, fam.n_head, 128) and k.shape == v.shape == (1, S, fam.n_kv_head, 128)
    pools = [jnp.zeros((1, 1 + S // page, fam.n_kv_head, page, 128)) for _ in range(2)]
    page_ids = jnp.arange(1, 1 + S // page)
    o, (k_pool, v_pool, *_) = smodel._attention_prefill_paged(fam, q, k, v, Cache(*pools), page_ids, 0)
    if path == "paged":
        o = paged_multitoken_cached_attention(
            q, k_pool, v_pool, page_ids[None], jnp.zeros(1, jnp.int32), sm_scale=fam.sm_scale, layer=0,
        ).reshape(1, S, -1)
    np.testing.assert_allclose(fam.attn_out(lp, o), want, rtol=0, atol=2e-6)


# -- a served engine --------------------------------------------------------------

@pytest.fixture(scope="module")
def engines():
    made = {}

    def get(n_head):
        if n_head not in made:
            cfg = _cfg(n_head)
            made[n_head] = (
                InferenceEngine(gpt2.make_module(cfg), params=gpt2.init_params(cfg, jax.random.PRNGKey(0)),
                                dtype=jnp.float32),
                cfg,
            )
        return made[n_head]

    return get


def _prompts(vocab, lens=PROMPTS, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, n).astype(np.int32) for n in lens]


def _serve_against_generate(engine, cfg, prompts, n_new=10, **over):
    srv = engine.serve(dict(SERVING, **over))
    reqs = [srv.submit(p, max_new_tokens=n_new, seed=i) for i, p in enumerate(prompts)]
    srv.run()
    for p, r in zip(prompts, reqs):
        ref = np.asarray(engine.generate(p[None, :], max_new_tokens=n_new))[0]
        np.testing.assert_array_equal(r.output, ref)
    srv.check_no_leaks()
    return srv


@pytest.mark.parametrize("path", ["whole_prompt", "chunked_mixed", "speculative_prefix"])
def test_served_tokens_are_generates_odd_heads(engines, path):
    """Five 64-wide heads (three pairs, one padding head): the tokens of every
    request are ``generate()``'s, through the whole-prompt program and the
    decode step; through chunks that ride decode steps (the mixed step); and
    through the verify step over a shared prefix."""
    engine, cfg = engines(5)
    over = {
        "whole_prompt": {},
        "chunked_mixed": {"prefill_chunk_tokens": 8},
        "speculative_prefix": {"speculative": {"enabled": True, "k": 3}, "prefix_cache": {"enabled": True},
                               "prefill_chunk_tokens": 8},
    }[path]
    srv = _serve_against_generate(engine, cfg, _prompts(cfg.vocab_size), **over)
    assert srv.family.pairs and srv.decode_set.cache.k.shape[-3:] == (3, 4, 128)
    if path == "chunked_mixed":
        assert srv.metrics.counter("serving_chunks_rode_total", "").value() > 0


@pytest.mark.skipif(jax.device_count() < 2, reason="needs the forced CPU mesh")
def test_served_tokens_are_generates_at_tp2(engines):
    """Six heads over two ranks: three a rank, so each rank pads its own
    fourth and the pool holds 2 x 2 pairs, never one across ranks."""
    engine, cfg = engines(6)
    srv = _serve_against_generate(engine, cfg, _prompts(cfg.vocab_size, PROMPTS[:5]), placement={"tp": 2},
                                  prefill_chunk_tokens=8)
    assert srv.decode_set.n_kv_head == 4 and srv.decode_set.local_kv_heads() == 2
    assert srv.decode_set.cache.k.shape[-3:] == (4, 4, 128)


def test_int8_cache_keeps_a_scale_a_published_head(engines):
    """An int8 page carries one scale a cached head: the engine serves such a
    cache a head a published head (no pair under one scale), and the tokens
    are those of the same cache before this layout existed."""
    engine, cfg = engines(5)
    srv = engine.serve(dict(SERVING, kv_cache_dtype="int8"))
    assert not srv.family.pairs
    assert srv.decode_set.cache.k.shape[-3:] == (5, 4, 64) and srv.decode_set.cache.scales.shape[2] == 5
    prompts = _prompts(cfg.vocab_size, PROMPTS[:4])
    reqs = [srv.submit(p, max_new_tokens=6, seed=i) for i, p in enumerate(prompts)]
    srv.run()
    # int8 against float32 is not bit-equal; it is close: most tokens agree
    same = total = 0
    for p, r in zip(prompts, reqs):
        ref = np.asarray(engine.generate(p[None, :], max_new_tokens=6))[0]
        same, total = same + int((np.asarray(r.output) == ref).sum()), total + len(ref)
    assert same / total > 0.9


def test_tiering_store_holds_the_cached_head(engines):
    """The host tier's pages are the pool's: pairs of 128 lanes."""
    engine, cfg = engines(5)
    srv = engine.serve(dict(SERVING, prefix_cache={"enabled": True},
                            tiering={"enabled": True, "host_budget_pages": 8}))
    assert srv.tiering.store.k_codes.shape[-3:] == (3, 4, 128)


# -- the shapes the chip's kernels plan from ---------------------------------------

def test_xl_paired_shapes_plan():
    """(d): XL's 13 pairs of 128 lanes take all heads and 16 pages a grid step
    of the decode kernel (25 x 64: 8 pages), the chunk kernel's step counts
    inside its VMEM budget, and the pool is stored in the plain 5-D shape."""
    assert paged_decode_blocks(13, 16, 128) == (13, 16)
    assert paged_decode_blocks(25, 16, 64) == (25, 8)
    assert paged_multitoken_blocks(13, 16, 128, 128, rep=2) is not None
    assert pool_stored_shape(48, 512, 13, 16, 128, jnp.bfloat16) == (48, 512, 13, 16, 128)
    fam = gpt2.get_config("gpt2-xl").serving_family()
    assert (fam.n_kv_head, fam.n_head, fam.head_dim) == (13, 26, 128)
