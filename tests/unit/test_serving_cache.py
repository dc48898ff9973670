"""The served cache as ONE value (ISSUE 62; docs/SERVING.md, "The cache").

Over the nine served families' tiny configurations (the ``CFG`` / ``SERVING``
dicts of their own test files): the leaves of ``ProgramSet.cache`` are, in
order, the operands the programs took one by one before the cache was a value
(written out here, not computed by the code under test), a program gives back
the same tree, and ``kv_cache.refusals`` names the kinds of state that stand in
a mechanism's way. Nothing here is compiled: the engines are built over the
weights' shapes and the program is traced."""

import functools
import importlib
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import pytest

from deepspeed_tpu.models import gpt2
from deepspeed_tpu.serving import model as smodel
from deepspeed_tpu.serving.kv_cache import MECHANISMS, Cache, refusals
from deepspeed_tpu.serving.scheduler import ServingEngine

F32 = "float32"
# family -> (its test file, its model's module, its config, the cache's fields that are there,
#            the leaves in program order: what ``pool_args()`` gave at the parent of ISSUE 62)
CASES = {
    "gpt2": (None, None, None, ("k", "v"), [((2, 64, 4, 4, 16), F32)] * 2),
    "gpt2_int8": (None, None, None, ("k", "v", "scales"), [((2, 64, 4, 4, 16), "int8")] * 2 + [((2, 64, 4, 2), F32)]),
    "exaone": ("test_serving_exaone", "exaone_moe", "ExaoneMoEConfig", ("k", "v", "win_k", "win_v"),
               [((1, 64, 2, 4, 8), F32)] * 2 + [((2, 16, 2, 4, 8), F32)] * 2),
    "mistral4": ("test_serving_mistral4", "mistral4", "Mistral4Config", ("k",), [((2, 64, 1, 4, 24), F32)]),
    "longcat_flash": ("test_serving_longcat_flash", "longcat_flash", "LongcatFlashConfig", ("k",), [((4, 64, 1, 4, 24), F32)]),
    "phi4flash": ("test_serving_phi4flash", "phi4flash", "Phi4FlashConfig", ("k", "v", "win_k", "win_v", "rec", "conv"),
                  [((1, 64, 1, 4, 16), F32)] * 2 + [((2, 16, 1, 4, 16), F32)] * 2 + [((3, 3, 16, 64), F32), ((3, 3, 3, 64), F32)]),
    "zaya": ("test_zaya", "zaya", "ZayaConfig", ("k", "v", "carry"), [((2, 96, 2, 4, 16), F32)] * 2 + [((2, 3, 208), F32)]),
    "qwen3_next": ("test_serving_qwen3_next", "qwen3_next", "Qwen3NextConfig", ("k", "v", "rec", "conv"),
                   [((2, 64, 2, 4, 16), F32)] * 2 + [((6, 3, 4, 16, 16), F32), ((6, 3, 3, 128), F32)]),
    "xing4": ("test_serving_xing4", "xing4", "Xing4Config", ("k",), [((3, 64, 1, 4, 24), F32)]),
    "ling3": ("test_serving_ling3", "ling3", "Ling3Config", ("k", "rec", "conv"),
              [((2, 64, 1, 4, 40), F32), ((4, 3, 4, 16, 16), F32), ((4, 3, 3, 192), F32)]),
}
# the kinds of state a family holds, as a refusal names them and in the order it names them
KINDS = {
    "gpt2": (), "exaone": ("sliding-window layers",), "mistral4": ("a latent KV pool",),
    "longcat_flash": ("a latent KV pool",), "phi4flash": ("recurrent state", "sliding-window layers"),
    "zaya": ("carried attention rows",), "qwen3_next": ("recurrent state",), "xing4": ("a latent KV pool",),
    "ling3": ("recurrent state", "a latent KV pool"),
}
NOT_ROLLED_BACK = ("recurrent state", "carried attention rows")   # what refuses a draft


@functools.lru_cache(maxsize=None)
def _model(family):
    """→ (the model's config, its weights' shapes, its ``SERVING`` dict)."""
    tmod, mmod, cname = CASES[family][:3]
    if tmod is None:
        from .test_serving import SERVING_CFG

        mcfg = gpt2.get_config("gpt2-tiny", attn_impl="jnp")
        serving = dict(SERVING_CFG, **({"kv_cache_dtype": "int8"} if family == "gpt2_int8" else {}))
        return mcfg, jax.eval_shape(lambda: gpt2.init_params(mcfg, jax.random.PRNGKey(0))), serving
    m = importlib.import_module("deepspeed_tpu.models." + mmod)
    mcfg = getattr(m, cname).from_dict(importlib.import_module("tests.unit." + tmod).CFG)
    serving = importlib.import_module("tests.unit." + tmod.replace("test_zaya", "test_serving_zaya")).SERVING
    return mcfg, jax.eval_shape(lambda: m.init_params(mcfg, jax.random.PRNGKey(0), jnp.float32)), dict(serving)


@pytest.mark.parametrize("family", list(CASES))
def test_the_caches_leaves_are_the_operands_the_programs_took_and_a_program_gives_back_the_same_tree(family):
    mcfg, params, serving = _model(family)
    srv = ServingEngine(SimpleNamespace(model_config=mcfg, dtype=jnp.float32, params=params), serving)
    cache = srv.decode_set.cache
    assert isinstance(cache, Cache)
    assert tuple(f for f, x in zip(Cache._fields, cache) if x is not None) == CASES[family][3]
    assert [(tuple(x.shape), x.dtype.name) for x in jax.tree.leaves(cache)] == CASES[family][4]
    assert (srv.k_pool, srv.v_pool, srv.kv_scales) == cache[:3]       # the engine's views, read from outside
    B, W = srv.max_slots, srv.pages_per_slot
    out = jax.eval_shape(
        lambda p, c: smodel.paged_decode_step(
            srv.model_config, p, jnp.zeros((B,), jnp.int32), jnp.zeros((B,), jnp.int32), c,
            jnp.zeros((B, W), jnp.int32), jnp.zeros((B, 2), jnp.uint32), ring=srv.ring_pages),
        params, cache,
    )
    assert jax.tree.structure(out[0]) == jax.tree.structure(cache)
    assert [(x.shape, x.dtype) for x in jax.tree.leaves(out[0])] == [(x.shape, x.dtype) for x in jax.tree.leaves(cache)]
    assert out[1].shape == (B,) and len(out) == 2 + bool(srv.family.sparse_layers)


@pytest.mark.parametrize("mechanism", MECHANISMS)
@pytest.mark.parametrize("family", list(KINDS))
def test_a_mechanism_is_refused_by_every_kind_of_state_that_stands_in_its_way_and_a_draft_by_what_cannot_be_rolled_back(
        family, mechanism):
    fam = _model(family)[0].serving_family()
    want = [k for k in KINDS[family] if mechanism != "serving.speculative" or k in NOT_ROLLED_BACK]
    why = refusals(fam, mechanism, "TheModel")
    assert len(why) == len(want) and all(w.startswith(k) for w, k in zip(why, want)), why
    # an admission's sentence cites the model, a migration's says what would stay behind
    assert all(("(TheModel)" in w) == (mechanism != "session migration") for w in why)
    if family == "ling3" and mechanism == "serving.tiering":    # a family of two kinds names both
        assert "matrix state a value head" in why[0] and "no V pool" in why[1]


def test_an_unknown_mechanism_is_no_silent_pass():
    with pytest.raises(ValueError, match="unknown mechanism"):
        refusals(_model("gpt2")[0].serving_family(), "serving.prefix-cache")
