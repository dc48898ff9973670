"""The plain float32 reference against ``models/gpt2.py`` and against the
paged served path, at ``gpt2-tiny`` on seeded weights.

Tolerances. Everything here is float32 on the CPU, where the system and the
reference differ only in the order of float32 additions (fused attention
against explicit softmax; scan against unrolled blocks): logits of size about
0.2 agree to 2e-5, losses near ln(512) = 6.2 to 1e-5. Those bounds are about
100 times the rounding seen and 1 000 times smaller than what dropping one of
the two layers does (shown below), so a missing layer or a lower precision
cannot pass. On the chip the margins are wider, because the system runs bf16;
they are in the configuration files, beside the reason."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import gpt2

from perfbench import reference

CFG = gpt2.get_config("gpt2-tiny")
KW = dict(n_head=CFG.n_head, eps=CFG.layer_norm_epsilon, vocab=CFG.vocab_size)


@pytest.fixture(scope="module")
def params():
    return gpt2.init_params(CFG, jax.random.PRNGKey(2**31 + 3))


@pytest.fixture(scope="module")
def ids():
    return np.random.default_rng(5).integers(0, CFG.vocab_size, (3, 48)).astype(np.int32)


def test_logits_agree_with_the_program_model(params, ids):
    want = np.asarray(gpt2.forward(CFG, params, jnp.asarray(ids)))
    for row, w in zip(ids, want):
        h = reference.hidden(params, jnp.asarray(row), CFG.n_head, CFG.layer_norm_epsilon)
        got = np.asarray(reference._logits(params, h, CFG.vocab_size))
        assert np.abs(got - w).max() < 2e-5
        assert np.abs(w).max() > 0.1   # the comparison is not of zeros


def test_loss_agrees_with_the_program_loss(params, ids):
    want, _ = gpt2.lm_loss(CFG, params, {"input_ids": jnp.asarray(ids)}, None, False)
    got = reference.lm_loss(params, jnp.asarray(ids), **KW)
    assert abs(float(got) - float(want)) < 1e-5
    assert abs(float(got) - np.log(CFG.vocab_size)) < 0.5


def test_a_dropped_layer_moves_logits_and_loss_far_beyond_the_tolerances(params, ids):
    full = reference.lm_loss(params, jnp.asarray(ids), **KW)
    cut = reference.lm_loss(params, jnp.asarray(ids), skip_layer=1, **KW)
    assert abs(float(full) - float(cut)) > 1e-3
    h_full = reference.hidden(params, jnp.asarray(ids[0]), CFG.n_head, CFG.layer_norm_epsilon)
    h_cut = reference.hidden(params, jnp.asarray(ids[0]), CFG.n_head, CFG.layer_norm_epsilon, skip_layer=0)
    assert np.abs(np.asarray(h_full) - np.asarray(h_cut)).max() > 0.1


def test_bf16_weights_are_read_as_they_are_and_computed_in_float32(params, ids):
    p16 = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
    p16_as_f32 = jax.tree.map(lambda a: a.astype(jnp.float32), p16)
    a = reference.lm_loss(p16, jnp.asarray(ids), **KW)
    b = reference.lm_loss(p16_as_f32, jnp.asarray(ids), **KW)
    assert a.dtype == jnp.float32 and float(a) == pytest.approx(float(b), abs=1e-6)


@pytest.fixture(scope="module")
def served():
    """Two requests through the paged server (whole-prompt and chunked
    prefill), float32, greedy."""
    import deepspeed_tpu

    eng = deepspeed_tpu.init_inference(model=gpt2.make_module(CFG), dtype=jnp.float32, seed=11)
    srv = eng.serve({"max_slots": 2, "page_size": 8, "num_pages": 40, "max_prompt_len": 96,
                     "max_new_tokens": 12, "prefill_chunk_tokens": 32})
    rng = np.random.default_rng(3)
    reqs = [srv.submit(rng.integers(0, CFG.vocab_size, n).astype(np.int32), max_new_tokens=12) for n in (20, 75)]
    srv.run()
    return eng, reqs


def _gaps(eng, r, skip_layer=-1):
    ids = np.concatenate([np.asarray(r.prompt, np.int32), np.asarray(r.tokens, np.int32)])
    padded = np.zeros((128,), np.int32)
    padded[: len(ids)] = ids
    gap, _ = reference.served_gaps(eng.params, jnp.asarray(padded), len(r.prompt), len(ids), skip_layer=skip_layer, **KW)
    return np.asarray(gap)


@pytest.mark.parametrize("which", [0, 1])
def test_every_served_token_is_the_references_choice(served, which):
    eng, reqs = served
    r = reqs[which]
    assert len(r.tokens) == 12
    gap = _gaps(eng, r)
    # float32 both sides: the served token is the reference's argmax, or a tie
    # closer than the rounding of two float32 sums (2e-5)
    assert gap.max() < 2e-5
    assert (gap[: len(r.prompt) - 1] == 0).all() and (gap[len(r.prompt) + 11:] == 0).all()


def test_a_reference_without_a_layer_disowns_the_served_tokens(served):
    eng, reqs = served
    assert max(_gaps(eng, r, skip_layer=1).max() for r in reqs) > 1e-2
