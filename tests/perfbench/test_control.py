"""The control of the served ``correct`` limit, kept at ``gpt2-tiny``: a copy
of the reference computed in int8 (``perfbench/tools/control.py``) chooses the
tokens and the float32 reference reads them as it reads the program's.

What it showed on the chip at GPT-2-XL (PERF.md section 2, PR 28) it shows
here: the rounding moves the logits by a fifth of their spread (here a
twentieth), hundreds of times what a sound run differs from the reference, and
the comparison the benchmark makes does not see it, because a greedy token
moves only where two logits lie closer than the error: the gap of the served
token stays under the limit. The second test pins that hole; the PR that
compares logits (PERF.md section 7) turns it round."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import gpt2

from perfbench import reference
from perfbench.tools import control

from . import tiny

CFG = gpt2.get_config("gpt2-tiny")
KW = dict(n_head=CFG.n_head, eps=CFG.layer_norm_epsilon, vocab=CFG.vocab_size)
MARGIN = tiny.serve_config()["reference"]["logit_margin"]
SOUND_LOGIT_ERR = 2e-5     # the float32 program against the reference (test_reference.py)
SEEDS = [2**31 + 11, 1999999973, 7]


@pytest.fixture(scope="module", params=SEEDS)
def reading(request):
    seed = request.param
    params = gpt2.init_params(CFG, jax.random.PRNGKey(seed % (2**31 - 1)))
    prompt = np.random.default_rng([seed, 9]).integers(0, CFG.vocab_size, 40).astype(np.int32)
    return params, prompt, control.control_gap(params, prompt, 24, n_positions=CFG.n_positions, **KW)


def test_dot8_is_a_matrix_product_to_int8_rounding():
    rng = np.random.default_rng(0)
    a, b = rng.standard_normal((3, 5, 64)).astype(np.float32), rng.standard_normal((3, 64, 7)).astype(np.float32)
    got, want = np.asarray(control.dot8(jnp.asarray(a), jnp.asarray(b))), a @ b
    err = np.abs(got - want).max()
    assert 1e-4 < err < 0.05 * np.abs(want).max()   # rounded, and not by much


def test_int8_moves_the_logits_far_more_than_a_sound_run_does(reading):
    _, _, r = reading
    assert r["logit_err"] > 100 * SOUND_LOGIT_ERR       # seen: 0.008-0.009
    assert r["logit_err"] < 0.2 * r["logit_std"]        # rounding, not another model


def test_the_served_token_gap_does_not_catch_it(reading):
    """The hole, pinned: the control passes the limit (on the chip 0.026-0.057
    under 0.1, here 0 under 1e-3)."""
    _, _, r = reading
    assert 0.0 <= r["gap"] <= MARGIN


def test_tokens_the_float32_reference_chose_read_a_gap_of_zero(reading):
    params, prompt, _ = reading
    ids = np.zeros((128,), np.int32)
    ids[:40] = prompt
    for n in range(40, 64):
        h = reference.hidden(params, jnp.asarray(ids), CFG.n_head, CFG.layer_norm_epsilon)
        ids[n] = int(jnp.argmax(reference._logits(params, h, CFG.vocab_size)[n - 1]))
    sound, _ = reference.served_gaps(params, jnp.asarray(ids), 40, 64, **KW)
    assert float(np.asarray(sound).max()) == 0.0
