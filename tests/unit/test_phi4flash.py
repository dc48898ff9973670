"""``models/phi4flash.py`` at a small size that keeps the pattern (8 layers:
Mamba, window, Mamba, window, Mamba-memory, full, GMU, cross), in float32: the
plain cached forward against the float32 reference's full forward
(``perfbench/reference_phi4flash.py``, which imports nothing from the model's
module, keeps the state ``[d, N]`` and attends with four softmax products a
pair), the padded pair-head identity, the layer pattern, the initialisation
and the published size."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import phi4flash as m
from perfbench import reference_phi4flash as reference

CFG = dict(
    vocab_size=96, hidden_size=32, intermediate_size=64, num_hidden_layers=8, num_attention_heads=4,
    num_key_value_heads=2, mb_per_layer=2, sliding_window=8, layer_norm_eps=1e-5, max_position_embeddings=512,
    tie_word_embeddings=True, initializer_range=0.25,
)
PUBLISHED = dict(
    vocab_size=200064, hidden_size=2560, intermediate_size=10240, num_hidden_layers=32, num_attention_heads=40,
    num_key_value_heads=20, mb_per_layer=2, sliding_window=512, layer_norm_eps=1e-5, max_position_embeddings=262144,
    tie_word_embeddings=True, model_type="phi4flash", hidden_act="silu", mlp_bias=False, lm_head_bias=False,
)


def _params(cfg, seed):
    """Seeded weights with every gain and bias moved off 1 and 0, so that a
    bias left out would show."""
    params = m.init_params(cfg, jax.random.PRNGKey(seed), jnp.float32)
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 100), len(leaves))
    return jax.tree_util.tree_unflatten(
        tree, [x + 0.1 * jax.random.normal(k, x.shape) if x.ndim == 1 else x for x, k in zip(leaves, keys)])


@pytest.fixture(scope="module")
def cfg():
    return m.Phi4FlashConfig.from_dict(CFG)


def test_the_layer_pattern_is_the_published_one_at_both_sizes(cfg):
    assert [cfg.kind(i) for i in range(8)] == ["ssm", "attn", "ssm", "attn", "ssm", "attn", "gmu", "cross"]
    assert [cfg.window(i) for i in range(8)] == [0, 8, 0, 8, 0, 0, 0, 0]
    assert [reference.kind(i, 8) for i in range(8)] == [cfg.kind(i) for i in range(8)]
    big = m.Phi4FlashConfig.from_dict(PUBLISHED)
    kinds = [big.kind(i) for i in range(32)]
    assert (kinds.count("ssm"), kinds.count("attn"), kinds.count("gmu"), kinds.count("cross")) == (9, 9, 7, 7)
    assert [i for i in range(32) if big.window(i)] == [1, 3, 5, 7, 9, 11, 13, 15] and big.window(17) == 0
    assert kinds[16] == "ssm" and kinds[17] == "attn" and kinds[18] == "gmu" and kinds[19] == "cross" and kinds[31] == "cross"
    fam = big.serving_family()
    assert fam.sources == {**{i: 16 for i in range(18, 32, 2)}, **{i: 17 for i in range(19, 32, 2)}}
    assert (fam.n_head, fam.n_kv_head, fam.head_dim, fam.sm_scale, fam.stop_after) == (40, 10, 128, 0.125, 17)
    assert fam.ssm_state == (16, 5120) and fam.ssm_conv == 4 and big.dt_rank == 160
    assert big.lambda_init(1) == pytest.approx(0.8 - 0.6 * math.exp(-0.3))


def test_the_published_size_is_3_85_billion_parameters():
    big = m.Phi4FlashConfig.from_dict(PUBLISHED)
    shapes = jax.eval_shape(lambda: m.init_params(big, jax.random.PRNGKey(0)))
    n = sum(x.size for x in jax.tree.leaves(shapes))
    assert abs(n - 3.852e9) < 0.005e9
    one = lambda l: sum(x.size for x in jax.tree.leaves(shapes["layers"][l]))  # noqa: E731
    assert abs(one(0) - 119.9e6) < 0.1e6 and abs(one(1) - 98.3e6) < 0.1e6
    assert abs(one(18) - 104.9e6) < 0.1e6 and abs(one(19) - 91.8e6) < 0.1e6


def test_the_initialisation_is_mambas_where_a_normal_draw_would_hide_a_dropped_state(cfg):
    p = m.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    s = p["layers"][0]["ssm"]
    assert np.allclose(np.exp(np.asarray(s["a_log"])), np.arange(1, 17)[None, :])
    dt = np.log1p(np.exp(np.asarray(s["b_dt"])))
    assert 1e-3 * 0.99 <= dt.min() and dt.max() <= 1e-1 * 1.01 and np.all(np.asarray(s["d"]) == 1)
    w = np.asarray(s["w_conv"])                      # a depthwise convolution's own default: uniform in +-1/sqrt(K)
    assert w.shape == (64, 4) and np.abs(w).max() <= 0.5 and 0.25 < w.std() < 0.33
    a = p["layers"][1]["attn"]
    assert 0.03 < float(np.std(np.asarray(a["lambda_q1"]))) < 0.3 and np.all(np.asarray(a["subln"]) == 1)
    assert set(p["layers"][6]) == {"norm_1", "norm_2", "mlp", "gmu"} and set(p["layers"][7]["cross"]) >= {"wq", "bq", "wo", "bo"}
    assert "wqkv" not in p["layers"][7]["cross"]                     # a cross layer projects no key and no value
    bf = m.init_params(cfg, jax.random.PRNGKey(0), jnp.bfloat16)
    assert {x.dtype for x in jax.tree.leaves(bf)} == {jnp.dtype(jnp.bfloat16)}


@pytest.mark.parametrize("seed", [0, 1])
def test_whole_forward_is_the_references(cfg, seed):
    params = _params(cfg, seed)
    ids = np.random.default_rng(seed).integers(0, 96, 48).astype(np.int32)
    want = np.asarray(reference.logits(params, jnp.asarray(ids), reference.Arch.from_config(CFG)))
    got = jax.jit(lambda p, i: m.forward(cfg, p, i)[0])(params, jnp.asarray(ids[None]))   # one program: eagerly, an op at a time
    np.testing.assert_allclose(np.asarray(got[0]), want, atol=2e-4)
    assert np.abs(want).max() > 1.0


@pytest.mark.parametrize("seed,prompt,total", [(0, 20, 24), (1, 5, 11)])
def test_prefill_then_decode_through_the_three_caches_is_the_references_full_forward(cfg, seed, prompt, total):
    """The plain cached forward: a prompt at once (past the window of 8, and
    shorter than it, so that decoding fills and passes it), then a token at a time behind the Mamba state,
    the convolution rows and the kv pairs so far."""
    params = _params(cfg, seed)
    ids = np.random.default_rng(seed).integers(0, 96, total).astype(np.int32)
    want = np.asarray(reference.logits(params, jnp.asarray(ids), reference.Arch.from_config(CFG)))
    lg, cache = m.forward(cfg, params, jnp.asarray(ids[None, :prompt]))
    rows = [np.asarray(lg[0])]
    for t in range(prompt, total):
        lg, cache = m.forward(cfg, params, jnp.asarray(ids[None, t:t + 1]), cache)
        rows.append(np.asarray(lg[0]))
    np.testing.assert_allclose(np.concatenate(rows), want, atol=2e-4)
    assert cache["len"] == total and cache[0][0].shape == (1, 16, 64) and cache[0][1].shape == (1, 3, 64)
    assert cache[5][0].shape == (1, total, 1, 16) and 6 not in cache and 7 not in cache   # GMU and cross keep nothing


@pytest.mark.parametrize("window,seed", [(0, 0), (5, 1)])
def test_padded_pair_head_attention_is_the_four_softmax_form(cfg, window, seed):
    """``softmax([q1|0] [k1|k2]^T) [v1|v2]`` and ``[0|q2]``, combined in
    ``attn_out``, against the reference's four products a pair."""
    fam = cfg.serving_family()
    params = _params(cfg, seed)
    lp = fam.layer(params, 1)
    rng = np.random.default_rng(seed)
    S, H, KV, D = 12, 4, 2, 8
    h = jnp.asarray(rng.normal(size=(1, S, 32)), jnp.float32)
    q, k, v = fam.qkv(lp, h, None, 1)
    assert q.shape == (1, S, H, 2 * D) and k.shape == v.shape == (1, S, KV // 2, 2 * D)
    assert np.all(np.asarray(q[0, :, 0, D:]) == 0) and np.all(np.asarray(q[0, :, 1, :D]) == 0)   # [q1 | 0], [0 | q2]
    i, j = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    mask = (j <= i) & ((j > i - window) if window else True)
    sc = jnp.einsum("bshd,btgd->bhst", q, k) * fam.sm_scale      # one kv pair: every query head reads it
    o = jnp.einsum("bhst,btgd->bshd", jax.nn.softmax(jnp.where(mask, sc, -1e30), -1), v)
    got = fam.attn_out(lp, o.reshape(1, S, -1))[0]
    a = reference.Arch.from_config(CFG)
    u = reference._ln(h[0], lp["norm_1"], 1e-5)
    w = lp["attn"]
    qkv = u @ w["wqkv"] + w["bqkv"]
    want = reference._diff_attention(
        w, qkv[:, :H * D].reshape(S, H, D), qkv[:, H * D:(H + KV) * D].reshape(S, KV, D),
        qkv[:, (H + KV) * D:].reshape(S, KV, D), cfg.lambda_init(1), window, a, "", reference.dot_f32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_each_control_of_the_reference_moves_the_logits(cfg):
    """512 tokens, past a 256-token chunk edge (where ``conv_edge`` cuts)."""
    params, a = _params(cfg, 0), reference.Arch.from_config(CFG)
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 96, 512).astype(np.int32))
    sound = np.asarray(reference.logits(params, ids, a))
    moved = {skip: float(np.abs(np.asarray(reference.logits(params, ids, a, skip)) - sound).max()) for skip in reference.SKIPS}
    assert all(d > (1e-4 if skip == "state_bf16" else 1e-2) for skip, d in moved.items()), moved


def test_bad_configs_are_refused():
    for bad in (dict(num_hidden_layers=7), dict(mb_per_layer=3), dict(num_key_value_heads=1, num_attention_heads=3),
                dict(tie_word_embeddings=False)):
        with pytest.raises(ValueError):
            m.Phi4FlashConfig.from_dict({**CFG, **bad})
    assert m.Phi4FlashConfig.from_dict({**CFG, "dtype": "bfloat16", "unknown_key": 1}).hidden_size == 32
    assert m.make_module(m.Phi4FlashConfig.from_dict(CFG)).loss_fn is None
