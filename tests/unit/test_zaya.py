"""The ``zaya`` family (ZAYA1-8B) at a small size on the CPU, in float32:
``models/zaya.forward`` (the served programs' pieces under a dense mask)
against the float32 reference (``perfbench/reference_zaya.py``, which imports
nothing from the model's module), every control of the reference, and the two
pieces ``qkv`` is given in: a sequence cut anywhere and continued from the rows
it carried is the sequence uncut."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import zaya as m
from perfbench import reference_zaya as reference

CFG = dict(
    vocab_size=128, hidden_size=64, num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    cca_time0=2, cca_time1=2, num_experts=4, num_experts_per_tok=1, moe_intermediate_size=64, router_hidden_size=16,
    rms_norm_eps=1e-5, max_position_embeddings=4096, partial_rotary_factor=0.5,
    rope_parameters={"hybrid": {"rope_theta": 5e6, "partial_rotary_factor": 0.5, "rope_type": "default"}},
    initializer_range=0.25,
)
S = 40
TOL = 1e-4


@pytest.fixture(scope="module")
def mcfg():
    return m.ZayaConfig.from_dict(CFG)


def seeded(mcfg, seed: int):
    """The seeded tree by ONE program (``init_params`` alone compiles one a
    distinct leaf, which at this size is most of a case's seconds)."""
    return jax.jit(lambda key: m.init_params(mcfg, key, jnp.float32))(jax.random.PRNGKey(seed))


@pytest.fixture(scope="module")
def params(mcfg):
    return seeded(mcfg, 0)


@pytest.fixture(scope="module")
def ids():
    return jax.random.randint(jax.random.PRNGKey(1), (S,), 0, CFG["vocab_size"])


@pytest.fixture(scope="module")
def arch():
    return reference.Arch.from_config(CFG)


def test_from_dict_reads_the_published_keys_and_refuses_what_the_module_does_not_build(mcfg):
    assert (mcfg.rope_theta, mcfg.rotary_dim, mcfg.latent, mcfg.q_width, mcfg.kv_width) == (5e6, 8, 96, 64, 32)
    fam = mcfg.serving_family()
    assert fam.carry_width == 2 * 96 + 16 and fam.windows == (0, 0) and fam.sparse_layers == (0, 1)
    assert (fam.experts_held, fam.experts_per_token, fam.kv_pools) == (4, 1, 2)
    for bad in (dict(cca_time0=3), dict(num_key_value_heads=4), dict(num_experts_per_tok=2)):
        with pytest.raises(ValueError):
            m.ZayaConfig.from_dict({**CFG, **bad})


def test_the_seeded_weights_let_a_check_see_each_mechanism_and_a_cut_sequence_continues_from_its_carried_rows(mcfg, params, cut=7):
    """One case for the family's pieces on the seeded tree (a case of its own pays the tree again on another worker)."""
    lp = params["layers"][0]
    assert float(jnp.abs(lp["cca"]["w0"]).mean()) > 0.2 and float(jnp.abs(lp["cca"]["w1"]).mean()) > 0.05
    assert float(lp["moe"]["gamma"].min()) >= 0.5 and float(jnp.abs(lp["cca"]["tau"]).min()) > 0
    assert float(jnp.abs(lp["res_a"]["sx"] - 1).mean()) > 0.05 and float(jnp.abs(lp["res_m"]["bf"]).mean()) > 0.01
    assert lp["cca"]["w_in"].shape == (64, 96 + 32) and lp["cca"]["w1"].shape == (6, 2, 16, 16)
    assert lp["moe"]["experts"]["w_gate"].shape == (4, 64, 64) and lp["moe"]["w3"].shape == (16, 4)
    # the router is not uniform, and the state from one layer up moves its scores
    fam = mcfg.serving_family()
    w = jax.random.normal(jax.random.PRNGKey(2), (S, 64), jnp.float32)
    logits, r = fam.router_logits(lp["moe"], w, None)
    assert float(jax.nn.softmax(logits, axis=-1).max(axis=-1).mean()) > 0.4 and r.shape == (S, 16)
    assert float(jnp.abs(fam.router_logits(lp["moe"], w, r)[0] - logits).max()) > 1e-2
    # a sequence cut anywhere and continued from the rows it carried is the sequence uncut
    lp = params["layers"][1]
    h = jax.random.normal(jax.random.PRNGKey(3), (2, S, 64), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(S)[None], (2, S))
    qkv = jax.jit(lambda h, pos, prev=None: fam.qkv(lp, h, pos, 1, prev))     # one program a shape, not one an op
    q, k, v, nxt = qkv(h, pos)
    assert nxt.shape == (2, S, fam.carry_width)
    q0, k0, v0, n0 = qkv(h[:, :cut], pos[:, :cut])
    q1, k1, v1, n1 = qkv(h[:, cut:], pos[:, cut:], n0[:, -1])
    for whole, a, b in ((q, q0, q1), (k, k0, k1), (v, v0, v1), (nxt, n0, n1)):
        np.testing.assert_allclose(np.asarray(jnp.concatenate([a, b], axis=1)), np.asarray(whole), atol=1e-6, rtol=0)
    # ... and dropped rows show: the first rows after the cut differ
    _, k2, v2, _ = qkv(h[:, cut:], pos[:, cut:])
    assert float(jnp.abs(k2[:, 0] - k[:, cut]).max()) > 1e-2 and float(jnp.abs(v2[:, 0] - v[:, cut]).max()) > 1e-2


def test_forward_is_the_references_full_forward_and_each_control_of_the_reference_moves_the_logits(mcfg, params, ids, arch, monkeypatch):
    """One case and one program: the reference, its seven controls
    (``carry_edge`` cuts at every 16th position here: the sequence is 40
    long) and the model's own forward. A case a control paid the weights on
    a worker of its own."""
    monkeypatch.setattr(reference, "CHUNK", 16)

    def everything(p, i):
        with jax.default_matmul_precision("highest"):
            got = m.forward(mcfg, p, i[None])[0]
        return got, [reference.logits(p, i, arch, skip) for skip in ("",) + reference.SKIPS]

    got, (want, *controls) = jax.jit(everything)(params, ids)
    want = np.asarray(want)
    assert want.std() > 1.0
    np.testing.assert_allclose(np.asarray(got), want, atol=TOL, rtol=0)
    for skip, lg in zip(reference.SKIPS, controls):
        moved = np.abs(np.asarray(lg) - want).max()
        assert moved > 1000 * TOL, (skip, moved)           # so the comparison can see every line of the equations
