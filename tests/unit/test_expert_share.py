"""The expert layer that knows its share (``moe/expert_share.py``), at a small
size in float32: the shares add up to the uncut layer, the bias selects and
does not weigh, and no held pair is ever dropped."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.moe import expert_share as es

E, F, N, K, T = 24, 12, 16, 4, 40
SCALE = 2.5


def _layer(seed=0, n=N, bias_std=0.5):
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    w = lambda k, s, std=0.3: jax.random.normal(k, s, jnp.float32) * std
    return {
        "router": w(ks[0], (E, N)), "bias": w(ks[1], (N,), bias_std),
        "experts": {"w_gate": w(ks[2], (n, E, F)), "w_up": w(ks[3], (n, E, F)), "w_down": w(ks[4], (n, F, E))},
        "shared": {"w_gate": w(ks[5], (E, F)), "w_up": w(ks[6], (E, F)), "w_down": w(ks[7], (F, E))},
    }


def _slice(lp, share):
    """What chip ``share.index`` holds of the whole layer."""
    lo, hi = share.index * share.n_held, (share.index + 1) * share.n_held
    return dict(lp, experts={k: v[lo:hi] for k, v in lp["experts"].items()})


def _plain(lp, u, use_bias_in_weights=False):
    """The uncut layer, one token and one expert at a time."""
    out = np.zeros((T, E), np.float64)
    s = 1.0 / (1.0 + np.exp(-(np.asarray(u, np.float64) @ np.asarray(lp["router"], np.float64))))
    b = np.asarray(lp["bias"], np.float64)
    ffn = lambda x, wg, wu, wd: ((lambda g: g / (1 + np.exp(-g)))(x @ wg) * (x @ wu)) @ wd
    for t in range(T):
        sel = np.argsort(-(s[t] + b))[:K]
        base = s[t] + b if use_bias_in_weights else s[t]
        for e in sel:
            wt = SCALE * base[e] / base[sel].sum()
            out[t] += wt * ffn(np.asarray(u[t], np.float64), *(np.asarray(lp["experts"][k][e], np.float64)
                                                               for k in ("w_gate", "w_up", "w_down")))
        out[t] += ffn(np.asarray(u[t], np.float64), *(np.asarray(lp["shared"][k], np.float64)
                                                      for k in ("w_gate", "w_up", "w_down")))
    return out


@pytest.fixture(scope="module")
def u():
    return jax.random.normal(jax.random.PRNGKey(9), (T, E), jnp.float32)


@pytest.mark.parametrize("chips", [1, 4, 8])
def test_the_shares_routed_parts_and_the_shared_expert_once_add_up_to_the_uncut_layer(u, chips):
    lp = _layer()
    shared = np.asarray(es.gated_ffn(u, *(lp["shared"][k] for k in ("w_gate", "w_up", "w_down"))))
    total, pairs = np.zeros((T, E)), 0
    for i in range(chips):
        share = es.ExpertShare(N, chips, i)
        y, counts = es.expert_share_layer(_slice(lp, share), u, share, K, SCALE)
        assert counts.shape == (N // chips,) and counts.dtype == jnp.int32
        total += np.asarray(y) - shared          # this chip's routed part
        pairs += int(counts.sum())
    assert pairs == T * K                         # every pair is held by exactly one chip: none dropped, none twice
    np.testing.assert_allclose(total + shared, _plain(lp, u), rtol=2e-5, atol=2e-6)


def test_selection_uses_s_plus_b_and_weights_use_s(u):
    """A bias as wide as the scores' spread changes who is selected; the
    weights are formed from the scores alone. A layer that weighed with
    s + b, or selected without b, is far from it."""
    lp = _layer(bias_std=0.5)
    share = es.ExpertShare(N, 1, 0)
    y = np.asarray(es.expert_share_layer(lp, u, share, K, SCALE)[0])
    np.testing.assert_allclose(y, _plain(lp, u), rtol=2e-5, atol=2e-6)
    assert np.abs(y - _plain(lp, u, use_bias_in_weights=True)).max() > 1e-2
    no_bias = np.asarray(es.expert_share_layer(dict(lp, bias=jnp.zeros(N)), u, share, K, SCALE)[0])
    assert np.abs(y - no_bias).max() > 1e-2
    idx, w = es.route(u, lp["router"], lp["bias"], K, SCALE)
    np.testing.assert_allclose(np.asarray(w.sum(-1)), SCALE, rtol=1e-6)   # renormalised over the k, then scaled


def test_no_held_pair_is_dropped_under_the_most_uneven_routing(u):
    """Every token selects the same K experts, all of them held here: each
    gets all T tokens (ten times an even share of 1.25 a held expert), and
    the output is the whole layer's."""
    lp = _layer()
    hot = jnp.zeros(N).at[jnp.array([4, 5, 6, 7])].set(100.0)     # chip 1 of 4 holds experts 4..7
    lp = dict(lp, bias=hot)
    share = es.ExpertShare(N, 4, 1)
    y, counts = es.expert_share_layer(_slice(lp, share), u, share, K, SCALE)
    assert counts.tolist() == [T] * 4
    np.testing.assert_allclose(np.asarray(y), _plain(lp, u), rtol=2e-5, atol=2e-6)
    # and the chips that hold none of them compute the shared expert alone
    other = es.ExpertShare(N, 4, 2)
    y2, counts2 = es.expert_share_layer(_slice(lp, other), u, other, K, SCALE)
    assert counts2.tolist() == [0] * 4
    np.testing.assert_allclose(np.asarray(y2), np.asarray(es.gated_ffn(u, *(lp["shared"][k] for k in ("w_gate", "w_up", "w_down")))),
                               rtol=1e-6, atol=1e-7)


def test_counts_leave_out_the_rows_that_are_no_tokens(u):
    lp = _layer()
    share = es.ExpertShare(N, 2, 0)
    _, all_rows = es.expert_share_layer(_slice(lp, share), u, share, K, SCALE)
    valid = jnp.arange(T) < 10
    y, some = es.expert_share_layer(_slice(lp, share), u, share, K, SCALE, valid=valid)
    _, first10 = es.expert_share_layer(_slice(lp, share), u[:10], share, K, SCALE)
    assert some.tolist() == first10.tolist() and int(some.sum()) < int(all_rows.sum())
    assert y.shape == (T, E)          # the rows are still computed: a mask on the count, not on the work


# -- the grouped form: the same sum over the sorted pairs -----------------------

@pytest.mark.parametrize("chips,index", [(1, 0), (4, 1), (8, 7)])
def test_the_grouped_product_equals_the_masked_one(u, chips, index):
    lp = _layer()
    share = es.ExpertShare(N, chips, index)
    ex = _slice(lp, share)["experts"]
    idx, w = es.route(u, lp["router"], lp["bias"], K, SCALE)
    masked = es.held_experts(u, es.held_weights(idx, w, share), ex["w_gate"], ex["w_up"], ex["w_down"])
    grouped = es.held_experts_grouped(u, idx, w, share, ex["w_gate"], ex["w_up"], ex["w_down"])
    np.testing.assert_allclose(np.asarray(grouped), np.asarray(masked), rtol=2e-5, atol=2e-6)


def test_the_grouped_product_drops_no_held_pair_under_the_most_uneven_routing(u):
    """Every token selects the same K experts, all held here: the whole
    static pair budget T x K is in the groups, none behind them. And with
    none held, every pair sorts behind the groups and the routed part is 0."""
    lp = _layer()
    lp = dict(lp, bias=jnp.zeros(N).at[jnp.array([4, 5, 6, 7])].set(100.0))
    idx, w = es.route(u, lp["router"], lp["bias"], K, SCALE)
    for index, full in ((1, True), (2, False)):
        share = es.ExpertShare(N, 4, index)
        ex = _slice(lp, share)["experts"]
        masked = es.held_experts(u, es.held_weights(idx, w, share), ex["w_gate"], ex["w_up"], ex["w_down"])
        grouped = es.held_experts_grouped(u, idx, w, share, ex["w_gate"], ex["w_up"], ex["w_down"])
        np.testing.assert_allclose(np.asarray(grouped), np.asarray(masked), rtol=2e-5, atol=2e-6)
        assert (float(jnp.abs(grouped).max()) > 0) == full


def test_the_layer_switches_form_by_its_static_row_count(monkeypatch):
    """Many rows take the grouped form (and a whole-prompt program's rows go
    through in blocks), few the masked one; the layer's result and counts are
    the same either way."""
    lp = _layer()
    share = es.ExpertShare(N, 4, 1)
    big = jax.random.normal(jax.random.PRNGKey(3), (64, E), jnp.float32)
    want_y, want_c = es.expert_share_layer(_slice(lp, share), big, share, K, SCALE)
    n = es.GROUPED_MIN_ROWS
    assert es.grouped_rows(64, K, n) == 0 and es.grouped_rows(n, K, n) == n * K and es.grouped_rows(8 * n, K, 0) == 0
    calls = []
    real = es.held_experts_grouped
    monkeypatch.setattr(es, "held_experts_grouped", lambda *a: calls.append(a[0].shape) or real(*a))
    es.expert_share_layer(_slice(lp, share), big, share, K, SCALE)
    assert calls == []                 # a family that does not ask keeps every call masked
    for block in (4096, 16):           # in one piece, then four blocks of 16 rows
        monkeypatch.setattr(es, "GROUPED_BLOCK_ROWS", block)
        calls.clear()
        y, c = es.expert_share_layer(_slice(lp, share), big, share, K, SCALE, grouped_from=16)
        assert calls == [(64 if block == 4096 else 16, E)]
        np.testing.assert_allclose(np.asarray(y), np.asarray(want_y), rtol=2e-5, atol=2e-6)
        np.testing.assert_array_equal(np.asarray(c), np.asarray(want_c))


# -- softmax scoring, identity columns, no shared expert (LongCat-Flash) ---------

NZ = 8                      # identity columns behind the N real ones
SCALE_Z, KZ = 6.0, 5


def _layer_z(seed=1, bias_std=1.0 / (N + NZ)):
    lp = _layer(seed)
    ks = jax.random.split(jax.random.PRNGKey(seed + 50), 2)
    lp = {k: v for k, v in lp.items() if k != "shared"}
    lp["router"] = jax.random.normal(ks[0], (E, N + NZ), jnp.float32) * 0.3
    lp["bias"] = jax.random.normal(ks[1], (N + NZ,), jnp.float32) * bias_std
    return lp


def _plain_z(lp, u, mode=""):
    """The uncut layer, one token and one pick at a time: softmax over all
    N + NZ columns, top-k of s + b, weights SCALE_Z * s unrenormalised, a real
    expert's FFN or, for an identity column, the token itself."""
    z = np.asarray(u, np.float64) @ np.asarray(lp["router"], np.float64)
    s = np.exp(z - z.max(-1, keepdims=True))
    s = s / s.sum(-1, keepdims=True)
    if mode == "sigmoid":
        s = 1.0 / (1.0 + np.exp(-z))
    b = np.asarray(lp["bias"], np.float64)
    ffn = lambda x, wg, wu, wd: ((lambda g: g / (1 + np.exp(-g)))(x @ wg) * (x @ wu)) @ wd
    out, zero_pairs = np.zeros((T, E), np.float64), 0
    for t in range(T):
        sel = np.argsort(-(s[t] + (0 if mode == "no_bias" else b)))[:KZ]
        for e in sel:
            wt = SCALE_Z * s[t, e] / (s[t, sel].sum() if mode == "renorm" else 1.0)
            x = np.asarray(u[t], np.float64)
            if e >= N:
                zero_pairs += 1
                out[t] += wt * x if mode != "no_identity" else 0.0
            else:
                out[t] += wt * ffn(x, *(np.asarray(lp["experts"][k][e], np.float64) for k in ("w_gate", "w_up", "w_down")))
    return out, zero_pairs


def _layer_call(lp, u, share, **kw):
    return es.expert_share_layer(lp, u, share, KZ, SCALE_Z, False, scoring="softmax", **kw)


@pytest.mark.parametrize("chips", [1, 4, 8])
def test_softmax_shares_routed_parts_and_the_identity_term_once_add_up_to_the_uncut_layer(u, chips):
    lp = _layer_z()
    want, zero_pairs = _plain_z(lp, u)
    idx, w = es.route(u, lp["router"], lp["bias"], KZ, SCALE_Z, False, "softmax")
    wz, nz = es.zero_weights(idx, w, es.ExpertShare(N, chips, 0, NZ))
    identity = np.asarray(wz)[:, None] * np.asarray(u)          # what every chip computes alike
    assert int(nz.sum()) == zero_pairs and 0 < zero_pairs < T * KZ
    total, held = np.zeros((T, E)), 0
    for i in range(chips):
        share = es.ExpertShare(N, chips, i, NZ)
        y, counts = _layer_call(_slice(lp, share), u, share)
        assert counts.shape == (N // chips + 1,) and int(counts[-1]) == zero_pairs
        total += np.asarray(y) - identity                          # this chip's routed part
        held += int(counts[:-1].sum())
    assert held + zero_pairs == T * KZ            # every pair is held by one chip or is an identity pair
    np.testing.assert_allclose(total + identity, want, rtol=2e-5, atol=2e-6)


def test_softmax_selection_uses_s_plus_b_and_weights_are_scale_times_s_unrenormalised(u):
    lp = _layer_z(bias_std=0.05)
    share = es.ExpertShare(N, 1, 0, NZ)
    y = np.asarray(_layer_call(lp, u, share)[0])
    np.testing.assert_allclose(y, _plain_z(lp, u)[0], rtol=2e-5, atol=2e-6)
    for mode in ("no_bias", "renorm", "sigmoid", "no_identity"):    # each is another layer
        assert np.abs(y - _plain_z(lp, u, mode)[0]).max() > 1e-2, mode
    idx, w = es.route(u, lp["router"], lp["bias"], KZ, SCALE_Z, False, "softmax")
    z = np.asarray(u) @ np.asarray(lp["router"])
    s = np.exp(z) / np.exp(z).sum(-1, keepdims=True)
    np.testing.assert_allclose(np.asarray(w), SCALE_Z * np.take_along_axis(s, np.asarray(idx), -1), rtol=1e-5)
    assert float(w.sum(-1).max()) < SCALE_Z       # not renormalised: the picks' scores do not sum to 1
    # and the sigmoid scoring of the other two families is what it was
    i2, w2 = es.route(u, lp["router"], lp["bias"], KZ, SCALE_Z)
    np.testing.assert_allclose(np.asarray(w2.sum(-1)), SCALE_Z, rtol=1e-6)


@pytest.mark.parametrize("grouped_from", [0, 8], ids=["masked", "grouped"])
def test_all_picks_held_drops_none_and_all_picks_identity_multiplies_nothing(u, grouped_from, monkeypatch):
    lp = _layer_z()
    share = es.ExpertShare(N, 2, 0, NZ)               # holds experts 0..7
    held_lp = _slice(lp, share)
    # every token picks the same 5 held experts: each gets all T tokens, none dropped
    hot = dict(held_lp, bias=jnp.zeros(N + NZ).at[jnp.arange(KZ)].set(100.0))
    y, counts = _layer_call(hot, u, share, grouped_from=grouped_from)
    assert counts.tolist() == [T] * KZ + [0] * (8 - KZ) + [0]
    np.testing.assert_allclose(np.asarray(y), _plain_z(dict(lp, bias=hot["bias"]), u)[0], rtol=2e-5, atol=2e-6)
    # every token picks 5 identity columns: T x 5 zero pairs, the held experts' weights all 0
    cold = dict(held_lp, bias=jnp.zeros(N + NZ).at[N + jnp.arange(KZ)].set(100.0))
    seen = []
    real = es.held_experts
    monkeypatch.setattr(es, "held_experts", lambda u_, wh, *a: seen.append(float(jnp.abs(wh).max())) or real(u_, wh, *a))
    y, counts = _layer_call(cold, u, share, grouped_from=grouped_from)
    assert counts.tolist() == [0] * 8 + [T * KZ] and (seen == [0.0] if not grouped_from else seen == [])
    idx, w = es.route(u, cold["router"], cold["bias"], KZ, SCALE_Z, False, "softmax")
    np.testing.assert_allclose(np.asarray(y), np.asarray(w.sum(-1))[:, None] * np.asarray(u), rtol=1e-6, atol=1e-7)
    # the rows that are no tokens count in neither kind
    _, some = _layer_call(cold, u, share, grouped_from=grouped_from, valid=jnp.arange(T) < 10)
    assert some.tolist() == [0] * 8 + [10 * KZ]


@pytest.mark.parametrize("chips,index", [(1, 0), (4, 1), (8, 7)])
def test_softmax_grouped_equals_masked_with_identity_pairs_outside_every_group(u, chips, index):
    lp = _layer_z()
    share = es.ExpertShare(N, chips, index, NZ)
    masked, cm = _layer_call(_slice(lp, share), u, share)
    grouped, cg = _layer_call(_slice(lp, share), u, share, grouped_from=8)
    np.testing.assert_allclose(np.asarray(grouped), np.asarray(masked), rtol=2e-5, atol=2e-6)
    np.testing.assert_array_equal(np.asarray(cm), np.asarray(cg))


def test_held_counts_come_from_the_selection_where_a_softmax_score_rounds_to_zero(u):
    """A router so sharp that the 5th pick's softmax score underflows to 0: the
    pair is still a held pair (its weight is 0, its count is 1)."""
    lp = _layer_z()
    lp = dict(lp, router=lp["router"] * 400.0, bias=jnp.zeros(N + NZ))
    share = es.ExpertShare(N, 1, 0, NZ)
    idx, w = es.route(u, lp["router"], lp["bias"], KZ, SCALE_Z, False, "softmax")
    assert float(w.min()) == 0.0
    _, counts = _layer_call(lp, u, share)
    assert int(counts.sum()) == T * KZ
    assert int((es.held_weights(idx, w, share) > 0).sum()) + int(counts[-1]) < T * KZ
