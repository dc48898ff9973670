"""The expert layer that knows its share (``moe/expert_share.py``), at a small
size in float32: the shares add up to the uncut layer, the bias selects and
does not weigh, and no held pair is ever dropped."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.moe import expert_share as es
from deepspeed_tpu.ops.pallas import grouped_experts

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
    assert y.shape == (T, E)
    # a row that is no token picks nothing: it gets the shared expert alone
    np.testing.assert_allclose(np.asarray(y[10:]), np.asarray(es.gated_ffn(u[10:], *(lp["shared"][k] for k in ("w_gate", "w_up", "w_down")))),
                               rtol=1e-6, atol=1e-7)


# -- the grouped form: the same sum over the sorted pairs, one kernel -----------

@pytest.fixture
def kernel(monkeypatch):
    """The layer takes the kernel (interpreted: this is the CPU) → the tile
    map of each call made outside a trace: (tile_expert, n_live)."""
    calls = []
    real = grouped_experts.grouped_expert_ffn

    def ffn(u_, tile_expert, tile_rows, row_token, n_live, *rest):
        if not isinstance(n_live, jax.core.Tracer):
            calls.append((np.asarray(tile_expert), int(n_live[0])))
        return real(u_, tile_expert, tile_rows, row_token, n_live, *rest[:-1], True)

    monkeypatch.setattr(grouped_experts, "grouped_expert_ffn", ffn)
    monkeypatch.setattr(es, "kernel_runs", lambda lp: True)
    return calls


def _streamed(call):
    tile_expert, n_live = call
    return len(set(tile_expert[:n_live].tolist()))


@pytest.mark.parametrize("chips,index", [(1, 0), (4, 1), (8, 7)])
def test_the_grouped_product_equals_the_masked_one(u, chips, index):
    lp = _layer()
    share = es.ExpertShare(N, chips, index)
    ex = _slice(lp, share)["experts"]
    idx, w = es.route(u, lp["router"], lp["bias"], K, SCALE)
    masked = es.held_experts(u, es.held_weights(idx, w, share), ex["w_gate"], ex["w_up"], ex["w_down"])
    grouped = es.held_experts_grouped(u, idx, w, share, ex["w_gate"], ex["w_up"], ex["w_down"], interpret=True)
    np.testing.assert_allclose(np.asarray(grouped), np.asarray(masked), rtol=2e-5, atol=2e-6)


def test_the_grouped_product_drops_no_held_pair_under_the_most_uneven_routing(u):
    """Every token selects the same K experts, all held here: the whole
    static pair budget T x K is in the groups, none behind them. With none
    held, no pair is in a tile and the routed part is 0. And with every pair
    on ONE held expert (no router gives that; the product takes any pairs)
    the whole budget is one group of many tiles."""
    lp = _layer()
    lp = dict(lp, bias=jnp.zeros(N).at[jnp.array([4, 5, 6, 7])].set(100.0))
    idx, w = es.route(u, lp["router"], lp["bias"], K, SCALE)
    for index, full in ((1, True), (2, False)):
        share = es.ExpertShare(N, 4, index)
        ex = _slice(lp, share)["experts"]
        masked = es.held_experts(u, es.held_weights(idx, w, share), ex["w_gate"], ex["w_up"], ex["w_down"])
        grouped = es.held_experts_grouped(u, idx, w, share, ex["w_gate"], ex["w_up"], ex["w_down"], interpret=True)
        np.testing.assert_allclose(np.asarray(grouped), np.asarray(masked), rtol=2e-5, atol=2e-6)
        assert (float(jnp.abs(grouped).max()) > 0) == full
    share = es.ExpertShare(N, 4, 1)
    ex = _slice(lp, share)["experts"]
    one = jnp.full_like(idx, 6)
    masked = es.held_experts(u, es.held_weights(one, w, share), ex["w_gate"], ex["w_up"], ex["w_down"])
    grouped = es.held_experts_grouped(u, one, w, share, ex["w_gate"], ex["w_up"], ex["w_down"], interpret=True)
    np.testing.assert_allclose(np.asarray(grouped), np.asarray(masked), rtol=2e-5, atol=2e-5)


def test_the_layer_takes_one_form_and_a_whole_prompts_rows_go_through_in_blocks(kernel, monkeypatch):
    """Where the kernel runs every call takes it, whatever its rows (no
    family says from where on); a program of more rows than one call holds
    goes through in equal blocks; result and counts are the masked form's."""
    lp = _layer()
    share = es.ExpertShare(N, 4, 1)
    big = jax.random.normal(jax.random.PRNGKey(3), (64, E), jnp.float32)
    calls = []
    real = es.held_experts_grouped
    monkeypatch.setattr(es, "held_experts_grouped", lambda *a: calls.append(a[0].shape) or real(*a))
    monkeypatch.setattr(es, "kernel_runs", lambda lp: False)
    want_y, want_c = es.expert_share_layer(_slice(lp, share), big, share, K, SCALE)
    assert calls == []                 # off the TPU: the masked form
    monkeypatch.setattr(es, "kernel_runs", lambda lp: True)
    served = lambda T, E_: es.block_rows(jax.ShapeDtypeStruct((T, E_), jnp.bfloat16))
    assert served(48, 4096) == 48 and served(1072, 4096) == 1072 and served(320, 6144) == 320
    assert served(24576, 4096) == 1024 and served(3072, 6144) == 768 and served(4096, 6144) == 512
    row = E * (4 + 4)                  # a float32 row of u and of y
    for block, rows in ((1536, 64), (16, 16), (24, 16)):     # in one piece, then four blocks of 16 rows (24 does not divide 64)
        monkeypatch.setattr(grouped_experts, "ROWS_BYTES", block * row)
        calls.clear()
        y, c = es.expert_share_layer(_slice(lp, share), big, share, K, SCALE)
        assert calls == [(rows, E)]
        np.testing.assert_allclose(np.asarray(y), np.asarray(want_y), rtol=2e-5, atol=2e-6)
        np.testing.assert_array_equal(np.asarray(c), np.asarray(want_c))


# the three served routings at their router widths (16 held), small inside
ROUTINGS = {
    "sigmoid-top8-of-128-shared": dict(n=128, nz=0, k=8, scale=2.5, norm=True, scoring="sigmoid", shared=True, chips=8),
    "sigmoid-top4-of-128": dict(n=128, nz=0, k=4, scale=1.0, norm=True, scoring="sigmoid", shared=False, chips=8),
    "softmax-top12-of-512+256": dict(n=512, nz=256, k=12, scale=6.0, norm=False, scoring="softmax", shared=False, chips=32),
}
EK, FK = 32, 16


def _routing_layer(r, seed=2):
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    w = lambda k, s, std=0.3: jax.random.normal(k, s, jnp.float32) * std
    cols = r["n"] + r["nz"]
    lp = {
        "router": w(ks[0], (EK, cols)), "bias": w(ks[1], (cols,), 0.5 if r["scoring"] == "sigmoid" else 1.0 / cols),
        "experts": {"w_gate": w(ks[2], (r["n"], EK, FK)), "w_up": w(ks[3], (r["n"], EK, FK)), "w_down": w(ks[4], (r["n"], FK, EK))},
    }
    if r["shared"]:
        lp["shared"] = {"w_gate": w(ks[5], (EK, FK)), "w_up": w(ks[6], (EK, FK)), "w_down": w(ks[7], (FK, EK))}
    return lp


@pytest.mark.parametrize("case", ["experts-unhit", "no-pair-held", "every-pick-held", "rows-not-valid"])
@pytest.mark.parametrize("rows", [64, 320, 1072])
@pytest.mark.parametrize("routing", list(ROUTINGS))
def test_the_kernel_equals_the_masked_form_and_the_uncut_layer(kernel, monkeypatch, routing, rows, case):
    """One chip's part through the kernel against the masked form of the same
    share (the oracle) and against the UNCUT float32 layer: all experts on
    one chip, the absent ones' ``w_down`` zero. The counts are the masked
    form's, and the experts the kernel's tile map streams are the experts
    counted (``experts_streamed``), where the masked form streams all 16."""
    r = ROUTINGS[routing]
    lp = _routing_layer(r)
    share = es.ExpertShare(r["n"], r["chips"], 1, r["nz"])
    held = np.asarray(share.held_ids())
    ub = jax.random.normal(jax.random.PRNGKey(rows), (rows, EK), jnp.float32)
    valid, bias = None, lp["bias"]
    if case == "experts-unhit":            # half the held experts can never be picked
        bias = bias.at[held[::2]].set(-100.0)
    elif case == "no-pair-held":
        bias = bias.at[held].set(-100.0)
    elif case == "every-pick-held":        # every token's k picks are held: the whole T x k budget is in the groups
        bias = bias.at[held[: r["k"]]].set(100.0)
    else:
        valid = jnp.arange(rows) % 5 != 0
    lp = dict(lp, bias=bias)
    call = lambda lp_, sh: es.expert_share_layer(lp_, ub, sh, r["k"], r["scale"], r["norm"], valid, scoring=r["scoring"])
    y, counts = call(_slice(lp, share), share)
    (te, n_live), = kernel
    monkeypatch.setattr(es, "kernel_runs", lambda lp: False)
    masked, cm = call(_slice(lp, share), share)
    absent = jnp.ones(r["n"]).at[held].set(0.0)[:, None, None] > 0
    uncut_lp = dict(lp, experts=dict(lp["experts"], w_down=jnp.where(absent, 0.0, lp["experts"]["w_down"])))
    uncut, cu = call(uncut_lp, es.ExpertShare(r["n"], 1, 0, r["nz"]))
    np.testing.assert_allclose(np.asarray(y), np.asarray(masked), rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(y), np.asarray(uncut), rtol=2e-5, atol=2e-5)
    np.testing.assert_array_equal(np.asarray(counts), np.asarray(cm))
    loads = np.asarray(counts)[: share.n_held]
    np.testing.assert_array_equal(loads, np.asarray(cu)[held])
    assert es.experts_streamed(loads[None], True) == _streamed((te, n_live)) == int((loads > 0).sum())
    assert es.experts_streamed(loads[None], False) == share.n_held
    tm = grouped_experts.row_tile(rows * r["k"], r["n"] + r["nz"])
    assert n_live == int(np.ceil(loads / tm).sum()) and len(te) == rows * r["k"] // tm + share.n_held
    if case == "experts-unhit":
        assert (loads[::2] == 0).all() and 0 < _streamed((te, n_live)) <= share.n_held // 2
    elif case == "no-pair-held":
        assert n_live == 0 and loads.sum() == 0
    elif case == "every-pick-held":
        assert loads.tolist() == [rows] * r["k"] + [0] * (share.n_held - r["k"])
    else:
        assert int(counts.sum()) <= int(valid.sum()) * r["k"]
        np.testing.assert_array_equal(np.asarray(y)[~np.asarray(valid)], np.asarray(masked)[~np.asarray(valid)])


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


@pytest.mark.parametrize("grouped", [False, True], ids=["masked", "grouped"])
def test_all_picks_held_drops_none_and_all_picks_identity_multiplies_nothing(u, grouped, monkeypatch, request):
    if grouped:
        request.getfixturevalue("kernel")
    lp = _layer_z()
    share = es.ExpertShare(N, 2, 0, NZ)               # holds experts 0..7
    held_lp = _slice(lp, share)
    # every token picks the same 5 held experts: each gets all T tokens, none dropped
    hot = dict(held_lp, bias=jnp.zeros(N + NZ).at[jnp.arange(KZ)].set(100.0))
    y, counts = _layer_call(hot, u, share)
    assert counts.tolist() == [T] * KZ + [0] * (8 - KZ) + [0]
    np.testing.assert_allclose(np.asarray(y), _plain_z(dict(lp, bias=hot["bias"]), u)[0], rtol=2e-5, atol=2e-6)
    # every token picks 5 identity columns: T x 5 zero pairs, the held experts' weights all 0
    cold = dict(held_lp, bias=jnp.zeros(N + NZ).at[N + jnp.arange(KZ)].set(100.0))
    seen = []
    real = es.held_experts
    monkeypatch.setattr(es, "held_experts", lambda u_, wh, *a: seen.append(float(jnp.abs(wh).max())) or real(u_, wh, *a))
    y, counts = _layer_call(cold, u, share)
    assert counts.tolist() == [0] * 8 + [T * KZ] and (seen == [0.0] if not grouped else seen == [])
    idx, w = es.route(u, cold["router"], cold["bias"], KZ, SCALE_Z, False, "softmax")
    np.testing.assert_allclose(np.asarray(y), np.asarray(w.sum(-1))[:, None] * np.asarray(u), rtol=1e-6, atol=1e-7)
    # the rows that are no tokens count in neither kind
    _, some = _layer_call(cold, u, share, valid=jnp.arange(T) < 10)
    assert some.tolist() == [0] * 8 + [10 * KZ]


@pytest.mark.parametrize("chips,index", [(1, 0), (4, 1), (8, 7)])
def test_softmax_grouped_equals_masked_with_identity_pairs_outside_every_group(u, chips, index, request):
    lp = _layer_z()
    share = es.ExpertShare(N, chips, index, NZ)
    masked, cm = _layer_call(_slice(lp, share), u, share)
    request.getfixturevalue("kernel")
    grouped, cg = _layer_call(_slice(lp, share), u, share)
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


@pytest.mark.parametrize("scoring,top_k,norm", [("sigmoid", K, True), ("softmax", 1, False)], ids=["sigmoid-top4", "softmax-top1"])
def test_scores_handed_in_equal_the_linear_routes_where_they_are_u_times_router(u, scoring, top_k, norm):
    """A family whose router is no single matrix (ZAYA's MLP over a state
    carried down the depth) hands ``logits`` in; where they ARE ``u @
    router``, picks, weights, output and counts are the linear route's, and
    the router's matrix is then not read at all."""
    lp = _layer()
    share = es.ExpertShare(N)
    logits = jnp.dot(u, lp["router"], precision=jax.lax.Precision.HIGHEST)
    want_idx, want_w = es.route(u, lp["router"], lp["bias"], top_k, SCALE, norm, scoring)
    got_idx, got_w = es.route(u, None, lp["bias"], top_k, SCALE, norm, scoring, logits=logits)
    np.testing.assert_array_equal(np.asarray(got_idx), np.asarray(want_idx))
    np.testing.assert_array_equal(np.asarray(got_w), np.asarray(want_w))
    valid = jnp.arange(T) % 5 != 0
    want_y, want_c = es.expert_share_layer(lp, u, share, top_k, SCALE, norm, valid, scoring)
    no_router = {k: v for k, v in lp.items() if k != "router"}
    got_y, got_c = es.expert_share_layer(no_router, u, share, top_k, SCALE, norm, valid, scoring, logits=logits)
    np.testing.assert_array_equal(np.asarray(got_y), np.asarray(want_y))
    np.testing.assert_array_equal(np.asarray(got_c), np.asarray(want_c))
    other = es.expert_share_layer(no_router, u, share, top_k, SCALE, norm, valid, scoring, logits=-logits)[0]
    assert float(jnp.abs(other - want_y).max()) > 1e-3      # the scores handed in are what routes


# -- a gated shared expert (PR 52) -----------------------------------------------------------------

# the four expert families' calls of the layer: (scoring, top_k, scale, renormalised), each with a shared expert here
FAMILY_CALLS = {"k-exaone": ("sigmoid", K, SCALE, True), "mistral4": ("sigmoid", K, 1.0, True),
                "longcat": ("softmax", K, SCALE, False), "zaya": ("softmax", 1, 1.0, False)}


@pytest.mark.parametrize("family", sorted(FAMILY_CALLS))
def test_without_a_shared_gate_the_layer_is_bit_for_bit_what_it_was(u, family):
    """``shared_gate`` absent: the routed part plus the shared expert's plain
    output, the sum the layer made before the key existed; present, the
    shared part alone is scaled by ``sigmoid(u . w_sg)`` a token."""
    scoring, top_k, scale, norm = FAMILY_CALLS[family]
    lp, share = _layer(), es.ExpertShare(N)
    sh = lp["shared"]
    y, counts = es.expert_share_layer(lp, u, share, top_k, scale, norm, scoring=scoring)
    routed, c0 = es.expert_share_layer({k: v for k, v in lp.items() if k != "shared"}, u, share, top_k, scale, norm, scoring=scoring)
    plain = es.gated_ffn(u, sh["w_gate"], sh["w_up"], sh["w_down"])
    np.testing.assert_array_equal(np.asarray(y), np.asarray(routed + plain))
    np.testing.assert_array_equal(np.asarray(counts), np.asarray(c0))
    w_sg = jax.random.normal(jax.random.PRNGKey(11), (E, 1), jnp.float32)
    gated, c1 = es.expert_share_layer(dict(lp, shared_gate=w_sg), u, share, top_k, scale, norm, scoring=scoring)
    np.testing.assert_allclose(np.asarray(gated), np.asarray(routed + jax.nn.sigmoid(u @ w_sg) * plain), rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(c1), np.asarray(c0))
    assert float(jnp.abs(gated - y).max()) > 1e-3


def test_softmax_top_k_counts_are_hits_not_weights(u):
    """Under softmax scoring with several renormalised picks (Qwen3-Next's
    top-10 of 512) a held expert's count is the TOKENS that picked it, whatever
    their weights: the eight shares' counts add up to ``T x k``."""
    lp = dict(_layer(), bias=jnp.zeros((N,)))
    idx, w = es.route(u, lp["router"], lp["bias"], K, 1.0, True, "softmax")
    np.testing.assert_allclose(np.asarray(w.sum(-1)), 1.0, rtol=1e-6)
    total = 0
    for i in range(4):
        share = es.ExpertShare(N, 4, i)
        _, counts = es.expert_share_layer(_slice(lp, share), u, share, K, 1.0, True, scoring="softmax")
        want = np.asarray((idx[:, :, None] == share.held_ids()[None, None, :]).sum((0, 1)))
        np.testing.assert_array_equal(np.asarray(counts), want)
        total += int(counts.sum())
    assert total == T * K


# -- a group limit (DeepSeek-V3's; Ling-3.0-flash: top-8 of 512 in 4 of 8 groups) --------------

NG, KG = 4, 2     # N = 16 columns: four groups of four, a token keeps two


def _plain_grouped(lp, u):
    """The uncut layer under the group limit, one token at a time: (the
    output, the picks ``[T, K]``, the groups kept ``[T, NG]``)."""
    out, picks, kept = np.zeros((T, E), np.float64), np.zeros((T, K), np.int64), np.zeros((T, NG), bool)
    s = 1.0 / (1.0 + np.exp(-(np.asarray(u, np.float64) @ np.asarray(lp["router"], np.float64))))
    sb = s + np.asarray(lp["bias"], np.float64)
    ffn = lambda x, wg, wu, wd: ((lambda g: g / (1 + np.exp(-g)))(x @ wg) * (x @ wu)) @ wd
    size = N // NG
    for t in range(T):
        score = [np.sort(sb[t, j * size:(j + 1) * size])[-2:].sum() for j in range(NG)]
        kept[t, np.argsort(score)[-KG:]] = True
        masked = np.where(np.repeat(kept[t], size), sb[t], -np.inf)
        picks[t] = np.argsort(-masked)[:K]
        for e in picks[t]:
            out[t] += SCALE * s[t, e] / s[t, picks[t]].sum() * ffn(
                np.asarray(u[t], np.float64), *(np.asarray(lp["experts"][k][e], np.float64) for k in ("w_gate", "w_up", "w_down")))
        out[t] += ffn(np.asarray(u[t], np.float64), *(np.asarray(lp["shared"][k], np.float64) for k in ("w_gate", "w_up", "w_down")))
    return out, picks, kept


def test_group_limited_route_is_the_plain_loop_and_one_group_is_todays_route_bit_for_bit(u):
    lp = _layer()
    _, picks, kept = _plain_grouped(lp, u)
    idx, w, kept_ = es.route_kept(u, lp["router"], lp["bias"], K, SCALE, n_group=NG, topk_group=KG)
    assert np.array_equal(np.sort(np.asarray(idx), axis=1), np.sort(picks, axis=1)) and np.array_equal(np.asarray(kept_), kept)
    assert kept.sum(1).tolist() == [KG] * T and np.all(kept[np.arange(T)[:, None], picks // (N // NG)])   # every pick in a kept group
    np.testing.assert_allclose(np.asarray(w.sum(-1)), SCALE, rtol=1e-6)
    free = es.route(u, lp["router"], lp["bias"], K, SCALE)
    assert not np.array_equal(np.sort(np.asarray(free[0]), axis=1), np.sort(picks, axis=1))           # the limit changes who is picked
    # n_group 1 is the route there was: the same values to the bit, and the same traced text
    one = es.route(u, lp["router"], lp["bias"], K, SCALE, n_group=1, topk_group=1)
    assert all(np.array_equal(np.asarray(a), np.asarray(b)) for a, b in zip(free, one))
    text = lambda **kw: jax.jit(lambda u: es.route(u, lp["router"], lp["bias"], K, SCALE, **kw)).lower(u).as_text()  # noqa: E731
    assert text() == text(n_group=1, topk_group=1) != text(n_group=NG, topk_group=KG)
    assert es.held_groups(es.ExpertShare(N, 4, 2), NG) == [2] and es.held_groups(es.ExpertShare(N, 2, 1), NG) == [2, 3]
    assert es.held_groups(es.ExpertShare(N, 8, 5), NG) == [2]                                          # half a group


@pytest.mark.parametrize("chips", [2, 4, 8])
def test_the_group_limited_shares_add_up_to_the_uncut_layer_and_a_share_sees_only_rows_that_kept_its_group(u, chips):
    """The guide's test that ties the share to the model: the ``chips`` shares'
    routed parts, with the shared expert once, are the uncut group-limited
    layer; a share's counts are of tokens that kept its group, and its last
    entry is how many rows did."""
    lp = _layer()
    want, picks, kept = _plain_grouped(lp, u)
    shared = np.asarray(es.gated_ffn(u, *(lp["shared"][k] for k in ("w_gate", "w_up", "w_down"))))
    total, pairs = np.zeros((T, E)), 0
    for i in range(chips):
        share = es.ExpertShare(N, chips, i)
        y, counts = es.expert_share_layer(_slice(lp, share), u, share, K, SCALE, n_group=NG, topk_group=KG)
        assert counts.shape == (N // chips + 1,)
        mine = es.held_groups(share, NG)
        rows = kept[:, mine].any(axis=1)
        assert int(counts[-1]) == int(rows.sum())
        part = np.asarray(y) - shared
        assert np.abs(part[~rows]).max(initial=0.0) == 0.0           # a row that kept none of this share's groups brings nothing here
        held = np.isin(picks, np.arange(i * share.n_held, (i + 1) * share.n_held))
        assert counts[:-1].tolist() == [int((picks == e).any(1).sum()) for e in range(i * share.n_held, (i + 1) * share.n_held)]
        assert not held[~rows].any()
        total, pairs = total + part, pairs + int(counts[:-1].sum())
    assert pairs == T * K
    np.testing.assert_allclose(total + shared, want, rtol=2e-5, atol=2e-6)
    # rows that are no tokens keep nothing
    share = es.ExpertShare(N, 4, 1)
    _, some = es.expert_share_layer(_slice(lp, share), u, share, K, SCALE, valid=jnp.arange(T) < 10, n_group=NG, topk_group=KG)
    assert int(some[-1]) == int(kept[:10, 1].sum())
