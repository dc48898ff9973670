"""The ``bailing_hybrid`` family (Ling-3.0-flash) through the paged programs at
a small size on the CPU (widths cut: E 64, two periods of two KDA layers and
one latent attention, the first layer's FFN dense, 16 experts in 4 groups of
which this share holds 8: two whole groups; page 4, chunk 8), in float32:
``forward``, absorbed and expanded, and the served streams against the float32
reference's full forward (``perfbench/reference_ling3.py``, the delta rule
token by token with a decay a key channel), the state pools beside ONE latent
pool, the group limit's rows, and the refusals of both kinds."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models import ling3 as m
from deepspeed_tpu.serving import model as smodel
from deepspeed_tpu.serving.kv_cache import STATE_KINDS
from deepspeed_tpu.telemetry import parts, spans
from perfbench import reference_ling3 as reference

CFG = dict(
    vocab_size=96, hidden_size=64, intermediate_size=96, moe_intermediate_size=32, moe_shared_expert_intermediate_size=32,
    num_hidden_layers=6, layer_group_size=3, num_attention_heads=4, head_dim=16, short_conv_kernel_size=4, kda_lower_bound=-5,
    kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, rope_theta=1e4, first_k_dense_replace=1,
    num_experts=8, published={"num_experts": 16}, expert_share={"chips": 2, "index": 1}, num_experts_per_tok=3, n_group=4,
    topk_group=2, routed_scaling_factor=2.5, norm_topk_prob=True, rms_norm_eps=1e-6, max_position_embeddings=512,
    initializer_range=0.25, expert_swiglu_limit_list=[0] * 6 + [4], share_expert_swiglu_limit_list=[0] * 6 + [5],
)
SERVING = dict(max_slots=3, page_size=4, num_pages=64, max_prompt_len=40, max_new_tokens=12,
               prefill_chunk_tokens=8, temperature=0.0)
# ONE chunk (<= a chunk: 5, 8; first and last in one call) and 2-5 chunks whose LAST has one row (9, 17, 33) or two (10), or is whole (40)
PROMPTS = (5, 8, 9, 10, 17, 19, 33, 40, 27)
GAP_TOL = 1e-4                          # float32 both ways, summed in another order


@pytest.fixture(scope="module")
def mcfg():
    return m.Ling3Config.from_dict(CFG)


@pytest.fixture(scope="module")
def arch():
    return reference.Arch.from_config(CFG)


@pytest.fixture(scope="module")
def engine(mcfg):
    return deepspeed_tpu.init_inference(model=m.make_module(mcfg), dtype=jnp.float32, seed=3)


@pytest.fixture(scope="module")
def prompts():
    rng = np.random.default_rng(0)
    return [rng.integers(0, 96, n).astype(np.int32) for n in PROMPTS]


@pytest.fixture(scope="module")
def served(engine, prompts):
    srv = engine.serve(dict(SERVING))
    reqs = [srv.submit(p, max_new_tokens=12, seed=i) for i, p in enumerate(prompts)]
    srv.run()
    return srv, reqs


def _gaps(params, prompt, tokens, arch):
    ids = np.concatenate([prompt, np.asarray(tokens, np.int32)])
    padded = np.zeros((64,), np.int32)
    padded[: len(ids)] = ids
    gap, _, _ = reference.served_gaps(params, jnp.asarray(padded), jnp.int32(len(prompt)), jnp.int32(len(ids)),
                                      arch=arch, rows=len(tokens))
    return np.asarray(gap)


def test_forward_absorbed_and_served_streams_are_the_references_with_both_kinds_of_pool_and_slot_reuse(mcfg, engine, served, prompts, arch):
    """One engine and one server, built once: the model's own ``forward``,
    expanded and absorbed, is the reference's logits; the served streams are
    the reference's across prefill in one chunk and in several
    (last chunks of one and two rows), cached decode and slot reuse; the state
    pools stand beside ONE latent pool; the gauges, the phase's attrs, the
    parts, the group limit's rows; migration is refused with both kinds named."""
    ids = jnp.asarray(np.random.default_rng(1).integers(0, 96, (2, 21)).astype(np.int32))
    lg = jax.jit(functools.partial(m.forward, mcfg))(engine.params, ids)
    absorbed = jax.jit(functools.partial(m.forward, mcfg, absorbed=True))(engine.params, ids)
    ref = jax.jit(jax.vmap(lambda i: reference.logits(engine.params, i, arch)))(ids)
    assert lg.shape == (2, 21, 96) and float(jnp.std(ref)) > 0.5
    assert float(jnp.abs(lg - ref).max()) <= 2e-4 and float(jnp.abs(absorbed - ref).max()) <= 2e-4
    # the reference tells this model from its neighbours: one decay a head, no bound, no group limit
    for skip in ("scalar_decay", "no_bound", "no_group_limit", "group_top1", "no_head_gate", "bias_in_weights", "beta_1", "no_conv"):
        other = jax.jit(jax.vmap(lambda i: reference.logits(engine.params, i, arch, skip)))(ids)
        assert float(jnp.abs(other - ref).max()) > 0.5, skip
    srv, reqs = served
    assert [k.holds(srv.family) for k in STATE_KINDS] == [True, False, False, True]   # recurrent AND latent; no carried rows, no rings
    for r, p in zip(reqs, prompts):      # 9 requests through 3 slots: every slot is used again, from zeros
        assert r.status == "finished" and len(r.tokens) == 12
        assert float(_gaps(engine.params, p, r.tokens, arch).max()) <= GAP_TOL, len(p)
    # -- the pools, the gauges, the phase: a latent pool of the 2 attention layers, no V pool, the states of the 4 KDA layers
    ds, fam = srv.decode_set, srv.family
    assert smodel.pool_layers(fam) == (2, 0, 4) and ds.n_layer == 2 and fam.kv_pools == 1 and ds.cache.latent
    assert ds.cache.k.shape == (2, 64, 1, 4, 40) and ds.cache.win_k is None
    lin, conv, by = ds.cache.rec, ds.cache.conv, ds.cache_bytes()
    assert lin.shape == (4, 3, 4, 16, 16) and lin.dtype == jnp.float32 and conv.shape == (4, 3, 3, 3 * 64)
    assert by["lin_state"] == 4 * 3 * 4 * 16 * 16 * 4
    assert srv.metrics.gauge("serving_lin_state_bytes", "").value() == by["lin_state"]
    kv = srv.metrics.gauge("serving_kv_bytes", "", labelnames=("class",))
    assert kv.value(**{"class": "state"}) == by["state"] and kv.value(**{"class": "latent"}) == 2 * 64 * 4 * 40 * 4
    phase = [p for p in spans.phases() if p[0] == "ds.init.programs"][-1]
    assert phase[3]["lin_state_bytes"] == by["lin_state"] and "state=" in phase[3]["kv_bytes"] and "latent=" in phase[3]["kv_bytes"]
    assert smodel._kv_homes(fam) == [(False, 0), (False, 1), (False, 0), (False, 2), (False, 3), (False, 1)]
    assert fam.kinds == ("lin", "lin", "attn") * 2 and fam.sparse_layers == (1, 2, 3, 4, 5) and fam.lin_g_min == -5
    whole = engine.serve(dict(SERVING, prefill_chunk_tokens=0))      # the whole-prompt program: only where nothing chunks
    whole._ensure_compiled()
    for name in ("jit_decode_fn", "jit_chunk_decode_fn", "jit_prefill_fn"):
        table = parts.tables()[name]
        assert {"lin.proj", "lin.scan", "attn.core", "moe.route", "moe.experts"} <= {e.part for e in table.values()}, name
        assert all(e.part for e in table.values() if e.has_dot), name
    # -- the group limit's rows: on the leaves that carry the expert loads, in the registry and in stats()
    emits = [a for n, _, _, a in spans.snapshot() if n in ("ds.serve.emit", "ds.serve.chunk") and "group_rows" in a]
    assert emits and all(0 <= a["group_rows"] <= a["rows"] and a["rows"] % 5 == 0 for a in emits)    # 5 expert layers a row
    assert all(a["moe_pairs_held"] <= a["moe_pairs_routed"] and a["moe_load_max"] * 5 <= a["rows"] for a in emits)
    total = srv.metrics.counter("serving_moe_group_rows_total", "").value()
    assert srv.stats()["group_rows"] == total == sum(a["group_rows"] for a in emits)
    share = total / srv.metrics.counter("serving_moe_rows_total", "").value()
    assert 0.6 < share <= 1.0                     # this share holds two of four groups and a token keeps two: 5 rows in 6
    # -- a slot that is used again serves the same tokens
    again = [srv.submit(p, max_new_tokens=12, seed=i) for i, p in enumerate(prompts[:4])]
    srv.run()
    assert [list(r.tokens) for r in again] == [list(r.tokens) for r in reqs[:4]]
    with pytest.raises(ValueError, match="session migration is not available for a model with recurrent state.*matrix state.*"
                                         "and with a latent KV pool"):
        srv._ensure_migration_programs()
    srv.drain(0.0)
    srv.check_no_leaks()


def test_the_mechanisms_that_know_pages_only_are_refused_with_both_kinds_of_state_named(engine):
    from deepspeed_tpu.runtime.config import ServingConfig

    for section, what in [
        ({"prefix_cache": {"enabled": True}}, "serving.prefix_cache"),
        ({"kv_cache_dtype": "int8"}, "serving.kv_cache_dtype=int8"),
        ({"placement": {"tp": 2}}, "serving.placement.tp > 1"),
        ({"placement": {"disaggregate": True}}, "serving.placement.disaggregate"),
    ]:
        with pytest.raises(ValueError, match="recurrent state.*and with a latent KV pool") as e:
            engine.serve(dict(SERVING, **section))
        assert what in str(e.value) and str(e.value).count("Ling3Config") == 2
    with pytest.raises(ValueError, match="serving.speculative.*recurrent state") as e:     # a draft is refused by the state alone
        engine.serve(dict(SERVING, speculative={"enabled": True, "k": 3, "ngram": 2}))
    assert "latent KV pool" not in str(e.value)
    cfg = ServingConfig.from_dict(dict(SERVING))
    cfg.tiering.enabled = True
    with pytest.raises(ValueError, match="serving.tiering"):
        engine.serve(cfg)


@pytest.mark.parametrize("over,word", [
    ({"expert_swiglu_limit_list": [0, 0, 4, 0, 0, 0]}, "expert_swiglu_limit_list is nonzero"),
    ({"share_expert_swiglu_limit_list": [0] * 5 + [7]}, "share_expert_swiglu_limit_list is nonzero"),
    ({"use_kda_lora": True}, "use_kda_lora"), ({"q_lora_rank": 48}, "q_lora_rank"),
    ({"num_kv_heads_for_linear_attn": 2}, "num_kv_heads_for_linear_attn=2"), ({"use_nGPT": True}, "use_nGPT"),
    ({"value_norm": True}, "value_norm"), ({"up_proj_norm": True}, "up_proj_norm"),
    ({"scale_router_input": True}, "scale_router_input"), ({"score_function": "softmax"}, "score_function"),
    ({"num_experts": 2, "expert_share": {"chips": 8, "index": 0}}, None),                   # half a group: a divisor of one, built
    ({"num_experts": 12, "published": {"num_experts": 24}, "n_group": 3}, "neither whole routing groups of 8 nor a divisor"),
    ({"kda_lower_bound": -6}, "kda_chunk: a decay's lower bound of -6"), ({"kda_safe_gate": False}, "kda_safe_gate"),
])
def test_a_config_the_module_does_not_build_is_refused_by_name(over, word):
    if word is None:
        assert m.Ling3Config.from_dict(dict(CFG, **over)).share.n_held == 2
        return
    with pytest.raises(ValueError, match=word):
        m.Ling3Config.from_dict(dict(CFG, **over))


def test_the_published_shapes_and_the_drawn_decays_stay_inside_the_bound():
    cfg = m.Ling3Config(num_hidden_layers=12, num_experts=64, expert_chips=8, vocab_size=19648)
    fam = cfg.serving_family()
    assert fam.lin_state == (32, 128, 128) and 4 * int(np.prod(fam.lin_state)) == 2_097_152 and fam.lin_conv == (4, 12288)
    assert fam.kinds == ("lin",) * 5 + ("attn",) + ("lin",) * 5 + ("attn",) and fam.sparse_layers == tuple(range(2, 12))
    assert (fam.head_dim, fam.v_width, fam.kv_pools, fam.experts_held) == (576, 512, 1, 64) and fam.sm_scale == pytest.approx(192 ** -0.5)
    shapes = m._leaf_shapes(cfg)["layers"]
    count = lambda t: sum(int(np.prod(s)) for s, _ in jax.tree_util.tree_leaves(t, is_leaf=m.mla.is_leaf_spec))  # noqa: E731
    assert count(shapes[0]["lin"]) == pytest.approx(63.05e6, rel=1e-3) and count(shapes[5]["attn"]) == pytest.approx(31.97e6, rel=3e-3)
    assert "wq" in shapes[5]["attn"] and "wq_a" not in shapes[5]["attn"] and shapes[5]["attn"]["w_gate"][0] == (2560, 32)
    # the drawn decays: half-lives of 4 to 4 096 tokens, inside (kda_lower_bound, 0) with room for what a token adds
    small = m.Ling3Config.from_dict(dict(CFG, num_hidden_layers=1, first_k_dense_replace=0))
    lin = m.init_params(small, jax.random.PRNGKey(0), jnp.float32)["layers"][0]["lin"]
    g = -5.0 * jax.nn.sigmoid(lin["dt_bias"])
    life = np.log(2.0) / -np.asarray(g)
    assert 3.9 < life.min() < 40 and 400 < life.max() < 4200 and float(g.min()) > -0.2
