"""The ``qwen3_next`` family through the paged programs at a small size on the
CPU (widths cut: E 64, two periods of three Gated DeltaNet layers and one
gated attention, 16 experts of which this share holds 8; page 4, chunk 8), in
float32: ``forward`` and the served streams against the float32 reference's
full forward (``perfbench/reference_qwen3_next.py``, the delta rule token by
token), the state pools beside the paged pools, the shares adding up, the
parts, the gauge, and the refusals."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models import qwen3_next as m
from deepspeed_tpu.moe import expert_share as es
from deepspeed_tpu.serving import model as smodel
from deepspeed_tpu.serving.kv_cache import STATE_KINDS, Cache
from deepspeed_tpu.telemetry import parts, spans
from perfbench import reference_qwen3_next as reference

CFG = dict(
    vocab_size=96, hidden_size=64, num_hidden_layers=8, full_attention_interval=4, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, partial_rotary_factor=0.25, rope_theta=1e4, linear_num_key_heads=2,
    linear_num_value_heads=4, linear_key_head_dim=16, linear_value_head_dim=16, linear_conv_kernel_dim=4,
    num_experts=8, published={"num_experts": 16}, expert_share={"chips": 2, "index": 1}, num_experts_per_tok=3,
    moe_intermediate_size=32, shared_expert_intermediate_size=32, norm_topk_prob=True, rms_norm_eps=1e-6,
    max_position_embeddings=512, initializer_range=0.25,
)
SERVING = dict(max_slots=3, page_size=4, num_pages=64, max_prompt_len=40, max_new_tokens=12,
               prefill_chunk_tokens=8, temperature=0.0)
# ONE chunk (<= a chunk: 5, 8; first and last in one call) and 2-5 chunks whose LAST has one row (9, 17, 33) or two (10), or is whole (40);
# a chunk of 8 rows is no multiple of the rule's sub-chunk of 64
PROMPTS = (5, 8, 9, 10, 17, 19, 33, 40, 27)
GAP_TOL = 1e-4                          # float32 both ways, summed in another order


@pytest.fixture(scope="module")
def mcfg():
    return m.Qwen3NextConfig.from_dict(CFG)


@pytest.fixture(scope="module")
def arch():
    return reference.Arch.from_config(CFG)


@pytest.fixture(scope="module")
def engine(mcfg):
    return deepspeed_tpu.init_inference(model=m.make_module(mcfg), dtype=jnp.float32, seed=3)


def _serve(engine, prompts, new=12, **over):
    srv = engine.serve(dict(SERVING, **over))
    reqs = [srv.submit(p, max_new_tokens=new, seed=i) for i, p in enumerate(prompts)]
    srv.run()
    return srv, reqs


@pytest.fixture(scope="module")
def prompts():
    rng = np.random.default_rng(0)
    return [rng.integers(0, 96, n).astype(np.int32) for n in PROMPTS]


@pytest.fixture(scope="module")
def served(engine, prompts):
    return _serve(engine, prompts)


def _gaps(params, prompt, tokens, arch):
    ids = np.concatenate([prompt, np.asarray(tokens, np.int32)])
    padded = np.zeros((64,), np.int32)
    padded[: len(ids)] = ids
    gap, _, _ = reference.served_gaps(params, jnp.asarray(padded), jnp.int32(len(prompt)), jnp.int32(len(ids)),
                                      arch=arch, rows=len(tokens))
    return np.asarray(gap)


# -- the model's own forward and the served streams are the reference's -------

def test_forward_and_served_streams_are_the_references_with_state_pools_parts_and_slot_reuse(mcfg, engine, served, prompts, arch):
    """One engine and one server, built once (the workers of a run share no
    fixture): the model's own ``forward`` is the reference's logits; the
    served streams are the reference's across prefill in one chunk and in
    several, last chunks of one and two rows and slot reuse; the state pools
    stand beside the paged pools; the gauge, the phase's attr and the parts;
    a slot used again serves the same tokens; migration is refused by name."""
    ids = jnp.asarray(np.random.default_rng(1).integers(0, 96, (2, 21)).astype(np.int32))
    lg = jax.jit(functools.partial(m.forward, mcfg))(engine.params, ids)
    ref = jax.jit(jax.vmap(lambda i: reference.logits(engine.params, i, arch)))(ids)
    assert lg.shape == (2, 21, 96) and float(jnp.std(ref)) > 0.5 and float(jnp.abs(lg - ref).max()) <= 2e-4
    srv, reqs = served
    assert [k.holds(srv.family) for k in STATE_KINDS] == [True, False, False, False]   # recurrent; no carried rows, rings or latent pool
    for r, p in zip(reqs, prompts):      # 9 requests through 3 slots: every slot is used again, from zeros
        assert r.status == "finished" and len(r.tokens) == 12
        assert float(_gaps(engine.params, p, r.tokens, arch).max()) <= GAP_TOL, len(p)
    # -- the pools, the gauge, the phase
    ds = srv.decode_set
    assert smodel.pool_layers(srv.family) == (2, 0, 6) and ds.n_layer == 2
    assert ds.cache.k.shape == (2, 64, 2, 4, 16) and ds.cache.win_k is None
    lin, conv, by = ds.cache.rec, ds.cache.conv, ds.cache_bytes()
    assert lin.shape == (6, 3, 4, 16, 16) and lin.dtype == jnp.float32 and conv.shape == (6, 3, 3, 2 * 32 + 64)
    assert len(jax.tree.leaves(ds.cache)) == 4 and by["lin_state"] == 6 * 3 * 4 * 16 * 16 * 4 and by["carry"] == 0
    assert srv.metrics.gauge("serving_lin_state_bytes", "").value() == by["lin_state"]
    assert srv.metrics.gauge("serving_kv_bytes", "", labelnames=("class",)).value(**{"class": "state"}) == by["state"]
    phase = [p for p in spans.phases() if p[0] == "ds.init.programs"][-1]
    assert phase[3]["lin_state_bytes"] == by["lin_state"] and "state=" in phase[3]["kv_bytes"]
    assert smodel._kv_homes(srv.family) == [(False, 0), (False, 1), (False, 2), (False, 0),
                                            (False, 3), (False, 4), (False, 5), (False, 1)]
    # -- every product of the served programs has a part, and the rule has its own
    whole = engine.serve(dict(SERVING, prefill_chunk_tokens=0))      # the whole-prompt program: only where nothing chunks
    whole._ensure_compiled()
    for name in ("jit_decode_fn", "jit_chunk_decode_fn", "jit_prefill_fn"):
        table = parts.tables()[name]
        got = {e.part for e in table.values()}
        assert {"lin.proj", "lin.scan", "attn.core", "moe.route", "moe.experts"} <= got, name
        assert all(e.part for e in table.values() if e.has_dot), name
    assert parts.KERNEL_FILES["ops/pallas/gated_delta.py"] == "lin.scan"
    # -- a slot that is used again (it holds the first pass's states and convolution rows) serves the same tokens
    again = [srv.submit(p, max_new_tokens=12, seed=i) for i, p in enumerate(prompts[:4])]
    srv.run()
    assert [list(r.tokens) for r in again] == [list(r.tokens) for r in reqs[:4]]
    with pytest.raises(ValueError, match="session migration is not available for a model with recurrent state.*matrix state"):
        srv._ensure_migration_programs()
    srv.drain(0.0)
    srv.check_no_leaks()


# -- the shares add up ----------------------------------------------------------

def test_the_eight_shares_routed_parts_and_the_gated_shared_expert_once_add_up_to_the_uncut_layer():
    E, F, N, k, T = 32, 16, 64, 10, 48
    ks = jax.random.split(jax.random.PRNGKey(5), 9)
    w = lambda key, s, std=0.3: jax.random.normal(key, s, jnp.float32) * std  # noqa: E731
    lp = {"router": w(ks[0], (E, N), 1.0), "bias": jnp.zeros((N,)), "shared_gate": w(ks[8], (E, 1)),
          "experts": {"w_gate": w(ks[1], (N, E, F)), "w_up": w(ks[2], (N, E, F)), "w_down": w(ks[3], (N, F, E))},
          "shared": {"w_gate": w(ks[4], (E, F)), "w_up": w(ks[5], (E, F)), "w_down": w(ks[6], (F, E))}}
    u = w(ks[7], (T, E), 1.0)
    uncut = reference.Arch.from_config(dict(CFG, num_experts=N, published={"num_experts": N}, expert_share={"chips": 1, "index": 0},
                                            num_experts_per_tok=k))
    whole, _ = reference._experts(lp, u, uncut, "")
    sh = lp["shared"]
    shared = es.gated_ffn(u, sh["w_gate"], sh["w_up"], sh["w_down"]) * jax.nn.sigmoid(u @ lp["shared_gate"])
    def routed(i, experts):      # share i's result less the shared expert's part, and its hits
        y, counts = es.expert_share_layer(dict(lp, experts=experts), u, es.ExpertShare(N, 8, i), k, 1.0, True, scoring="softmax")
        return y - shared, counts.sum()

    by_share = jax.tree.map(lambda x: x.reshape(8, 8, *x.shape[1:]), lp["experts"])
    ys, hs = jax.jit(jax.vmap(routed))(jnp.arange(8), by_share)      # one program, a share a row
    total, hits = shared + ys.sum(0), int(hs.sum())
    assert hits == T * k                                       # every pair is some share's, once: the counts are HITS
    assert float(jnp.abs(total - whole).max()) <= 1e-4 * float(jnp.abs(whole).max())


# -- the refusals ----------------------------------------------------------------

def test_the_seven_mechanisms_that_know_pages_only_are_refused_by_name(engine):
    """Prefix cache, int8 pages, tp > 1, disaggregation and speculation here,
    the host tier below; migration on a live server above (seven in all)."""
    from deepspeed_tpu.runtime.config import ServingConfig

    for section, what in [
        ({"prefix_cache": {"enabled": True}}, "serving.prefix_cache"),
        ({"kv_cache_dtype": "int8"}, "serving.kv_cache_dtype=int8"),
        ({"placement": {"tp": 2}}, "serving.placement.tp > 1"),
        ({"placement": {"disaggregate": True}}, "serving.placement.disaggregate"),
        ({"speculative": {"enabled": True, "k": 3, "ngram": 2}}, "serving.speculative"),
    ]:
        with pytest.raises(ValueError, match="recurrent state") as e:
            engine.serve(dict(SERVING, **section))
        assert what in str(e.value) and "Qwen3NextConfig" in str(e.value)
        assert "matrix state a value head (0.0 MB a slot and layer)" in str(e.value)      # 4 x 16 x 16 float32 here; 2.1 MB published
    cfg = ServingConfig.from_dict(dict(SERVING))
    cfg.tiering.enabled = True
    with pytest.raises(ValueError, match="serving.tiering"):
        engine.serve(cfg)


def test_the_published_state_is_two_megabytes_a_slot_and_layer():
    fam = m.Qwen3NextConfig().serving_family()
    assert fam.lin_state == (32, 128, 128) and 4 * int(np.prod(fam.lin_state)) == 2_097_152
    assert fam.lin_conv == (4, 8192) and fam.kinds[:4] == ("lin", "lin", "lin", "attn") and len(fam.kinds) == 48


# -- the kernels under the block --------------------------------------------------

def test_the_delta_kernels_interpreted_inside_the_block_give_what_the_lax_forms_give():
    """Heads of 128 x 128, so that the kernels take them: ``_lin_block`` over a
    mixed call's rows (a chunk of 8 rows of one slot, 3 of them padding, and a
    row for each of 3 slots, one idle) with the chunk and step entries of
    ``ops/pallas/gated_delta.py`` interpreted, against the lax forms."""
    wide = dict(CFG, num_hidden_layers=4, linear_num_key_heads=1, linear_num_value_heads=2,
                linear_key_head_dim=128, linear_value_head_dim=128)
    outs = []
    one_layer = m.Qwen3NextConfig.from_dict(dict(wide, num_hidden_layers=1))
    lp = jax.jit(lambda k: m.init_params(one_layer, k, jnp.float32))(jax.random.PRNGKey(3))["layers"][0]
    for impl in ("jnp", "interpret"):
        fam = m.Qwen3NextConfig.from_dict(wide, lin_impl=impl).serving_family()
        rng = np.random.default_rng(7)
        state = Cache(None, rec=jnp.asarray(rng.normal(size=(3, 3, *fam.lin_state)), jnp.float32),
                      conv=jnp.asarray(rng.normal(size=(3, 3, 3, fam.lin_conv[1])), jnp.float32))
        h = jnp.asarray(rng.normal(size=(1, 8 + 3, 64)), jnp.float32)
        block = jax.jit(lambda h, state: smodel._lin_block(
            fam, lp, h, state, 1, 8, (jnp.int32(2), jnp.int32(16), jnp.int32(5)), jnp.array([True, False, True])))
        outs.append(block(h, state))
    (a0, (*_, lin0, conv0, _)), (a1, (*_, lin1, conv1, _)) = outs
    assert float(jnp.abs(a0 - a1).max()) <= 1e-5 and float(jnp.abs(lin0 - lin1).max()) <= 1e-5
    assert bool((conv0 == conv1).all()) and float(jnp.abs(a0).max()) > 1e-3
