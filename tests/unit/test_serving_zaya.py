"""The ``zaya`` family through the paged programs at a small size on the CPU (2
layers, 4 query and 2 kv heads of 16, 4 experts one pick, page 4, chunk 8), in
float32: the served streams against the float32 reference's full forward
(``perfbench/reference_zaya.py``, which imports nothing from the model's
module), the rows an attention carries from call to call under its paged K and
V (the fourth kind of per-slot state), the spans and gauges, and the refusals."""

import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models import zaya as m
from deepspeed_tpu.serving import model as smodel
from deepspeed_tpu.serving.kv_cache import Cache
from deepspeed_tpu.telemetry import spans
from perfbench import reference_zaya as reference

from .test_zaya import CFG, seeded

SERVING = dict(max_slots=3, page_size=4, num_pages=96, max_prompt_len=40, max_new_tokens=40,
               prefill_chunk_tokens=8, temperature=0.0)
# ONE chunk, a prompt's first and last in one call (5, 8), chunk program with a last chunk that is
# not full (19 = 8 + 8 + 3, 33, 27) and one that is (40); seven requests in three slots: the later ones are
# admitted while others decode (the mixed step) and into slots a request has left; the last is the first again
PROMPTS = (5, 8, 19, 33, 40, 27, 5)
NEW = (12, 12, 40, 12, 12, 12, 12)      # 40 decode steps on carried rows for the third
# The reference sums in another order than the programs, both in float32: the served token is the
# reference's argmax but for a tie closer than this.
GAP_TOL = 1e-4


@pytest.fixture(scope="module")
def mcfg():
    return m.ZayaConfig.from_dict(CFG)


@pytest.fixture(scope="module")
def params(mcfg):
    return seeded(mcfg, 3)


@pytest.fixture(scope="module")
def engine(mcfg, params):
    return deepspeed_tpu.init_inference(model=m.make_module(mcfg), dtype=jnp.float32, params=params)


@pytest.fixture(scope="module")
def prompts():
    rng = np.random.default_rng(0)
    out = [rng.integers(0, CFG["vocab_size"], n).astype(np.int32) for n in PROMPTS]
    out[-1] = out[0].copy()
    return out


@pytest.fixture(scope="module")
def served(engine, prompts):
    t0 = spans._clock()      # not the last record's end: `since` is inclusive, and that record may be another server's emit
    srv = engine.serve(dict(SERVING))
    reqs = [srv.submit(p, max_new_tokens=n, seed=i) for i, (p, n) in enumerate(zip(prompts, NEW))]
    srv.run()
    return srv, reqs, spans.snapshot(since=t0)


def test_every_served_position_is_the_references_full_forward_and_the_mix_ran_every_program(mcfg, engine, served, prompts):
    """One case for the seven requests and for what the run left behind: under
    xdist a case may land on a worker of its own and serve the whole mix
    again for it."""
    srv, reqs, recs = served
    arch = reference.Arch.from_config(CFG)
    for r, p, n in zip(reqs, prompts, NEW):
        assert r.status == "finished" and len(r.tokens) == n
        ids = np.concatenate([p, np.asarray(r.tokens, np.int32)])
        padded = np.zeros((96,), np.int32)
        padded[: len(ids)] = ids
        gap, std, ties = reference.served_gaps(engine.params, jnp.asarray(padded), jnp.int32(len(p)), jnp.int32(len(ids)),
                                               arch=arch, rows=max(NEW))
        assert float(np.asarray(std)[:n].mean()) > 1.0 and ties.shape == (2, 96)
        assert float(np.asarray(gap).max()) <= GAP_TOL, (len(p), np.asarray(gap).max())
    assert list(reqs[0].tokens) == list(reqs[-1].tokens)        # slot reuse: the programs start a request from zeros
    chunks = [r[3] for r in recs if r[0] == "ds.serve.chunk"]
    assert sum(c["rode"] for c in chunks) > 0                    # a chunk rode a decode step: the mixed program
    assert sum(c["chunks"] + c["rode"] for c in chunks) == sum(-(-n // 8) for n in PROMPTS)
    assert srv.metrics.counter("serving_decode_steps_total", "").value() >= 40
    # the carried rows are a pool of their own, and the gauge and the phase say so
    ds, fam = srv.decode_set, mcfg.serving_family()
    assert fam.carry_width == 2 * 96 + 16
    carry_bytes = ds.cache_bytes()["carry"]
    assert ds.cache.rec is None and ds.cache.carry.shape == (2, 3, fam.carry_width)
    assert carry_bytes == 2 * 3 * fam.carry_width * 4 and ds.cache_bytes()["state"] == 0
    assert srv.metrics.gauge("serving_attn_carry_bytes", "").value() == carry_bytes
    assert srv.metrics.gauge("serving_kv_bytes", "", labelnames=("class",)).value(**{"class": "carry"}) == carry_bytes
    attrs = [p[3] for p in spans.phases() if p[0] == "ds.init.programs" and p[3].get("what") == "serving"][-1]
    assert attrs["carry_rows"] == carry_bytes and f"carry={carry_bytes}" in attrs["kv_bytes"]
    assert srv.metrics.gauge("serving_moe_experts_held", "").value() == 4
    # the expert layers report their loads: one pick a token, 2 layers, all held; the masked form off the TPU
    emits = [r[3] for r in recs if r[0] == "ds.serve.emit"]
    assert emits and all({"moe_pairs_held", "moe_pairs_routed", "moe_load_max", "moe_experts_hit", "moe_experts_streamed"}
                         <= set(a) for a in emits)
    for a in emits:
        assert a["moe_pairs_held"] == a["moe_pairs_routed"] and a["moe_pairs_routed"] % 2 == 0
        assert 1 <= a["moe_experts_hit"] <= 4 * 2 and a["moe_experts_streamed"] == 4 * 2
    with pytest.raises(ValueError, match="session migration is not available for a model with carried attention rows"):
        srv._ensure_migration_programs()      # the one refusal a server makes later: when the transport is first asked for
    srv.drain(0.0)
    srv.check_no_leaks()


def test_idle_slots_keep_their_rows_bitwise_and_a_request_starts_from_zeros_whatever_the_slot_held(mcfg, params):
    """The programs by hand: a decode step moves the rows of the slots that
    decode and no other's; a prompt's first chunk into a slot that holds
    another request's leavings reads zeros."""
    import functools

    import jax

    fam = mcfg.serving_family()
    page, W, slots = 4, 10, 3
    pools = lambda carry: Cache(*(jnp.zeros((2, 32, 2, page, 16), jnp.float32),) * 2, carry=carry)  # noqa: E731
    dirty = jnp.full((2, slots, fam.carry_width), 0.37, jnp.float32)
    prompt = np.random.default_rng(7).integers(0, CFG["vocab_size"], 11).astype(np.int32)
    pages = [7, 3, 9, 12]
    row = np.zeros((1, W), np.int32)
    row[0, : len(pages)] = pages
    chunk = jax.jit(functools.partial(smodel.paged_chunk_prefill, mcfg))
    key = jnp.zeros((2,), jnp.uint32)

    def prefill(carry):
        cache = pools(carry)
        for start in (0, 8):
            ids = np.zeros((1, 8), np.int32)
            seg = prompt[start: start + 8]
            ids[0, : len(seg)] = seg
            cache, _, _ = chunk(params, jnp.asarray(ids), jnp.int32(start), jnp.int32(11), cache,
                                jnp.asarray(row[0, start // page: start // page + 2]), jnp.asarray(row), key,
                                slot=jnp.int32(1))
        return cache

    cache = prefill(dirty)
    rows, clean = cache.carry, prefill(jnp.zeros_like(dirty)).carry
    np.testing.assert_array_equal(np.asarray(rows[:, 1]), np.asarray(clean[:, 1]))           # from zeros, not from 0.37
    assert np.all(np.asarray(rows[:, 0]) == np.float32(0.37)) and np.all(np.asarray(rows[:, 2]) == np.float32(0.37))
    # the whole-prompt program leaves the same rows at the prompt's TRUE length (11 of a 12-wide bucket)
    ids = np.zeros((1, 12), np.int32)
    ids[0, :11] = prompt
    out = jax.jit(functools.partial(smodel.paged_prefill, mcfg))(
        params, jnp.asarray(ids), jnp.int32(11), pools(dirty), jnp.asarray(row[0, :3]), key, slot=jnp.int32(1))
    np.testing.assert_allclose(np.asarray(out[0].carry[:, 1]), np.asarray(rows[:, 1]), atol=1e-6)
    # a decode step: slot 1 decodes, slots 0 and 2 are idle
    bt = np.zeros((slots, W), np.int32)
    bt[1, : len(pages)] = pages
    got = jax.jit(functools.partial(smodel.paged_decode_step, mcfg))(
        params, jnp.asarray([0, 17, 0], jnp.int32), jnp.asarray([0, 11, 0], jnp.int32), cache, jnp.asarray(bt),
        jnp.zeros((slots, 2), jnp.uint32))[0].carry
    assert np.array_equal(np.asarray(got[:, 0]), np.asarray(rows[:, 0])) and np.array_equal(np.asarray(got[:, 2]), np.asarray(rows[:, 2]))
    C = mcfg.latent
    np.testing.assert_array_equal(np.asarray(got[:, 1, C: 2 * C]), np.asarray(rows[:, 1, :C]))     # z_{t-1} moved to z_{t-2}'s place
    assert not np.array_equal(np.asarray(got[:, 1, :C]), np.asarray(rows[:, 1, :C]))


@pytest.mark.parametrize("section,what", [
    ({"prefix_cache": {"enabled": True}}, "serving.prefix_cache"),
    ({"tiering": {"enabled": True}}, "serving.tiering"),
    ({"speculative": {"enabled": True, "k": 2}}, "serving.speculative"),
    ({"kv_cache_dtype": "int8"}, "serving.kv_cache_dtype=int8"),
    ({"placement": {"tp": 2}}, "serving.placement.tp > 1"),
    ({"placement": {"disaggregate": True}}, "serving.placement.disaggregate"),
], ids=["prefix_cache", "tiering", "speculative", "int8", "tp", "disaggregate"])
def test_mechanisms_that_do_not_know_the_carried_rows_are_refused_by_name(mcfg, section, what):
    """``ServingEngine`` refuses from the model's config alone, before it
    looks at an engine's parameters: a stand-in engine does (a case here may
    land on a worker of its own, and a real engine is eight seconds). The
    seventh, session migration, is refused when the transport is first asked
    for: in the served case above."""
    from types import SimpleNamespace

    from deepspeed_tpu.runtime.config import ServingConfig
    from deepspeed_tpu.serving import ServingEngine

    cfg = ServingConfig.from_dict(dict(SERVING, **{k: v for k, v in section.items() if k != "tiering"}))
    if "tiering" in section:
        cfg.tiering.enabled = True
    with pytest.raises(ValueError, match="carried attention rows") as e:
        ServingEngine(SimpleNamespace(model_config=mcfg, dtype=jnp.float32), cfg)
    assert what in str(e.value)


def test_the_verify_step_refuses_a_family_that_carries_rows(mcfg):
    with pytest.raises(NotImplementedError, match="carry no rows"):
        smodel.paged_verify_step(mcfg, None, jnp.zeros((3, 2), jnp.int32), jnp.zeros((3,), jnp.int32),
                                 None, jnp.zeros((3, 4), jnp.int32))
