"""A prompt that fits one chunk takes ONE call of the chunk program (ISSUE 63).

An engine that chunks its cold prompts (``serving.prefill_chunk_tokens``)
prefills EVERY prompt with the chunk program and builds no whole-prompt
program; a prompt no longer than a chunk is one chunk, its first and its last
in one call, from the slot's zero state. Every served family at its tiny size
on the CPU, float32, against an engine with ``prefill_chunk_tokens`` 0 (the
whole-prompt program): a prompt shorter than a chunk, one of exactly a chunk
and one a token longer, alone on an idle server, admitted while another slot
decodes (its chunk rides that slot's step) and two at once (one rides, one is
a call with no decode row); the program set; the ``whole`` attribute; no page
leaked."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.inference.engine import InferenceEngine
from deepspeed_tpu.models import (
    exaone_moe, gpt2, ling3, longcat_flash, mistral4, phi4flash, qwen3_next, xing4, zaya,
)
from deepspeed_tpu.telemetry import spans

from .test_serving_exaone import CFG as KX_CFG
from .test_serving_ling3 import CFG as L3_CFG
from .test_serving_longcat_flash import CFG as LCF_CFG
from .test_serving_mistral4 import CFG as MS4_CFG
from .test_serving_phi4flash import CFG as P4F_CFG
from .test_serving_qwen3_next import CFG as Q3N_CFG
from .test_serving_xing4 import CFG as X4_CFG
from .test_zaya import CFG as ZAYA_CFG, seeded as zaya_seeded

CHUNK = 8
SERVING = dict(max_slots=4, page_size=4, num_pages=96, max_prompt_len=40, max_new_tokens=12,
               prefill_chunk_tokens=CHUNK, temperature=0.0)
NEW = 6
LENS = (5, CHUNK, CHUNK + 1)     # shorter than a chunk, exactly one, and a token more (two chunks, the last of one row)
FAMILIES = {
    "gpt2": None,
    "exaone_moe": (exaone_moe, exaone_moe.ExaoneMoEConfig, KX_CFG),
    "mistral4": (mistral4, mistral4.Mistral4Config, MS4_CFG),
    "longcat_flash": (longcat_flash, longcat_flash.LongcatFlashConfig, LCF_CFG),
    "phi4flash": (phi4flash, phi4flash.Phi4FlashConfig, P4F_CFG),
    "zaya": (zaya, zaya.ZayaConfig, ZAYA_CFG),
    "qwen3_next": (qwen3_next, qwen3_next.Qwen3NextConfig, Q3N_CFG),
    "xing4": (xing4, xing4.Xing4Config, X4_CFG),
    "ling3": (ling3, ling3.Ling3Config, L3_CFG),
}
# a family's cases stand side by side in the order of collection, so that under xdist a worker that is handed
# the next few cases builds few families: an engine and its two servers are most of a case's seconds
CASES = [pytest.param(f, how, n, id=f"{f}-{how}" + (f"-{n}" if n else ""))
         for f in FAMILIES for how, n in [("alone", m) for m in LENS] + [("beside", m) for m in LENS] + [("programs", 0)]]


@pytest.fixture(scope="module")
def servers():
    """family -> (the chunking server, the whole-prompt server, vocabulary):
    each built once and served from again and again, so a later case's prompt
    starts in a slot an earlier one left. ``get.drained(family)``: the two
    are drained, which is terminal; a later case builds them anew."""
    made = {}

    def get(family):
        if family not in made:
            if family == "gpt2":
                cfg = gpt2.get_config("gpt2-tiny", attn_impl="jnp")
                eng = InferenceEngine(gpt2.make_module(cfg), params=gpt2.init_params(cfg, jax.random.PRNGKey(0)),
                                      dtype=jnp.float32)
            else:
                mod, Config, raw = FAMILIES[family]
                cfg = Config.from_dict(raw)
                how = dict(params=zaya_seeded(cfg, 3)) if family == "zaya" else dict(seed=3)
                eng = deepspeed_tpu.init_inference(model=mod.make_module(cfg), dtype=jnp.float32, **how)
            made[family] = (eng.serve(dict(SERVING)), eng.serve(dict(SERVING, prefill_chunk_tokens=0)), cfg.vocab_size)
        return made[family]

    get.drained = made.pop
    return get


def _prompt(vocab, n, seed):
    return np.random.default_rng([seed, n]).integers(0, vocab, n).astype(np.int32)


def _alone(srv, prompts):
    """Each prompt through a server that holds nothing else."""
    out = []
    for i, p in enumerate(prompts):
        out.append(srv.submit(p, max_new_tokens=NEW, seed=i))
        srv.run()
    assert all(r.status == "finished" for r in out)
    return [list(r.tokens) for r in out]


def _launches(since):
    """(the ``ds.serve.launch`` leaves, the ``ds.serve.chunk`` leaves) since."""
    recs = spans.snapshot(since=since)
    return [r[3] for r in recs if r[0] == "ds.serve.launch"], [r[3] for r in recs if r[0] == "ds.serve.chunk"]


def _check_whole(launches, chunks, lens):
    """No whole-prompt program ran; a prompt of at most a chunk is ONE chunk
    call that says ``whole``, a longer one's calls say nothing."""
    assert launches and all(a["kind"] in ("chunk", "mixed") for a in launches)
    whole = [a for a in launches if a.get("whole")]
    assert sorted(a["tokens"] for a in whole) == sorted(n for n in lens if n <= CHUNK)
    assert all(a["whole"] == 1 for a in whole)
    assert len(launches) == sum(-(-n // CHUNK) for n in lens)
    assert sum(c["whole"] for c in chunks) == len(whole)
    assert sum(c["tokens"] for c in chunks) == sum(lens)


def _a_prompt_alone_on_an_idle_server_gets_the_whole_prompt_programs_tokens(srv, ref, vocab, n):
    prompts = [_prompt(vocab, n, 1)]
    t0 = spans._clock()
    got = _alone(srv, prompts)
    launches, chunks = _launches(t0)
    assert got == _alone(ref, prompts)
    _check_whole(launches, chunks, [n])
    assert all(a["kind"] == "chunk" and a["rows"] == 0 for a in launches)   # nothing decodes: nothing to ride
    srv.check_no_leaks()


def _prompts_admitted_while_a_slot_decodes_ride_its_step_or_run_beside_it(srv, ref, vocab, n):
    """One admitted beside a decoding slot rides that slot's step (the mixed
    call); of two admitted in one call one rides and one is a call with no
    decode row, in the same launch."""
    first, one, two_a, two_b = (_prompt(vocab, m, s) for m, s in ((19, 2), (n, 3), (n, 4), (n, 5)))
    want = _alone(ref, [first, one, two_a, two_b])
    a = srv.submit(first, max_new_tokens=12, seed=0)
    while len(a.tokens) < 2:
        srv.step()
    t0 = spans._clock()
    b = srv.submit(one, max_new_tokens=NEW, seed=1)
    srv.step()
    launches, chunks = _launches(t0)
    assert [(x["kind"], x["rows"], x["tokens"], x.get("whole", 0)) for x in launches] \
        == [("mixed", 1, min(n, CHUNK), int(n <= CHUNK))]
    assert [(c["chunks"], c["rode"], c["whole"]) for c in chunks] == [(0, 1, int(n <= CHUNK))]
    while not b.tokens:
        srv.step()
    assert not a.done      # still decoding: the next two find a step to ride
    t1 = spans._clock()
    c, d = (srv.submit(p, max_new_tokens=NEW, seed=i) for i, p in ((2, two_a), (3, two_b)))
    srv.step()
    launches, chunks = _launches(t1)
    assert [(x["kind"], x.get("whole", 0)) for x in launches] == [("chunk", int(n <= CHUNK)), ("mixed", int(n <= CHUNK))]
    assert [(ch["chunks"], ch["rode"], ch["whole"]) for ch in chunks] == [(1, 1, 2 * int(n <= CHUNK))]
    srv.run()
    every, _ = _launches(t0)
    assert all(x["kind"] in ("chunk", "mixed") for x in every)
    assert sum(1 for x in every if x.get("whole")) == 3 * int(n <= CHUNK)
    assert [list(r.tokens)[:NEW] for r in (a, b, c, d)] == [w[:NEW] for w in want]
    assert all(r.status == "finished" for r in (a, b, c, d))
    srv.check_no_leaks()


def _a_chunking_engine_builds_no_whole_prompt_program_and_a_drain_leaks_no_page(srv, ref, vocab):
    names = [name for name, _ in srv.executable_names()]
    assert names == ["serving_decode", "serving_chunk_prefill"]
    assert len(srv.executables) == srv.expected_executables == 2 and srv._prefill_exec is None
    # with no prefill_chunk_tokens the whole-prompt program is the cold prompts' only one, as before
    assert [name for name, _ in ref.executable_names()] == ["serving_prefill", "serving_decode"]
    assert len(ref.executables) == ref.expected_executables == 2 and ref._chunk_exec is None
    t0 = spans._clock()
    _alone(ref, [_prompt(vocab, 5, 9)])
    launches, chunks = _launches(t0)
    assert [(a["kind"], a["tokens"], "whole" in a) for a in launches] == [("prefill", 5, False)] and not chunks
    # a drain that finds one-chunk prompts in every state: decoding, its token on the device, prefilling, queued
    reqs = [srv.submit(_prompt(vocab, n, 7), max_new_tokens=NEW, seed=i) for i, n in enumerate((5, CHUNK, 5))]
    srv.step()
    reqs += [srv.submit(_prompt(vocab, n, 8), max_new_tokens=NEW, seed=i) for i, n in enumerate((CHUNK, 5))]
    srv.step()
    assert any(r.tokens for r in reqs) and not all(r.done for r in reqs)
    for s in (srv, ref):
        s.drain(0.0)
        s.check_no_leaks()
    assert all(r.done for r in reqs) and srv.submit(_prompt(vocab, 5, 9)).status == "rejected"


@pytest.mark.parametrize("family,how,n", CASES)
def test_a_prompt_that_fits_one_chunk_takes_one_call_of_the_chunk_program(servers, family, how, n):
    srv, ref, vocab = servers(family)
    if how == "alone":
        _a_prompt_alone_on_an_idle_server_gets_the_whole_prompt_programs_tokens(srv, ref, vocab, n)
    elif how == "beside":
        _prompts_admitted_while_a_slot_decodes_ride_its_step_or_run_beside_it(srv, ref, vocab, n)
    else:
        servers.drained(family)
        _a_chunking_engine_builds_no_whole_prompt_program_and_a_drain_leaks_no_page(srv, ref, vocab)
