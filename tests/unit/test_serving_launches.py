"""The launch number (docs/OBSERVABILITY.md, "Spans inside the program"): every
call of a compiled serving program takes the next number and its leaf span
carries it with what the program carries (``launch``, ``kind``, ``rows``,
``tokens``); the leaves that read a step program name it (``flight``), the leaf
that hands a first token out names the program that sampled it (``firsts``, or
a synchronous wait's ``launch``) and the request keeps that number
(``Request.first_launch``). On the CPU, at the tiny size and with the programs
``test_serving.py::TestStepAhead`` compiles."""

import collections

import pytest

from deepspeed_tpu.serving import RequestStatus

from .test_serving import (  # noqa: F401  (the two fixtures are used by name)
    FakeClock, _ahead_prompts, _ahead_srv, _play, _span_clock, _StallOnce, inference_engine, tiny_cfg,
)

pytestmark = pytest.mark.serving

LAUNCHERS = ("ds.serve.launch", "ds.serve.decode.dispatch")
SYNC_WAITS = ("ds.serve.prefill.wait", "ds.serve.chunk.wait", "ds.serve.handoff.wait")
# how a server is made and driven: a step ahead, the same loop held to depth 0 (nothing in flight at a read),
# a step ahead whose every step is read by settle() outside step(), the two servers that launch nothing ahead, and
# a server a step ahead that chunks no cold prompt: the only one with the whole-prompt program (ISSUE 63)
PATHS = {
    "ahead": {},
    "sync": {"ahead": False},
    "settle": {},
    "speculation": {"speculative": {"enabled": True, "k": 3, "ngram": 2}},
    "disaggregated": {"placement": {"disaggregate": True}},
    "whole": {"prefill_chunk_tokens": 0},
}


def _records(t0):
    from deepspeed_tpu.telemetry import spans

    return [r for r in spans.snapshot(since=t0) if r[0].startswith("ds.serve.")]


def _spied(srv):
    """The server with every compiled serving program wrapped: → the list of
    (program, the launch counter at the call) that the calls fill."""
    calls = []
    for name in ("_prefill_exec", "_decode_exec", "_verify_exec", "_chunk_exec"):
        exe = getattr(srv, name, None)
        if exe is not None:
            setattr(srv, name, lambda *a, _exe=exe, _name=name: calls.append((_name, srv._launches)) or _exe(*a))
    return calls


def _serve(engine, vocab, path):
    """Four prompts that end in ONE chunk, their first and last, with nothing
    in flight (5), a last chunk that rides (20), a last chunk alone in the
    same call (19: the second slot prefilling) and one chunk that rides (7),
    staggered over the calls; on the ``whole`` path four whole prefills, the
    first with nothing in flight and three behind a step. → (server, requests,
    the program calls, the ``ds.serve.*`` records)."""
    prompts = _ahead_prompts(vocab, (5, 20, 19, 7), seed=2)
    plan = [(0, prompts[0], dict(max_new_tokens=9, seed=0)), (2, prompts[1], dict(max_new_tokens=12, seed=1)),
            (2, prompts[2], dict(max_new_tokens=12, seed=2)), (4, prompts[3], dict(max_new_tokens=6, seed=3))]
    t0 = _span_clock()
    srv = _ahead_srv(engine, **PATHS[path])
    srv._ensure_compiled()
    calls = _spied(srv)
    reqs = _play(srv, plan, after=(lambda call: srv.settle()) if path == "settle" else None)
    return srv, reqs, calls, _records(t0)


def _launchers(recs):
    return [r[3] for r in recs if r[0] in LAUNCHERS and "launch" in r[3]]


@pytest.mark.parametrize("path", PATHS)
def test_every_call_of_a_serving_program_takes_exactly_one_number_strictly_rising(inference_engine, tiny_cfg, path):
    srv, reqs, calls, recs = _serve(inference_engine, tiny_cfg.vocab_size, path)
    assert all(r.status == RequestStatus.FINISHED for r in reqs)
    # at each call the counter stands one above where the call before left it: one number a call, taken before it
    assert [n for _, n in calls] == list(range(1, len(calls) + 1)) and srv._launches == len(calls)
    leaves = _launchers(recs)
    assert [a["launch"] for a in leaves] == [n for _, n in calls]
    kind_of = {"_prefill_exec": {"prefill"}, "_decode_exec": {"plain"}, "_verify_exec": {"verify"},
               "_chunk_exec": {"mixed", "chunk"}}
    for (name, _), a in zip(calls, leaves):
        assert a["kind"] in kind_of[name] and set(a) >= {"launch", "kind", "rows", "tokens"}
        assert (a["rows"] > 0) == (a["kind"] in ("plain", "mixed", "verify"))
        assert (a["tokens"] > 0) == (a["kind"] in ("prefill", "mixed", "chunk"))
    # what the programs carried is what was served: a row a token past the first, every prompt token once
    if path != "speculation":       # a verify row emits its accepted run
        assert sum(a["rows"] for a in leaves) == sum(len(r.tokens) - 1 for r in reqs) + srv.stats()["rows_dropped"]
    assert sum(a["tokens"] for a in leaves) == sum(r.prompt_len for r in reqs)
    # a launch leaf of its own only where its parent leaf is not the launch's: never around a plain or verify step
    own = [r[3]["kind"] for r in recs if r[0] == "ds.serve.launch"]
    assert set(own) <= {"prefill", "mixed", "chunk"} and len(own) == sum(n != "_decode_exec" and n != "_verify_exec"
                                                                        for n, _ in calls)
    srv.release_prefix_cache()
    srv.check_no_leaks()


@pytest.mark.parametrize("path", PATHS)
def test_a_steps_launch_wait_and_emit_leaves_carry_the_same_number(inference_engine, tiny_cfg, path):
    srv, reqs, calls, recs = _serve(inference_engine, tiny_cfg.vocab_size, path)
    steps = [a["launch"] for a in _launchers(recs) if a["kind"] in ("plain", "mixed", "verify")]
    waits = [r[3]["flight"] for r in recs if r[0] == "ds.serve.decode.wait"]
    emits = [r[3]["flight"] for r in recs if r[0] == "ds.serve.emit"]
    # every step program is read once, in the order of its launch, by one wait and one emit that name it
    assert steps == waits == emits and len(set(steps)) == len(steps) == srv.stats()["decode_steps"]
    by_start = sorted((r for r in recs if r[0] in LAUNCHERS + ("ds.serve.decode.wait",)), key=lambda r: r[1])
    order = [("L", r[3]["launch"]) if r[0] in LAUNCHERS else ("R", r[3]["flight"]) for r in by_start
             if r[0] == "ds.serve.decode.wait" or r[3].get("kind") in ("plain", "mixed", "verify")]
    nxt = dict(zip(steps, steps[1:]))
    behind = [k for k, (what, n) in enumerate(order) if what == "R" and ("L", nxt.get(n)) in order[:k]]
    if path in ("ahead", "whole"):
        # the step after it was launched before a step was read, but for the last of a burst
        assert len(behind) >= len(steps) - 3
        assert sum(r[3]["ahead"] for r in recs if r[0] == "ds.serve.decode.dispatch") == srv.stats()["steps_ahead"]
    elif path == "settle":
        # a call launches two steps and reads the first; settle() reads the second outside any call
        roots = [(r[1], r[2]) for r in recs if r[0] == "ds.serve.step"]
        outside = [r for r in recs if r[0] == "ds.serve.emit" and not any(a <= r[1] and r[2] <= b for a, b in roots)]
        assert len(outside) >= len(steps) // 2 - 1 and all("flight" in r[3] for r in outside)
    else:
        assert not behind       # nothing in flight at a read


@pytest.mark.parametrize("path", PATHS)
def test_each_first_token_is_named_once_and_the_request_keeps_the_number(inference_engine, tiny_cfg, path):
    srv, reqs, calls, recs = _serve(inference_engine, tiny_cfg.vocab_size, path)
    leaves = {a["launch"]: a for a in _launchers(recs)}
    firsts = [int(n) for r in recs if r[0] == "ds.serve.emit" for n in str(r[3].get("firsts", "")).split(",") if n]
    waited = [r[3]["launch"] for r in recs if r[0] in SYNC_WAITS and "launch" in r[3]]
    assert all("firsts" not in r[3] or r[3]["firsts"] for r in recs)          # an empty string never reaches a trace
    assert sorted(firsts + waited) == sorted(r.first_launch for r in reqs) and len(set(firsts + waited)) == len(reqs)
    for r in reqs:
        a = leaves[r.first_launch]
        # the program that sampled the first token carried the prompt's end: a whole prefill, or its last chunk
        assert a["kind"] in ("prefill", "mixed", "chunk")
        assert a["tokens"] == (r.prompt_len if a["kind"] == "prefill" else (r.prompt_len - 1) % 8 + 1)
    whole = [n for n, a in leaves.items() if a.get("whole")]
    # a chunk call says ``whole`` where it carried a prompt no longer than a chunk: its first chunk and its last
    assert sorted(whole) == sorted(r.first_launch for r in reqs if r.prompt_len <= 8 and path != "whole")
    if path == "ahead":
        # the empty server's first prompt's one chunk waited where it was launched; the three others were left on
        # the device: two in the step their last chunk rode (the flight's own number: a prompt of three chunks and
        # one of ONE), one on its slot (a last chunk launched alone behind a step in flight)
        assert len(waited) == 1 and len(firsts) == 3
        own = [r[3]["flight"] for r in recs if r[0] == "ds.serve.emit" and "firsts" in r[3]
               and str(r[3]["flight"]) in str(r[3]["firsts"]).split(",")]
        assert len(own) == 2 and all(leaves[n]["kind"] == "mixed" for n in own)
        assert sorted(leaves[n]["kind"] for n in firsts) == ["chunk", "mixed", "mixed"]
    if path == "whole":
        # the first whole prefill waited where it was launched, each of the others left its token on its slot
        assert len(waited) == 1 and len(firsts) == 3 and {leaves[n]["kind"] for n in firsts + waited} == {"prefill"}
        assert {r[0] for r in recs if r[0] in SYNC_WAITS and "launch" in r[3]} == {"ds.serve.prefill.wait"}
    if path in ("sync", "speculation", "disaggregated"):
        assert not firsts and len(waited) == len(reqs)      # nothing in flight: every first token is waited for
        want = {"disaggregated": {"ds.serve.handoff.wait"}}.get(path, {"ds.serve.chunk.wait"})
        assert {r[0] for r in recs if r[0] in SYNC_WAITS and "launch" in r[3]} == want


def test_a_slot_ended_while_its_first_token_lay_on_it_leaves_no_firsts_entry(inference_engine, tiny_cfg):
    """A prompt whose last chunk rides a step, and whose deadline passes before
    that step is read: the token is nobody's, and no leaf names its program.
    (A token left on a SLOT is read in the call that launched its program, and
    ``release_slot`` and ``drain`` read the step in flight first.)"""
    prompts = _ahead_prompts(tiny_cfg.vocab_size, (5, 20), seed=3)
    clock = FakeClock()
    srv = _ahead_srv(inference_engine, clock=clock)
    a = srv.submit(prompts[0], max_new_tokens=12, seed=0)
    srv.step()
    t0 = _span_clock()
    c = srv.submit(prompts[1], max_new_tokens=6, seed=2, deadline_s=5.0)
    while not any(r[0] == "ds.serve.launch" and r[3]["kind"] == "mixed" and r[3]["tokens"] == 4 for r in _records(t0)):
        srv.step()
    assert srv._flight.started is not None and srv._flight.started[1].request is c
    number = srv._flight.launch
    clock.t = 10.0
    srv.step()                       # evicts c at its deadline, then reads the step its last chunk rode
    recs = _records(t0)
    assert any(r[0] == "ds.serve.emit" and r[3]["flight"] == number for r in recs)
    assert not any("firsts" in r[3] for r in recs)
    assert c.status == RequestStatus.TRUNCATED and c.first_launch is None and not c.tokens
    srv.run()
    assert a.status == RequestStatus.FINISHED and a.first_launch == 1
    srv.check_no_leaks()


def test_a_retried_request_names_the_program_of_its_second_residency(inference_engine, tiny_cfg):
    (p,) = _ahead_prompts(tiny_cfg.vocab_size, (6,), seed=5)
    srv = _ahead_srv(inference_engine)
    srv.fault_injector = _StallOnce()
    t0 = _span_clock()
    (r,) = _play(srv, [(0, p, dict(max_new_tokens=8, seed=2))])
    assert r.retries == 1 and len(r.tokens) == 8
    prefills = [a["launch"] for a in _launchers(_records(t0)) if a.get("whole")]    # each residency's one chunk
    assert len(prefills) == 2 and r.first_launch == prefills[1]


@pytest.mark.parametrize("path", ["ahead", "sync"])
def test_a_plain_step_opens_no_new_span(inference_engine, tiny_cfg, path):
    """A server that only decodes: a call of ``step`` opens the six spans it
    opened before the launch number, and the number rides the dispatch leaf."""
    (p,) = _ahead_prompts(tiny_cfg.vocab_size, (5,), seed=4)
    srv = _ahead_srv(inference_engine, **PATHS[path])
    srv.submit(p, max_new_tokens=12, seed=0)
    for _ in range(3):
        srv.step()
    t0 = _span_clock()
    for _ in range(4):
        srv.step()
    names = collections.Counter(r[0] for r in _records(t0))
    assert names == {n: 4 for n in ("ds.serve.step", "ds.serve.admit", "ds.serve.decode.dispatch",
                                    "ds.serve.decode.wait", "ds.serve.emit", "ds.serve.housekeep")}
    for r in _records(t0):
        if r[0] == "ds.serve.decode.dispatch":
            assert r[3]["kind"] == "plain" and r[3]["rows"] == 1 and r[3]["tokens"] == 0 and r[3]["launch"] > 0
    srv.run()
    srv.check_no_leaks()


def test_the_attributes_reach_a_profilers_annotation_from_the_leafs_entry(inference_engine, tiny_cfg, monkeypatch):
    """With a profiler session open a span enters a ``TraceAnnotation``: the
    launch attributes are in its constructor's arguments (the leaf's entry),
    ``flight`` in the reading leaves', and ``firsts`` reaches the open
    annotation as metadata."""
    from deepspeed_tpu.telemetry import spans

    seen = []

    class Ann:
        def __init__(self, name, **attrs):
            self.rec = (name, dict(attrs), {})
            seen.append(self.rec)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

        def set_metadata(self, **attrs):
            self.rec[2].update(attrs)

    monkeypatch.setattr(spans, "_tracing", lambda: True)
    monkeypatch.setattr(spans, "TraceAnnotation", Ann)
    _serve(inference_engine, tiny_cfg.vocab_size, "ahead")
    entry = collections.defaultdict(list)
    for name, attrs, later in seen:
        entry[name].append((attrs, later))
    assert all(set(a) >= {"launch", "kind", "rows", "tokens"} for a, _ in entry["ds.serve.launch"])
    assert all(("launch" in a) == ("kind" in a) for a, _ in entry["ds.serve.decode.dispatch"])
    assert any("launch" in a for a, _ in entry["ds.serve.decode.dispatch"])
    assert all("flight" in a for name in ("ds.serve.decode.wait", "ds.serve.emit") for a, _ in entry[name])
    assert all("launch" in a for a, _ in entry["ds.serve.prefill.wait"])
    assert sum("firsts" in later for _, later in entry["ds.serve.emit"]) >= 2
