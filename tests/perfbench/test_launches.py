"""perfbench/launches.py and its two readers: the rows and the quantiles of a
hand-built trace by hand arithmetic, what a trace of the parent (no launch
numbers) gives, nothing, the file recorded on the v5e with the change
(tests/perfbench/data/launches.xplane.pb: a tiny GPT-2 served a step ahead,
perfbench/tools/record_launches_fixture.py), and the six entries that read
them (five of PR 55; the chunk program with no decode row since PR 59)."""

import os

import pytest

from perfbench import launches, program_spans, xplane
from perfbench.context import Context
from perfbench.manifest import Manifest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
MS = 1_000_000          # a hand-built trace is written in milliseconds
BEHIND = 2 * MS         # how far its device's clock runs behind the host's
LATENCY = MS // 10      # from the runtime's launch to the program's start on an idle device: what the offset misses
PERIOD = 20 * MS


class Built:
    """A served window by hand, on the host's clock: every launch is asked for
    inside its leaf and made by the runtime 0.3 ms later, after the leaf has
    closed and, after a chunk launched alone, inside the NEXT launch's leaf."""

    def __init__(self):
        self.dev = xplane.DeviceTrace("/device:TPU:0")
        self.loaded = launches.Loaded(xplane.Trace([self.dev], [], []))
        self.free = 0        # when the device has run what it was given (host clock)
        self.number = 0
        self.span("perfbench.window", 0, 1000 * MS)

    def span(self, name, s, e, **attrs):
        self.loaded.spans.append((name, s, e, attrs))
        self.loaded.trace.host_spans.append((name, s, e))

    def launch(self, name, leaf, kind, rows, tokens, module, device_ms, numbered=True, **more):
        """One call: the leaf ``[s, e]``, asked for 0.1 ms into it, launched 0.3
        ms after that, started ``LATENCY`` later or when the device is free.
        → (its number, its true end on the host's clock)."""
        s, e = leaf
        self.number += 1
        attrs = dict(launch=self.number, kind=kind, rows=rows, tokens=tokens) if numbered else {}
        self.span(name, s, e, **attrs, **more)
        call = (0, 0, 100 + self.number)
        asked, launched = s + MS // 10, s + 4 * MS // 10
        start = max(launched + LATENCY, self.free)
        self.free = start + device_ms * MS
        self.loaded.asked[call] = asked
        self.loaded.trace.launches[call] = launched
        self.dev.modules.append((module, start - BEHIND, self.free - BEHIND))
        self.dev.module_calls.append(call)
        return self.number, self.free


def built(steps=18, numbered=True):
    """A prefill waited for where it is launched, then ``steps`` steps a step
    ahead, 20 ms apart: the even ones plain (8 ms), the odd ones a chunk alone
    (5 ms) and the mixed program behind it (12 ms) whose chunk is its
    prompt's last; every fourth step a whole prefill (4 ms) is launched ahead
    of the plain step and its token left on the slot. Each step is read in the
    call after it, 1.3 ms into that call or when what its fetch waits for has
    ended and 0.2 ms more."""
    b = Built()
    n, end = b.launch("ds.serve.launch", (10 * MS, 10 * MS + 3 * MS // 10), "prefill", 0, 96, "jit_prefill_fn(1)", 10,
                      numbered)
    b.span("ds.serve.prefill.wait", 10 * MS + 4 * MS // 10, end + 4 * MS // 10, **({"launch": n} if numbered else {}))
    before = None       # (the step in flight's number, its true end, whether its chunk was a prompt's last)
    for k in range(steps + 1):
        t = 100 * MS + k * PERIOD
        late = None
        if k < steps:
            if k % 2 == 0:
                if k % 4 == 0:
                    late = b.launch("ds.serve.launch", (t - 6 * MS // 10, t - 4 * MS // 10), "prefill", 0, 64,
                                    "jit_prefill_fn(1)", 4, numbered)
                now = (*b.launch("ds.serve.decode.dispatch", (t, t + 4 * MS // 10), "plain", 8, 0, "jit_decode_fn(2)", 8,
                                 numbered, active=8, ahead=1), False)
            else:
                b.span("ds.serve.decode.dispatch", t, t + MS, active=8, ahead=1)
                b.launch("ds.serve.launch", (t + MS // 10, t + 3 * MS // 10), "chunk", 0, 128, "jit_chunk_decode_fn(3)", 5,
                         numbered)
                now = (*b.launch("ds.serve.launch", (t + 4 * MS // 10, t + 7 * MS // 10), "mixed", 8, 100,
                                 "jit_chunk_decode_fn(3)", 12, numbered), True)
        if before is not None:
            n, end, last = before
            got = max(t + 13 * MS // 10, max(end, late[1] if late else 0) + 2 * MS // 10)
            b.span("ds.serve.decode.wait", t + 11 * MS // 10, got, **({"flight": n} if numbered else {}))
            firsts = ([n] if last else []) + ([late[0]] if late else [])
            more = {"firsts": ",".join(map(str, firsts))} if firsts and numbered else {}
            b.span("ds.serve.emit", got, got + 2 * MS // 10, tokens=8, **({"flight": n} if numbered else {}), **more)
        before = now
    return b.loaded


def ctx_for(monkeypatch, loaded, tmp_path, trace=True):
    """A run's context whose trace directory holds ``loaded`` (the file is
    not read: ``launches.load`` is stood in for)."""
    ctx = Context(cell={"name": "no-such-cell"}, config={}, traffic={}, chips=1, peak=None)
    ctx.trace = object() if trace else None
    monkeypatch.setattr(program_spans, "trace_dir", lambda cell: str(tmp_path))
    monkeypatch.setattr(launches, "load", lambda path: loaded)
    return ctx


def reader(name):
    return Manifest(REPO).reader(name)


# -- a built trace, by hand arithmetic ----------------------------------------------

def test_the_rows_of_a_built_trace_are_joined_by_the_asking_event_not_by_where_the_launch_lies():
    loaded = built()
    rows, stats = launches.rows_of(loaded)
    # 1 prefill + 9 plain + 9 x (chunk, mixed) + 5 late prefills (steps 0, 4, 8, 12, 16)
    assert len(rows) == 33 == stats["leaves"] and stats["programs"] == stats["joined"] == 33 and stats["doubled"] == 0
    assert [r.number for r in rows] == list(range(1, 34))
    kinds = [r.kind for r in rows]
    assert kinds[:6] == ["prefill", "prefill", "plain", "chunk", "mixed", "plain"]
    assert {k: kinds.count(k) for k in set(kinds)} == {"prefill": 6, "plain": 9, "chunk": 9, "mixed": 9}
    # the offset is read from a program that started on an idle device: short of the true 2 ms by the launch latency
    assert stats["offset_ns"] == BEHIND - LATENCY
    by = {r.number: r for r in rows}
    chunk, mixed = by[4], by[5]          # step 1's two calls, at 120 ms
    assert (chunk.kind, chunk.module, chunk.rows, chunk.tokens) == ("chunk", "jit_chunk_decode_fn(3)", 0, 128)
    assert (mixed.kind, mixed.module, mixed.rows, mixed.tokens) == ("mixed", "jit_chunk_decode_fn(3)", 8, 100)
    # the chunk's launch (120.1 + 0.4) lies inside the MIXED call's leaf (120.4-120.7): by where it lies it would
    # be the mixed step's; by the event that asked for it (120.2) it is the chunk's
    assert mixed.asked <= chunk.launched <= mixed.asked + 3 * MS // 10 and chunk.launched == 120 * MS + 5 * MS // 10
    assert chunk.end - chunk.start == 5 * MS and mixed.end - mixed.start == 12 * MS
    assert mixed.start == chunk.end == 120 * MS + 6 * MS // 10 + 5 * MS - LATENCY      # queued behind the chunk
    # what reads them: the prefill's synchronous wait (its end), the steps' emits (their start) in the call after
    assert by[1].first == 10 * MS + 5 * MS // 10 + 10 * MS + 4 * MS // 10 and by[1].read is None
    assert mixed.read == mixed.first == 140 * MS + 13 * MS // 10 and chunk.read is None and chunk.first is None
    assert by[3].read == 120 * MS + 13 * MS // 10 and by[3].first is None                # step 0, plain
    assert by[2].kind == "prefill" and by[2].first is None      # step 0's late prefill: nothing was in flight to read it


def test_the_three_quantiles_of_a_built_trace(monkeypatch, tmp_path):
    ctx = ctx_for(monkeypatch, built(), tmp_path)
    time, hold = reader("launch_time"), reader("launch_hold")
    assert time.read(ctx, kind="plain", q=0.5) == pytest.approx(0.008)
    assert time.read(ctx, kind="mixed", q=0.5) == pytest.approx(0.012)
    assert time.read(ctx, kind="chunk", q=0.5) == pytest.approx(0.005)
    assert time.read(ctx, kind="prefill", q=0.5) is None          # six of them: under eight
    assert time.read(ctx, kind="verify", q=0.5) is None
    # a step's hold: its emit's start less its end on the device, which the offset puts 0.1 ms early.
    # plain step k (even) with no prefill ahead of it: 100 + 20k + 0.5 + 8 true end, read at 100 + 20(k+1) + 1.3
    #   -> 12.8 + 0.1 ms (steps 2, 6, 10, 14); behind a late prefill (4 ms) it ends 3.4 ms later -> 9.4 + 0.1 (0, 4, 8,
    #   12, 16). mixed step k (odd): ends at 100 + 20k + 0.6 + 5 + 12, read at 100 + 20(k+1) + 1.3 -> 3.7 + 0.1 ms where
    #   the next call has no late prefill (1, 5, 9, 13, and 17, which is read where nothing follows); where it has one
    #   (3, 7, 11, 15) the fetch waits for the prefill: it ends at 100 + 20(k+1) - 0.6 + 0.5 + 4 and the emit starts
    #   0.2 ms later -> 4.1 + 20 - 17.6 = 6.5 + 0.1 ms
    token = sorted([12.9] * 4 + [9.5] * 5 + [3.8] * 5 + [6.6] * 4)
    assert len(token) == 18 and hold.read(ctx, of="token", q=0.5) == pytest.approx((token[8] + token[9]) / 2 / 1e3)
    assert hold.read(ctx, of="token", q=0.5) == pytest.approx(0.00805)
    # first tokens: the synchronous one 0.4 + 0.1 ms; a mixed step's own: the step's hold; a late prefill's: its end
    # to the emit 0.2 ms behind it, 0.2 + 0.1 ms (steps 4, 8, 12, 16; step 0's is read by no emit: nothing was in flight)
    first = sorted([0.5] + [3.8] * 5 + [6.6] * 4 + [0.3] * 4)
    assert len(first) == 14 and hold.read(ctx, of="first", q=0.5) == pytest.approx((first[6] + first[7]) / 2 / 1e3)
    assert hold.read(ctx, of="first", q=0.5) == pytest.approx(0.0038)
    assert hold.read(ctx, of="first", q=1.0) == pytest.approx(0.0066)
    with pytest.raises(ValueError):
        hold.read(ctx, of="second", q=0.5)
    # only what ran in the window: with the window closed at 200 ms, five steps' programs are left, and no quantile
    loaded = built()
    loaded.spans[0] = ("perfbench.window", 0, 200 * MS, {})
    loaded.trace.host_spans[0] = ("perfbench.window", 0, 200 * MS)
    ctx = ctx_for(monkeypatch, loaded, tmp_path)
    assert len([r for r in launches.rows(ctx) if r.kind in launches.STEP_KINDS]) == 5
    assert time.read(ctx, kind="plain", q=0.5) is None and hold.read(ctx, of="token", q=0.5) is None


def test_under_eight_programs_give_nothing(monkeypatch, tmp_path):
    ctx = ctx_for(monkeypatch, built(steps=14), tmp_path)          # seven plain, seven mixed steps
    assert reader("launch_time").read(ctx, kind="plain", q=0.5) is None
    assert reader("launch_time").read(ctx, kind="mixed", q=0.5) is None
    assert reader("launch_hold").read(ctx, of="token", q=0.5) is not None      # fourteen steps read
    ctx = ctx_for(monkeypatch, built(steps=16), tmp_path)
    assert reader("launch_time").read(ctx, kind="plain", q=0.5) == pytest.approx(0.008)
    assert launches.quantile([1.0] * 7, 0.5) is None and launches.quantile([1.0] * 8, 0.5) == 1.0
    assert launches.quantile([1.0] * 2, 0.5, least=3) is None and launches.quantile([1.0, 3.0, 4.0], 0.5, least=3) == 3.0


def test_the_open_chat_cells_traced_window_holds_four_arrivals_in_every_run(table):
    """Why ``first_token_hold_p50_s.chat`` states ``least`` 3: the arrivals of a
    mix do not depend on ``--seed``, and the last ``trace_s`` seconds of the
    cell's window hold four of them, each a second or more before the close."""
    from perfbench import traffic

    m = table
    cell = m.cell("serve-xl-chat-open")
    seconds, trace_s = m.doc["run_seconds"], m.config(cell["config"])["trace_s"]
    due = [a.due_s for a in traffic.open_arrivals(m.traffic(cell["traffic"]), seconds - trace_s, seconds)]
    assert len(due) == 4 and seconds - trace_s + 1.0 < min(due) and max(due) < seconds - 0.9
    assert m.metric_spec("first_token_hold_p50_s.chat")["args"]["least"] == 3
    loaded_cell = m.cell("serve-xl-chat-loaded")
    assert len(traffic.open_arrivals(m.traffic(loaded_cell["traffic"]), seconds - trace_s, seconds)) >= 12


def test_a_run_without_a_trace_or_a_program_without_numbers_gives_nothing(monkeypatch, tmp_path):
    for ctx in (ctx_for(monkeypatch, built(), tmp_path, trace=False), ctx_for(monkeypatch, built(numbered=False), tmp_path)):
        assert launches.rows(ctx) is None
        assert reader("launch_time").read(ctx, kind="plain", q=0.5) is None
        assert reader("launch_hold").read(ctx, of="first", q=0.5) is None
    ctx = ctx_for(monkeypatch, built(), tmp_path)
    monkeypatch.setattr(program_spans, "trace_dir", lambda cell: str(tmp_path / "nothing-here"))   # no trace written
    assert launches.rows(ctx) is None and reader("launch_time").read(ctx, kind="plain", q=0.5) is None
    ctx = ctx_for(monkeypatch, built(), tmp_path)
    monkeypatch.setattr(program_spans, "program", lambda: None)     # a program without the spans module
    assert launches.rows(ctx) is None


# -- recorded traces ----------------------------------------------------------------

def test_a_trace_of_the_parent_gives_no_row_and_every_reader_nothing(monkeypatch, tmp_path):
    """ahead.xplane.pb: five calls under ``ds.serve.decode.dispatch`` leaves
    that carry no number (recorded before the numbers)."""
    loaded = launches.load(os.path.join(DATA, "ahead.xplane.pb"))
    assert len(loaded.trace.launches) == 5 and len(loaded.asked) == 5       # the runtime's side is there
    assert [s[0] for s in loaded.spans].count("ds.serve.decode.dispatch") == 5
    assert all(not s[3] for s in loaded.spans)
    rows, stats = launches.rows_of(loaded)
    assert rows == [] and stats["joined"] == 0 and stats["leaves"] == 0
    ctx = ctx_for(monkeypatch, loaded, tmp_path)
    assert launches.rows(ctx) is None
    for kind in ("plain", "mixed", "chunk", "prefill", "verify"):
        assert reader("launch_time").read(ctx, kind=kind, q=0.5) is None
    for of in ("token", "first"):
        assert reader("launch_hold").read(ctx, of=of, q=0.5) is None


def test_the_parents_trace_shows_why_the_join_goes_by_the_asking_event():
    """In ahead.xplane.pb the runtime launches call k on a thread of its own
    AFTER leaf k has closed, for the first call inside leaf k + 1: where the
    launch lies says nothing. The event that asked for it lies in leaf k."""
    loaded = launches.load(os.path.join(DATA, "ahead.xplane.pb"))
    leaves = sorted((s, e) for name, s, e, _ in loaded.spans if name == "ds.serve.decode.dispatch")
    calls = sorted(loaded.trace.launches, key=loaded.trace.launches.get)
    holds = lambda t: [k for k, (s, e) in enumerate(leaves) if s <= t <= e]     # noqa: E731
    assert [holds(loaded.asked[c]) for c in calls] == [[0], [1], [2], [3], [4]]
    assert holds(loaded.trace.launches[calls[0]]) == [1]
    assert [holds(loaded.trace.launches[c]) for c in calls[1:]] == [[], [], [], []]


def test_the_trace_recorded_with_the_change_gives_every_serving_program_its_row():
    path = os.path.join(DATA, "launches.xplane.pb")
    assert os.path.getsize(path) < 150_000
    loaded = launches.load(path)
    rows, stats = launches.rows_of(loaded)
    programs = [(m, call) for d in loaded.trace.devices for m, call in zip(d.modules, d.module_calls)]
    held = [m for m, call in programs if call in loaded.trace.launches]
    assert len(held) == len(programs) == 22 and all(launches.SERVING.search(m[0]) for m in held)
    # every program whose launch the trace holds is joined to exactly one number, and no number to two programs
    assert stats["programs"] == stats["joined"] == len(held) == stats["leaves"] == len(rows) and stats["doubled"] == 0
    assert [r.number for r in rows] == list(range(rows[0].number, rows[0].number + len(rows)))
    assert all(r.module is not None and r.asked <= r.launched and r.start < r.end for r in rows)
    # the kind is the launch's own, and one compiled program runs as two kinds
    by_kind = {}
    for r in rows:
        by_kind.setdefault(r.kind, set()).add(r.module.split("(")[0])
    assert by_kind == {"prefill": {"jit_prefill_fn"}, "plain": {"jit_decode_fn"},
                       "chunk": {"jit_chunk_decode_fn"}, "mixed": {"jit_chunk_decode_fn"}}
    assert all((r.rows > 0) == (r.kind in launches.STEP_KINDS) and (r.tokens > 0) == (r.kind != "plain") for r in rows)
    # the recorder's plan: prompts of 5, 20, 19 and 7 tokens in chunks of 8
    assert sum(r.tokens for r in rows) == 5 + 20 + 19 + 7
    steps = [r for r in rows if r.kind in launches.STEP_KINDS]
    assert all(r.read is not None and r.read >= r.end for r in steps)        # every step read, after it ended
    firsts = [r for r in rows if r.first is not None]
    assert sorted(r.kind for r in firsts) == ["chunk", "mixed", "prefill", "prefill"]
    assert all(r.first >= r.end for r in firsts)
    sync = rows[0]
    assert sync.kind == "prefill" and sync.first is not None and sync.read is None
    # a step launched ahead waited in the device's queue or started at once; its hold is under a millisecond or two
    assert all(0 <= h < 0.005 for h in launches.holds(rows, "token"))
    assert len(launches.in_window(rows, xplane.window_of(loaded.trace))) == len(rows)
    assert len(launches.summary(rows)) == 4 + 1 + 3      # a line a kind, and the first tokens' by the kind that sampled


# -- the five entries ---------------------------------------------------------------

ENTRIES = {
    "plain_step_p50_s.backlog": ("launch_time", {"kind": "plain", "q": 0.5}, "serve_tok_s", "serve programs"),
    "mixed_step_p50_s.backlog": ("launch_time", {"kind": "mixed", "q": 0.5}, "serve_tok_s", "serve programs"),
    "chunk_step_p50_s.backlog": ("launch_time", {"kind": "chunk", "q": 0.5}, "serve_tok_s", "serve programs"),
    # the open chat cell's arrivals are the mix's own, the same in every run: the traced 5 s hold FOUR first tokens
    "first_token_hold_p50_s.chat": ("launch_hold", {"of": "first", "q": 0.5, "least": 3}, "latency_per_token_p50_s",
                                    "serve scheduler"),
    "first_token_hold_p50_s.loaded": ("launch_hold", {"of": "first", "q": 0.5}, "completed_tok_s", "serve scheduler"),
    "token_hold_p50_s.chat": ("launch_hold", {"of": "token", "q": 0.5}, "latency_per_token_p50_s", "serve scheduler"),
}


# where a traced run of the builder's held fewer than 8 steps of a kind, the cell is NOT listed: a `null` in the ledger
# reads as a metric that an accepted PR did away with. What was read, in a traced 5 s (PERF.md sections 5 and 6):
TOO_FEW = {
    "plain_step_p50_s.backlog": {"serve-ms4-longdoc-backlog"},          # 3 plain steps (PR 55): chunks ride nearly every step
    "mixed_step_p50_s.backlog": {"serve-phi4flash-reason-backlog"},     # 7 mixed steps in PR 55's run, 11 and 11 in PR 59's two
    # no chunk program without a decode row in any of the six runs PR 59 made of the three reasoning cells (one prompt
    # in 64 steps or more: a second slot never prefills beside the first)
    "chunk_step_p50_s.backlog": {"serve-phi4flash-reason-backlog", "serve-zaya1-reason-backlog", "serve-qwen3next-reason-backlog"},
}


@pytest.mark.parametrize("name", sorted(ENTRIES))
def test_an_entry_names_its_reader_and_moves_a_metric_its_cells_report(table, name):
    m = table
    by_name = {e["name"]: e for e in m.doc["per_layer"]}
    rd, args, moves, layer = ENTRIES[name]
    mine = by_name[name]
    assert m.metric_spec(name) == {"reader": rd, "args": args}
    assert mine["moves"] == moves and mine["layer"].startswith(layer) and mine["source"] == "device_trace"
    assert (mine["unit"], mine["better"]) == ("s", "lower")
    assert mine["layer"] in {e["layer"] for e in m.doc["per_layer"] if e["name"] not in ENTRIES}
    for cell in mine["workloads"]:
        assert moves in {e["name"] for e in m.metrics_for(cell, "end_to_end")}
    if name.endswith(".backlog"):
        assert mine["workloads"] and not TOO_FEW[name] & set(mine["workloads"])
        # of the cells of dispatched_ahead_share.backlog: every cell that reports serve_tok_s (test_table.py)
        assert set(mine["workloads"]) <= set(by_name["dispatched_ahead_share.backlog"]["workloads"])
    else:
        assert mine["workloads"] == by_name[f"dispatched_ahead_share.{name.rsplit('.', 1)[1]}"]["workloads"]
