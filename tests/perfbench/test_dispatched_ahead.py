"""``dispatched_ahead_share.*``: the share of the window's decode dispatches that
were launched with the step before still in flight (``ds.serve.decode.dispatch``'s
``ahead``, the serving loop's own flag), read by ``span_flag_share`` for the three
lists of cells that ``decode_slots_active.*`` has; and what a program whose
dispatch spans carry no such flag (the parent of the PR that brought it) gives:
nothing."""

import collections
import os

import pytest

from perfbench.context import Context
from perfbench.manifest import Manifest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
ARGS = {"name": "ds.serve.decode.dispatch", "attr": "ahead"}


@pytest.fixture
def ring(monkeypatch):
    from deepspeed_tpu.telemetry import spans

    monkeypatch.setattr(spans, "_ring", collections.deque(maxlen=1024))
    return spans


def _ctx(window=(100.0, 200.0)):
    return Context(cell={"name": "no-such-cell"}, config={}, traffic={}, chips=1, peak=None, window=window)


def _dispatch(ring, t, **attrs):
    ring._ring.append(("ds.serve.decode.dispatch", t, t + 0.0004, {"active": 2, "attended": 10, "pages": 2, **attrs}))


def test_the_share_counts_the_dispatches_in_the_window_that_carry_the_flag(ring):
    rd = Manifest(REPO).reader("span_flag_share")
    _dispatch(ring, 90.0, ahead=0)                       # before the window: not counted
    for k, ahead in enumerate((0, 1, 1, 1, 0, 1, 1, 1)):
        _dispatch(ring, 100.0 + k, ahead=ahead)
    ring._ring.append(("ds.serve.emit", 101.0, 101.1, {"tokens": 2, "finished": 0}))
    _dispatch(ring, 250.0, ahead=1)                      # behind it
    assert rd.read(_ctx(), **ARGS) == pytest.approx(75.0)
    assert rd.read(_ctx((300.0, 400.0)), **ARGS) is None


def test_a_program_without_the_flag_gives_nothing(ring, monkeypatch):
    rd = Manifest(REPO).reader("span_flag_share")
    for k in range(4):
        _dispatch(ring, 100.0 + k)                       # the parent's dispatch leaf: no ``ahead``
    assert rd.read(_ctx(), **ARGS) is None
    from perfbench import program_spans

    monkeypatch.setattr(program_spans, "program", lambda: None)
    assert rd.read(_ctx(), **ARGS) is None


@pytest.mark.parametrize("group,moves", [("chat", "tpot_p95_s"), ("loaded", "completed_tok_s"),
                                         ("backlog", "serve_tok_s")])
def test_the_three_entries_list_the_cells_of_decode_slots_active_and_move_their_lists_metric(table, group, moves):
    m = table
    by_name = {e["name"]: e for e in m.doc["per_layer"]}
    mine = by_name[f"dispatched_ahead_share.{group}"]
    assert m.metric_spec(mine["name"]) == {"reader": "span_flag_share", "args": ARGS}
    # every cell whose dispatches are counted: since PR 59 the ZAYA and the Qwen3-Next cell too (their own tests had
    # pinned the exact set of entries those cells list; a cell's test now holds what the cell must keep, by name)
    assert mine["workloads"] == by_name[f"decode_slots_active.{group}"]["workloads"]
    assert {"serve-zaya1-reason-backlog", "serve-qwen3next-reason-backlog"} <= set(by_name["dispatched_ahead_share.backlog"]["workloads"])
    assert mine["moves"] == moves
    assert mine["layer"] == by_name[f"decode_slots_active.{group}"]["layer"] and mine["source"] == "program_counter"
    for cell in mine["workloads"]:
        assert moves in {e["name"] for e in m.metrics_for(cell, "end_to_end")}
