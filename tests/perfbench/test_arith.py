"""Metric arithmetic on hand-made stamps."""

import pytest

from perfbench import arith
from perfbench.arith import Rec


def rec(due, admit, first, emissions, plen=100, **kw):
    return Rec(due=due, prompt_len=plen, new_tokens=len(emissions), t_submit=due, t_admit=admit,
               t_first_token=first, t_emissions=list(emissions), n_tokens=len(emissions), **kw)


def test_quantile_is_linear_interpolation():
    assert arith.quantile([], 0.5) is None
    assert arith.quantile([3.0], 0.95) == 3.0
    assert arith.quantile([1, 2, 3, 4], 0.5) == 2.5
    assert arith.quantile([4, 1, 3, 2, 5], 0.5) == 3
    assert arith.quantile(list(range(101)), 0.95) == pytest.approx(95.0)


@pytest.mark.parametrize("admit,first,want_prompt", [
    (10.0, 12.0, 100.0),    # prefill wholly inside
    (8.0, 12.0, 50.0),      # half of the prefill before the window opens
    (18.0, 22.0, 50.0),     # half after it closes
    (5.0, 25.0, 50.0),      # the window inside a long prefill: 10 of 20 seconds
    (2.0, 4.0, 0.0),        # wholly before
    (30.0, 31.0, 0.0),      # wholly after
])
def test_prompt_tokens_are_prorated_at_the_edges(admit, first, want_prompt):
    r = rec(0.0, admit, first, [])
    assert arith.tokens_in_window([r], 10.0, 20.0) == pytest.approx(want_prompt)


def test_generated_tokens_count_by_their_own_stamp_never_by_request():
    # a request that straddles both edges gives only what fell inside
    r = rec(0.0, 1.0, 2.0, [2.0, 9.9, 10.0, 15.0, 19.999, 20.0, 25.0], plen=7)
    assert arith.tokens_in_window([r], 10.0, 20.0) == 3
    # an unfinished prefill is not known to have done anything
    r2 = rec(0.0, 15.0, None, [])
    assert arith.tokens_in_window([r2], 10.0, 20.0) == 0
    # never admitted
    assert arith.tokens_in_window([rec(0.0, None, None, [])], 10.0, 20.0) == 0


def test_pooled_gaps_pool_all_requests_and_use_the_closing_token():
    a = rec(0.0, 0.0, 1.0, [1.0, 1.1, 1.3, 1.6])
    b = rec(0.0, 0.0, 1.2, [1.2, 1.25, 2.5])
    gaps = arith.pooled_gaps([a, b], 1.05, 2.0)
    assert sorted(round(g, 3) for g in gaps) == [0.05, 0.1, 0.2, 0.3]   # b's last gap closes after the window
    assert arith.pooled_gaps([a, b], 0.0, 10.0).__len__() == 5
    assert arith.pooled_gaps([rec(0, 0, 1, [1.0])], 0, 10) == []


def test_latency_counts_from_the_due_time_not_the_submit():
    r = rec(10.0, 10.5, 11.0, [11.0, 12.0, 14.0])
    r.t_submit = 10.4   # the generator was late; the user has been waiting since 10.0
    assert arith.latency_per_token(r) == pytest.approx((14.0 - 10.0) / 3)
    assert arith.latency_per_token(rec(0, 0, None, [])) is None


def test_train_rate_counts_whole_steps_between_boundaries_inside():
    ends = [0.0, 1.0, 2.0, 3.0, 4.0, 5.5]
    rate, n = arith.train_rate(ends, 4096, 0.0, 5.0)
    assert n == 4 and rate == pytest.approx(4 * 4096 / 4.0)
    rate, n = arith.train_rate(ends, 4096, 0.5, 3.5)    # boundaries 1, 2, 3: two whole steps
    assert n == 2 and rate == pytest.approx(4096.0)
    assert arith.train_rate([0.0, 9.0], 4096, 1.0, 8.0) == (None, 0)


def test_overlap():
    assert arith.overlap(0, 10, 5, 20) == 5 and arith.overlap(0, 1, 2, 3) == 0 and arith.overlap(2, 3, 0, 10) == 1
