"""The generator offers the same load whatever the seed: equal counts and
equal sorted lengths in every block; the seed decides order, offsets, ids."""

import collections
import json
import os

import numpy as np
import pytest

from perfbench import traffic as tg

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
SEEDS = [0, 1, 7, 2**31 + 12345, 2**32 + 5]   # of the run (--seed), and of a mix's own schedule


def mix(name):
    with open(os.path.join(REPO, "perfbench", "traffic", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("seed", SEEDS)
def test_open_blocks_hold_the_same_multiset_whatever_the_schedule(seed):
    """The run's seed cannot reach the schedule at all (``open_arrivals`` takes
    none); a mix with another ``order_seed`` and ``arrival_seed`` still offers
    the same count and the same sorted lengths in every block."""
    tr = dict(mix("chat-open-r70"), order_seed=seed, arrival_seed=seed + 1)
    want = sorted((p, n) for p, n, _, _ in tg.block_multiset(tr))
    assert len(want) == round(tr["rate_rps"] * tr["block_s"])
    assert "seed" not in tg.open_arrivals.__code__.co_varnames and "seed" not in tg.backlog_cycle.__code__.co_varnames
    arr = tg.open_arrivals(tr, -10.0, 50.0)
    by_block = collections.defaultdict(list)
    for a in arr:
        by_block[a.block].append((a.prompt_len, a.new_tokens))
    assert sorted(by_block) == [-1, 0, 1, 2, 3, 4]
    for b, pairs in by_block.items():
        assert sorted(pairs) == want, f"block {b} of seed {seed}"
        assert all(b * 10 <= a.due_s < b * 10 + 10 for a in arr if a.block == b)
    assert [a.due_s for a in arr] == sorted(a.due_s for a in arr)


def test_seeds_differ_in_contents_never_in_what_is_offered_when():
    tr = mix("chat-open-r70")
    a = tg.open_arrivals(tr, -8.0, 40.0)
    b = tg.open_arrivals(tr, -8.0, 40.0)
    assert a == b   # same requests, same due times, same order: no seed reaches them
    ids_a = [tg.prompt_tokens(tr, 1, x, 50257) for x in a]
    ids_b = [tg.prompt_tokens(tr, 2**31 + 2, x, 50257) for x in b]
    assert all(len(x) == len(y) and not (x == y).all() for x, y in zip(ids_a, ids_b))
    gaps = np.diff([x.due_s for x in a])
    assert gaps.std() > 0.3 * gaps.mean()   # irregular as a Poisson process is, not a metronome
    blocks = [[(round(x.due_s - 10 * k, 6), x.prompt_len) for x in a if x.block == k] for k in range(4)]
    assert blocks[0] != blocks[1]           # each block has its own offsets and order
    assert [x.due_s for x in tg.open_arrivals(dict(tr, arrival_seed=1), -8.0, 40.0)] != [x.due_s for x in a]
    reordered = tg.open_arrivals(dict(tr, order_seed=1), -8.0, 40.0)
    assert [x.due_s for x in reordered] == [x.due_s for x in a]
    assert [x.prompt_len for x in reordered] != [x.prompt_len for x in a]
    whole = lambda arr: sum(x.prompt_len for x in arr if x.block >= 0)   # the ramp is part of a block
    assert whole(reordered) == whole(a)


@pytest.mark.parametrize("seed", SEEDS)
def test_same_seed_same_trace(seed):
    tr = mix("chat-open-r70")
    a = tg.open_arrivals(tr, -8.0, 30.0)
    b = tg.open_arrivals(tr, -8.0, 30.0)
    assert a == b
    ids_a = [tg.prompt_tokens(tr, seed, x, 50257) for x in a[:5]]
    ids_b = [tg.prompt_tokens(tr, seed, x, 50257) for x in b[:5]]
    for x, y, arr in zip(ids_a, ids_b, a):
        assert x.dtype == np.int32 and len(x) == arr.prompt_len and (x == y).all()
        assert 0 <= x.min() and x.max() < 50257


@pytest.mark.parametrize("seed", SEEDS)
def test_backlog_cycles_through_its_multiset(seed):
    tr = dict(mix("doc-backlog"), order_seed=seed)
    want = sorted(p for p, _, _, _ in tg.block_multiset(tr))
    n = len(want)
    assert n == tr["block_requests"]
    cyc = tg.backlog_cycle(tr)
    first = [next(cyc) for _ in range(n)]
    second = [next(cyc) for _ in range(n)]
    assert sorted(a.prompt_len for a in first) == want == sorted(a.prompt_len for a in second)
    assert [a.prompt_len for a in first] != [a.prompt_len for a in second]  # a new order each cycle
    again = tg.backlog_cycle(tr)
    assert [next(again).prompt_len for _ in range(n)] == [a.prompt_len for a in first]
    assert min(want) >= 512 and max(want) <= 960 and all(a.new_tokens == 64 for a in first)


def test_quantile_points_are_clipped_and_centred():
    pts = tg.quantile_points({"dist": "lognormal", "median": 128, "sigma": 0.9, "min": 32, "max": 512}, 9)
    assert pts == sorted(pts) and pts[0] >= 32 and pts[-1] <= 512 and pts[4] == 128
    assert tg.quantile_points({"dist": "const", "value": 64}, 3) == [64, 64, 64]
    assert tg.quantile_points({"dist": "uniform", "min": 0, "max": 10}, 5) == [1, 3, 5, 7, 9]
    with pytest.raises(ValueError):
        tg.quantile_points({"dist": "zipf"}, 3)


def test_components_share_a_block_and_prefix_groups_share_tokens():
    tr = {"loop": "open", "rate_rps": 1.0, "block_s": 10, "components": [
        {"share": 0.7, "prompt_len": {"dist": "const", "value": 100}, "new_tokens": {"dist": "const", "value": 8},
         "shared_prefix": {"tokens": 40, "groups": 2}},
        {"share": 0.3, "prompt_len": {"dist": "const", "value": 500}, "new_tokens": {"dist": "uniform", "min": 4, "max": 12}}]}
    ms = tg.block_multiset(tr)
    assert len(ms) == 10 and sum(1 for p, _, c, _ in ms if c == 0) == 7
    arr = tg.open_arrivals(tr, 0.0, 10.0)
    grouped = collections.defaultdict(list)
    for a in arr:
        if a.prefix_group >= 0:
            grouped[a.prefix_group].append(tg.prompt_tokens(tr, 5, a, 1000))
    assert set(grouped) == {0, 1}
    for ids in grouped.values():
        assert all((x[:40] == ids[0][:40]).all() for x in ids)
        assert not all((x[40:] == ids[0][40:]).all() for x in ids[1:])
    assert not (grouped[0][0][:40] == grouped[1][0][:40]).all()


def test_profile_moves_arrivals_not_their_number():
    base = mix("chat-open-r70")
    burst = dict(base, profile=[[0, 2, 3.0], [2, 10, 1.0]])
    for k in range(5):
        a = tg.open_block(base, k)
        b = tg.open_block(burst, k)
        assert len(a) == len(b)
        assert sorted(x.prompt_len for x in a) == sorted(x.prompt_len for x in b)
    early = sum(1 for k in range(200) for x in tg.open_block(burst, k) if x.due_s - 10 * k < 2.0)
    total = 200 * tg.block_count(burst)
    assert abs(early / total - 6.0 / 14.0) < 0.05  # 2 s at weight 3 of a mass of 6 + 8


def test_train_batches_are_fresh_and_seeded():
    a = tg.train_batch(2**31 + 9, 0, 4, 16, 50257)
    assert a.shape == (4, 16) and a.dtype == np.int32
    assert (a == tg.train_batch(2**31 + 9, 0, 4, 16, 50257)).all()
    assert not (a == tg.train_batch(2**31 + 9, 1, 4, 16, 50257)).all()
    assert not (a == tg.train_batch(3, 0, 4, 16, 50257)).all()


def test_offered_load_is_what_the_files_say():
    chat, doc = tg.offered(mix("chat-open-r70")), tg.offered(mix("doc-backlog"))
    assert chat["new_tokens"] == 64 * chat["requests"] and doc["new_tokens"] == 64 * doc["requests"]
    assert doc["prompt_tokens"] / doc["requests"] > 650
