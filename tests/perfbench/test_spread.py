"""``perfbench/tools/spread.py`` on five small dumps of the document cell
(``tests/perfbench/data/spread``; ``make_spread_dumps.py`` beside them says how
they were cut from chip dumps of PR 28's second set, taken before the runner
stepped prefills past the close): three sound runs, one whose every step is 8%
longer, one whose window opened 1.3 s late."""

import os
import random
import statistics

import pytest

from perfbench.tools import spread

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data", "spread")


@pytest.fixture(scope="module")
def res():
    return spread.analyse(spread.collect([DATA]))


def by_name(res):
    return {r["run"][:-len(".json")]: r for r in res["rows"]}


@pytest.mark.parametrize("n", range(4, 13))
def test_quartile_weights_are_the_quartile_distance(n):
    v = sorted(random.Random(n).uniform(0, 1) for _ in range(n))
    q = statistics.quantiles(v, n=4)
    assert sum(w * x for w, x in zip(spread.quartile_weights(n), v)) == pytest.approx(q[2] - q[0], abs=1e-12)


@pytest.mark.parametrize("key", ["terms", "terms_trimmed", "terms_outside_mode"])
def test_the_three_terms_add_up_to_the_sets_spread(res, key):
    t = res[key]
    assert t["mode"] + t["phase"] + t["rest"] == pytest.approx(t["spread"], abs=1e-12)
    assert res["terms"]["spread"] == pytest.approx(res["spread"]) and res["terms_trimmed"]["spread"] == pytest.approx(res["trimmed_spread"])


def test_every_runs_terms_add_up_to_its_distance_from_the_median_run(res):
    rows = by_name(res)
    ref = rows[res["median_run"][:-len(".json")]]
    assert max(abs(ref[k]) for k in ("mode", "phase", "rest")) < 1e-9
    for r in rows.values():
        assert r["mode"] + r["phase"] + r["rest"] == pytest.approx(r["reading"] - ref["reading"], abs=1e-9)
        assert r["recounted"] == pytest.approx(r["reading"], rel=1e-9)


def test_the_mode_run_is_named_and_its_loss_is_filed_under_mode(res):
    rows = by_name(res)
    assert res["mode_runs"] == ["mode.json"] and res["left_out"] == ["mode.json"]
    m = rows["mode"]
    assert m["decode_ratio"] == pytest.approx(1.08, abs=0.005)
    assert m["mode"] < -80 and abs(m["rest"]) < 1          # tokens/s of about 1 260
    # 8% on every step is 7.4% of the rate; the rest of what it lost is the two
    # prompts its earlier close left in flight, which the metric credits nothing
    assert m["mode"] / rows[res["median_run"][:-5]]["reading"] == pytest.approx(1 / 1.08 - 1, abs=0.003)
    assert len(m["in_flight_at_close"]) == 2 and m["phase"] < -20
    # without it the mode term is gone
    assert abs(res["terms_outside_mode"]["mode"]) < 0.005 < 0.03 < res["terms"]["mode"]


def test_the_late_window_is_a_phase_shift_and_nothing_else(res):
    rows = by_name(res)
    p = rows["phase"]
    others = [rows[k] for k in ("sound-a", "sound-b", "sound-c")]
    assert p["open_pos"] - max(r["open_pos"] for r in others) > 15      # steps further into the cycle
    assert p["first_admitted"] > others[0]["first_admitted"] == others[1]["first_admitted"]
    assert abs(p["mode"]) < 3 and abs(p["rest"]) < 1 and p["stalls"] == []
    # what it read over the others is the prompt their close left in flight and its own did not
    assert p["phase"] > 20 and p["in_flight_at_close"] == [] and all(r["in_flight_at_close"] for r in others)
    assert res["terms_outside_mode"]["phase"] > 0.8 * res["terms_outside_mode"]["spread"]
    assert p["decode_ratio"] == pytest.approx(1.0, abs=0.01)


def test_steps_that_do_not_line_up_get_rows_and_no_terms():
    runs = spread.collect([DATA])
    runs[1].gen[5] += 1      # as in an open loop: another request at another step
    out = spread.analyse(runs)
    assert out["terms"] is None and len(out["rows"]) == 5 and out["spread"] > 0
    assert "no terms" in spread._fmt(out)


def test_the_table_prints(res, capsys):
    assert spread.main([DATA]) == 0
    text = capsys.readouterr().out
    assert "mode runs: mode.json" in text and text.count("\n") >= 9
