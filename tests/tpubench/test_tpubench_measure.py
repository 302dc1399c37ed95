"""The builder's tool that runs cells as the driver does: the spread the
bounds are set from, and the rationing of chip time."""

import pytest

from tpubench import measure


def _run(cell, seed, value, wall_s=10.0):
    return {"cell": cell, "seed": seed, "rc": 0, "wall_s": wall_s,
            "line": {"metrics": {"rows_per_s": {"value": value, "unit": "rows/s"}}}}


@pytest.mark.parametrize("values,spread", [
    ([100, 100, 100], 0.0),
    # quartiles as `statistics.quantiles` (what the driver reads): of three
    # values the outer two, of six a quarter of the way to the outer ones
    ([98, 100, 102], 0.04),
    ([90, 100, 100, 100, 100, 130], 0.1),  # 97.5 and 107.5 over 100
    ([100, 100, 100, 100, 100, 100, 100, 130], 0.0),
    ([100], 0.0),
])
def test_spread_is_the_quartile_distance_over_the_median(values, spread):
    assert measure.quartile_spread(values) == pytest.approx(spread)


def test_summary_gives_each_set_and_the_shift_between_them():
    runs = [dict(_run("c", i, v), set=s) for i, (s, v) in enumerate(
        [(0, 98), (0, 100), (0, 102), (1, 103), (1, 105), (1, 107)])]
    runs.append({"set": 1, "line": None})  # a failed run holds no number
    m = measure.summarise(runs)["rows_per_s"]
    assert [s["median"] for s in m["sets"]] == [100, 105]
    assert [s["n"] for s in m["sets"]] == [3, 3]
    assert m["sets"][0]["spread"] == pytest.approx(0.04)
    assert m["shift"] == pytest.approx(0.05)


def test_no_run_is_started_that_the_budget_cannot_hold(monkeypatch, tmp_path, capsys):
    clock = [0.0]
    seeds = []

    def run_once(spec, cell, seed, seconds, trace, extra):
        clock[0] += 100.0
        seeds.append(seed)
        return _run(cell, seed, 1e7 + seed, wall_s=100.0)

    monkeypatch.setattr(measure, "run_once", run_once)
    monkeypatch.setattr(measure.time, "time", lambda: clock[0])
    monkeypatch.setattr(measure.Spec, "__init__", lambda self: self.__dict__.update(
        root=str(tmp_path), bench={"run_seconds": 51}, cell=lambda name: {}))
    code = measure.main(["--cells", "q6_sf10_streams", "--sets", "2", "--runs", "3",
                         "--first-seed", "21", "--budget-s", "450"])
    # runs end at 100 .. 400 s; a fifth would end at 500.  The second set
    # starts again from the first seed
    assert code == 0 and seeds == [21, 22, 23, 21]
    out = capsys.readouterr().out
    assert out.count("no time left") == 2 and "(n=3)" in out and "(n=1)" in out
