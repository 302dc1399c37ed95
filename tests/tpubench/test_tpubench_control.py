"""`correct` has been shown to fail: the float32 control in the engine's
place is refused by every data set's oracle, and a run whose timed path is
broken underneath (an answer altered where it is produced; the device
path's counter silent) prints `correct: false`."""

import numpy as np
import pytest

import control_f32
from bench_helpers import REPO, copy_benchmark, edit_json, run_harness
from tpubench.check import RTOL
from tpubench.spec import Spec

SPEC = Spec(REPO)
ROWS = 12_000


@pytest.mark.parametrize("dataset,fails", [
    ("tpch_lineitem", {"q1", "q6"}),
    # q1 and q2 sum integers, exact in any precision below 2^24
    ("h2o_g1", {"q3", "q5"}),
])
@pytest.mark.parametrize("seed", [3, 2147483659])
def test_the_float32_control_is_refused(dataset, fails, seed):
    gaps = control_f32.widest_gap(SPEC, dataset, seed, ROWS,
                                  control_f32.QUERIES[dataset])
    assert {t for t, (said, _) in gaps.items() if said is not None} == fails
    for template in fails:
        assert gaps[template][1] > 3 * RTOL, template
    assert all(gap == 0 for t, (_, gap) in gaps.items() if t not in fails)


def test_the_control_in_float64_is_the_oracle(monkeypatch):
    """The control differs from the oracle in its precision alone."""
    monkeypatch.setattr(control_f32, "F32", np.float64)
    for dataset, queries in control_f32.QUERIES.items():
        gaps = control_f32.widest_gap(SPEC, dataset, 5, ROWS, queries)
        assert all(said is None and gap <= RTOL
                   for said, gap in gaps.values()), (dataset, gaps)


def _scaled(result, factor):
    """The engine's result with its last column, a float aggregate in
    every template, off by `factor`."""
    result.columns[-1] = result.columns[-1] * factor
    return result


# every cell, by the call that hands the harness its answers
CELLS = [("q1_sf10_warm", "collect"), ("q1_sf10_cold", "collect"),
         ("h2o_1e7_groupby", "collect"), ("q6_sf10_streams", "ticket")]


@pytest.mark.parametrize("cell,seam", CELLS)
def test_an_answer_altered_where_it_is_produced_is_not_correct(
        capsys, tmp_path, monkeypatch, cell, seam):
    """The rest of a run, without the look for a chip, over an engine
    whose answers are off by 1e-6 (what a float32 path would give)."""
    import datafusion_tpu.exec.materialize as materialize
    import datafusion_tpu.serve as serve

    factor = 1 + 1e-6
    if seam == "collect":
        collect = materialize.collect
        monkeypatch.setattr(materialize, "collect",
                            lambda rel: _scaled(collect(rel), factor))
    else:
        result = serve.Ticket.result
        monkeypatch.setattr(
            serve.Ticket, "result",
            lambda self, timeout=None: _scaled(result(self, timeout), factor))
    code, line, _ = run_harness(
        capsys, copy_benchmark(tmp_path), "--workload", cell, "--seed", "7",
        "--seconds", "0.3", "--trace", "0", "--rehearse-rows", "20000")
    assert code == 0 and line["correct"] is False
    assert line["failed"] == line["attempted"] > 0
    gap = line["compared"]["worst_rel_gap"]
    assert gap["value"] == pytest.approx(1e-6, rel=1e-3) and gap["at_most"] == RTOL
    assert line["compared"]["wrong_answers"]["value"] == line["attempted"]
    assert line["compared"]["device.launches"]["value"] > 0


def test_a_window_that_never_reaches_the_device_is_not_correct(capsys, tmp_path):
    """Every answer right and the guard's `must_launch` counter silent
    (here: a counter this cell never bumps named in its place)."""
    root = copy_benchmark(tmp_path)
    edit_json(
        root + "/tpubench/configs/h2o_g1_1e7.json",
        lambda d: d["guarantees"]["device"].update(must_launch="join.build.dense"))
    code, line, _ = run_harness(
        capsys, root, "--workload", "h2o_1e7_groupby", "--seed", "7",
        "--seconds", "0.3", "--trace", "0", "--rehearse-rows", "20000")
    assert code == 0 and line["correct"] is False and line["failed"] == 0
    assert line["compared"]["join.build.dense"] == {"value": 0, "at_least": 1}
    assert line["compared"]["wrong_answers"]["value"] == 0
