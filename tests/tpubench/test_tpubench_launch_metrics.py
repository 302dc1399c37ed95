"""The five per-layer metrics that open the launch call
(`tpubench/metrics/launch_*`, `mesh_assemble_ms_per_query`): each on
hand-made runs, on a run with no query and on a program that keeps no such
timer or counter (an older engine under this benchmark); their entries in
`BENCHMARK.json`, found by name; a traced harness run on the CPU whose run
all four `launch_*` readers read; and, found by name, what two accepted
tests hold of `resident_hit_share` and of the four-chip cell beside the one
line each that an appended entry cannot keep (`tests/conftest.py` marks
those two expected failures)."""

import json
import os
import types

import pytest

from bench_helpers import REPO, copy_benchmark, run_harness
from tpubench.spec import Spec

TIMINGS = {"device.dispatch": 0.64, "device.dispatch.cpu": 0.48,
           "mesh.assemble": 1.1, "mesh.assemble.cpu": 1.0,
           "pipeline.wait": 0.2, "pipeline.wait.cpu": 0.001}
COUNTS = {"device.launches": 242, "device.dispatch.leaves": 20570,
          "device.dispatch.host_leaves": 484,
          "device.dispatch.host_bytes": 1936}

# metric -> (where it reads, the names it needs there, its value on the
# run above over two queries)
CASES = {
    "launch_cpu_ms_per_query": ("timings", ["device.dispatch.cpu"], 240.0),
    "launch_wait_ms_per_query": ("timings", ["device.dispatch.cpu"], 80.0),
    "launch_args_per_launch": ("counts", ["device.dispatch.leaves"], 85.0),
    "launch_host_args_per_launch": (
        "counts", ["device.dispatch.host_leaves"], 2.0),
    "mesh_assemble_ms_per_query": ("timings", ["mesh.assemble"], 550.0),
}
LAUNCH = sorted(n for n in CASES if n.startswith("launch_"))
CELLS = [w["name"] for w in Spec(REPO).bench["workloads"]]
MESH_CELL = "q1_sf100_mesh4"


def _run(queries=2, timings=TIMINGS, counts=COUNTS):
    """All a reader touches of a `harness.Run`."""
    return types.SimpleNamespace(queries=queries, timings=timings,
                                 counts=counts, mix={"entry": "sql"})


def _read(name, run):
    return Spec(REPO).metric_reader(name)(run)


@pytest.mark.parametrize("name", sorted(CASES))
def test_a_launch_metric_reads_its_timer_or_counter(name):
    where, names, value = CASES[name]
    assert _read(name, _run()) == pytest.approx(value)
    # an older engine: the launch timer and counter are there, the CPU
    # clock, the census and the assembly's timer are not
    held = dict(TIMINGS if where == "timings" else COUNTS)
    without = {k: v for k, v in held.items() if k not in names}
    assert _read(name, _run(**{where: without})) is None
    assert _read(name, _run(timings={}, counts={})) is None
    # there and not bumped in the window: zero, a number
    zeroed = {**held, **dict.fromkeys(names, 0)}
    if name == "launch_wait_ms_per_query":
        assert _read(name, _run(timings=zeroed)) == pytest.approx(320.0)
    else:
        assert _read(name, _run(**{where: zeroed})) == 0.0


@pytest.mark.parametrize("name", [
    "launch_cpu_ms_per_query", "launch_wait_ms_per_query",
    "mesh_assemble_ms_per_query"])
def test_a_timer_metric_of_a_window_with_no_query_is_none(name):
    assert _read(name, _run(queries=0)) is None


@pytest.mark.parametrize("name", [
    "launch_args_per_launch", "launch_host_args_per_launch"])
def test_a_census_metric_of_a_window_with_no_launch_is_none(name):
    assert _read(name, _run(counts={**COUNTS, "device.launches": 0})) is None
    # a launch, not a query, is what it divides by
    assert _read(name, _run(queries=0)) == pytest.approx(CASES[name][2])


@pytest.mark.parametrize("timings", [
    TIMINGS,
    {"device.dispatch": 1.9563, "device.dispatch.cpu": 0.0},
    {"device.dispatch": 0.25, "device.dispatch.cpu": 0.25},
    # the CPU clock is coarser than the wall's: a wait a little under zero
    {"device.dispatch": 0.1, "device.dispatch.cpu": 0.1004},
])
def test_cpu_and_wait_sum_to_the_launch_calls_wall(timings):
    run = _run(queries=3, timings=timings)
    assert (_read("launch_cpu_ms_per_query", run)
            + _read("launch_wait_ms_per_query", run)) == pytest.approx(
        _read("launch_dispatch_ms_per_query", run))


def _entries():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    return bench, {m["name"]: m for m in bench["per_layer"]}


@pytest.mark.parametrize("name,unit,source,workloads", [
    ("launch_cpu_ms_per_query", "ms", "program_span", None),
    ("launch_wait_ms_per_query", "ms", "program_span", None),
    ("launch_args_per_launch", "arguments", "program_counter", None),
    ("launch_host_args_per_launch", "arguments", "program_counter", None),
    ("mesh_assemble_ms_per_query", "ms", "program_span", [MESH_CELL]),
])
def test_the_entry_is_in_the_benchmark_by_name(name, unit, source, workloads):
    bench, entries = _entries()
    want = {"name": name, "unit": unit, "better": "lower", "source": source,
            "layer": "operator_drivers", "moves": "rows_per_s"}
    if workloads:
        want["workloads"] = workloads
    assert entries[name] == want
    spec = Spec(REPO)
    for cell in CELLS:
        listed = entries[name] in spec.metrics_of(cell, "per_layer")
        assert listed == (workloads is None or cell in workloads)
        # every cell reports the metric it moves
        assert any(e["name"] == "rows_per_s"
                   for e in spec.metrics_of(cell, "end_to_end"))
    assert callable(spec.metric_reader(name))


def test_the_five_are_appended_after_every_accepted_entry():
    bench, _ = _entries()
    names = [m["name"] for m in bench["per_layer"]]
    assert names[-5:] == [
        "launch_cpu_ms_per_query", "launch_wait_ms_per_query",
        "launch_args_per_launch", "launch_host_args_per_launch",
        "mesh_assemble_ms_per_query"]
    assert names[-6] == "resident_hit_share" and len(set(names)) == len(names)


@pytest.mark.parametrize("cell", ["q1_sf10_warm", MESH_CELL])
def test_a_traced_run_on_the_cpu_holds_all_four_launch_metrics(
        capsys, tmp_path, monkeypatch, cell):
    """The rehearsal's line names counts only, so it holds the two census
    metrics; the run it was printed from gives every reader of the cell a
    number: the CPU clock runs with every timer, the census with the
    profile."""
    from tpubench.harness import CellRun

    runs, report = [], CellRun.report

    def keeping(self, run):
        runs.append(run)
        return report(self, run)

    monkeypatch.setattr(CellRun, "report", keeping)
    code, line, _ = run_harness(
        capsys, copy_benchmark(tmp_path), "--workload", cell, "--seed",
        "2147483659", "--seconds", "0.5", "--trace", "1",
        "--rehearse-rows", "20000")
    assert code == 0 and line["correct"] is True and line["failed"] == 0
    (run,) = runs
    got = {n: _read(n, run) for n in LAUNCH}
    assert all(isinstance(v, float) and v >= 0 for n, v in got.items()
               if n != "launch_wait_ms_per_query"), got
    assert got["launch_cpu_ms_per_query"] > 0
    assert (got["launch_cpu_ms_per_query"] + got["launch_wait_ms_per_query"]
            == pytest.approx(_read("launch_dispatch_ms_per_query", run)))
    # what a launch is handed: the cell's own programs, some leaves each
    assert got["launch_args_per_launch"] >= 5
    assert 0 <= got["launch_host_args_per_launch"] <= got[
        "launch_args_per_launch"]
    for name in ("launch_args_per_launch", "launch_host_args_per_launch"):
        assert line["metrics"][name] == {"value": got[name],
                                         "unit": "arguments"}
    # a CPU run names no time
    assert not {"launch_cpu_ms_per_query", "launch_wait_ms_per_query",
                "mesh_assemble_ms_per_query"} & set(line["metrics"])
    assemble = _read("mesh_assemble_ms_per_query", run)
    if cell == MESH_CELL:
        assert assemble > 0
        rounds = run.counts["mesh.rounds"]
        assert rounds == run.queries  # 20,000 rows: one round a query
        # the query thread's residue no longer holds the assembly
        assert run.timings["query.other"] <= (
            run.timings["query"] - run.timings["mesh.assemble"])
    else:
        # no mesh, no such timer (zero where an earlier test of this
        # process ran a mesh: the registry is the process's)
        assert not assemble


# -- what the two superseded tests hold besides their one line --------------

def test_resident_hit_share_is_a_counter_of_the_h2d_layer_in_every_cell():
    bench, entries = _entries()
    m = entries["resident_hit_share"]
    assert m == {
        "name": "resident_hit_share", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "H2D", "moves": "rows_per_s",
        "workloads": [w["name"] for w in bench["workloads"]],
    }
    spec = Spec(REPO)
    for cell in m["workloads"]:
        assert m in spec.metrics_of(cell, "per_layer")
        assert any(e["name"] == "rows_per_s"
                   for e in spec.metrics_of(cell, "end_to_end"))


def test_what_the_benchmark_holds_of_the_four_chip_cell():
    spec = Spec(REPO)
    bench = spec.bench
    cell, config = bench["workloads"][-1], bench["configs"][-1]
    assert cell["name"] == MESH_CELL
    assert config["name"] == "tpch_lineitem_sf100_mesh4"
    assert cell["chips"] == 4 and len(cell["why"]) <= 200
    assert len(config["source"]) <= 200 and len(config["why"]) <= 200
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    e2e = {m["name"] for m in spec.metrics_of(MESH_CELL, "end_to_end")}
    assert e2e == {"rows_per_s", "setup_s"}
    share = next(m for m in bench["per_layer"]
                 if m["name"] == "resident_hit_share")
    assert share["workloads"][-1] == MESH_CELL
    # the nineteen it was accepted with, and this PR's five: every entry
    # with no list is the cell's too, and the mesh path observes each
    asked = [m["name"] for m in spec.metrics_of(MESH_CELL, "per_layer")]
    assert {"stage_wait_ms_per_query", "launch_dispatch_ms_per_query",
            "query_other_ms_per_query", "d2h_wait_ms_per_query",
            "h2d_encode_ms_per_query", "h2d_dispatch_ms_per_query",
            "resident_hit_share", "query_roofline"} <= set(asked)
    assert len(asked) == 19 + len(CASES)
    assert set(asked[19:]) == set(CASES)
