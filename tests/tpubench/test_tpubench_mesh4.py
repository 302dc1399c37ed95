"""The cell `q1_sf100_mesh4` in the sandbox: every name of it resolves to a
file, the first entry point over `PartitionedContext` and the first
configuration on four chips; its rehearsal, with the cell's five readers
appended as `per_layer` entries to a copy (in the tree they are files
without entries: PERF.md section 7), is correct on four of the CPU's virtual
devices, runs its rounds folded and places no column twice; the readers on
made-up runs, among them a run of four chips on which `mesh_query_roofline`
is a quarter of `query_roofline`; and what `BENCHMARK.json` holds of the
cell, found by name."""

import json
import types

import pytest

from bench_helpers import REPO, copy_benchmark, edit_json, run_harness
from tpubench.spec import Spec, device_guard

CELL = "q1_sf100_mesh4"
CONFIG_NAME = "tpch_lineitem_sf100_mesh4"

# the entries a `benchmark` PR appends for this cell (PERF.md section 7)
MESH_METRICS = [
    {"name": name, "unit": unit, "better": better, "source": source,
     "layer": layer, "moves": "rows_per_s", "workloads": [CELL]}
    for name, unit, better, source, layer in [
        ("mesh_stage_ms_per_query", "ms", "lower", "program_span", "mesh"),
        ("mesh_combine_ms_per_query", "ms", "lower", "program_span", "mesh"),
        ("mesh_rounds_per_launch", "rounds", "higher", "program_counter",
         "mesh"),
        ("mesh_shard_skew", "x", "lower", "program_counter", "mesh"),
        ("mesh_query_roofline", "%", "higher", "device_trace", "kernels")]]


def _run(counts=None, timings=None, queries=2, trace=None, chips=4,
         bytes_needed=0):
    return types.SimpleNamespace(
        queries=queries, counts=counts or {}, timings=timings or {},
        trace=trace, bytes_needed=bytes_needed,
        device={"kind": "TPU v5 lite", "count": chips})


EMPTY = _run()
# two queries over 600 M rows in 1,145 rounds each, 36 launches a query
MESH = {"mesh.rounds": 2290, "device.launches.mesh.multi": 72,
        "mesh.shards": 2 * 4,
        "mesh.shard_rows.max": 2 * 150_077_440,
        "mesh.shard_rows.total": 2 * 600_000_000,
        "device.launches.mesh.combine": 2}
# Q1 reads 44 B a row: 26.4 GB a query, 32.2 ms at one chip's 819 GB/s
TRACED = dict(trace={"busy_s": 0.5, "chips": 4, "device_ops": []},
              bytes_needed=2 * 600_000_000 * 44)


@pytest.mark.parametrize("name,run,value", [
    ("mesh_stage_ms_per_query", _run(MESH, {"mesh.stage": 11.0}), 5500.0),
    ("mesh_stage_ms_per_query", _run(MESH, {"pipeline.stage": 11.0}), None),
    ("mesh_combine_ms_per_query",
     _run(MESH, {"execute.collective_combine": 0.004}), 2.0),
    ("mesh_combine_ms_per_query", _run(MESH), None),
    ("mesh_rounds_per_launch", _run(MESH), 2290 / 72),
    ("mesh_rounds_per_launch",
     _run({**MESH, "device.launches.mesh.stacked": 2}), 2290 / 74),
    ("mesh_rounds_per_launch", _run({"device.launches": 121}), None),
    ("mesh_shard_skew", _run(MESH), 4 * 150_077_440 / 600_000_000),
    # the mesh's shards, not the host's devices
    ("mesh_shard_skew", _run(MESH, chips=8), 4 * 150_077_440 / 600_000_000),
    ("mesh_shard_skew", _run({"device.launches": 121}), None),
    # 52.8 GB over 4 x 819 GB/s = 16.117 ms, over 500 ms busy a chip
    ("mesh_query_roofline", _run(MESH, **TRACED), 3.2234432234),
    ("mesh_query_roofline", _run(MESH, bytes_needed=1), None),  # untraced
])
def test_the_cells_readers(name, run, value):
    got = Spec(REPO).metric_reader(name)(run)
    assert got == (pytest.approx(value) if value is not None else None)


@pytest.mark.parametrize("name", [m["name"] for m in MESH_METRICS])
def test_the_cells_readers_give_nothing_on_an_empty_run(name):
    assert Spec(REPO).metric_reader(name)(EMPTY) is None


def test_on_four_chips_the_mesh_roofline_is_a_quarter_of_the_accepted_one():
    """`query_roofline` divides the whole table's bytes by one chip's
    peak; four chips each read a quarter of them."""
    spec, run = Spec(REPO), _run(MESH, **TRACED)
    whole = spec.metric_reader("query_roofline")(run)
    assert spec.metric_reader("mesh_query_roofline")(run) == \
        pytest.approx(whole / 4)
    one = _run(MESH, trace={**TRACED["trace"], "chips": 1},
               bytes_needed=TRACED["bytes_needed"], chips=1)
    assert spec.metric_reader("mesh_query_roofline")(one) == \
        pytest.approx(spec.metric_reader("query_roofline")(one))


def test_every_name_of_the_cell_resolves():
    spec = Spec(REPO)
    cell = spec.cell(CELL)
    assert cell["chips"] == 4 and cell["config"] == CONFIG_NAME
    config, mix = spec.config(cell["config"]), spec.traffic(cell["traffic"])
    assert mix["entry"] == "mesh"
    assert mix["loop"] == {"kind": "closed", "clients": 1}
    assert [t["name"] for t in mix["templates"]] == ["q1"]
    assert mix["templates"][0]["params"]["delta"] == {"dist": "const",
                                                      "value": 90}
    assert mix["warmup"] == {"requests_each": 1}
    sibling = spec.config("tpch_lineitem_sf10")
    assert config["chips"] == 4 and config["scale_factor"] == 100
    assert config["rows"] == 10 * sibling["rows"] == 600_000_000
    for key in ("dataset", "queries", "tables", "resident_columns",
                "row_group_rows"):
        assert config[key] == sibling[key]
    assert config["engine"]["device"] == "tpu"
    assert config["engine"]["result_cache"] is False
    assert config["guarantees"]["answers"] == sibling["guarantees"]["answers"]
    assert device_guard(config) == (
        "device.launches",
        ["aggregate.host_routed_slots", "sort.host_routed_runs"])
    listed = next(c for c in spec.bench["configs"]
                  if c["name"] == CONFIG_NAME)
    assert listed["reduced"] == ["tables", "resident_columns", "rows"]
    assert all(k in config and k in config["reduced"]
               for k in listed["reduced"])
    assert "1.5.4" in listed["source"] and "1.5.4" in config["source"]


def test_the_entry_registers_through_the_resident_call_alone(monkeypatch):
    """`entries/mesh.py` builds a `PartitionedContext` and hands every
    table to `register_resident_parquet`, so an engine without that call
    fails in set-up."""
    from datafusion_tpu.parallel.partition import PartitionedContext
    from tpubench import entries

    seen = []
    monkeypatch.setattr(PartitionedContext, "register_resident_parquet",
                        lambda self, name, path: seen.append((name, path)))
    entry = Spec(REPO).entry("mesh")(
        "cpu", {}, {"lineitem": "a.parquet", "orders": "b.parquet"},
        entries.Spans())
    assert isinstance(entry.ctx, PartitionedContext)
    assert entry.ctx.mesh.devices.size == 4  # of the suite's eight
    assert entry.ctx.datasources == {}
    assert seen == [("lineitem", "a.parquet"), ("orders", "b.parquet")]
    monkeypatch.delattr(PartitionedContext, "register_resident_parquet")
    with pytest.raises(AttributeError, match="register_resident_parquet"):
        Spec(REPO).entry("mesh")("cpu", {}, {"lineitem": "a.parquet"},
                                 entries.Spans())


def test_the_rehearsal_with_the_readers_registered(capsys, tmp_path):
    """On a copy with the five appended for this cell alone the spec
    loads and the traced rehearsal prints the counted ones: 20,000 rows
    are one row group, so one shard of four holds them."""
    root = copy_benchmark(tmp_path)
    edit_json(root + "/BENCHMARK.json",
              lambda d: d["per_layer"].extend(MESH_METRICS))
    spec = Spec(root)
    for m in MESH_METRICS:
        assert m in spec.metrics_of(CELL, "per_layer")
        assert m not in spec.metrics_of("q1_sf10_warm", "per_layer")
        assert callable(spec.metric_reader(m["name"]))
    assert not {m["name"] for m in Spec(REPO).bench["per_layer"]} & {
        m["name"] for m in MESH_METRICS}
    code, line, out = run_harness(
        capsys, root, "--workload", CELL, "--seed", "2147483659",
        "--seconds", "0.3", "--trace", "1", "--rehearse-rows", "20000")
    assert code == 0 and line["correct"] is True and line["failed"] == 0
    detail = json.loads(next(
        l for l in out.splitlines() if "] detail {" in l).split(
            "] detail ", 1)[1])
    queries = line["attempted"]
    counts = detail["counts"]
    assert counts["mesh.rounds"] == queries
    assert counts["device.launches.mesh.combine"] == queries
    assert counts["mesh.shard_rows.total"] == 20_000 * queries
    assert "h2d.resident_misses" not in counts  # placed in the warm-up
    assert line["metrics"]["resident_hit_share"]["value"] == 100.0
    assert line["metrics"]["mesh_rounds_per_launch"]["value"] == 1.0
    # all rows on one shard of the mesh's four, whatever the host has
    assert counts["mesh.shards"] == 4 * queries
    assert line["metrics"]["mesh_shard_skew"]["value"] == 4.0
    for timed in ("mesh_stage_ms_per_query", "mesh_combine_ms_per_query",
                  "mesh_query_roofline"):
        assert timed not in line["metrics"]  # a CPU run names no time
    for name in ("mesh.stage", "execute.collective_combine", "d2h.wait",
                 "query", "query.other"):
        assert detail["timings"].get(name, 0) > 0, name


def test_what_the_benchmark_holds_of_the_cell():
    """The cell on four chips at the end of `workloads`, within the share
    of four-chip cells the benchmark may have, each `why` and `source`
    within its 200 characters; it reports `rows_per_s` and `setup_s` and
    not `request_p50_ms`, and `resident_hit_share` lists it."""
    spec = Spec(REPO)
    bench = spec.bench
    cell = bench["workloads"][-1]
    config = bench["configs"][-1]
    assert cell["name"] == CELL and config["name"] == CONFIG_NAME
    assert cell["chips"] == 4 and len(cell["why"]) <= 200
    assert len(config["source"]) <= 200 and len(config["why"]) <= 200
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    e2e = {m["name"] for m in spec.metrics_of(CELL, "end_to_end")}
    assert e2e == {"rows_per_s", "setup_s"}
    share = next(m for m in bench["per_layer"]
                 if m["name"] == "resident_hit_share")
    assert share["workloads"][-1] == CELL
    # the six timers whose readers give nothing where the program never
    # observed them have no list, so the cell has to print them: the mesh
    # path observes each (tests/test_mesh_resident.py)
    asked = {m["name"] for m in spec.metrics_of(CELL, "per_layer")}
    assert {"stage_wait_ms_per_query", "launch_dispatch_ms_per_query",
            "query_other_ms_per_query", "d2h_wait_ms_per_query",
            "h2d_encode_ms_per_query", "h2d_dispatch_ms_per_query",
            "resident_hit_share", "query_roofline"} <= asked
    assert len(asked) == 19
