"""The cell `q3_sf10_join3` through the harness in the sandbox: every name
of it resolves; its rehearsal probes both builds on the device, turns the
three numeric group keys into groups on the device and is correct, with the
cell's readers registered at the end of `per_layer` of a copy (in the tree
they are files without entries, as the join's are: PERF.md section 7); the
control shows `correct: false` for a revenue altered by 1e-6, for two of the
ten rows swapped and for a silent `must_launch`; the readers on made-up
runs; and what `BENCHMARK.json` holds of the cell, found by name."""

import json
import types

import pytest

from bench_helpers import REPO, copy_benchmark, edit_json, run_harness
from tpubench.spec import Spec

CELL = "q3_sf10_join3"
CONFIG_NAME = "tpch_sf10_customer_orders_lineitem"
CONFIG = f"/tpubench/configs/{CONFIG_NAME}.json"

# the entries a `benchmark` PR appends for this cell (PERF.md section 7)
AGG_METRICS = [
    {"name": name, "unit": unit, "better": better, "source": source,
     "layer": layer, "moves": "rows_per_s", "workloads": [CELL]}
    for name, unit, better, source, layer in [
        ("agg_key_ids_ms_per_query", "ms", "lower", "program_span",
         "operator_drivers"),
        ("agg_key_pull_mb_per_query", "MB", "lower", "program_counter", "D2H"),
        ("agg_live_groups_per_query", "groups", "lower", "program_counter",
         "operator_drivers"),
        ("agg_key_ids_roofline", "%", "higher", "device_trace", "kernels")]]


def _with_agg_metrics(tmp_path) -> str:
    root = copy_benchmark(tmp_path)
    edit_json(root + "/BENCHMARK.json",
              lambda d: d["per_layer"].extend(AGG_METRICS))
    return root


def _detail(out: str) -> dict:
    line = next(l for l in out.splitlines() if "] detail {" in l)
    return json.loads(line.split("] detail ", 1)[1])


def _rehearse(capsys, root, rows, trace="1", seed="2147483659"):
    code, line, out = run_harness(
        capsys, root, "--workload", CELL, "--seed", seed, "--seconds", "0.3",
        "--trace", trace, "--rehearse-rows", str(rows))
    assert code == 0
    return line, _detail(out)


def test_every_name_of_the_cell_resolves():
    spec = Spec(REPO)
    cell = spec.cell(CELL)
    assert cell["chips"] == 1 and cell["config"] == CONFIG_NAME
    config, mix = spec.config(cell["config"]), spec.traffic(cell["traffic"])
    assert mix["entry"] == "sql" and mix["loop"] == {"kind": "closed",
                                                     "clients": 1}
    assert [t["name"] for t in mix["templates"]] == ["q3"]
    assert mix["warmup"] == {"requests_each": 1} and mix["trace_seconds"] == 6
    dataset = spec.dataset(config["dataset"])
    assert list(dataset.TABLES) == config["tables"] == [
        "lineitem", "orders", "customer"]
    assert config["resident_columns"] == [
        c for cols in dataset.TABLES.values() for c in cols]
    assert config["rows"] == 60_000_000 and config["chips"] == 1
    text = spec.query(config["queries"], "q3")
    params = {k: v["value"] for k, v in mix["templates"][0]["params"].items()}
    assert params == {"segment": dataset.SEGMENT, "date": dataset.DATE}
    sql = text.format(**dataset.bind("q3", params))
    assert "c_mktsegment = 'BUILDING'" in sql and "ORDER BY revenue DESC" in sql
    assert sql.count("9204") == 2 and sql.endswith("LIMIT 10")
    e2e = {m["name"] for m in spec.metrics_of(CELL, "end_to_end")}
    assert e2e == {"rows_per_s", "setup_s"}
    # 76.5 M rows a request: the three tables its text names
    from tpubench import peaks

    rows = {"lineitem": 60_000_000, "orders": 15_000_000,
            "customer": 1_500_000}
    assert peaks.query_scan(sql, dataset.TABLES, rows)[0] == 76_500_000


def test_rehearsal_keeps_both_probes_and_the_group_keys_on_the_device(
        capsys, tmp_path):
    """1.2 M lineitem rows, 300,000 orders, 30,000 customers.  Every row
    goes through two device probes against builds made once, the three
    keys' groups are made on the device from the rows the predicate
    keeps, and ten rows come back."""
    line, detail = _rehearse(capsys, _with_agg_metrics(tmp_path), 1_200_000)
    assert line["correct"] is True and line["failed"] == 0
    counts, queries = detail["counts"], line["attempted"]
    assert queries >= 1
    assert counts["join.probe.rows"] == 2 * 1_200_000 * queries
    probes = counts["device.launches.join.probe"]
    assert probes == 2 * 10 * queries
    # orders' three payload columns, customer's codes
    assert counts["join.probe.gathers"] == probes // 2 * (3 + 1)
    assert counts["join.build.reuse"] == 2 * queries  # built in warm-up only
    for absent in ("join.host_probe.rows", "join.build.rows",
                   "join.build.bytes", "device.launches.join.build",
                   "aggregate.key_pull.bytes", "h2d.resident_misses",
                   "h2d.bytes"):
        assert absent not in counts, absent
    assert counts["h2d.resident_hits"] == probes // 2
    groups = counts["aggregate.device_key.groups"] // queries
    assert 0.005 < groups / 300_000 < 0.011
    assert counts["aggregate.device_key.rows"] > counts[
        "aggregate.device_key.groups"]
    # what the step read: every probed row's mask, and of the kept rows
    # three int64 keys and the two float64 columns of the revenue
    assert counts["aggregate.device_key.offered"] == 1_200_000 * queries
    assert counts["aggregate.device_key.input_bytes"] == 40 * counts[
        "aggregate.device_key.rows"]
    assert counts["device.launches.agg.key_ids"] >= queries
    assert counts["device.launches.topk.final"] == queries
    metrics = line["metrics"]
    assert metrics["h2d_mb_per_query"]["value"] == 0
    assert metrics["h2d_transfers_per_query"]["value"] == 0
    assert metrics["d2h_kb_per_query"]["value"] < 2
    assert metrics["resident_hit_share"]["value"] == 100
    assert metrics["compiles_in_window"]["value"] == 0
    # a rehearsal's line carries the counter-read metrics alone
    assert metrics["agg_key_pull_mb_per_query"]["value"] == 0
    assert metrics["agg_live_groups_per_query"]["value"] == groups
    assert "agg_key_ids_ms_per_query" not in metrics
    assert detail["timings"]["aggregate.device_key_ids"] > 0
    assert line["compared"]["worst_rel_gap"]["value"] < 1e-12


def _altered(fault):
    """`collect` handing the harness a result with `fault` in it."""
    import datafusion_tpu.exec.materialize as materialize

    collect = materialize.collect

    def faulty(rel):
        result = collect(rel)
        revenue = result.columns[-1]
        if fault == "revenue":
            result.columns[-1] = revenue * (1 + 1e-6)
        else:
            order = list(range(len(revenue)))
            order[2], order[7] = order[7], order[2]
            result.columns = [c[order] for c in result.columns]
        return result

    return materialize, faulty


@pytest.mark.parametrize("fault", ["revenue", "swap"])
def test_an_altered_answer_is_not_correct(capsys, tmp_path, monkeypatch,
                                          fault):
    """The rest of a run over an engine whose revenues are off by 1e-6
    (what a float32 sum would give), or whose ten rows come back with
    two of them swapped."""
    module, faulty = _altered(fault)
    monkeypatch.setattr(module, "collect", faulty)
    line, _ = _rehearse(capsys, copy_benchmark(tmp_path), 200_000, trace="0")
    assert line["correct"] is False
    assert line["failed"] == line["attempted"] > 0
    assert line["compared"]["wrong_answers"]["value"] == line["attempted"]
    assert line["compared"]["device.launches"]["value"] > 0
    gap = line["compared"]["worst_rel_gap"]
    if fault == "revenue":
        assert gap["value"] == pytest.approx(1e-6, rel=1e-3)
    else:
        assert gap["value"] < 1e-12  # every number right, the order wrong


def test_a_silent_must_launch_is_not_correct(capsys, tmp_path):
    """Every answer right and the guard's counter silent (a counter this
    cell never bumps named in its place)."""
    root = copy_benchmark(tmp_path)
    edit_json(root + CONFIG, lambda d: d["guarantees"]["device"].update(
        must_launch="serve.megabatch_launches"))
    line, _ = _rehearse(capsys, root, 200_000, trace="0")
    assert line["correct"] is False and line["failed"] == 0
    assert line["compared"]["serve.megabatch_launches"] == {
        "value": 0, "at_least": 1}
    assert line["compared"]["wrong_answers"]["value"] == 0


def test_a_key_pulled_to_the_host_shows_in_the_cells_readers(
        capsys, tmp_path, monkeypatch):
    """The device key step taken away (no batch offers its key columns):
    the answers are right, the guard in the tree does not see it, and the
    pull is counted where the traced line shows it."""
    from datafusion_tpu.exec.aggregate import AggregateRelation

    monkeypatch.setattr(AggregateRelation, "_device_key_columns",
                        lambda self, batch: None)
    line, detail = _rehearse(capsys, _with_agg_metrics(tmp_path), 200_000)
    assert line["correct"] is True and line["failed"] == 0
    pulled = detail["counts"]["aggregate.key_pull.bytes"]
    # three int64 columns of two batches, as they lie: padded to 131,072
    assert pulled == 3 * 8 * 2 * 131_072 * line["attempted"]
    assert "aggregate.device_key.groups" not in detail["counts"]
    assert line["metrics"]["agg_key_pull_mb_per_query"]["value"] == 6.291456
    assert "agg_live_groups_per_query" not in line["metrics"]
    assert line["metrics"]["d2h_kb_per_query"]["value"] > 6_291
    assert line["metrics"]["h2d_mb_per_query"]["value"] > 0  # the ids, back


# -- the four readers (files under tpubench/metrics/) ------------------------

def _run(counts=None, timings=None, queries=2, device_ops=None):
    trace = None if device_ops is None else {"device_ops": device_ops}
    return types.SimpleNamespace(
        queries=queries, counts=counts or {}, timings=timings or {},
        trace=trace, device={"kind": "TPU v5 lite"})


EMPTY = _run()
KEYED = {"join.probe.rows": 240_000_000, "device.launches.join.probe": 1832,
         "aggregate.device_key.offered": 120_000_000,
         "aggregate.device_key.rows": 600_000,
         "aggregate.device_key.input_bytes": 600_000 * 40,
         "aggregate.device_key.groups": 230_000,
         "device.launches.agg.key_ids": 30}
# an engine that has the step and not the counters of what it read
UNCOUNTED = {k: v for k, v in KEYED.items()
             if k not in ("aggregate.device_key.offered",
                          "aggregate.device_key.input_bytes")}
Q12 = {"join.probe.rows": 120_000_000, "device.launches.join.probe": 1034}


@pytest.mark.parametrize("name,run,value", [
    ("agg_key_ids_ms_per_query",
     _run(KEYED, {"aggregate.device_key_ids": 0.65}), 325.0),
    ("agg_key_ids_ms_per_query", _run(Q12, {"aggregate.group_ids": 0.05}),
     None),
    ("agg_key_pull_mb_per_query", _run(KEYED), 0.0),
    ("agg_key_pull_mb_per_query",
     _run({"aggregate.key_pull.bytes": 2_880_000_000}), 1440.0),
    ("agg_key_pull_mb_per_query", _run(Q12), None),
    ("agg_live_groups_per_query", _run(KEYED), 115_000.0),
    ("agg_live_groups_per_query", _run(Q12), None),
    # 120 M rows offered x 1 B of mask + 600,000 kept rows x (3 x 8 B of
    # keys + 2 x 8 B of values, as the engine counted them) = 144 MB:
    # 0.175824 ms at 819 GB/s, over 0.4 s of `_keyed_` operations
    ("agg_key_ids_roofline", _run(KEYED, device_ops=[
        ["jit__keyed_rows:sort.12", 0.2], ["jit_join_probe:fusion", 7.0],
        ["jit__keyed_reduce:fusion.85", 0.15],
        ["jit__keyed_counts:fusion", 0.05]]), 0.0439560439),
    ("agg_key_ids_roofline", _run(KEYED, device_ops=[
        ["jit_join_probe:fusion", 7.0]]), None),
    ("agg_key_ids_roofline", _run(Q12, device_ops=[
        ["jit__keyed_rows:sort.12", 0.2]]), None),
    ("agg_key_ids_roofline", _run(UNCOUNTED, device_ops=[
        ["jit__keyed_rows:sort.12", 0.2]]), None),
    ("agg_key_ids_roofline", _run(KEYED), None),  # an untraced run
])
def test_the_cells_readers(name, run, value):
    got = Spec(REPO).metric_reader(name)(run)
    assert got == (pytest.approx(value) if value is not None else None)


@pytest.mark.parametrize("name", [m["name"] for m in AGG_METRICS])
def test_the_cells_readers_give_nothing_on_an_empty_run(name):
    assert Spec(REPO).metric_reader(name)(EMPTY) is None
    assert Spec(REPO).metric_reader(name)(_run(queries=0)) is None


def test_key_step_bytes_are_the_mask_and_what_the_engine_counted():
    import importlib.util
    import os

    path = os.path.join(REPO, "tpubench", "metrics", "agg_key_ids_roofline.py")
    spec = importlib.util.spec_from_file_location("_agg_roofline", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.key_step_bytes(1_000, 10 * 40) == 1_000 + 400
    assert mod.key_step_bytes(1_000, 0) == 1_000


def test_the_readers_register_for_their_cell_alone(tmp_path):
    """Appended to a copy, this cell's four entries and the join's five
    (`test_tpubench_q12_join.JOIN_METRICS`) resolve to their readers for
    their own cell and for no other; the tree has neither set, both cells,
    each mix through `sql`, and both cells in the list of
    `resident_hit_share`, wherever in it."""
    from test_tpubench_q12_join import CELL as Q12_CELL, JOIN_METRICS

    root = _with_agg_metrics(tmp_path)
    edit_json(root + "/BENCHMARK.json",
              lambda d: d["per_layer"].extend(JOIN_METRICS))
    spec = Spec(root)
    for mine, metrics, other in ((CELL, AGG_METRICS, Q12_CELL),
                                 (Q12_CELL, JOIN_METRICS, "q1_sf10_warm")):
        for m in metrics:
            assert m in spec.metrics_of(mine, "per_layer")
            assert m not in spec.metrics_of(other, "per_layer")
            assert callable(spec.metric_reader(m["name"]))
    tree = Spec(REPO)
    assert not {m["name"] for m in tree.bench["per_layer"]} & {
        m["name"] for m in AGG_METRICS + JOIN_METRICS}
    share = next(m for m in tree.bench["per_layer"]
                 if m["name"] == "resident_hit_share")
    for cell in (Q12_CELL, CELL):
        assert cell in share["workloads"]
        assert tree.traffic(tree.cell(cell)["traffic"])["entry"] == "sql"


def test_what_the_benchmark_holds_of_the_cell():
    """The configuration and the cell on one chip, found by name, each
    `why` and `source` within its 200 characters; the cell reports
    `rows_per_s` and not `request_p50_ms`.  (That nothing else of
    `BENCHMARK.json` changed is the driver's check against the parent
    commit, not a test's.)"""
    bench = Spec(REPO).bench
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    config = next(c for c in bench["configs"] if c["name"] == CONFIG_NAME)
    assert cell["chips"] == 1 and cell["config"] == CONFIG_NAME
    assert len(config["source"]) <= 200 and len(cell["why"]) <= 200
    assert CELL not in next(m for m in bench["end_to_end"]
                            if m["name"] == "request_p50_ms")["workloads"]
    spec = Spec(REPO)
    assert {"rows_per_s", "setup_s"} <= {
        m["name"] for m in spec.metrics_of(CELL, "end_to_end")}
