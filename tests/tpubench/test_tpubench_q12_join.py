"""The cell `q12_sf10_join` through the harness in the sandbox: its rehearsal
past 2^20 key slots probes on the device and is correct; with the join's
five readers registered at the end of `per_layer` (a data edit of a copy:
in the tree the accepted `test_tpubench_resident_hit_share.py` holds the
last entry and the driver holds new entries to the end, PERF.md section 7)
its traced line carries them, and a join made to probe on the host shows
in them (`join_host_probe_share` 100) though the guard in the tree does
not see it; the control shows `correct: false` when an answer is altered
and, with the join's own counters in the configuration's guard, when the
join is made to probe on the host; and the five readers on made-up runs."""

import json
import types

import pytest

from bench_helpers import REPO, copy_benchmark, edit_json, run_harness
from tpubench.spec import Spec

CELL = "q12_sf10_join"
CONFIG = "/tpubench/configs/tpch_sf10_orders_lineitem.json"
# what ISSUE 28 asked the configuration's guard to be; the benchmark's own
# test (test_tpubench_spec.py) holds every configuration in BENCHMARK.json
# to the three counters of the first two, so the file in the tree has those
JOIN_GUARD = {
    "must_launch": "device.launches.join.probe",
    "must_be_zero": ["join.host_probe.rows", "aggregate.host_routed_slots",
                     "sort.host_routed_runs"],
}

# the five `per_layer` entries ISSUE 28 names, as a `benchmark` PR appends them
JOIN_METRICS = [
    {"name": name, "unit": unit, "better": better, "source": source,
     "layer": layer, "moves": "rows_per_s", "workloads": [CELL]}
    for name, unit, better, source, layer in [
        ("join_probe_ms_per_query", "ms", "lower", "program_span",
         "operator_drivers"),
        ("join_build_ms_per_query", "ms", "lower", "program_span",
         "operator_drivers"),
        ("join_probe_launches_per_query", "launches", "lower",
         "program_counter", "operator_drivers"),
        ("join_host_probe_share", "%", "lower", "program_counter",
         "operator_drivers"),
        ("join_probe_roofline", "%", "higher", "device_trace", "kernels")]]


def _with_join_metrics(tmp_path) -> str:
    """A copy of the benchmark whose `per_layer` ends with the five entries."""
    root = copy_benchmark(tmp_path)
    edit_json(root + "/BENCHMARK.json",
              lambda d: d["per_layer"].extend(JOIN_METRICS))
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


def test_rehearsal_past_2_20_key_slots_probes_on_the_device(capsys, tmp_path):
    """1.2 M lineitem rows: 300,000 orders over 1.2 M keys.  Every row is
    probed by a device launch against a build made once, the ids of both
    string keys are made on the device, and the answer is what returns."""
    line, detail = _rehearse(capsys, _with_join_metrics(tmp_path), 1_200_000)
    assert line["correct"] is True and line["failed"] == 0
    counts, queries = detail["counts"], line["attempted"]
    assert queries >= 1
    assert counts["join.probe.rows"] == 1_200_000 * queries
    assert "join.host_probe.rows" not in counts
    assert counts["join.build.reuse"] == queries  # built in warm-up only
    assert not any(c in counts for c in (
        "join.build.rows", "join.build.bytes", "device.launches.join.build"))
    # the ids are made inside the aggregate's launches, not in one of their own
    assert counts["device.launches"] == counts["device.launches.join.probe"] + sum(
        n for tag, n in counts.items() if tag.startswith("device.launches.agg"))
    assert counts["h2d.resident_hits"] == counts["device.launches.join.probe"]
    assert "h2d.resident_misses" not in counts
    assert line["metrics"]["h2d_mb_per_query"]["value"] < 0.001
    assert line["metrics"]["d2h_kb_per_query"]["value"] < 1
    assert line["metrics"]["resident_hit_share"]["value"] == 100
    assert line["metrics"]["compiles_in_window"]["value"] == 0
    assert "join.build" not in detail["timings"]
    assert detail["timings"]["join.probe"] > 0
    # a rehearsal's line carries the counter-read metrics alone
    assert line["metrics"]["join_host_probe_share"]["value"] == 0
    assert line["metrics"]["join_probe_launches_per_query"]["value"] == 11


def test_an_altered_count_is_not_correct(capsys, tmp_path, monkeypatch):
    """The rest of a run over an engine whose counts are off by one."""
    import datafusion_tpu.exec.materialize as materialize

    collect = materialize.collect

    def off_by_one(rel):
        result = collect(rel)
        result.columns[-1] = result.columns[-1] + 1
        return result

    monkeypatch.setattr(materialize, "collect", off_by_one)
    line, _ = _rehearse(capsys, copy_benchmark(tmp_path), 40_000, trace="0")
    assert line["correct"] is False
    assert line["failed"] == line["attempted"] > 0
    assert line["compared"]["wrong_answers"]["value"] == line["attempted"]
    assert line["compared"]["device.launches"]["value"] > 0


def test_the_joins_five_metrics_register_for_this_cell_alone(tmp_path):
    """Appended to a copy they resolve to their readers for the new cell and
    for no other; the tree has the cell, its mix through `sql`, and the cell
    in the list of the one accepted metric that names its cells."""
    spec = Spec(_with_join_metrics(tmp_path))
    for m in JOIN_METRICS:
        assert m in spec.metrics_of(CELL, "per_layer")
        assert m not in spec.metrics_of("q1_sf10_warm", "per_layer")
        assert callable(spec.metric_reader(m["name"]))
    tree = Spec(REPO)
    assert not {m["name"] for m in tree.bench["per_layer"]} & {
        m["name"] for m in JOIN_METRICS}
    assert tree.bench["per_layer"][-1]["workloads"][-1] == CELL
    assert tree.traffic(tree.cell(CELL)["traffic"])["entry"] == "sql"


def test_a_build_with_no_room_shows_as_host_probe_share(
        capsys, tmp_path, monkeypatch):
    """What the ledger has free is less than the cell's build (100,000
    orders over 400,000 slots and lineitem's copies to come): the engine
    probes on the host, every answer is right, and the traced line says
    where the probe ran."""
    from datafusion_tpu.obs.device import LEDGER

    monkeypatch.setenv("DATAFUSION_TPU_HBM_BYTES",
                       str(LEDGER.live_bytes() + (1 << 20)))
    line, detail = _rehearse(capsys, _with_join_metrics(tmp_path), 400_000)
    assert line["correct"] is True and line["failed"] == 0
    assert line["metrics"]["join_host_probe_share"]["value"] == 100
    assert not line["metrics"].get(
        "join_probe_launches_per_query", {"value": 0})["value"]
    assert "join.probe.rows" not in detail["counts"]
    # no room: not pinned either, so every query builds again
    assert detail["counts"]["join.build.rows"] == 100_000 * line["attempted"]


@pytest.mark.parametrize("device_probe", [True, False])
def test_a_join_probed_on_the_host_is_not_correct_under_the_joins_guard(
        capsys, tmp_path, monkeypatch, device_probe):
    """With the join's counters in the guard (a data edit of the copy's
    configuration), `DATAFUSION_TPU_JOIN_DEVICE=0` reads `correct: false`
    though every answer is right; the engine as it is reads true."""
    root = copy_benchmark(tmp_path)
    edit_json(root + CONFIG,
              lambda d: d["guarantees"]["device"].update(JOIN_GUARD))
    if not device_probe:
        monkeypatch.setenv("DATAFUSION_TPU_JOIN_DEVICE", "0")
    line, detail = _rehearse(capsys, root, 40_000, trace="0",
                             seed="7" if device_probe else "8")
    compared = line["compared"]
    assert compared["wrong_answers"]["value"] == 0 and line["failed"] == 0
    assert line["correct"] is device_probe
    rows = 40_000 * line["attempted"]
    assert compared["join.host_probe.rows"] == {
        "value": 0 if device_probe else rows, "at_most": 0}
    assert (compared["device.launches.join.probe"]["value"] > 0) is device_probe
    assert detail["counts"].get("join.probe.rows", 0) == (
        rows if device_probe else 0)


def test_the_guard_in_the_tree_does_not_see_a_host_probe(
        capsys, tmp_path, monkeypatch):
    """Under the three counters every configuration is held to
    (`test_tpubench_spec.py`), a host probe still reads `correct: true`
    (PERF.md section 7); two accepted metrics of the H2D layer show it, and
    once registered the join's own two."""
    monkeypatch.setenv("DATAFUSION_TPU_JOIN_DEVICE", "0")
    line, detail = _rehearse(capsys, _with_join_metrics(tmp_path), 40_000,
                             seed="9")
    assert line["correct"] is True and set(line["compared"]) == {
        "wrong_answers", "worst_rel_gap", "device.launches",
        "aggregate.host_routed_slots", "sort.host_routed_runs"}
    assert detail["counts"]["join.host_probe.rows"] == 40_000 * line["attempted"]
    assert detail["timings"]["join.host_probe"] > 0
    assert line["metrics"]["join_host_probe_share"]["value"] == 100
    assert not line["metrics"].get(
        "join_probe_launches_per_query", {"value": 0})["value"]
    # what the tree's own line shows of it: every probe batch shipped again
    assert line["metrics"]["resident_hit_share"]["value"] == 0
    assert line["metrics"]["h2d_mb_per_query"]["value"] > 1


# -- the five readers (files under tpubench/metrics/) ------------------------

def _run(counts=None, timings=None, queries=2, device_ops=None):
    trace = None if device_ops is None else {"device_ops": device_ops}
    return types.SimpleNamespace(
        queries=queries, counts=counts or {}, timings=timings or {},
        trace=trace, device={"kind": "TPU v5 lite"})


EMPTY = _run()
PROBED = {"join.probe.rows": 120_000_000, "device.launches.join.probe": 916,
          "join.build.reuse": 2}


@pytest.mark.parametrize("name,run,value", [
    ("join_probe_ms_per_query", _run(PROBED, {"join.probe": 0.5}), 250.0),
    ("join_probe_ms_per_query", _run({}, {"query": 1.0}), None),
    ("join_build_ms_per_query", _run(PROBED, {"join.probe": 0.5}), 0.0),
    ("join_build_ms_per_query", _run(PROBED, {"join.build": 7.0}), 3500.0),
    ("join_build_ms_per_query",
     _run({"join.host_probe.rows": 5}, {"join.build": 1.0}, queries=1), 1000.0),
    ("join_build_ms_per_query", _run({"device.launches": 9}, {}), None),
    ("join_probe_launches_per_query", _run(PROBED), 458.0),
    ("join_probe_launches_per_query", _run({"device.launches": 121}), None),
    ("join_host_probe_share", _run(PROBED, {"join.probe": 0.5}), 0.0),
    ("join_host_probe_share", _run({"join.host_probe.rows": 60_000_000},
                                   {"join.host_probe": 9.0}), 100.0),
    ("join_host_probe_share",
     _run({"join.host_probe.rows": 1, "join.probe.rows": 3},
          {"join.host_probe": 0.1, "join.probe": 0.1}), 25.0),
    ("join_host_probe_share", _run({"join.build.reuse": 1},
                                   {"join.probe": 0.1}), None),
    # an engine that times neither probe counts the host's rows as probed
    ("join_host_probe_share",
     _run({"join.probe.rows": 60_000_000}, {"join.build": 3.5}), None),
    # 120 M rows x (8 key + 4 slot + 2 x 4 payload + 1 mask) B = 2.52 GB:
    # 3.0769 ms at 819 GB/s, over 1.0 s of `join_probe` operations
    ("join_probe_roofline", _run(PROBED, device_ops=[
        ["jit_join_probe:fusion.3", 0.75], ["jit__fused_group:fusion", 2.0],
        ["jit_join_probe:gather.1", 0.25]]), 0.30769230769),
    ("join_probe_roofline", _run(PROBED, device_ops=[
        ["jit__fused_group:fusion", 2.0]]), None),
    ("join_probe_roofline", _run({}, device_ops=[
        ["jit_join_probe:fusion.3", 0.75]]), None),
    ("join_probe_roofline", _run(PROBED), None),  # an untraced run
])
def test_the_joins_readers(name, run, value):
    got = Spec(REPO).metric_reader(name)(run)
    assert got == (pytest.approx(value) if value is not None else None)


@pytest.mark.parametrize("name", [
    "join_probe_ms_per_query", "join_build_ms_per_query",
    "join_probe_launches_per_query", "join_host_probe_share",
    "join_probe_roofline"])
def test_the_joins_readers_give_nothing_on_an_empty_run(name):
    assert Spec(REPO).metric_reader(name)(EMPTY) is None
    assert Spec(REPO).metric_reader(name)(_run(queries=0)) is None


def test_probe_bytes_count_the_work_from_the_data_sets_tables():
    import importlib.util
    import os

    path = os.path.join(REPO, "tpubench", "metrics", "join_probe_roofline.py")
    spec = importlib.util.spec_from_file_location("jpr", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    orders = Spec(REPO).dataset("tpch_orders_lineitem").TABLES["orders"]
    assert mod._build_side() == orders
    assert mod.probe_bytes(1, orders, "o_orderkey") == 8 + 4 + 2 * 4 + 1
    assert mod.probe_bytes(10, {"k": "i64", "a": "f64", "b": "str"}, "k") == \
        10 * (8 + 4 + 2 * (8 + 4) + 1)
