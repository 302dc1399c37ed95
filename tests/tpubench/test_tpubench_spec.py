"""BENCHMARK.json against the benchmark's contract, and every name in it
against the files under tpubench/."""

import json
import os
import re

import pytest

from bench_helpers import (DEVICE_GUARD, REPO, copy_benchmark, edit_json,
                           snapshot_files)
from tpubench import entries, peaks
from tpubench.spec import Spec, SpecError, device_guard

NAME = re.compile(r"^[A-Za-z0-9][A-Za-z0-9_.-]{0,63}$")
LAYER = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def spec():
    return Spec(REPO)


def test_top_level_keys(spec):
    assert set(spec.bench) == {"command", "paths", "run_seconds", "configs",
                               "workloads", "end_to_end", "per_layer"}
    assert isinstance(spec.bench["run_seconds"], int)
    assert 1 <= spec.bench["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 64 << 10


def test_command_and_paths(spec):
    assert spec.bench["command"] == ["python3", "-m", "tpubench"]
    assert spec.bench["paths"] == ["tpubench", "tests/tpubench"]
    for p in spec.bench["paths"]:
        assert os.path.isdir(os.path.join(REPO, p))


def test_names_are_plain_and_used_once(spec):
    names = [e["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for e in spec.bench[k]]
    assert all(NAME.match(n) for n in names)
    assert len(names) == len(set(names))
    for k in ("configs", "workloads"):
        assert all(len(e["why"]) <= 200 for e in spec.bench[k])


def test_every_config_has_its_file_and_a_cell(spec):
    used = {w["config"] for w in spec.bench["workloads"]}
    files = set()
    for c in spec.bench["configs"]:
        assert c["name"] in used
        assert c["file"].startswith("tpubench/") and c["file"] not in files
        files.add(c["file"])
        doc = spec.config(c["name"])
        assert doc["name"] == c["name"]
        # every cut the benchmark declares is a key of the file, with its reason
        assert set(c["reduced"]) == set(doc["reduced"])
        assert all(k in doc for k in c["reduced"])


def test_cells_pair_a_config_and_a_traffic_mix_once(spec):
    cells = spec.bench["workloads"]
    assert 2 <= len(cells) <= 24
    pairs = [(w["config"], w["traffic"]) for w in cells]
    assert len(pairs) == len(set(pairs))
    assert all(w["chips"] in (1, 4) for w in cells)
    assert sum(w["chips"] == 4 for w in cells) <= max(len(cells) // 2, 1)


@pytest.mark.parametrize("cell", [w["name"] for w in Spec(REPO).bench["workloads"]])
def test_every_name_of_a_cell_resolves_to_a_file(spec, cell):
    w = spec.cell(cell)
    config = spec.config(w["config"])
    mix = spec.traffic(w["traffic"])
    dataset = spec.dataset(config["dataset"])
    for attr in ("TABLES", "generate", "bind", "Oracle"):
        assert hasattr(dataset, attr)
    for schema in dataset.TABLES.values():
        assert set(schema.values()) <= set(peaks.RESIDENT_BYTES)
    for t in mix["templates"]:
        text = spec.query(config["queries"], t["name"])
        assert peaks.named_in(text, dataset.TABLES)
    assert issubclass(spec.entry(mix["entry"]), entries.Entry)
    # the configuration says how a run shows the device did the work
    assert device_guard(config) == (
        "device.launches",
        ["aggregate.host_routed_slots", "sort.host_routed_runs"])
    assert isinstance(config["guarantees"]["device"]["sentence"], str)
    e2e = {m["name"] for m in spec.metrics_of(cell, "end_to_end")}
    assert "setup_s" in e2e and len(e2e) >= 2
    layers = spec.metrics_of(cell, "per_layer")
    assert layers
    for m in spec.metrics_of(cell, "end_to_end") + layers:
        assert callable(spec.metric_reader(m["name"]))
    # a per-layer metric is reported only where the metric it moves is
    assert all(m["moves"] in e2e for m in layers)


def test_metric_entries(spec):
    e2e = spec.bench["end_to_end"]
    assert 1 <= len(e2e) <= 16 and 1 <= len(spec.bench["per_layer"]) <= 128
    for m in e2e:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
        assert m["better"] in ("lower", "higher")
    assert next(m for m in e2e if m["name"] == "setup_s")["bound"] == 0.25
    cells = {w["name"] for w in spec.bench["workloads"]}
    for m in spec.bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves",
                          "workloads"}
        assert m["source"] in SOURCES
        assert LAYER.match(m["layer"])
        assert set(m.get("workloads", cells)) <= cells
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"


def test_unknown_names_are_errors(spec):
    with pytest.raises(SpecError):
        spec.cell("no_such_cell")
    with pytest.raises(SpecError):
        spec.traffic("no_such_mix")
    with pytest.raises(SpecError):
        spec.metric_reader("no_such_metric")
    with pytest.raises(SpecError):
        spec.query("tpch_lineitem", "q99")
    with pytest.raises(SpecError):
        spec.entry("no_such_entry")


def test_new_files_are_found_without_editing_any(tmp_path):
    """A cell, a configuration, a traffic mix, a query template, a data
    set, an entry point and a metric, each added as files plus a
    BENCHMARK.json entry."""
    root = copy_benchmark(tmp_path)
    bench = os.path.join(root, "tpubench")
    before = snapshot_files(root)

    with open(os.path.join(bench, "datasets", "h2o_g1_wide.py"), "w") as f:
        f.write("from tpubench.spec import Spec\n"
                "_base = Spec().dataset('h2o_g1')\n"
                "TABLES, Oracle = _base.TABLES, _base.Oracle\n"
                "generate, bind = _base.generate, _base.bind\n")
    with open(os.path.join(bench, "entries", "sql_logged.py"), "w") as f:
        f.write("from tpubench.entries.sql import SqlEntry\n\n\n"
                "class SqlLoggedEntry(SqlEntry):\n"
                "    def query(self, q, req):\n"
                "        print(q.sql)\n"
                "        return super().query(q, req)\n\n\n"
                "ENTRY = SqlLoggedEntry\n")
    os.makedirs(os.path.join(bench, "queries", "h2o_wide"))
    with open(os.path.join(bench, "queries", "h2o_wide", "q2.sql"), "w") as f:
        f.write("SELECT id1, id2, SUM(v1) FROM x GROUP BY id1, id2\n")
    with open(os.path.join(bench, "configs", "h2o_wide.json"), "w") as f:
        json.dump({"name": "h2o_wide", "dataset": "h2o_g1_wide",
                   "queries": "h2o_wide", "rows": 5000, "row_group_rows": 2000,
                   "engine": {"device": "tpu", "result_cache": False},
                   "reduced": {}, "guarantees": {"device": DEVICE_GUARD}}, f)
    with open(os.path.join(bench, "traffic", "q2_closed2.json"), "w") as f:
        json.dump({"entry": "sql_logged", "loop": {"kind": "closed", "clients": 2},
                   "request": "query", "trace_seconds": 1,
                   "templates": [{"name": "q2", "params": {}}]}, f)
    with open(os.path.join(bench, "metrics", "groups_per_query.py"), "w") as f:
        f.write("def read(run):\n    return 7.0\n")

    def add(doc):
        doc["configs"].append({"name": "h2o_wide", "source": "test",
                               "file": "tpubench/configs/h2o_wide.json",
                               "reduced": [], "why": "test"})
        doc["workloads"].append({"name": "h2o_wide.q2", "config": "h2o_wide",
                                 "traffic": "q2_closed2", "chips": 1,
                                 "why": "test"})
        doc["per_layer"].append({"name": "groups_per_query", "unit": "groups",
                                 "better": "lower", "source": "program_counter",
                                 "layer": "operator_drivers",
                                 "moves": "request_p50_ms",
                                 "workloads": ["h2o_wide.q2"]})
    edit_json(os.path.join(root, "BENCHMARK.json"), add)

    spec = Spec(root)
    cfg = spec.config(spec.cell("h2o_wide.q2")["config"])
    assert list(spec.dataset(cfg["dataset"]).TABLES) == ["x"]
    entry = spec.entry(spec.traffic("q2_closed2")["entry"])
    assert entry.__name__ == "SqlLoggedEntry"
    assert issubclass(entry, entries.Entry)
    assert "GROUP BY id1, id2" in spec.query(cfg["queries"], "q2")
    assert spec.traffic("q2_closed2")["loop"]["clients"] == 2
    assert spec.metric_reader("groups_per_query")(None) == 7.0
    names = {m["name"] for m in spec.metrics_of("h2o_wide.q2", "per_layer")}
    assert "groups_per_query" in names and "h2o_q1_ms" not in names
    assert "groups_per_query" not in {
        m["name"] for m in spec.metrics_of("q1_sf10_warm", "per_layer")}
    after = snapshot_files(root)
    assert all(after[p] == content for p, content in before.items())
