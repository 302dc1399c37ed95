"""Shared by the tests of the benchmark (`tests/tpubench/`): a copy of the
benchmark in a temporary root, and a run of the harness in this process."""

import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def copy_benchmark(tmp_path) -> str:
    """BENCHMARK.json and tpubench/ copied under `tmp_path`; the root."""
    root = str(tmp_path / "root")
    os.makedirs(root)
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(REPO, "tpubench"),
                    os.path.join(root, "tpubench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    return root


def edit_json(path: str, change) -> None:
    with open(path) as f:
        doc = json.load(f)
    change(doc)
    with open(path, "w") as f:
        json.dump(doc, f)


def run_harness(capsys, root, *argv) -> tuple:
    """(exit code, parsed last stdout line or None, all stdout)."""
    from tpubench.harness import main

    capsys.readouterr()
    code = main(list(argv), root=root)
    out = capsys.readouterr().out
    last = out.strip().splitlines()[-1] if out.strip() else ""
    try:
        return code, json.loads(last), out
    except ValueError:
        return code, None, out


# -- a deployment of two tables, added to a copy as files ---------------------
# What a later `model_config` PR brings: a data set with its oracle, a query
# template, a configuration, mixes, an entry point and metric readers, each a
# new file, and entries in BENCHMARK.json; no file of the copy is edited.

STAR2_DATASET = '''\
"""A fact table and a dimension a quarter of its size, joined on an i64 key."""
import numpy as np

TABLES = {"fact": {"f_key": "i64", "f_v": "f64"},
          "dim": {"d_key": "i64", "d_grp": "i64"}}
GROUPS = 8


def generate(seed, rows, threads=1):
    rng = np.random.default_rng(seed)
    n_dim = max(rows // 4, 1)
    dim = {"d_key": rng.permutation(n_dim).astype(np.int64),
           "d_grp": rng.integers(0, GROUPS, n_dim, dtype=np.int64)}
    fact = {"f_key": rng.integers(0, n_dim, rows, dtype=np.int64),
            "f_v": np.round(rng.uniform(0.0, 100.0, rows), 2)}
    return {"tables": {"fact": fact, "dim": dim}, "oracle": Oracle(fact, dim)}


def bind(template, params):
    return {}


class Oracle:
    def __init__(self, fact, dim):
        grp_of_key = np.empty(len(dim["d_key"]), np.int64)
        grp_of_key[dim["d_key"]] = dim["d_grp"]
        grp = grp_of_key[fact["f_key"]]
        self.count = np.bincount(grp, minlength=GROUPS)
        self.total = np.bincount(grp, weights=fact["f_v"], minlength=GROUPS)

    def answer(self, template, params):
        return [(g, int(n), float(t)) for g, (n, t)
                in enumerate(zip(self.count, self.total)) if n]

    def check(self, template, params, result, worst=None):
        from tpubench.check import diff_rows

        return diff_rows(result.to_rows(), self.answer(template, params),
                         worst=worst)
'''
STAR2_SQL = ("SELECT d_grp, COUNT(1), SUM(f_v) FROM fact JOIN dim "
             "ON fact.f_key = dim.d_key GROUP BY d_grp\n")
STAR2_ENTRY = '''\
"""`sql` under another name: an entry point that arrives as a file."""
from tpubench.entries.sql import SqlEntry


class SqlAgainEntry(SqlEntry):
    pass


ENTRY = SqlAgainEntry
'''
STAR2_ENTRY_POINTS = ("sql", "cold", "serve", "sql_again")
DEVICE_GUARD = {"sentence": "every request reaches the device",
                "must_launch": "device.launches",
                "must_be_zero": ["aggregate.host_routed_slots",
                                 "sort.host_routed_runs"]}


def _write(root, rel, text):
    path = os.path.join(root, "tpubench", *rel.split("/"))
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        f.write(text)


def add_star2(root: str) -> None:
    """The two-table deployment `star2_small` with a cell `star2.<entry>`
    for each of `STAR2_ENTRY_POINTS`, and two counts as per-layer metrics."""
    _write(root, "datasets/star2.py", STAR2_DATASET)
    _write(root, "queries/star2/join_grp.sql", STAR2_SQL)
    _write(root, "entries/sql_again.py", STAR2_ENTRY)
    _write(root, "configs/star2_small.json", json.dumps({
        "name": "star2_small", "dataset": "star2", "queries": "star2",
        "rows": 4000, "row_group_rows": 1500,
        "engine": {"device": "tpu", "result_cache": False}, "reduced": {},
        "guarantees": {"device": DEVICE_GUARD}}))
    for name in ("rows_scanned", "bytes_needed"):
        _write(root, f"metrics/{name}.py",
               f"def read(run):\n    return run.{name}\n")
    for entry in STAR2_ENTRY_POINTS:
        _write(root, f"traffic/join_{entry}.json", json.dumps({
            "entry": entry, "loop": {"kind": "closed", "clients": 1},
            "request": "query", "trace_seconds": 1,
            "templates": [{"name": "join_grp", "params": {}}]}))

    def add(doc):
        cells = ["star2." + e for e in STAR2_ENTRY_POINTS]
        doc["configs"].append({
            "name": "star2_small", "source": "test", "reduced": [],
            "file": "tpubench/configs/star2_small.json", "why": "test"})
        doc["workloads"] += [
            {"name": "star2." + e, "config": "star2_small",
             "traffic": "join_" + e, "chips": 1, "why": "test"}
            for e in STAR2_ENTRY_POINTS]
        doc["per_layer"] += [
            {"name": n, "unit": u, "better": "lower",
             "source": "program_counter", "layer": "kernels",
             "moves": "rows_per_s", "workloads": cells}
            for n, u in (("rows_scanned", "rows"), ("bytes_needed", "B"))]
    edit_json(os.path.join(root, "BENCHMARK.json"), add)


def snapshot_files(root: str) -> dict:
    """{path: bytes} of every file under the copy's tpubench/."""
    out = {}
    for d, _, fs in os.walk(os.path.join(root, "tpubench")):
        if "__pycache__" in d:
            continue
        for f in fs:
            with open(os.path.join(d, f), "rb") as fh:
                out[os.path.join(d, f)] = fh.read()
    return out
