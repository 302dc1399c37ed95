"""A Parquet scan hands on whole batches (`io/readers.whole_batches`):
pyarrow cuts the batch that straddles a row group's end in two wherever
a column is read dictionary-encoded, the reader joins the two pieces
before anything keeps them, and a resident table then folds into full
ladder groups: Q1's launches follow the table's shape classes, not its
row groups."""

import json
import math
import os
import sys

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from datafusion_tpu.exec.context import ExecutionContext
from datafusion_tpu.exec.datasource import MemoryDataSource
from datafusion_tpu.exec.materialize import collect
from datafusion_tpu.io.readers import ParquetReader, whole_batches
from datafusion_tpu.utils.metrics import METRICS
from tpubench import data as tdata
from tpubench.spec import Spec

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "tpubench"))
from bench_helpers import copy_benchmark, run_harness  # noqa: E402

ROWS, GROUP_ROWS = 4_000, 1_000
# the last 100 rows of the first row group: with a batch size that does
# not divide the group they are one of pyarrow's two pieces, alone
NULLS = slice(900, 1_000)


def counted(name: str) -> int:
    return METRICS.counts.get(name, 0)


@pytest.fixture(scope="module")
def files(tmp_path_factory):
    """{kind: (path, columns as the file holds them)}: `strings` has a
    string column whose values differ row group by row group (each
    group's pages bring their own dictionary, the reader's grows piece
    by piece), `nulls` a nullable int64 beside it whose nulls all fall
    in `NULLS`, `numbers` no string column at all (pyarrow cuts
    nothing)."""
    d = tmp_path_factory.mktemp("recut")
    x = np.arange(ROWS, dtype=np.int64)
    s = np.array([f"g{i // GROUP_ROWS}-{i % 7}" for i in range(ROWS)],
                 dtype=object)
    n = [None if NULLS.start <= i < NULLS.stop else int(i) for i in x]
    tables = {
        "strings": {"x": x, "s": s},
        "nulls": {"x": x, "s": s, "n": n},
        "numbers": {"x": x, "y": x * 0.5},
    }
    out = {}
    for kind, cols in tables.items():
        path = str(d / f"{kind}.parquet")
        pq.write_table(pa.table({k: pa.array(v) for k, v in cols.items()}),
                       path, row_group_size=GROUP_ROWS)
        out[kind] = (path, cols)
    return out


def joined_pieces(pieces: list, size: int) -> int:
    """How many of a scan's pieces end up in a batch made of more than
    one of them, reckoned from their row counts alone."""
    bounds = np.cumsum([0] + pieces)
    total, joined = int(bounds[-1]), 0
    for lo in range(0, total, size):
        hi = min(total, lo + size)
        parts = sum(1 for a, b in zip(bounds[:-1], bounds[1:])
                    if a < hi and b > lo)
        joined += parts if parts > 1 else 0
    return joined


# 250 and 500 divide the row group, 300 and 384 do not; 4,096 holds the
# whole file
@pytest.mark.parametrize("size", [250, 300, 384, 500, 4_096])
@pytest.mark.parametrize("kind,groups", [
    ("strings", None), ("nulls", None), ("numbers", None),
    ("strings", [0, 2, 3]), ("nulls", [0, 3])])
def test_a_parquet_scan_yields_whole_batches(files, kind, groups, size):
    path, cols = files[kind]
    rows = np.concatenate([
        np.arange(g * GROUP_ROWS, (g + 1) * GROUP_ROWS)
        for g in (range(ROWS // GROUP_ROWS) if groups is None else groups)])
    pieces = list(ParquetReader(
        path, batch_size=size, row_groups=groups).batches(whole=False))
    cut = [b.num_rows for b in pieces]
    assert sum(cut) == len(rows)
    if kind != "numbers" and GROUP_ROWS % size and size < GROUP_ROWS:
        assert any(n != size for n in cut[:-1])  # pyarrow did cut

    before = counted("scan.recut.pieces")
    reader = ParquetReader(path, batch_size=size, row_groups=groups)
    got = list(reader.batches())
    assert counted("scan.recut.pieces") - before == joined_pieces(cut, size)
    assert [b.num_rows for b in got] == (
        [size] * (len(rows) // size)
        + ([len(rows) % size] if len(rows) % size else []))

    # rows, codes and nulls as the file has them, in its order
    x = np.concatenate([np.asarray(b.data[0])[:b.num_rows] for b in got])
    assert x.tolist() == np.asarray(cols["x"])[rows].tolist()
    if kind == "numbers":
        y = np.concatenate([np.asarray(b.data[1])[:b.num_rows] for b in got])
        assert y.tolist() == np.asarray(cols["y"])[rows].tolist()
        return
    assert len({id(b.dicts[1]) for b in got}) == 1
    s = np.concatenate([
        b.dicts[1].decode(np.asarray(b.data[1])[:b.num_rows]) for b in got])
    assert list(s) == list(cols["s"][rows])
    if kind == "nulls":
        valid = np.concatenate([
            np.ones(b.num_rows, bool) if b.validity[2] is None
            else np.asarray(b.validity[2])[:b.num_rows] for b in got])
        want = np.array([cols["n"][i] is not None for i in rows])
        assert valid.tolist() == want.tolist()
        n = np.concatenate([np.asarray(b.data[2])[:b.num_rows] for b in got])
        assert n[want].tolist() == [cols["n"][i] for i in rows[want]]
        # a validity array only on the batches that hold a null
        lo = 0
        for b in got:
            has = not want[lo:lo + b.num_rows].all()
            assert (b.validity[2] is not None) == has
            lo += b.num_rows
    assert all(b.validity[0] is None and b.validity[1] is None for b in got)


@pytest.mark.parametrize("size", [250, 300, 384, 500])
@pytest.mark.parametrize("kind", ["strings", "nulls", "numbers"])
def test_a_batch_that_arrives_whole_is_not_copied(files, kind, size):
    """The pieces as pyarrow cut them, through the reader's own re-cut:
    a piece of `size` rows that finds nothing waiting comes out as the
    same object, and every other output is made of the cut ones."""
    path, _ = files[kind]
    pieces = list(ParquetReader(path, batch_size=size).batches(whole=False))
    out = list(whole_batches(iter(pieces), size))
    at, whole = 0, []
    for p in pieces:
        if p.num_rows == size and at % size == 0:
            whole.append(p)
        at += p.num_rows
    assert whole, "the file has batches pyarrow left alone"
    came, alone = {id(p) for p in pieces}, {id(p) for p in whole}
    same = [b for b in out if id(b) in came]
    assert {id(b) for b in same} >= alone
    # nothing else comes out as it came but the scan's short last batch
    assert all(id(b) in alone or b is out[-1] for b in same)
    for b in out:
        if id(b) not in came:  # a joined batch owns its arrays
            assert not any(np.shares_memory(b.data[0], p.data[0])
                           for p in pieces)


SPEC = Spec()
LINEITEM = SPEC.dataset("tpch_lineitem")
# pieces of 1,808 and 2,288 rows at a row group's end: capacities 2,048 and
# 4,096, two shape classes (under `MIN_CAPACITY` rows all are one)
Q1_ROWS, Q1_GROUP_ROWS, Q1_BATCH = 240_000, 10_000, 4_096


def q1(delta: int = 90) -> str:
    return SPEC.query("tpch_lineitem", "q1").format(
        **LINEITEM.bind("q1", {"delta": delta}))


@pytest.fixture(scope="module")
def lineitem(tmp_path_factory):
    """(Parquet file of 24 row groups, oracle)."""
    made = LINEITEM.generate(11, Q1_ROWS, threads=1)
    path = str(tmp_path_factory.mktemp("q1") / "lineitem.parquet")
    tdata.write_parquet(made["tables"]["lineitem"], path, Q1_GROUP_ROWS)
    return path, made["oracle"]


def test_q1_over_a_resident_table_launches_by_shape_class(lineitem):
    """The benchmark's resident table (`tpubench/entries/sql.py`: a
    `MemoryDataSource` of the engine's own scan) over a file of 24 row
    groups at a batch size that divides none: one shape class, so the
    launches are the flushes' and not the row groups'."""
    path, oracle = lineitem
    ctx = ExecutionContext(batch_size=Q1_BATCH, result_cache=False)
    ctx.register_parquet("lineitem", path)
    before = counted("scan.recut.pieces")
    scan = ctx.datasources["lineitem"]
    batches = list(scan.batches())
    ctx.register_datasource("lineitem", MemoryDataSource(scan.schema, batches))
    n = math.ceil(Q1_ROWS / Q1_BATCH)
    assert [b.num_rows for b in batches] == (
        [Q1_BATCH] * (n - 1) + [Q1_ROWS - (n - 1) * Q1_BATCH])
    assert counted("scan.recut.pieces") - before == 2 * (
        Q1_ROWS // Q1_GROUP_ROWS - 1)
    for delta in (90, 60):
        c0 = dict(METRICS.counts)
        got = collect(ctx.sql(q1(delta)))
        assert oracle.check("q1", {"delta": delta}, got) is None
        launches = (counted("device.launches.agg.group")
                    - c0.get("device.launches.agg.group", 0)
                    + counted("device.launches.agg")
                    - c0.get("device.launches.agg", 0))
        assert 1 <= launches <= math.ceil(n / 256) + 2
        groups = counted("fused.groups") - c0.get("fused.groups", 0)
        folded = (counted("fused.group_batches")
                  - c0.get("fused.group_batches", 0))
        assert folded / groups > 8


def test_q1_through_the_server_launches_by_shape_class(lineitem):
    """The same through `Server.submit`: the pinned table is the
    reader's scan, two Q1s meet in one megabatch (of one DELTA: a date
    literal is its program's own, not a parameter), and its launches
    are the flushes'."""
    path, oracle = lineitem
    ctx = ExecutionContext(batch_size=Q1_BATCH, result_cache=False)
    ctx.register_parquet("lineitem", path)
    n = math.ceil(Q1_ROWS / Q1_BATCH)
    srv = ctx.serve(workers=1, window_s=0.5, megabatch_max=8)
    try:
        srv.submit(q1(75)).result(timeout=120)  # pins the table
        before = counted("serve.megabatch_launches")
        tickets = [(d, srv.submit(q1(d))) for d in (90, 90)]
        for d, t in tickets:
            assert oracle.check(
                "q1", {"delta": d}, t.result(timeout=120)) is None
    finally:
        srv.stop()
    launched = counted("serve.megabatch_launches") - before
    assert 1 <= launched <= math.ceil(n / 256) + 2


@pytest.fixture
def jax_cache_threshold_as_found():
    """A harness run sets JAX's persistent-cache threshold for its
    process (`tests/tpubench/conftest.py` puts it back the same way)."""
    import jax

    name = "jax_persistent_cache_min_compile_time_secs"
    before = getattr(jax.config, name)
    yield
    jax.config.update(name, before)


def test_q12s_rehearsal_probes_whole_batches(
        capsys, tmp_path, jax_cache_threshold_as_found):
    """What `tests/tpubench/test_tpubench_q12_join.py::test_rehearsal_
    past_2_20_key_slots_probes_on_the_device` holds (an accepted file,
    marked in `tests/conftest.py`: its line 94 counts 11 probe launches,
    the 10 batches of 1.2 M rows and the one pyarrow cut), with the
    count a whole-batch scan gives: 10 probes a query, and the
    aggregate above them two launches.  Over the same copy of the
    benchmark, the join's five readers at the end of `per_layer`, so
    the one digit is all that differs."""
    from test_tpubench_q12_join import _with_join_metrics

    code, line, out = run_harness(
        capsys, _with_join_metrics(tmp_path), "--workload", "q12_sf10_join",
        "--seed", "2147483659", "--seconds", "0.3", "--trace", "1",
        "--rehearse-rows", "1200000")
    assert code == 0
    detail = json.loads(next(
        l for l in out.splitlines() if "] detail {" in l).split("] detail ", 1)[1])
    assert line["correct"] is True and line["failed"] == 0
    counts, queries = detail["counts"], line["attempted"]
    assert queries >= 1
    assert counts["join.probe.rows"] == 1_200_000 * queries
    assert "join.host_probe.rows" not in counts
    assert counts["join.build.reuse"] == queries  # built in warm-up only
    assert not any(c in counts for c in (
        "join.build.rows", "join.build.bytes", "device.launches.join.build"))
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
    assert line["metrics"]["join_probe_launches_per_query"]["value"] == 10
    assert counts["device.launches.join.probe"] == 10 * queries
    # nine whole batches fold in one launch; the short last one (20,352
    # rows: another capacity) takes its own
    assert counts["device.launches.agg.group"] == queries
    assert counts["device.launches.agg"] == queries
