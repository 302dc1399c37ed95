"""The data sets: deterministic in the seed, written to a file the
engine's reader gives back unchanged, and their oracles equal to the
engine at ~1e4 rows."""

import hashlib
import os
from types import SimpleNamespace

import numpy as np
import pytest

from bench_helpers import REPO
from tpubench import data as tdata
from tpubench.spec import Spec

ROWS = 12_000
ROW_GROUP = 5_000
BATCH = 2_048
SPEC = Spec(REPO)
DATASETS = ("tpch_lineitem", "h2o_g1")


def _plain(col):
    return col[0] if isinstance(col, tuple) else col


def _only_table(name):
    """(table name, its schema) of a data set of one table."""
    (table, schema), = SPEC.dataset(name).TABLES.items()
    return table, schema


def _columns(made, name):
    return made[name]["tables"][_only_table(name)[0]]


@pytest.fixture(scope="module")
def made():
    return {n: SPEC.dataset(n).generate(5, ROWS, threads=2) for n in DATASETS}


@pytest.fixture(scope="module")
def files(made, tmp_path_factory):
    """Each data set's Parquet file, as a run writes it."""
    out = {}
    for name in DATASETS:
        out[name] = str(tmp_path_factory.mktemp(name) / "table.parquet")
        tdata.write_parquet(_columns(made, name), out[name], ROW_GROUP)
    return out


@pytest.fixture(scope="module")
def contexts(files):
    """One engine context per data set over its file."""
    from datafusion_tpu.exec.context import ExecutionContext

    out = {}
    for name in DATASETS:
        ctx = ExecutionContext(device="cpu", batch_size=BATCH,
                               result_cache=False)
        ctx.register_parquet(_only_table(name)[0], files[name])
        out[name] = ctx
    return out


def _engine(contexts, name, queries, template, params):
    from datafusion_tpu.exec.materialize import collect

    ds = SPEC.dataset(name)
    sql = SPEC.query(queries, template).format(**ds.bind(template, params))
    return collect(contexts[name].sql(sql))


@pytest.mark.parametrize("name", DATASETS)
def test_generator_is_a_function_of_the_seed_alone(made, name):
    ds = SPEC.dataset(name)
    table, schema = _only_table(name)
    first = _columns(made, name)
    again = ds.generate(5, ROWS, threads=1)["tables"]
    other = ds.generate(6, ROWS, threads=2)["tables"][table]
    assert list(again) == [table] and list(again[table]) == list(schema)
    for col in schema:
        assert np.array_equal(_plain(first[col]), _plain(again[table][col]))
    assert any(not np.array_equal(_plain(first[c]), _plain(other[c]))
               for c in schema)


def test_lineitem_domains(made):
    c = _columns(made, "tpch_lineitem")
    assert c["l_quantity"].min() >= 1 and c["l_quantity"].max() <= 50
    assert np.array_equal(c["l_quantity"], np.floor(c["l_quantity"]))
    assert set(np.rint(c["l_discount"] * 100).astype(int)) <= set(range(11))
    assert set(np.rint(c["l_tax"] * 100).astype(int)) <= set(range(9))
    assert c["l_shipdate"][1][0] == "1992-01-02"
    assert c["l_shipdate"][1][-1] == "1998-12-01"
    # Q1 at DELTA 90 keeps ~96 % of the rows; Q6 a couple of per cent
    oracle = made["tpch_lineitem"]["oracle"]
    kept = sum(r[-1] for r in oracle.answer("q1", {"delta": 90}))
    assert 0.94 < kept / ROWS < 0.98
    assert len(oracle.answer("q1", {"delta": 90})) == 4


def test_h2o_domains(made):
    c = _columns(made, "h2o_g1")
    assert len(c["id1"][1]) == 100 and c["id1"][1][0] == "id001"
    assert len(c["id3"][1]) == ROWS // 100 and c["id3"][1][0] == "id0000000001"
    assert c["id4"].min() >= 1 and c["id4"].max() <= 100
    assert c["id6"].max() <= ROWS // 100
    assert set(np.unique(c["v1"])) <= set(range(1, 6))
    assert set(np.unique(c["v2"])) <= set(range(1, 16))
    assert np.array_equal(c["v3"], np.round(c["v3"], 6))


@pytest.mark.parametrize("name", DATASETS)
def test_the_engines_reader_gives_the_generated_columns_back(made, contexts, name):
    table, schema = _only_table(name)
    scan = contexts[name].datasources[table]
    assert scan.schema.names() == list(schema)
    got = {col: [] for col in schema}
    for b in scan.batches():
        for i, col in enumerate(schema):
            part = b.data[i][: b.num_rows]
            got[col].append(b.dicts[i].decode(part) if b.dicts[i] is not None
                            else part)
    for col, want in _columns(made, name).items():
        if isinstance(want, tuple):
            want = np.asarray(want[1], object)[want[0]]
        assert np.array_equal(np.concatenate(got[col]), want), col


@pytest.mark.parametrize("entry", ["sql", "serve"])
def test_a_resident_table_is_the_engines_reading_of_the_file(files, entry):
    """Batch sizes, dictionaries and schema are the reader's own: the
    benchmark cuts nothing itself."""
    from tpubench import entries

    e = SPEC.entry(entry)("cpu", {}, {"lineitem": files["tpch_lineitem"]},
                          entries.Spans())
    try:
        from datafusion_tpu.exec.datasource import ParquetDataSource

        scan = ParquetDataSource(files["tpch_lineitem"], None, e.ctx.batch_size)
        resident = e.ctx.datasources["lineitem"]
        assert repr(resident.schema) == repr(scan.schema)
        assert [(b.num_rows, b.capacity) for b in resident.batches()] == \
            [(b.num_rows, b.capacity) for b in scan.batches()]
        assert sum(b.num_rows for b in resident.batches()) == ROWS
    finally:
        e.close()


@pytest.mark.parametrize("delta", [60, 90, 120])
def test_q1_oracle_equals_the_engine(contexts, made, delta):
    got = _engine(contexts, "tpch_lineitem", "tpch_lineitem", "q1", {"delta": delta})
    oracle = made["tpch_lineitem"]["oracle"]
    assert oracle.check("q1", {"delta": delta}, got) is None
    assert oracle.check("q1", {"delta": delta - 30}, got) is not None


@pytest.mark.parametrize("year", [1993, 1994, 1995, 1996, 1997])
def test_q6_oracle_equals_the_engine_for_every_parameter(contexts, made, year):
    oracle = made["tpch_lineitem"]["oracle"]
    for d in range(2, 10):
        for qty in (24, 25):
            p = {"year": year, "discount_pct": d, "quantity": qty}
            got = _engine(contexts, "tpch_lineitem", "tpch_lineitem", "q6", p)
            assert oracle.check("q6", p, got) is None, p
    assert oracle.check("q6", {**p, "year": year - 1}, got) is not None


@pytest.mark.parametrize("question", ["q1", "q2", "q3", "q5"])
def test_h2o_oracle_equals_the_engine(contexts, made, question):
    got = _engine(contexts, "h2o_g1", "h2o_g1", question, {})
    assert made["h2o_g1"]["oracle"].check(question, {}, got) is None
    got.columns[-1] = got.columns[-1] + 1
    assert made["h2o_g1"]["oracle"].check(question, {}, got) is not None


def _files(ds, name, seed, rows, row_group, root):
    made = tdata.prepare(ds, name, seed, rows, root, threads=2)
    return (made, *tdata.parquet_files(made, row_group))


def test_oracle_cubes_survive_the_file_cache(tmp_path):
    ds = SPEC.dataset("tpch_lineitem")
    args = (ds, "tpch_lineitem", 9, ROWS, ROW_GROUP, str(tmp_path))
    first, paths, rows = _files(*args)
    again, paths2, rows2 = _files(*args)
    assert (first["cached"], again["cached"]) == (False, True)
    assert paths == paths2 and list(paths) == ["lineitem"]
    assert paths["lineitem"].startswith(tdata.data_dir(str(tmp_path)))
    # the row counts are the files' own: found again without the columns
    assert rows == rows2 == {"lineitem": ROWS} and "tables" not in again
    p = {"year": 1995, "discount_pct": 4, "quantity": 25}
    assert first["oracle"].answer("q6", p) == again["oracle"].answer("q6", p)
    assert first["oracle"].answer("q1", {"delta": 75}) == \
        again["oracle"].answer("q1", {"delta": 75})


class _SumOracle:
    def __init__(self, total):
        self.total = int(total)

    def arrays(self):
        return {"total": np.array(self.total)}

    @classmethod
    def from_arrays(cls, arrays):
        return cls(arrays["total"])


# a data set of two tables, the second a quarter of the first
TWO_TABLES = SimpleNamespace(
    TABLES={"big": {"k": "i64"}, "small": {"k": "i64", "v": "f64"}},
    Oracle=_SumOracle,
    generate=lambda seed, rows, threads: {
        "tables": {"big": {"k": np.arange(rows) + seed},
                   "small": {"k": np.arange(rows // 4),
                             "v": np.full(rows // 4, 0.5)}},
        "oracle": _SumOracle(rows + seed)})


def test_a_data_set_of_two_tables_is_a_file_each_and_the_arrays_once(tmp_path):
    import pyarrow.parquet as pq

    args = (TWO_TABLES, "two", 3, 1_000, 400, str(tmp_path))
    first, paths, rows = _files(*args)
    again, paths2, rows2 = _files(*args)
    assert (first["cached"], again["cached"]) == (False, True)
    assert rows == rows2 == {"big": 1_000, "small": 250} and paths == paths2
    assert sorted(os.listdir(tdata.data_dir(str(tmp_path)))) == [
        "two_1000_seed3.big.parquet", "two_1000_seed3.npz",
        "two_1000_seed3.small.parquet"]
    assert pq.read_table(paths["small"]).column_names == ["k", "v"]
    assert pq.read_metadata(paths["big"]).num_row_groups == 3
    assert again["oracle"].total == first["oracle"].total == 1_003
    # one table's file lost: the set is made anew, not half found
    os.remove(paths["small"])
    assert _files(*args)[0]["cached"] is False


@pytest.mark.parametrize("ds,name,per_set", [
    (SPEC.dataset("tpch_lineitem"), "tpch_lineitem", 2),
    (TWO_TABLES, "two", 3),
])
def test_only_the_newest_sets_of_files_are_kept(tmp_path, ds, name, per_set):
    for seed in range(tdata.KEEP_FILES + 2):
        _files(ds, name, seed, 1_000, 500, str(tmp_path))
    left = sorted(os.listdir(tdata.data_dir(str(tmp_path))))
    assert len(left) == tdata.KEEP_FILES * per_set
    assert len([f for f in left if f.endswith(".npz")]) == tdata.KEEP_FILES
    assert {f.split("_seed")[1].split(".")[0] for f in left} == \
        {str(s) for s in range(2, tdata.KEEP_FILES + 2)}


def _sum(*arrays):
    m = hashlib.sha256()
    for a in arrays:
        a = np.ascontiguousarray(a)
        m.update(str(a.dtype).encode())
        m.update(a.tobytes())
    return m.hexdigest()[:16]


# seed 7, 10,000 rows, as `generate` of the parent of PR 27 (f69e170) made
# them: column -> checksum of its values (a string column: its int32 codes,
# then its dictionary), and the oracle's answers
AT_THE_PARENT = {
    "tpch_lineitem": ({
        "l_returnflag": "855b439860200df9", "l_linestatus": "a5f19041687863bd",
        "l_quantity": "95491f290246fdfe", "l_extendedprice": "e1d1b6ef1e46aa64",
        "l_discount": "69fa386859b4e1df", "l_tax": "3ec1b990fcf650d2",
        "l_shipdate": "85d5c4c6f2d87fab",
    }, {
        "q1": "e1301a7097e59d05",
        "q1 groups": [("A", "F", 2543), ("N", "F", 1166), ("N", "O", 3404),
                      ("R", "F", 2531)],
        "q6": [(373304.1944,)],
    }),
    "h2o_g1": ({
        "id1": "c9005599992a7c45", "id2": "60177f0e71a2ca4d",
        "id3": "0d844579709cdc4c", "id4": "356dfd591c2e2518",
        "id5": "07e83984e4402d69", "id6": "b55200cd88c50061",
        "v1": "f417622f45e4f18b", "v2": "fe6b0e8f1bceabe7",
        "v3": "32f3af89f39aa6dc",
    }, {
        "q1": ("6cad046396d506e3", 100), "q2": ("69c71c1061df56de", 6329),
        "q3": ("12ff57a03be505fc", 100), "q5": ("cdcdf85660ae4cd0", 100),
    }),
}


@pytest.mark.parametrize("name", DATASETS)
def test_the_same_seed_still_gives_the_parents_columns_and_answers(name):
    """Moving a data set to `TABLES` changed no byte of what it makes."""
    made = SPEC.dataset(name).generate(7, 10_000, threads=2)
    want_cols, want = AT_THE_PARENT[name]
    (table, cols), = made["tables"].items()
    assert table == _only_table(name)[0]
    got = {c: (_sum(v[0], np.asarray(v[1], dtype="S")) if isinstance(v, tuple)
               else _sum(v)) for c, v in cols.items()}
    assert got == want_cols
    oracle = made["oracle"]
    if name == "tpch_lineitem":
        q1 = oracle.answer("q1", {"delta": 90})
        assert _sum(np.array([r[2:] for r in q1], float)) == want["q1"]
        assert [r[:2] + (r[-1],) for r in q1] == want["q1 groups"]
        assert oracle.answer("q6", {"year": 1995, "discount_pct": 4,
                                    "quantity": 25}) == want["q6"]
    else:
        for q, (checksum, groups) in want.items():
            keys, vals = oracle.answer(q)
            assert (_sum(*keys, *vals), len(keys[0])) == (checksum, groups)
