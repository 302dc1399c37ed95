"""The data sets: deterministic in the seed, written to a file the
engine's reader gives back unchanged, and their oracles equal to the
engine at ~1e4 rows."""

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


@pytest.fixture(scope="module")
def made():
    return {n: SPEC.dataset(n).generate(5, ROWS, threads=2) for n in DATASETS}


@pytest.fixture(scope="module")
def files(made, tmp_path_factory):
    """Each data set's Parquet file, as a run writes it."""
    out = {}
    for name in DATASETS:
        out[name] = str(tmp_path_factory.mktemp(name) / "table.parquet")
        tdata.write_parquet(made[name]["columns"], out[name], ROW_GROUP)
    return out


@pytest.fixture(scope="module")
def contexts(files):
    """One engine context per data set over its file."""
    from datafusion_tpu.exec.context import ExecutionContext

    out = {}
    for name in DATASETS:
        ctx = ExecutionContext(device="cpu", batch_size=BATCH,
                               result_cache=False)
        ctx.register_parquet(SPEC.dataset(name).TABLE, files[name])
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
    again = ds.generate(5, ROWS, threads=1)["columns"]
    other = ds.generate(6, ROWS, threads=2)["columns"]
    assert list(again) == list(ds.SCHEMA)
    for col in ds.SCHEMA:
        assert np.array_equal(_plain(made[name]["columns"][col]), _plain(again[col]))
    assert any(not np.array_equal(_plain(made[name]["columns"][c]), _plain(other[c]))
               for c in ds.SCHEMA)


def test_lineitem_domains(made):
    c = made["tpch_lineitem"]["columns"]
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
    c = made["h2o_g1"]["columns"]
    assert len(c["id1"][1]) == 100 and c["id1"][1][0] == "id001"
    assert len(c["id3"][1]) == ROWS // 100 and c["id3"][1][0] == "id0000000001"
    assert c["id4"].min() >= 1 and c["id4"].max() <= 100
    assert c["id6"].max() <= ROWS // 100
    assert set(np.unique(c["v1"])) <= set(range(1, 6))
    assert set(np.unique(c["v2"])) <= set(range(1, 16))
    assert np.array_equal(c["v3"], np.round(c["v3"], 6))


@pytest.mark.parametrize("name", DATASETS)
def test_the_engines_reader_gives_the_generated_columns_back(made, contexts, name):
    ds = SPEC.dataset(name)
    scan = contexts[name].datasources[ds.TABLE]
    assert scan.schema.names() == list(ds.SCHEMA)
    got = {col: [] for col in ds.SCHEMA}
    for b in scan.batches():
        for i, col in enumerate(ds.SCHEMA):
            part = b.data[i][: b.num_rows]
            got[col].append(b.dicts[i].decode(part) if b.dicts[i] is not None
                            else part)
    for col, want in made[name]["columns"].items():
        if isinstance(want, tuple):
            want = np.asarray(want[1], object)[want[0]]
        assert np.array_equal(np.concatenate(got[col]), want), col


@pytest.mark.parametrize("entry", ["sql", "serve"])
def test_a_resident_table_is_the_engines_reading_of_the_file(files, entry):
    """Batch sizes, dictionaries and schema are the reader's own: the
    benchmark cuts nothing itself."""
    from tpubench import entries

    e = entries.ENTRIES[entry]("cpu", {}, "lineitem", entries.Spans(),
                               files["tpch_lineitem"])
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


def _file(ds, seed, rows, row_group, root):
    made = tdata.prepare(ds, "tpch_lineitem", seed, rows, root, threads=2)
    return made, tdata.parquet_file(made, row_group)


def test_oracle_cubes_survive_the_file_cache(tmp_path):
    ds = SPEC.dataset("tpch_lineitem")
    first, path = _file(ds, 9, ROWS, ROW_GROUP, str(tmp_path))
    again, path2 = _file(ds, 9, ROWS, ROW_GROUP, str(tmp_path))
    assert (first["cached"], again["cached"]) == (False, True)
    assert path == path2 and path.startswith(tdata.data_dir(str(tmp_path)))
    assert "columns" not in again
    p = {"year": 1995, "discount_pct": 4, "quantity": 25}
    assert first["oracle"].answer("q6", p) == again["oracle"].answer("q6", p)
    assert first["oracle"].answer("q1", {"delta": 75}) == \
        again["oracle"].answer("q1", {"delta": 75})


def test_only_the_newest_files_are_kept(tmp_path):
    import os

    ds = SPEC.dataset("tpch_lineitem")
    for seed in range(tdata.KEEP_FILES + 2):
        _file(ds, seed, 1_000, 500, str(tmp_path))
    left = sorted(os.listdir(tdata.data_dir(str(tmp_path))))
    assert len([f for f in left if f.endswith(".parquet")]) == tdata.KEEP_FILES
    assert len([f for f in left if f.endswith(".npz")]) == tdata.KEEP_FILES
