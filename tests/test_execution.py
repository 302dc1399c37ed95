"""End-to-end execution tests.

Models the reference's operator/integration tests
(`projection.rs:85-107`: real file fixtures, no mocks) and its
example-as-test (`examples/csv_sql.rs` — the uk_cities query is the
canonical smoke-proof of the full pipeline).
"""

import os

import numpy as np
import pytest

from datafusion_tpu import DataType, ExecutionContext, Field, Schema


@pytest.fixture
def ctx(test_data_dir):
    c = ExecutionContext(batch_size=1024)
    c.register_csv(
        "cities",
        os.path.join(test_data_dir, "uk_cities.csv"),
        Schema(
            [
                Field("city", DataType.UTF8, False),
                Field("lat", DataType.FLOAT64, False),
                Field("lng", DataType.FLOAT64, False),
            ]
        ),
        has_header=False,
    )
    c.register_csv(
        "people",
        os.path.join(test_data_dir, "people.csv"),
        Schema(
            [
                Field("id", DataType.INT32, False),
                Field("first_name", DataType.UTF8, False),
            ]
        ),
        has_header=True,
    )
    c.register_csv(
        "null_test",
        os.path.join(test_data_dir, "null_test.csv"),
        Schema(
            [
                Field("c_int", DataType.INT32, True),
                Field("c_float", DataType.FLOAT64, True),
                Field("c_string", DataType.UTF8, True),
                Field("c_bool", DataType.BOOLEAN, True),
            ]
        ),
        has_header=True,
    )
    c.register_csv(
        "numerics",
        os.path.join(test_data_dir, "numerics.csv"),
        Schema(
            [
                Field("a", DataType.INT64, False),
                Field("b", DataType.INT64, False),
                Field("a_f", DataType.FLOAT64, False),
                Field("b_f", DataType.FLOAT64, False),
            ]
        ),
        has_header=True,
    )
    return c


def test_csv_sql_example(ctx):
    # the reference's examples/csv_sql.rs workload — its only end-to-end proof
    t = ctx.sql_collect(
        "SELECT city, lat, lng, lat + lng FROM cities "
        "WHERE lat > 51.0 AND lat < 53"
    )
    assert t.schema.names() == ["city", "lat", "lng", "binary_expr"]
    rows = t.to_rows()
    assert len(rows) == 18  # uk_cities.csv rows with 51 < lat < 53
    for _city, lat, lng, s in rows:
        assert 51.0 < lat < 53.0
        assert s == pytest.approx(lat + lng)
    assert any(r[0].startswith("Solihull") for r in rows)


def test_projection_all_columns(ctx):
    # ported from reference projection.rs:85-107
    t = ctx.sql_collect("SELECT id FROM people")
    assert t.schema.names() == ["id"]
    assert t.column_values(0) == list(range(1, 11))


def test_select_star(ctx):
    t = ctx.sql_collect("SELECT * FROM people")
    rows = t.to_rows()
    assert len(rows) == 10
    assert rows[:4] == [(1, "Andy"), (2, "Brian"), (3, "Chris"), (4, "Donna")]
    assert rows[-1] == (10, "Juliet")


def test_string_filter(ctx):
    t = ctx.sql_collect("SELECT id FROM people WHERE first_name = 'Brian'")
    assert t.column_values(0) == [2]
    t = ctx.sql_collect("SELECT id FROM people WHERE first_name != 'Brian'")
    assert t.column_values(0) == [1] + list(range(3, 11))
    # ordered comparison on strings via dictionary lookup table
    t = ctx.sql_collect("SELECT first_name FROM people WHERE first_name >= 'Gary'")
    assert sorted(t.column_values(0)) == ["Gary", "Helen", "Irene", "Juliet"]


def test_arithmetic(ctx):
    t = ctx.sql_collect("SELECT a + b, a - b, a * b, a_f / b_f FROM numerics")
    rows = t.to_rows()
    assert rows[0][0] == 5 and rows[0][1] == -1 and rows[0][2] == 6
    assert rows[0][3] == pytest.approx(3.14 / -2.13)


def test_int_division_and_modulus(ctx):
    t = ctx.sql_collect("SELECT b / a, b % a FROM numerics WHERE a > 0")
    # rows where a>0: (2,3) and (5,5)
    assert t.to_rows() == [(1, 1), (1, 0)]


def test_nulls(ctx):
    t = ctx.sql_collect("SELECT c_int, c_float, c_string FROM null_test")
    vals = t.column_values(1)
    assert vals[2] is None  # row 3 has empty c_float
    assert t.column_values(2)[3] is None  # row 4 has empty c_string
    t = ctx.sql_collect("SELECT c_int FROM null_test WHERE c_float IS NULL")
    assert t.column_values(0) == [3]
    t = ctx.sql_collect("SELECT c_int FROM null_test WHERE c_float IS NOT NULL")
    assert t.column_values(0) == [1, 2, 4, 5]


def test_null_comparison_drops_rows(ctx):
    # SQL: a comparison with NULL input is NULL -> row filtered out
    t = ctx.sql_collect("SELECT c_int FROM null_test WHERE c_float > 0.0")
    assert t.column_values(0) == [1, 2, 4, 5]


def test_global_aggregates(ctx):
    t = ctx.sql_collect(
        "SELECT MIN(lat), MAX(lat), SUM(lat), AVG(lat), COUNT(1) FROM cities"
    )
    lats = _cities_lats(ctx)
    row = t.to_rows()[0]
    assert row[0] == pytest.approx(lats.min())
    assert row[1] == pytest.approx(lats.max())
    assert row[2] == pytest.approx(lats.sum())
    assert row[3] == pytest.approx(lats.mean())
    assert row[4] == len(lats)


def test_aggregate_with_filter(ctx):
    t = ctx.sql_collect("SELECT COUNT(1), SUM(lat) FROM cities WHERE lat > 52")
    lats = _cities_lats(ctx)
    sel = lats[lats > 52]
    assert t.to_rows()[0][0] == len(sel)
    assert t.to_rows()[0][1] == pytest.approx(sel.sum())


def test_group_by_string(ctx):
    t = ctx.sql_collect(
        "SELECT c_bool, COUNT(1), SUM(c_int) FROM null_test GROUP BY c_bool"
    )
    by_key = {r[0]: r for r in t.to_rows()}
    # fixture: rows 1-3 true (c_int 1,2,3), rows 4-5 false (c_int 4,5)
    assert by_key[True][1] == 3 and by_key[True][2] == 6
    assert by_key[False][1] == 2 and by_key[False][2] == 9
    t2 = ctx.sql_collect(
        "SELECT first_name, COUNT(1) FROM people GROUP BY first_name"
    )
    rows2 = sorted(t2.to_rows())
    assert len(rows2) == 10
    assert rows2[:2] == [("Andy", 1), ("Brian", 1)]


def test_avg_of_nullable_column(ctx):
    t = ctx.sql_collect("SELECT AVG(c_float), COUNT(c_float) FROM null_test")
    row = t.to_rows()[0]
    # null row excluded from both
    assert row[1] == 4
    assert row[0] == pytest.approx((1.1 + 2.2 + 4.4 + 6.6) / 4)


def test_order_by(ctx):
    t = ctx.sql_collect("SELECT city, lat FROM cities ORDER BY lat DESC LIMIT 3")
    lats = [r[1] for r in t.to_rows()]
    assert lats == sorted(lats, reverse=True)
    assert len(lats) == 3
    all_lats = sorted(_cities_lats(ctx), reverse=True)
    assert lats == pytest.approx(all_lats[:3])


def test_order_by_string(ctx):
    t = ctx.sql_collect("SELECT first_name FROM people ORDER BY first_name DESC")
    assert t.column_values(0) == [
        "Juliet", "Irene", "Helen", "Gary", "Fiona",
        "Edward", "Donna", "Chris", "Brian", "Andy",
    ]


def test_limit(ctx):
    t = ctx.sql_collect("SELECT id FROM people LIMIT 2")
    assert t.column_values(0) == [1, 2]


def test_select_literal_no_table(ctx):
    t = ctx.sql_collect("SELECT 1")
    assert t.to_rows() == [(1,)]
    t = ctx.sql_collect("SELECT sqrt(9)")
    assert t.to_rows()[0][0] == pytest.approx(3.0)


def test_udf(ctx):
    import jax.numpy as jnp

    ctx.register_udf("plus_one", [DataType.FLOAT64], DataType.FLOAT64, lambda x: x + 1)
    t = ctx.sql_collect("SELECT plus_one(lat) FROM cities LIMIT 1")
    lats = _cities_lats(ctx)
    assert t.to_rows()[0][0] == pytest.approx(lats[0] + 1)


def test_ddl_create_external_table(ctx, test_data_dir):
    path = os.path.join(test_data_dir, "uk_cities.csv")
    res = ctx.sql(
        f"CREATE EXTERNAL TABLE uk (city VARCHAR(100) NOT NULL, "
        f"lat DOUBLE NOT NULL, lng DOUBLE NOT NULL) "
        f"STORED AS CSV WITHOUT HEADER ROW LOCATION '{path}'"
    )
    assert "uk" in ctx.datasources
    t = ctx.sql_collect("SELECT COUNT(1) FROM uk")
    assert t.to_rows()[0][0] == 37


def test_explain(ctx):
    res = ctx.sql("EXPLAIN SELECT id FROM people WHERE id > 2")
    s = repr(res)
    assert "Projection" in s and "Selection" in s and "TableScan" in s


def test_cast(ctx):
    t = ctx.sql_collect("SELECT CAST(id AS DOUBLE) FROM people")
    assert t.column_values(0) == [float(i) for i in range(1, 11)]
    assert t.schema.fields[0].data_type == DataType.FLOAT64


def test_cpu_device_explicit(test_data_dir):
    c = ExecutionContext(device="cpu")
    c.register_csv(
        "cities",
        os.path.join(test_data_dir, "uk_cities.csv"),
        Schema(
            [
                Field("city", DataType.UTF8, False),
                Field("lat", DataType.FLOAT64, False),
                Field("lng", DataType.FLOAT64, False),
            ]
        ),
        has_header=False,
    )
    t = c.sql_collect("SELECT COUNT(1) FROM cities")
    assert t.to_rows()[0][0] == 37


def _cities_lats(ctx):
    import csv

    ds = ctx.datasources["cities"]
    with open(ds.path) as f:
        return np.array([float(r[1]) for r in csv.reader(f)])

def test_count_star_vs_count_column(ctx):
    # COUNT(1) counts rows even where columns are NULL; COUNT(col)
    # counts non-null values of that column
    t = ctx.sql_collect("SELECT COUNT(1) FROM null_test")
    assert t.to_rows()[0][0] == 5
    t = ctx.sql_collect("SELECT COUNT(c_float) FROM null_test")
    assert t.to_rows()[0][0] == 4
    # COUNT(1) where column 0 itself has the NULL (c_int is col 0 and
    # fully populated here, so force the edge through c_float as arg 0
    # of the rewritten plan): the flag, not the arg, drives row counting
    t = ctx.sql_collect("SELECT COUNT(1), COUNT(c_float) FROM null_test WHERE c_int > 0")
    assert t.to_rows()[0] == (5, 4)


def test_group_by_null_keys(ctx):
    # SQL: NULL forms its own group, distinct from every real value
    t = ctx.sql_collect(
        "SELECT c_string, COUNT(1) FROM null_test GROUP BY c_string"
    )
    rows = t.to_rows()
    null_groups = [r for r in rows if r[0] is None]
    assert len(null_groups) == 1
    assert null_groups[0][1] == 2  # rows 4 and 5 have null c_string
    real = {r[0]: r[1] for r in rows if r[0] is not None}
    assert real == {"1.11": 1, "2.22": 1, "3.33": 1}


def test_or_with_null_operand(ctx):
    # TRUE OR NULL = TRUE: row 3 (c_float null, c_int 3) must survive
    t = ctx.sql_collect(
        "SELECT c_int FROM null_test WHERE c_int = 3 OR c_float > 100.0"
    )
    assert t.column_values(0) == [3]
    # FALSE AND NULL = FALSE is just dropped either way; but
    # NULL AND TRUE = NULL drops the row
    t = ctx.sql_collect(
        "SELECT c_int FROM null_test WHERE c_float > 0.0 AND c_int > 0"
    )
    assert t.column_values(0) == [1, 2, 4, 5]


class TestHighCardinalityGroupBy:
    def _mem_ctx(self, n, n_groups, seed=0, batch=4096):
        from datafusion_tpu.exec.batch import make_host_batch
        from datafusion_tpu.exec.datasource import MemoryDataSource

        rng = np.random.default_rng(seed)
        schema = Schema(
            [Field("k", DataType.INT64, False), Field("v", DataType.FLOAT64, False)]
        )
        keys = rng.integers(0, n_groups, n)
        vals = rng.uniform(0, 100, n)
        batches = [
            make_host_batch(
                schema,
                [keys[i : i + batch], vals[i : i + batch]],
                [None, None],
                [None, None],
            )
            for i in range(0, n, batch)
        ]
        ctx = ExecutionContext(batch_size=batch)
        ctx.register_datasource("t", MemoryDataSource(schema, batches))
        return ctx, keys, vals

    def test_many_groups_across_batches(self):
        # far above DENSE_GROUP_MAX: exercises the vectorized encoder
        # and the large-capacity update path over multiple batches
        n, n_groups = 40_000, 5_000
        ctx, keys, vals = self._mem_ctx(n, n_groups)
        t = ctx.sql_collect(
            "SELECT k, SUM(v), COUNT(1), MIN(v), AVG(v) FROM t GROUP BY k"
        )
        assert t.num_rows == len(np.unique(keys))
        got = {r[0]: r[1:] for r in t.to_rows()}
        for g in np.unique(keys)[:50]:
            sel = vals[keys == g]
            s, c, mn, av = got[int(g)]
            np.testing.assert_allclose(s, sel.sum(), rtol=1e-12)
            assert c == len(sel)
            np.testing.assert_allclose(mn, sel.min(), rtol=1e-12)
            np.testing.assert_allclose(av, sel.mean(), rtol=1e-12)

    def test_slot_sharing_sum_avg_count(self):
        # SUM(v)/AVG(v)/COUNT(v) share accumulator slots; results must
        # still be independent and correct
        from datafusion_tpu.exec.aggregate import AggregateRelation

        n, n_groups = 10_000, 7
        ctx, keys, vals = self._mem_ctx(n, n_groups)
        rel = ctx.sql("SELECT k, SUM(v), AVG(v), COUNT(1), COUNT(k) FROM t GROUP BY k")
        agg = rel
        while not isinstance(agg, AggregateRelation):
            agg = agg.child
        # 1 shared sum slot + 1 shared cnt slot for v, 1 cnt slot for k
        assert len(agg.slots) == 3
        from datafusion_tpu.exec.materialize import collect

        t = collect(rel)
        got = {r[0]: r[1:] for r in t.to_rows()}
        for g in range(n_groups):
            sel = vals[keys == g]
            s, av, c1, ck = got[g]
            np.testing.assert_allclose(s, sel.sum(), rtol=1e-12)
            np.testing.assert_allclose(av, sel.mean(), rtol=1e-12)
            assert c1 == len(sel) and ck == len(sel)

    def test_encoder_null_keys_and_growth(self):
        from datafusion_tpu.exec.aggregate import GroupKeyEncoder

        enc = GroupKeyEncoder(1)
        a = np.asarray([5, 7, 5, 9], np.int64)
        ids1 = enc.encode([a], [np.asarray([True, True, False, True])])
        # 5, 7, NULL, 9 -> 4 distinct groups (NULL groups separately)
        assert len(set(ids1.tolist())) == 4
        # same keys in a later batch map to the same ids
        ids2 = enc.encode([a], [np.asarray([True, True, False, True])])
        np.testing.assert_array_equal(ids1, ids2)
        # new keys get fresh ids, old ids stable
        ids3 = enc.encode([np.asarray([7, 100], np.int64)], [None])
        assert ids3[0] == ids1[1]
        assert ids3[1] == enc.num_groups - 1
        vals, valid = enc.key_column(0)
        assert valid is not None and not valid[ids1[2]]

    def test_float_group_keys_bitcast(self):
        # float GROUP BY keys must not merge 1.5 and 1.7 (value cast)
        from datafusion_tpu.exec.batch import make_host_batch
        from datafusion_tpu.exec.datasource import MemoryDataSource

        schema = Schema(
            [Field("k", DataType.FLOAT64, False), Field("v", DataType.INT64, False)]
        )
        k = np.asarray([1.5, 1.7, 2.5, 1.5, -0.0, 0.0])
        v = np.asarray([1, 2, 4, 8, 16, 32], np.int64)
        ctx2 = ExecutionContext()
        ctx2.register_datasource(
            "ft",
            MemoryDataSource(
                schema, [make_host_batch(schema, [k, v], [None, None], [None, None])]
            ),
        )
        t = ctx2.sql_collect("SELECT k, SUM(v) FROM ft GROUP BY k")
        got = {r[0]: r[1] for r in t.to_rows()}
        assert got == {1.5: 9, 1.7: 2, 2.5: 4, 0.0: 48}

    def test_string_minmax_many_groups_dict_growth(self):
        # >DENSE_GROUP_MAX groups with MIN/MAX over Utf8, where batch 2
        # grows the dictionary (ranks shift between merges)
        from datafusion_tpu.exec.batch import StringDictionary, make_host_batch
        from datafusion_tpu.exec.datasource import MemoryDataSource

        rng = np.random.default_rng(3)
        schema = Schema(
            [Field("k", DataType.INT64, False), Field("s", DataType.UTF8, False)]
        )
        n_groups = 200
        d = StringDictionary()
        all_k, all_s, batches = [], [], []
        # batch 1 uses words starting m..z; batch 2 adds a..l words that
        # sort BEFORE every earlier dictionary entry
        for lo, hi in ((12, 26), (0, 26)):
            k = rng.integers(0, n_groups, 3000)
            words = [
                chr(97 + rng.integers(lo, hi)) + f"{rng.integers(0, 100):02d}"
                for _ in range(3000)
            ]
            codes = d.encode(words)
            batches.append(
                make_host_batch(schema, [k, codes], [None, None], [None, d])
            )
            all_k.append(k)
            all_s.extend(words)
        keys = np.concatenate(all_k)
        words = np.asarray(all_s, dtype=object)
        ctx = ExecutionContext(batch_size=4096)
        ctx.register_datasource("st", MemoryDataSource(schema, batches))
        t = ctx.sql_collect("SELECT k, MIN(s), MAX(s), COUNT(1) FROM st GROUP BY k")
        assert t.num_rows == len(np.unique(keys))
        got = {r[0]: r[1:] for r in t.to_rows()}
        for g in np.unique(keys):
            sel = sorted(words[keys == g])
            mn, mx, c = got[int(g)]
            assert mn == sel[0] and mx == sel[-1] and c == len(sel)

    def test_nullable_values_many_groups(self):
        # null handling (cnt slots diverge from row counts) on the
        # sort-merge path, plus integer sums
        from datafusion_tpu.exec.batch import make_host_batch
        from datafusion_tpu.exec.datasource import MemoryDataSource

        rng = np.random.default_rng(5)
        schema = Schema(
            [Field("k", DataType.INT64, False), Field("v", DataType.INT64, True)]
        )
        n, n_groups = 20_000, 300
        keys = rng.integers(0, n_groups, n)
        vals = rng.integers(-50, 50, n)
        valid = rng.random(n) < 0.7
        batches = [
            make_host_batch(
                schema,
                [keys[i : i + 4096], vals[i : i + 4096]],
                [None, valid[i : i + 4096]],
                [None, None],
            )
            for i in range(0, n, 4096)
        ]
        ctx = ExecutionContext(batch_size=4096)
        ctx.register_datasource("nt", MemoryDataSource(schema, batches))
        t = ctx.sql_collect(
            "SELECT k, SUM(v), COUNT(v), COUNT(1), MAX(v) FROM nt GROUP BY k"
        )
        got = {r[0]: r[1:] for r in t.to_rows()}
        for g in range(0, n_groups, 17):
            m = (keys == g) & valid
            s, cv, c1, mx = got[g]
            assert s == vals[m].sum() and cv == m.sum()
            assert c1 == (keys == g).sum() and mx == vals[m].max()


class TestIdentityPassthrough:
    """Bare-column projections bypass the device kernel: exact values
    (f64 is emulated on TPU — an identity round trip perturbs ~1e-14)
    and no transfer for untouched columns."""

    def test_filtered_select_passes_input_arrays(self):
        import numpy as np

        from datafusion_tpu.datatypes import DataType, Field, Schema
        from datafusion_tpu.exec.batch import make_host_batch
        from datafusion_tpu.exec.context import ExecutionContext
        from datafusion_tpu.exec.datasource import MemoryDataSource

        schema = Schema(
            [Field("a", DataType.FLOAT64, False), Field("b", DataType.INT64, False)]
        )
        a = np.array([43.21, 12.34, 0.5])
        b = np.array([1, -2, 3], dtype=np.int64)
        batch = make_host_batch(schema, [a, b], [None, None], [None, None])
        ctx = ExecutionContext(device="cpu")
        ctx.register_datasource("t", MemoryDataSource(schema, [batch]))

        out = next(ctx.sql("SELECT a, b, a * 2 FROM t WHERE b > 0").batches())
        # identity outputs ARE the input arrays — no kernel round trip
        assert out.data[0] is batch.data[0]
        assert out.data[1] is batch.data[1]
        t = ctx.sql_collect("SELECT a, b FROM t WHERE b > 0")
        assert t.column_values(0) == [43.21, 0.5]

    def test_pure_selection_no_device_work(self):
        import numpy as np

        from datafusion_tpu.datatypes import DataType, Field, Schema
        from datafusion_tpu.exec.batch import make_host_batch
        from datafusion_tpu.exec.context import ExecutionContext
        from datafusion_tpu.exec.datasource import MemoryDataSource

        schema = Schema([Field("a", DataType.FLOAT64, False)])
        batch = make_host_batch(schema, [np.array([1.5, 2.5])], [None], [None])
        ctx = ExecutionContext(device="cpu")
        ctx.register_datasource("t", MemoryDataSource(schema, [batch]))
        out = next(ctx.sql("SELECT a FROM t").batches())
        assert out.data[0] is batch.data[0]
        assert out.mask is None  # no kernel ran at all


class TestLiteralParameterization:
    """WHERE x > <literal> must compile ONE kernel for every literal
    value (SURVEY §7 recompilation control; kernels.parameterize_exprs)."""

    def _src(self):
        import numpy as np

        from datafusion_tpu.datatypes import DataType, Field, Schema
        from datafusion_tpu.exec.batch import make_host_batch
        from datafusion_tpu.exec.datasource import MemoryDataSource

        rng = np.random.default_rng(13)
        schema = Schema(
            [Field("x", DataType.FLOAT64, False), Field("k", DataType.INT64, False)]
        )
        batch = make_host_batch(
            schema,
            [rng.uniform(0, 100, 5000), rng.integers(0, 7, 5000)],
            [None, None],
            [None, None],
        )
        return schema, MemoryDataSource(schema, [batch])

    def test_pipeline_cache_stays_one_across_literals(self):
        import numpy as np

        from datafusion_tpu.exec import kernels
        from datafusion_tpu.exec.context import ExecutionContext

        schema, src = self._src()
        ctx = ExecutionContext(device="cpu")
        ctx.register_datasource("t", src)

        def n_pipeline_cores():
            return sum(1 for k in kernels._REGISTRY if k[0] == "pipeline")

        want = None
        base = None
        for i, lit in enumerate(np.linspace(10.0, 90.0, 10)):
            out = ctx.sql_collect(f"SELECT x, x * 2.0 FROM t WHERE x > {lit:.4f}")
            if i == 0:
                base = n_pipeline_cores()
                want = out  # sanity below
            # correctness per literal
            assert all(r[0] > lit for r in out.to_rows())
        assert n_pipeline_cores() == base, "literal value leaked into cache key"

    def test_aggregate_cache_stays_one_across_literals(self):
        from datafusion_tpu.exec import kernels
        from datafusion_tpu.exec.context import ExecutionContext

        schema, src = self._src()
        ctx = ExecutionContext(device="cpu")
        ctx.register_datasource("t", src)

        def n_agg_cores():
            return sum(1 for k in kernels._REGISTRY if k[0] == "aggregate")

        base = None
        import numpy as np

        for i, lit in enumerate(np.linspace(0.1, 0.9, 10)):
            out = ctx.sql_collect(
                f"SELECT k, SUM(x * {lit:.3f}), AVG(x * {lit:.3f}) FROM t "
                f"WHERE x > {10 + i} GROUP BY k"
            )
            if i == 0:
                base = n_agg_cores()
            assert out.num_rows == 7
        assert n_agg_cores() == base

    def test_distinct_value_patterns_do_not_share_a_core(self):
        # SUM(x*a), AVG(x*b) with a != b must NOT reuse the a == b core
        # (different accumulator dedup structure)
        from datafusion_tpu.exec.context import ExecutionContext

        schema, src = self._src()
        ctx = ExecutionContext(device="cpu")
        ctx.register_datasource("t", src)
        same = ctx.sql_collect("SELECT k, SUM(x * 0.5), AVG(x * 0.5) FROM t GROUP BY k")
        diff = ctx.sql_collect("SELECT k, SUM(x * 0.5), AVG(x * 0.25) FROM t GROUP BY k")
        import numpy as np

        for rs, rd in zip(sorted(same.to_rows()), sorted(diff.to_rows())):
            assert rs[0] == rd[0]
            np.testing.assert_allclose(rd[2], rs[2] / 2, rtol=1e-9)


class TestWireCompression:
    """H2D wire codecs must be exactly lossless (exec/batch.py)."""

    def test_roundtrip_exact(self):
        import jax.numpy as jnp
        import numpy as np

        from datafusion_tpu.exec.batch import _decode_wire, _encode_wire

        rng = np.random.default_rng(0)
        cases = [
            np.array([True, False] * 512),
            np.arange(1024, dtype=np.int64),                    # narrow
            (np.arange(1024) * 10**9).astype(np.int64),         # raw
            np.linspace(0, 50, 1024).round(0),                  # f32-exact
            np.round(rng.uniform(900, 105000, 1024), 2),        # raw f64
            rng.integers(0, 11, 1024) / 100.0,                  # dict
            np.concatenate([[1.5, np.nan, -0.0, np.inf], np.zeros(1020)]),
            np.arange(1024, dtype=np.uint64) + 2**63,           # raw u64
            np.array([-129, 127] * 512, dtype=np.int64),        # int16
        ]
        for a in cases:
            spec, wires = _encode_wire(a)
            dec = np.asarray(
                _decode_wire(spec, tuple(jnp.asarray(w) for w in wires))
            )
            assert dec.dtype == a.dtype
            assert np.array_equal(dec, a, equal_nan=(a.dtype.kind == "f"))
            assert sum(w.nbytes for w in wires) <= a.nbytes

    def test_device_inputs_roundtrip(self):
        import numpy as np

        from datafusion_tpu.datatypes import DataType, Field, Schema
        from datafusion_tpu.exec.batch import device_inputs, make_host_batch

        schema = Schema(
            [
                Field("i", DataType.INT64, True),
                Field("f", DataType.FLOAT64, False),
                Field("d", DataType.FLOAT64, False),
            ]
        )
        rng = np.random.default_rng(1)
        cols = [
            rng.integers(-100, 100, 2048).astype(np.int64),
            np.round(rng.uniform(900, 105000, 2048), 2),
            rng.integers(0, 9, 2048) / 100.0,
        ]
        valid = rng.random(2048) > 0.1
        batch = make_host_batch(schema, cols, [valid, None, None], [None] * 3)
        data, validity, _ = device_inputs(batch)
        for got, want in zip(data, batch.data):
            assert np.array_equal(np.asarray(got), want)
        assert np.array_equal(np.asarray(validity[0]), batch.validity[0])
        # second call hits the batch cache
        data2, _, _ = device_inputs(batch)
        assert data2[0] is data[0]

    def test_decimal_wire(self):
        # fixed-point f64 (prices with 2 decimals) travels as int32 +
        # static scale, halving the bytes of the biggest TPC-H column
        import jax.numpy as jnp
        import numpy as np

        from datafusion_tpu.exec.batch import _decode_wire, _encode_wire

        rng = np.random.default_rng(3)
        a = np.round(rng.uniform(900.0, 104950.0, 4096), 2)
        spec, wires = _encode_wire(a)
        assert spec == ("decimal", 100)
        assert wires[0].dtype == np.int32
        dec = np.asarray(_decode_wire(spec, tuple(jnp.asarray(w) for w in wires)))
        assert np.array_equal(dec.view(np.int64), a.view(np.int64))
        # 3 decimals
        b = np.round(rng.uniform(-1000.0, 1000.0, 4096), 3)
        spec_b, _ = _encode_wire(b)
        assert spec_b == ("decimal", 1000)
        # not fixed-point: falls through to raw
        c = rng.standard_normal(4096)
        spec_c, _ = _encode_wire(c)
        assert spec_c == ("raw",)

    def test_decimal_wire_rejects_overflow_and_negzero(self):
        # values >= 2^31/scale in rows the strided sample skips must NOT
        # silently wrap through int32; -0.0 has no int32 image at all
        import numpy as np

        from datafusion_tpu.exec.batch import _encode_wire

        a = np.round(np.linspace(900.0, 104950.0, 8192), 2)
        a[1] = 50_000_000.00  # odd index: stride-2 sample misses it
        spec, wires = _encode_wire(a)
        if spec[0] == "decimal":
            codes, scale = wires
            got = codes.astype(np.float64) / scale[0]
            assert np.array_equal(got, a)
        else:
            assert spec == ("raw",)

        b = np.round(np.linspace(-10.0, 10.0, 4096), 2)
        b[7] = -0.0
        spec_b, wires_b = _encode_wire(b)
        if spec_b[0] == "decimal":
            codes, scale = wires_b
            got = codes.astype(np.float64) / scale[0]
            assert np.array_equal(got.view(np.int64), b.view(np.int64))
        # dict codec legitimately captures -0.0 bit-exactly; decimal
        # would have lost the sign

    def test_dict_preferred_over_decimal(self):
        # low-cardinality fixed-point (l_discount shape) must take the
        # 1-byte dict wire, not the 4-byte decimal wire
        import numpy as np

        from datafusion_tpu.exec.batch import _encode_wire

        rng = np.random.default_rng(11)
        a = rng.integers(0, 11, 8192) / 100.0
        spec, wires = _encode_wire(a)
        assert spec == ("dict",)

    def test_staged_aux_not_consumed_cross_relation(self, monkeypatch):
        # two different queries over the same long-lived batches: the
        # second must not consume the first's staged aux entries
        import numpy as np

        from datafusion_tpu.datatypes import DataType, Field, Schema
        from datafusion_tpu.exec.batch import StringDictionary, make_host_batch
        from datafusion_tpu.exec.context import ExecutionContext
        from datafusion_tpu.exec.datasource import MemoryDataSource

        schema = Schema([Field("s", DataType.UTF8, False),
                         Field("v", DataType.FLOAT64, False)])
        d = StringDictionary()
        rng = np.random.default_rng(2)
        strs = [f"k{i:03d}" for i in rng.integers(0, 40, 4096)]
        batch = make_host_batch(
            schema,
            [d.encode(strs), rng.uniform(0, 1, 4096)],
            [None, None],
            [d, None],
        )
        src = MemoryDataSource(schema, [batch])
        monkeypatch.setenv("DATAFUSION_TPU_PREFETCH", "1")
        ctx = ExecutionContext(device="cpu")
        ctx.register_datasource("t", src)
        r1 = ctx.sql_collect("SELECT s, SUM(v) FROM t WHERE s > 'k010' GROUP BY s")
        # a different aggregate over the same batches (different core,
        # different aux specs) — must recompute, not reuse r1's aux
        r2 = ctx.sql_collect("SELECT s, COUNT(1) FROM t WHERE s < 'k030' GROUP BY s")
        want = {}
        for s in strs:
            if s < "k030":
                want[s] = want.get(s, 0) + 1
        got = dict(r2.to_rows())
        assert got == want
        assert all(row[0] > "k010" for row in r1.to_rows())

    def test_packed_mask_pull(self):
        import jax.numpy as jnp
        import numpy as np

        from datafusion_tpu.datatypes import DataType, Field, Schema
        from datafusion_tpu.exec.batch import RecordBatch
        from datafusion_tpu.exec.materialize import _fetch_mask, _start_mask_pull

        rng = np.random.default_rng(9)
        mask = rng.random(1024) > 0.4
        schema = Schema([Field("x", DataType.INT64, False)])
        b = RecordBatch(
            schema,
            [jnp.arange(1024, dtype=jnp.int64)],
            [None],
            [None],
            num_rows=1000,
            mask=jnp.asarray(mask),
        )
        _start_mask_pull(b)
        assert "packed_mask" in b.cache
        got = _fetch_mask(b)
        assert np.array_equal(got, mask)

    def test_dict_wire_is_bit_exact(self):
        # -0.0 and NaN payloads survive the dictionary encoding
        # bit-for-bit (np.unique on float VALUES would collapse them)
        import jax.numpy as jnp
        import numpy as np

        from datafusion_tpu.exec.batch import _decode_wire, _encode_wire

        a = np.tile(np.array([0.01, 0.07, -0.0, np.nan, 104949.99, -0.03]), 256)
        spec, wires = _encode_wire(a)
        assert spec == ("dict",)
        dec = np.asarray(_decode_wire(spec, tuple(jnp.asarray(w) for w in wires)))
        assert np.array_equal(dec.view(np.int64), a.view(np.int64))
        # the values table is fixed-size: one decoder shape per capacity
        assert wires[1].shape == (256,)


class TestHostRouting:
    """Host-routed scalar projections / predicates (relation._host_routed):
    active only on accelerator devices, so the CPU suite forces the mode
    via monkeypatched `_is_accelerator` and asserts exact agreement with
    the device-kernel path on the same queries."""

    @pytest.fixture
    def host_mode(self, monkeypatch):
        import datafusion_tpu.exec.kernels as kernels
        import datafusion_tpu.exec.relation as relation

        monkeypatch.setattr(relation, "_is_accelerator", lambda device: True)
        # host-routing changes kernel cache keys; isolate so other tests
        # never see cores built in forced-host mode
        saved = dict(kernels._REGISTRY)
        kernels._REGISTRY.clear()
        yield
        kernels._REGISTRY.clear()
        kernels._REGISTRY.update(saved)

    def _both(self, make_ctx, sql):
        from datafusion_tpu.exec.materialize import collect

        return sorted(collect(make_ctx().sql(sql)).to_rows())

    def test_scalar_projection_matches_device(self, ctx, host_mode, test_data_dir):
        from datafusion_tpu.exec.materialize import collect

        sql = (
            "SELECT city, lat, lng, lat + lng, lat * 2 - lng "
            "FROM cities WHERE lat > 51.0 AND lat < 53.0"
        )
        got = sorted(collect(ctx.sql(sql)).to_rows())
        assert len(got) == 18
        for row in got:
            assert row[3] == row[1] + row[2]
            assert row[4] == row[1] * 2 - row[2]

    def test_int_division_modulus_parity(self, ctx, host_mode):
        # C-style truncation on negatives: host eval must match the
        # device kernel's lax.div/lax.rem semantics
        from datafusion_tpu.exec.materialize import collect

        rows = sorted(
            collect(
                ctx.sql("SELECT a, b, a / b, a % b FROM numerics WHERE b <> 0")
            ).to_rows()
        )
        for a, b, q, r in rows:
            # C-style truncation: round the true quotient toward zero
            want_q = -(-a // b) if (a < 0) != (b < 0) and a % b != 0 else a // b
            assert q == want_q, (a, b, q)
            assert r == a - want_q * b, (a, b, r)

    def test_string_predicate_aggregate(self, ctx, host_mode):
        # Utf8-vs-literal predicate host-routes through the dictionary
        # compare table on the aggregate path
        from datafusion_tpu.exec.materialize import collect

        got = collect(
            ctx.sql(
                "SELECT COUNT(1), MIN(city), MAX(lat) FROM cities "
                "WHERE city > 'M'"
            )
        ).to_rows()
        rows = collect(ctx.sql("SELECT city, lat FROM cities")).to_rows()
        want = [r for r in rows if r[0] > "M"]
        assert got[0][0] == len(want)
        assert got[0][1] == min(r[0] for r in want)
        assert got[0][2] == max(r[1] for r in want)

    def test_nullable_predicate_and_projection(self, ctx, host_mode):
        from datafusion_tpu.exec.materialize import collect

        got = collect(
            ctx.sql(
                "SELECT c_int, c_int + 1, c_float / 2 FROM null_test "
                "WHERE c_int IS NOT NULL"
            )
        ).to_rows()
        assert all(r[0] is not None for r in got)
        for r in got:
            assert r[1] == r[0] + 1

    def test_three_valued_logic_or_and(self, ctx, host_mode):
        # TRUE OR NULL = TRUE / FALSE AND NULL = FALSE: a null operand
        # must not poison a determined result (device bool_fn parity)
        from datafusion_tpu.exec.materialize import collect

        raw = collect(ctx.sql("SELECT c_int, c_float FROM null_test")).to_rows()

        got = collect(
            ctx.sql("SELECT COUNT(1) FROM null_test WHERE c_int > 0 OR c_float > 0")
        ).to_rows()[0][0]
        want = sum(
            1 for ci, cf in raw
            if (ci is not None and ci > 0) or (cf is not None and cf > 0)
        )
        assert got == want

        got = collect(
            ctx.sql(
                "SELECT COUNT(1) FROM null_test WHERE c_int > 0 AND c_float > 0"
            )
        ).to_rows()[0][0]
        want = sum(
            1 for ci, cf in raw
            if ci is not None and ci > 0 and cf is not None and cf > 0
        )
        assert got == want

    def test_literal_variants_share_compiled_core(self, ctx, host_mode):
        # host-routed predicates/projections must not fork the device
        # kernel per literal value (SURVEY §7 recompilation control)
        r1 = ctx.sql("SELECT lat + 1.0 FROM cities WHERE lat > 51.0")
        r2 = ctx.sql("SELECT lat + 2.0 FROM cities WHERE lat > 52.0")
        assert r1.core is r2.core
        a1 = ctx.sql("SELECT COUNT(1), SUM(lat) FROM cities WHERE city > 'A'")
        a2 = ctx.sql("SELECT COUNT(1), SUM(lat) FROM cities WHERE city > 'Q'")
        assert a1.core is a2.core
        from datafusion_tpu.exec.materialize import collect

        # and each relation still applies ITS OWN literals
        c1 = collect(a1).to_rows()[0][0]
        c2 = collect(a2).to_rows()[0][0]
        assert c1 > c2 > 0

    def test_bare_string_literal_matches_device_error(self, ctx, host_mode):
        from datafusion_tpu.errors import NotSupportedError

        with pytest.raises(NotSupportedError):
            ctx.sql_collect("SELECT city, 'x' FROM cities")


class TestHostRoutedPredicate:
    """On accelerators, numpy-evaluable predicates run on the host
    (relation.PipelineRelation._host_pred_expr): the predicate's input
    columns never cross H2D, and together with host-routed projections
    the batch usually never touches the device at all."""

    def test_filter_never_builds_device_kernel(self, ctx, test_data_dir, monkeypatch):
        import datafusion_tpu.exec.kernels as kernels
        import datafusion_tpu.exec.relation as relation
        from datafusion_tpu.exec.materialize import collect
        from datafusion_tpu.exec.relation import PipelineRelation

        monkeypatch.setattr(relation, "_is_accelerator", lambda device: True)
        saved = dict(kernels._REGISTRY)
        kernels._REGISTRY.clear()
        try:
            rel = ctx.sql(
                "SELECT city, lat, lng, lat + lng FROM cities "
                "WHERE lat > 51.0 AND lat < 53.0"
            )
            node = rel
            pipe = None
            while node is not None:
                if isinstance(node, PipelineRelation):
                    pipe = node
                    break
                node = getattr(node, "child", None)
            assert pipe is not None
            assert pipe._host_pred_expr is not None
            assert not pipe.core.needs_kernel  # scalar projections host-route too
            got = sorted(collect(rel).to_rows())
        finally:
            kernels._REGISTRY.clear()
            kernels._REGISTRY.update(saved)
        want = sorted(
            collect(
                ctx.sql(
                    "SELECT city, lat, lng, lat + lng FROM cities "
                    "WHERE lat > 51.0 AND lat < 53.0"
                )
            ).to_rows()
        )
        assert got == want
        assert len(got) == 18

    def test_distinct_literals_share_core_not_results(self, ctx, monkeypatch):
        # the host predicate carries per-query literals; the shared
        # compiled core must not leak one query's mask into another's
        import datafusion_tpu.exec.kernels as kernels
        import datafusion_tpu.exec.relation as relation
        from datafusion_tpu.exec.materialize import collect

        monkeypatch.setattr(relation, "_is_accelerator", lambda device: True)
        saved = dict(kernels._REGISTRY)
        kernels._REGISTRY.clear()
        try:
            a = collect(ctx.sql("SELECT city FROM cities WHERE lat > 52.0"))
            b = collect(ctx.sql("SELECT city FROM cities WHERE lat > 54.0"))
        finally:
            kernels._REGISTRY.clear()
            kernels._REGISTRY.update(saved)
        assert a.num_rows > b.num_rows > 0


class TestWirePolicy:
    """put_compressed skips the codec entirely when the transfer target
    is the host platform (no link to compress for); DATAFUSION_TPU_WIRE
    forces either mode."""

    def _batch(self):
        from datafusion_tpu.exec.batch import make_host_batch
        from datafusion_tpu.datatypes import DataType, Field, Schema

        rng = np.random.default_rng(11)
        schema = Schema(
            [
                Field("p", DataType.FLOAT64, False),
                Field("q", DataType.FLOAT64, False),
                Field("i", DataType.INT64, True),
            ]
        )
        cols = [
            np.round(rng.uniform(900, 105000, 2048), 2),
            rng.integers(0, 11, 2048) / 100.0,
            rng.integers(-100, 100, 2048).astype(np.int64),
        ]
        valid = rng.random(2048) > 0.2
        return make_host_batch(schema, cols, [None, None, valid], [None] * 3)

    def test_host_target_skips_wire(self, monkeypatch):
        from datafusion_tpu.exec import batch as B

        monkeypatch.setenv("DATAFUSION_TPU_WIRE", "auto")
        calls = []
        orig = B._encode_wire
        monkeypatch.setattr(
            B, "_encode_wire", lambda a, d=None: calls.append(1) or orig(a, d)
        )
        b = self._batch()
        data, validity, _ = B.device_inputs(b, None)
        assert not calls  # CPU target: no codec probing at all
        for got, want in zip(data, b.data):
            assert np.array_equal(np.asarray(got), want)
        assert np.array_equal(np.asarray(validity[2]), b.validity[2])

    def test_forced_wire_matches_raw(self, monkeypatch):
        from datafusion_tpu.exec import batch as B

        b1 = self._batch()
        b2 = self._batch()
        monkeypatch.setenv("DATAFUSION_TPU_WIRE", "always")
        d_wire, v_wire, _ = B.device_inputs(b1, None)
        monkeypatch.setenv("DATAFUSION_TPU_WIRE", "never")
        d_raw, v_raw, _ = B.device_inputs(b2, None)
        for a, c in zip(d_wire, d_raw):
            ha, hc = np.asarray(a), np.asarray(c)
            assert ha.dtype == hc.dtype
            assert np.array_equal(ha, hc)
        assert np.array_equal(np.asarray(v_wire[2]), np.asarray(v_raw[2]))

    def test_wire_hints_skip_probe_and_stay_exact(self, monkeypatch):
        from datafusion_tpu.exec import batch as B

        monkeypatch.setenv("DATAFUSION_TPU_WIRE", "always")
        rng = np.random.default_rng(5)
        col1 = np.round(rng.uniform(900, 105000, 2048), 2)   # decimal 100
        col2 = rng.integers(0, 11, 2048) / 100.0             # dict
        hints: dict = {}
        out1 = B.put_compressed([col1, col2], None, hints)
        assert set(hints) == {0, 1}
        assert hints[0][0] == "decimal" and hints[1][0] == "dict"
        # second batch of the same columns: the hint path must produce
        # bit-identical decodes
        col1b = np.round(rng.uniform(900, 105000, 2048), 2)
        col2b = rng.integers(0, 11, 2048) / 100.0
        full = []
        orig = B._encode_wire
        monkeypatch.setattr(
            B, "_encode_wire", lambda a, d=None: full.append(1) or orig(a, d)
        )
        out2 = B.put_compressed([col1b, col2b], None, hints)
        assert not full  # both columns rode their hints
        assert np.array_equal(np.asarray(out2[0]).view(np.int64), col1b.view(np.int64))
        assert np.array_equal(np.asarray(out2[1]).view(np.int64), col2b.view(np.int64))
        assert np.array_equal(np.asarray(out1[0]).view(np.int64), col1.view(np.int64))

    @pytest.mark.parametrize("table", [
        "quantities", "cents", "signs_and_nans", "full", "no_window"])
    def test_dict_hint_finds_places_as_the_search_does(self, table):
        """The hinted dict codec looks a row's place up through a 16-bit
        window of its bit pattern where one tells the table's values
        apart: the same places (wire bytes) as `searchsorted` gives, the
        same refusal of a value the table lacks, and the search itself
        where no window does."""
        from datafusion_tpu.exec import batch as B

        rng = np.random.default_rng(11)
        values = {
            "quantities": np.arange(1, 51, dtype=np.float64),
            "cents": np.arange(0, 11) / 100.0,
            "signs_and_nans": np.array(
                [-0.0, 0.0, -1.5, 1.5, np.nan, -np.inf, np.inf, 1e-300, -2.5e200]),
            "full": rng.standard_normal(B._DICT_MAX),
            # 1.0, 1.0 with its lowest bit set, -1.0: bits 0 and 63 are
            # never in one window
            "no_window": (np.float64(1.0).view(np.int64) | np.array(
                [0, 1, -(1 << 63)], np.int64)).view(np.float64),
        }[table]
        col = values[rng.integers(0, len(values), 4096)]
        spec, wires = B._encode_wire(col)
        assert spec == ("dict",)
        hint = B._wire_hint_of(spec, wires)
        assert (hint[2] is None) == (table == "no_window")
        searched = B._encode_wire_hinted(col, (hint[0], hint[1], None))
        windowed = B._encode_wire_hinted(col, hint)
        for got, want in zip(windowed[1], searched[1]):
            assert got.dtype == want.dtype
            assert got.tobytes() == want.tobytes()
        decoded = wires[1].view(np.int64)[windowed[1][0]]
        assert np.array_equal(decoded, col.view(np.int64))
        # a value the table lacks (it shares every window with one the
        # table holds, or none): refused either way
        other = col.copy()
        other[7] = np.float64(12345.678)
        assert B._encode_wire_hinted(other, hint) is None
        near = col.view(np.int64).copy()
        near[9] ^= 1 << 20
        assert B._encode_wire_hinted(near.view(np.float64), hint) is None

    def test_wire_hint_miss_falls_back(self, monkeypatch):
        from datafusion_tpu.exec import batch as B

        monkeypatch.setenv("DATAFUSION_TPU_WIRE", "always")
        rng = np.random.default_rng(6)
        col = np.round(rng.uniform(0, 100, 2048), 2)  # decimal 100
        hints: dict = {}
        B.put_compressed([col], None, hints)
        assert hints[0][0] == "decimal"
        # next batch breaks the fixed-point assumption: full probe rules
        wild = rng.standard_normal(2048)
        out = B.put_compressed([wild], None, hints)
        assert np.array_equal(
            np.asarray(out[0]).view(np.int64), wild.view(np.int64)
        )

    def test_blob_pull_roundtrip_forced(self, monkeypatch):
        # DATAFUSION_TPU_WIRE=always keeps the blob-packed D2H path live
        # on CPU (device_pull_start's host-platform skip is bypassed)
        import jax.numpy as jnp

        from datafusion_tpu.exec import batch as B

        monkeypatch.setenv("DATAFUSION_TPU_WIRE", "always")
        rng = np.random.default_rng(9)
        tree = (
            jnp.asarray(rng.integers(-(2**62), 2**62, 1024)),
            (
                jnp.asarray(rng.standard_normal(1024)),
                jnp.asarray(rng.random(1024) > 0.5),
            ),
            jnp.asarray(rng.integers(0, 255, 1024).astype(np.uint8)),
        )
        pull = B.device_pull_start(tree)
        assert pull._blob is not None  # the packed path, not direct pulls
        out = pull.finish()
        leaves_in = [tree[0], tree[1][0], tree[1][1], tree[2]]
        leaves_out = [out[0], out[1][0], out[1][1], out[2]]
        for want, got in zip(leaves_in, leaves_out):
            w = np.asarray(want)
            assert got.dtype == w.dtype
            assert np.array_equal(got, w, equal_nan=(w.dtype.kind == "f"))


def _approx_rows(got, want, rtol=1e-12):
    assert len(got) == len(want)
    for rg, rw in zip(got, want):
        assert len(rg) == len(rw)
        for vg, vw in zip(rg, rw):
            if isinstance(vw, float):
                np.testing.assert_allclose(vg, vw, rtol=rtol)
            else:
                assert vg == vw


def _null_sort_key(row):
    return tuple((x is None, 0 if x is None else x) for x in row)


class TestAggregateOverTheWire:
    """Float SUM / AVG / COUNT over scans whose batches are new objects
    every query (files, streams), with the compressed wire forced on
    (DATAFUSION_TPU_WIRE=always): every column the aggregate names
    travels, and the answers match an oracle computed here in plain
    Python / numpy."""

    @pytest.fixture(autouse=True)
    def wire(self, monkeypatch):
        monkeypatch.setenv("DATAFUSION_TPU_WIRE", "always")

    def _rows(self, ctx, sql):
        from datafusion_tpu.exec.materialize import collect

        return sorted(collect(ctx.sql(sql)).to_rows())

    def _cities(self, test_data_dir):
        import csv

        with open(os.path.join(test_data_dir, "uk_cities.csv")) as fh:
            return [(c, float(lat), float(lng)) for c, lat, lng in csv.reader(fh)]

    def test_grouped_sum_avg_count_with_predicate(self, ctx, test_data_dir):
        want = sorted(
            (c, lat, lng, 1) for c, lat, lng in self._cities(test_data_dir)
            if lat > 51.0
        )
        got = self._rows(
            ctx,
            "SELECT city, SUM(lat), AVG(lng), COUNT(1) FROM cities "
            "WHERE lat > 51.0 GROUP BY city",
        )
        _approx_rows(got, want)

    def test_global_sum_avg_count_min_max(self, ctx, test_data_dir):
        live = [r for r in self._cities(test_data_dir) if r[1] > 51.0]
        lngs = np.array([r[2] for r in live])
        want = [(float(lngs.sum()), float(lngs.mean()), len(live),
                 min(r[1] for r in live), max(r[0] for r in live))]
        got = self._rows(
            ctx,
            "SELECT SUM(lng), AVG(lng), COUNT(1), MIN(lat), MAX(city) "
            "FROM cities WHERE lat > 51.0",
        )
        _approx_rows(got, want)

    def test_nulls_through_sum_avg_count(self, ctx):
        # null_test.csv: c_float = 1.1, 2.2, NULL, 4.4, 6.6
        vals = np.array([1.1, 2.2, 4.4, 6.6])
        got = self._rows(
            ctx,
            "SELECT COUNT(1), COUNT(c_float), SUM(c_float), AVG(c_float) "
            "FROM null_test",
        )
        _approx_rows(got, [(5, 4, float(vals.sum()), float(vals.mean()))])

    def test_count_utf8_column(self, ctx):
        # c_string: "1.11", "2.22", "3.33", and two the CSV reader
        # takes as NULL (the empty field and the quoted empty string)
        got = self._rows(
            ctx, "SELECT COUNT(c_string), SUM(c_float) FROM null_test"
        )
        _approx_rows(got, [(3, 1.1 + 2.2 + 4.4 + 6.6)])

    def test_every_named_column_travels(self, tmp_path):
        # random doubles have no smaller exact wire form: a column that
        # travels costs 8 bytes a row, and one the query stops naming
        # stops travelling
        from datafusion_tpu.utils.metrics import METRICS

        rng = np.random.default_rng(30)
        n = 3000
        k = rng.integers(0, 7, n)
        v1, v2 = rng.uniform(-1, 1, n), rng.uniform(-1, 1, n)
        path = tmp_path / "t.csv"
        with open(path, "w") as fh:
            fh.write("k,v1,v2\n")
            for row in zip(k.tolist(), v1.tolist(), v2.tolist()):
                fh.write("%d,%r,%r\n" % row)
        schema = Schema([Field("k", DataType.INT64, False),
                         Field("v1", DataType.FLOAT64, False),
                         Field("v2", DataType.FLOAT64, False)])

        def run(sql):
            c = ExecutionContext(batch_size=1024, result_cache=False)
            c.register_csv("t", str(path), schema, has_header=True)
            METRICS.reset()
            rows = self._rows(c, sql)
            return rows, METRICS.snapshot()["counts"].get("h2d.bytes", 0)

        both, both_bytes = run("SELECT k, SUM(v1), AVG(v2) FROM t GROUP BY k")
        one, one_bytes = run("SELECT k, SUM(v1) FROM t GROUP BY k")
        want = sorted(
            (int(g), float(v1[k == g].sum()), float(v2[k == g].mean()))
            for g in np.unique(k)
        )
        _approx_rows(both, want, rtol=1e-9)
        _approx_rows(one, [r[:2] for r in want], rtol=1e-9)
        # batches are padded to their capacity bucket on the way
        assert one_bytes >= 8 * n
        assert both_bytes - one_bytes >= 8 * n


def test_package_version_in_sync():
    """pyproject.toml's version must match datafusion_tpu.__version__
    (two declarations where the reference's Cargo.toml has one)."""
    tomllib = pytest.importorskip("tomllib")  # stdlib only on Python 3.11+

    import datafusion_tpu

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "pyproject.toml"), "rb") as fh:
        meta = tomllib.load(fh)
    assert meta["project"]["version"] == datafusion_tpu.__version__
    scripts = meta["project"]["scripts"]
    assert scripts["datafusion-tpu"] == "datafusion_tpu.cli:main"
    assert scripts["datafusion-tpu-worker"] == "datafusion_tpu.parallel.worker:main"


class TestAggregateOverStreamedBatches:
    """Aggregates over a multi-batch stream with the wire forced on,
    against numpy oracles: groups that first appear in later batches,
    NULL group keys, and HAVING / ORDER BY / LIMIT over the result."""

    @pytest.fixture(autouse=True)
    def wire(self, monkeypatch):
        monkeypatch.setenv("DATAFUSION_TPU_WIRE", "always")

    def _ctx(self, schema, cols, valids, batch):
        from datafusion_tpu.exec.batch import make_host_batch
        from datafusion_tpu.exec.datasource import MemoryDataSource

        class StreamSource(MemoryDataSource):
            # as a file scan's, its batches are new to every query
            reusable_batches = False

        n = len(cols[0])
        batches = [
            make_host_batch(
                schema, [c[i:i + batch] for c in cols],
                [None if v is None else v[i:i + batch] for v in valids],
                [None] * len(cols),
            )
            for i in range(0, n, batch)
        ]
        c = ExecutionContext(batch_size=batch, result_cache=False)
        c.register_datasource("t", StreamSource(schema, batches))
        return c

    def test_group_growth_across_batches(self):
        from datafusion_tpu.exec.materialize import collect

        schema = Schema([Field("k", DataType.INT64, False),
                         Field("v", DataType.FLOAT64, False)])
        rng = np.random.default_rng(8)
        # later batches introduce new keys: the accumulator grows
        k = np.concatenate([rng.integers(lo, lo + 50, 4096)
                            for lo in (0, 40, 90)])
        v = np.round(rng.uniform(-10, 10, len(k)), 2)
        c = self._ctx(schema, [k, v], [None, None], 4096)
        got = sorted(collect(c.sql(
            "SELECT k, SUM(v), AVG(v), COUNT(1) FROM t GROUP BY k")).to_rows())
        want = sorted(
            (int(g), float(v[k == g].sum()), float(v[k == g].mean()),
             int((k == g).sum()))
            for g in np.unique(k)
        )
        assert len(want) == 140
        _approx_rows(got, want)

    def test_having_order_limit_over_aggregate(self):
        from datafusion_tpu.exec.materialize import collect

        schema = Schema([Field("k", DataType.INT64, False),
                         Field("v", DataType.FLOAT64, True)])
        rng = np.random.default_rng(12)
        k = rng.integers(0, 30, 8192)
        v = np.round(rng.uniform(-5, 5, 8192), 2)
        valid = rng.random(8192) > 0.15
        c = self._ctx(schema, [k, v], [None, valid], 2048)
        got = collect(c.sql(
            "SELECT k, SUM(v), COUNT(v) FROM t WHERE k < 25 GROUP BY k "
            "HAVING COUNT(v) > 100 ORDER BY k LIMIT 10")).to_rows()
        want = []
        for g in range(25):
            m = (k == g) & valid
            if m.sum() > 100:
                want.append((g, float(v[m].sum()), int(m.sum())))
        want = want[:10]
        assert len(want) == 10
        _approx_rows(got, want)

    def test_null_group_keys(self):
        from datafusion_tpu.exec.materialize import collect

        schema = Schema([Field("k", DataType.INT64, True),
                         Field("v", DataType.FLOAT64, False)])
        rng = np.random.default_rng(13)
        k = rng.integers(0, 5, 4096)
        kvalid = rng.random(4096) > 0.2
        v = np.round(rng.uniform(0, 10, 4096), 2)
        c = self._ctx(schema, [k, v], [kvalid, None], 1024)
        got = sorted(collect(c.sql(
            "SELECT k, SUM(v), AVG(v), COUNT(1) FROM t GROUP BY k")).to_rows(),
            key=_null_sort_key)
        groups = [(g, (k == g) & kvalid) for g in range(5)] + [(None, ~kvalid)]
        want = [(g, float(v[m].sum()), float(v[m].mean()), int(m.sum()))
                for g, m in groups]
        assert len(got) == 6  # 5 keys + the NULL group
        _approx_rows(got, want)


@pytest.fixture
def accel(monkeypatch):
    """The accelerator's lowering on the CPU (as TestHostRouting forces
    it) with the compressed wire (as TestWirePolicy does), and a kernel
    registry of its own: cores built in this mode stay out of the
    other tests' way."""
    import datafusion_tpu.exec.kernels as kernels
    import datafusion_tpu.exec.relation as relation

    monkeypatch.setattr(relation, "_is_accelerator", lambda device: True)
    monkeypatch.setenv("DATAFUSION_TPU_WIRE", "always")
    monkeypatch.setattr(kernels, "_REGISTRY", type(kernels._REGISTRY)())


class TestResidentTableShipsNothingAgain:
    """The `ctx.sql` door over a reusable source (the twin of
    tests/test_serve.py::test_warm_pinned_table_skips_h2d_entirely):
    device copies of columns and group ids are keyed by the table's
    long-lived batch and the aggregate's predicate stays in the core
    over them, so after the first query nothing travels; over a
    streamed scan the host evaluates the predicate and its bit-packed
    mask rides with the columns.  The accelerator lowering is forced
    as in TestHostRouting, the wire as in TestWirePolicy."""

    ROWS, BATCHES = 2048, 4
    MASK_BYTES = ROWS // 8 * BATCHES  # one packed mask a batch
    # names all seven columns, as TPC-H Q1 does: the scan has no projection
    Q1 = ("SELECT flag, status, SUM(qty), SUM(price * (1 - disc)), "
          "SUM(price * (1 - disc) * (1 + tax)), AVG(qty), COUNT(1) "
          "FROM t{where} GROUP BY flag, status")
    # the later two empty the ('N', 'F') group, whose rows all shipped
    # in August 1998
    LITERALS = ("1998-09-02", "1998-06-15", "1997-01-01")

    @classmethod
    def _batches(cls):
        from datafusion_tpu.exec.batch import StringDictionary, make_host_batch

        schema = Schema([
            Field("flag", DataType.UTF8, False),
            Field("status", DataType.UTF8, False),
            Field("qty", DataType.FLOAT64, False),
            Field("price", DataType.FLOAT64, False),
            Field("disc", DataType.FLOAT64, True),
            Field("tax", DataType.FLOAT64, False),
            Field("shipdate", DataType.UTF8, False),
        ])
        rng = np.random.default_rng(26)
        dicts = [StringDictionary() for _ in range(3)]
        out = []
        for _ in range(cls.BATCHES):
            n = cls.ROWS
            flag = rng.choice(["A", "N", "R"], n)
            status = rng.choice(["F", "O"], n)
            year = rng.integers(1992, 1999, n)
            month = rng.integers(1, 13, n)
            late = (flag == "N") & (status == "F")
            year[late], month[late] = 1998, 8
            date = [f"{y}-{m:02d}-{d:02d}" for y, m, d in zip(
                year, month, rng.integers(1, 29, n))]
            cols = [
                dicts[0].encode(list(flag)), dicts[1].encode(list(status)),
                rng.integers(1, 51, n).astype(np.float64),
                np.round(rng.uniform(900, 105000, n), 2),
                np.round(rng.uniform(0, 0.1, n), 2),
                np.round(rng.uniform(0, 0.08, n), 2),
                dicts[2].encode(date),
            ]
            valid = [None] * 7
            valid[4] = rng.random(n) > 0.1
            out.append(make_host_batch(
                schema, cols, valid,
                [dicts[0], dicts[1], None, None, None, None, dicts[2]]))
        return schema, out

    @classmethod
    def _ctx(cls, reusable=True):
        from datafusion_tpu.exec.batch import RecordBatch
        from datafusion_tpu.exec.datasource import MemoryDataSource

        class StreamSource(MemoryDataSource):
            """As a file scan: new batch objects every scan."""

            reusable_batches = False

            def batches(self):
                for b in self._batches:
                    yield RecordBatch(b.schema, list(b.data), list(b.validity),
                                      list(b.dicts), num_rows=b.num_rows)

            def with_projection(self, projection):
                base = super().with_projection(projection)
                return StreamSource(base.schema, base._batches)

        schema, batches = cls._batches()
        c = ExecutionContext(result_cache=False)
        c.register_datasource(
            "t", (MemoryDataSource if reusable else StreamSource)(schema, batches))
        return c

    @classmethod
    def _sql(cls, literal):
        where = "" if literal is None else f" WHERE shipdate <= '{literal}'"
        return cls.Q1.format(where=where)

    @staticmethod
    def _run(c, sql):
        """(sorted rows, h2d.bytes moved, h2d.resident hits, misses)."""
        from datafusion_tpu.exec.materialize import collect
        from datafusion_tpu.utils.metrics import METRICS

        names = ("h2d.bytes", "h2d.resident_hits", "h2d.resident_misses")
        before = [METRICS.counts.get(n, 0) for n in names]
        rows = sorted(collect(c.sql(sql)).to_rows())
        return (rows, *(METRICS.counts.get(n, 0) - b
                        for n, b in zip(names, before)))

    @staticmethod
    def _same(got, want):
        assert len(got) == len(want)
        for ra, rb in zip(got, want):
            for va, vb in zip(ra, rb):
                if isinstance(va, float):
                    np.testing.assert_allclose(va, vb, rtol=1e-12)
                else:
                    assert va == vb

    @pytest.mark.parametrize("predicate", [True, False])
    def test_later_queries_ship_nothing(self, accel, predicate):
        from datafusion_tpu.exec.aggregate import AggregateRelation
        from datafusion_tpu.utils.metrics import METRICS

        literals = self.LITERALS if predicate else (None,) * 3
        c = self._ctx()
        rel = c.sql(self._sql(literals[0]))
        assert isinstance(rel, AggregateRelation)
        # the table keeps its batches: the predicate is the core's
        assert rel._host_pred_expr is None
        assert (rel._core_pred is not None) == predicate
        names = ("device.h2d.transfers", "expr.cmp_lookups")
        moved = []
        for i, lit in enumerate(literals):
            before = [METRICS.counts.get(n, 0) for n in names]
            host_s = METRICS.timings.get("host.predicate", 0.0)
            rows, nbytes, hits, misses = self._run(c, self._sql(lit))
            puts, lookups = (METRICS.counts.get(n, 0) - b
                             for n, b in zip(names, before))
            moved.append((nbytes, puts))
            # one count a batch a query: found on the batch, or shipped
            assert (hits, misses) == ((0, self.BATCHES) if i == 0
                                      else (self.BATCHES, 0))
            # one device lookup a batch, and the host evaluates nothing
            assert lookups == (self.BATCHES if predicate else 0)
            assert METRICS.timings.get("host.predicate", 0.0) == host_s
            # the unshared path: a table nobody has queried before
            self._same(rows, self._run(self._ctx(), self._sql(lit))[0])
            if lit == self.LITERALS[-1]:
                assert ("N", "F") not in {r[:2] for r in rows}
                assert len(rows) == 5
        assert moved[0][0] > 4 * self.MASK_BYTES and moved[0][1] > 0
        # another literal reads the same copies: one view a used-column
        # set on the table's batch
        assert moved[1:] == [(0, 0)] * 2
        for b in c.datasources["t"].batches():
            assert sum(1 for k in b.cache if k[0] == "agg_subset") <= 1

    @pytest.mark.parametrize("predicate", [True, False])
    def test_a_streamed_scan_ships_its_mask_with_the_columns(
            self, accel, predicate):
        from datafusion_tpu.utils.metrics import METRICS

        literals = self.LITERALS if predicate else (None,) * 3
        c = self._ctx(reusable=False)
        rel = c.sql(self._sql(literals[0]))
        # batches that die with the scan: the host takes the predicate
        assert (rel._host_pred_expr is not None) == predicate
        assert rel._core_pred is None
        moved = []
        for lit in literals:
            lookups = METRICS.counts.get("expr.cmp_lookups", 0)
            rows, nbytes, hits, misses = self._run(c, self._sql(lit))
            moved.append(nbytes)
            assert (hits, misses) == (0, self.BATCHES)
            assert METRICS.counts.get("expr.cmp_lookups", 0) == lookups
            self._same(rows, self._run(self._ctx(), self._sql(lit))[0])
        # every query ships its columns anew, and the mask with them
        assert min(moved) > 4 * self.MASK_BYTES
        assert moved[1:] == [moved[0]] * 2

    def test_answers_match_wherever_the_predicate_runs(self, accel):
        from datafusion_tpu.exec.aggregate import force_core_predicate

        resident, streamed, forced = (
            self._ctx(), self._ctx(reusable=False), self._ctx(reusable=False))
        for lit in self.LITERALS:
            with force_core_predicate():
                rel = forced.sql(self._sql(lit))
                assert rel._host_pred_expr is None and rel._core_pred is not None
                want = self._run(forced, self._sql(lit))[0]
            self._same(self._run(resident, self._sql(lit))[0], want)
            self._same(self._run(streamed, self._sql(lit))[0], want)

    def test_projected_query_ships_nothing_the_second_time(self, accel):
        c = self._ctx()
        sql = "SELECT status, SUM(qty), MAX(tax) FROM t GROUP BY status"
        first = self._run(c, sql)
        assert first[1] > 0 and first[2:] == (0, self.BATCHES)
        for _ in range(2):
            again = self._run(c, sql)
            assert again[0] == first[0]
            assert again[1:] == (0, self.BATCHES, 0)
        # every query's projection is the same view on the table's batch
        from datafusion_tpu.exec.batch import PROJECTION_TAG

        table = c.datasources["t"]
        narrowed = table.with_projection([1, 2, 5])
        for parent, view in zip(table.batches(), narrowed.batches()):
            assert parent.cache[(PROJECTION_TAG, (1, 2, 5))] is view
        # a projection of a projection is a view cached on that view
        deeper = narrowed.with_projection([0, 2])  # status, tax
        for view, inner in zip(narrowed.batches(), deeper.batches()):
            assert view.cache[(PROJECTION_TAG, (0, 2))] is inner
            assert [f.name for f in inner.schema.fields] == ["status", "tax"]
        # ... and whatever the projection, one encoder per GROUP BY
        # column set of the table: status is its column 1
        enc = c.sql(sql)
        enc._adopt_source_state()
        by_status = table._shared._by_keys[(1,)]["encoder"]
        assert enc.encoder is by_status
        wide = c.sql("SELECT status, MIN(price) FROM t GROUP BY status")
        wide._adopt_source_state()
        assert wide.core is not enc.core and wide.encoder is by_status

    @pytest.mark.parametrize("reusable", [False, True])
    def test_one_put_compressed_call_per_batch_when_columns_ship(
            self, accel, reusable, monkeypatch):
        import datafusion_tpu.exec.batch as batch_mod

        calls = []
        real = batch_mod.put_compressed

        def counting(host_arrays, *args, **kwargs):
            calls.append(len(host_arrays))
            return real(host_arrays, *args, **kwargs)

        monkeypatch.setattr(batch_mod, "put_compressed", counting)
        c = self._ctx(reusable=reusable)
        # the used columns and disc's validity: the ship date's codes
        # where the table stays (the predicate is the core's), the
        # host's mask in their place where it is streamed
        used = c.sql(self._sql(self.LITERALS[0])).core.used_cols
        assert (6 in used) == reusable  # shipdate is the table's column 6
        width = len(used) + 1 + (0 if reusable else 1)
        for i, lit in enumerate(self.LITERALS):
            del calls[:]
            _, _, hits, misses = self._run(c, self._sql(lit))
            if reusable and i > 0:
                # the copies are on the batch: nothing travels
                assert calls == []
                assert (hits, misses) == (self.BATCHES, 0)
            else:
                # columns (and mask) in ONE call, one decode launch
                assert calls == [width] * self.BATCHES
                assert (hits, misses) == (0, self.BATCHES)

    def test_resident_batch_cache_is_bounded(self, accel):
        c = self._ctx()
        shapes = [self._sql(lit) for lit in self.LITERALS] + [
            self._sql(None),
            "SELECT status, SUM(qty), MAX(tax) FROM t GROUP BY status",
            "SELECT status, SUM(qty) FROM t WHERE tax < 0.05 GROUP BY status",
        ]
        def nodes(b):
            """The batch and every view cached on it, at any depth."""
            yield b
            for v in list(b.cache.values()):
                if hasattr(v, "cache"):
                    yield from nodes(v)

        def entries():
            return [sum(len(n.cache) for n in nodes(b))
                    for b in c.datasources["t"].batches()]

        sizes = []
        for i in range(20):
            self._run(c, shapes[i % len(shapes)])
            sizes.append(entries())
        # every shape has run by the sixth query: nothing grows after
        assert sizes[len(shapes):] == [sizes[len(shapes) - 1]] * (
            20 - len(shapes))
        for b in c.datasources["t"].batches():
            keys = [k if isinstance(k, str) else k[0] for k in b.cache]
            # on the table's own batch: one mask slot, one id slot for
            # the one GROUP BY column set that scans it unprojected,
            # one view per distinct column set
            assert keys.count("agg_inputs") == 1
            assert keys.count("group_ids") == 1
            assert keys.count("agg_subset") == 1
            assert keys.count("pin_proj") == 2
            assert len(b.cache) <= 6  # + the aux pin where staging runs
            for n in nodes(b):
                inner = [k if isinstance(k, str) else k[0] for k in n.cache]
                assert inner.count("agg_inputs") <= 1
                # one id slot per GROUP BY column set: the two
                # narrower shapes read one view through two cores and
                # share it
                assert inner.count("group_ids") <= 1

    def test_literal_cores_share_one_id_array_and_are_not_pinned(self):
        """With the predicate in the core (the CPU, the served path)
        every date literal compiles its own core: the table keeps one
        encoder and one id array a batch for all of them, and holds no
        core alive past the kernel LRU."""
        import gc

        from datafusion_tpu.exec import kernels

        c = self._ctx()
        table = c.datasources["t"]
        sizes, cores = [], set()
        for day in range(1, 21):
            rel = c.sql(self._sql(f"1998-08-{day:02d}"))
            assert rel._host_pred_expr is None
            cores.add(id(rel.core))
            self._same(
                self._run(c, self._sql(f"1998-08-{day:02d}"))[0],
                self._run(self._ctx(), self._sql(f"1998-08-{day:02d}"))[0])
            sizes.append([len(b.cache) for b in table.batches()])
            del rel
        assert len(cores) == 20
        assert sizes[1:] == [sizes[0]] * 19
        assert list(table._shared._by_keys) == [(0, 1)]
        kernels._REGISTRY.clear()
        gc.collect()
        # the last query's core is still the pin of the batches' one
        # `agg_inputs` slot
        assert len(table._shared._by_core) <= 1

    def test_concurrent_literals_get_their_own_answers(self, accel):
        import sys
        import threading

        c = self._ctx()
        want = {lit: self._run(self._ctx(), self._sql(lit))[0]
                for lit in self.LITERALS[::2]}
        self._run(c, self._sql(self.LITERALS[1]))  # the copies are there
        clients = [lit for lit in want for _ in range(2)]
        got, errors = [[] for _ in clients], []
        start = threading.Barrier(len(clients))

        def client(i, lit):
            try:
                start.wait(timeout=30)
                for _ in range(4):
                    got[i].append(self._run(c, self._sql(lit))[0])
            except BaseException as e:  # noqa: BLE001 — reported below
                errors.append(e)

        threads = [threading.Thread(target=client, args=(i, lit))
                   for i, lit in enumerate(clients)]
        switch = sys.getswitchinterval()
        sys.setswitchinterval(1e-4)  # the mask slot changes hands often
        try:
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=120)
        finally:
            sys.setswitchinterval(switch)
        assert not any(t.is_alive() for t in threads)
        assert not errors, errors
        for lit, runs in zip(clients, got):
            assert len(runs) == 4
            for rows in runs:
                self._same(rows, want[lit])
        assert len(want[self.LITERALS[0]]) == 6
        assert len(want[self.LITERALS[2]]) == 5


class TestWhereTheAggregatesPredicateRuns:
    """The one rule (`AggregateRelation._keeps_batches`): on an
    accelerator the host takes the aggregate's predicate over a
    streamed scan, whose batches die after the kernel read them; over
    a source that hands every query the same batches it stays in the
    core.  Read from the child's source and nothing else; either way
    the answer is the oracle's."""

    ROWS, BATCH = 600, 64
    SCHEMA = Schema([
        Field("k", DataType.UTF8, False),
        Field("d", DataType.UTF8, True),
        Field("n", DataType.INT64, True),
        Field("v", DataType.FLOAT64, False),
    ])
    SOURCES = {  # name -> does the source keep its batches
        "memory": True, "pinned": True, "unpinned": False,
        "parquet": False, "csv": False,
    }
    PREDICATES = {
        # a string range compare over a column with NULLs
        "string": ("d <= '1995-06-15'",
                   lambda d, n: d is not None and d <= "1995-06-15"),
        "numeric": ("n > 5", lambda d, n: n is not None and n > 5),
        # TRUE OR NULL is TRUE, FALSE AND NULL is FALSE
        "or": ("d > '1997-01-01' OR n < 3",
               lambda d, n: (d is not None and d > "1997-01-01")
               or (n is not None and n < 3)),
        "is_null": ("d IS NULL AND n >= 0",
                    lambda d, n: d is None and n is not None and n >= 0),
        "keeps_no_row": ("d > '2999-01-01'", lambda d, n: False),
    }

    @pytest.fixture(scope="class")
    def files(self, tmp_path_factory):
        """(rows, Parquet file, CSV file): dates ascend with the row
        number, so every batch brings the dictionary new strings."""
        import pyarrow as pa
        import pyarrow.csv as pacsv
        import pyarrow.parquet as pq

        rng = np.random.default_rng(36)
        n = self.ROWS
        k = rng.choice(["a", "b", "c"], n).tolist()
        day = np.sort(rng.integers(0, 2500, n))
        d = [None if rng.random() < 0.15 else
             str(np.datetime64("1992-01-01") + int(x)) for x in day]
        num = [None if rng.random() < 0.2 else int(x)
               for x in rng.integers(0, 10, n)]
        v = np.round(rng.uniform(0, 100, n), 2).tolist()
        table = pa.table({
            "k": pa.array(k, pa.string()), "d": pa.array(d, pa.string()),
            "n": pa.array(num, pa.int64()), "v": pa.array(v, pa.float64())})
        base = tmp_path_factory.mktemp("rule")
        parquet, csv = str(base / "t.parquet"), str(base / "t.csv")
        pq.write_table(table, parquet, row_group_size=200)
        pacsv.write_csv(table, csv)
        return list(zip(k, d, num, v)), parquet, csv

    def _ctx(self, files, source):
        from datafusion_tpu.exec.datasource import MemoryDataSource
        from datafusion_tpu.serve import PinnedSource

        _, parquet, csv = files
        c = ExecutionContext(batch_size=self.BATCH, result_cache=False)
        if source == "csv":
            c.register_csv("t", csv, self.SCHEMA, has_header=True)
            return c
        c.register_parquet("t", parquet, self.SCHEMA)
        scan = c.datasources["t"]
        if source == "memory":
            c.register_datasource(
                "t", MemoryDataSource(scan.schema, list(scan.batches())))
        elif source in ("pinned", "unpinned"):
            pin = PinnedSource(scan, f"rule_{id(c)}")
            if source == "pinned":
                assert pin.ensure()
            c.register_datasource("t", pin)
        return c

    @pytest.mark.parametrize("source", list(SOURCES))
    def test_the_rule_reads_the_source(self, accel, files, source):
        from datafusion_tpu.exec.aggregate import (
            AggregateRelation,
            force_core_predicate,
        )
        from datafusion_tpu.obs.device import LEDGER

        c = self._ctx(files, source)
        try:
            grouped = c.sql(
                "SELECT k, COUNT(1) FROM t WHERE n > 5 GROUP BY k")
            projected = c.sql("SELECT SUM(v) FROM t WHERE d <= '1995-06-15'")
            for rel in (grouped, projected):
                assert isinstance(rel, AggregateRelation)
                assert rel._keeps_batches() == self.SOURCES[source]
                in_core = rel._core_pred is not None
                assert in_core == self.SOURCES[source]
                assert (rel._host_pred_expr is None) == in_core
            # nothing to evaluate, nothing to place
            bare = c.sql("SELECT k, COUNT(1) FROM t GROUP BY k")
            assert bare._host_pred_expr is None and bare._core_pred is None
            # the serving scope keeps the core whatever the source
            with force_core_predicate():
                served = c.sql("SELECT SUM(v) FROM t WHERE n > 5")
            assert served._host_pred_expr is None
            assert served._core_pred is not None
        finally:
            if source == "pinned":
                LEDGER.unpin(c.datasources["t"].fingerprint)

    def test_the_cpu_keeps_every_predicate_in_the_core(self, files):
        for source in ("memory", "parquet"):
            rel = self._ctx(files, source).sql(
                "SELECT SUM(v) FROM t WHERE n > 5")
            assert rel._host_pred_expr is None and rel._core_pred is not None

    def test_a_child_that_is_no_scan_streams(self, accel, files):
        """A limit (as a pipeline, a sort, a host-probed join) makes
        new batches a query, whatever table feeds it."""
        from datafusion_tpu.exec.aggregate import AggregateRelation
        from datafusion_tpu.exec.sort import LimitRelation
        from datafusion_tpu.plan.logical import Aggregate, Selection
        from datafusion_tpu.sql.parser import parse_sql

        c = self._ctx(files, "memory")
        plan = c._plan(parse_sql("SELECT COUNT(1) FROM t WHERE n > 5"))
        assert isinstance(plan, Aggregate)
        assert isinstance(plan.input, Selection)
        scan = c.execute(plan.input.input)
        assert scan.datasource.reusable_batches

        def over(child):
            return AggregateRelation(
                child, plan.group_expr, plan.aggr_expr, plan.schema,
                predicate=plan.input.expr)

        assert over(scan)._core_pred is not None
        rel = over(LimitRelation(scan, 10 ** 6, scan.schema))
        assert not rel._keeps_batches()
        assert rel._host_pred_expr is not None and rel._core_pred is None

    @pytest.mark.parametrize("source", list(SOURCES))
    @pytest.mark.parametrize("predicate", list(PREDICATES))
    def test_the_answer_is_the_oracles(self, accel, files, source, predicate):
        from datafusion_tpu.exec.materialize import collect
        from datafusion_tpu.obs.device import LEDGER

        rows = files[0]
        where, keep = self.PREDICATES[predicate]
        kept = [r for r in rows if keep(r[1], r[2])]
        c = self._ctx(files, source)
        try:
            for _ in range(2):  # the second finds what the first left
                got = sorted(collect(c.sql(
                    "SELECT k, COUNT(1), COUNT(n), SUM(v), MIN(d), MAX(n) "
                    f"FROM t WHERE {where} GROUP BY k")).to_rows())
                want = []
                for key in sorted({r[0] for r in kept}):
                    g = [r for r in kept if r[0] == key]
                    ds = [r[1] for r in g if r[1] is not None]
                    ns = [r[2] for r in g if r[2] is not None]
                    want.append((key, len(g), len(ns), sum(r[3] for r in g),
                                 min(ds) if ds else None,
                                 max(ns) if ns else None))
                assert [r[:3] + r[4:] for r in got] == [
                    r[:3] + r[4:] for r in want]
                np.testing.assert_allclose(
                    [r[3] for r in got], [r[3] for r in want], rtol=1e-12)
                (count, total), = collect(c.sql(
                    f"SELECT COUNT(1), SUM(v) FROM t WHERE {where}")).to_rows()
                assert count == len(kept)
                if kept:
                    np.testing.assert_allclose(
                        total, sum(r[3] for r in kept), rtol=1e-12)
                else:
                    assert total is None
        finally:
            if source == "pinned":
                LEDGER.unpin(c.datasources["t"].fingerprint)

    def test_the_dictionary_grows_between_batches(self, files):
        c = self._ctx(files, "memory")
        sizes = []
        for b in c.datasources["t"].batches():
            codes = np.asarray(b.data[1])[:b.num_rows]
            sizes.append(int(codes.max()) + 1)
        assert sizes == sorted(sizes) and sizes[-1] > 2 * sizes[0]
