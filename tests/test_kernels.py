"""Kernel parity + fused-pass coverage (ISSUE 6).

Three layers, all on the CPU tier-1 backend:

- The join's slot-table build against its numpy oracle.
- The engine's fused passes against numpy / Python oracles: right
  answers, one launch a batch group, plan-chain collapse in effect.
- Sort semantics that must survive any backend/kernel swap: stability,
  NaN / signed-zero ordering, multi-key and mixed-dtype keys, and
  high-cardinality group-by exact-key/count parity vs numpy.
"""

from __future__ import annotations

import numpy as np
import pytest

from datafusion_tpu import DataType, ExecutionContext, Field, Schema
from datafusion_tpu.exec.batch import make_host_batch
from datafusion_tpu.exec.datasource import MemoryDataSource
from datafusion_tpu.exec.materialize import collect
from datafusion_tpu.utils.metrics import METRICS


def _ctx(schema, columns, validity=None, batch_size=4096, name="t"):
    from datafusion_tpu.exec.batch import StringDictionary

    n = len(columns[0])
    # Utf8 columns travel as dictionary codes (one shared dictionary
    # per column, as a real scan produces)
    dicts = [None] * len(columns)
    cols = []
    for j, c in enumerate(columns):
        c = np.asarray(c)
        if schema.field(j).data_type == DataType.UTF8:
            dicts[j] = StringDictionary()
            c = dicts[j].encode([str(x) for x in c])
        cols.append(c)
    batches = []
    for i in range(0, n, batch_size):
        sl = slice(i, i + batch_size)
        batches.append(make_host_batch(
            schema,
            [c[sl] for c in cols],
            [None if v is None else np.asarray(v)[sl]
             for v in (validity or [None] * len(columns))],
            dicts,
        ))
    ctx = ExecutionContext(device="cpu", result_cache=False)
    ctx.register_datasource(name, MemoryDataSource(schema, batches))
    return ctx


def _rows(ctx, sql):
    return collect(ctx.sql(sql)).to_rows()


# ------------------------------------------------------------ join build


class TestHashBuildParity:
    """The join's slot-table build (join/relation.py) against its numpy
    oracle."""

    @pytest.mark.parametrize("n,slots", [
        (8192, 8192),   # full table, one row each
        (5000, 8192),   # sparse table
        (700, 300),     # duplicates: counts > 1, max row index wins
        (1, 1),
    ])
    def test_hash_build_parity(self, n, slots):
        import jax

        from datafusion_tpu.join.relation import (
            build_slot_table_numpy,
            build_slot_table_xla,
        )

        rng = np.random.default_rng(7)
        pos = (rng.permutation(max(n, slots))[:n] % slots).astype(np.int32)
        live = rng.random(n) > 0.15
        want = build_slot_table_numpy(pos, live, slots)
        got = jax.jit(lambda p, l: build_slot_table_xla(
            p, l, slots))(pos, live)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g), w)


# ------------------------------------------------------------ fused pass


class TestFusedPasses:
    def _agg_data(self):
        rng = np.random.default_rng(23)
        n, g = 40_000, 3000  # past DENSE_GROUP_MAX: sort-merge territory
        schema = Schema([
            Field("k", DataType.INT64, False),
            Field("v", DataType.FLOAT64, False),
            Field("w", DataType.INT64, False),
        ])
        cols = [rng.integers(0, g, n), rng.normal(size=n),
                rng.integers(-100, 100, n)]
        return schema, cols, g

    def test_fused_aggregate_matches_numpy(self):
        schema, (k, v, w), g = self._agg_data()
        sql = ("SELECT k, SUM(w), MIN(v), MAX(v), COUNT(1) FROM t "
               "WHERE v > -1.5 GROUP BY k")
        got = sorted(_rows(_ctx(schema, [k, v, w]), sql))
        live = v > -1.5
        want = sorted(
            (int(key), int(w[m].sum()), float(v[m].min()),
             float(v[m].max()), int(m.sum()))
            for key in np.unique(k[live])
            for m in [live & (k == key)]
        )
        assert len(got) == len(want) == g
        assert got == want  # integer sums, counts, MIN and MAX are exact

    def test_one_launch_per_batch_group(self):
        schema, cols, _ = self._agg_data()
        sql = "SELECT k, SUM(w), COUNT(1) FROM t GROUP BY k"
        # 40,000 rows in batches of 2,048: 20 batches of one capacity
        # (the short last one is padded), so one shape class
        ctx = _ctx(schema, cols, batch_size=2048)
        METRICS.reset()
        collect(ctx.sql(sql))
        snap = METRICS.snapshot()["counts"]
        assert snap.get("fused.groups", 0) == 1
        assert snap.get("fused.group_batches", 0) == 20
        assert snap.get("device.launches.agg.group", 0) == 1
        assert snap.get("device.launches", 0) == 1

    @pytest.mark.parametrize("n_batches", [1, 2, 3, 17, 33])
    @pytest.mark.parametrize("shape", ["aggregate", "topk"])
    def test_batch_group_ladder(self, shape, n_batches):
        # 17 and 33 batches fall between rungs of `fused._LADDER` (they
        # pad to 24 and 48 with zero-row entries); 1 takes the
        # single-batch launch.  Whatever the padding, one launch folds
        # the scan and the dead entries change no answer.
        rng = np.random.default_rng(59 + n_batches)
        rows = 256
        n = rows * n_batches - 100  # a short last batch
        schema = Schema([
            Field("k", DataType.INT64, False),
            Field("v", DataType.FLOAT64, False),
            Field("w", DataType.INT64, False),
        ])
        k = rng.integers(0, 12, n)
        v = rng.normal(size=n)
        w = rng.integers(-100, 100, n)
        ctx = _ctx(schema, [k, v, w], batch_size=rows)
        METRICS.reset()
        if shape == "aggregate":
            got = sorted(_rows(
                ctx, "SELECT k, SUM(w), COUNT(1), MIN(v) FROM t GROUP BY k"))
            want = sorted(
                (int(g), int(w[k == g].sum()), int((k == g).sum()),
                 float(v[k == g].min()))
                for g in np.unique(k)
            )
        else:
            got = _rows(ctx, "SELECT v, w FROM t ORDER BY v LIMIT 10")
            want = sorted(zip(v.tolist(), w.tolist()))[:10]
        assert got == want
        snap = METRICS.snapshot()["counts"]
        assert snap.get("device.launches", 0) == 1
        assert snap.get("fused.group_batches", 0) == (
            n_batches if n_batches > 1 or shape == "topk" else 0)

    def test_fuse_group_bucketing_bounds_compiles(self):
        from datafusion_tpu.exec.fused import bucket_group

        assert bucket_group(1) == 1
        assert bucket_group(5) == 6
        assert bucket_group(115) == 128
        assert bucket_group(9000) == 9000  # beyond the ladder: as-is

    def test_aggregate_over_projection_chain_collapses(self):
        # DataFrame-style Aggregate(Projection(Selection(scan))) lowers
        # to ONE AggregateRelation under fusion
        from datafusion_tpu.plan.expr import (
            AggregateFunction, BinaryExpr, Column, Literal, Operator,
            ScalarValue,
        )
        from datafusion_tpu.plan.logical import (
            Aggregate, Projection, Selection, TableScan,
        )

        rng = np.random.default_rng(29)
        n = 10_000
        schema = Schema([
            Field("a", DataType.FLOAT64, False),
            Field("k", DataType.INT64, False),
        ])
        cols = [rng.normal(size=n), rng.integers(0, 40, n)]
        scan = TableScan("default", "t", schema)
        sel = Selection(
            BinaryExpr(Column(0), Operator.Gt,
                       Literal(ScalarValue.float64(-0.7))), scan,
        )
        proj = Projection(
            [Column(1),
             BinaryExpr(Column(0), Operator.Multiply,
                        Literal(ScalarValue.float64(3.0)))],
            sel,
            Schema([Field("k", DataType.INT64, False),
                    Field("x", DataType.FLOAT64, False)]),
        )
        agg = Aggregate(
            proj, [Column(0)],
            [AggregateFunction("sum", [Column(1)], DataType.FLOAT64)],
            Schema([Field("k", DataType.INT64, False),
                    Field("s", DataType.FLOAT64, False)]),
        )

        rel = _ctx(schema, cols).execute(agg)
        got = sorted(collect(rel).to_rows())
        assert getattr(rel, "_fused_chain", None) == "filter+project+aggregate"
        assert type(rel).__name__ == "AggregateRelation"
        assert rel.op_children() and type(
            rel.op_children()[0]
        ).__name__ == "DataSourceRelation"  # no interposed pipeline
        a, k = cols
        live = a > -0.7
        want = sorted(
            (int(key), float((a[live & (k == key)] * 3.0).sum()))
            for key in np.unique(k[live])
        )
        assert [r[0] for r in got] == [r[0] for r in want]
        np.testing.assert_allclose(
            [r[1] for r in got], [r[1] for r in want], rtol=1e-12
        )

    def test_sort_chain_collapses_with_filter_and_projection(self):
        rng = np.random.default_rng(31)
        n = 20_000
        schema = Schema([
            Field("a", DataType.FLOAT64, False),
            Field("b", DataType.INT64, False),
            Field("c", DataType.INT64, False),
        ])
        cols = [rng.normal(size=n), rng.integers(0, 1000, n),
                rng.integers(0, 5, n)]
        sql = "SELECT b, a FROM t WHERE c < 3 ORDER BY b DESC, a LIMIT 25"

        rel = _ctx(schema, cols).sql(sql)
        got = collect(rel).to_rows()
        assert getattr(rel, "_fused_chain", None) == "filter+project+sort"
        assert "+filter" in rel.op_label() and "+project" in rel.op_label()
        a, b, c = cols
        kept = [(int(y), float(x)) for x, y, z in zip(a, b, c) if z < 3]
        assert got == sorted(kept, key=lambda r: (-r[0], r[1]))[:25]
        # and the full-sort (no LIMIT) variant
        f_got = _rows(_ctx(schema, cols),
                      "SELECT b, a FROM t WHERE c < 3 ORDER BY b, a")
        assert f_got == sorted(kept)

    def test_explain_analyze_reports_fused_passes(self):
        rng = np.random.default_rng(37)
        n = 8000
        schema = Schema([
            Field("k", DataType.INT64, False),
            Field("v", DataType.FLOAT64, False),
        ])
        ctx = _ctx(schema, [rng.integers(0, 500, n), rng.normal(size=n)],
                   batch_size=1024)
        res = ctx.sql(
            "EXPLAIN ANALYZE SELECT k, SUM(v) FROM t WHERE v > 0 GROUP BY k"
        )
        report = res.report()
        assert "launches_per_pass=" in report
        assert "kernel_cache hit/miss=" in report
        assert res.counters["device.launches"] >= 1
        # the gauges export through the Prometheus text path
        text = ctx.metrics_text()
        assert 'name="query.launches_per_pass"' in text

    def test_repeat_query_no_kernel_cache_misses(self):
        rng = np.random.default_rng(41)
        n = 5000
        schema = Schema([
            Field("k", DataType.INT64, False),
            Field("v", DataType.FLOAT64, False),
        ])
        ctx = _ctx(schema, [rng.integers(0, 100, n), rng.normal(size=n)])
        sql = "SELECT k, SUM(v) FROM t GROUP BY k"
        first = _rows(ctx, sql)
        METRICS.reset()
        second = _rows(ctx, sql)
        snap = METRICS.snapshot()["counts"]
        assert snap.get("kernel_cache.misses", 0) == 0
        assert sorted(first) == sorted(second)


# ---------------------------------------------------- sort semantics


class TestSortSemantics:
    def test_stability_under_heavy_ties(self):
        rng = np.random.default_rng(43)
        n = 30_000
        schema = Schema([
            Field("a", DataType.INT64, False),
            Field("tag", DataType.INT64, False),
        ])
        a = rng.integers(0, 8, n)  # 8 distinct keys: massive tie runs
        tag = np.arange(n, dtype=np.int64)
        rows = _rows(_ctx(schema, [a, tag], batch_size=4096),
                     "SELECT a, tag FROM t ORDER BY a")
        # within each key run, the original row order must survive
        last = {}
        for key, tag_v in rows:
            assert last.get(key, -1) < tag_v, f"unstable at key {key}"
            last[key] = tag_v

    def test_nan_and_signed_zero_ordering(self):
        vals = np.array([1.5, np.nan, -0.0, 0.0, -np.inf, np.inf,
                         -1.5, np.nan, 0.0, -0.0])
        tag = np.arange(len(vals), dtype=np.int64)
        schema = Schema([
            Field("a", DataType.FLOAT64, False),
            Field("tag", DataType.INT64, False),
        ])
        rows = _rows(_ctx(schema, [vals, tag]),
                     "SELECT a, tag FROM t ORDER BY a")
        order = [t for _, t in rows]
        # -inf first, then -1.5; NaNs sort last (stable between them);
        # the four zeros stay contiguous (±0.0 compare equal or split —
        # backend-dependent — but never interleave with nonzeros)
        assert order[0] == 4 and order[1] == 6
        assert order[-2:] == [1, 7]
        zeros = [t for v, t in rows if v == 0.0]
        assert sorted(zeros) == [2, 3, 8, 9]
        assert order[2:6] == zeros

    def test_multi_key_mixed_dtype(self):
        rng = np.random.default_rng(47)
        n = 6000
        words = np.array(["ash", "birch", "cedar", "oak"], dtype=object)
        schema = Schema([
            Field("s", DataType.UTF8, False),
            Field("f", DataType.FLOAT64, False),
            Field("i", DataType.INT64, False),
        ])
        s = words[rng.integers(0, 4, n)]
        f = rng.normal(size=n).round(1)  # ties across keys
        i = rng.integers(-40, 40, n)
        sql = "SELECT s, f, i FROM t ORDER BY s, f DESC, i"
        got = _rows(_ctx(schema, [s, f, i]), sql)
        want = sorted(
            zip(s.tolist(), f.tolist(), i.tolist()),
            key=lambda r: (r[0], -r[1], r[2]),
        )
        assert got == [tuple(w) for w in want]

    def test_high_cardinality_groupby_exact_keys_and_counts(self):
        rng = np.random.default_rng(53)
        n, g = 60_000, 20_000  # most groups have 1-6 rows
        schema = Schema([
            Field("k", DataType.INT64, False),
            Field("v", DataType.FLOAT64, False),
        ])
        k = rng.integers(0, g, n)
        v = rng.normal(size=n)
        rows = _rows(_ctx(schema, [k, v], batch_size=8192),
                     "SELECT k, COUNT(1), SUM(v) FROM t GROUP BY k")
        got_keys = sorted(r[0] for r in rows)
        want_keys, want_counts = np.unique(k, return_counts=True)
        assert got_keys == want_keys.tolist()
        counts = {r[0]: r[1] for r in rows}
        assert all(
            counts[kk] == cc
            for kk, cc in zip(want_keys.tolist(), want_counts.tolist())
        )
        sums = {r[0]: r[2] for r in rows}
        want_sums = np.bincount(k, weights=v, minlength=g)
        for kk in want_keys.tolist():
            np.testing.assert_allclose(sums[kk], want_sums[kk], rtol=1e-9)
