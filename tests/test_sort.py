"""Sort / TopK / high-cardinality aggregation tests.

Covers the two device sort paths (`exec/sort.py`): the streaming TopK
(`ORDER BY ... LIMIT k`) and the run-sort + host-merge full sort, plus
the sort-merge aggregation path at 10^5 groups (`exec/aggregate.py`).
The reference planned Sort/Limit but left them `unimplemented!()`
(`/root/reference/src/execution/context.rs:161`), so expected values
come from numpy on identical inputs.
"""

import numpy as np
import pytest

from datafusion_tpu.datatypes import DataType, Field, Schema
from datafusion_tpu.exec.batch import StringDictionary, make_host_batch
from datafusion_tpu.exec.context import ExecutionContext
from datafusion_tpu.exec.datasource import MemoryDataSource


def _ctx_with(name, schema, cols, valids=None, dicts=None, batch_rows=1000):
    """Context over an in-memory table split into batch_rows-row batches."""
    n = len(cols[0])
    valids = valids if valids is not None else [None] * len(cols)
    dicts = dicts if dicts is not None else [None] * len(cols)
    batches = []
    for i in range(0, n, batch_rows):
        batches.append(
            make_host_batch(
                schema,
                [c[i : i + batch_rows] for c in cols],
                [None if v is None else v[i : i + batch_rows] for v in valids],
                dicts,
            )
        )
    ctx = ExecutionContext()
    ctx.register_datasource(name, MemoryDataSource(schema, batches))
    return ctx


class TestStreamingTopK:
    def test_multibatch_asc_desc(self):
        rng = np.random.default_rng(0)
        n = 50_000
        v = rng.permutation(n).astype(np.int64)
        x = rng.uniform(-1, 1, n)
        schema = Schema(
            [Field("v", DataType.INT64, False), Field("x", DataType.FLOAT64, False)]
        )
        ctx = _ctx_with("t", schema, [v, x], batch_rows=4096)

        t = ctx.sql_collect("SELECT v, x FROM t ORDER BY v LIMIT 7")
        order = np.argsort(v)[:7]
        assert list(t.column_values(0)) == v[order].tolist()
        np.testing.assert_allclose(np.asarray(t.column_values(1)), x[order])

        t = ctx.sql_collect("SELECT v FROM t ORDER BY v DESC LIMIT 5")
        assert list(t.column_values(0)) == sorted(v.tolist(), reverse=True)[:5]

    def test_multikey_with_ties(self):
        rng = np.random.default_rng(1)
        n = 20_000
        a = rng.integers(0, 50, n).astype(np.int32)
        b = rng.uniform(0, 1, n)
        schema = Schema(
            [Field("a", DataType.INT32, False), Field("b", DataType.FLOAT64, False)]
        )
        ctx = _ctx_with("t", schema, [a, b], batch_rows=3000)
        t = ctx.sql_collect("SELECT a, b FROM t ORDER BY a DESC, b LIMIT 100")
        # expected: lexsort on (-a, b)
        order = np.lexsort((b, -a.astype(np.int64)))[:100]
        np.testing.assert_array_equal(np.asarray(t.column_values(0)), a[order])
        np.testing.assert_allclose(np.asarray(t.column_values(1)), b[order])

    def test_nulls_last(self):
        v = np.asarray([5, 2, 9, 1, 7], np.int64)
        valid = np.asarray([True, False, True, True, False])
        schema = Schema([Field("v", DataType.INT64, True)])
        ctx = _ctx_with("t", schema, [v], valids=[valid], batch_rows=2)
        t = ctx.sql_collect("SELECT v FROM t ORDER BY v LIMIT 5")
        vals = t.to_rows()
        assert [r[0] for r in vals[:3]] == [1, 5, 9]
        assert vals[3][0] is None and vals[4][0] is None

    def test_limit_larger_than_input(self):
        v = np.asarray([3, 1, 2], np.int64)
        schema = Schema([Field("v", DataType.INT64, False)])
        ctx = _ctx_with("t", schema, [v])
        t = ctx.sql_collect("SELECT v FROM t ORDER BY v LIMIT 50")
        assert list(t.column_values(0)) == [1, 2, 3]

    def test_string_keys_dict_growth(self):
        # batch 2 introduces words that sort before batch 1's whole
        # dictionary: rank tables must be recomputed per version
        rng = np.random.default_rng(2)
        d = StringDictionary()
        words = []
        for lo, hi in ((13, 26), (0, 26)):
            words.extend(
                chr(97 + rng.integers(lo, hi)) + f"{rng.integers(0, 1000):03d}"
                for _ in range(5000)
            )
        codes = d.encode(words)
        schema = Schema([Field("s", DataType.UTF8, False)])
        ctx = _ctx_with("t", schema, [codes], dicts=[d], batch_rows=5000)
        t = ctx.sql_collect("SELECT s FROM t ORDER BY s LIMIT 20")
        assert list(t.column_values(0)) == sorted(words)[:20]
        t = ctx.sql_collect("SELECT s FROM t ORDER BY s DESC LIMIT 20")
        assert list(t.column_values(0)) == sorted(words, reverse=True)[:20]


class TestFullSort:
    def test_multirun_merge_exact_order(self, monkeypatch):
        # force small runs so multiple device-sorted runs merge on host
        # (the default single-sort threshold is far larger)
        monkeypatch.setenv("DATAFUSION_TPU_SORT_RUN_ROWS", "16384")
        rng = np.random.default_rng(3)
        n = 120_000
        a = rng.integers(0, 1000, n).astype(np.int64)
        b = rng.permutation(n).astype(np.int64)
        schema = Schema(
            [Field("a", DataType.INT64, False), Field("b", DataType.INT64, False)]
        )
        ctx = _ctx_with("t", schema, [a, b], batch_rows=8192)
        t = ctx.sql_collect("SELECT a, b FROM t ORDER BY a, b DESC")
        order = np.lexsort((-b, a))
        np.testing.assert_array_equal(np.asarray(t.column_values(0)), a[order])
        np.testing.assert_array_equal(np.asarray(t.column_values(1)), b[order])

    def test_full_sort_with_nulls_and_strings(self):
        rng = np.random.default_rng(4)
        n = 30_000
        d = StringDictionary()
        words = [f"w{rng.integers(0, 500):03d}" for _ in range(n)]
        codes = d.encode(words)
        v = rng.integers(-100, 100, n).astype(np.int64)
        valid = rng.random(n) < 0.9
        schema = Schema(
            [Field("s", DataType.UTF8, False), Field("v", DataType.INT64, True)]
        )
        ctx = _ctx_with(
            "t", schema, [codes, v], valids=[None, valid], dicts=[d, None],
            batch_rows=4096,
        )
        t = ctx.sql_collect("SELECT s, v FROM t ORDER BY s DESC, v")
        # expected: s DESC, then v ASC with NULLs last
        warr = np.asarray(words)
        vkey = np.where(valid, v, np.iinfo(np.int64).max)
        # np.lexsort is ascending; invert string order via negated ranks
        svals, sranks = np.unique(warr, return_inverse=True)
        order = np.lexsort((vkey, -sranks))
        assert list(t.column_values(0)) == warr[order].tolist()
        got_v = t.to_rows()
        exp_v = [int(v[i]) if valid[i] else None for i in order]
        assert [r[1] for r in got_v] == exp_v

    def test_limit_above_topk_max_uses_run_merge(self, monkeypatch):
        import datafusion_tpu.exec.sort as sort_mod

        monkeypatch.setattr(sort_mod, "TOPK_MAX", 4)
        rng = np.random.default_rng(5)
        n = 5_000
        v = rng.permutation(n).astype(np.int64)
        schema = Schema([Field("v", DataType.INT64, False)])
        ctx = _ctx_with("t", schema, [v], batch_rows=512)
        t = ctx.sql_collect("SELECT v FROM t ORDER BY v LIMIT 10")
        assert list(t.column_values(0)) == list(range(10))

    def test_uint64_full_range(self):
        # keys above 2^63: ordering must survive the sign-flip trick
        v = np.asarray(
            [0, 1, 2**63 - 1, 2**63, 2**64 - 1, 42], dtype=np.uint64
        )
        schema = Schema([Field("v", DataType.UINT64, False)])
        ctx = _ctx_with("t", schema, [v], batch_rows=2)
        t = ctx.sql_collect("SELECT v FROM t ORDER BY v DESC")
        assert list(t.column_values(0)) == sorted(v.tolist(), reverse=True)
        t = ctx.sql_collect("SELECT v FROM t ORDER BY v LIMIT 3")
        assert list(t.column_values(0)) == sorted(v.tolist())[:3]

    def test_empty_input(self):
        schema = Schema([Field("v", DataType.INT64, False)])
        ctx = _ctx_with("t", schema, [np.empty(0, np.int64)])
        t = ctx.sql_collect("SELECT v FROM t ORDER BY v")
        assert t.num_rows == 0
        t = ctx.sql_collect("SELECT v FROM t ORDER BY v LIMIT 5")
        assert t.num_rows == 0


class TestHighCardinalityAggregate:
    @pytest.mark.parametrize("n_groups", [100_000])
    def test_sum_count_min_max_100k_groups(self, n_groups):
        rng = np.random.default_rng(6)
        n = 400_000
        k = rng.integers(0, n_groups, n).astype(np.int64)
        v = rng.integers(-1000, 1000, n).astype(np.int64)
        schema = Schema(
            [Field("k", DataType.INT64, False), Field("v", DataType.INT64, False)]
        )
        ctx = _ctx_with("t", schema, [k, v], batch_rows=65536)
        t = ctx.sql_collect(
            "SELECT k, SUM(v), COUNT(1), MIN(v), MAX(v) FROM t GROUP BY k"
        )
        uniq = np.unique(k)
        assert t.num_rows == len(uniq)
        sums = np.zeros(n_groups, np.int64)
        np.add.at(sums, k, v)
        cnts = np.bincount(k, minlength=n_groups)
        mins = np.full(n_groups, np.iinfo(np.int64).max)
        np.minimum.at(mins, k, v)
        maxs = np.full(n_groups, np.iinfo(np.int64).min)
        np.maximum.at(maxs, k, v)
        got = {r[0]: r[1:] for r in t.to_rows()}
        for g in uniq.tolist():
            assert got[g] == (sums[g], cnts[g], mins[g], maxs[g])

    def test_avg_float_100k_groups_matches_dense_semantics(self):
        rng = np.random.default_rng(7)
        n, n_groups = 300_000, 120_000
        k = rng.integers(0, n_groups, n).astype(np.int64)
        v = rng.uniform(-1, 1, n)
        schema = Schema(
            [Field("k", DataType.INT64, False), Field("v", DataType.FLOAT64, False)]
        )
        ctx = _ctx_with("t", schema, [k, v], batch_rows=65536)
        t = ctx.sql_collect("SELECT k, AVG(v), SUM(v) FROM t GROUP BY k")
        sums = np.zeros(n_groups)
        np.add.at(sums, k, v)
        cnts = np.bincount(k, minlength=n_groups)
        got = {r[0]: r[1:] for r in t.to_rows()}
        uniq = np.unique(k)
        assert t.num_rows == len(uniq)
        for g in rng.choice(uniq, 500, replace=False).tolist():
            a, s = got[g]
            np.testing.assert_allclose(s, sums[g], rtol=1e-9)
            np.testing.assert_allclose(a, sums[g] / cnts[g], rtol=1e-9)


class TestSentinelCollisions:
    """Real extreme values must not collide with the NULL/padding
    markers: ~int64.min == int64.max and -(-inf) == +inf, so nulls ride
    a separate dead-flag sort operand instead of value sentinels."""

    def test_int64_min_desc_with_nulls(self):
        schema = Schema([Field("x", DataType.INT64, True)])
        vals = np.array([0, np.iinfo(np.int64).min, 5], dtype=np.int64)
        valid = np.array([False, True, True])
        ctx = _ctx_with("t", schema, [vals], valids=[valid])

        t = ctx.sql_collect("SELECT x FROM t ORDER BY x DESC")
        assert t.column_values(0) == [5, np.iinfo(np.int64).min, None]

        t = ctx.sql_collect("SELECT x FROM t ORDER BY x DESC LIMIT 2")
        assert t.column_values(0) == [5, np.iinfo(np.int64).min]

    def test_int64_extremes_asc(self):
        schema = Schema([Field("x", DataType.INT64, True)])
        vals = np.array(
            [np.iinfo(np.int64).max, 0, np.iinfo(np.int64).min], dtype=np.int64
        )
        valid = np.array([True, False, True])
        ctx = _ctx_with("t", schema, [vals], valids=[valid])
        t = ctx.sql_collect("SELECT x FROM t ORDER BY x")
        assert t.column_values(0) == [
            np.iinfo(np.int64).min, np.iinfo(np.int64).max, None,
        ]
        t = ctx.sql_collect("SELECT x FROM t ORDER BY x LIMIT 3")
        assert t.column_values(0) == [
            np.iinfo(np.int64).min, np.iinfo(np.int64).max, None,
        ]

    def test_float_inf_desc_with_nulls(self):
        schema = Schema([Field("x", DataType.FLOAT64, True)])
        vals = np.array([-np.inf, 1.0, np.inf, 0.0])
        valid = np.array([True, True, True, False])
        ctx = _ctx_with("t", schema, [vals], valids=[valid])
        t = ctx.sql_collect("SELECT x FROM t ORDER BY x DESC")
        assert t.column_values(0) == [np.inf, 1.0, -np.inf, None]
        t = ctx.sql_collect("SELECT x FROM t ORDER BY x DESC LIMIT 3")
        assert t.column_values(0) == [np.inf, 1.0, -np.inf]

    def test_uint64_max_asc_with_nulls(self):
        schema = Schema([Field("x", DataType.UINT64, True)])
        vals = np.array([np.iinfo(np.uint64).max, 1, 0], dtype=np.uint64)
        valid = np.array([True, True, False])
        ctx = _ctx_with("t", schema, [vals], valids=[valid])
        t = ctx.sql_collect("SELECT x FROM t ORDER BY x")
        assert t.column_values(0) == [1, np.iinfo(np.uint64).max, None]

    def test_wide_f64_topk_matches_numpy(self):
        # float64 single-key TopK rides the wide lax.top_k path (host
        # bit-image); parity against numpy stable sort incl. ties
        rng = np.random.default_rng(21)
        n = 30_000
        x = np.round(rng.uniform(-1e6, 1e6, n), 1)  # ties likely
        pay = np.arange(n, dtype=np.int64)
        schema = Schema(
            [Field("x", DataType.FLOAT64, False), Field("p", DataType.INT64, False)]
        )
        ctx = _ctx_with("t", schema, [x, pay], batch_rows=4096)
        for sql, order in [
            ("SELECT x, p FROM t ORDER BY x LIMIT 50", np.argsort(x, kind="stable")[:50]),
            (
                "SELECT x, p FROM t ORDER BY x DESC LIMIT 50",
                np.argsort(-x, kind="stable")[:50],
            ),
        ]:
            t = ctx.sql_collect(sql)
            assert t.column_values(0) == x[order].tolist()
            assert t.column_values(1) == pay[order].tolist()

    def test_wide_f64_topk_nan_and_nulls(self):
        # ladder: real values > NaN > NULL; all must fill a big LIMIT
        schema = Schema([Field("x", DataType.FLOAT64, True)])
        vals = np.array([3.0, np.nan, -np.inf, 0.0, np.inf, 1.0])
        valid = np.array([True, True, True, False, True, True])
        ctx = _ctx_with("t", schema, [vals], valids=[valid])
        t = ctx.sql_collect("SELECT x FROM t ORDER BY x DESC LIMIT 6")
        got = t.column_values(0)
        assert got[:4] == [np.inf, 3.0, 1.0, -np.inf]
        assert np.isnan(got[4]) and got[5] is None
        t = ctx.sql_collect("SELECT x FROM t ORDER BY x LIMIT 6")
        got = t.column_values(0)
        assert got[:4] == [-np.inf, 1.0, 3.0, np.inf]
        assert np.isnan(got[4]) and got[5] is None

    def test_wide_int64_collision_fallback_fires(self):
        # int64.min under DESC lands on the sentinel ladder: the wide
        # path must detect the collision and replay via the exact sort
        from datafusion_tpu.utils.metrics import METRICS

        schema = Schema([Field("x", DataType.INT64, False)])
        vals = np.array([7, np.iinfo(np.int64).min, -3, 12], dtype=np.int64)
        ctx = _ctx_with("t", schema, [vals])
        METRICS.reset()
        t = ctx.sql_collect("SELECT x FROM t ORDER BY x DESC LIMIT 4")
        assert t.column_values(0) == [12, 7, -3, np.iinfo(np.int64).min]
        assert METRICS.snapshot()["counts"].get("sort.wide_fallbacks", 0) >= 1
        # and without extremes the fast path serves alone
        vals2 = np.array([7, -5, -3, 12], dtype=np.int64)
        ctx2 = _ctx_with("t", schema, [vals2])
        METRICS.reset()
        t2 = ctx2.sql_collect("SELECT x FROM t ORDER BY x DESC LIMIT 2")
        assert t2.column_values(0) == [12, 7]
        assert METRICS.snapshot()["counts"].get("sort.wide_fallbacks", 0) == 0

    def test_full_sort_multirun_int64_min(self, monkeypatch):
        # force the run-merge path (no LIMIT, multiple small runs)
        monkeypatch.setenv("DATAFUSION_TPU_SORT_RUN_ROWS", "1024")
        rng = np.random.default_rng(5)
        n = 3000
        vals = rng.integers(-1000, 1000, n).astype(np.int64)
        vals[0] = np.iinfo(np.int64).min
        vals[n // 2] = np.iinfo(np.int64).max
        valid = np.ones(n, bool)
        valid[1::7] = False
        schema = Schema([Field("x", DataType.INT64, True)])
        ctx = _ctx_with("t", schema, [vals], valids=[valid], batch_rows=1000)
        t = ctx.sql_collect("SELECT x FROM t ORDER BY x DESC")
        got = t.column_values(0)
        want = sorted(vals[valid].tolist(), reverse=True) + [None] * int(
            (~valid).sum()
        )
        assert got == want


class TestOrderByHiddenColumn:
    """ORDER BY a column not in the SELECT list: planned as a hidden
    projection column + final strip (the reference resolves only
    against the projection schema, `sqlplanner.rs:139-151`, and fails)."""

    def test_order_by_unselected_column(self):
        schema = Schema(
            [Field("name", DataType.UTF8, False), Field("v", DataType.INT64, False)]
        )
        d = StringDictionary()
        names = np.array([d.add(s) for s in ["b", "c", "a"]], dtype=np.int32)
        v = np.array([2, 3, 1], dtype=np.int64)
        ctx = _ctx_with("t", schema, [names, v], dicts=[d, None])
        t = ctx.sql_collect("SELECT name FROM t ORDER BY v DESC")
        assert t.column_values(0) == ["c", "b", "a"]
        assert len(t.schema) == 1  # hidden column stripped

        t = ctx.sql_collect("SELECT name FROM t ORDER BY v LIMIT 2")
        assert t.column_values(0) == ["a", "b"]

    def test_order_by_alias_still_works(self):
        schema = Schema([Field("v", DataType.INT64, False)])
        ctx = _ctx_with("t", schema, [np.array([3, 1, 2], dtype=np.int64)])
        t = ctx.sql_collect("SELECT v AS w FROM t ORDER BY w")
        assert t.column_values(0) == [1, 2, 3]


class TestSingleKeyFastPath:
    """Single-key TopK rides lax.top_k with an exact int64 score image
    (floats, ints <= 32 bits, strings); results must match the general
    sort path exactly."""

    @pytest.mark.parametrize(
        "dtype,lo,hi",
        [(np.int32, -(2**31), 2**31 - 1), (np.int16, -100, 100),
         (np.uint32, 0, 2**32 - 1)],
    )
    def test_small_int_keys(self, dtype, lo, hi):
        rng = np.random.default_rng(3)
        v = rng.integers(lo, hi, 5000, dtype=dtype)
        v[0], v[1] = lo, hi  # extremes must survive
        valid = np.ones(5000, bool)
        valid[2::11] = False
        dt = {np.int32: DataType.INT32, np.int16: DataType.INT16,
              np.uint32: DataType.UINT32}[dtype]
        schema = Schema([Field("v", dt, True)])
        ctx = _ctx_with("t", schema, [v], valids=[valid], batch_rows=1024)
        for order, rev in (("", False), (" DESC", True)):
            t = ctx.sql_collect(f"SELECT v FROM t ORDER BY v{order} LIMIT 40")
            want = sorted(v[valid].tolist(), reverse=rev)[:40]
            assert t.column_values(0) == want, (dtype, order)

    def test_float_extremes_and_ties(self):
        # float32: the fast-path-eligible float width
        rng = np.random.default_rng(4)
        v = np.round(rng.uniform(-1e6, 1e6, 20000), 2).astype(np.float32)
        v[5], v[6], v[7] = np.inf, -np.inf, v[8]  # dupes + infinities
        # small-magnitude mixed signs: the region where a naive
        # sign-flip bit image breaks monotonicity
        v[100:120] = np.linspace(-1.5, 1.5, 20, dtype=np.float32)
        v[120], v[121] = -0.0, 0.0
        valid = rng.random(20000) > 0.05
        schema = Schema([Field("v", DataType.FLOAT32, True)])
        ctx = _ctx_with("t", schema, [v], valids=[valid], batch_rows=4096)
        for order, rev in (("", False), (" DESC", True)):
            t = ctx.sql_collect(f"SELECT v FROM t ORDER BY v{order} LIMIT 100")
            want = sorted(v[valid].tolist(), reverse=rev)[:100]
            np.testing.assert_array_equal(
                np.asarray(t.column_values(0)), np.asarray(want), err_msg=order
            )

    def test_limit_exceeds_batch_capacity(self):
        # LIMIT (bucketed to k=2048) > the 1024-row batch capacity:
        # lax.top_k(full, k) would demand k <= capacity and crash; the
        # kernel must clamp its per-batch pick and pad with dead slots
        rng = np.random.default_rng(9)
        v = rng.permutation(5000).astype(np.int32)
        schema = Schema([Field("v", DataType.INT32, False)])
        ctx = _ctx_with("t", schema, [v], batch_rows=1000)
        t = ctx.sql_collect("SELECT v FROM t ORDER BY v LIMIT 2000")
        assert t.column_values(0) == list(range(2000))
        t = ctx.sql_collect("SELECT v FROM t ORDER BY v DESC LIMIT 2000")
        assert t.column_values(0) == list(range(4999, 2999, -1))

    def test_limit_exceeds_live_rows(self):
        # dead sentinel slots must not displace real NULL-key rows
        # (FLOAT32: fast-path eligible, so this pins the score ladder)
        schema = Schema([Field("v", DataType.FLOAT32, True)])
        vals = np.array([3.5, 1.25, 2.0, 0.0, 9.0])
        valid = np.array([True, True, True, False, False])
        ctx = _ctx_with("t", schema, [vals], valids=[valid])
        t = ctx.sql_collect("SELECT v FROM t ORDER BY v LIMIT 5")
        assert t.column_values(0) == [1.25, 2.0, 3.5, None, None]


class TestTopKFinalFold:
    """The TopK result's (live-mask, row-ids) pull is folded INTO the
    fused group launch: a warm pass is ONE counted device launch
    (`device.launches.topk.final`), with no separate blob-pack launch
    for the mask — and the rows are Python's `sorted` prefix."""

    def _ctx(self):
        rng = np.random.default_rng(21)
        schema = Schema([
            Field("a", DataType.INT32, False),
            Field("b", DataType.FLOAT64, False),
        ])
        cols = [rng.integers(0, 100000, 5000).astype(np.int32),
                rng.uniform(0, 1, 5000)]
        batches = [
            make_host_batch(schema, [c[i:i + 1000] for c in cols])
            for i in range(0, 5000, 1000)
        ]
        # result cache OFF: the warm run must re-execute the pass (the
        # launch count is the thing under test)
        ctx = ExecutionContext(result_cache=False)
        ctx.register_datasource("t", MemoryDataSource(schema, batches))
        return ctx, "SELECT a, b FROM t ORDER BY a LIMIT 10"

    def test_warm_pass_is_one_launch(self):
        from datafusion_tpu.exec.materialize import collect
        from datafusion_tpu.utils.metrics import METRICS

        ctx, q = self._ctx()
        want = collect(ctx.sql(q)).to_rows()
        collect(ctx.sql(q))  # warm device copies + compiled programs
        before = dict(METRICS.counts)
        got = collect(ctx.sql(q)).to_rows()
        delta = {
            k: v - before.get(k, 0) for k, v in METRICS.counts.items()
        }
        assert got == want
        assert delta.get("device.launches.topk.final", 0) == 1
        assert delta.get("device.launches", 0) == 1

    def test_folded_result_matches_sorted(self):
        from datafusion_tpu.exec.materialize import collect

        ctx, q = self._ctx()
        src = ctx.datasources["t"]
        rows = [
            (int(a), float(b))
            for batch in src.batches()
            for a, b in zip(np.asarray(batch.data[0])[: batch.num_rows],
                            np.asarray(batch.data[1])[: batch.num_rows])
        ]
        want = sorted(rows, key=lambda r: r[0])[:10]
        assert collect(ctx.sql(q)).to_rows() == want

    def test_empty_scan_and_wide_keys_still_fold(self):
        from datafusion_tpu.exec.materialize import collect

        rng = np.random.default_rng(22)
        schema = Schema([
            Field("a", DataType.INT64, False),
            Field("b", DataType.FLOAT64, False),
        ])
        ctx = _ctx_with(
            "t", schema,
            [rng.integers(-(2**60), 2**60, 3000).astype(np.int64),
             rng.uniform(0, 1, 3000)],
        )
        # wide int64 key: the collision flag rides the folded header
        got = collect(ctx.sql(
            "SELECT a FROM t ORDER BY a DESC LIMIT 7"
        )).to_rows()
        want = sorted(
            (int(v),) for v in
            collect(ctx.sql("SELECT a FROM t")).columns[0]
        )[-7:][::-1]
        assert got == want
        # LIMIT over an all-filtered scan: the empty path still answers
        empty = collect(ctx.sql(
            "SELECT a FROM t WHERE a > 4611686018427387904 "
            "AND a < -4611686018427387904 ORDER BY a LIMIT 3"
        ))
        assert empty.num_rows == 0


class TestTopKExactPayloads:
    """TopK carries global row indices, not payload columns: payloads
    gather host-side from the source batches, so ORDER BY ... LIMIT
    equals the no-LIMIT sort prefix BIT-FOR-BIT even on emulated-f64
    devices (round-3 ADVICE item)."""

    def _src(self, rows=20_000):
        import numpy as np

        from datafusion_tpu.datatypes import DataType, Field, Schema
        from datafusion_tpu.exec.batch import make_host_batch
        from datafusion_tpu.exec.datasource import MemoryDataSource

        rng = np.random.default_rng(99)
        schema = Schema([
            Field("a", DataType.FLOAT64, False),
            Field("b", DataType.INT64, False),
            Field("x", DataType.FLOAT64, False),
        ])
        batches = []
        for lo in range(0, rows, 4096):
            n = min(4096, rows - lo)
            batches.append(make_host_batch(schema, [
                rng.uniform(-1e6, 1e6, n),
                rng.integers(-1000, 1000, n),
                rng.uniform(-1e9, 1e9, n),
            ]))
        return schema, MemoryDataSource(schema, batches)

    @pytest.mark.parametrize("sql_key", ["a DESC", "a", "b, a DESC"])
    def test_limit_equals_full_sort_prefix_bitwise(self, sql_key):
        import numpy as np

        from datafusion_tpu.exec.context import ExecutionContext
        from datafusion_tpu.exec.materialize import collect

        _, src = self._src()
        ctx = ExecutionContext()
        ctx.register_datasource("t", src)
        limited = collect(ctx.sql(f"SELECT a, b, x FROM t ORDER BY {sql_key} LIMIT 137"))
        full = collect(ctx.sql(f"SELECT a, b, x FROM t ORDER BY {sql_key}"))
        for i in range(3):
            want = np.asarray(full.columns[i][:137])
            got = np.asarray(limited.columns[i])
            if want.dtype.kind == "f":
                assert np.array_equal(
                    got.view(np.int64), want.view(np.int64)
                ), f"col {i} not bit-identical"
            else:
                assert np.array_equal(got, want)

    def test_state_carries_no_payload_columns(self):
        # structural: the streaming state is (keys, live, rows[, flag])
        from datafusion_tpu.exec.context import ExecutionContext
        from datafusion_tpu.exec.materialize import collect

        _, src = self._src(rows=5000)
        ctx = ExecutionContext()
        ctx.register_datasource("t", src)
        rel = ctx.sql("SELECT a, b, x FROM t ORDER BY a DESC LIMIT 10")
        init = rel._topk_init(128, rel.child.schema)
        # wide single-key path: (keys, live, rows, flag)
        assert len(init) == 4
        keys, live, rows = init[0], init[1], init[2]
        assert rows.dtype.name == "int64"
        collect(rel)  # executes end to end


class TestRunSortOverTheWire:
    """Full ORDER BY with the compressed wire forced on
    (DATAFUSION_TPU_WIRE=always): the run's key operands travel
    encoded, the device sorts, the permutation comes back as byte
    planes.  Orders are held to Python's stable `sorted`: NULL keys
    last whatever the direction, NaN above +inf, -0.0 before +0.0."""

    N = 4096

    @pytest.fixture(autouse=True)
    def wire(self, monkeypatch):
        monkeypatch.setenv("DATAFUSION_TPU_WIRE", "always")

    def _table(self, nulls=False, nans=False):
        rng = np.random.default_rng(21)
        n = self.N
        a = np.round(rng.uniform(-100, 100, n), 2)
        if nans:
            a[::97] = np.nan
        valid_a = rng.random(n) > 0.1 if nulls else None
        b = rng.integers(-50, 50, n)
        s = [f"v{int(x) % 13}" for x in b]
        return a, valid_a, b, s

    def _ctx(self, a, valid_a, b, s):
        from datafusion_tpu.exec.batch import StringDictionary

        schema = Schema([
            Field("a", DataType.FLOAT64, True),
            Field("b", DataType.INT64, False),
            Field("s", DataType.UTF8, False),
        ])
        d = StringDictionary()
        codes = d.encode(s)
        half = self.N // 2
        batches = [
            make_host_batch(
                schema,
                [a[lo:hi], b[lo:hi], codes[lo:hi]],
                [None if valid_a is None else valid_a[lo:hi], None, None],
                [None, None, d],
            )
            for lo, hi in ((0, half), (half, self.N))
        ]
        ctx = ExecutionContext(batch_size=half)
        ctx.register_datasource("t", MemoryDataSource(schema, batches))
        return ctx

    @staticmethod
    def _key(v, desc=False):
        """Sort key of one value: NULLs last, then the value (negated
        for DESC; strings never sort DESC here)."""
        if v is None:
            return (1, 0)
        return (0, -v if desc else v)

    @pytest.mark.parametrize("sql,cols,key", [
        ("SELECT a, b, s FROM t ORDER BY a, b", "abs",
         lambda k, r: (k(r[0]), k(r[1]))),
        ("SELECT a, b, s FROM t ORDER BY b DESC, a", "abs",
         lambda k, r: (k(r[1], True), k(r[0]))),
        ("SELECT s, a FROM t ORDER BY s, a DESC", "sa",
         lambda k, r: (k(r[0]), k(r[1], True))),
    ])
    def test_multi_key_order_matches_sorted(self, sql, cols, key):
        from datafusion_tpu.exec.materialize import collect

        a, valid_a, b, s = self._table(nulls=True)
        got = collect(self._ctx(a, valid_a, b, s).sql(sql)).to_rows()
        col = {
            "a": [float(x) if ok else None for x, ok in zip(a, valid_a)],
            "b": b.tolist(),
            "s": s,
        }
        rows = list(zip(*(col[c] for c in cols)))
        assert got == sorted(rows, key=lambda r: key(self._key, r))

    def test_nan_keys_sort_above_infinity(self):
        from datafusion_tpu.exec.materialize import collect

        a, _, b, s = self._table(nans=True)
        ctx = self._ctx(a, None, b, s)
        nan = np.isnan(a)
        assert nan.any()
        rest = [(float(x), int(y)) for x, y in zip(a[~nan], b[~nan])]
        got = collect(ctx.sql("SELECT a, b FROM t ORDER BY a")).to_rows()
        # ASC: the NaNs trail, in scan order (the sort is stable)
        assert got[: len(rest)] == sorted(rest, key=lambda r: r[0])
        assert all(np.isnan(r[0]) for r in got[len(rest):])
        assert [r[1] for r in got[len(rest):]] == b[nan].tolist()
        # DESC sorts by the negated key, and where -NaN stands is the
        # backend's: at one end, together, never among the numbers
        got = collect(ctx.sql("SELECT a, b FROM t ORDER BY a DESC")).to_rows()
        nums = [r for r in got if not np.isnan(r[0])]
        assert nums == sorted(rest, key=lambda r: -r[0])
        n_nan = int(nan.sum())
        assert nums in (got[n_nan:], got[: len(rest)])

    def test_signed_zeros_stay_together(self):
        # -0.0 and +0.0 tie or split by sign (the backend's total
        # order), but never interleave with other values, and rows
        # whose keys are the same bits keep their scan order (the
        # payload column tells the zeros apart: -0.0 == 0.0 in Python)
        from datafusion_tpu.exec.materialize import collect

        rng = np.random.default_rng(9)
        n = 512
        a = rng.uniform(-1, 1, n)
        a[::7] = 0.0
        a[::11] = -0.0
        schema = Schema([
            Field("a", DataType.FLOAT64, False),
            Field("tag", DataType.INT64, False),
        ])
        b = make_host_batch(
            schema, [a.copy(), np.arange(n, dtype=np.int64)],
            [None, None], [None, None],
        )
        ctx = ExecutionContext(batch_size=n)
        ctx.register_datasource("t", MemoryDataSource(schema, [b]))
        got = [r[1] for r in collect(
            ctx.sql("SELECT a, tag FROM t ORDER BY a")).to_rows()]
        tied = sorted(range(n), key=lambda i: a[i])
        split = sorted(range(n), key=lambda i: (a[i], not np.signbit(a[i])))
        assert tied != split
        assert got in (tied, split)

    def test_warm_requery_reuses_the_permutation(self):
        # the third batches() pass on one relation (seen, admitted,
        # hit) skips the key encode, the sort launch and its pull
        from datafusion_tpu.exec.materialize import collect
        from datafusion_tpu.utils.metrics import METRICS

        rel = self._ctx(*self._table()).sql(
            "SELECT a, b, s FROM t ORDER BY a, b")
        first = collect(rel).to_rows()
        collect(rel)  # second pass: key admitted to the cache
        before = dict(METRICS.snapshot()["counts"])
        third = collect(rel).to_rows()
        after = METRICS.snapshot()["counts"]
        assert after.get("sort.perm_cache_hits", 0) > before.get(
            "sort.perm_cache_hits", 0)
        assert after.get("device.launches.sort.run", 0) == before.get(
            "device.launches.sort.run", 0)
        assert third == first

    def test_full_sort_with_large_limit(self):
        # LIMIT above TOPK_MAX takes the full-sort path; the
        # permutation must honor the prefix take
        from datafusion_tpu.exec.materialize import collect
        from datafusion_tpu.exec.sort import TOPK_MAX

        rng = np.random.default_rng(3)
        n = TOPK_MAX + 4096
        schema = Schema([Field("a", DataType.INT64, False)])
        b = make_host_batch(schema, [rng.integers(0, 10**6, n)], [None], [None])
        ctx = ExecutionContext(batch_size=n)
        ctx.register_datasource("t", MemoryDataSource(schema, [b]))
        lim = TOPK_MAX + 1
        out = collect(ctx.sql(f"SELECT a FROM t ORDER BY a LIMIT {lim}"))
        vals = [r[0] for r in out.to_rows()]
        want = sorted(np.asarray(b.data[0])[: b.num_rows].tolist())[:lim]
        assert vals == want
