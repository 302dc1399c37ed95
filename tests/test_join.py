"""Hash joins: SQL front-end through device/host execution and the
shuffle exchange.

Parity oracle is `pandas.merge` over the same host rows (the reference
repo has no join to compare against — PAPER.md §L2's LogicalPlan is
single-table).  Covers the dense-int device path (fused-launch counts,
pinned-build reuse with zero build-side H2D on warm probes), the host
fallback (duplicate keys, NULL keys, Utf8 keys, multi-key), plan JSON
round-trips, verifier diagnostics, projection push-down through Join,
and the shuffle partition/dedup units distributed joins build on.
"""

from __future__ import annotations

import os

import numpy as np
import pandas as pd
import pytest

from datafusion_tpu import DataType, ExecutionContext, Field, Schema
from datafusion_tpu.exec.materialize import collect
from datafusion_tpu.utils.metrics import METRICS


def _write_csv(path, header, rows):
    with open(path, "w", encoding="utf-8") as f:
        f.write(header + "\n")
        for r in rows:
            f.write(",".join("" if v is None else str(v) for v in r) + "\n")
    return str(path)


@pytest.fixture
def jctx(tmp_path):
    """fact (600 rows, dup + dangling keys) and dim (50 rows, unique
    int key) — the canonical probe/build pair."""
    rng = np.random.default_rng(7)
    fact = [(int(rng.integers(0, 60)), i, round(float(rng.uniform(0, 10)), 3))
            for i in range(600)]  # keys 50..59 dangle (no dim row)
    dim = [(i, f"name{i}", int(i % 7)) for i in range(50)]
    ctx = ExecutionContext(batch_size=256)
    ctx.register_csv(
        "fact", _write_csv(tmp_path / "fact.csv", "k,seq,x", fact),
        Schema([Field("k", DataType.INT64, False),
                Field("seq", DataType.INT64, False),
                Field("x", DataType.FLOAT64, False)]),
        has_header=True,
    )
    ctx.register_csv(
        "dim", _write_csv(tmp_path / "dim.csv", "k,name,grp", dim),
        Schema([Field("k", DataType.INT64, False),
                Field("name", DataType.UTF8, False),
                Field("grp", DataType.INT64, False)]),
        has_header=True,
    )
    ctx._fact = pd.DataFrame(fact, columns=["k", "seq", "x"])
    ctx._dim = pd.DataFrame(dim, columns=["k", "name", "grp"])
    return ctx


def _rows(ctx, sql):
    def key(row):
        return tuple((v is None, 0 if v is None else v) for v in row)

    return sorted(collect(ctx.sql(sql)).to_rows(), key=key)


def _pd_rows(df, cols):
    out = []
    for t in df[cols].itertuples(index=False):
        out.append(tuple(None if pd.isna(v) else v for v in t))

    def key(row):
        return tuple((v is None, 0 if v is None else v) for v in row)

    return sorted(out, key=key)


def _counts():
    return dict(METRICS.snapshot()["counts"])


def _delta(a, b, k):
    return b.get(k, 0) - a.get(k, 0)


class TestJoinParity:
    def test_inner_dense_path(self, jctx):
        s0 = _counts()
        got = _rows(jctx, "SELECT seq, name FROM fact "
                          "JOIN dim ON fact.k = dim.k")
        s1 = _counts()
        exp = _pd_rows(jctx._fact.merge(jctx._dim, on="k"), ["seq", "name"])
        assert got == exp
        # unique int build key in a small range: the dense device path
        # must engage, probing every (256-row) batch in ONE fused launch
        assert _delta(s0, s1, "join.build.dense") == 1
        assert _delta(s0, s1, "device.launches.join.build") == 1
        n_batches = -(-600 // 256)
        assert _delta(s0, s1, "device.launches.join.probe") == n_batches

    def test_dense_build_under_interpret_kernel(self, jctx, monkeypatch):
        # DATAFUSION_TPU_PALLAS=interpret routes the slot-table build
        # through the Pallas hash-build kernel (counted), same rows
        sql = "SELECT seq, name FROM fact JOIN dim ON fact.k = dim.k"
        monkeypatch.setenv("DATAFUSION_TPU_PALLAS", "interpret")
        s0 = _counts()
        got = _rows(jctx, sql)
        s1 = _counts()
        assert _delta(s0, s1, "join.build.pallas_runs") == 1
        assert got == _pd_rows(jctx._fact.merge(jctx._dim, on="k"),
                               ["seq", "name"])

    def test_left_outer(self, jctx):
        got = _rows(jctx, "SELECT seq, name FROM fact "
                          "LEFT JOIN dim ON fact.k = dim.k")
        exp = _pd_rows(jctx._fact.merge(jctx._dim, on="k", how="left"),
                       ["seq", "name"])
        assert got == exp
        assert any(r[1] is None for r in got)  # dangling keys NULL-extend

    def test_join_filter_aggregate(self, jctx):
        got = _rows(jctx, "SELECT grp, COUNT(seq) FROM fact "
                          "JOIN dim ON fact.k = dim.k "
                          "WHERE x > 5 GROUP BY grp")
        df = jctx._fact.merge(jctx._dim, on="k")
        df = df[df.x > 5].groupby("grp", as_index=False).agg(n=("seq", "count"))
        exp = _pd_rows(df, ["grp", "n"])
        assert [(g, int(n)) for g, n in got] == exp

    def test_duplicate_build_keys_host_path(self, jctx, tmp_path):
        # grp repeats in dim -> non-unique build keys -> host CSR path
        s0 = _counts()
        got = _rows(jctx, "SELECT seq, name FROM fact "
                          "JOIN dim ON fact.k = dim.grp")
        s1 = _counts()
        exp = _pd_rows(
            jctx._fact.merge(jctx._dim, left_on="k", right_on="grp"),
            ["seq", "name"])
        assert got == exp
        assert _delta(s0, s1, "join.build.dense") == 0

    def test_utf8_key(self, jctx, tmp_path):
        # string-keyed join: dictionary codes differ per table, so the
        # match must go through content, never through code equality
        labels = [(f"name{i}", i * 11) for i in range(0, 60, 2)]
        jctx.register_csv(
            "labels", _write_csv(tmp_path / "lab.csv", "name,score", labels),
            Schema([Field("name", DataType.UTF8, False),
                    Field("score", DataType.INT64, False)]),
            has_header=True,
        )
        got = _rows(jctx, "SELECT grp, score FROM dim "
                          "JOIN labels ON dim.name = labels.name")
        lf = pd.DataFrame(labels, columns=["name", "score"])
        exp = _pd_rows(jctx._dim.merge(lf, on="name"), ["grp", "score"])
        assert got == exp

    def test_multi_key_join(self, jctx):
        got = _rows(jctx, "SELECT seq, name FROM fact "
                          "JOIN dim ON fact.k = dim.k AND fact.k = dim.grp")
        exp = _pd_rows(
            jctx._fact.merge(jctx._dim, on="k")
            .query("k == grp"), ["seq", "name"])
        assert got == exp


class TestJoinEdges:
    def _mini(self, tmp_path, left_rows, right_rows,
              left_null=False, right_null=False):
        ctx = ExecutionContext(batch_size=64)
        ctx.register_csv(
            "l", _write_csv(tmp_path / "l.csv", "k,v", left_rows),
            Schema([Field("k", DataType.INT64, left_null),
                    Field("v", DataType.INT64, False)]),
            has_header=True,
        )
        ctx.register_csv(
            "r", _write_csv(tmp_path / "r.csv", "k,w", right_rows),
            Schema([Field("k", DataType.INT64, right_null),
                    Field("w", DataType.INT64, False)]),
            has_header=True,
        )
        return ctx

    def test_null_keys_match_nothing(self, tmp_path):
        ctx = self._mini(
            tmp_path,
            [(1, 10), (None, 11), (2, 12), (None, 13)],
            [(1, 100), (None, 101), (2, 102)],
            left_null=True, right_null=True,
        )
        got = _rows(ctx, "SELECT v, w FROM l JOIN r ON l.k = r.k")
        assert got == [(10, 100), (12, 102)]  # NULL != NULL
        got = _rows(ctx, "SELECT v, w FROM l LEFT JOIN r ON l.k = r.k")
        assert got == [(10, 100), (11, None), (12, 102), (13, None)]

    def test_empty_build_side(self, tmp_path):
        ctx = self._mini(tmp_path, [(1, 10), (2, 20)], [])
        assert _rows(ctx, "SELECT v, w FROM l JOIN r ON l.k = r.k") == []
        assert _rows(ctx, "SELECT v, w FROM l LEFT JOIN r ON l.k = r.k") \
            == [(10, None), (20, None)]

    def test_empty_probe_side(self, tmp_path):
        ctx = self._mini(tmp_path, [], [(1, 100)])
        assert _rows(ctx, "SELECT v, w FROM l JOIN r ON l.k = r.k") == []
        assert _rows(ctx, "SELECT v, w FROM l LEFT JOIN r ON l.k = r.k") == []

    @pytest.mark.parametrize("dtype,vals", [
        (DataType.INT32, [3, 1, 4, 1, 5]),
        (DataType.INT64, [-(1 << 40), 0, 1 << 40, 0, 7]),
        (DataType.FLOAT64, [1.5, -0.0, 2.25, 0.0, 1.5]),
    ])
    def test_dtype_matrix(self, tmp_path, dtype, vals):
        left = [(v, i) for i, v in enumerate(vals)]
        right = [(v, i * 100) for i, v in enumerate(sorted(set(vals)))]
        ctx = ExecutionContext(batch_size=64)
        ctx.register_csv(
            "l", _write_csv(tmp_path / "l.csv", "k,v", left),
            Schema([Field("k", dtype, False),
                    Field("v", DataType.INT64, False)]),
            has_header=True,
        )
        ctx.register_csv(
            "r", _write_csv(tmp_path / "r.csv", "k,w", right),
            Schema([Field("k", dtype, False),
                    Field("w", DataType.INT64, False)]),
            has_header=True,
        )
        got = _rows(ctx, "SELECT v, w FROM l JOIN r ON l.k = r.k")
        lf = pd.DataFrame(left, columns=["k", "v"])
        rf = pd.DataFrame(right, columns=["k", "w"])
        exp = _pd_rows(lf.merge(rf, on="k"), ["v", "w"])
        assert got == exp
        # -0.0 joined 0.0 above: equal SQL values must meet


class TestPinnedBuild:
    def test_warm_probe_reuses_pinned_build_zero_h2d(self, jctx):
        q = "SELECT seq, name FROM fact JOIN dim ON fact.k = dim.k"
        s0 = _counts()
        _rows(jctx, q)
        s1 = _counts()
        # different predicate -> result cache miss, same build subtree
        _rows(jctx, q + " WHERE x > 5")
        s2 = _counts()
        assert _delta(s1, s2, "join.build.reuse") == 1
        assert _delta(s1, s2, "device.launches.join.build") == 0
        # the warm probe moved ZERO build-side bytes: its H2D
        # transfers are probe-input-only, strictly fewer than the cold
        # pass which also uploaded the build artifact
        cold = _delta(s0, s1, "device.h2d.transfers")
        warm = _delta(s1, s2, "device.h2d.transfers")
        assert warm < cold

    def test_distinct_key_columns_distinct_pins(self, jctx):
        # same build subtree joined on DIFFERENT right-side key columns
        # must not share a pinned artifact (regression: a k-keyed build
        # served a grp-keyed probe)
        a = _rows(jctx, "SELECT seq, name FROM fact JOIN dim ON fact.k = dim.k")
        b = _rows(jctx, "SELECT seq, name FROM fact JOIN dim ON fact.k = dim.grp")
        exp_a = _pd_rows(jctx._fact.merge(jctx._dim, on="k"), ["seq", "name"])
        exp_b = _pd_rows(
            jctx._fact.merge(jctx._dim, left_on="k", right_on="grp"),
            ["seq", "name"])
        assert a == exp_a
        assert b == exp_b


def _plan_of(ctx, sql):
    from datafusion_tpu.sql.parser import parse_sql

    return ctx._plan(parse_sql(sql))


class TestJoinPlanIR:
    def test_json_roundtrip(self, jctx):
        from datafusion_tpu.plan.logical import Join, LogicalPlan

        plan = _plan_of(
            jctx, "SELECT seq, name FROM fact JOIN dim ON fact.k = dim.k")
        wire = plan.to_json()
        back = LogicalPlan.from_json(wire)
        assert back.to_json() == wire

        def find_join(p):
            if isinstance(p, Join):
                return p
            for c in p.children():
                j = find_join(c)
                if j is not None:
                    return j
            return None

        assert find_join(back) is not None

    def test_verifier_accepts_join(self, jctx):
        from datafusion_tpu.analysis.verify import verify_plan

        plan = _plan_of(
            jctx, "SELECT seq, name FROM fact LEFT JOIN dim ON fact.k = dim.k")
        assert verify_plan(plan).ok

    def test_pushdown_through_join(self, jctx):
        from datafusion_tpu.plan.logical import Join, TableScan

        # ctx._plan already runs push_down_projection
        opt = _plan_of(
            jctx, "SELECT seq, name FROM fact JOIN dim ON fact.k = dim.k")

        def scans(p, out):
            if isinstance(p, TableScan):
                out.append(p)
            for c in p.children():
                scans(c, out)
            return out

        got = {s.table_name: s.projection for s in scans(opt, [])}
        # fact needs k (key) + seq; dim needs k (key) + name — x and
        # grp must be trimmed before any byte is parsed or shipped
        assert got["fact"] == [0, 1]
        assert got["dim"] == [0, 1]

        def find_join(p):
            if isinstance(p, Join):
                return p
            for c in p.children():
                j = find_join(c)
                if j is not None:
                    return j

        j = find_join(opt)
        assert j.on == [(0, 0)]  # keys remapped to trimmed positions

    def test_parser_rejects_non_equi(self, jctx):
        from datafusion_tpu.errors import DataFusionError

        with pytest.raises(DataFusionError):
            _plan_of(jctx,
                     "SELECT seq FROM fact JOIN dim ON fact.k > dim.k")


class TestShuffleUnits:
    def test_partition_deterministic_and_content_hashed(self):
        from datafusion_tpu.exec.batch import StringDictionary
        from datafusion_tpu.join.core import partition_of

        keys = np.array([5, 17, 5, 99, -3], np.int64)
        a = partition_of([keys], [None], 4)
        b = partition_of([keys.copy()], [None], 4)
        assert (a == b).all()
        assert (a[0] == a[2]).all()  # equal keys, equal partition
        # utf8: two dictionaries with DIFFERENT code orders for the
        # same strings must partition identically (content, not codes)
        d1, d2 = StringDictionary(), StringDictionary()
        c1 = d1.encode(["x", "y", "z"])
        c2 = d2.encode(["z", "y", "x"])[::-1].copy()
        p1 = partition_of([c1], [None], 8, dicts=[d1])
        p2 = partition_of([c2], [None], 8, dicts=[d2])
        assert (p1 == p2).all()

    def test_split_merge_dedup_roundtrip(self):
        from datafusion_tpu.parallel import shuffle

        raw = {
            "num_rows": 40,
            "columns": [
                np.arange(40, dtype=np.int64),
                {"codes": (np.arange(40) % 3).astype(np.int32),
                 "values": ["a", "b", "c"]},
            ],
            "validity": [None, np.array([True] * 39 + [False])],
        }
        blocks = shuffle.split_blocks(raw, [0], 5, ("frag-fp", "L", 5, [0]))
        assert len(blocks) == 5
        assert sum(b["num_rows"] for b in blocks) == 40
        rt = [shuffle.decode_block(shuffle.encode_block(b, None))
              for b in blocks]
        s0 = _counts()
        # the same blocks delivered twice (replayed map task): the
        # merge must drop the duplicates by fingerprint, not double the rows
        cols, valids, dicts, total = shuffle.merge_side(rt + rt)
        s1 = _counts()
        assert total == 40
        assert sorted(cols[0].tolist()) == list(range(40))
        assert _delta(s0, s1, "shuffle.dedup_drops") == 5
        assert dicts[1] is not None and valids[1] is not None

    def test_reduce_join_parity(self):
        from datafusion_tpu.parallel import shuffle

        rng = np.random.default_rng(3)
        lk = rng.integers(0, 25, 300)
        rk = rng.integers(0, 25, 60)
        lraw = {"num_rows": 300,
                "columns": [lk.astype(np.int64),
                            np.arange(300, dtype=np.int64)],
                "validity": [None, None]}
        rraw = {"num_rows": 60,
                "columns": [rk.astype(np.int64),
                            np.arange(60, dtype=np.int64)],
                "validity": [None, None]}
        for join_type, how in (("inner", "inner"), ("left", "left")):
            tot = 0
            for p in range(4):
                lb = shuffle.split_blocks(lraw, [0], 4, ("l",))
                rb = shuffle.split_blocks(rraw, [0], 4, ("r",))
                out = shuffle.reduce_join([lb[p]], [rb[p]], [(0, 0)],
                                          join_type)
                tot += out["num_rows"]
            exp = pd.DataFrame({"k": lk}).merge(
                pd.DataFrame({"k": rk}), on="k", how=how).shape[0]
            assert tot == exp, (join_type, tot, exp)
