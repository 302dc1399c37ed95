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

import numpy as np
import pandas as pd
import pytest

from datafusion_tpu import DataType, ExecutionContext, Field, Schema
from datafusion_tpu.exec.materialize import collect
from datafusion_tpu.exec.rowgather import LANES, pad_rows
from datafusion_tpu.obs.device import LEDGER
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


def _nulls_last(row):
    return tuple((v is None, 0 if v is None else v) for v in row)


def _rows(ctx, sql):
    return sorted(collect(ctx.sql(sql)).to_rows(), key=_nulls_last)


def _pd_rows(df, cols):
    out = []
    for t in df[cols].itertuples(index=False):
        out.append(tuple(None if pd.isna(v) else v for v in t))
    return sorted(out, key=_nulls_last)


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
        # (over a column the cold query read too: the probe side ships
        # the same columns both times)
        _rows(jctx, q + " WHERE seq >= 0")
        s2 = _counts()
        assert _delta(s1, s2, "join.build.reuse") == 1
        assert _delta(s1, s2, "device.launches.join.build") == 0
        # the warm probe moved ZERO build-side bytes: its H2D
        # transfers are probe-input-only, strictly fewer than the cold
        # pass which also uploaded the build artifact
        cold = _delta(s0, s1, "device.h2d.transfers")
        warm = _delta(s1, s2, "device.h2d.transfers")
        # slot positions, live flags and `name`'s codes: not the key
        assert cold - warm == 3

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


# -- a fact-to-fact join: sparse keys over a wide range, a large build ------


def _mem_table(ctx, name, columns, batch_rows=512):
    """Register an in-memory table from {column: ndarray | (values,
    validity) | list of str}: the array's own integer type, or Utf8
    through one dictionary."""
    from datafusion_tpu.datatypes import from_np_dtype
    from datafusion_tpu.exec.batch import StringDictionary, make_host_batch
    from datafusion_tpu.exec.datasource import MemoryDataSource

    fields, cols, valids, dicts = [], [], [], []
    for cname, col in columns.items():
        valid = None
        if isinstance(col, tuple):
            col, valid = col
        if isinstance(col, list):
            d = StringDictionary()
            col, dtype = d.encode(col), DataType.UTF8
        else:
            d, dtype = None, from_np_dtype(np.asarray(col).dtype)
        fields.append(Field(cname, dtype, valid is not None))
        cols.append(np.asarray(col))
        valids.append(valid)
        dicts.append(d)
    schema = Schema(fields)
    n = len(cols[0])
    batches = [
        make_host_batch(
            schema, [c[lo: lo + batch_rows] for c in cols],
            [None if v is None else v[lo: lo + batch_rows] for v in valids],
            dicts)
        for lo in range(0, max(n, 1), batch_rows)
    ]
    ctx.register_datasource(name, MemoryDataSource(schema, batches))


PRIOS = ["1-URGENT", "2-HIGH", "3-MEDIUM"]


def _sparse_pair(ctx, suffix, n_build=400_000, n_probe=5_000, span=1_600_000):
    """`o<suffix>` with `n_build` unique keys spread over `span` (past
    2^20 slots, four a build row as TPC-H's orders have them),
    `l<suffix>` probing it with hits, in-range misses, keys far outside
    the build's range and NULLs.  Returns the arrays."""
    rng = np.random.default_rng(11)
    okey = np.sort(rng.choice(span, n_build, replace=False)).astype(np.int64) + 7
    oprio = rng.integers(0, len(PRIOS), n_build)
    lkey = np.concatenate([
        rng.choice(okey, n_probe - 900),
        rng.integers(7, span + 7, 500),  # mostly misses, inside the range
        rng.integers(-(1 << 40), 0, 200),  # far below
        rng.integers(span + 8, 1 << 41, 200),  # far above: must not wrap
    ]).astype(np.int64)
    rng.shuffle(lkey)
    lvalid = rng.random(n_probe) > 0.05
    lseq = np.arange(n_probe, dtype=np.int64)
    _mem_table(ctx, "o" + suffix, {
        "ok": okey, "oprio": [PRIOS[i] for i in oprio]}, batch_rows=1 << 16)
    _mem_table(ctx, "l" + suffix, {"lk": (lkey, lvalid), "lseq": lseq})
    return okey, oprio, lkey, lvalid, lseq


def _numpy_merge(okey, oprio, lkey, lvalid, lseq, how):
    """The join written out: each probe row's build row by its key in
    the sorted build keys."""
    pos = np.minimum(np.searchsorted(okey, lkey), len(okey) - 1)
    hit = lvalid & (okey[pos] == lkey)
    rows = [(int(s), PRIOS[oprio[p]]) for s, p in zip(lseq[hit], pos[hit])]
    if how == "left":
        rows += [(int(s), None) for s in lseq[~hit]]
    return sorted(rows, key=lambda r: (r[0], r[1] is None))


class TestFactToFactJoin:
    @pytest.fixture(autouse=True)
    def _no_learned_sizes(self, monkeypatch):
        """These tables probe a large build with a few rows; a planner
        that has seen the sizes would build from the small side."""
        monkeypatch.setenv("DATAFUSION_TPU_COST", "0")

    @pytest.mark.parametrize("how", ["inner", "left"])
    def test_sparse_key_past_2_20_slots_probes_on_the_device(
            self, how, monkeypatch):
        join = "JOIN" if how == "inner" else "LEFT JOIN"
        sql = "SELECT lseq, oprio FROM l{0} " + join + " o{0} ON l{0}.lk = o{0}.ok"
        ctx = ExecutionContext(batch_size=512)
        arrays = _sparse_pair(ctx, "_dev_" + how)
        assert arrays[0].max() - arrays[0].min() + 1 > 1 << 20
        s0 = _counts()
        got = _rows(ctx, sql.format("_dev_" + how))
        s1 = _counts()
        want = _numpy_merge(*arrays, how)
        assert got == want and len(want) > 3_000
        assert 4 * len(arrays[0]) > arrays[0].max() - arrays[0].min() + 1
        assert _delta(s0, s1, "join.build.dense") == 1
        assert _delta(s0, s1, "device.launches.join.probe") == -(-5_000 // 512)
        assert _delta(s0, s1, "join.probe.rows") == 5_000
        assert _delta(s0, s1, "join.host_probe.rows") == 0
        assert _delta(s0, s1, "join.build.bytes") > 4 * (1 << 20)
        # the same tables through the host HashIndex
        monkeypatch.setenv("DATAFUSION_TPU_JOIN_DEVICE", "0")
        host = ExecutionContext(batch_size=512)
        _sparse_pair(host, "_host_" + how)
        assert _rows(host, sql.format("_host_" + how)) == want
        s2 = _counts()
        assert _delta(s1, s2, "device.launches.join.probe") == 0
        assert _delta(s1, s2, "join.probe.rows") == 0
        # the host probe compacts first: NULL keys stay in, masked rows out
        assert _delta(s1, s2, "join.host_probe.rows") == 5_000

    @pytest.mark.parametrize("wide,words", [(0, 1), (1 << 40, 2)])
    def test_a_build_over_64_mb_is_pinned_and_probed_again(self, wide, words):
        """2.2 M keys, every fourth of 8.8 M: two int64 payload columns
        and a 35 MB slot table; the 18 MB key column is not placed.
        Values that fit 32 bits are placed as one word each (53 MB in
        all), values past them as both (70 MB).  It fits what is free,
        so the second query builds nothing."""
        ctx = ExecutionContext(batch_size=512)
        okey = np.arange(2_200_000, dtype=np.int64) * 4 + 5
        _mem_table(ctx, f"o_big{words}", {"ok": okey, "oval": okey * 3 + wide,
                                          "oneg": -okey - wide},
                   batch_rows=1 << 18)
        lkey = np.array([5, 6, 8_800_001, 8_800_005, 9], np.int64)
        _mem_table(ctx, f"l_big{words}",
                   {"lk": lkey, "lseq": np.arange(5, dtype=np.int64)})
        sql = (f"SELECT lseq, oval, oneg FROM l_big{words} JOIN o_big{words} "
               f"ON l_big{words}.lk = o_big{words}.ok")
        want = [(0, 15 + wide, -5 - wide),
                (2, 26_400_003 + wide, -8_800_001 - wide),
                (4, 27 + wide, -9 - wide)]
        s0 = _counts()
        assert _rows(ctx, sql) == want
        s1 = _counts()
        # what is placed: two payload columns and the slot table
        placed = (2 * words * okey.nbytes // 2
                  + 4 * pad_rows(int(okey[-1] - okey[0]) + 1))
        assert _delta(s0, s1, "join.build.bytes") == placed
        assert (placed > 64 << 20) == (words == 2)
        assert _delta(s0, s1, "device.launches.join.build") == 1
        assert LEDGER.pinned_bytes() >= _delta(s0, s1, "join.build.bytes")
        assert _rows(ctx, sql + " WHERE lseq >= 0") == want
        s2 = _counts()
        assert _delta(s1, s2, "join.build.reuse") == 1
        assert _delta(s1, s2, "join.build.rows") == 0
        assert _delta(s1, s2, "join.build.bytes") == 0
        assert _delta(s1, s2, "device.launches.join.build") == 0
        assert _delta(s1, s2, "device.launches.join.probe") == 1

    def test_a_small_build_over_a_wide_key_range_stays_on_the_host(self):
        """The density side of the rule: 100 keys spread over 2^31
        would scatter an 8.6 GB slot table.  The host index keeps the
        job, is pinned (it holds no HBM: the pin counts none), and its
        bytes are its columns' alone."""
        ctx = ExecutionContext(batch_size=512)
        okey = np.arange(100, dtype=np.int64) * ((1 << 31) // 100) + 1
        _mem_table(ctx, "o_wide", {"ok": okey, "oval": okey * 3})
        lkey = np.concatenate([okey[::7], okey[:5] + 1])
        _mem_table(ctx, "l_wide", {"lk": lkey,
                                   "lseq": np.arange(len(lkey), dtype=np.int64)})
        sql = "SELECT lseq, oval FROM l_wide JOIN o_wide ON l_wide.lk = o_wide.ok"
        want = [(i, int(k) * 3) for i, k in enumerate(okey[::7])]
        s0, pinned0 = _counts(), LEDGER.pinned_bytes()
        assert _rows(ctx, sql) == want
        assert _rows(ctx, sql + " WHERE lseq >= 0") == want
        s1 = _counts()
        assert _delta(s0, s1, "join.build.dense") == 0
        assert _delta(s0, s1, "device.launches.join.build") == 0
        assert _delta(s0, s1, "join.host_probe.rows") == 2 * len(lkey)
        assert _delta(s0, s1, "join.build.bytes") == 100 * 16
        assert _delta(s0, s1, "join.build.reuse") == 1
        assert LEDGER.pinned_bytes() == pinned0
        # eight slots a row are the limit: 100 keys over 800 build dense
        _mem_table(ctx, "o_near", {"ok": np.arange(100, dtype=np.int64) * 8 + 1,
                                   "oval": okey})
        assert len(_rows(ctx, sql.replace("o_wide", "o_near"))) == 1
        assert _delta(s1, _counts(), "join.build.dense") == 1

    def test_a_build_that_does_not_fit_is_probed_on_the_host(self, monkeypatch):
        """The other side of the rule: what the ledger has free is less
        than the slot table, so the host index keeps the job, is not
        pinned, and the counter says so."""
        ctx = ExecutionContext(batch_size=512)
        arrays = _sparse_pair(ctx, "_nofit")  # 4.8 MB of columns, 6.4 MB of slots
        monkeypatch.setenv("DATAFUSION_TPU_HBM_BYTES",
                           str(LEDGER.live_bytes() + (4 << 20)))
        assert not LEDGER.fits(12 << 20) and LEDGER.fits(1 << 20)
        sql = ("SELECT lseq, oprio FROM l_nofit JOIN o_nofit "
               "ON l_nofit.lk = o_nofit.ok")
        s0 = _counts()
        assert _rows(ctx, sql) == _numpy_merge(*arrays, "inner")
        assert _rows(ctx, sql + " WHERE lseq >= 0") == _numpy_merge(
            *arrays, "inner")
        s1 = _counts()
        assert _delta(s0, s1, "join.build.dense") == 0
        assert _delta(s0, s1, "device.launches.join.probe") == 0
        assert _delta(s0, s1, "join.host_probe.rows") == 2 * 5_000
        assert _delta(s0, s1, "join.build.reuse") == 0  # built twice
        assert _delta(s0, s1, "join.build.rows") == 2 * 400_000
        # no slot table was made, and none is counted
        assert _delta(s0, s1, "join.build.bytes") == 2 * 400_000 * 12

    def test_grouping_join_output_by_a_build_side_string_pulls_no_key(self):
        """Aggregate(Selection(Join)) grouped by (probe-side string,
        build-side string): the ids are made on the device from the
        dictionaries' codes, and the answer is all that comes back."""
        ctx = ExecutionContext(batch_size=512)
        okey, oprio, lkey, lvalid, lseq = _sparse_pair(ctx, "_agg")
        modes = ["MAIL", "SHIP", "RAIL"]
        lmode = np.random.default_rng(5).integers(0, 3, len(lkey))
        _mem_table(ctx, "l_agg", {"lk": (lkey, lvalid), "lseq": lseq,
                                  "lmode": [modes[i] for i in lmode]})
        sql = ("SELECT lmode, oprio, COUNT(1) FROM l_agg JOIN o_agg "
               "ON l_agg.lk = o_agg.ok WHERE lseq >= 100 AND "
               "(lmode = 'MAIL' OR lmode = 'SHIP') GROUP BY lmode, oprio")
        pos = np.minimum(np.searchsorted(okey, lkey), len(okey) - 1)
        assert _rows(ctx, sql)  # warm: programs, dictionaries, the build
        s0 = _counts()
        got = collect(ctx.sql(sql.replace("100", "101")))
        s1 = _counts()
        keep = (lvalid & (okey[pos] == lkey) & (lseq >= 101) & (lmode < 2))
        want = {}
        for m, p in zip(lmode[keep], oprio[pos[keep]]):
            want[(modes[m], PRIOS[p])] = want.get((modes[m], PRIOS[p]), 0) + 1
        assert sorted(got.to_rows()) == sorted(
            (m, p, n) for (m, p), n in want.items())
        assert len(want) == 6
        # the ids are made inside the aggregate's own launches
        assert _delta(s0, s1, "device.launches") == -(-5_000 // 512) + sum(
            _delta(s0, s1, "device.launches.agg" + t)
            for t in ("", ".group", ".chunk"))
        assert _delta(s0, s1, "device.launches.join.probe") == -(-5_000 // 512)
        assert _delta(s0, s1, "join.host_probe.rows") == 0
        # the accumulator's state (counts of at most 16 slots and the
        # group's row counts) is all that crosses: no 512-row key column
        assert 0 < _delta(s0, s1, "d2h.bytes") <= 1024


@pytest.mark.parametrize("how", ["inner", "left"])
@pytest.mark.parametrize("keys", ["clustered", "scattered", "no_live_row"])
def test_probe_reading_rows_equals_numpy_reading_elements(how, keys):
    """The probe program (tables read as 128-wide rows, the lane
    selected afterwards) against the same lookups written with numpy's
    element indexing: a nullable payload, NULL and far keys, keys that
    would wrap into a slot as int32.  The build key column is made
    from the probe key: equal to `bkey[sb]` wherever a row hit."""
    import jax.numpy as jnp

    from datafusion_tpu.join import relation as jr

    rng = np.random.default_rng(3)
    n_build, kmin = 40_000, 1_000
    num_slots = 700_077
    pos = np.sort(rng.choice(num_slots, n_build, replace=False))
    slot_row = np.full(pad_rows(num_slots), -1, np.int32)
    slot_row[pos] = np.arange(n_build, dtype=np.int32)
    pad = pad_rows(n_build) - n_build
    bkey = np.pad(pos.astype(np.int32) + kmin, (0, pad))
    pay = np.pad(rng.integers(0, 1 << 40, n_build), (0, pad))
    pay_valid = np.pad(rng.random(n_build) > 0.2, (0, pad))
    cap = 4_096
    if keys == "clustered":
        lo = int(pos[20_000])
        key = rng.integers(lo, lo + 60_000, cap) + kmin
        key[:2_000] = rng.choice(pos[(pos >= lo) & (pos < lo + 60_000)], 2_000) + kmin
    else:
        key = rng.integers(-5, num_slots + 5, cap) + kmin
        key[:2_000] = rng.choice(pos, 2_000) + kmin
        key[2_000:2_010] = [-(1 << 40), 1 << 41, kmin - 1, kmin + num_slots,
                            (1 << 32) + kmin + int(pos[0])] * 2
    rows = lambda a: jnp.asarray(a).reshape(-1, LANES)  # noqa: E731
    kvalid = rng.random(cap) > 0.1
    if keys == "no_live_row":
        kvalid[:] = False
    mask = rng.random(cap) > 0.3
    args = (jnp.asarray(key.astype(np.int64)), jnp.asarray(kvalid),
            jnp.asarray(mask), np.int64(kmin), np.int64(num_slots),
            rows(slot_row), (rows(pay),), (rows(pay_valid),))
    kcol, kval, gath, gval, out_mask, windows = jr._probe_fn_for(
        how, "int32")(*args)
    # a probe key of the build column's own dtype is handed on as it is
    assert jr._probe_fn_for(how, "int64")(*args)[0] is None
    d = key - kmin
    inr = kvalid & (d >= 0) & (d < num_slots)
    bidx = np.where(inr, slot_row[np.where(inr, d, 0)], -1)
    hit = bidx >= 0
    assert np.array_equal(hit, kvalid & np.isin(d, pos))
    assert hit.sum() > (0 if keys != "no_live_row" else -1)
    # a row the selection dropped looks nothing up: the launch's `hit`
    # holds the mask, and what a miss row reads is not observable
    hit &= mask
    sb = np.where(hit, bidx, 0)
    assert kcol.dtype == bkey.dtype
    assert np.array_equal(np.asarray(kcol)[hit], bkey[sb][hit])
    # across signedness too: a hit's key fits the build column's type
    ucol = jr._probe_fn_for(how, "uint32")(*args)[0]
    assert ucol.dtype == np.uint32
    assert np.array_equal(np.asarray(ucol)[hit], bkey[sb][hit])
    assert len(gath) == len(gval) == 1
    assert np.array_equal(np.asarray(gath[0])[hit], pay[sb][hit])
    assert np.array_equal(np.asarray(gval[0]), hit & pay_valid[sb])
    if how == "inner":
        assert kval is None
        assert np.array_equal(np.asarray(out_mask), hit)
    else:
        assert np.array_equal(np.asarray(kval), hit)
        assert np.array_equal(np.asarray(out_mask), mask)
    # 60,000 slots are 469 rows of the 5,470-row slot table, and their
    # build rows a few dozen of the payload's 313 (a table of at most
    # `WINDOW_ROWS` rows is read whole, by shape)
    assert windows.dtype == np.int32
    assert np.asarray(windows).tolist() == [int(keys != "scattered"), 0]


# -- the window a probe launch picks from its batch's own keys ----------
_WINDOW_FEATURES = ["misses", "null_keys", "out_of_range_keys",
                    "selection_mask", "ragged_last_batch",
                    "int64_payload_of_two_words", "payload_with_validity"]


def _window_tables(ctx, suffix, order, feature):
    """`o<suffix>`: 300,000 keys, every fourth of 1.2 M (a slot table
    of 9,375 rows, a payload of 2,344: both past `WINDOW_ROWS`), and
    `l<suffix>` probing it in batches of 512, in key order (a batch
    spans ~1,170 rows of slots and ~290 of payload) or shuffled (every
    batch spans all of both).  Rows that look nothing up (a NULL key,
    a key out of range, a row an earlier join dropped) carry keys from
    both ends of the build.  Returns the SQL over them."""
    rng = np.random.default_rng(len(feature))
    okey = np.arange(300_000, dtype=np.int64) * 4 + 7
    oval = okey * 3
    if feature == "int64_payload_of_two_words":
        oval = oval + (1 << 40)
    if feature == "payload_with_validity":
        oval = (oval, rng.random(len(okey)) > 0.2)
    n = 4_000 if feature == "ragged_last_batch" else 4_096
    lkey = rng.choice(okey, n)
    if order == "key_order":
        lkey.sort()
    ends = np.where(np.arange(n) % 2 == 0, okey[0], okey[-1])
    cols = {"lseq": np.arange(n, dtype=np.int64)}
    if feature == "misses":
        lkey[::5] += 1
    if feature == "null_keys":
        lvalid = rng.random(n) > 0.1
        lkey = (np.where(lvalid, lkey, ends), lvalid)
    if feature == "out_of_range_keys":
        far = rng.choice(n, 300, replace=False)
        # far below, far above, and one that is a slot once cut to 32 bits
        lkey[far] = np.resize(
            [-(1 << 40), 1 << 41, okey[0] - 1, okey[-1] + 1,
             (1 << 32) + okey[5]], 300)
    join = "JOIN f{0} ON l{0}.lf = f{0}.fid " if feature == "selection_mask" else ""
    if feature == "selection_mask":
        lf = rng.integers(0, 13, n)
        lkey = np.where(lf < 10, lkey, ends)  # the first join drops lf >= 10
        cols["lf"] = lf
        _mem_table(ctx, "f" + suffix, {"fid": np.arange(10, dtype=np.int64)})
    cols["lk"] = lkey
    _mem_table(ctx, "o" + suffix, {"ok": okey, "oval": oval},
               batch_rows=1 << 16)
    _mem_table(ctx, "l" + suffix, cols)
    return ("SELECT lseq, ok, oval FROM l{0} " + join
            + "{1} o{0} ON l{0}.lk = o{0}.ok").format(suffix, "{0}")


@pytest.mark.parametrize("feature", _WINDOW_FEATURES)
@pytest.mark.parametrize("how", ["inner", "left"])
@pytest.mark.parametrize("order", ["key_order", "shuffled"])
def test_probe_window_engages_by_the_batchs_own_keys(
        order, how, feature, monkeypatch):
    """Every launch of an ordered probe side reads the slot table and
    the payload through the window, no launch of a shuffled one does,
    and the rows are the host index's either way."""
    from datafusion_tpu.join import relation as jr

    monkeypatch.setenv("DATAFUSION_TPU_COST", "0")
    join = "JOIN" if how == "inner" else "LEFT JOIN"
    suffix = f"_w_{order[0]}{how[0]}{_WINDOW_FEATURES.index(feature)}"
    ctx = ExecutionContext(batch_size=512)
    sql = _window_tables(ctx, suffix, order, feature).format(join)
    pulls = []

    def counted_pull(tree):
        before = _counts().get("d2h.bytes", 0)
        out = pull(tree)
        pulls.append(_counts().get("d2h.bytes", 0) - before)
        return out

    pull = jr.device_pull
    monkeypatch.setattr(jr, "device_pull", counted_pull)
    s0 = _counts()
    got = _rows(ctx, sql)
    s1 = _counts()
    joins = 2 if feature == "selection_mask" else 1
    batches = 8
    assert _delta(s0, s1, "join.build.dense") == joins
    assert _delta(s0, s1, "device.launches.join.probe") == joins * batches
    assert _delta(s0, s1, "join.host_probe.rows") == 0
    # the ten-row table of the first join is read whole, by shape
    taken = batches if order == "key_order" else 0
    assert _delta(s0, s1, "join.probe.window.slot") == taken
    assert _delta(s0, s1, "join.probe.window.payload") == taken
    # one pull of at most 16 B a join, after its last batch (the sum it
    # reads is the pull's own: no launch of the engine's is counted)
    assert len(pulls) == joins and all(0 < b <= 16 for b in pulls)
    tags = {k for k in s1 if k.startswith("device.launches.join")
            and _delta(s0, s1, k)}
    assert tags == {"device.launches.join.probe", "device.launches.join.build"}
    arrays = 2 if feature == "payload_with_validity" else 1
    assert _delta(s0, s1, "join.probe.gathers") == batches * arrays
    monkeypatch.setenv("DATAFUSION_TPU_JOIN_DEVICE", "0")
    host = ExecutionContext(batch_size=512)
    want = _rows(host, _window_tables(host, suffix + "h", order, feature)
                 .format(join))
    s2 = _counts()
    assert _delta(s1, s2, "join.host_probe.rows") > 0
    assert _delta(s1, s2, "join.probe.window.slot") == 0
    assert got == want and len(want) > 2_000
    # a LEFT join keeps the rows that found nothing, as NULLs
    assert any(r[2] is None for r in want) == (
        how == "left" and feature in _WINDOW_FEATURES[:3]
        or feature == "payload_with_validity")


# -- the build key column is made from the probe key ------------------------
# (probe key dtype, build key dtype, join, nullable build key, select
# list; the build has a payload column `oval` where the list names it,
# else its key is its only column): every dense probe hands the build
# key on from the probe batch, so each shape of key must read as the
# gather did
_KEY_CASES = {
    "i32_probe_i64_build": (np.int32, np.int64, "inner", False,
                            "lseq, ok, oval"),
    "i64_probe_i32_build": (np.int64, np.int32, "inner", False,
                            "lseq, ok, oval"),
    # (the planner coerces no unsigned key to a signed one: the program
    # test above casts across signedness)
    "u32_probe_u64_build": (np.uint32, np.uint64, "left", False,
                            "lseq, ok, oval"),
    "u32_probe_u32_build": (np.uint32, np.uint32, "inner", False,
                            "lseq, ok"),
    "left_outer_null_key": (np.int64, np.int64, "left", False,
                            "lseq, lk, ok, oval"),
    "nullable_build_key_inner": (np.int64, np.int64, "inner", True,
                                 "lseq, ok, oval"),
    "nullable_build_key_left": (np.int64, np.int64, "left", True,
                                "lseq, ok, oval"),
    "key_only_build_inner": (np.int64, np.int64, "inner", False,
                             "lseq, ok"),
    "key_only_build_left": (np.int32, np.int64, "left", False,
                            "lseq, ok"),
    "select_build_key_alone": (np.int64, np.int64, "inner", False,
                               "ok"),
    "group_by_build_key": (np.int64, np.int64, "inner", False,
                           "ok, COUNT(1)"),
    "group_by_cast_build_key": (np.int64, np.int32, "inner", False,
                                "ok, COUNT(1)"),
}


def _key_case_tables(ctx, suffix, probe_dt, build_dt, null_build, payload):
    """`o<suffix>` (300 unique keys over 1,500 slots, its key of
    `build_dt`, NULL in a tenth of its rows if `null_build`) and
    `l<suffix>` (1,000 rows of `probe_dt`: hits, misses inside the
    range, keys whose cast to the build's type would wrap onto a build
    key, NULLs).  Returns the arrays."""
    rng = np.random.default_rng(29)
    okey = (np.sort(rng.choice(1_500, 300, replace=False)) + 40).astype(build_dt)
    ovalid = rng.random(300) > 0.1 if null_build else None
    oval = rng.integers(-(1 << 40), 1 << 40, 300)
    lkey = np.concatenate([rng.choice(okey, 600),
                           rng.integers(0, 1_600, 380)]).astype(np.int64)
    far = okey[:20].astype(np.int64)
    if np.dtype(probe_dt).itemsize == 8:
        far = far + (1 << 32)  # as int32 / uint32: a build key
    lkey = np.concatenate([lkey, far])
    rng.shuffle(lkey)
    lkey = lkey.astype(probe_dt)
    lvalid = rng.random(1_000) > 0.05
    lseq = np.arange(1_000, dtype=np.int64)
    build = {"ok": okey if ovalid is None else (okey, ovalid)}
    if payload:
        build["oval"] = oval
    _mem_table(ctx, "o" + suffix, build, batch_rows=128)
    _mem_table(ctx, "l" + suffix, {"lk": (lkey, lvalid), "lseq": lseq},
               batch_rows=256)
    return okey, ovalid, oval, lkey, lvalid, lseq


def _key_case_rows(arrays, how, select):
    """The join written out with a dict from live build key to row."""
    okey, ovalid, oval, lkey, lvalid, lseq = arrays
    row_of = {int(k): i for i, k in enumerate(okey)
              if ovalid is None or ovalid[i]}
    rows = []
    for k, v, seq in zip(lkey.tolist(), lvalid.tolist(), lseq.tolist()):
        b = row_of.get(k) if v else None
        if b is None and how == "inner":
            continue
        rows.append({
            "lseq": seq, "lk": k if v else None,
            "ok": None if b is None else int(okey[b]),
            "oval": None if b is None else int(oval[b])})
    names = select.split(", ")
    if names[-1] == "COUNT(1)":
        tally: dict = {}
        for r in rows:
            tally[r["ok"]] = tally.get(r["ok"], 0) + 1
        out = list(tally.items())
    else:
        out = [tuple(r[n] for n in names) for r in rows]
    return sorted(out, key=_nulls_last)


@pytest.mark.parametrize("case", list(_KEY_CASES))
def test_build_key_column_is_made_from_the_probe_key(case, monkeypatch):
    """A dense probe's output column for the build key against numpy
    and against the host `HashIndex` path over the same tables, and
    `join.probe.gathers`: a launch gathers from the build's arrays
    other than its key's."""
    monkeypatch.setenv("DATAFUSION_TPU_COST", "0")
    probe_dt, build_dt, how, null_build, select = _KEY_CASES[case]
    payload = "oval" in select
    sql = ("SELECT {0} FROM l{1} " + ("JOIN" if how == "inner" else "LEFT JOIN")
           + " o{1} ON l{1}.lk = o{1}.ok"
           + (" GROUP BY ok" if "COUNT" in select else ""))
    ctx = ExecutionContext(batch_size=256)
    arrays = _key_case_tables(ctx, "_kd_" + case, probe_dt, build_dt,
                              null_build, payload)
    want = _key_case_rows(arrays, how, select)
    assert len(want) > (200 if "COUNT" in select else 500)
    s0 = _counts()
    assert _rows(ctx, sql.format(select, "_kd_" + case)) == want
    s1 = _counts()
    assert _delta(s0, s1, "join.build.dense") == 1
    assert _delta(s0, s1, "join.host_probe.rows") == 0
    assert _delta(s0, s1, "join.probe.rows") == 1_000
    launches = _delta(s0, s1, "device.launches.join.probe")
    assert launches == -(-1_000 // 256)
    # the key's column and validity are never among them, nor placed:
    # the slot table and the payload column are all
    assert _delta(s0, s1, "join.probe.gathers") == launches * payload
    assert _delta(s0, s1, "join.build.bytes") == 4 * 1_536 + 8 * 300 * payload
    monkeypatch.setenv("DATAFUSION_TPU_JOIN_DEVICE", "0")
    host = ExecutionContext(batch_size=256)
    _key_case_tables(host, "_kh_" + case, probe_dt, build_dt, null_build,
                     payload)
    assert _rows(host, sql.format(select, "_kh_" + case)) == want
    s2 = _counts()
    assert _delta(s1, s2, "join.probe.gathers") == 0
    assert _delta(s1, s2, "join.host_probe.rows") > 0


def test_probe_gathers_counts_the_builds_arrays_other_than_the_key():
    """Two payload columns, one of them nullable, the key standing
    between them: three arrays a launch, where the build has five
    (key, its validity, two columns, one validity)."""
    ctx = ExecutionContext(batch_size=256)
    rng = np.random.default_rng(31)
    okey = np.arange(500, dtype=np.int64) * 3 + 11
    _mem_table(ctx, "o_g3", {
        "oa": okey * 7, "ok": (okey, rng.random(500) > 0.1),
        "ob": (okey * 9, rng.random(500) > 0.5)})
    _mem_table(ctx, "l_g3", {"lk": rng.integers(0, 1_600, 700),
                             "lseq": np.arange(700, dtype=np.int64)})
    s0 = _counts()
    got = _rows(ctx, "SELECT lseq, ok, oa, ob FROM l_g3 JOIN o_g3 "
                     "ON l_g3.lk = o_g3.ok")
    s1 = _counts()
    assert got and all(k * 7 == a and b in (None, k * 9)
                       for _, k, a, b in got)
    assert _delta(s0, s1, "device.launches.join.probe") == 2
    assert _delta(s0, s1, "join.probe.gathers") == 2 * 3


def test_contexts_with_same_named_memory_tables_do_not_share_a_build():
    """A pinned build outlives its context in the process-wide ledger:
    two contexts that register different in-memory tables under the
    same names (a test process, a benchmark run after another) each
    get their own, by the fingerprint of the tables' identity."""
    sql = "SELECT lseq, oprio FROM l_same JOIN o_same ON l_same.lk = o_same.ok"
    seen = []
    for prios in (["1-URGENT", "2-HIGH"], ["3-MEDIUM", "2-HIGH"]):
        ctx = ExecutionContext(batch_size=512)
        _mem_table(ctx, "o_same", {"ok": np.array([3, 9], np.int64),
                                   "oprio": prios})
        _mem_table(ctx, "l_same", {"lk": np.array([9, 3, 4], np.int64),
                                   "lseq": np.arange(3, dtype=np.int64)})
        s0 = _counts()
        assert _rows(ctx, sql) == [(0, prios[1]), (1, prios[0])]
        assert _rows(ctx, sql + " WHERE lseq >= 0") == [(0, prios[1]),
                                                        (1, prios[0])]
        s1 = _counts()
        # its own build, once; the second query of the context reuses it
        assert _delta(s0, s1, "join.build.rows") == 2
        assert _delta(s0, s1, "join.build.reuse") == 1
        seen.append(ctx.query_fingerprint(_plan_of(ctx, sql)))
    assert seen[0] != seen[1]
