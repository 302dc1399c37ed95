"""Partitioned execution over an 8-device CPU-simulated mesh.

The hermetic analog of the reference's planned docker-compose
multi-worker smoketest (`scripts/smoketest.sh:30-66`): conftest forces
8 virtual CPU devices, so partial-aggregate + psum/pmin/pmax combine
runs over a real (simulated) mesh without TPUs.
"""

import numpy as np
import pytest

from datafusion_tpu import DataType, Field, Schema
from datafusion_tpu.parallel import (
    PartitionedContext,
    PartitionedDataSource,
    PhysicalPlan,
    PlanFragment,
    make_mesh,
)
from datafusion_tpu.exec.context import ExecutionContext


SCHEMA = Schema(
    [
        Field("region", DataType.UTF8, False),
        Field("qty", DataType.INT64, True),
        Field("price", DataType.FLOAT64, False),
    ]
)

REGIONS = ["north", "south", "east", "west", "centre"]


def _write_partitions(tmp_path, n_parts=5, rows_per_part=200, seed=7):
    rng = np.random.default_rng(seed)
    paths, all_rows = [], []
    for p in range(n_parts):
        path = tmp_path / f"part{p}.csv"
        lines = ["region,qty,price"]
        for _i in range(rows_per_part):
            region = REGIONS[rng.integers(len(REGIONS))]
            qty = "" if rng.random() < 0.05 else str(int(rng.integers(-50, 500)))
            price = f"{rng.random() * 100:.4f}"
            lines.append(f"{region},{qty},{price}")
            all_rows.append((region, None if qty == "" else int(qty), float(price)))
        path.write_text("\n".join(lines) + "\n")
        paths.append(str(path))
    return paths, all_rows


@pytest.fixture(scope="module")
def parts(tmp_path_factory):
    return _write_partitions(tmp_path_factory.mktemp("parts"))


def _partitioned_ctx(paths, n_devices=8):
    ctx = PartitionedContext(mesh=make_mesh(n_devices), batch_size=64)
    ctx.register_partitioned_csv("sales", paths, SCHEMA)
    return ctx


def _single_ctx(paths):
    # reference single-device answer: same files via union scan
    ctx = ExecutionContext(batch_size=64)
    from datafusion_tpu.exec.datasource import CsvDataSource

    ctx.register_datasource(
        "sales", PartitionedDataSource([CsvDataSource(p, SCHEMA, True, 64) for p in paths])
    )
    return ctx


SQL_GROUPED = (
    "SELECT region, SUM(qty), COUNT(qty), MIN(price), MAX(price), AVG(price) "
    "FROM sales GROUP BY region"
)


def _as_dict(table, key_cols=1):
    rows = table.to_rows()
    return {r[:key_cols]: r[key_cols:] for r in rows}


class TestPartitionedAggregate:
    def test_grouped_matches_single_device(self, parts):
        paths, _ = parts
        got = _as_dict(_partitioned_ctx(paths).sql_collect(SQL_GROUPED))
        want = _as_dict(_single_ctx(paths).sql_collect(SQL_GROUPED))
        assert set(got) == set(want)
        for k in want:
            np.testing.assert_allclose(
                np.asarray(got[k], dtype=float), np.asarray(want[k], dtype=float),
                rtol=1e-9,
            )

    def test_global_aggregate(self, parts):
        paths, rows = parts
        table = _partitioned_ctx(paths).sql_collect(
            "SELECT SUM(price), COUNT(price), MIN(qty), MAX(qty) FROM sales"
        )
        (s, c, mn, mx), = table.to_rows()
        prices = [r[2] for r in rows]
        qtys = [r[1] for r in rows if r[1] is not None]
        assert c == len(prices)
        np.testing.assert_allclose(s, sum(prices), rtol=1e-9)
        assert mn == min(qtys) and mx == max(qtys)

    @pytest.mark.parametrize("host_predicate", [False, True])
    def test_where_fused_into_partials(self, parts, host_predicate, monkeypatch):
        paths, _ = parts
        sql = "SELECT region, COUNT(price), SUM(price) FROM sales WHERE qty > 100 GROUP BY region"
        want = _as_dict(_single_ctx(paths).sql_collect(sql))
        if host_predicate:
            # the accelerator lowering: the predicate runs on the host
            # and folds into each shard's mask plane (`_query_mask`)
            import datafusion_tpu.exec.kernels as kernels
            import datafusion_tpu.exec.relation as relation

            monkeypatch.setattr(relation, "_is_accelerator", lambda device: True)
            monkeypatch.setattr(kernels, "_REGISTRY", {})
        rel = _partitioned_ctx(paths).sql(sql)
        assert (rel._host_pred_expr is not None) == host_predicate
        from datafusion_tpu.exec.materialize import collect

        got = _as_dict(collect(rel))
        assert set(got) == set(want)
        for k in want:
            np.testing.assert_allclose(
                np.asarray(got[k], dtype=float), np.asarray(want[k], dtype=float),
                rtol=1e-9,
            )

    def test_string_predicate_shared_dictionaries(self, parts):
        paths, rows = parts
        table = _partitioned_ctx(paths).sql_collect(
            "SELECT COUNT(price) FROM sales WHERE region = 'north'"
        )
        ((n,),) = (table.to_rows(),)
        assert n[0] == sum(1 for r in rows if r[0] == "north")

    def test_fewer_devices_than_partitions(self, parts):
        paths, _ = parts
        got = _as_dict(_partitioned_ctx(paths, n_devices=2).sql_collect(SQL_GROUPED))
        want = _as_dict(_single_ctx(paths).sql_collect(SQL_GROUPED))
        for k in want:
            np.testing.assert_allclose(
                np.asarray(got[k], dtype=float), np.asarray(want[k], dtype=float),
                rtol=1e-9,
            )

    def test_more_devices_than_partitions(self, parts):
        paths, _ = parts
        ctx = PartitionedContext(mesh=make_mesh(8), batch_size=64)
        ctx.register_partitioned_csv("sales", paths[:3], SCHEMA)
        want_ctx = _single_ctx(paths[:3])
        got = _as_dict(ctx.sql_collect(SQL_GROUPED))
        want = _as_dict(want_ctx.sql_collect(SQL_GROUPED))
        for k in want:
            np.testing.assert_allclose(
                np.asarray(got[k], dtype=float), np.asarray(want[k], dtype=float),
                rtol=1e-9,
            )

    def test_fragments_round_trip_wire_format(self, parts):
        paths, _ = parts
        ctx = _partitioned_ctx(paths)
        ctx.sql_collect(SQL_GROUPED)
        frags = ctx.last_fragments
        assert len(frags) == len(paths)
        for i, f in enumerate(frags):
            assert f.shard == i and f.num_shards == len(paths)
            rt = PlanFragment.from_json_str(f.to_json_str())
            assert rt.plan == f.plan
            # the shipped plan parses back into a real LogicalPlan
            assert rt.logical_plan().schema.names() == f.logical_plan().schema.names()


class TestPartitionedPipeline:
    """Non-aggregate plans (filter / project) run the stacked shard_map
    kernel across the mesh instead of the round-2 serial union scan."""

    def test_filter_project_matches_single_device(self, parts):
        from datafusion_tpu.utils.metrics import METRICS

        paths, rows = parts
        sql = (
            "SELECT region, price * 2.0, qty FROM sales "
            "WHERE price > 30.0 AND qty > 100"
        )
        METRICS.reset()
        table = _partitioned_ctx(paths).sql_collect(sql)
        snap = METRICS.snapshot()
        assert snap["timings_s"].get("execute.partitioned_pipeline", 0) > 0, (
            "partitioned filter/project did not take the mesh path"
        )
        single = _single_ctx(paths).sql_collect(sql)
        assert sorted(table.to_rows()) == sorted(single.to_rows())
        want = [
            (r[0], r[2] * 2.0, r[1])
            for r in rows
            if r[2] > 30.0 and r[1] is not None and r[1] > 100
        ]
        assert len(table.to_rows()) == len(want)

    def test_filter_only_parity(self, parts):
        paths, rows = parts
        sql = "SELECT region, qty, price FROM sales WHERE qty > 250"
        table = _partitioned_ctx(paths).sql_collect(sql)
        want = [r for r in rows if r[1] is not None and r[1] > 250]
        assert sorted(table.to_rows()) == sorted(want)

    def test_string_predicate_over_mesh(self, parts):
        paths, rows = parts
        sql = "SELECT region, price FROM sales WHERE region = 'north'"
        table = _partitioned_ctx(paths).sql_collect(sql)
        want = [(r[0], r[2]) for r in rows if r[0] == "north"]
        assert sorted(table.to_rows()) == sorted(want)

    def test_four_partitions_on_eight_devices(self, tmp_path):
        paths, rows = _write_partitions(tmp_path, n_parts=4, rows_per_part=333)
        sql = "SELECT price, qty FROM sales WHERE price < 20.0"
        table = _partitioned_ctx(paths).sql_collect(sql)
        want = [(r[2], r[1]) for r in rows if r[2] < 20.0]
        assert sorted(table.to_rows(), key=repr) == sorted(want, key=repr)

    def test_host_fn_projection_falls_back_to_serial(self, parts):
        from datafusion_tpu.utils.metrics import METRICS

        paths, rows = parts
        ctx = _partitioned_ctx(paths)
        ctx.register_udf(
            "tagit", [DataType.FLOAT64], DataType.UTF8,
            host_fn=lambda x: np.asarray([f"p{v:.0f}" for v in x], dtype=object),
        )
        METRICS.reset()
        table = ctx.sql_collect("SELECT region, tagit(price) FROM sales WHERE qty > 400")
        snap = METRICS.snapshot()
        assert snap["timings_s"].get("execute.partitioned_pipeline", 0) == 0
        want = [
            (r[0], f"p{r[2]:.0f}") for r in rows
            if r[1] is not None and r[1] > 400
        ]
        assert sorted(table.to_rows()) == sorted(want)


class TestPartitionedFallback:
    def test_non_aggregate_matches_union_semantics(self, parts):
        paths, rows = parts
        table = _partitioned_ctx(paths).sql_collect(
            "SELECT region, price FROM sales WHERE price > 50.0"
        )
        want = [(r[0], r[2]) for r in rows if r[2] > 50.0]
        got = table.to_rows()
        assert len(got) == len(want)
        assert sorted(got) == sorted(want)

    def test_sort_limit_over_partitions(self, parts):
        paths, rows = parts
        table = _partitioned_ctx(paths).sql_collect(
            "SELECT price FROM sales ORDER BY price DESC LIMIT 5"
        )
        want = sorted((r[2] for r in rows), reverse=True)[:5]
        np.testing.assert_allclose([r[0] for r in table.to_rows()], want, rtol=1e-12)


class TestMemoryPartitions:
    def test_memory_partitions_remap_string_codes(self):
        """Partitions whose dictionaries assigned codes in different
        orders must still group correctly (codes remap into a shared
        dictionary at registration)."""
        from datafusion_tpu.exec.batch import StringDictionary, make_host_batch
        from datafusion_tpu.exec.datasource import MemoryDataSource

        schema = Schema(
            [Field("region", DataType.UTF8, False), Field("qty", DataType.INT64, False)]
        )

        def mem_part(regions, qtys):
            d = StringDictionary()
            codes = d.encode(regions)
            batch = make_host_batch(
                schema,
                [codes, np.asarray(qtys, np.int64)],
                [None, None],
                [d, None],
            )
            return MemoryDataSource(schema, [batch])

        # p0 assigns north=0, south=1; p1 assigns south=0, north=1
        p0 = mem_part(["north", "north", "south"], [1, 2, 300])
        p1 = mem_part(["south", "north"], [4, 1000])
        ctx = PartitionedContext(mesh=make_mesh(2))
        ctx.register_datasource("t", PartitionedDataSource([p0, p1]))
        got = _as_dict(ctx.sql_collect("SELECT region, SUM(qty) FROM t GROUP BY region"))
        assert got == {("north",): (1003,), ("south",): (304,)}


class TestFusedMeshRounds:
    def test_rounds_fold_into_one_launch(self):
        """Multi-round mesh aggregates fold like the single-device
        batch-group fold: consecutive rounds of one shape class
        dispatch as ONE multi-round launch, whichever relation runs
        them, with the answers numpy gives; the column copies stay on
        the table's batches, so a second `ctx.sql` places none again."""
        from datafusion_tpu.exec.batch import make_host_batch
        from datafusion_tpu.exec.datasource import MemoryDataSource
        from datafusion_tpu.exec.materialize import collect
        from datafusion_tpu.utils.metrics import METRICS

        schema = Schema([
            Field("k", DataType.INT64, False),
            Field("v", DataType.FLOAT64, False),
        ])
        rng = np.random.default_rng(11)
        parts = []
        ks, vs = [], []
        for _p in range(4):
            batches = []
            for _ in range(3):  # 3 rounds per scan
                ks.append(rng.integers(0, 6, 512).astype(np.int64))
                vs.append(rng.uniform(0, 10, 512))
                batches.append(make_host_batch(schema, [ks[-1], vs[-1]]))
            parts.append(MemoryDataSource(schema, batches))
        k, v = np.concatenate(ks), np.concatenate(vs)
        ctx = PartitionedContext(mesh=make_mesh(4), result_cache=False)
        ctx.register_datasource("t", PartitionedDataSource(parts))
        sql = "SELECT k, SUM(v), COUNT(1) FROM t GROUP BY k"

        def run():
            before = dict(METRICS.counts)
            rows = sorted(collect(ctx.sql(sql)).to_rows())
            return rows, {
                k: v - before.get(k, 0) for k, v in METRICS.counts.items()
            }

        want, cold = run()
        assert [(r[0], r[2]) for r in want] == [
            (g, int((k == g).sum())) for g in range(6)]
        np.testing.assert_allclose(
            [r[1] for r in want], [v[k == g].sum() for g in range(6)],
            rtol=1e-12)
        assert cold.get("h2d.resident_misses", 0) == 12
        got, delta = run()  # a new relation: the batches hold the copies
        assert got == want
        for d in (cold, delta):
            assert d.get("mesh.rounds", 0) == 3
            assert d.get("mesh.fused_rounds", 0) == 3
            assert d.get("mesh.fused_round_launches", 0) == 1
            assert d.get("device.launches.mesh.multi", 0) == 1
            assert d.get("device.launches.mesh.stacked", 0) == 0
            assert d.get("device.launches.mesh.combine", 0) == 1
        assert delta.get("h2d.resident_misses", 0) == 0
        assert delta.get("h2d.resident_hits", 0) == 12


class TestPhysicalPlanParity:
    def test_physical_plan_json_round_trip(self):
        """Mirrors the reference's PhysicalPlan variants
        (physicalplan.rs:18-34) in the JSON wire format."""
        from datafusion_tpu.plan.logical import EmptyRelation

        plan = EmptyRelation(Schema([]))
        for pp in (
            PhysicalPlan("interactive", plan),
            PhysicalPlan("write", plan, filename="/tmp/out.csv", file_format="csv"),
            PhysicalPlan("show", plan, count=10),
        ):
            rt = PhysicalPlan.from_json(pp.to_json())
            assert rt.kind == pp.kind
            assert rt.filename == pp.filename
            assert rt.count == pp.count


class TestCacheConsistency:
    def test_pack_overflow_keeps_groups_distinct(self):
        """Mixed-radix pack must bail (not wrap) when an int64 key spans
        more than 63 bits."""
        from datafusion_tpu.exec.aggregate import GroupKeyEncoder

        enc = GroupKeyEncoder(2)
        k0 = np.asarray([-(2**62), 2**62, -(2**62), 2**62], dtype=np.int64)
        k1 = np.asarray([0, 0, 1, 1], dtype=np.int64)
        ids = enc.encode([k0, k1], [None, None])
        assert len(set(ids.tolist())) == 4

    def test_merge_codes_invalidates_device_cache(self):
        """A query before partitioned registration must not leave stale
        device copies of pre-merge dict codes."""
        from datafusion_tpu.exec.batch import StringDictionary, make_host_batch
        from datafusion_tpu.exec.datasource import MemoryDataSource

        schema = Schema(
            [Field("s", DataType.UTF8, False), Field("v", DataType.INT64, False)]
        )

        def mem(strings, vals):
            d = StringDictionary()
            codes = d.encode(strings)
            return MemoryDataSource(
                schema,
                [make_host_batch(schema, [codes, np.asarray(vals, np.int64)],
                                 [None, None], [d, None])],
            )

        p0 = mem(["a", "b"], [1, 1])
        p1 = mem(["b", "a"], [1, 1])  # opposite code order
        ctx = ExecutionContext()
        ctx.register_datasource("t0", p1)
        # populate p1's device cache with pre-merge codes
        before = ctx.sql_collect("SELECT SUM(v) FROM t0 WHERE s = 'b'")
        assert before.to_rows() == [(1,)]
        pctx = PartitionedContext(mesh=make_mesh(2))
        pctx.register_datasource("t", PartitionedDataSource([p0, p1]))
        after = pctx.sql_collect("SELECT SUM(v) FROM t WHERE s = 'b'")
        assert after.to_rows() == [(2,)]


class TestMeshStringMinMax:
    def test_utf8_minmax_over_mesh(self):
        """MIN/MAX(Utf8) rides the collective combine in rank space
        (partitions share dictionaries, so codes are globally valid)."""
        import numpy as np

        from datafusion_tpu.datatypes import DataType, Field, Schema
        from datafusion_tpu.exec.batch import StringDictionary, make_host_batch
        from datafusion_tpu.exec.context import ExecutionContext
        from datafusion_tpu.exec.datasource import MemoryDataSource
        from datafusion_tpu.parallel.partition import (
            PartitionedContext,
            PartitionedDataSource,
        )

        schema = Schema(
            [
                Field("k", DataType.INT64, False),
                Field("name", DataType.UTF8, True),
            ]
        )
        rng = np.random.default_rng(23)
        parts = []
        for _p in range(4):
            d = StringDictionary()
            names = [f"name_{int(i):03d}" for i in rng.integers(0, 200, 300)]
            codes = d.encode(names)
            valid = rng.random(300) > 0.1
            cols = [rng.integers(0, 5, 300).astype(np.int64), codes]
            parts.append(
                MemoryDataSource(
                    schema, [make_host_batch(schema, cols, [None, valid], [None, d])]
                )
            )
        pds = PartitionedDataSource(parts)

        sql = "SELECT k, MIN(name), MAX(name), COUNT(name) FROM t GROUP BY k"
        mctx = PartitionedContext(n_devices=4)
        mctx.register_datasource("t", pds)
        got = sorted(mctx.sql_collect(sql).to_rows())

        lctx = ExecutionContext(device="cpu")
        lctx.register_datasource("t", pds)
        want = sorted(lctx.sql_collect(sql).to_rows())
        assert got == want
        # prove the mesh path actually ran (not the serial fallback)
        from datafusion_tpu.parallel.partition import _match_partitioned_aggregate

        plan = mctx._plan(
            __import__("datafusion_tpu.sql.parser", fromlist=["parse_sql"]).parse_sql(sql)
        )
        agg, _, _ = _match_partitioned_aggregate(plan, mctx.datasources)
        assert agg is not None
