"""A table that stays, sharded over a mesh
(`PartitionedContext.register_resident_parquet`): the file's row groups
dealt to the mesh's devices, each shard's column copies kept on its
batches on that device, a later query shipping nothing (its predicate
is in the core, over the copies); the round loop made of the
single-device scan loop's parts.  On the suite's eight
virtual CPU devices, against the data set's own numpy oracle."""

import numpy as np
import pytest

from datafusion_tpu.errors import ExecutionError
from datafusion_tpu.exec.context import ExecutionContext
from datafusion_tpu.exec.materialize import collect
from datafusion_tpu.exec.datasource import MemoryDataSource
from datafusion_tpu.parallel import PartitionedContext, make_mesh
from datafusion_tpu.utils.metrics import METRICS
from tpubench import data as tdata
from tpubench.spec import Spec

ROWS, GROUP_ROWS, BATCH = 6_000, 1_000, 512
SPEC = Spec()
LINEITEM = SPEC.dataset("tpch_lineitem")


def q1(delta: int = 90) -> str:
    return SPEC.query("tpch_lineitem", "q1").format(
        **LINEITEM.bind("q1", {"delta": delta}))


@pytest.fixture(scope="module")
def table(tmp_path_factory):
    """(Parquet file of six row groups, a one-row-group file, oracle)."""
    made = LINEITEM.generate(5, ROWS, threads=1)
    d = tmp_path_factory.mktemp("lineitem")
    path, one = str(d / "lineitem.parquet"), str(d / "one_group.parquet")
    tdata.write_parquet(made["tables"]["lineitem"], path, GROUP_ROWS)
    tdata.write_parquet(made["tables"]["lineitem"], one, ROWS)
    return path, one, made["oracle"]


def mesh_ctx(path: str, n: int) -> PartitionedContext:
    ctx = PartitionedContext(mesh=make_mesh(n), batch_size=BATCH,
                             result_cache=False)
    ctx.register_resident_parquet("lineitem", path)
    return ctx


def delta_of(before: dict, snap: dict) -> dict:
    return {k: v - before.get(k, 0) for k, v in snap.items()
            if v - before.get(k, 0)}


@pytest.fixture
def as_on_the_chip(monkeypatch):
    """The accelerator's lowering on the CPU mesh: the predicate where
    the sources say (the host's over streamed partitions, the core's
    over shards that stay), the compressed wire, the staging threads."""
    import datafusion_tpu.exec.kernels as kernels
    import datafusion_tpu.exec.relation as relation

    monkeypatch.setattr(relation, "_is_accelerator", lambda device: True)
    monkeypatch.setattr(kernels, "_REGISTRY", type(kernels._REGISTRY)())
    monkeypatch.setenv("DATAFUSION_TPU_WIRE", "always")
    monkeypatch.setenv("DATAFUSION_TPU_PREFETCH", "1")


# six row groups: two of eight shards stay empty; the one-group file
# leaves all shards but the first empty
@pytest.mark.parametrize("n_shards,one_group", [
    (2, False), (4, False), (8, False), (4, True)])
def test_the_sharded_table_answers_as_the_oracle_and_one_device_do(
        table, n_shards, one_group):
    path, one, oracle = table
    ctx = mesh_ctx(one if one_group else path, n_shards)
    shards = ctx.datasources["lineitem"].partitions
    assert len(shards) == n_shards
    assert all(type(p) is MemoryDataSource for p in shards)
    rows = [sum(b.num_rows for b in p.batches()) for p in shards]
    groups = 1 if one_group else ROWS // GROUP_ROWS
    assert rows == [
        len(range(s, groups, n_shards)) * (ROWS // groups)
        for s in range(n_shards)]
    assert len({b.capacity for p in shards for b in p.batches()}) == 1
    for delta in (90, 60):
        got = collect(ctx.sql(q1(delta)))
        assert oracle.check("q1", {"delta": delta}, got) is None
        single = ExecutionContext(batch_size=BATCH, result_cache=False)
        single.register_parquet("lineitem", path)
        want = sorted(single.sql_collect(q1(delta)).to_rows())
        got = sorted(got.to_rows())
        assert [r[:2] for r in got] == [r[:2] for r in want]
        np.testing.assert_allclose([r[2:] for r in got],
                                   [r[2:] for r in want], rtol=1e-12)


def streamed_ctx(path: str, n: int) -> PartitionedContext:
    """The same rows as `mesh_ctx`'s, shard s reading its row groups of
    the file anew every query."""
    from datafusion_tpu.exec.datasource import ParquetDataSource
    from datafusion_tpu.parallel.partition import PartitionedDataSource

    ctx = PartitionedContext(mesh=make_mesh(n), batch_size=BATCH,
                             result_cache=False)
    ctx.register_datasource("lineitem", PartitionedDataSource([
        ParquetDataSource(path, None, BATCH,
                          row_groups=list(range(s, ROWS // GROUP_ROWS, n)))
        for s in range(n)]))
    return ctx


def test_a_second_query_ships_nothing(table, as_on_the_chip):
    """Under `ctx.sql` + `collect`, a new relation a query: the first
    places every batch's columns on its shard's device, the second (the
    same literal) and the third (another) place none again, evaluate
    nothing on the host and put a round's row counts and the
    predicate's one table only."""
    path, _, oracle = table
    ctx = mesh_ctx(path, 4)
    batches = sum(1 for p in ctx.datasources["lineitem"].partitions
                  for _ in p.batches())
    rounds = -(-2 * GROUP_ROWS // BATCH)  # the fullest shard: 2,000 rows
    seen = []
    for delta in (90, 90, 60):
        rel = ctx.sql(q1(delta))
        assert rel._host_pred_expr is None and rel._core_pred is not None
        before = dict(METRICS.counts)
        host_s = METRICS.timings.get("host.predicate", 0.0)
        got = collect(rel)
        assert oracle.check("q1", {"delta": delta}, got) is None
        assert METRICS.timings.get("host.predicate", 0.0) == host_s
        seen.append(delta_of(before, METRICS.counts))
    first, *later = seen
    assert first["h2d.resident_misses"] == batches
    assert first["h2d.bytes"] > ROWS * 8  # at least one float column
    for d in seen:
        # one `cmp_table` a round for all four shards
        assert d["expr.cmp_lookups"] == rounds
    for d in later:
        assert "h2d.resident_misses" not in d
        assert d["h2d.resident_hits"] == batches
        # the row counts (four int32 a distinct round shape and the
        # dead rounds' zeros) and the predicate's one table, put on the
        # mesh once a query: `h2d.bytes` counts neither
        assert 0 < d["device.h2d.transfers"] <= rounds + 2
        assert "h2d.bytes" not in d
    # one view a used-column set on the batch: the other literal read
    # the same copies
    for p in ctx.datasources["lineitem"].partitions:
        for b in p.batches():
            assert sum(1 for k in b.cache if k[0] == "agg_subset") == 1
    # the copies sit on their shard's device (partition s on mesh
    # device s), on the table's batches
    for dev, p in zip(ctx.mesh.devices.flat,
                      ctx.datasources["lineitem"].partitions):
        for b in p.batches():
            placed = list(_device_copies(b))
            assert placed and all(a.devices() == {dev} for a in placed)


def _device_copies(batch):
    """The column copies `device_inputs` keeps on a batch and on the
    views cached on it."""
    for key, kept in batch.cache.items():
        if hasattr(kept, "cache"):  # a projection's or a core's view
            yield from _device_copies(kept)
        elif key[0] == "device":
            yield from kept[0]


def test_shards_read_side_by_side_end_with_one_dictionary_set(table):
    """Each shard's reader codes its strings by itself (the shards are
    read at once); the registered table holds ONE dictionary a string
    column, and every batch's codes read back, through it, as the
    strings of its row groups in the file's order."""
    import pyarrow.parquet as pq

    path, _, _ = table
    ctx = mesh_ctx(path, 4)
    shards = ctx.datasources["lineitem"].partitions
    schema = shards[0].schema
    strings = [i for i, d in enumerate(next(shards[0].batches()).dicts)
               if d is not None]
    assert len(strings) == 3  # the two flags and the ship date
    pf = pq.ParquetFile(path)
    for i in strings:
        assert len({id(b.dicts[i]) for p in shards for b in p.batches()}) == 1
        name = schema.field(i).name
        for s, p in enumerate(shards):
            got = np.concatenate([
                b.dicts[i].decode(np.asarray(b.data[i])[:b.num_rows])
                for b in p.batches()])
            want = np.concatenate([
                pf.read_row_group(g, columns=[name]).column(0).to_numpy(
                    zero_copy_only=False)
                for g in range(s, ROWS // GROUP_ROWS, 4)])
            assert list(got) == list(want)


@pytest.mark.parametrize("pieces,size", [
    ([5, 3, 8], 4), ([3, 3], 8), ([4, 4], 4), ([1, 9, 2], 5), ([], 4)])
def test_a_scan_is_recut_into_whole_batches(pieces, size):
    """`whole_batches`, the reader's own re-cut, as the mesh's
    registration calls it: every batch but the last holds `size` rows, the
    rows keep their order, and a validity array appears where a piece
    brought one (rows of the pieces that brought none are valid)."""
    from datafusion_tpu.datatypes import DataType, Field, Schema
    from datafusion_tpu.exec.batch import make_host_batch
    from datafusion_tpu.io.readers import whole_batches

    schema = Schema([Field("x", DataType.INT64, True),
                     Field("y", DataType.FLOAT64, False)])
    total = sum(pieces)
    x = np.arange(total, dtype=np.int64)
    valid = x % 3 != 0
    scan, lo = [], 0
    for k, n in enumerate(pieces):
        sl = slice(lo, lo + n)
        # every other piece carries no validity array at all
        scan.append(make_host_batch(
            schema, [x[sl], x[sl] * 0.5],
            [valid[sl] if k % 2 == 0 else None, None]))
        lo += n
    want_valid = np.concatenate([
        valid[sum(pieces[:k]):sum(pieces[:k + 1])] if k % 2 == 0
        else np.ones(n, bool) for k, n in enumerate(pieces)] or [valid])
    out = list(whole_batches(iter(scan), size))
    assert [b.num_rows for b in out] == (
        [size] * (total // size) + ([total % size] if total % size else []))
    if not out:
        return
    got_x = np.concatenate([np.asarray(b.data[0])[:b.num_rows] for b in out])
    got_y = np.concatenate([np.asarray(b.data[1])[:b.num_rows] for b in out])
    got_v = np.concatenate([
        np.ones(b.num_rows, bool) if b.validity[0] is None
        else np.asarray(b.validity[0])[:b.num_rows] for b in out])
    assert got_x.tolist() == x.tolist()
    assert got_y.tolist() == (x * 0.5).tolist()
    assert got_v.tolist() == want_valid.tolist()
    assert all(b.validity[1] is None for b in out)


def test_registration_recuts_the_files_own_cut_once(table, monkeypatch):
    """The mesh's readers hand on the file's own cut (eight table
    batches at a time, a row group if it is no longer) and the shard's
    thread cuts that down to table batches, each row copied once: a
    reader that joined first would copy every row twice.  A shard holds
    its row groups' rows in the file's order, in batches of `BATCH`
    rows but the last, every float as the file has it."""
    import pyarrow.parquet as pq

    from datafusion_tpu.io.readers import ParquetReader

    path, _, _ = table
    asked = []
    real = ParquetReader.batches

    def batches(self, whole=True):
        asked.append((self.batch_size, whole))
        return real(self, whole)

    monkeypatch.setattr(ParquetReader, "batches", batches)
    before = METRICS.counts.get("scan.recut.pieces", 0)
    ctx = mesh_ctx(path, 4)
    assert asked == [(8 * BATCH, False)] * 4
    shards = ctx.datasources["lineitem"].partitions
    pf = pq.ParquetFile(path)
    col = shards[0].schema.names().index("l_extendedprice")
    joined = 0
    for s, p in enumerate(shards):
        groups = list(range(s, ROWS // GROUP_ROWS, 4))
        rows = len(groups) * GROUP_ROWS
        assert [b.num_rows for b in p.batches()] == (
            [BATCH] * (rows // BATCH) + ([rows % BATCH] if rows % BATCH else []))
        # a table batch that straddles two of the shard's row groups is
        # made of two pieces
        joined += sum(
            2 for lo in range(0, rows, BATCH)
            if lo // GROUP_ROWS != (min(rows, lo + BATCH) - 1) // GROUP_ROWS)
        if not groups:
            continue
        got = np.concatenate([
            np.asarray(b.data[col])[:b.num_rows] for b in p.batches()])
        want = np.concatenate([
            pf.read_row_group(g, columns=["l_extendedprice"]).column(0)
            .to_numpy() for g in groups])
        assert got.tolist() == want.tolist()
    assert METRICS.counts.get("scan.recut.pieces", 0) - before == joined


def test_group_ids_are_kept_by_device(table):
    """A batch of the table scanned alone on the default device and
    then as shard 1 of the mesh (one encoder, the table's) keeps an id
    array on each: the mesh does not replay the other device's."""
    path, _, oracle = table
    ctx = mesh_ctx(path, 2)
    d0, d1 = ctx.mesh.devices.flat
    shard = ctx.datasources["lineitem"].partitions[1]
    alone = ExecutionContext(batch_size=BATCH, result_cache=False)
    alone.register_datasource("lineitem", shard)
    assert len(alone.sql_collect(q1()).to_rows()) == 4
    assert oracle.check("q1", {"delta": 90}, collect(ctx.sql(q1()))) is None
    for b in shard.batches():
        ids = {slot[2]: kept[1].devices()
               for view in _views(b) for slot, kept in view.cache.items()
               if slot[0] == "group_ids"}
        assert ids == {None: {d0}, repr(d1): {d1}}


def _views(batch):
    yield batch
    for kept in batch.cache.values():
        if hasattr(kept, "cache"):
            yield from _views(kept)


@pytest.mark.parametrize("projection", [None, [6, 2, 0]])
def test_a_source_of_some_row_groups_says_so_on_the_wire(table, projection):
    """`to_meta` -> `PlanFragment` -> `build_datasource`: the rebuilt
    source scans the row groups the first one was held to, and no more."""
    from datafusion_tpu.exec.datasource import ParquetDataSource
    from datafusion_tpu.parallel.physical import PlanFragment

    path, _, _ = table
    src = ParquetDataSource(path, None, BATCH, row_groups=[1, 4])
    if projection is not None:
        src = src.with_projection(projection)
    assert src.to_meta()["ParquetFile"]["row_groups"] == [1, 4]
    frag = PlanFragment.from_json_str(
        PlanFragment(0, 1, {}, src.to_meta()).to_json_str())
    rebuilt = frag.build_datasource(BATCH)
    assert rebuilt.row_groups == [1, 4]
    assert rebuilt.schema.names() == src.schema.names()
    assert sum(b.num_rows for b in rebuilt.batches()) == 2 * GROUP_ROWS
    whole = ParquetDataSource(path, None, BATCH)
    assert "row_groups" not in whole.to_meta()["ParquetFile"]


def test_shards_by_row_group_count_every_row_once_through_fragments(table):
    """Partitions that are row groups of ONE file, shipped as fragments
    (`_ship_fragments` rebuilds each from its meta): the counts are the
    oracle's, not one file's a shard."""
    from datafusion_tpu.exec.datasource import ParquetDataSource
    from datafusion_tpu.parallel.partition import PartitionedDataSource

    path, _, oracle = table
    ctx = PartitionedContext(mesh=make_mesh(2), batch_size=BATCH,
                             result_cache=False)
    ctx.register_datasource("lineitem", PartitionedDataSource([
        ParquetDataSource(path, None, BATCH, row_groups=list(range(s, 6, 2)))
        for s in range(2)]))
    assert oracle.check("q1", {"delta": 90}, collect(ctx.sql(q1()))) is None
    assert [f.datasource_meta["ParquetFile"]["row_groups"]
            for f in ctx.last_fragments] == [[0, 2, 4], [1, 3, 5]]


@pytest.mark.parametrize("kind", ["csv", "ndjson", "parquet"])
def test_every_file_source_describes_itself(tmp_path, kind):
    """`to_meta` of each file source, with a result cache on (the
    query's fingerprint reads it) and through a fragment."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    from datafusion_tpu import DataType, Field, Schema
    from datafusion_tpu.cache import CacheStore
    from datafusion_tpu.parallel.physical import PlanFragment

    schema = Schema([Field("k", DataType.UTF8, False),
                     Field("v", DataType.FLOAT64, False)])
    path = str(tmp_path / f"t.{kind}")
    store = CacheStore(1 << 20, None, name="result")
    ctx = ExecutionContext(batch_size=BATCH, result_cache=store)
    if kind == "csv":
        open(path, "w").write("k,v\na,1.0\nb,2.0\na,4.0\n")
        ctx.register_csv("t", path, schema)
    elif kind == "ndjson":
        open(path, "w").write(
            '{"k": "a", "v": 1.0}\n{"k": "b", "v": 2.0}\n{"k": "a", "v": 4.0}\n')
        ctx.register_ndjson("t", path, schema)
    else:
        pq.write_table(pa.table({"k": ["a", "b", "a"], "v": [1.0, 2.0, 4.0]}),
                       path)
        ctx.register_parquet("t", path)
    sql = "SELECT k, SUM(v) FROM t GROUP BY k"
    for _ in range(2):  # the second answer is the cache's
        assert sorted(ctx.sql_collect(sql).to_rows()) == [("a", 5.0), ("b", 2.0)]
    assert store.stats()["hits"] == 1
    meta = ctx.datasources["t"].to_meta()
    (body,) = meta.values()
    assert body["filename"] == path and "row_groups" not in body
    rebuilt = PlanFragment(0, 1, {}, meta).build_datasource(BATCH)
    assert type(rebuilt) is type(ctx.datasources["t"])
    assert sum(b.num_rows for b in rebuilt.batches()) == 3


@pytest.mark.parametrize("resident", [True, False])
def test_one_mesh_query_observes_every_timer_and_counter(
        table, as_on_the_chip, resident):
    """The timers and counters the mesh path owes its layer, and those
    of the single-device parts it is made of: over shards that stay,
    the wire's and the put's in the query that ships the columns and
    never the host predicate's; over streamed partitions all of them
    in every query."""
    path, _, _ = table
    before = dict(METRICS.counts)
    ctx = mesh_ctx(path, 4) if resident else streamed_ctx(path, 4)
    if resident:
        per_device = METRICS.counts["mesh.resident.bytes"] - before.get(
            "mesh.resident.bytes", 0)
        assert per_device >= ROWS * 44  # seven columns, 44 B a row, + padding
        assert all(b.num_rows == BATCH
                   for p in ctx.datasources["lineitem"].partitions
                   for b in list(p.batches())[:-1])
    snap = METRICS.snapshot()
    t0 = dict(snap["timings_s"])
    collect(ctx.sql(q1()))  # the columns' trip
    snap = METRICS.snapshot()
    shipped = delta_of(t0, snap["timings_s"])
    t0, c0 = dict(snap["timings_s"]), dict(snap["counts"])
    collect(ctx.sql(q1()))
    snap = METRICS.snapshot()
    timed = delta_of(t0, snap["timings_s"])
    counts = delta_of(c0, snap["counts"])
    every_query = ("mesh.stage", "execute.partitioned_aggregate",
                   "execute.collective_combine", "pipeline.wait",
                   "pipeline.stage", "d2h.wait", "h2d.dispatch", "query")
    the_wire = ("h2d.encode", "h2d.decode")
    for name in every_query + the_wire:
        assert shipped.get(name, 0) > 0, name
    for name in every_query + (() if resident else the_wire):
        assert timed.get(name, 0) > 0, name
    for d in (shipped, timed):
        assert ("host.predicate" in d) == (not resident)
    if resident:
        assert not set(the_wire) & set(timed)
    assert "query.other" in snap["timings_s"]
    if not resident:
        # every batch of the file ships again, its mask with it
        assert counts["h2d.resident_misses"] >= 12
        assert counts["h2d.bytes"] > ROWS * 8
        assert "h2d.resident_hits" not in counts
        assert "expr.cmp_lookups" not in counts
        return
    rounds = -(-2 * GROUP_ROWS // BATCH)  # the fullest shard: 2,000 rows
    assert counts["mesh.rounds"] == rounds
    assert counts["expr.cmp_lookups"] == rounds
    assert counts["mesh.fused_round_launches"] == 1
    assert counts["mesh.fused_rounds"] == rounds
    assert counts["mesh.shards"] == 4
    assert counts["mesh.shard_rows.total"] == ROWS
    # six row groups over four shards: two shards hold two
    assert counts["mesh.shard_rows.max"] == 2 * GROUP_ROWS
    assert counts["device.launches.mesh.multi"] == 1
    assert counts["device.launches.mesh.combine"] == 1
    assert counts["device.launches"] == 2


def test_a_round_is_staged_a_thread_a_shard_under_the_producer(
        table, as_on_the_chip, monkeypatch):
    """Where the scan loop has a producer, a round's shards are staged
    side by side on the mesh's staging threads; without one (the CPU's
    lowering) on the thread that runs the query."""
    import threading

    from datafusion_tpu.parallel import partition

    path, _, oracle = table
    ctx = mesh_ctx(path, 4)
    seen = set()
    inputs = partition.device_inputs

    def watched(*args, **kwargs):
        seen.add(threading.current_thread().name)
        return inputs(*args, **kwargs)

    monkeypatch.setattr(partition, "device_inputs", watched)
    assert oracle.check("q1", {"delta": 90}, collect(ctx.sql(q1()))) is None
    assert len(seen) > 1
    assert all(n.startswith("df-tpu-mesh-stage") for n in seen)
    seen.clear()
    monkeypatch.setenv("DATAFUSION_TPU_PREFETCH", "0")
    assert oracle.check("q1", {"delta": 60}, collect(ctx.sql(q1(60)))) is None
    assert seen == {threading.current_thread().name}


def test_a_single_round_takes_the_stacked_launch(table):
    path, one, oracle = table
    ctx = PartitionedContext(mesh=make_mesh(2), batch_size=8192,
                             result_cache=False)
    ctx.register_resident_parquet("lineitem", one)
    before = dict(METRICS.counts)
    assert oracle.check("q1", {"delta": 90}, collect(ctx.sql(q1()))) is None
    counts = delta_of(before, METRICS.counts)
    assert counts["mesh.rounds"] == 1
    assert counts["device.launches.mesh.stacked"] == 1
    assert "device.launches.mesh.multi" not in counts


def test_a_shard_that_does_not_fit_its_device_is_refused(table, monkeypatch):
    path, _, _ = table
    # 200,000 B a device, and a ledger that holds nothing of the tests
    # before this one: shard 0 is 2,000 rows in four batches of capacity
    # 1,024 at four shards (180,224 B), 3,000 in six at two
    from datafusion_tpu.obs.device import LEDGER

    monkeypatch.setattr(LEDGER, "devices", dict)
    monkeypatch.setenv("DATAFUSION_TPU_HBM_BYTES", str(8 * 200_000))
    mesh_ctx(path, 4)
    with pytest.raises(ExecutionError,
                       match=r"shard 0 holds 270336 bytes.* 200000 bytes free"):
        mesh_ctx(path, 2)


def test_the_ledger_reckons_headroom_by_device(monkeypatch):
    import jax

    from datafusion_tpu.obs import device as obs_device

    monkeypatch.setenv("DATAFUSION_TPU_HBM_BYTES", str(8 << 20))
    ledger = obs_device.DeviceLedger()
    d0, d1 = jax.devices()[:2]
    assert obs_device.hbm_capacity_bytes(d0) == 1 << 20
    assert obs_device.hbm_capacity_bytes() == 8 << 20
    kept = ledger.put(np.zeros(100_000, np.float64), d0, owner="t")
    assert ledger.headroom(d0) == (1 << 20) - 800_000
    assert ledger.headroom(d1) == 1 << 20
    assert not ledger.fits(300_000, d0) and ledger.fits(300_000, d1)
    assert ledger.fits(300_000)  # the whole host's
    del kept
