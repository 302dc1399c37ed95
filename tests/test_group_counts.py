"""A batch group's row counts travel as ONE int32 array a launch
(`exec/fused.pad_group`): each group route folds a scan whose group needs
dead entries (5 batches: rung 6) and whose last batch is short, and gives
the numpy answer and the single-batch path's; a dead entry, count 0, adds
nothing.  Under a profile the census of every group launch
(`utils/retry.device_call`) holds what it is handed to a few host values,
whatever the rung."""

import jax
import numpy as np
import pytest

from datafusion_tpu.datatypes import DataType, Field, Schema
from datafusion_tpu.exec import aggregate as agg_mod
from datafusion_tpu.exec.batch import make_host_batch
from datafusion_tpu.exec.context import ExecutionContext
from datafusion_tpu.exec.datasource import MemoryDataSource
from datafusion_tpu.exec.fused import bucket_group, pad_group
from datafusion_tpu.exec.materialize import collect
from datafusion_tpu.utils.metrics import METRICS

BATCH_ROWS = 200  # every batch below 1,024 rows: one capacity, one class
SCHEMA = Schema([
    Field("k", DataType.INT64, False),
    Field("v", DataType.FLOAT64, False),
    Field("w", DataType.INT64, False),
    Field("p", DataType.FLOAT64, False),
])
LIT = 0.6

# route -> (span of the key: the route it takes, how it is driven)
ROUTES = {
    "dense": (7, "sql"),  # <= DENSE_GROUP_MAX groups: the scan fold
    "sort_merge": (500, "sql"),  # > DENSE_GROUP_MAX: one sort a group
    "megabatch": (7, "serve"),  # two queries, one launch a group
    "keyed": (1 << 20, "sql"),  # a key space past WIDE_KEY_SPAN
}
TAGS = {"dense": ["agg.group"], "sort_merge": ["agg.group"],
        "megabatch": ["serve.megabatch"], "keyed": ["agg.key_ids", "agg.group"]}


def _data(n_batches: int, key_span: int, seed: int = 3):
    """Columns of `n_batches` batches, the last one of 100 rows: v a
    multiple of 1/4 (its sums are exact in any order)."""
    n = (n_batches - 1) * BATCH_ROWS + 100
    rng = np.random.default_rng(seed)
    return {"k": rng.integers(0, key_span, n),
            "v": rng.integers(-400, 400, n) / 4,
            "w": rng.integers(-100, 100, n),
            "p": np.round(rng.uniform(0, 1, n), 3)}


def _ctx(cols: dict) -> ExecutionContext:
    n = len(cols["k"])
    batches = [
        make_host_batch(SCHEMA, [cols[f.name][lo:lo + BATCH_ROWS]
                                 for f in SCHEMA.fields])
        for lo in range(0, n, BATCH_ROWS)]
    ctx = ExecutionContext(result_cache=False)
    ctx.register_datasource("t", MemoryDataSource(SCHEMA, batches))
    return ctx


def _q(lit: float = LIT) -> str:
    return ("SELECT k, SUM(w), SUM(v), MIN(v), MAX(v), COUNT(1) FROM t "
            f"WHERE p < {lit} GROUP BY k")


def _oracle(cols: dict, lit: float = LIT) -> list:
    live = cols["p"] < lit
    out = []
    for key in np.unique(cols["k"][live]):
        m = live & (cols["k"] == key)
        out.append((int(key), int(cols["w"][m].sum()),
                    float(cols["v"][m].sum()), float(cols["v"][m].min()),
                    float(cols["v"][m].max()), int(m.sum())))
    return sorted(out)


def _answer(ctx, how: str, lits=(LIT,)) -> list:
    """Each literal's rows, sorted: through `ctx.sql`, or submitted
    together to a server that folds them in one megabatch."""
    if how == "sql":
        return [sorted(tuple(r) for r in collect(ctx.sql(_q(lit))).to_rows())
                for lit in lits]
    srv = ctx.serve(workers=1, window_s=0.3, megabatch_max=16)
    try:
        tickets = [srv.submit(_q(lit)) for lit in lits]
        return [sorted(tuple(r) for r in t.result(timeout=120).to_rows())
                for t in tickets]
    finally:
        srv.stop()


def _launched(before: dict) -> dict:
    return {k: METRICS.counts.get(k, 0) - before.get(k, 0)
            for k in ("serve.megabatch_launches", "fused.groups",
                      "fused.group_batches", "device.launches.agg",
                      "device.launches.agg.group",
                      "device.launches.agg.key_ids")}


def test_pad_group_pads_with_entry_zero_and_a_zero_count():
    entries, counts = pad_group(["a", "b", "c", "d", "e"], [7, 7, 7, 7, 3])
    assert entries == ("a", "b", "c", "d", "e", "a")
    assert counts.dtype == np.int32 and counts.tolist() == [7, 7, 7, 7, 3, 0]
    whole, rows = pad_group(["a", "b"], [np.int32(4), np.int32(1)])
    assert whole == ("a", "b") and rows.tolist() == [4, 1]


@pytest.mark.parametrize("route", list(ROUTES))
def test_a_group_with_dead_entries_and_a_short_batch_is_exact(
        route, monkeypatch):
    span, how = ROUTES[route]
    cols = _data(5, span)
    assert bucket_group(5) == 6  # one dead entry
    lits = (LIT, 0.3) if how == "serve" else (LIT,)
    want = [_oracle(cols, lit) for lit in lits]
    assert all(len(w) > 1 for w in want)
    if route == "sort_merge":
        assert len(want[0]) > agg_mod.DENSE_GROUP_MAX

    ctx = _ctx(cols)
    before = dict(METRICS.counts)
    got = _answer(ctx, how, lits)
    grew = _launched(before)
    assert got == want
    if route == "megabatch":
        assert grew["serve.megabatch_launches"] == 1
    elif route == "keyed":
        assert grew["device.launches.agg.key_ids"] == 1
        assert grew["device.launches.agg.group"] == 1
    else:
        assert grew["fused.groups"] == 1 and grew["fused.group_batches"] == 5
        assert grew["device.launches.agg"] == 0

    # the single-batch path: a launch a batch (the keyed fold's groups
    # of one batch, rung 1: no dead entry)
    monkeypatch.setenv("DATAFUSION_TPU_FUSE_GROUP", "1")
    monkeypatch.setattr(agg_mod._KeyedAccumulator, "_FLUSH_BATCHES", 1)
    before = dict(METRICS.counts)
    single = [_answer(_ctx(cols), "sql", (lit,))[0] for lit in lits]
    assert single == want
    if route != "keyed":
        assert _launched(before)["device.launches.agg"] == 5 * len(lits)


# -- the census of a group launch ------------------------------------------

def _group_census(tmp_path, route: str, n_batches: int) -> list:
    """(tag, host values) of every group launch of two queries over one
    resident table of `n_batches` batches, traced."""
    from datafusion_tpu.utils.profiling import trace
    from spans_helper import host_spans

    span, how = ROUTES[route]
    cols = _data(n_batches, span, seed=n_batches)
    ctx = _ctx(cols)
    lits = (LIT, 0.3) if how == "serve" else (LIT,)
    out = str(tmp_path / f"{route}-{n_batches}")
    with trace(out):
        assert _answer(ctx, how, lits) == [_oracle(cols, x) for x in lits]
    return [(s.stats["tag"], s.stats["host"]) for s in host_spans(out)
            if s.name == "dftpu.device.dispatch"
            and s.stats.get("tag") in TAGS[route]]


@pytest.mark.parametrize("route", list(ROUTES))
def test_a_group_launch_is_handed_a_few_host_values_whatever_its_rung(
        route, tmp_path):
    small = _group_census(tmp_path, route, 5)  # rung 6
    large = _group_census(tmp_path, route, 40)  # rung 48
    assert bucket_group(40) == 48
    assert {tag for tag, _ in small} == set(TAGS[route])
    assert all(host <= 3 for _, host in small + large), (small, large)
    # the rung changes nothing of what the launch is handed
    assert sorted(set(small)) == sorted(set(large))


def test_a_second_query_over_the_resident_table_compiles_nothing():
    compiles: list = []

    def listen(event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(secs)

    cols = _data(5, ROUTES["dense"][0])
    ctx = _ctx(cols)
    assert _answer(ctx, "sql") == [_oracle(cols)]
    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        before = dict(METRICS.counts)
        # another literal: the same programs, its value a traced one
        assert _answer(ctx, "sql", (0.3,)) == [_oracle(cols, 0.3)]
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    assert METRICS.counts.get("kernel_cache.misses", 0) == before.get(
        "kernel_cache.misses", 0)
    assert _launched(before)["device.launches.agg.group"] == 1
    assert compiles == []
