"""The semi join's mark by tile (`exec/rowgather.set_rows_window`, the
`jit_semi_mark` program's write): for every input the table it leaves is
the one the element scatter leaves and the one a numpy set of the live
keys gives, bit for bit, whichever way the launch went; clustered keys go
by tile, shuffled and sparse ones fall back to the scatter; a launch
marks a group of batches, padded to its rung with entries that mark and
count nothing; the count of the batches by tile travels with the table
and reaches `join.semi.mark.tiled` once a query."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from datafusion_tpu.exec.context import ExecutionContext
from datafusion_tpu.exec.materialize import collect
from datafusion_tpu.exec.rowgather import (
    LANES,
    MARK_BLOCK,
    MARK_TILE_ROWS,
    _scatter_rows,
    set_rows_window,
)
from test_join import _counts, _delta, _mem_table

WINDOW = 64
ROWS = 5 * WINDOW + 13


def _lines(rng, n, first=0):
    """`n` slots of lineitem's order keys from slot `first`, in key
    order: 8 of every 32 keys an order, 1..7 lines each (TPC-H)."""
    i = np.arange(n, dtype=np.int64)
    okey = (i // 8) * 32 + i % 8
    return first + np.repeat(okey, rng.integers(1, 8, n))[:n]


def _earlier(rng, rows):
    """A table that earlier batches marked here and there."""
    return rng.random((rows, LANES)) < 0.05


def _case(name, rng):
    """(idx, live, table rows, whether the launch goes by tile)."""
    n = 4 * MARK_BLOCK * 8
    live = rng.random(n) < 0.63
    if name == "clustered":
        return _lines(rng, n, 1_000), live, ROWS, True
    if name in ("block_spans_its_tile", "block_spans_past_its_tile"):
        # every block's live keys run from lane 0 of one row to lane 127
        # of the row `MARK_TILE_ROWS - 1` below, straddling each row; or
        # one row further, which no tile holds
        past = name == "block_spans_past_its_tile"
        span = (MARK_TILE_ROWS + past) * LANES
        blocks = n // MARK_BLOCK
        idx = (np.arange(blocks)[:, None] * LANES + 3 * LANES
               + np.linspace(0, span - 1, MARK_BLOCK).astype(np.int64))
        live = np.ones(n, bool)
        return idx.reshape(-1), live, ROWS, not past
    if name == "window_edge":
        # the live keys end in the window's last row; the last blocks'
        # tiles reach past it
        first = 15 * LANES
        top = first + WINDOW * LANES - 1
        idx = np.sort(rng.integers(first, top + 1, n))
        idx[[0, -1]] = first, top
        return idx, np.ones(n, bool), ROWS, True
    if name == "table_end":
        # the window is clamped to end where the table does
        idx = _lines(rng, n, (ROWS - WINDOW // 2) * LANES)
        return np.minimum(idx, ROWS * LANES - 1), live, ROWS, True
    if name == "duplicates":
        idx = np.full(n, 5_000, np.int64)
        idx[n // 3:] = 5_001
        return idx, live, ROWS, True
    if name == "nothing_live":
        return _lines(rng, n, 1_000), np.zeros(n, bool), ROWS, True
    if name == "ragged_length":
        m = n - 77
        return _lines(rng, m, 900), live[:m], ROWS, True
    if name == "table_within_window":
        return _lines(rng, n, 10), live, WINDOW, True
    if name == "shuffled":
        return rng.permutation(_lines(rng, n, 1_000)), live, ROWS, False
    if name == "shuffled_within_window":
        return rng.permutation(_lines(rng, n, 10)), live, WINDOW, False
    if name == "sparse":
        # 40 slots a key: a block spans 40 rows and the batch the table
        return np.arange(n, dtype=np.int64) * 40 % (ROWS * LANES), live, \
            ROWS, False
    raise AssertionError(name)


CASES = ["clustered", "block_spans_its_tile", "block_spans_past_its_tile",
         "window_edge", "table_end",
         "duplicates", "nothing_live", "ragged_length", "table_within_window",
         "shuffled", "shuffled_within_window", "sparse"]


@pytest.mark.parametrize("name", CASES)
def test_the_table_is_the_scatters_and_the_live_keys(name):
    rng = np.random.default_rng(CASES.index(name))
    idx, live, rows, by_tile = _case(name, rng)
    assert idx.min() >= 0 and idx.max() < rows * LANES
    before = _earlier(rng, rows)
    want = before.reshape(-1).copy()
    want[idx[live]] = True

    mark = jax.jit(set_rows_window, static_argnums=3)
    got, tiled = mark(jnp.asarray(before), jnp.asarray(idx, jnp.int32),
                      jnp.asarray(live), WINDOW)
    scattered = jax.jit(_scatter_rows, static_argnums=3)(
        jnp.asarray(before), jnp.asarray(idx, jnp.int32), jnp.asarray(live),
        0)
    assert bool(tiled) is by_tile
    assert got.dtype == jnp.bool_ and got.shape == (rows, LANES)
    np.testing.assert_array_equal(np.asarray(got).reshape(-1), want)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(scattered))


def _mark_launch(core, batches, kmin, slots, rows, table=None, tiled=None):
    """One `jit_semi_mark` launch over a group of `batches`, each (key,
    key validity, row count, mask, predicate columns), padded to its
    rung as the join pads it."""
    from datafusion_tpu.exec.fused import pad_group
    from datafusion_tpu.join.relation import _semi_mark_fn

    if table is None:
        table = jnp.zeros((rows, LANES), jnp.bool_)
        tiled = jnp.zeros((), jnp.int32)
    entries = []
    for key, kvalid, _, mask, cols in batches:
        data = (jnp.asarray(key), *(jnp.asarray(c) for c in cols))
        validity = (None if kvalid is None else jnp.asarray(kvalid),
                    *(None for _ in cols))
        entries.append((data, validity,
                        None if mask is None else jnp.asarray(mask)))
    group, counts = pad_group(entries, [b[2] for b in batches])
    pred_at = () if core is None else tuple(range(1, 1 + len(batches[0][4])))
    return _semi_mark_fn(core, 0, pred_at)(
        table, tiled, jnp.int64(kmin), jnp.int64(slots), group, counts, (),
        ())


def _late_core():
    """The pipeline core of `c1 < c2` over (key, c1, c2)."""
    from datafusion_tpu.datatypes import DataType, Field, Schema
    from datafusion_tpu.exec.relation import _PipelineCore
    from datafusion_tpu.plan.expr import BinaryExpr, Column, Operator

    schema = Schema([Field(n, DataType.INT64, False)
                     for n in ("k", "c1", "c2")])
    return _PipelineCore.build(schema, BinaryExpr(
        Column(1), Operator.Lt, Column(2)), None, {}, {})


LAUNCH_CASES = ["nulls", "rows_past_num_rows", "keys_outside_the_table",
                "predicate_keeps_nothing", "predicate", "mask", "shuffled"]


@pytest.mark.parametrize("name", LAUNCH_CASES)
def test_a_mark_launch_against_the_live_keys(name):
    """Through `jit_semi_mark` itself, three batches into a table past
    the window of 8,192 rows, two in one launch and the third in the
    next: which keys are live (NULL, past the row count, unselected,
    outside `[kmin, kmin + slots)`, dropped by the predicate) is the
    launch's, and the count of batches by tile runs on with the table,
    step after step and launch after launch."""
    from datafusion_tpu.join.relation import _SEMI_WINDOW_ROWS

    rng = np.random.default_rng(100 + LAUNCH_CASES.index(name))
    n, kmin = 4_096, 1_000_000_007
    slots = (_SEMI_WINDOW_ROWS + 500) * LANES - 3
    rows = -(-slots // LANES)
    keys = kmin + _lines(rng, 3 * n, 20_000)
    if name == "shuffled":
        keys = rng.permutation(keys)
    if name == "keys_outside_the_table":
        out = rng.random(3 * n) < 0.1
        keys[out] = rng.choice([kmin - 1, kmin - 2**40, kmin + slots,
                                kmin + slots + 2**32, -(2**62)], out.sum())
    c1, c2 = rng.integers(0, 60, 3 * n), rng.integers(0, 60, 3 * n)
    if name == "predicate_keeps_nothing":
        c2 = np.minimum(c1, c2)
    core = _late_core() if name.startswith("predicate") else None
    want = np.zeros(rows * LANES, bool)
    batches = []
    for b in range(3):
        at = slice(b * n, (b + 1) * n)
        key = keys[at]
        kvalid = rng.random(n) > 0.1 if name == "nulls" else None
        mask = rng.random(n) < 0.5 if name == "mask" else None
        num_rows = n - 1_000 if name == "rows_past_num_rows" and b == 2 else n
        batches.append((key, kvalid, num_rows, mask,
                        (c1[at], c2[at]) if core is not None else ()))
        live = (np.arange(n) < num_rows) & (key >= kmin) & (key < kmin + slots)
        for m in (kvalid, mask, None if core is None else c1[at] < c2[at]):
            if m is not None:
                live &= m
        want[key[live] - kmin] = True
    table, tiled = _mark_launch(core, batches[:2], kmin, slots, rows)
    table, tiled = _mark_launch(core, batches[2:], kmin, slots, rows,
                                table=table, tiled=tiled)
    np.testing.assert_array_equal(np.asarray(table).reshape(-1), want)
    d = np.where(want)[0]
    scattered = _scatter_rows(jnp.zeros((rows, LANES), jnp.bool_),
                              jnp.asarray(d, jnp.int32),
                              jnp.ones(len(d), bool), 0)
    np.testing.assert_array_equal(np.asarray(table), np.asarray(scattered))
    assert int(tiled) == (0 if name == "shuffled" else 3)


def _ctx(lk, ok):
    ctx = ExecutionContext(batch_size=2_048, result_cache=False)
    _mem_table(ctx, "o", {"ok": ok}, batch_rows=2_048)
    _mem_table(ctx, "l", {"lk": lk}, batch_rows=2_048)
    return ctx


@pytest.mark.parametrize("order", ["clustered", "shuffled", "sparse"])
def test_the_query_counts_the_marks_by_tile(order):
    """`join.semi.mark.tiled` beside `join.semi.mark_batches`: every
    batch of a clustered build, none of a shuffled or a sparse one (20
    slots a line: a block spans 20 rows), the answer right either way,
    the ten batches of one shape class one launch, and the count read
    back once, 4 B."""
    rng = np.random.default_rng(7)
    lk = _lines(rng, 20_000, 1)
    if order == "shuffled":
        lk = rng.permutation(lk)
    if order == "sparse":
        lk = 1 + 20 * np.arange(20_000, dtype=np.int64)
    ok = np.arange(1, lk.max() + 50, 3, dtype=np.int64)
    ctx = _ctx(lk, ok)
    s0 = _counts()
    got = collect(ctx.sql(
        "SELECT ok FROM o WHERE EXISTS (SELECT * FROM l WHERE lk = ok)"))
    s1 = _counts()
    assert sorted(r[0] for r in got.to_rows()) == sorted(
        set(ok.tolist()) & set(lk.tolist()))
    batches = _delta(s0, s1, "join.semi.mark_batches")
    assert batches == -(-len(lk) // 2_048)
    assert _delta(s0, s1, "device.launches.join.semi_mark") == 1
    assert _delta(s0, s1, "join.semi.mark.tiled") == (
        batches if order == "clustered" else 0)
    assert _delta(s0, s1, "join.host_probe.rows") == 0


# name -> (lines, whether in key order, `fuse_group_max()`, the mark
# launches: batches of 2,048 lines, one of at most 1,024 a shape class
# of its own)
GROUPED = {
    # five batches, padded to rung 6 with a dead entry
    "padded": (5 * 2_048, True, 256, 1),
    "padded_shuffled": (5 * 2_048, False, 256, 1),
    # six batches of 2,048 and one of 300: two classes, two groups
    "two_shapes": (6 * 2_048 + 300, True, 256, 2),
    # eleven batches, four at most a group: 4 + 4 + 3
    "past_the_group_max": (11 * 2_048, True, 4, 3),
}


@pytest.mark.parametrize("name", list(GROUPED))
def test_a_grouped_mark_leaves_the_live_keys_table(name, monkeypatch):
    """The semi join's own mark loop (`HashJoinRelation._semi_mark`): the
    batches collected by shape class, a group a launch, each padded to
    its rung; the table it leaves is numpy's set of the build's keys, a
    pad marks and counts nothing (a dead entry would read as by tile),
    and the counters say batches and launches."""
    n, clustered, group_max, launches = GROUPED[name]
    monkeypatch.setenv("DATAFUSION_TPU_FUSE_GROUP", str(group_max))
    rng = np.random.default_rng(list(GROUPED).index(name))
    lk = _lines(rng, n, 1)
    if not clustered:
        lk = rng.permutation(lk)
    ctx = _ctx(lk, np.arange(1, lk.max() + 50, 3, dtype=np.int64))
    join = ctx.sql(
        "SELECT ok FROM o WHERE EXISTS (SELECT * FROM l WHERE lk = ok)").child
    s0 = _counts()
    (table, kmin, slots), tiled = join._semi_mark(*join._mark_source())
    s1 = _counts()
    want = np.zeros(np.asarray(table).size, bool)
    want[lk - int(kmin)] = True
    assert int(kmin) == lk.min() and int(slots) == lk.max() - lk.min() + 1
    np.testing.assert_array_equal(np.asarray(table).reshape(-1), want)
    batches = -(-n // 2_048)
    assert _delta(s0, s1, "device.launches.join.semi_mark") == launches
    assert _delta(s0, s1, "join.semi.mark_batches") == batches
    assert _delta(s0, s1, "join.semi.mark_rows") == n
    assert int(tiled) == (batches if clustered else 0)
