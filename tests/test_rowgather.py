"""Tables read on the device as 128-wide rows (`exec/rowgather.py`) and
the ordered string compare that reads its truth table through them
(`exec/expression.py`: `cmp_table`, `compute_aux_values`).

The references are numpy's element indexing of the flat table and
Python's own string order over the decoded rows.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from datafusion_tpu import DataType, ExecutionContext, Field, Schema
from datafusion_tpu.exec.batch import StringDictionary, make_host_batch
from datafusion_tpu.exec.datasource import MemoryDataSource
from datafusion_tpu.exec.expression import AuxSpec, compute_aux_values
from datafusion_tpu.exec.materialize import collect
from datafusion_tpu.exec.rowgather import (
    LANES,
    WINDOW_ROWS,
    WORD_BITS,
    pack_bits,
    take_bits,
    take_rows,
    take_rows_window,
)
from datafusion_tpu.utils.metrics import METRICS

ROW_CODES = LANES * WORD_BITS  # dictionary codes a row of packed words holds


def _indices(cap: int) -> np.ndarray:
    """0, cap - 1, what - 1 and cap clip to, and a scatter between."""
    rng = np.random.default_rng(cap)
    edge = np.clip(np.array([0, cap - 1, -1, cap]), 0, cap - 1)
    return np.concatenate([edge, rng.integers(0, cap, 3_000)]).astype(np.int32)


@pytest.mark.parametrize("cap", [1_024, 4_096, 1 << 20])
@pytest.mark.parametrize("dtype", ["bool", "int32", "uint32"])
def test_take_rows_is_flat_indexing(dtype, cap):
    rng = np.random.default_rng(7)
    flat = (rng.random(cap) > 0.5 if dtype == "bool"
            else rng.integers(0, 1 << 31, cap).astype(dtype))
    idx = _indices(cap)
    got = jax.jit(take_rows)(jnp.asarray(flat.reshape(-1, LANES)),
                             jnp.asarray(idx))
    assert got.dtype == flat.dtype
    np.testing.assert_array_equal(np.asarray(got), flat[idx])


@pytest.mark.parametrize("cap", [1_024, 4_096, 1 << 20])
def test_take_bits_is_flat_indexing_of_the_unpacked_table(cap):
    rng = np.random.default_rng(11)
    n = cap - cap // 3  # a dictionary fills part of its capacity
    flat = rng.random(n) > 0.4
    words = pack_bits(flat, cap)
    assert words.dtype == np.uint32
    assert words.shape == (max(cap // ROW_CODES, 1), LANES)
    padded = np.zeros(words.size * WORD_BITS, dtype=bool)
    padded[:n] = flat
    idx = _indices(words.size * WORD_BITS)
    got = jax.jit(take_bits)(jnp.asarray(words), jnp.asarray(idx))
    assert got.dtype == jnp.bool_
    np.testing.assert_array_equal(np.asarray(got), padded[idx])

# -- the window a launch picks from the batch's own indices -----------

TABLE_ROWS = 3 * WINDOW_ROWS + 77


def _window_case(case: str, rng):
    """(table rows, indices, live, whether the window is taken)."""
    n, rows = 5_000, TABLE_ROWS
    live = np.ones(n, bool)
    if case == "clustered":
        idx = rng.integers(700 * LANES, (700 + WINDOW_ROWS) * LANES, n)
        idx[:2] = [700 * LANES, (700 + WINDOW_ROWS) * LANES - 1]
    elif case == "shuffled":
        idx = rng.integers(0, rows * LANES, n)
    elif case == "one_row_too_wide":
        idx = rng.integers(700 * LANES, (700 + WINDOW_ROWS) * LANES, n)
        idx[:2] = [700 * LANES, (700 + WINDOW_ROWS) * LANES]
    elif case == "last_rows":
        # the smallest live row lies less than a window before the
        # table's end: the start is clamped, the window still holds all
        idx = rng.integers((rows - 100) * LANES, rows * LANES, n)
        idx[0] = rows * LANES - 1
    elif case == "small_table":
        rows = WINDOW_ROWS
        idx = rng.integers(0, rows * LANES, n)
    elif case == "no_live_index":
        idx = rng.integers(0, rows * LANES, n)
        live[:] = False
    elif case == "dead_at_both_ends":
        idx = rng.integers(900 * LANES, 1_000 * LANES, n)
        live = rng.random(n) > 0.3
        idx[~live] = np.where(rng.random((~live).sum()) > 0.5, 0,
                              rows * LANES - 1)
        live[:2], idx[:2] = False, [0, rows * LANES - 1]
    took = case not in ("shuffled", "one_row_too_wide", "small_table")
    return rows, idx.astype(np.int32), live, took


_WINDOW_CASES = ["clustered", "shuffled", "one_row_too_wide", "last_rows",
                 "small_table", "no_live_index", "dead_at_both_ends"]


@pytest.mark.parametrize("case", _WINDOW_CASES)
@pytest.mark.parametrize("dtype", ["int32", "uint32", "bool"])
def test_take_rows_window_is_flat_indexing_of_the_live_indices(dtype, case):
    rng = np.random.default_rng(len(case))
    rows, idx, live, took = _window_case(case, rng)
    flat = (rng.random(rows * LANES) > 0.5 if dtype == "bool"
            else rng.integers(0, 1 << 31, rows * LANES).astype(dtype))
    # a pytree of tables shares the one decision
    tables = (jnp.asarray(flat.reshape(-1, LANES)),
              {"twin": jnp.asarray(flat[::-1].reshape(-1, LANES))})
    fn = jax.jit(take_rows_window)
    (got, rest), flag = fn(tables, jnp.asarray(idx), jnp.asarray(live))
    assert got.dtype == flat.dtype and flag.dtype == jnp.bool_
    assert flag.shape == () and bool(flag) == took
    np.testing.assert_array_equal(np.asarray(got)[live], flat[idx[live]])
    np.testing.assert_array_equal(
        np.asarray(rest["twin"])[live], flat[::-1][idx[live]])
    # decided by shape: a table of at most `WINDOW_ROWS` rows compiles
    # no conditional
    jaxpr = str(jax.make_jaxpr(take_rows_window)(
        tables, jnp.asarray(idx), jnp.asarray(live)))
    assert ("cond[" in jaxpr) == (case != "small_table")


def test_take_rows_window_of_no_table_gathers_nothing():
    idx = jnp.zeros(8, jnp.int32)
    got, flag = take_rows_window((), idx, idx == 0)
    assert got == () and not bool(flag)


def _unpack(words: np.ndarray) -> np.ndarray:
    return np.unpackbits(words.reshape(-1).view(np.uint8),
                         bitorder="little").astype(bool)


def _counts(name: str) -> int:
    return METRICS.snapshot()["counts"].get(name, 0)


def test_compute_aux_values_packs_caches_by_version_and_regrows():
    schema = Schema([Field("s", DataType.UTF8, True)])
    d = StringDictionary()
    rng = np.random.default_rng(5)
    names = [f"k{j:05d}" for j in rng.permutation(6_000)]
    specs = [AuxSpec("cmp_table", 0, "<", "k03000"),
             AuxSpec("eq_code", 0, "=", names[3])]

    def batch(strings):
        return make_host_batch(schema, [d.encode(strings)], dicts=[d])

    cache: dict = {}
    b1 = batch(names[:ROW_CODES - 1])
    before = _counts("expr.cmp_lookups")
    table, code = compute_aux_values(specs, b1, cache)
    assert table.shape == (1, LANES) and table.dtype == np.uint32
    assert code == d.code_of(names[3])
    np.testing.assert_array_equal(
        _unpack(table)[: d.version], d.compare_table("<", "k03000"))
    assert not _unpack(table)[d.version:].any()
    # same dictionary version: the same object, so a launch group's
    # identity signature holds across its batches
    assert compute_aux_values(specs, b1, cache)[0] is table
    # one more entry fills the row exactly: still one row
    b2 = batch(names[ROW_CODES - 1: ROW_CODES])
    full = compute_aux_values(specs, b2, cache)[0]
    assert full is not table and full.shape == (1, LANES)
    # the dictionary passes the capacity: the next bucket, two rows
    b3 = batch(names[ROW_CODES:])
    grown = compute_aux_values(specs, b3, cache)[0]
    assert grown.shape == (2, LANES)
    np.testing.assert_array_equal(
        _unpack(grown)[: d.version], d.compare_table("<", "k03000"))
    # one cmp_table spec, four calls: one lookup a batch each
    assert _counts("expr.cmp_lookups") - before == 4


# -- the compare through the cores ------------------------------------

N_ROWS, BATCH = 9_000, 1_024
LITERAL = "s002500"


def _write_table(tmp_path):
    """9 batches of 1,024 rows whose string column walks through 6,000
    values in shuffled order (three rows in ten repeat an earlier
    one), a tenth of them NULL: the scan's dictionary is unsorted,
    grows from batch to batch and passes a packed row's 4,096 codes
    on the way."""
    rng = np.random.default_rng(23)
    pool = np.array([f"s{j:06d}" for j in rng.permutation(6_000)])
    walk = np.arange(N_ROWS) * 6_000 // N_ROWS
    again = rng.random(N_ROWS) < 0.3
    walk[again] = (rng.random(again.sum()) * (walk[again] + 1)).astype(int)
    s = pool[walk].astype(object)
    s[rng.random(N_ROWS) < 0.1] = None
    s[0] = LITERAL  # the literal itself is a row: `<` and `<=` differ
    v = rng.integers(0, 1_000, N_ROWS).astype(float)
    p = np.round(rng.random(N_ROWS), 3)
    g = rng.integers(0, 5, N_ROWS)
    path = tmp_path / "t.csv"
    with open(path, "w", encoding="utf-8") as f:
        f.write("id,s,v,p,g\n")
        for i in range(N_ROWS):
            f.write(f"{i},{'' if s[i] is None else s[i]},{v[i]},{p[i]},{g[i]}\n")
    schema = Schema([Field("id", DataType.INT64, False),
                     Field("s", DataType.UTF8, True),
                     Field("v", DataType.FLOAT64, False),
                     Field("p", DataType.FLOAT64, False),
                     Field("g", DataType.INT64, False)])
    return str(path), schema, s, v, p, g


@pytest.fixture(scope="module")
def table(tmp_path_factory):
    return _write_table(tmp_path_factory.mktemp("rowgather"))


def _ctx(table) -> ExecutionContext:
    path, schema = table[:2]
    ctx = ExecutionContext(batch_size=BATCH, result_cache=False)
    ctx.register_csv("t", path, schema, has_header=True)
    return ctx


_PY_OPS = {"<": lambda a, b: a < b, "<=": lambda a, b: a <= b,
           ">": lambda a, b: a > b, ">=": lambda a, b: a >= b}


def _keep(s, op: str) -> np.ndarray:
    """Rows the compare keeps: NULL compares to nothing."""
    return np.array([x is not None and _PY_OPS[op](x, LITERAL) for x in s])


def _filter_core(ctx, op, s, v, p, g):
    rows = collect(ctx.sql(f"SELECT id FROM t WHERE s {op} '{LITERAL}'")).to_rows()
    assert sorted(r[0] for r in rows) == np.flatnonzero(_keep(s, op)).tolist()


def _aggregate_core(ctx, op, s, v, p, g):
    rows = collect(ctx.sql(
        f"SELECT COUNT(1), SUM(v) FROM t WHERE s {op} '{LITERAL}'")).to_rows()
    keep = _keep(s, op)
    assert rows == [(int(keep.sum()), float(v[keep].sum()))]


def _two_query_megabatch(ctx, op, s, v, p, g):
    lits = (0.35, 0.7)
    before = _counts("serve.megabatch_launches")
    srv = ctx.serve(workers=1, window_s=0.5, megabatch_max=8)
    try:
        tickets = [srv.submit(
            f"SELECT g, SUM(v), COUNT(1) FROM t "
            f"WHERE s {op} '{LITERAL}' AND p < {lit} GROUP BY g")
            for lit in lits]
        got = [sorted(t.result(timeout=120).to_rows()) for t in tickets]
    finally:
        srv.stop()
    assert _counts("serve.megabatch_launches") > before
    for lit, rows in zip(lits, got):
        keep = _keep(s, op) & (p < lit)
        want = sorted((int(k), float(v[keep & (g == k)].sum()),
                       int((keep & (g == k)).sum()))
                      for k in np.unique(g[keep]))
        assert rows == want


@pytest.mark.parametrize("op", ["<", "<=", ">", ">="])
@pytest.mark.parametrize(
    "route", [_filter_core, _aggregate_core, _two_query_megabatch],
    ids=["filter_core", "aggregate_core", "megabatch"])
def test_ordered_compare_agrees_with_python_on_every_row(table, route, op):
    ctx = _ctx(table)
    route(ctx, op, *table[2:])


def test_the_scan_dictionary_is_unsorted_and_grows_past_a_row(table):
    """What the route tests rest on."""
    ctx = _ctx(table)
    versions, d = [], None
    for b in ctx.datasources["t"].batches():
        d = b.dicts[1]
        versions.append(d.version)
    assert versions[0] < ROW_CODES < versions[-1]
    assert sorted(versions) == versions and len(set(versions)) > 2
    assert list(d.values) != sorted(d.values)


def test_cmp_lookups_counts_two_a_batch_for_q6_and_none_for_equality():
    rng = np.random.default_rng(3)
    schema = Schema([Field("l_shipdate", DataType.UTF8, False),
                     Field("l_discount", DataType.FLOAT64, False),
                     Field("l_quantity", DataType.FLOAT64, False),
                     Field("l_extendedprice", DataType.FLOAT64, False)])
    d = StringDictionary()
    batches = []
    for _ in range(3):
        days = rng.integers(0, 2_500, 2_048)
        dates = (np.datetime64("1992-01-01") + days).astype(str)
        batches.append(make_host_batch(
            schema,
            [d.encode(list(dates)), np.round(rng.uniform(0, 0.1, 2_048), 2),
             rng.integers(1, 51, 2_048).astype(float),
             np.round(rng.uniform(900, 105_000, 2_048), 2)],
            dicts=[d, None, None, None]))
    ctx = ExecutionContext(result_cache=False)
    ctx.register_datasource("lineitem", MemoryDataSource(schema, batches))
    q6 = ("SELECT SUM(l_extendedprice * l_discount) FROM lineitem "
          "WHERE l_shipdate >= '1994-01-01' AND l_shipdate < '1995-01-01' "
          "AND l_discount >= 0.05 AND l_discount <= 0.07 AND l_quantity < 24")
    before = _counts("expr.cmp_lookups")
    collect(ctx.sql(q6))
    assert _counts("expr.cmp_lookups") - before == 2 * len(batches)
    before = _counts("expr.cmp_lookups")
    rows = collect(ctx.sql(
        "SELECT COUNT(1) FROM lineitem WHERE l_shipdate = '1994-01-01'")).to_rows()
    assert rows[0][0] == int((d.decode(np.concatenate(
        [b.data[0][: b.num_rows] for b in batches])) == "1994-01-01").sum())
    assert _counts("expr.cmp_lookups") == before
