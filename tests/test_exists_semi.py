"""`EXISTS (SELECT ... WHERE inner = outer AND ...)` decorrelated into a semi
join, and the semi join's three builds: the parser and the plan, the answer
through `ctx.sql` against a plain Python evaluation of EXISTS (a set of the
build's keys) on seeded random tables, small and past the compare probe's
size, clustered and not (duplicate matches once, NULL keys on either side
matching nothing, an empty build, a predicate that keeps nothing), which
build the size rule gives each shape (a membership table marked and looked
up on the device past 4,096 rows, the compare probe for a small sparse
build as TPC-H Q18's, the host index for a large sparse one), the same
under an accelerator's rules, and the refusals by name; TPC-H Q4 through
the entry and the harness of the benchmark's cell at 400,000 lines, whose
batches are marked a group of one shape class a launch."""

import numpy as np
import pytest

from datafusion_tpu.errors import NotSupportedError
from datafusion_tpu.exec.context import ExecutionContext
from datafusion_tpu.exec.fused import fuse_group_max
from datafusion_tpu.exec.materialize import collect
from datafusion_tpu.plan.logical import Join, Projection, Selection
from datafusion_tpu.sql import ast
from datafusion_tpu.sql.parser import parse_sql
from test_join import _counts, _delta, _mem_table

PRIOS = ["1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED", "5-LOW"]
BATCH = 2_048

# name -> (the subquery's conjuncts besides the correlation, what they
# keep, the bytes a line the mark reads: the key, the columns they name
# and the key's validity)
CASES = {
    "late": ("AND lc < lr", lambda d: d["lc"] < d["lr"], 8 * 3 + 1),
    "every_line": ("", lambda d: np.ones(len(d["lk"]), bool), 8 + 1),
    "keeps_nothing": ("AND lc < 0", lambda d: np.zeros(len(d["lk"]), bool),
                      8 * 2 + 1),
}


def _tables(n_lines, clustered, seed=7):
    """`o` (orders: keys 1, 5, 9, ... with NULLs, a date, a priority) and
    `l` (lines: 0..7 an order, keys with NULLs and a few that no order
    has, two dates).  Lines are in key order where `clustered`."""
    rng = np.random.default_rng(seed)
    n_orders = n_lines // 4
    ok = np.arange(n_orders, dtype=np.int64) * 4 + 1
    ok_valid = rng.random(n_orders) > 0.05
    per = rng.integers(0, 8, n_orders)
    lk = np.repeat(ok, per)[:n_lines]
    lk = np.concatenate([lk, rng.integers(0, 4 * n_orders + 40,
                                          n_lines - len(lk))])
    if clustered:
        lk = np.sort(lk)
    else:
        lk = rng.permutation(lk)
    n = len(lk)
    return {
        "ok": ok, "ok_valid": ok_valid,
        "od": rng.integers(0, 100, n_orders),
        "op": rng.integers(0, len(PRIOS), n_orders),
        "lk": lk, "lk_valid": rng.random(n) > 0.03,
        "lc": rng.integers(0, 60, n), "lr": rng.integers(0, 60, n),
    }


def _groups(batches):
    """Mark launches of `batches` batches of one shape class: a group
    holds at most `fuse_group_max()` of them."""
    return -(-batches // fuse_group_max())


def _context(d):
    ctx = ExecutionContext(batch_size=BATCH, result_cache=False)
    _mem_table(ctx, "o", {"ok": (d["ok"], d["ok_valid"]), "od": d["od"],
                          "op": [PRIOS[i] for i in d["op"]]},
               batch_rows=BATCH)
    _mem_table(ctx, "l", {"lk": (d["lk"], d["lk_valid"]), "lc": d["lc"],
                          "lr": d["lr"]}, batch_rows=BATCH)
    return ctx


def _exists_by_python(d, keep):
    """EXISTS written out: the set of build keys (valid lines the
    subquery's other conjuncts keep), and each order whose key is valid
    and in it, once."""
    build = set(d["lk"][keep & d["lk_valid"]].tolist())
    return [i for i, (k, ok) in enumerate(zip(d["ok"], d["ok_valid"]))
            if ok and int(k) in build]


def _q4(cond, lo=20, hi=70):
    return ("SELECT op, COUNT(*) AS order_count FROM o "
            f"WHERE od >= {lo} AND od < {hi} AND EXISTS (SELECT * FROM l "
            f"WHERE lk = ok {cond}) GROUP BY op ORDER BY op")


@pytest.fixture(scope="module", params=[(1_000, True), (40_000, True),
                                        (40_000, False)],
                ids=["small", "large_clustered", "large_shuffled"])
def made(request):
    n, clustered = request.param
    d = _tables(n, clustered)
    return d, _context(d), n > 4_096


@pytest.mark.parametrize("case", list(CASES))
def test_each_order_once_against_python(made, case):
    """Every order with a match, once whatever its lines' duplicates; a
    NULL key on either side matches nothing; the build's path by size."""
    d, ctx, large = made
    cond, keep, row_bytes = CASES[case]
    rows = _exists_by_python(d, keep(d))
    s0 = _counts()
    got = sorted(r[0] for r in collect(ctx.sql(
        f"SELECT ok FROM o WHERE EXISTS (SELECT * FROM l WHERE lk = ok {cond})"
    )).to_rows())
    s1 = _counts()
    assert got == sorted(int(d["ok"][i]) for i in rows)
    assert _delta(s0, s1, "join.host_probe.rows") == 0
    probes = -(-len(d["ok"]) // BATCH)
    marks = -(-len(d["lk"]) // BATCH)
    # the lines' batches are one shape class: one group a launch
    assert _delta(s0, s1, "device.launches.join.semi_mark") == (
        _groups(marks) if large else 0)
    assert _delta(s0, s1, "join.semi.mark_batches") == (marks if large else 0)
    assert _delta(s0, s1, "device.launches.join.semi_lookup") == (
        probes if large else 0)
    assert _delta(s0, s1, "device.launches.join.semi") == (
        0 if large else probes)
    assert _delta(s0, s1, "join.semi.probe_rows") == len(d["ok"])
    if large:
        assert _delta(s0, s1, "join.semi.mark_rows") == len(d["lk"])
        slots = _delta(s0, s1, "join.semi.table_slots")
        assert slots == int(d["lk"][d["lk_valid"]].max()
                            - d["lk"][d["lk_valid"]].min()) + 1
        # the lines' bytes, and the table's once
        assert _delta(s0, s1, "join.semi.mark_bytes") == (
            len(d["lk"]) * row_bytes + -(-slots // 128) * 128)


@pytest.mark.parametrize("case", list(CASES))
def test_q4_shape_against_python(made, case):
    """Q4's shape: EXISTS beside a date range, grouped by a string key
    over the semi join's output and ordered by it."""
    d, ctx, _ = made
    cond, keep, _ = CASES[case]
    inside = (d["od"] >= 20) & (d["od"] < 70)
    counts = np.bincount([d["op"][i] for i in _exists_by_python(d, keep(d))
                          if inside[i]], minlength=len(PRIOS))
    want = [(p, int(n)) for p, n in zip(PRIOS, counts) if n]
    got = [(str(p), int(n)) for p, n in collect(ctx.sql(_q4(cond))).to_rows()]
    assert got == want


def test_a_clustered_build_marks_by_tile(made):
    """Every batch of a build in key order is marked by tile
    (`join.semi.mark.tiled`, counted on the device and read once a
    query, beside `join.semi.mark_batches`); a shuffled build's batches
    scatter; a small build marks nothing."""
    d, ctx, large = made
    s0 = _counts()
    collect(ctx.sql(_q4("AND lc < lr")))
    s1 = _counts()
    batches = _delta(s0, s1, "join.semi.mark_batches")
    assert batches == (-(-len(d["lk"]) // BATCH) if large else 0)
    assert _delta(s0, s1, "device.launches.join.semi_mark") == (
        _groups(batches) if large else 0)
    clustered = bool(np.all(np.diff(d["lk"]) >= 0))
    assert _delta(s0, s1, "join.semi.mark.tiled") == (
        batches if clustered else 0)


def test_an_empty_build():
    d = _tables(8_000, True)
    ctx = _context(d)
    _mem_table(ctx, "e", {"ek": np.zeros(0, np.int64)})
    assert collect(ctx.sql(
        "SELECT ok FROM o WHERE EXISTS (SELECT * FROM e WHERE ek = ok)"
    )).to_rows() == []


def test_warm_queries_mark_again_and_upload_nothing():
    """Each query marks its own table from the resident batches: a second
    query uploads no column, reads back no key, and answers the same."""
    d = _tables(40_000, True)
    ctx = _context(d)
    sql = _q4("AND lc < lr")
    first = collect(ctx.sql(sql)).to_rows()
    s0 = _counts()
    assert collect(ctx.sql(sql)).to_rows() == first
    s1 = _counts()
    assert _delta(s0, s1, "device.launches.join.semi_mark") == 1
    assert _delta(s0, s1, "join.semi.mark_batches") == 20
    assert _delta(s0, s1, "device.launches.join.semi_table") == 1
    assert _delta(s0, s1, "h2d.resident_misses") == 0
    # the two scalars of the table, and the final sort's few rows
    assert _delta(s0, s1, "h2d.bytes") < 16 * 1024
    assert _delta(s0, s1, "d2h.bytes") < 16 * 1024


def test_under_an_accelerators_rules(monkeypatch):
    """Where the subquery's pipeline would evaluate its predicate on the
    host, the mark evaluates it in the launch: no mask is made or put."""
    from datafusion_tpu.exec import relation as exec_rel

    monkeypatch.setattr(exec_rel, "_is_accelerator", lambda dev: True)
    d = _tables(40_000, False)
    ctx = _context(d)
    sql = _q4("AND lc < lr")
    collect(ctx.sql(sql))
    s0 = _counts()
    got = [(str(p), int(n)) for p, n in collect(ctx.sql(sql)).to_rows()]
    s1 = _counts()
    inside = (d["od"] >= 20) & (d["od"] < 70)
    counts = np.bincount([d["op"][i] for i in _exists_by_python(
        d, d["lc"] < d["lr"]) if inside[i]], minlength=len(PRIOS))
    assert got == [(p, int(n)) for p, n in zip(PRIOS, counts) if n]
    assert _delta(s0, s1, "device.launches.join.semi_mark") == 1
    assert _delta(s0, s1, "join.semi.mark_batches") == 20
    assert _delta(s0, s1, "join.host_probe.rows") == 0
    assert _delta(s0, s1, "h2d.bytes") < 16 * 1024


# TPC-H Q4 through the entry of the benchmark's cell `q4_sf10_exists`, at
# 400,000 lineitem rows: Parquet files of its data set, registered and
# kept resident as the cell keeps them
Q4_CELL = "q4_sf10_exists"
Q4_ROWS = 400_000
Q4_SEED = 2147483659


@pytest.fixture(scope="module")
def q4_engine(tmp_path_factory):
    import os

    from tpubench import data as tdata
    from tpubench.entries import Spans
    from tpubench.spec import Spec

    spec = Spec(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    ds = spec.dataset("tpch_q4_orders_lineitem")
    made = ds.generate(Q4_SEED, Q4_ROWS, threads=4)
    d = tmp_path_factory.mktemp("q4")
    paths = {}
    for table, cols in made["tables"].items():
        paths[table] = str(d / f"{table}.parquet")
        tdata.write_parquet(cols, paths[table], 100_000)
    entry = spec.entry(spec.traffic(spec.cell(Q4_CELL)["traffic"])["entry"])
    sql = spec.query("tpch_q4_orders_lineitem", "q4").format(
        **ds.bind("q4", {"date": "1993-07-01"}))
    return (entry("cpu", {"result_cache": False}, paths, Spans()), sql,
            made, spec.dataset("tpch_orders_lineitem"))


@pytest.mark.parametrize("rules", ["cpu", "accelerator"])
def test_each_q4_request_marks_its_batches_a_group_a_launch(
        q4_engine, monkeypatch, rules):
    """Two requests in a row, under a CPU's rules and an accelerator's:
    each marks every batch of lineitem into the membership table, a
    group of one shape class a launch (three batches of 131,072 lines
    and one of 6,784, a class of its own), looks every batch of orders
    up on the device, reads the lines' bytes and the table's once, and
    sends nothing to the host index; the second request finds every
    column resident."""
    import types

    from datafusion_tpu.exec import relation as exec_rel

    engine, sql, made, sibling = q4_engine
    if rules == "accelerator":
        monkeypatch.setattr(exec_rel, "_is_accelerator", lambda d: True)
    lines, orders = -(-Q4_ROWS // 131_072), -(-(Q4_ROWS // 4) // 131_072)
    for _ in range(2):
        s0 = _counts()
        got = engine.query(types.SimpleNamespace(sql=sql),
                           types.SimpleNamespace(rid=0))
        s1 = _counts()
        assert made["oracle"].check("q4", {"date": "1993-07-01"}, got) is None
        assert _delta(s0, s1, "join.semi.mark_batches") == lines
        assert _delta(s0, s1, "device.launches.join.semi_mark") == 2
        assert _delta(s0, s1, "join.semi.mark.tiled") == lines
        assert _delta(s0, s1, "device.launches.join.semi_lookup") == orders
        assert _delta(s0, s1, "join.semi.mark_rows") == Q4_ROWS
        assert _delta(s0, s1, "join.semi.probe_rows") == Q4_ROWS // 4
        # keys 1 .. the last order's, the table in rows of 128
        slots = int(sibling.order_keys(Q4_ROWS // 4 - 1, Q4_ROWS // 4)[0])
        assert _delta(s0, s1, "join.semi.table_slots") == slots
        assert _delta(s0, s1, "join.semi.mark_bytes") == (
            Q4_ROWS * 24 + -(-slots // 128) * 128)
        assert _delta(s0, s1, "join.host_probe.rows") == 0
        assert _delta(s0, s1, "device.launches.join.semi") == 0
        assert _delta(s0, s1, "aggregate.key_pull.bytes") == 0
        assert _delta(s0, s1, "d2h.bytes") < 16 * 1024
    assert _delta(s0, s1, "h2d.resident_misses") == 0


@pytest.fixture
def jax_cache_threshold_as_found():
    """A harness run sets JAX's persistent-cache threshold for its
    process; put back, so a later test on the same worker
    (`test_compile_cache.py` reads JAX's own default) finds JAX as it
    was."""
    import jax

    name = "jax_persistent_cache_min_compile_time_secs"
    before = getattr(jax.config, name)
    yield
    jax.config.update(name, before)


def test_the_q4_cells_rehearsal_marks_a_group_a_launch(
        capsys, tmp_path, jax_cache_threshold_as_found):
    """The cell's harness at 400,000 rows on the CPU (counts only): the
    answer `correct`, every column resident, nothing compiled in the
    window, and a request's launches two mark groups, the one lookup of
    orders' batch, the table, the aggregate and the sort of its five
    rows."""
    import json
    import os
    import shutil

    from tpubench.harness import main

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    root = str(tmp_path / "root")
    os.makedirs(root)
    shutil.copy(os.path.join(repo, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(repo, "tpubench"),
                    os.path.join(root, "tpubench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    capsys.readouterr()
    code = main(["--workload", Q4_CELL, "--seed", str(Q4_SEED), "--seconds",
                 "0.3", "--trace", "1", "--rehearse-rows", str(Q4_ROWS)],
                root=root)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert code == 0 and line["correct"] is True and line["failed"] == 0
    m = line["metrics"]
    assert m["resident_hit_share"]["value"] == 100
    assert m["d2h_kb_per_query"]["value"] < 16
    assert m["h2d_mb_per_query"]["value"] < 0.1
    assert m["compiles_in_window"]["value"] == 0
    assert m["launches_per_query"]["value"] == 2 + 1 + 1 + 1 + 1
    # no device time in a rehearsal
    for name in ("semi_mark_ms_per_query", "semi_mark_roofline"):
        assert name not in m


@pytest.mark.parametrize("shape", ["grouped", "scan"])
def test_a_small_sparse_build_keeps_the_compare_probe(shape):
    """TPC-H Q18's shape: some 600 keys over a 60 M-key span, made on the
    device by a grouped subquery or scanned, are compared, not tabled: a
    table would be 60 MB."""
    rng = np.random.default_rng(3)
    keys = np.sort(rng.choice(60_000_000, 600, replace=False)).astype(np.int64)
    probe = np.concatenate([keys[::3], rng.integers(0, 60_000_000, 3_000)])
    ctx = ExecutionContext(batch_size=BATCH, result_cache=False)
    _mem_table(ctx, "t", {"k": keys}, batch_rows=BATCH)
    _mem_table(ctx, "p", {"pk": probe}, batch_rows=BATCH)
    sub = ("SELECT k FROM t GROUP BY k" if shape == "grouped"
           else "SELECT k FROM t")
    s0 = _counts()
    got = sorted(r[0] for r in collect(ctx.sql(
        f"SELECT pk FROM p WHERE pk IN ({sub})")).to_rows())
    s1 = _counts()
    assert got == sorted(int(x) for x in probe if x in set(keys.tolist()))
    assert _delta(s0, s1, "device.launches.join.semi") == -(-len(probe) // BATCH)
    assert _delta(s0, s1, "device.launches.join.semi_build") == (
        1 if shape == "grouped" else 0)
    for tag in ("semi_mark", "semi_lookup", "semi_table"):
        assert _delta(s0, s1, f"device.launches.join.{tag}") == 0
    assert _delta(s0, s1, "join.host_probe.rows") == 0


def test_a_large_sparse_build_takes_the_host_index():
    """Past the compare probe's size and sparser than 32 slots a row: the
    host index, as before."""
    rng = np.random.default_rng(4)
    keys = rng.choice(1 << 40, 5_000, replace=False).astype(np.int64)
    probe = np.concatenate([keys[:100], rng.integers(0, 1 << 40, 500)])
    ctx = ExecutionContext(batch_size=BATCH, result_cache=False)
    _mem_table(ctx, "t", {"k": keys}, batch_rows=BATCH)
    _mem_table(ctx, "p", {"pk": probe}, batch_rows=BATCH)
    s0 = _counts()
    got = sorted(r[0] for r in collect(ctx.sql(
        "SELECT pk FROM p WHERE EXISTS (SELECT * FROM t WHERE k = pk)")
    ).to_rows())
    s1 = _counts()
    assert got == sorted(int(x) for x in probe if x in set(keys.tolist()))
    assert _delta(s0, s1, "device.launches.join.semi_mark") == 0
    assert _delta(s0, s1, "join.host_probe.rows") == len(probe)


def test_the_plan_keeps_the_subquerys_conjuncts_inside_the_build():
    d = _tables(1_000, True)
    ctx = _context(d)
    plan = ctx._plan(parse_sql(_q4("AND lc < lr")))
    join = plan
    while not isinstance(join, Join):
        join = join.input
    assert join.join_type == "semi" and join.on == [(0, 0)]
    assert isinstance(join.right, Projection)
    assert isinstance(join.right.input, Selection)
    assert "Join: type=semi, on=[#0=#0]" in repr(ctx.sql("EXPLAIN " + _q4("")))


def test_the_parser_reads_exists():
    node = parse_sql("SELECT a FROM t WHERE a > 1 AND EXISTS (SELECT * FROM u "
                     "WHERE b = a)")
    right = node.selection.right
    assert isinstance(right, ast.SqlExists) and not right.negated
    assert right.subquery.selection is not None
    neg = parse_sql("SELECT a FROM t WHERE NOT EXISTS (SELECT b FROM u)")
    assert isinstance(neg.selection, ast.SqlExists) and neg.selection.negated


@pytest.mark.parametrize("where, why", [
    ("NOT EXISTS (SELECT * FROM l WHERE lk = ok)", "NOT EXISTS"),
    ("od > 3 OR EXISTS (SELECT * FROM l WHERE lk = ok)", "not under OR"),
    ("EXISTS (SELECT * FROM l WHERE lk < ok)", "non-equality correlation"),
    ("EXISTS (SELECT * FROM l WHERE lk = ok AND lc = od)",
     "more than one correlated column"),
    ("EXISTS (SELECT * FROM l WHERE lc < lr)", "uncorrelated EXISTS"),
])
def test_what_is_refused(where, why):
    ctx = _context(_tables(1_000, True))
    with pytest.raises(NotSupportedError, match=why):
        ctx.sql(f"SELECT ok FROM o WHERE {where}")
