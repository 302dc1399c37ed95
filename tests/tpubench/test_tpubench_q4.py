"""The cell `q4_sf10_exists` (TPC-H Q4 over ORDERS and LINEITEM): every name
of it resolves; its data set shares the sibling's columns and adds the
order date the sibling draws first; its oracle equals EXISTS written out in
numpy; the comparison refuses a count off by one, and the query through
`ctx.sql` at 400,000 lineitem rows is right while an inner join in place of
the semi join (lines counted, not orders) and the subquery without
`l_commitdate < l_receiptdate` are caught; two requests in a row each mark
the membership table from lineitem's resident batches and look orders up
there, under an accelerator's rules too; a rehearsal reads `correct: true`;
the readers of the two new metrics on made-up runs; and what the two
accepted tests this cell's entries supersede hold besides their line."""

import types

import numpy as np
import pytest

from bench_helpers import REPO, copy_benchmark, run_harness
from tpubench import data as tdata
from tpubench.check import Worst
from tpubench.spec import Spec

CELL = "q4_sf10_exists"
CONFIG_NAME = "tpch_sf10_q4_orders_lineitem"
SPEC = Spec(REPO)
DS = SPEC.dataset("tpch_q4_orders_lineitem")
SIBLING = SPEC.dataset("tpch_orders_lineitem")
SEED = 2147483659
ROWS = 400_000
DATE = "1993-07-01"
NEW_METRICS = ("semi_mark_ms_per_query", "semi_mark_roofline")
Q18_METRICS = ("semi_probe_ms_per_query", "having_ms_per_query",
               "agg_reduce_roofline", "semi_probe_roofline")
LAUNCH_FIVE = ["launch_cpu_ms_per_query", "launch_wait_ms_per_query",
               "launch_args_per_launch", "launch_host_args_per_launch",
               "mesh_assemble_ms_per_query"]


def _sql(date=DATE):
    return SPEC.query("tpch_q4_orders_lineitem", "q4").format(
        **DS.bind("q4", {"date": date}))


def test_every_name_of_the_cell_resolves():
    cell = SPEC.cell(CELL)
    assert cell["chips"] == 1 and cell["config"] == CONFIG_NAME
    assert len(cell["why"]) <= 200
    config, mix = SPEC.config(cell["config"]), SPEC.traffic(cell["traffic"])
    assert mix["entry"] == "sql" and mix["loop"] == {"kind": "closed",
                                                     "clients": 1}
    assert mix["warmup"] == {"requests_each": 1} and mix["trace_seconds"] == 6
    assert [t["name"] for t in mix["templates"]] == ["q4"]
    params = {k: v["value"] for k, v in mix["templates"][0]["params"].items()}
    assert params == {"date": DATE}
    assert list(DS.TABLES) == config["tables"] == ["lineitem", "orders"]
    assert config["resident_columns"] == [
        c for cols in DS.TABLES.values() for c in cols]
    assert config["rows"] == 60_000_000
    sql = _sql()
    assert ("EXISTS (SELECT * FROM lineitem WHERE l_orderkey = o_orderkey "
            "AND l_commitdate < l_receiptdate)") in sql
    # 1993-07-01 and 1993-10-01 as day numbers
    assert "o_orderdate >= 8582 AND o_orderdate < 8674" in sql
    from tpubench import peaks

    rows = {"lineitem": 60_000_000, "orders": 15_000_000}
    assert peaks.query_scan(sql, DS.TABLES, rows) == (
        75_000_000, 60_000_000 * 24 + 15_000_000 * 20)
    e2e = {m["name"] for m in SPEC.metrics_of(CELL, "end_to_end")}
    assert e2e == {"rows_per_s", "setup_s"}
    layer = {m["name"]: m for m in SPEC.metrics_of(CELL, "per_layer")}
    for name in NEW_METRICS:
        assert layer[name]["workloads"] == [CELL]
    assert CELL in layer["resident_hit_share"]["workloads"]
    # the accepted test_tpubench_q18.py holds both lists to q18 alone
    assert not {"semi_probe_ms_per_query", "semi_probe_roofline"} & set(layer)
    # every per-layer metric with no list of cells is this cell's too
    unlisted = [m["name"] for m in SPEC.bench["per_layer"]
                if "workloads" not in m]
    assert set(unlisted) <= set(layer)


@pytest.fixture(scope="module")
def made():
    return DS.generate(SEED, ROWS, threads=4)


def test_the_shared_columns_are_the_sibling_data_sets():
    mine = DS.generate(SEED, 600_003, threads=3)["tables"]
    theirs = SIBLING.generate(SEED, 600_003, threads=2)["tables"]
    for table, col in (("lineitem", "l_orderkey"),
                       ("lineitem", "l_commitdate"),
                       ("lineitem", "l_receiptdate"),
                       ("orders", "o_orderkey")):
        assert np.array_equal(mine[table][col], theirs[table][col]), col
    assert np.array_equal(mine["orders"]["o_orderpriority"][0],
                          theirs["orders"]["o_orderpriority"][0])
    assert tuple(mine["orders"]["o_orderpriority"][1]) == DS.PRIORITIES


def test_the_order_date_is_the_one_the_line_dates_were_made_from(made):
    """Commit is order date + 30..90 and receipt order date + 2..151 for
    every line, by its order's key."""
    t = made["tables"]
    o, li = t["orders"], t["lineitem"]
    row = np.searchsorted(o["o_orderkey"], li["l_orderkey"])
    odate = o["o_orderdate"][row]
    commit = li["l_commitdate"] - odate
    receipt = li["l_receiptdate"] - odate
    assert commit.min() == 30 and commit.max() == 90
    assert receipt.min() >= 2 and receipt.max() <= 151
    assert DS.SIBLING.ORDER_DATE_LO <= odate.min()
    assert odate.max() <= DS.SIBLING.ORDER_DATE_HI
    # about 63 % of the lines are late, about 3.8 % of the orders in Q4's
    # three months
    late = np.mean(li["l_commitdate"] < li["l_receiptdate"])
    assert 0.60 < late < 0.66
    lo, hi = DS.window(DATE)
    inside = np.mean((o["o_orderdate"] >= lo) & (o["o_orderdate"] < hi))
    assert 0.035 < inside < 0.041


def _written_out(made, date):
    """Q4 by numpy on the whole generated tables: the set of late lines'
    keys, the orders in the window whose key is in it, by priority."""
    t = made["tables"]
    li, o = t["lineitem"], t["orders"]
    late = np.unique(li["l_orderkey"][li["l_commitdate"] < li["l_receiptdate"]])
    lo, hi = DS.window(date)
    keep = ((o["o_orderdate"] >= lo) & (o["o_orderdate"] < hi)
            & np.isin(o["o_orderkey"], late))
    counts = np.bincount(o["o_orderpriority"][0][keep],
                         minlength=len(DS.PRIORITIES))
    return [(p, int(n)) for p, n in zip(DS.PRIORITIES, counts) if n]


@pytest.mark.parametrize("date", [DATE, "1993-01-01", "1997-10-01"])
def test_the_oracle_equals_q4_written_out(made, date):
    want = _written_out(made, date)
    assert made["oracle"].answer("q4", {"date": date}) == want
    assert len(want) == 5 and sum(n for _, n in want) > 1_000


def test_bind_resolves_the_interval():
    assert DS.bind("q4", {"date": "1993-07-01"}) == {
        "date_lo": 8582, "date_hi": 8674}
    assert DS.window("1997-11-01")[1] - DS.window("1997-11-01")[0] == 92
    with pytest.raises(ValueError):
        DS.window("1993-07-02")


def _result(rows, names=("o_orderpriority", "COUNT")):
    return types.SimpleNamespace(
        to_rows=lambda: list(rows),
        schema=types.SimpleNamespace(names=lambda: list(names)))


@pytest.mark.parametrize("fault", ["count_off_by_one", "row_missing",
                                   "rows_swapped", None])
def test_check_refuses_what_it_has_to(made, fault):
    oracle = made["oracle"]
    rows = oracle.answer("q4", {"date": DATE})
    if fault == "count_off_by_one":
        rows[2] = (rows[2][0], rows[2][1] + 1)
    elif fault == "row_missing":
        rows = rows[:-1]
    elif fault == "rows_swapped":
        rows[0], rows[1] = rows[1], rows[0]
    said = oracle.check("q4", {"date": DATE}, _result(rows), Worst())
    assert (said is None) == (fault is None), said


@pytest.fixture(scope="module")
def engine(made, tmp_path_factory):
    """The cell's entry over the data set's files."""
    from tpubench.entries import Spans

    entry = SPEC.entry(SPEC.traffic(SPEC.cell(CELL)["traffic"])["entry"])
    d = tmp_path_factory.mktemp("q4")
    paths = {}
    for table, cols in made["tables"].items():
        paths[table] = str(d / f"{table}.parquet")
        tdata.write_parquet(cols, paths[table], 100_000)
    return entry("cpu", {"result_cache": False}, paths, Spans())


def _query(engine, sql):
    return engine.query(types.SimpleNamespace(sql=sql),
                        types.SimpleNamespace(rid=0))


@pytest.mark.parametrize("date", [DATE, "1995-02-01"])
def test_q4_through_ctx_sql_against_the_oracle(made, engine, date):
    result = _query(engine, _sql(date))
    assert made["oracle"].check("q4", {"date": date}, result) is None
    assert result.num_rows == 5


@pytest.mark.parametrize("fault", ["inner_join", "conjunct_dropped"])
def test_a_wrong_query_is_caught(made, engine, fault):
    """An inner join counts an order once a late line; the subquery
    without its own conjunct counts orders with any line."""
    sql = _sql()
    if fault == "inner_join":
        lo, hi = DS.window(DATE)
        sql = ("SELECT o_orderpriority, COUNT(*) AS order_count FROM orders "
               "JOIN lineitem ON orders.o_orderkey = lineitem.l_orderkey "
               f"WHERE o_orderdate >= {lo} AND o_orderdate < {hi} AND "
               "l_commitdate < l_receiptdate GROUP BY o_orderpriority "
               "ORDER BY o_orderpriority")
    else:
        sql = sql.replace(" AND l_commitdate < l_receiptdate", "")
        assert "l_commitdate" not in sql
    said = made["oracle"].check("q4", {"date": DATE}, _query(engine, sql))
    assert said is not None and "oracle" in said


@pytest.mark.parametrize("rules", ["cpu", "accelerator"])
def test_each_request_marks_and_looks_up_on_the_device(engine, monkeypatch,
                                                       rules):
    from datafusion_tpu.exec import relation as exec_rel
    from datafusion_tpu.utils.metrics import METRICS

    if rules == "accelerator":
        monkeypatch.setattr(exec_rel, "_is_accelerator", lambda d: True)
    lines, orders = -(-ROWS // 131_072), -(-(ROWS // 4) // 131_072)
    for _ in range(2):
        s0 = dict(METRICS.snapshot()["counts"])
        _query(engine, _sql())
        s1 = dict(METRICS.snapshot()["counts"])

        def delta(k):
            return s1.get(k, 0) - s0.get(k, 0)

        assert delta("device.launches.join.semi_mark") == lines
        assert delta("device.launches.join.semi_lookup") == orders
        assert delta("join.semi.mark_rows") == ROWS
        assert delta("join.semi.probe_rows") == ROWS // 4
        # keys 1 .. the last order's, the table in rows of 128
        slots = int(SIBLING.order_keys(ROWS // 4 - 1, ROWS // 4)[0])
        assert delta("join.semi.table_slots") == slots
        assert delta("join.semi.mark_bytes") == (
            ROWS * 24 + -(-slots // 128) * 128)
        assert delta("join.host_probe.rows") == 0
        assert delta("device.launches.join.semi") == 0
        assert delta("aggregate.key_pull.bytes") == 0
        assert delta("d2h.bytes") < 16 * 1024
    # the second request found every column resident
    assert delta("h2d.resident_misses") == 0


def test_rehearsal_is_correct_and_keeps_its_data_on_the_device(
        capsys, tmp_path):
    code, line, out = run_harness(
        capsys, copy_benchmark(tmp_path), "--workload", CELL, "--seed",
        str(SEED), "--seconds", "0.3", "--trace", "1", "--rehearse-rows",
        str(ROWS))
    assert code == 0 and line["correct"] is True and line["failed"] == 0
    m = line["metrics"]
    assert m["resident_hit_share"]["value"] == 100
    assert m["d2h_kb_per_query"]["value"] < 16
    assert m["h2d_mb_per_query"]["value"] < 0.1
    assert m["compiles_in_window"]["value"] == 0
    # a launch a build batch and a probe batch, the table, the aggregate
    # and the sort of its five rows
    assert m["launches_per_query"]["value"] == 4 + 1 + 1 + 1 + 1
    for name in NEW_METRICS:  # no device time in a rehearsal
        assert name not in m


def _run(**kw):
    base = dict(trace={"device_ops": []}, counts={}, timings={},
                queries=2, device={"kind": "TPU v5 lite"})
    base.update(kw)
    return types.SimpleNamespace(**base)


def test_the_readers_on_made_up_runs():
    read = {n: SPEC.metric_reader(n) for n in NEW_METRICS}
    # an engine without the span and counters (the parent): nothing
    parent = _run(trace={"device_ops": [["jit_semi_probe:fusion", 1.0]]},
                  timings={"join.semi_probe": 0.5})
    assert all(r(parent) is None for r in read.values())
    run = _run(
        trace={"device_ops": [["jit_semi_mark:scatter.3", 0.6],
                              ["jit_semi_mark:fusion.1", 0.1],
                              ["jit_semi_lookup:fusion", 0.07]]},
        counts={"join.semi.mark_bytes": 2 * 1_500_000_000},
        timings={"join.semi_mark": 0.8})
    assert read["semi_mark_ms_per_query"](run) == pytest.approx(400.0)
    assert read["semi_mark_roofline"](run) == pytest.approx(
        100 * 3e9 / 819e9 / 0.7)
    # a cell whose trace holds no mark: nothing, whatever the counters say
    assert read["semi_mark_roofline"](_run(
        counts={"join.semi.mark_bytes": 10})) is None


# -- what the two tests this cell's entries supersede hold besides their line --


def test_q18s_four_then_this_cells_two_close_per_layer():
    names = [m["name"] for m in SPEC.bench["per_layer"]]
    assert names[-2:] == list(NEW_METRICS)
    assert names[-6:-2] == list(Q18_METRICS)
    assert names[-11:-6] == LAUNCH_FIVE
    assert names[-12] == "resident_hit_share" and len(set(names)) == len(names)


def test_the_four_chip_cell_by_name():
    bench = SPEC.bench
    mesh = SPEC.cell("q1_sf100_mesh4")
    config = next(c for c in bench["configs"]
                  if c["name"] == "tpch_lineitem_sf100_mesh4")
    assert mesh["config"] == config["name"]
    assert mesh["chips"] == 4 and len(mesh["why"]) <= 200
    assert len(config["source"]) <= 200 and len(config["why"]) <= 200
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    e2e = {m["name"] for m in SPEC.metrics_of(mesh["name"], "end_to_end")}
    assert e2e == {"rows_per_s", "setup_s"}
    share = next(m for m in bench["per_layer"]
                 if m["name"] == "resident_hit_share")
    assert share["workloads"][-3:] == [mesh["name"], "q18_sf10_highcard", CELL]
    asked = [m["name"] for m in SPEC.metrics_of(mesh["name"], "per_layer")]
    assert len(asked) == 19 + len(LAUNCH_FIVE)
    assert set(asked[19:]) == set(LAUNCH_FIVE)
    assert not set(asked) & (set(NEW_METRICS) | set(Q18_METRICS))
