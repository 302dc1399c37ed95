"""The data set `tpch_customer_orders_lineitem` (TPC-H CUSTOMER, ORDERS and
LINEITEM as clause 4.2.3 populates them, the columns Q3 reads) and its
oracle: the tables do not depend on the thread count, their checksums and
the Q3 answer at one seed are pinned, the columns it shares with
`tpch_orders_lineitem` are that data set's, the oracle equals a join,
sum and sort written out here, the spec's rules for customers and orders
hold, and the comparison refuses what it has to (a revenue off by 1e-6,
two rows swapped, the sums made in float32)."""

import types
import zlib

import numpy as np
import pytest

from bench_helpers import REPO
from tpubench.check import RTOL, Worst
from tpubench.spec import Spec

SPEC = Spec(REPO)
DS = SPEC.dataset("tpch_customer_orders_lineitem")
SIBLING = SPEC.dataset("tpch_orders_lineitem")
ROWS = 2_600_003  # three chunks of orders, the last one short; not 4 x orders
SEED = 2147483659
Q3 = {"segment": "BUILDING", "date": "1995-03-15"}
NAMES = ["l_orderkey", "o_orderdate", "o_shippriority", "SUM"]  # the engine's


def _codes(col):
    return col[0] if isinstance(col, tuple) else col


def _result(rows, names=NAMES):
    """What the oracle's `check` reads of an engine result: rows in the
    engine's column order (keys, then the aggregate)."""
    engine = [(k, d, p, r) for k, r, d, p in rows]
    return types.SimpleNamespace(
        to_rows=lambda: engine,
        schema=types.SimpleNamespace(names=lambda: list(names)))


@pytest.fixture(scope="module")
def made():
    return DS.generate(SEED, ROWS, threads=8)


def test_tables_do_not_depend_on_the_thread_count(made):
    one = DS.generate(SEED, ROWS, threads=1)
    for table, cols in DS.TABLES.items():
        for name in cols:
            assert np.array_equal(_codes(one["tables"][table][name]),
                                  _codes(made["tables"][table][name])), name
    assert one["oracle"].answer("q3", Q3) == made["oracle"].answer("q3", Q3)
    assert one["oracle"].groups == made["oracle"].groups
    other = DS.generate(5, ROWS, threads=8)
    assert other["oracle"].answer("q3", Q3) != made["oracle"].answer("q3", Q3)


def test_checksums_and_the_q3_answer_at_one_seed_are_pinned(made):
    sums = {name: zlib.crc32(np.ascontiguousarray(
                _codes(made["tables"][table][name])).tobytes())
            for table, cols in DS.TABLES.items() for name in cols}
    assert sums == PINNED_CRC32
    assert made["oracle"].answer("q3", Q3) == PINNED_Q3
    assert made["oracle"].groups == PINNED_GROUPS


def test_the_shared_columns_are_the_sibling_data_sets(made):
    sibling = SIBLING.generate(SEED, ROWS, threads=4)["tables"]
    for table, name in (("lineitem", "l_orderkey"), ("lineitem", "l_shipdate"),
                        ("orders", "o_orderkey")):
        assert np.array_equal(made["tables"][table][name],
                              sibling[table][name]), name
    assert DS.order_count(ROWS) == SIBLING.order_count(ROWS)


def test_row_counts_kinds_and_domains(made):
    line, orders, customer = (made["tables"][t] for t in DS.TABLES)
    assert len(line["l_orderkey"]) == ROWS
    assert len(orders["o_orderkey"]) == ROWS // 4 == DS.order_count(ROWS)
    assert len(customer["c_custkey"]) == ROWS // 40 == DS.customer_count(ROWS)
    assert DS.customer_count(60_000_000) == 1_500_000
    for table, cols in DS.TABLES.items():
        for name, kind in cols.items():
            col = made["tables"][table][name]
            assert isinstance(col, tuple) == (kind == "str"), name
            assert _codes(col).dtype == {"str": np.int32, "i64": np.int64,
                                         "f64": np.float64}[kind], name
    # customers: dense keys, five segments each near a fifth
    assert np.array_equal(customer["c_custkey"],
                          np.arange(1, len(customer["c_custkey"]) + 1))
    assert customer["c_mktsegment"][1] == DS.SEGMENTS and len(DS.SEGMENTS) == 5
    share = np.bincount(customer["c_mktsegment"][0], minlength=5) / len(
        customer["c_custkey"])
    assert np.all(np.abs(share - 0.2) < 0.01)
    # orders: no customer key divisible by 3, so a third have no order;
    # every other customer key is drawn
    cust = orders["o_custkey"]
    assert cust.min() == 1 and cust.max() <= len(customer["c_custkey"])
    assert not (cust % 3 == 0).any()
    assert len(np.unique(cust)) == len(customer["c_custkey"]) - len(
        customer["c_custkey"]) // 3
    assert not orders["o_shippriority"].any()
    first, last = DS.day_number("1992-01-01"), DS.day_number("1998-08-02")
    assert orders["o_orderdate"].min() == first
    assert orders["o_orderdate"].max() == last
    # lines: ship date = order date + 1..121, discount 0.00..0.10 by 0.01
    lag = line["l_shipdate"] - orders["o_orderdate"][
        np.searchsorted(orders["o_orderkey"], line["l_orderkey"])]
    assert lag.min() == 1 and lag.max() == 121
    assert np.array_equal(np.unique(np.round(line["l_discount"] * 100)),
                          np.arange(11))
    price = line["l_extendedprice"]
    assert 900 <= price.min() < 901 and 104_949 < price.max() <= 104_950
    assert np.array_equal(price, np.round(price, 2))


@pytest.mark.parametrize("seed,rows", [(3, 30_000), (SEED, 1_000_007)])
def test_oracle_equals_a_join_sum_and_sort_written_out(seed, rows):
    """Q3 by dictionaries from key to row, not by the oracle's sorted
    search: filter, join twice, sum line by line, sort."""
    made = DS.generate(seed, rows, threads=2)
    line, orders, customer = (made["tables"][t] for t in DS.TABLES)
    day = DS.bind("q3", Q3)["date"]
    building = {int(k) for k, s in zip(customer["c_custkey"],
                                       customer["c_mktsegment"][0])
                if DS.SEGMENTS[s] == "BUILDING"}
    order_of = {int(k): (int(d), int(p)) for k, c, d, p in zip(
        orders["o_orderkey"], orders["o_custkey"], orders["o_orderdate"],
        orders["o_shippriority"]) if d < day and int(c) in building}
    revenue: dict = {}
    kept = np.flatnonzero(line["l_shipdate"] > day)
    for k, p, d in zip(line["l_orderkey"][kept].tolist(),
                       line["l_extendedprice"][kept].tolist(),
                       line["l_discount"][kept].tolist()):
        if k in order_of:
            revenue[k] = revenue.get(k, 0.0) + p * (1 - d)
    first = sorted(revenue, key=lambda k: (-revenue[k], order_of[k][0]))[:10]
    got = made["oracle"].answer("q3", Q3)
    assert [(k, d, p) for k, _, d, p in got] == [
        (k, *order_of[k]) for k in first]
    assert [r for _, r, _, _ in got] == pytest.approx(
        [revenue[k] for k in first], rel=1e-13)
    assert made["oracle"].groups == len(revenue) > 50
    # the spec's shape: ~0.8 % of the orders keep a line
    assert 0.005 < len(revenue) / len(orders["o_orderkey"]) < 0.011
    # the arrays a later run of the seed finds again give the same oracle
    again = DS.Oracle.from_arrays(made["oracle"].arrays())
    assert again.answer("q3", Q3) == got and again.groups == len(revenue)


def test_bind_and_the_parameters_answered():
    assert DS.bind("q3", Q3) == {"segment": "BUILDING", "date": 9204}
    assert DS.day_number("1970-01-02") == 1
    with pytest.raises(KeyError):
        DS.bind("q12", {})
    oracle = DS.generate(3, 30_000, threads=1)["oracle"]
    for params in ({"segment": "MACHINERY", "date": "1995-03-15"},
                   {"segment": "BUILDING", "date": "1995-03-16"}):
        with pytest.raises(KeyError):
            oracle.answer("q3", params)
    with pytest.raises(KeyError):
        oracle.answer("q12", Q3)


def test_the_oracle_imports_nothing_of_the_engine():
    import ast

    with open(DS.__file__) as f:
        tree = ast.parse(f.read())
    imported = {n.module if isinstance(n, ast.ImportFrom) else a.name
                for n in ast.walk(tree)
                if isinstance(n, (ast.Import, ast.ImportFrom))
                for a in n.names}
    assert not any(m.startswith(("datafusion_tpu", "jax")) for m in imported)


def test_check_takes_the_rows_in_order_by_the_engines_column_names(made):
    rows = made["oracle"].answer("q3", Q3)
    worst = Worst()
    assert made["oracle"].check("q3", Q3, _result(rows), worst) is None
    assert worst.gap == 0.0
    # the aggregate's name is the engine's business: any fourth name
    other = ["l_orderkey", "o_orderdate", "o_shippriority", "revenue"]
    assert made["oracle"].check("q3", Q3, _result(rows, other)) is None
    assert "columns" in made["oracle"].check(
        "q3", Q3, _result(rows, ["a", "b", "c", "d"]))


@pytest.mark.parametrize("fault,said", [
    ("revenue_off_by_1e-6", "vs oracle"), ("two_rows_swapped", "row 3"),
    ("a_key_off_by_one", "!="), ("nine_rows", "rows"),
    ("revenue_inside_the_tolerance", None)])
def test_check_refuses_what_it_has_to(made, fault, said):
    rows = made["oracle"].answer("q3", Q3)
    k, r, d, p = rows[3]
    if fault == "revenue_off_by_1e-6":
        rows[3] = (k, r * (1 + 1e-6), d, p)
    elif fault == "two_rows_swapped":
        rows[3], rows[4] = rows[4], rows[3]
    elif fault == "a_key_off_by_one":
        rows[3] = (k + 1, r, d, p)
    elif fault == "nine_rows":
        rows = rows[:9]
    else:
        rows[3] = (k, r * (1 + 1e-11), d, p)
    worst = Worst()
    bad = made["oracle"].check("q3", Q3, _result(rows), worst)
    assert (bad is None) if said is None else (said in bad)
    if fault == "revenue_off_by_1e-6":
        assert worst.gap == pytest.approx(1e-6, rel=1e-3)


def test_a_tie_within_the_tolerance_refuses_the_seed(made):
    a = made["oracle"].arrays()
    a["revenue"] = a["revenue"].copy()
    a["revenue"][10] = a["revenue"][9] * (1 - RTOL)
    with pytest.raises(AssertionError, match="tie"):
        DS.Oracle.from_arrays(a).require_no_tie()
    made["oracle"].require_no_tie()


@pytest.mark.parametrize("seed", [3, SEED, 2718281828])
def test_the_float32_control_is_refused(seed):
    """The oracle's own sums made in float32 (price, discount, the product
    and `np.add.at` in float32) miss the limit: the limit 1e-9 stands
    between the engine's widest gap on the chip (PERF.md section 4) and
    this reading."""
    made = DS.generate(seed, 400_000, threads=2)
    line, orders, customer = (made["tables"][t] for t in DS.TABLES)
    day = DS.day_number(DS.DATE)
    order = np.searchsorted(orders["o_orderkey"], line["l_orderkey"])
    keep = ((line["l_shipdate"] > day) & (orders["o_orderdate"][order] < day)
            & (customer["c_mktsegment"][0][orders["o_custkey"][order] - 1]
               == DS.SEGMENTS.index("BUILDING")))
    want = made["oracle"].answer("q3", Q3)
    gaps = []
    for f in (np.float32, np.float64):
        rev = np.zeros(len(orders["o_orderkey"]), f)
        np.add.at(rev, order[keep],
                  line["l_extendedprice"][keep].astype(f)
                  * (f(1) - line["l_discount"][keep].astype(f)))
        rows = [(k, float(rev[np.searchsorted(orders["o_orderkey"], k)]), d, p)
                for k, _, d, p in want]
        worst = Worst()
        said = made["oracle"].check("q3", Q3, _result(rows), worst)
        gaps.append((said, worst.gap))
    (said32, gap32), (said64, gap64) = gaps
    assert said32 is not None and gap32 > 3 * RTOL
    assert said64 is None and gap64 < RTOL / 1000


PINNED_CRC32 = {
    "l_orderkey": 2090393506, "l_shipdate": 3671865479,
    "l_extendedprice": 2716974348, "l_discount": 1021609081,
    "o_orderkey": 1542981948, "o_custkey": 2156053821,
    "o_orderdate": 1950014683, "o_shippriority": 1643020006,
    "c_custkey": 1296902169, "c_mktsegment": 130122607,
}
# 4,952 of 650,000 orders, 0.76 %: dbgen's Q3 keeps 11,620 of 1.5 M at SF-1
PINNED_GROUPS = 4952
PINNED_Q3 = [
    (185348, 559119.9073, 9180, 0), (2390625, 546284.3658, 9173, 0),
    (891206, 512872.6213, 9199, 0), (676101, 500864.7062000001, 9199, 0),
    (348546, 488884.06049999996, 9167, 0),
    (112196, 484576.74030000006, 9195, 0),
    (1486245, 481931.05870000005, 9196, 0), (207073, 473141.6538, 9180, 0),
    (561698, 465899.4256, 9199, 0), (230627, 464623.0992, 9195, 0),
]
