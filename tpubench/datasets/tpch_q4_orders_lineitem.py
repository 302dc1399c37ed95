"""TPC-H `orders` and `lineitem` as clause 4.2.3 populates them, the six
columns Q4 (Order Priority Checking) reads, made from a seed.

The tables are `tpch_orders_lineitem`'s, made by its own code, imported
and not edited: for one seed `l_orderkey`, `l_commitdate`, `l_receiptdate`,
`o_orderkey` and `o_orderpriority` are that data set's (a test pins it).
`o_orderdate` is the first draw of that data set's chunk stream
(`default_rng([seed, chunk])`), uniform over 1992-01-01..1998-08-02, which
its chunk makes, derives the line dates from, and does not return: it is
drawn again here from the same stream.  Commit is order date + 30..90
days, receipt order date + 1..121 + 1..30, so about 63 % of the lines have
`l_commitdate < l_receiptdate`; a chunk's lines belong to the chunk's
orders, and lineitem is clustered by `l_orderkey`.

The oracle is part of the data set and imports nothing of the engine: for
each chunk numpy flags the orders that have a line with commit < receipt
(each such line's order found by its key in the sorted `o_orderkey`), and
counts them by (priority, month of the order date).  Q4 at any DATE (the
first of a month, 1993-01 .. 1997-10) is the sum of three months of that
cube.
"""

from __future__ import annotations

import importlib.util
import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np


def _sibling():
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "tpch_orders_lineitem.py")
    spec = importlib.util.spec_from_file_location(
        "tpubench.datasets.tpch_orders_lineitem", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


SIBLING = _sibling()

# table -> column -> resident kind, in registration order; `rows` counts
# the first table
TABLES = {
    "lineitem": {"l_orderkey": "i64", "l_commitdate": "i64",
                 "l_receiptdate": "i64"},
    "orders": {"o_orderkey": "i64", "o_orderdate": "i64",
               "o_orderpriority": "str"},
}
PRIORITIES = SIBLING.PRIORITIES
FIRST_MONTH = np.datetime64("1992-01", "M")
N_MONTHS = 80  # an order date lies in 1992-01 .. 1998-08
WINDOW_MONTHS = 3  # DATE .. DATE + INTERVAL '3' MONTH


def month_of(days: np.ndarray) -> np.ndarray:
    """Months since 1992-01 of day numbers (days since 1970-01-01)."""
    months = (SIBLING.EPOCH + days.astype("timedelta64[D]")).astype(
        "datetime64[M]")
    return (months - FIRST_MONTH).astype(np.int64)


def _chunk(seed: int, index: int, orders: int, lines: int) -> dict:
    """The sibling's chunk and the order dates it draws first."""
    c = SIBLING._chunk(seed, index, orders, lines)
    c["o_orderdate"] = np.random.default_rng([seed, index]).integers(
        SIBLING.ORDER_DATE_LO, SIBLING.ORDER_DATE_HI + 1, orders)
    return c


def _cube(c: dict) -> np.ndarray:
    """One chunk's count[priority, month of order date] of the orders
    with a line whose commit date is before its receipt date."""
    late = c["l_commitdate"] < c["l_receiptdate"]
    order = np.searchsorted(c["o_orderkey"], c["l_orderkey"][late])
    if not np.array_equal(c["o_orderkey"][order], c["l_orderkey"][late]):
        raise AssertionError("a line without its order")
    flagged = np.zeros(len(c["o_orderkey"]), bool)
    flagged[order] = True
    cell = (c["o_orderpriority"][flagged].astype(np.int64) * N_MONTHS
            + month_of(c["o_orderdate"][flagged]))
    return np.bincount(cell, minlength=len(PRIORITIES) * N_MONTHS)


def generate(seed: int, rows: int, threads: int = 8) -> dict:
    """{"tables": {table: {column: ndarray | (int32 codes, dictionary
    values)}}, "oracle": Oracle}, the chunks made, written into their
    place and folded into the oracle's cube on `threads` threads."""
    n_orders = SIBLING.order_count(rows)
    starts = range(0, n_orders, SIBLING.CHUNK_ORDERS)
    sizes = {"lineitem": rows, "orders": n_orders}
    tables = {t: {name: np.empty(sizes[t], np.int32 if kind == "str"
                                 else np.int64)
                  for name, kind in cols.items()}
              for t, cols in TABLES.items()}

    def work(i):
        o_lo, last = starts[i], i == len(starts) - 1
        o_n = min(SIBLING.CHUNK_ORDERS, n_orders - o_lo)
        l_lo = o_lo * SIBLING.LINES_PER_ORDER
        l_n = rows - l_lo if last else o_n * SIBLING.LINES_PER_ORDER
        c = _chunk(seed, i, o_n, l_n)
        for t, lo in (("lineitem", l_lo), ("orders", o_lo)):
            for name in TABLES[t]:
                tables[t][name][lo: lo + len(c[name])] = c[name]
        return _cube(c)

    with ThreadPoolExecutor(max_workers=threads) as pool:
        cube = np.sum(list(pool.map(work, range(len(starts)))), axis=0)
    tables["orders"]["o_orderpriority"] = (
        tables["orders"]["o_orderpriority"], PRIORITIES)
    return {"tables": tables, "oracle": Oracle(cube)}


def window(date: str) -> tuple:
    """(first day, day after the last) of DATE .. DATE + 3 months, as
    day numbers; DATE is the first of a month."""
    first = np.datetime64(date, "D")
    month = first.astype("datetime64[M]")
    if month.astype("datetime64[D]") != first:
        raise ValueError(f"Q4's DATE is the first of a month, not {date}")
    end = (month + WINDOW_MONTHS).astype("datetime64[D]")
    return SIBLING.day_number(str(first)), SIBLING.day_number(str(end))


def bind(template: str, params: dict) -> dict:
    """The fields a query template's text is formatted with."""
    if template == "q4":
        date_lo, date_hi = window(params["date"])
        return {"date_lo": date_lo, "date_hi": date_hi}
    raise KeyError(f"tpch_q4_orders_lineitem has no template {template!r}")


class Oracle:
    """Answers from the cube; `check` compares an engine result."""

    def __init__(self, cube: np.ndarray):
        self.cube = np.asarray(cube).reshape(len(PRIORITIES), N_MONTHS)

    def arrays(self) -> dict:
        return {"cube": self.cube}

    @classmethod
    def from_arrays(cls, arrays) -> "Oracle":
        return cls(arrays["cube"])

    def answer(self, template: str, params: dict) -> list[tuple]:
        """Q4's rows, (o_orderpriority, order_count), by priority."""
        if template != "q4":
            raise KeyError(f"tpch_q4_orders_lineitem has no template {template!r}")
        first = int(month_of(np.array([window(params["date"])[0]]))[0])
        counts = self.cube[:, first: first + WINDOW_MONTHS].sum(axis=1)
        return [(prio, int(n)) for prio, n in zip(PRIORITIES, counts) if n]

    def check(self, template: str, params: dict, result,
              worst=None) -> "str | None":
        """None where `result` (an engine ResultTable) holds Q4's rows in
        the spec's order, else what differs.  The priority is found by
        name, the count is the other column; every value is exact (Q4
        has no float: `worst` sees none)."""
        names = result.schema.names()
        if len(names) != 2 or "o_orderpriority" not in names:
            return f"columns {names}, not o_orderpriority and a count"
        at = names.index("o_orderpriority")
        got = [(row[at], row[1 - at]) for row in result.to_rows()]
        want = self.answer(template, params)
        if got != want:
            return f"rows {got}, oracle {want}"
        return None
