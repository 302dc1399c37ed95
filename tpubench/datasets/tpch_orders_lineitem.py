"""TPC-H `orders` and `lineitem` as clause 4.2.3 populates them, the seven
columns Q12 reads, made from a seed.

The shapes are the spec's.  `o_orderkey` is sparse: only the first 8 of
every 32 keys are used, so N orders span keys 1..4N (15 M orders at SF-10
span 1..60,000,000).  An order has 1..7 lines; `lineitem` is in dbgen's
order, clustered by `l_orderkey`.  `o_orderdate` is uniform over
1992-01-01..1998-08-02 (STARTDATE .. ENDDATE - 151 days), `l_shipdate` =
order date + 1..121, `l_commitdate` = order date + 30..90, `l_receiptdate`
= ship date + 1..30; `l_shipmode` is uniform over the 7 modes and
`o_orderpriority` over the 5 priorities.  Dates are int64 days since
1970-01-01: the engine has no date type, and Q12 compares two date columns
with each other.

`rows` counts lineitem; orders = rows / 4 (the mean of 1..7).  Orders come
in chunks of `CHUNK_ORDERS`, each from its own `default_rng([seed, chunk])`
stream and each owning exactly 4 lines an order (the last chunk takes
what is left of `rows`): the 1..7 draw of a chunk misses that total by
about +-1,000 lines in 1,000,000, and as many of its orders, chosen by the
same stream among those with room, gain or lose one line.  So the tables
do not depend on how many threads made them, and lineitem has `rows` rows
to the row.

The oracle is part of the data set and imports nothing of the engine:
numpy filters a chunk's lines by the two column comparisons, finds each
kept line's order by its key in the sorted `o_orderkey`, and counts by
(ship mode, order priority, year of receipt); Q12 for every pair of modes
and every year follows from that cube.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

# table -> column -> resident kind, in registration order; `rows` counts
# the first table
TABLES = {
    "lineitem": {"l_orderkey": "i64", "l_shipmode": "str", "l_shipdate": "i64",
                 "l_commitdate": "i64", "l_receiptdate": "i64"},
    "orders": {"o_orderkey": "i64", "o_orderpriority": "str"},
}
LINES_PER_ORDER = 4  # the mean of 1..7: orders = rows / 4
MAX_LINES = 7
CHUNK_ORDERS = 250_000  # 1,000,000 lines a chunk
MODES = ("REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL", "FOB")
PRIORITIES = ("1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED", "5-LOW")
EPOCH = np.datetime64("1970-01-01")
ORDER_DATE_LO = int((np.datetime64("1992-01-01") - EPOCH).astype(int))
ORDER_DATE_HI = int((np.datetime64("1998-08-02") - EPOCH).astype(int))
FIRST_YEAR, N_YEARS = 1992, 7  # a receipt date lies in 1992-01-03..1998-12-31


def day_number(date: str) -> int:
    return int((np.datetime64(date) - EPOCH).astype(int))


_YEAR_STARTS = np.array([day_number(f"{FIRST_YEAR + y}-01-01")
                         for y in range(N_YEARS + 1)], np.int64)


def order_count(rows: int) -> int:
    return max(rows // LINES_PER_ORDER, 1)


def order_keys(lo: int, hi: int) -> np.ndarray:
    """`o_orderkey` of the orders numbered lo..hi-1 (from 0): the first 8
    of every 32 keys, from 1."""
    i = np.arange(lo, hi, dtype=np.int64)
    return (i // 8) * 32 + i % 8 + 1


def _line_counts(rng, orders: int, lines: int) -> np.ndarray:
    """1..7 lines an order, drawn uniformly and then brought to `lines` in
    all: orders drawn among those with room gain (or lose) one line."""
    counts = rng.integers(1, MAX_LINES + 1, orders)
    while (miss := lines - int(counts.sum())) != 0:
        room = np.flatnonzero(counts < MAX_LINES if miss > 0 else counts > 1)
        take = rng.permutation(room)[: abs(miss)]
        counts[take] += 1 if miss > 0 else -1
    return counts


def _chunk(seed: int, index: int, orders: int, lines: int) -> dict:
    """The orders numbered from `index * CHUNK_ORDERS` and their lines."""
    rng = np.random.default_rng([seed, index])
    lo = index * CHUNK_ORDERS
    okey = order_keys(lo, lo + orders)
    odate = rng.integers(ORDER_DATE_LO, ORDER_DATE_HI + 1, orders)
    counts = _line_counts(rng, orders, lines)
    line_odate = np.repeat(odate, counts)
    ship = line_odate + rng.integers(1, 122, lines)
    return {
        "o_orderkey": okey,
        "o_orderpriority": rng.integers(0, len(PRIORITIES), orders,
                                        dtype=np.int32),
        "l_orderkey": np.repeat(okey, counts),
        "l_shipmode": rng.integers(0, len(MODES), lines, dtype=np.int32),
        "l_shipdate": ship,
        "l_commitdate": line_odate + rng.integers(30, 91, lines),
        "l_receiptdate": ship + rng.integers(1, 31, lines),
    }


def _cube(c: dict) -> np.ndarray:
    """One chunk's share of count[mode, priority, year of receipt] over the
    lines with commit < receipt and ship < commit, each joined to its order
    by key (a chunk's lines belong to the chunk's orders)."""
    keep = ((c["l_commitdate"] < c["l_receiptdate"])
            & (c["l_shipdate"] < c["l_commitdate"]))
    order = np.searchsorted(c["o_orderkey"], c["l_orderkey"][keep])
    if not np.array_equal(c["o_orderkey"][order], c["l_orderkey"][keep]):
        raise AssertionError("a line without its order")
    year = np.searchsorted(_YEAR_STARTS, c["l_receiptdate"][keep],
                           side="right") - 1
    cell = ((c["l_shipmode"][keep].astype(np.int64) * len(PRIORITIES)
             + c["o_orderpriority"][order]) * N_YEARS + year)
    return np.bincount(cell, minlength=len(MODES) * len(PRIORITIES) * N_YEARS)


def generate(seed: int, rows: int, threads: int = 8) -> dict:
    """{"tables": {table: {column: ndarray | (int32 codes, dictionary
    values)}}, "oracle": Oracle}, the chunks made, written into their
    place and folded into the oracle's cube on `threads` threads."""
    n_orders = order_count(rows)
    starts = range(0, n_orders, CHUNK_ORDERS)
    sizes = {"lineitem": rows, "orders": n_orders}
    probe = _chunk(seed, 0, 1, 1)
    tables = {t: {name: np.empty(sizes[t], probe[name].dtype) for name in cols}
              for t, cols in TABLES.items()}

    def work(i):
        o_lo, last = starts[i], i == len(starts) - 1
        o_n = min(CHUNK_ORDERS, n_orders - o_lo)
        l_lo = o_lo * LINES_PER_ORDER
        l_n = rows - l_lo if last else o_n * LINES_PER_ORDER
        c = _chunk(seed, i, o_n, l_n)
        for t, lo in (("lineitem", l_lo), ("orders", o_lo)):
            for name in TABLES[t]:
                tables[t][name][lo: lo + len(c[name])] = c[name]
        return _cube(c)

    with ThreadPoolExecutor(max_workers=threads) as pool:
        cube = np.sum(list(pool.map(work, range(len(starts)))), axis=0)
    tables["lineitem"]["l_shipmode"] = (tables["lineitem"]["l_shipmode"], MODES)
    tables["orders"]["o_orderpriority"] = (
        tables["orders"]["o_orderpriority"], PRIORITIES)
    return {"tables": tables, "oracle": Oracle(cube)}


def bind(template: str, params: dict) -> dict:
    """The fields a query template's text is formatted with."""
    if template == "q12":
        return {"mode1": params["mode1"], "mode2": params["mode2"],
                "date_lo": day_number(f"{params['year']}-01-01"),
                "date_hi": day_number(f"{params['year'] + 1}-01-01")}
    raise KeyError(f"tpch_orders_lineitem has no template {template!r}")


class Oracle:
    """Answers from the cube; `check` compares an engine result."""

    def __init__(self, cube: np.ndarray):
        self.cube = np.asarray(cube).reshape(
            len(MODES), len(PRIORITIES), N_YEARS)

    def arrays(self) -> dict:
        return {"cube": self.cube}

    @classmethod
    def from_arrays(cls, arrays) -> "Oracle":
        return cls(arrays["cube"])

    def answer(self, template: str, params: dict) -> list[tuple]:
        """Q12's rows before its CASE: (mode, priority, count).  The
        spec's `high_line_count` of a mode is the sum over 1-URGENT and
        2-HIGH, `low_line_count` the sum over the other three."""
        if template != "q12":
            raise KeyError(f"tpch_orders_lineitem has no template {template!r}")
        year = params["year"] - FIRST_YEAR
        return [(mode, prio, int(n))
                for mode in sorted({params["mode1"], params["mode2"]})
                for p, prio in enumerate(PRIORITIES)
                if (n := self.cube[MODES.index(mode), p, year])]

    def check(self, template: str, params: dict, result,
              worst=None) -> "str | None":
        """None where `result` (an engine ResultTable) holds the right
        rows, else what differs (Q12 has no float: `worst` sees none)."""
        from tpubench.check import diff_rows

        return diff_rows(result.to_rows(), self.answer(template, params),
                         worst=worst)
