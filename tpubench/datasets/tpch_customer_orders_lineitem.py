"""TPC-H `lineitem`, `orders` and `customer` as clause 4.2.3 populates them,
the ten columns Q3 (Shipping Priority) reads, made from a seed.

The shapes are the spec's.  `o_orderkey` is sparse (the first 8 of every 32
keys: N orders span 1..4N), an order has 1..7 lines, `lineitem` is in dbgen's
order, clustered by `l_orderkey`; `o_orderdate` is uniform over
1992-01-01..1998-08-02 and `l_shipdate` = order date + 1..121;
`o_shippriority` is 0; `c_custkey` is dense 1..C with C = orders / 10 (1.5 M
customers for 15 M orders), `c_mktsegment` uniform over the five segments;
`o_custkey` is uniform over the customer keys not divisible by 3 (a third of
the customers have no order); `l_discount` is 0.00..0.10 by 0.01.
`l_extendedprice` has no PART table to be priced from: it is uniform over
900.00..104,950.00, the domain `tpch_lineitem` assumes.  Dates are int64 days
since 1970-01-01: the engine has no date type.

`rows` counts lineitem; orders = rows / 4, customers = orders / 10.  Orders
come in chunks of `CHUNK_ORDERS`, each owning exactly 4 lines an order.  A
chunk's order dates, line counts and ship dates are drawn from
`default_rng([seed, chunk])` in the order `tpch_orders_lineitem` draws them,
so for one seed the two data sets' shared columns (`o_orderkey`, `l_orderkey`,
`l_shipdate`) are equal; what only this data set has comes from streams of
its own (`[seed, chunk, 1]`, customers `[seed, 0, 2]`).  So the tables do not
depend on how many threads made them.

The oracle is part of the data set and imports nothing of the engine: numpy
filters each table (segment, order date, ship date), finds each kept line's
order by its key in the sorted `o_orderkey` and each order's customer by its
key, sums `price * (1 - discount)` in float64 by order, orders by (revenue
descending, order date) and keeps the first eleven: ten to answer with, one
to show that no tie within the tolerance decides the tenth.  Q3 is answered
at its validation parameters only (`SEGMENT`, `DATE`).
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

# table -> column -> resident kind, in registration order; `rows` counts
# the first table
TABLES = {
    "lineitem": {"l_orderkey": "i64", "l_shipdate": "i64",
                 "l_extendedprice": "f64", "l_discount": "f64"},
    "orders": {"o_orderkey": "i64", "o_custkey": "i64", "o_orderdate": "i64",
               "o_shippriority": "i64"},
    "customer": {"c_custkey": "i64", "c_mktsegment": "str"},
}
LINES_PER_ORDER = 4  # the mean of 1..7: orders = rows / 4
ORDERS_PER_CUSTOMER = 10
MAX_LINES = 7
CHUNK_ORDERS = 250_000  # 1,000,000 lines a chunk
SEGMENTS = ("AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY", "HOUSEHOLD")
EPOCH = np.datetime64("1970-01-01")
ORDER_DATE_LO = int((np.datetime64("1992-01-01") - EPOCH).astype(int))
ORDER_DATE_HI = int((np.datetime64("1998-08-02") - EPOCH).astype(int))
# Q3's validation parameters (clause 2.4.3.3), the only ones answered
SEGMENT, DATE = "BUILDING", "1995-03-15"
LIMIT = 10
RTOL = 1e-9  # the comparison's, tpubench/check.py


def day_number(date: str) -> int:
    return int((np.datetime64(date) - EPOCH).astype(int))


def order_count(rows: int) -> int:
    return max(rows // LINES_PER_ORDER, 1)


def customer_count(rows: int) -> int:
    return max(order_count(rows) // ORDERS_PER_CUSTOMER, 3)


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


def _customers(seed: int, n: int) -> dict:
    rng = np.random.default_rng([seed, 0, 2])
    return {"c_custkey": np.arange(1, n + 1, dtype=np.int64),
            "c_mktsegment": rng.integers(0, len(SEGMENTS), n, dtype=np.int32)}


def _chunk(seed: int, index: int, orders: int, lines: int,
           customers: int) -> dict:
    """The orders numbered from `index * CHUNK_ORDERS` and their lines."""
    rng = np.random.default_rng([seed, index])
    lo = index * CHUNK_ORDERS
    okey = order_keys(lo, lo + orders)
    odate = rng.integers(ORDER_DATE_LO, ORDER_DATE_HI + 1, orders)
    counts = _line_counts(rng, orders, lines)
    ship = np.repeat(odate, counts) + rng.integers(1, 122, lines)
    own = np.random.default_rng([seed, index, 1])
    # the j-th customer key not divisible by 3 (from 0): 1 2 4 5 7 8 ...
    with_orders = customers - customers // 3
    j = own.integers(0, with_orders, orders)
    return {
        "o_orderkey": okey,
        "o_custkey": j + j // 2 + 1,
        "o_orderdate": odate,
        "o_shippriority": np.zeros(orders, np.int64),
        "l_orderkey": np.repeat(okey, counts),
        "l_shipdate": ship,
        "l_extendedprice": np.round(own.uniform(900.0, 104950.0, lines), 2),
        "l_discount": own.integers(0, 11, lines) / 100.0,
    }


def _top(okey, revenue, odate, prio) -> tuple:
    """The first `LIMIT + 1` of some orders by (revenue descending, order
    date), as four arrays."""
    first = np.lexsort((odate, -revenue))[: LIMIT + 1]
    return okey[first], revenue[first], odate[first], prio[first]


def _chunk_top(c: dict, customer: dict) -> tuple:
    """One chunk's share of Q3's answer: (how many orders it keeps, its
    first eleven).  A chunk's lines belong to the chunk's orders."""
    date = day_number(DATE)
    keep = c["l_shipdate"] > date
    order = np.searchsorted(c["o_orderkey"], c["l_orderkey"][keep])
    if not np.array_equal(c["o_orderkey"][order], c["l_orderkey"][keep]):
        raise AssertionError("a line without its order")
    buyer = np.searchsorted(customer["c_custkey"], c["o_custkey"])
    if not np.array_equal(customer["c_custkey"][buyer], c["o_custkey"]):
        raise AssertionError("an order without its customer")
    order_ok = ((c["o_orderdate"] < date)
                & (customer["c_mktsegment"][buyer] == SEGMENTS.index(SEGMENT)))
    line_ok = order_ok[order]
    order = order[line_ok]
    price = c["l_extendedprice"][keep][line_ok]
    discount = c["l_discount"][keep][line_ok]
    n = len(c["o_orderkey"])
    revenue = np.bincount(order, weights=price * (1 - discount), minlength=n)
    live = np.flatnonzero(np.bincount(order, minlength=n))
    return len(live), _top(c["o_orderkey"][live], revenue[live],
                           c["o_orderdate"][live], c["o_shippriority"][live])


def generate(seed: int, rows: int, threads: int = 8) -> dict:
    """{"tables": {table: {column: ndarray | (int32 codes, dictionary
    values)}}, "oracle": Oracle}, the chunks made, written into their
    place and folded into the oracle's first eleven on `threads` threads."""
    n_orders, n_customers = order_count(rows), customer_count(rows)
    starts = range(0, n_orders, CHUNK_ORDERS)
    sizes = {"lineitem": rows, "orders": n_orders, "customer": n_customers}
    customer = _customers(seed, n_customers)
    probe = {**_chunk(seed, 0, 1, 1, n_customers), **customer}
    tables = {t: {name: np.empty(sizes[t], probe[name].dtype) for name in cols}
              for t, cols in TABLES.items()}
    tables["customer"] = dict(customer)

    def work(i):
        o_lo, last = starts[i], i == len(starts) - 1
        o_n = min(CHUNK_ORDERS, n_orders - o_lo)
        l_lo = o_lo * LINES_PER_ORDER
        l_n = rows - l_lo if last else o_n * LINES_PER_ORDER
        c = _chunk(seed, i, o_n, l_n, n_customers)
        for t, lo in (("lineitem", l_lo), ("orders", o_lo)):
            for name in TABLES[t]:
                tables[t][name][lo: lo + len(c[name])] = c[name]
        return _chunk_top(c, customer)

    with ThreadPoolExecutor(max_workers=threads) as pool:
        shares = list(pool.map(work, range(len(starts))))
    groups = sum(n for n, _ in shares)
    top = _top(*(np.concatenate([s[k] for _, s in shares]) for k in range(4)))
    oracle = Oracle(groups, *top)
    oracle.require_no_tie()
    tables["customer"]["c_mktsegment"] = (customer["c_mktsegment"], SEGMENTS)
    return {"tables": tables, "oracle": oracle}


def bind(template: str, params: dict) -> dict:
    """The fields a query template's text is formatted with."""
    if template == "q3":
        return {"segment": params["segment"],
                "date": day_number(params["date"])}
    raise KeyError(f"tpch_customer_orders_lineitem has no template {template!r}")


class Oracle:
    """Q3's first eleven rows at the validation parameters; `check`
    compares an engine result, row by row in the spec's order."""

    def __init__(self, groups, okey, revenue, odate, prio):
        self.groups = int(groups)  # orders with a kept line, before LIMIT
        self.okey = np.asarray(okey, np.int64)
        self.revenue = np.asarray(revenue, np.float64)
        self.odate = np.asarray(odate, np.int64)
        self.prio = np.asarray(prio, np.int64)

    def arrays(self) -> dict:
        return {"groups": np.int64(self.groups), "okey": self.okey,
                "revenue": self.revenue, "odate": self.odate,
                "prio": self.prio}

    @classmethod
    def from_arrays(cls, arrays) -> "Oracle":
        return cls(arrays["groups"], arrays["okey"], arrays["revenue"],
                   arrays["odate"], arrays["prio"])

    def require_no_tie(self) -> None:
        """No two of the first eleven revenues within the comparison's
        tolerance of each other: an answer inside the tolerance cannot
        put two rows in another order, nor another row tenth."""
        gap = -np.diff(self.revenue)
        if (gap <= 4 * RTOL * self.revenue[:-1]).any():
            raise AssertionError(
                "two of Q3's first eleven revenues tie within the tolerance: "
                f"{self.revenue.tolist()}")

    def answer(self, template: str, params: dict) -> list[tuple]:
        """The ten rows (l_orderkey, revenue, o_orderdate, o_shippriority)
        in the spec's order."""
        if (template != "q3" or params.get("segment") != SEGMENT
                or params.get("date") != DATE):
            raise KeyError(
                f"tpch_customer_orders_lineitem answers q3 at {SEGMENT}, "
                f"{DATE} only, not {template!r} {params!r}")
        return [(int(k), float(r), int(d), int(p))
                for k, r, d, p in zip(self.okey[:LIMIT], self.revenue[:LIMIT],
                                      self.odate[:LIMIT], self.prio[:LIMIT])]

    def check(self, template: str, params: dict, result,
              worst=None) -> "str | None":
        """None where `result` (an engine ResultTable) holds the ten rows
        in order, else what differs.  The engine names its columns; the
        aggregate is the one that is not a key."""
        from tpubench.check import diff_rows

        want = self.answer(template, params)
        names = result.schema.names()
        keys = ("l_orderkey", "o_orderdate", "o_shippriority")
        if sorted(n for n in names if n in keys) != sorted(keys) or len(
                names) != 4:
            return f"columns {names}, not Q3's three keys and its revenue"
        at = [names.index("l_orderkey"),
              next(i for i, n in enumerate(names) if n not in keys),
              names.index("o_orderdate"), names.index("o_shippriority")]
        got = [tuple(row[i] for i in at) for row in result.to_rows()]
        if len(got) != len(want):
            return f"{len(got)} rows, oracle has {len(want)}"
        for i, (g, w) in enumerate(zip(got, want)):
            if (bad := diff_rows([g], [w], worst=worst)):
                return f"row {i}: {bad}"
        return None
