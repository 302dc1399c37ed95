"""TPC-H `lineitem`, the seven columns Q1 and Q6 read, made from a seed.

The value domains are those of the repo's earlier generator
(`benchmarks/data.lineitem_parquet`, seed fixed at 42 there): quantity a
whole number 1..50, discount 0.00..0.10, tax 0.00..0.08, price uniform
900.00..104,950.00, ship date uniform over 2,526 days from 1992-01-02,
return flag and line status derived from the ship date so that Q1 has
the spec's four groups.  Q1 at DELTA = 90 keeps ~96 % of the rows and Q6
~1.9 %, as the spec's do.  Rows come in chunks of `CHUNK`, each from its
own `default_rng([seed, chunk])` stream, so the table does not depend on
how many threads made it.

The oracle is part of the data set and imports nothing of the engine:
one numpy pass over each chunk fills two small cubes from which Q1 for
every DELTA and Q6 for every (DATE, DISCOUNT, QUANTITY) follow.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

# table -> column -> resident kind ("str": dictionary codes, int32 on the
# device), in registration order; `rows` counts the first table
TABLES = {"lineitem": {
    "l_returnflag": "str", "l_linestatus": "str", "l_quantity": "f64",
    "l_extendedprice": "f64", "l_discount": "f64", "l_tax": "f64",
    "l_shipdate": "str",
}}
CHUNK = 1_000_000
N_DATES = 2526  # 1992-01-02 .. 1998-12-01
BASE_DATE = np.datetime64("1992-01-02")
END_DATE = np.datetime64("1998-12-01")  # Q1 counts DELTA days back from here
FLAGS = ("A", "N", "R")
STATUSES = ("F", "O")
N_GROUPS = len(FLAGS) * len(STATUSES)
N_YEARS = 7  # 1992..1998
N_DISC = 11  # discount in hundredths, 0..10
N_QTY = 51  # quantity 1..50
Q1_MEASURES = 6  # qty, price, disc_price, charge, discount, count

_DATES = BASE_DATE + np.arange(N_DATES)
DATE_STRINGS = tuple(str(d) for d in _DATES)
_YEAR_OF_DAY = (_DATES.astype("datetime64[Y]").astype(int) + 1970 - 1992
                ).astype(np.int64)


def _chunk(seed: int, index: int, n: int) -> dict:
    rng = np.random.default_rng([seed, index])
    ship = rng.integers(0, N_DATES, n, dtype=np.int32)
    # returns only for old orders, as in TPC-H: old -> A/R, recent -> N
    flag = np.where(ship < N_DATES // 2,
                    rng.integers(0, 2, n, dtype=np.int32) * 2,
                    np.int32(1)).astype(np.int32)
    status = (ship >= N_DATES * 5 // 8).astype(np.int32)  # F then O
    return {
        "l_returnflag": flag,
        "l_linestatus": status,
        "l_quantity": np.floor(rng.uniform(1, 51, n)),
        "l_extendedprice": np.round(rng.uniform(900.0, 104950.0, n), 2),
        "l_discount": rng.integers(0, 11, n) / 100.0,
        "l_tax": rng.integers(0, 9, n) / 100.0,
        "l_shipdate": ship,
    }


def _cubes(c: dict) -> tuple:
    """One chunk's share of the Q1 cube [group, day, measure] and the Q6
    cube [year, discount, quantity] of sum(price * discount)."""
    qty, price = c["l_quantity"], c["l_extendedprice"]
    disc, tax = c["l_discount"], c["l_tax"]
    cell = (c["l_returnflag"].astype(np.int64) * len(STATUSES)
            + c["l_linestatus"]) * N_DATES + c["l_shipdate"]
    n1 = N_GROUPS * N_DATES
    disc_price = price * (1 - disc)
    q1 = np.stack([
        np.bincount(cell, weights=w, minlength=n1)
        for w in (qty, price, disc_price, disc_price * (1 + tax), disc, None)
    ], axis=-1)
    cell6 = ((_YEAR_OF_DAY[c["l_shipdate"]] * N_DISC
              + np.rint(disc * 100).astype(np.int64)) * N_QTY
             + qty.astype(np.int64))
    q6 = np.bincount(cell6, weights=price * disc,
                     minlength=N_YEARS * N_DISC * N_QTY)
    return q1, q6


def generate(seed: int, rows: int, threads: int = 8) -> dict:
    """{"tables": {"lineitem": {column: ndarray | (int32 codes, dictionary
    values)}}, "oracle": Oracle}.  Chunks are made, written into their place and
    folded into the oracle's cubes on `threads` threads (numpy releases
    the GIL in all three)."""
    starts = range(0, rows, CHUNK)
    probe = _chunk(seed, 0, 1)
    cols = {name: np.empty(rows, probe[name].dtype)
            for name in TABLES["lineitem"]}

    def work(i):
        lo = starts[i]
        c = _chunk(seed, i, min(CHUNK, rows - lo))
        for name, part in c.items():
            cols[name][lo: lo + len(part)] = part
        return _cubes(c)

    with ThreadPoolExecutor(max_workers=threads) as pool:
        cubes = list(pool.map(work, range(len(starts))))
    for name, values in (("l_returnflag", FLAGS), ("l_linestatus", STATUSES),
                         ("l_shipdate", DATE_STRINGS)):
        cols[name] = (cols[name], values)
    q1 = np.sum([c[0] for c in cubes], axis=0)
    q6 = np.sum([c[1] for c in cubes], axis=0)
    return {"tables": {"lineitem": cols}, "oracle": Oracle(q1, q6)}


def bind(template: str, params: dict) -> dict:
    """The fields a query template's text is formatted with."""
    if template == "q1":
        return {"cutoff": str(END_DATE - np.timedelta64(params["delta"], "D"))}
    if template == "q6":
        d = params["discount_pct"]
        return {
            "date_lo": f"{params['year']}-01-01",
            "date_hi": f"{params['year'] + 1}-01-01",
            "disc_lo": f"{(d - 1) / 100:.2f}",
            "disc_hi": f"{(d + 1) / 100:.2f}",
            "quantity": params["quantity"],
        }
    raise KeyError(f"tpch_lineitem has no template {template!r}")


class Oracle:
    """Answers from the cubes; `check` compares an engine result."""

    def __init__(self, q1: np.ndarray, q6: np.ndarray):
        self.q1 = q1.reshape(N_GROUPS, N_DATES, Q1_MEASURES)
        self.q6 = q6.reshape(N_YEARS, N_DISC, N_QTY)

    def arrays(self) -> dict:
        return {"q1": self.q1, "q6": self.q6}

    @classmethod
    def from_arrays(cls, arrays) -> "Oracle":
        return cls(arrays["q1"], arrays["q6"])

    def answer(self, template: str, params: dict) -> list[tuple]:
        if template == "q1":
            cutoff = int((END_DATE - np.timedelta64(params["delta"], "D")
                          - BASE_DATE).astype(int))
            out = []
            for g in range(N_GROUPS):
                m = self.q1[g, : cutoff + 1].sum(axis=0)
                n = int(round(m[5]))
                if n:
                    out.append((FLAGS[g // len(STATUSES)],
                                STATUSES[g % len(STATUSES)],
                                m[0], m[1], m[2], m[3],
                                m[0] / n, m[1] / n, m[4] / n, n))
            return out
        if template == "q6":
            d = params["discount_pct"]
            cube = self.q6[params["year"] - 1992,
                           max(d - 1, 0): d + 2, : params["quantity"]]
            return [(float(cube.sum()),)]
        raise KeyError(f"tpch_lineitem has no template {template!r}")

    def check(self, template: str, params: dict, result,
              worst=None) -> "str | None":
        """None where `result` (an engine ResultTable) holds the right
        rows, else what differs; `worst` (`check.Worst`) is shown the
        float values' gaps."""
        from tpubench.check import diff_rows

        return diff_rows(result.to_rows(), self.answer(template, params),
                         worst=worst)
