"""The h2oai/db-benchmark group-by table `G1_<N>_<K>_0_0`, from a seed.

As `_data/groupby-datagen.R` makes it with no NAs and no sorting: `id1`,
`id2` are "id%03d" over K values, `id3` "id%010d" over N/K values, `id4`,
`id5` integers 1..K, `id6` 1..N/K, `v1` 1..5, `v2` 1..15, `v3` uniform
0..100 rounded to 6 decimals; all drawn uniformly with replacement.
Integers are int64 (the source's are 32-bit; the engine's SUM of them is
64-bit either way).  Rows come in chunks, each from its own
`default_rng([seed, chunk])` stream.

The oracle is plain numpy on the same arrays and imports nothing of the
engine.  `QUESTIONS` holds the questions of the source's group-by
scripts that a cell sends (a later data set module adds the others with
their templates); which of them a cell sends is the traffic file's
business.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

TABLES = {"x": {
    "id1": "str", "id2": "str", "id3": "str",
    "id4": "i64", "id5": "i64", "id6": "i64",
    "v1": "i64", "v2": "i64", "v3": "f64",
}}
KINDS = TABLES["x"]
CHUNK = 1_000_000
K = 100

# question -> (group keys, ((aggregate, column), ...)), in select order
QUESTIONS = {
    "q1": (("id1",), (("sum", "v1"),)),
    "q2": (("id1", "id2"), (("sum", "v1"),)),
    "q3": (("id3",), (("sum", "v1"), ("mean", "v3"))),
    "q5": (("id6",), (("sum", "v1"), ("sum", "v2"), ("sum", "v3"))),
}


def _domains(rows: int) -> dict:
    """Number of distinct values each key column can take."""
    big = max(rows // K, 1)
    return {"id1": K, "id2": K, "id3": big, "id4": K, "id5": K, "id6": big}


def _chunk(seed: int, index: int, n: int, rows: int) -> dict:
    rng = np.random.default_rng([seed, index])
    dom = _domains(rows)
    c = {k: rng.integers(0, dom[k], n, dtype=np.int32)
         for k in ("id1", "id2", "id3")}
    for k in ("id4", "id5", "id6"):
        c[k] = rng.integers(1, dom[k] + 1, n, dtype=np.int64)
    c["v1"] = rng.integers(1, 6, n, dtype=np.int64)
    c["v2"] = rng.integers(1, 16, n, dtype=np.int64)
    c["v3"] = np.round(rng.uniform(0.0, 100.0, n), 6)
    return c


def generate(seed: int, rows: int, threads: int = 8) -> dict:
    """{"tables": {"x": {column: ndarray | (int32 codes, dictionary
    values)}}, "oracle": Oracle}; string code c stands for "id%0Nd" % (c + 1)."""
    starts = range(0, rows, CHUNK)
    probe = _chunk(seed, 0, 1, rows)
    cols = {name: np.empty(rows, probe[name].dtype) for name in KINDS}

    def work(i):
        lo = starts[i]
        for name, part in _chunk(seed, i, min(CHUNK, rows - lo), rows).items():
            cols[name][lo: lo + len(part)] = part

    with ThreadPoolExecutor(max_workers=threads) as pool:
        list(pool.map(work, range(len(starts))))
    oracle = Oracle(dict(cols), rows)
    dom = _domains(rows)
    for name, fmt in (("id1", "id%03d"), ("id2", "id%03d"),
                      ("id3", "id%010d")):
        cols[name] = (cols[name],
                      tuple(fmt % (i + 1) for i in range(dom[name])))
    return {"tables": {"x": cols}, "oracle": oracle}


def bind(template: str, params: dict) -> dict:
    if template not in QUESTIONS:
        raise KeyError(f"h2o_g1 has no template {template!r}")
    return {}


def _id_codes(col) -> np.ndarray:
    """"id007" -> 6: the 0-based code of an id string column, by digits
    (every value of a column has the same width)."""
    b = np.asarray(col, dtype="S")
    w = b.dtype.itemsize
    digits = b.view(np.uint8).reshape(len(b), w)[:, 2:].astype(np.int64) - 48
    return digits @ (10 ** np.arange(w - 3, -1, -1, dtype=np.int64)) - 1


class Oracle:
    """Group-by answers by numpy on the generated arrays: the group of a
    row is its keys' mixed-radix number; sums are `np.bincount` in f64
    (exact for the integer columns at these sizes)."""

    def __init__(self, columns: dict, rows: int):
        self.c = columns
        self.dom = _domains(rows)
        self._answers: dict = {}

    def _zero_based(self, name: str) -> np.ndarray:
        col = self.c[name].astype(np.int64)
        return col if KINDS[name] == "str" else col - 1

    def answer(self, template: str, params: dict = None) -> tuple:
        """(key columns as 0-based codes, value columns), one row per
        group that has rows."""
        if template in self._answers:
            return self._answers[template]
        keys, aggs = QUESTIONS[template]
        gid = np.zeros(len(self.c["v1"]), np.int64)
        for k in keys:
            gid = gid * self.dom[k] + self._zero_based(k)
        space = int(np.prod([self.dom[k] for k in keys]))
        count = np.bincount(gid, minlength=space)
        ids = pick = np.flatnonzero(count)
        key_cols = []
        for k in reversed(keys):
            key_cols.append(ids % self.dom[k])
            ids = ids // self.dom[k]
        vals = []
        for fn, col in aggs:
            total = np.bincount(gid, weights=self.c[col], minlength=space)[pick]
            if fn == "mean":
                vals.append(total / count[pick])
            elif KINDS[col] == "i64":
                vals.append(np.rint(total).astype(np.int64))
            else:
                vals.append(total)
        out = (key_cols[::-1], vals)
        self._answers[template] = out
        return out

    def check(self, template: str, params: dict, result,
              worst=None) -> "str | None":
        """None where `result` (an engine ResultTable) holds the right
        groups and aggregates, else what differs; `worst` (`check.Worst`)
        is shown the float values' gaps."""
        from tpubench.check import diff_columns

        keys, _ = QUESTIONS[template]
        want_keys, want_vals = self.answer(template)
        got_keys = []
        for i, k in enumerate(keys):
            col = result.columns[i]
            if len(col) == 0:
                got_keys.append(np.zeros(0, np.int64))
            elif KINDS[k] == "str":
                got_keys.append(_id_codes(col))
            else:
                got_keys.append(np.asarray(col, np.int64) - 1)
        got_vals = [np.asarray(c) for c in result.columns[len(keys):]]
        if len(got_vals) != len(want_vals):
            return f"{len(got_vals)} value columns, oracle has {len(want_vals)}"
        return diff_columns(got_keys, got_vals, want_keys, want_vals,
                            worst=worst)
