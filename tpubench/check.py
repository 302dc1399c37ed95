"""The comparison that decides `correct`.

Group keys, row counts and integers are exact.  Sums and averages compare
at rtol 1e-9: f64 is f32-pair software on the TPU and the device's
reduction order differs from numpy's (the reason `chip_smoke.py:31-33`
gives for the same tolerance); a result computed in f32 would miss it by
five orders of magnitude.
"""

from __future__ import annotations

import numpy as np

RTOL = 1e-9


class Worst:
    """The widest relative gap |got - oracle| / |oracle| among the float
    values that the comparisons handed this object saw: the number a run
    reports beside `RTOL` (an oracle value of 0 has to be met exactly and
    gives no gap)."""

    def __init__(self):
        self.gap = 0.0

    def see(self, gap: float) -> None:
        self.gap = max(self.gap, float(gap))


def diff_rows(got: list, want: list, rtol: float = RTOL,
              worst: "Worst | None" = None) -> "str | None":
    """Compare two small lists of row tuples, in any order.  None where
    they agree, else the first difference."""
    got, want = sorted(got), sorted(want)
    if len(got) != len(want):
        return f"{len(got)} rows, oracle has {len(want)}"
    for g, w in zip(got, want):
        if len(g) != len(w):
            return f"row widths differ: {g} vs {w}"
        for gv, wv in zip(g, w):
            if isinstance(gv, float) or isinstance(wv, float):
                if worst is not None and gv is not None and wv:
                    worst.see(abs(gv - wv) / abs(wv))
                if gv is None or not (np.isfinite(gv)
                                      and abs(gv - wv) <= rtol * abs(wv)):
                    return f"{gv!r} vs oracle {wv!r} in {g} vs {w}"
            elif gv != wv:
                return f"{gv!r} != {wv!r} in {g} vs {w}"
    return None


def diff_columns(got_keys: list, got_vals: list, want_keys: list,
                 want_vals: list, rtol: float = RTOL,
                 worst: "Worst | None" = None) -> "str | None":
    """The same comparison on whole columns, for results of many rows.
    Keys are integer arrays (a data set turns its string keys into
    codes first); both sides are put in key order and compared."""
    n = len(want_keys[0])
    if any(len(c) != n for c in want_keys + want_vals):
        raise ValueError("oracle columns differ in length")
    if any(len(c) != len(got_keys[0]) for c in got_keys + got_vals):
        return "result columns differ in length"
    if len(got_keys[0]) != n:
        return f"{len(got_keys[0])} rows, oracle has {n}"
    g_ord = np.lexsort(got_keys[::-1])
    w_ord = np.lexsort(want_keys[::-1])
    for i, (g, w) in enumerate(zip(got_keys, want_keys)):
        if not np.array_equal(np.asarray(g)[g_ord], np.asarray(w)[w_ord]):
            return f"key column {i} differs"
    for i, (g, w) in enumerate(zip(got_vals, want_vals)):
        g, w = np.asarray(g)[g_ord], np.asarray(w)[w_ord]
        if np.issubdtype(w.dtype, np.floating):
            gap, size = np.abs(g - w), np.abs(w)
            if worst is not None and size.any():
                worst.see(np.max(gap[size > 0] / size[size > 0]))
            bad = ~(np.isfinite(g) & (gap <= rtol * size))
        else:
            bad = g.astype(np.int64) != w.astype(np.int64)
        if bad.any():
            j = int(np.flatnonzero(bad)[0])
            return (f"value column {i}: {g[j]!r} vs oracle {w[j]!r} "
                    f"({int(bad.sum())} rows differ)")
    return None
