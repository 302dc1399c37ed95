"""Operator drivers: ms a query of `collect_columns`' wall on the calling
thread that no other stage timer of that thread names (`query.other`, the
`query` span's self time): what of a query's wall is still dark.
None where the program has no such timer."""
from tpubench.readers import timer_per_query


def read(run):
    if "query.other" not in run.timings:
        return None
    s = timer_per_query(run, "query.other")
    return None if s is None else s * 1e3
