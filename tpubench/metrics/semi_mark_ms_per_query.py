"""Operator drivers: the program's `join.semi_mark` timer per query, ms:
the marks of a semi join's membership table, a build batch at a time (the
call that enqueues the launch, not the device's time).  None on an engine
or in a cell without a semi join marked on the device."""
from tpubench.readers import timer_per_query


def read(run):
    s = (timer_per_query(run, "join.semi_mark")
         if "join.semi_mark" in run.timings else None)
    return None if s is None else s * 1e3
