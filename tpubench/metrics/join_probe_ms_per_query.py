"""Operator drivers: the program's `join.probe` timer per query, ms: the
device probe of the join's resident build, a batch at a time (the call
that enqueues the launch, not the device's time)."""
from tpubench.readers import timer_per_query


def read(run):
    s = (timer_per_query(run, "join.probe")
         if "join.probe" in run.timings else None)
    return None if s is None else s * 1e3
