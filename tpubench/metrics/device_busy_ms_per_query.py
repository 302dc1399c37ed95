"""Kernels: device busy time (union of the trace's device-op intervals)
per query of the traced window, ms."""
from tpubench.readers import per_query


def read(run):
    if not run.trace:
        return None
    s = per_query(run, run.trace["busy_s"])
    return None if s is None else s * 1e3
