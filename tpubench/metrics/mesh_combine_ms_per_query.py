"""Mesh: ms a query its thread spends enqueueing the collective combine
(`execute.collective_combine`: the `psum` of the shards' partial states;
the host's call, not the device's time, which the trace's `device_ops`
show).  None where the program has no such timer."""
from tpubench.readers import timer_per_query


def read(run):
    if "execute.collective_combine" not in run.timings:
        return None
    s = timer_per_query(run, "execute.collective_combine")
    return None if s is None else s * 1e3
