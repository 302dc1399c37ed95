"""Operator drivers: the program's `join.build` timer per query of the
window, ms: collecting, indexing and shipping the join's build side.  0
where the build stays pinned and a query only probes it; None where the
window ran no join."""
from tpubench.readers import timer_per_query


def read(run):
    probed = any(run.counts.get(c) for c in ("join.probe.rows",
                                             "join.host_probe.rows"))
    s = timer_per_query(run, "join.build") if probed else None
    return None if s is None else s * 1e3
