"""Mesh: ms a query the launching thread spends making a staged round's
mesh arrays of its shards' single-device arrays (`mesh.assemble`, one
interval a round in `PartitionedAggregateRelation.accumulate`): on the
query's own thread, so it adds to the request.  None where the program has
no such timer."""
from tpubench.readers import timer_per_query


def read(run):
    if "mesh.assemble" not in run.timings:
        return None
    s = timer_per_query(run, "mesh.assemble")
    return None if s is None else s * 1e3
