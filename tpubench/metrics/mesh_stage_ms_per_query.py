"""Mesh: ms a query the staging thread spends on its rounds (`mesh.stage`,
one interval a round: the host predicate of the round's shard batches, each
mask's encode and put onto its shard's device, group ids where the key set
is new).  Near the query wall, staging sets the pace.  None where the
program has no such timer."""
from tpubench.readers import timer_per_query


def read(run):
    if "mesh.stage" not in run.timings:
        return None
    s = timer_per_query(run, "mesh.stage")
    return None if s is None else s * 1e3
