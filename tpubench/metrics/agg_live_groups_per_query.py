"""Operator drivers: the groups an aggregate over device-born numeric keys
holds at the end (`aggregate.device_key.groups`), per query: the groups
that rows kept by the predicate make, which is what its state and its
answer are sized by.  None in a cell without the device key step."""


def read(run):
    groups = run.counts.get("aggregate.device_key.groups", 0)
    if not groups or not run.queries:
        return None
    return groups / run.queries
