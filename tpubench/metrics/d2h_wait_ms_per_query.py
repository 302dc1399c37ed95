"""D2H: ms a query the host blocks for device results (`d2h.wait` in
`_PendingCompact.resolve`): the device finishing its queue, then the copy.
None where the program has no such timer."""
from tpubench.readers import timer_per_query


def read(run):
    if "d2h.wait" not in run.timings:
        return None
    s = timer_per_query(run, "d2h.wait")
    return None if s is None else s * 1e3
