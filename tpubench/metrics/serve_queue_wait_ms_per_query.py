"""Serving: ms a query between admission and a worker starting its group,
less the batching window (`serve.path.queue_wait`, from the ticket's stamps
in `Server._finish`): waiting for a worker behind earlier groups.
None where the program has no such timer."""
from tpubench.readers import timer_per_query


def read(run):
    if "serve.path.queue_wait" not in run.timings:
        return None
    s = timer_per_query(run, "serve.path.queue_wait")
    return None if s is None else s * 1e3
