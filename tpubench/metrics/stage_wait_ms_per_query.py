"""Operator drivers: ms a query its thread waits for the stager to hand it a
batch (`pipeline.wait`, the consumer's `q.get()` in `exec/prefetch.py`).
Near the query wall, the stager (encode + ship) sets the pace; near zero,
the launch loop or the device does.  Summed over serve workers.
None where the program has no such timer."""
from tpubench.readers import timer_per_query


def read(run):
    if "pipeline.wait" not in run.timings:
        return None
    s = timer_per_query(run, "pipeline.wait")
    return None if s is None else s * 1e3
