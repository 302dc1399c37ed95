"""Operator drivers: host ms a query inside the launch call
(`device.dispatch` in `utils/retry.device_call`): the enqueue, which waits
where the device's queue is full.  Summed over serve workers.
None where the program has no such timer."""
from tpubench.readers import timer_per_query


def read(run):
    if "device.dispatch" not in run.timings:
        return None
    s = timer_per_query(run, "device.dispatch")
    return None if s is None else s * 1e3
