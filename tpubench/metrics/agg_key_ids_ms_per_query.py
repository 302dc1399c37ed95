"""Operator drivers: the program's `aggregate.device_key_ids` timer per
query, ms: what the host spends on the device key step of an aggregate
whose numeric group keys were born on the device (a join's output): the
calls that enqueue a batch group's count launch and the wait for its
answer, 4 B a group (not the device's time).  None on an engine or in a
cell without that step."""
from tpubench.readers import timer_per_query


def read(run):
    s = (timer_per_query(run, "aggregate.device_key_ids")
         if "aggregate.device_key_ids" in run.timings else None)
    return None if s is None else s * 1e3
