"""Operator drivers: device launches tagged `join.probe` per query."""
from tpubench.readers import counter_per_query


def read(run):
    if "device.launches.join.probe" not in run.counts:
        return None
    return counter_per_query(run, "device.launches.join.probe")
