"""Operator drivers: executable dispatches per query (`device.launches`)."""
from tpubench.readers import counter_per_query


def read(run):
    return counter_per_query(run, "device.launches")
