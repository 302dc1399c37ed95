"""H2D: transfers per query (`device.h2d.transfers`)."""
from tpubench.readers import counter_per_query


def read(run):
    return counter_per_query(run, "device.h2d.transfers")
