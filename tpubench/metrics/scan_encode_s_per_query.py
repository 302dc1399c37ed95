"""Scan: Parquet decode (`scan.parse`) + wire encode (`h2d.encode`) host
seconds per query."""
from tpubench.readers import timer_per_query


def read(run):
    return timer_per_query(run, "scan.parse", "h2d.encode")
