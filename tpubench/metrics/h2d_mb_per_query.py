"""H2D: megabytes put on the wire per query (`h2d.bytes`)."""
from tpubench.readers import counter_per_query


def read(run):
    b = counter_per_query(run, "h2d.bytes")
    return None if b is None else b / 1e6
