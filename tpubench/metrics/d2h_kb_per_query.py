"""D2H: kilobytes pulled back per query (`d2h.bytes`)."""
from tpubench.readers import counter_per_query


def read(run):
    b = counter_per_query(run, "d2h.bytes")
    return None if b is None else b / 1e3
