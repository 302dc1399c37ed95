"""H2D: host ms a query inside the wire encoder (`h2d.encode` in
`put_compressed`), on the stager thread; with two serve workers staging at
once the sum can exceed the window.
None where the program has no such timer."""
from tpubench.readers import timer_per_query


def read(run):
    if "h2d.encode" not in run.timings:
        return None
    s = timer_per_query(run, "h2d.encode")
    return None if s is None else s * 1e3
