"""Serving: ms a query inside `Server._finish` (`serve.finish`: materialise
the ticket's relation and fulfil it).  A solo query's whole scan runs here;
summed over the workers, so it can exceed the window.
None where the program has no such timer."""
from tpubench.readers import timer_per_query


def read(run):
    if "serve.finish" not in run.timings:
        return None
    s = timer_per_query(run, "serve.finish")
    return None if s is None else s * 1e3
