"""H2D: host ms a query inside `jax.device_put` (`h2d.dispatch`, the transfer
at the ledger seam in `obs/device.py`): the enqueue, not the copy.  Summed
over the threads that ship, so it can exceed the window.
None where the program has no such timer."""
from tpubench.readers import timer_per_query


def read(run):
    if "h2d.dispatch" not in run.timings:
        return None
    s = timer_per_query(run, "h2d.dispatch")
    return None if s is None else s * 1e3
