"""Kernels: the least time the chip could take for the window's device key
step (`key_step_bytes` at the HBM peak) over the device time of the
operations whose program name contains `_keyed_` among the trace's
`device_ops` (the aggregate's count, compaction, store, reduce and output
programs).  None where the trace names no such program, or the program
under test does not count what the step read.

The bytes are those of the work, not of the implementation: one read of
the mask of every row the step was offered (1 B a row of the join's output,
`aggregate.device_key.offered`) to find the rows the predicate keeps, and
of each kept row its key columns and the columns its aggregates are made
from, once (`aggregate.device_key.input_bytes`: the engine counts them from
the query's own columns, whatever query it is).  The predicate's own
columns belong to the query's scan (`query_roofline`) and are not counted
again."""
from tpubench.peaks import roofline_share

PROGRAM = "_keyed_"
MASK_BYTES = 1


def key_step_bytes(offered_rows: int, input_bytes: int) -> int:
    """Least bytes moved to turn `offered_rows` rows, whose kept rows'
    key and aggregated columns are `input_bytes`, into groups."""
    return offered_rows * MASK_BYTES + input_bytes


def read(run):
    if not run.trace:
        return None
    seconds = sum(s for name, s in run.trace["device_ops"] if PROGRAM in name)
    offered = run.counts.get("aggregate.device_key.offered", 0)
    kept = run.counts.get("aggregate.device_key.input_bytes", 0)
    if not seconds or not offered:
        return None
    return 100 * roofline_share(key_step_bytes(offered, kept), seconds,
                                run.device["kind"])
