"""Kernels: the least time the chip could take for the window's marks of a
semi join's membership table (`join.semi.mark_bytes` at the HBM peak) over
the device time of the operations whose program name contains `semi_mark`
among the trace's `device_ops`.  None where the trace names no such
program, or the engine does not count what the marks read and write.

An upper bound: `device_ops` is the window's ten longest operations
(`trace_reduce.TOP_N`), and the mark is a program of several fusions (the
predicate, the window's choice, the scatter), of which those ten hold
some, so the seconds divided by are the listed fusions' and not the whole
program's.  The share is exact only once the trace's reduction keeps each
program's total (PERF.md section 7).

The bytes are the engine's own count of the work (`join.semi.mark_bytes`):
of each build row offered to a mark, its key and the columns the
subquery's predicate reads (TPC-H Q4: `l_orderkey`, `l_commitdate`,
`l_receiptdate`, 24 B), and the table's bytes once a query."""
from tpubench.peaks import roofline_share

PROGRAM = "semi_mark"


def read(run):
    if not run.trace:
        return None
    seconds = sum(s for name, s in run.trace["device_ops"] if PROGRAM in name)
    nbytes = run.counts.get("join.semi.mark_bytes", 0)
    if not seconds or not nbytes:
        return None
    return 100 * roofline_share(nbytes, seconds, run.device["kind"])
