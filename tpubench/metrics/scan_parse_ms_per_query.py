"""Scan: host ms a query inside the reader's batch iterator (`scan.parse`:
Parquet decode and dictionary coding), on the parse thread of the pipeline.
`scan_encode_s_per_query` adds `h2d.encode`, which runs on another thread.
None where the program has no such timer."""
from tpubench.readers import timer_per_query


def read(run):
    if "scan.parse" not in run.timings:
        return None
    s = timer_per_query(run, "scan.parse")
    return None if s is None else s * 1e3
