"""Kernels: `query_roofline` for a table sharded over several chips: the
least time the chips could take together for the traced queries (the bytes
of the referenced resident columns, at chips x the HBM peak: each chip reads
its own shard) over the mean busy time a chip (`trace["busy_s"]` is that
mean).  `query_roofline` divides the whole table's bytes by ONE chip's peak
and reads a cell of n chips n times too high."""
from tpubench.peaks import roofline_share


def read(run):
    if not run.trace or not run.bytes_needed:
        return None
    return 100 * roofline_share(run.bytes_needed / run.trace["chips"],
                                run.trace["busy_s"], run.device["kind"])
