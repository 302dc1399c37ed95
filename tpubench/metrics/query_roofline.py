"""Kernels: the least time the chip could take for the traced queries
(bytes of the referenced resident columns, `peaks.required_bytes`, at the
HBM peak: the bound is memory bandwidth) over the device time they took."""
from tpubench.peaks import roofline_share


def read(run):
    if not run.trace or not run.bytes_needed:
        return None
    return 100 * roofline_share(run.bytes_needed, run.trace["busy_s"],
                                run.device["kind"])
