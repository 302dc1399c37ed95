"""Operator drivers: host values a launch is handed
(`device.dispatch.host_leaves` / `device.launches`): numpy arrays, numpy
and Python scalars, each of which the call itself puts on the device,
outside the ledger seam that `h2d_transfers_per_query` counts.
None where the program takes no such census, or launched nothing."""


def read(run):
    launches = run.counts.get("device.launches", 0)
    if "device.dispatch.host_leaves" not in run.counts or not launches:
        return None
    return run.counts["device.dispatch.host_leaves"] / launches
