"""Operator drivers: ms a query of the launching threads' own CPU time
inside the launch call (`device.dispatch.cpu`, beside `device.dispatch` in
`utils/retry.device_call`): flattening the arguments, putting the host
values among them, the enqueue.  Summed over serve workers.  With
`launch_wait_ms_per_query` it is `launch_dispatch_ms_per_query`.
None where the program's timers keep no CPU seconds."""
from tpubench.readers import timer_per_query


def read(run):
    if "device.dispatch.cpu" not in run.timings:
        return None
    s = timer_per_query(run, "device.dispatch.cpu")
    return None if s is None else s * 1e3
