"""Operator drivers: ms a query the launching threads spent off the CPU
inside the launch call (`device.dispatch` less `device.dispatch.cpu`):
waiting for the interpreter lock, for room in the device's queue, or
blocked in the runtime.  Fewer launches cut `launch_cpu_ms_per_query`
and nothing of this.  None where the program's timers keep no CPU
seconds."""
from tpubench.readers import timer_per_query


def read(run):
    if ("device.dispatch" not in run.timings
            or "device.dispatch.cpu" not in run.timings):
        return None
    wall = timer_per_query(run, "device.dispatch")
    cpu = timer_per_query(run, "device.dispatch.cpu")
    return None if wall is None else (wall - cpu) * 1e3
