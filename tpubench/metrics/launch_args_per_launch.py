"""Operator drivers: pytree leaves a launch is handed
(`device.dispatch.leaves` / `device.launches`; `device_call` counts them
while a profile runs): what the call flattens and checks every time.
None where the program takes no such census, or launched nothing."""


def read(run):
    launches = run.counts.get("device.launches", 0)
    if "device.dispatch.leaves" not in run.counts or not launches:
        return None
    return run.counts["device.dispatch.leaves"] / launches
