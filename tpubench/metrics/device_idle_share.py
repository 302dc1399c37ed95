"""Device: 1 - busy / traced window, from the profiler's trace."""


def read(run):
    if not run.trace:
        return None
    return 100 * (1 - run.trace["busy_s"] / run.trace["window_s"])
