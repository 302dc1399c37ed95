"""Front end: the program's parse + plan + optimize + verify timers per query."""
from tpubench.readers import timer_per_query


def read(run):
    s = timer_per_query(run, "parse", "plan", "optimize", "verify")
    return None if s is None else s * 1e3
