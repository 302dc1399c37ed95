"""Operator drivers: median wall of H2O question q1 (the benchmark's own
span around `ctx.sql` + `collect`), ms."""
from tpubench.readers import span_median_ms


def read(run):
    return span_median_ms(run, "query.q1")
