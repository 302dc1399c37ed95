"""Median request wall in the window, client side, ms."""
from tpubench.readers import percentile_ms


def read(run):
    return percentile_ms(run, 50)
