"""What the metric readers under `metrics/` share.  A reader is
`read(run) -> number | None`; `run` is a `harness.Run`.  None means the
run holds nothing for this metric, and the harness leaves it out."""

from __future__ import annotations

import numpy as np


def per_query(run, total: float) -> "float | None":
    return total / run.queries if run.queries else None


def counter_per_query(run, *names: str) -> "float | None":
    return per_query(run, sum(run.counts.get(n, 0) for n in names))


def timer_per_query(run, *names: str) -> "float | None":
    """Seconds of the program's stage timers per query."""
    return per_query(run, sum(run.timings.get(n, 0.0) for n in names))


def span_median_ms(run, name: str) -> "float | None":
    """Median of the benchmark's own spans of that name, in ms."""
    d = run.spans.durations(name)
    return float(np.median(d)) * 1e3 if d else None


def percentile_ms(run, q: float) -> "float | None":
    lat = run.latencies_ms()
    return float(np.percentile(lat, q)) if lat else None
