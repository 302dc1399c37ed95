"""The entry points a traffic mix can send its requests to: one file
each beside this one, found by the name a mix gives as its `entry`
(`spec.entry`), so a later PR adds one by adding a file.

Each wraps the request, each query and each call it makes into the engine
in a span: a `jax.profiler.TraceAnnotation` (so that the profiler's trace
carries the benchmark's host spans on the device's clock and idle gaps
can be labelled by them) and a row in `Spans` on the host clock (for the
per-layer metrics read from the benchmark's own spans).

- `sql`:   `collect(ctx.sql(text))` on one context over the resident tables
- `cold`:  a new context, `register_parquet` of every table, the query, `collect`
- `serve`: `Server.submit(text).result()` on a server over that context

Every entry starts from the Parquet files of the run's seed, one a table.
A resident table is what the engine's own reader makes of its file
(`register_parquet`, then its batches kept in memory, as `chip_smoke.py`
builds its warm table): its batch sizes, dictionaries and schema are the
program's, so a change to the reader shows in every cell.
"""

from __future__ import annotations

import time
from contextlib import contextmanager

RESULT_TIMEOUT_S = 300.0


class Spans:
    """(name, start, end, request id) rows on `time.perf_counter`."""

    def __init__(self):
        self.rows: list = []

    @contextmanager
    def span(self, name: str, rid: int = -1):
        import jax

        with jax.profiler.TraceAnnotation("tpubench." + name):
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.rows.append((name, t0, time.perf_counter(), rid))

    def durations(self, name: str) -> list:
        return [t1 - t0 for n, t0, t1, _ in self.rows if n == name]


class Entry:
    """Holds the engine objects of one cell; `send(request)` is what the
    load generator calls, from as many threads as the loop has clients.
    `tables` is {table name: its Parquet file}, in registration order."""

    def __init__(self, device: str, engine_cfg: dict, tables: dict,
                 spans: Spans):
        self.device = device
        self.result_cache = engine_cfg.get("result_cache", False)
        self.tables = tables
        self.spans = spans

    def context(self):
        from datafusion_tpu.exec.context import ExecutionContext

        kwargs = {} if self.result_cache else {"result_cache": False}
        return ExecutionContext(device=self.device, **kwargs)

    def send(self, req) -> list:
        with self.spans.span("request", req.rid):
            out = []
            for q in req.queries:
                with self.spans.span("query." + q.template, req.rid):
                    out.append(self.query(q, req))
            return out

    def send_together(self, reqs: list) -> list:
        """Warm-up only: requests that arrive at once.  Here, one after
        the other; an entry point that can run several queries as one
        program says how they reach it together."""
        return [self.send(r) for r in reqs]

    def query(self, q, req):
        raise NotImplementedError

    def close(self) -> None:
        pass
