"""The entry points a traffic mix can send its requests to.

Each wraps the request, each query and each call it makes into the engine
in a span: a `jax.profiler.TraceAnnotation` (so that the profiler's trace
carries the benchmark's host spans on the device's clock and idle gaps
can be labelled by them) and a row in `Spans` on the host clock (for the
per-layer metrics read from the benchmark's own spans).

- `sql`:   `collect(ctx.sql(text))` on one context over the resident table
- `cold`:  a new context, `register_parquet`, the query, `collect`
- `serve`: `Server.submit(text).result()` on a server over that context

Every entry starts from the Parquet file of the run's seed.  A resident
table is what the engine's own reader makes of that file
(`register_parquet`, then its batches kept in memory, as `chip_smoke.py`
builds its warm table): its batch sizes, dictionaries and schema are the
program's, so a change to the reader shows in every cell.
"""

from __future__ import annotations

import sys
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
    load generator calls, from as many threads as the loop has clients."""

    def __init__(self, device: str, engine_cfg: dict, table: str,
                 spans: Spans, path: str):
        self.device = device
        self.result_cache = engine_cfg.get("result_cache", False)
        self.table = table
        self.spans = spans
        self.path = path

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


class SqlEntry(Entry):
    def __init__(self, device, engine_cfg, table, spans, path):
        from datafusion_tpu.exec.datasource import MemoryDataSource

        super().__init__(device, engine_cfg, table, spans, path)
        self.ctx = self.context()
        self.ctx.register_parquet(table, path)
        scan = self.ctx.datasources[table]
        self.ctx.register_datasource(
            table, MemoryDataSource(scan.schema, list(scan.batches())))

    def query(self, q, req):
        from datafusion_tpu.exec.materialize import collect

        with self.spans.span("call.sql", req.rid):
            rel = self.ctx.sql(q.sql)
        with self.spans.span("call.collect", req.rid):
            return collect(rel)


class ColdEntry(Entry):
    def query(self, q, req):
        from datafusion_tpu.exec.materialize import collect

        with self.spans.span("call.register", req.rid):
            ctx = self.context()
            ctx.register_parquet(self.table, self.path)
        with self.spans.span("call.sql", req.rid):
            rel = ctx.sql(q.sql)
        with self.spans.span("call.collect", req.rid):
            return collect(rel)


class ServeEntry(SqlEntry):
    """`ctx.serve()` with no argument: the engine's serving defaults."""

    def __init__(self, device, engine_cfg, table, spans, path):
        super().__init__(device, engine_cfg, table, spans, path)
        self.server = self.ctx.serve()

    def query(self, q, req):
        with self.spans.span("call.submit", req.rid):
            ticket = self.server.submit(q.sql, client_id=req.client)
        with self.spans.span("call.result", req.rid):
            return ticket.result(timeout=RESULT_TIMEOUT_S)

    def send_together(self, reqs: list) -> list:
        """All submitted from this thread before it waits for any, with
        the interpreter's thread switch held off meanwhile, so that the
        server's loop finds them in one serving window and fuses those
        that share a program (the server's window is a fraction of a
        millisecond once arrivals are sparse, and a submit parses and
        plans for about one)."""
        switch = sys.getswitchinterval()
        sys.setswitchinterval(1.0)
        try:
            tickets = [self.server.submit(q.sql, client_id=r.client)
                       for r in reqs for q in r.queries]
        finally:
            sys.setswitchinterval(switch)
        return [[t.result(timeout=RESULT_TIMEOUT_S)] for t in tickets]

    def close(self) -> None:
        self.server.stop()


ENTRIES = {"sql": SqlEntry, "cold": ColdEntry, "serve": ServeEntry}
