"""`serve`: `Server.submit(text).result()` on a server over the `sql`
entry's context; `ctx.serve()` with no argument, the engine's serving
defaults."""

import sys

from tpubench.entries import RESULT_TIMEOUT_S
from tpubench.entries.sql import SqlEntry


class ServeEntry(SqlEntry):
    def __init__(self, device, engine_cfg, tables, spans):
        super().__init__(device, engine_cfg, tables, spans)
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


ENTRY = ServeEntry
