"""`cold`: each query in a new context that registers every table from
its file: file to answer, nothing resident."""

from tpubench.entries import Entry


class ColdEntry(Entry):
    def query(self, q, req):
        from datafusion_tpu.exec.materialize import collect

        with self.spans.span("call.register", req.rid):
            ctx = self.context()
            for table, path in self.tables.items():
                ctx.register_parquet(table, path)
        with self.spans.span("call.sql", req.rid):
            rel = ctx.sql(q.sql)
        with self.spans.span("call.collect", req.rid):
            return collect(rel)


ENTRY = ColdEntry
