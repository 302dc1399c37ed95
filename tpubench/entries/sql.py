"""`sql`: `collect(ctx.sql(text))` on one context over the resident tables."""

from tpubench.entries import Entry


class SqlEntry(Entry):
    def __init__(self, device, engine_cfg, tables, spans):
        from datafusion_tpu.exec.datasource import MemoryDataSource

        super().__init__(device, engine_cfg, tables, spans)
        self.ctx = self.context()
        for table, path in tables.items():
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


ENTRY = SqlEntry
