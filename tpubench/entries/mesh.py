"""`mesh`: `collect(ctx.sql(text))` on one `PartitionedContext` over a mesh
of `CHIPS` devices; every table is registered through
`register_resident_parquet` and through nothing else: the engine's reader
deals the file's row groups to the mesh's devices, each shard stays in
memory and, from the first query on, on its device."""

from tpubench.entries import Entry

CHIPS = 4


class MeshEntry(Entry):
    def __init__(self, device, engine_cfg, tables, spans):
        import jax

        from datafusion_tpu.parallel.mesh import make_mesh
        from datafusion_tpu.parallel.partition import PartitionedContext

        super().__init__(device, engine_cfg, tables, spans)
        # the rehearsal (`device` "cpu") takes as many of the CPU's
        # virtual devices as there are, four under the tests' eight
        n = CHIPS if device != "cpu" else min(CHIPS, len(jax.devices()))
        self.ctx = PartitionedContext(
            mesh=make_mesh(n),
            result_cache=None if self.result_cache else False)
        for table, path in tables.items():
            self.ctx.register_resident_parquet(table, path)

    def query(self, q, req):
        from datafusion_tpu.exec.materialize import collect

        with self.spans.span("call.sql", req.rid):
            rel = self.ctx.sql(q.sql)
        with self.spans.span("call.collect", req.rid):
            return collect(rel)


ENTRY = MeshEntry
