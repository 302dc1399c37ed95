"""Mesh: the fullest shard's rows over the mean shard's, summed over the
window's queries: shards x `mesh.shard_rows.max` / `mesh.shard_rows.total`,
the shards counted by the engine (`mesh.shards`, the mesh's size a query:
not the host's devices, of which a mesh may take some).  1.0 where the
table is dealt evenly; the fullest shard is the one the others wait for at
the combine.  None where no mesh query ran."""
from tpubench.readers import counter_per_query


def read(run):
    total = run.counts.get("mesh.shard_rows.total", 0)
    shards = counter_per_query(run, "mesh.shards")
    if not total or not shards:
        return None
    return shards * run.counts.get("mesh.shard_rows.max", 0) / total
