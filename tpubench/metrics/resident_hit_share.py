"""H2D: of the batches whose inputs a query asked for in the window, the
share whose column copies were already on the table's batch, in per cent:
100 x `h2d.resident_hits` / (`h2d.resident_hits` + `h2d.resident_misses`),
one count a batch in `exec/batch.device_inputs`.  100 where a resident
table ships nothing again, 0 where every query reads its file anew.
None where the program has no such counters."""


def read(run):
    hits = run.counts.get("h2d.resident_hits", 0)
    total = hits + run.counts.get("h2d.resident_misses", 0)
    return 100 * hits / total if total else None
