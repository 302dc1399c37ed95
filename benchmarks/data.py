"""Seeded inputs for the smoke scripts and the reference benches.

A scaled-up uk_cities CSV (cached on disk under test/data/bench/) and
two in-memory tables: a group-by table and a sort table.  The timed
benchmark's data sets are `tpubench/datasets/`.
"""

from __future__ import annotations

import os

import numpy as np

BENCH_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "test", "data", "bench",
)


def cities_csv(rows: int) -> str:
    """A scaled-up uk_cities.csv (the `examples/csv_sql.rs` workload
    shape): city name, lat, lng; header row."""
    import pyarrow as pa
    import pyarrow.csv as pacsv

    os.makedirs(BENCH_DIR, exist_ok=True)
    path = os.path.join(BENCH_DIR, f"cities_{rows}.csv")
    if os.path.exists(path):
        return path
    rng = np.random.default_rng(7)
    pool = np.array([f"city_{i:04d}" for i in range(2000)])
    tbl = pa.table(
        {
            "city": pa.array(pool[rng.integers(0, len(pool), rows)]),
            "lat": pa.array(np.round(rng.uniform(49.9, 59.0, rows), 6)),
            "lng": pa.array(np.round(rng.uniform(-7.6, 1.8, rows), 6)),
        }
    )
    tmp = path + ".tmp"
    pacsv.write_csv(tbl, tmp)
    os.replace(tmp, path)
    return path


def groupby_batches(rows: int, groups: int, batch_rows: int, seed: int = 3):
    """In-memory group-by table: int64 key of `groups` cardinality +
    three value columns.  Returns (schema, MemoryDataSource)."""
    from datafusion_tpu.datatypes import DataType, Field, Schema
    from datafusion_tpu.exec.batch import make_host_batch
    from datafusion_tpu.exec.datasource import MemoryDataSource

    schema = Schema(
        [
            Field("k", DataType.INT64, False),
            Field("v1", DataType.FLOAT64, False),
            Field("v2", DataType.FLOAT64, False),
            Field("v3", DataType.INT64, False),
        ]
    )
    rng = np.random.default_rng(seed)
    batches = []
    for start in range(0, rows, batch_rows):
        n = min(batch_rows, rows - start)
        cols = [
            rng.integers(0, groups, n).astype(np.int64),
            rng.uniform(0.0, 1000.0, n),
            rng.uniform(-1.0, 1.0, n),
            rng.integers(-(10**9), 10**9, n).astype(np.int64),
        ]
        batches.append(make_host_batch(schema, cols, [None] * 4, [None] * 4))
    return schema, MemoryDataSource(schema, batches)


def sort_batches(rows: int, batch_rows: int):
    """In-memory sort table: two sort keys + payload."""
    from datafusion_tpu.datatypes import DataType, Field, Schema
    from datafusion_tpu.exec.batch import make_host_batch
    from datafusion_tpu.exec.datasource import MemoryDataSource

    schema = Schema(
        [
            Field("a", DataType.FLOAT64, False),
            Field("b", DataType.INT64, False),
            Field("x", DataType.FLOAT64, False),
            Field("s", DataType.FLOAT32, False),  # single-key fast path
        ]
    )
    rng = np.random.default_rng(11)
    batches = []
    for start in range(0, rows, batch_rows):
        n = min(batch_rows, rows - start)
        cols = [
            rng.uniform(0.0, 1e6, n),
            rng.integers(0, 1 << 40, n).astype(np.int64),
            rng.uniform(0.0, 1.0, n),
            rng.uniform(0.0, 1e6, n).astype(np.float32),
        ]
        batches.append(make_host_batch(schema, cols, [None] * 4, [None] * 4))
    return schema, MemoryDataSource(schema, batches)
