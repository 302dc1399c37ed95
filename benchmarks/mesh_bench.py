"""Config 5: partitioned GROUP BY aggregate over an 8-device mesh.

Run with JAX_PLATFORMS=cpu and
XLA_FLAGS=--xla_force_host_platform_device_count=8 (suite.py sets
both); compares the shard_map partial-aggregate + psum-combine path
against the same query on one device, on identical in-memory
partitions.  Prints one JSON line.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np


def main():
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    import jax

    from benchmarks import data as bdata
    from datafusion_tpu.exec.context import ExecutionContext
    from datafusion_tpu.exec.materialize import collect
    from datafusion_tpu.parallel.partition import (
        PartitionedContext,
        PartitionedDataSource,
    )

    n_dev = len(jax.devices())
    rows = int(os.environ.get("BENCH_MESH_ROWS", 4_000_000))
    groups = int(os.environ.get("BENCH_MESH_GROUPS", 1000))
    per_part = rows // n_dev
    parts = []
    schema = None
    for i in range(n_dev):
        # distinct seed per partition: 8 copies of the same rows would
        # benchmark a degenerate input
        schema, src = bdata.groupby_batches(per_part, groups, 1 << 18, seed=100 + i)
        parts.append(src)
    pds = PartitionedDataSource(parts)
    sql = "SELECT k, SUM(v1), AVG(v2), MIN(v3), MAX(v3), COUNT(1) FROM t GROUP BY k"

    def timed(fn, runs=5, warmup=2):
        out = None
        for _ in range(warmup):
            out = fn()
        times = []
        for _ in range(runs):
            t0 = time.perf_counter()
            out = fn()
            times.append(time.perf_counter() - t0)
        return float(np.median(times)), out

    # single device (partitions scanned as a serial union)
    ctx1 = ExecutionContext(device="cpu")
    ctx1.register_datasource("t", pds)
    rel1 = ctx1.sql(sql)
    p50_1, out1 = timed(lambda: collect(rel1))

    # 8-device mesh: shard_map partial aggregates + psum combine
    ctxm = PartitionedContext(n_devices=n_dev)
    ctxm.register_datasource("t", pds)
    relm = ctxm.sql(sql)
    p50_m, outm = timed(lambda: collect(relm))

    got = sorted(outm.to_rows())
    want = sorted(out1.to_rows())
    assert len(got) == len(want), f"{len(got)} vs {len(want)} groups"
    for g, w in zip(got, want):
        np.testing.assert_allclose(
            np.asarray(g, float), np.asarray(w, float), rtol=1e-9
        )

    # non-aggregate mesh path: filter+project over partitions via the
    # stacked shard_map pipeline (round 2 ran these as a serial union)
    psql = "SELECT k, v1 * 2.0, v3 FROM t WHERE v1 > 500.0"
    relp1 = ctx1.sql(psql)
    pipe_p50_1, pout1 = timed(lambda: collect(relp1), runs=3, warmup=1)
    relpm = ctxm.sql(psql)
    pipe_p50_m, poutm = timed(lambda: collect(relpm), runs=3, warmup=1)
    assert poutm.num_rows == pout1.num_rows, (
        f"{poutm.num_rows} vs {pout1.num_rows} rows"
    )
    # value parity, not just cardinality (same protection the aggregate
    # check above has)
    got_rows = sorted(poutm.to_rows())
    want_rows = sorted(pout1.to_rows())
    for g, w in zip(got_rows, want_rows):
        np.testing.assert_allclose(
            np.asarray(g, float), np.asarray(w, float), rtol=1e-9
        )

    print(json.dumps({
        "name": "partitioned_mesh_aggregate",
        "rows": rows,
        "groups": groups,
        "devices": n_dev,
        "unit": "rows/s",
        "value": round(rows / p50_m, 1),
        "p50_ms": round(p50_m * 1e3, 2),
        "single_device_p50_ms": round(p50_1 * 1e3, 2),
        "vs_baseline": round(p50_1 / p50_m, 3),
        "pipeline": {
            "rows": rows,
            "out_rows": int(poutm.num_rows),
            "value": round(rows / pipe_p50_m, 1),
            "p50_ms": round(pipe_p50_m * 1e3, 2),
            "single_device_p50_ms": round(pipe_p50_1 * 1e3, 2),
            "vs_baseline": round(pipe_p50_1 / pipe_p50_m, 3),
        },
        "note": (
            f"{n_dev} VIRTUAL devices share one physical core: this "
            "validates the shard_map+psum path and bounds its overhead; "
            "it cannot show scaling (no multi-chip hardware here)"
        ),
    }))


if __name__ == "__main__":
    main()
